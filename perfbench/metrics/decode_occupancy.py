"""Layer: routing and scheduling. Mean share of the slots that delivered
a token in a tick of the window, in percent. Source: program_counter
(the tokens ``router.step`` returns)."""


def read(outcome):
    c = outcome["counters"]
    if not c["ticks"]:
        return None
    return 100.0 * sum(n for _, n, _ in c["ticks"]) / (
        len(c["ticks"]) * c["slots"])
