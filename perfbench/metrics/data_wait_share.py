"""Layer: trainer loop. Share of the window the loop spent waiting in the
loader's ``next`` (the benchmark's span around it), in percent of the
window. Source: program_span (host clock around the program's loader)."""


def read(outcome):
    t0, t1 = outcome["counters"]["window"]
    return 100.0 * outcome["spans"].total("loader.next", t0, t1) / (t1 - t0)
