"""Layer: collectives. Per step and per device, the part of the time in
collective operations during which no other operation ran on that device,
over the steps whose single operations the trace holds (the profiler's
buffer may fill before the traced window ends). Source: device_trace."""


def read(outcome):
    tr = outcome["trace"]
    if tr["devices"] < 2 or not tr["modules"]:
        return None
    step = max(tr["modules"], key=lambda k: sum(tr["modules"][k]))
    steps = tr["modules_covered"].get(step, 0) / tr["devices"]
    if steps < 1:
        return None
    return 1e3 * tr["collective_exposed_s"] / steps
