"""Layer: routing and scheduling. Device-idle time per tick in the traced
window: what the host loop (collect a tick, admit, build and launch the
next) keeps the device waiting. Source: device_trace."""


def read(outcome):
    tr, ticks = outcome["trace"], outcome["counters"]["traced_ticks"]
    if not ticks:
        return None
    return 1e3 * (tr["window_s"] - tr["busy_s"]) / len(ticks)
