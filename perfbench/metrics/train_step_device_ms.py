"""Layer: step programs. Median device time of one execution of the step
program in the traced window: the program with the most device time
there. Source: device_trace."""

import statistics


def read(outcome):
    modules = outcome["trace"]["modules"]
    if not modules:
        return None
    step = max(modules.values(), key=sum)
    return 1e3 * statistics.median(step)
