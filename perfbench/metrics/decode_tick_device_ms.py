"""Layer: programs. Median device time of one decode tick (the program
learned under the label ``decode_tick`` during warm-up). Source:
device_trace."""

import statistics

from perfbench.metrics import _programs


def read(outcome):
    ds = _programs.durations(outcome, "decode_tick")
    return 1e3 * statistics.median(ds) if ds else None
