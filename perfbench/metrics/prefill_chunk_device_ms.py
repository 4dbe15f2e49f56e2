"""Layer: programs. Median device time of one chunk-prefill program, over
all buckets that ran in the traced window. Source: device_trace."""

import statistics

from perfbench.metrics import _programs


def read(outcome):
    ds = _programs.durations(outcome, "prefill_chunk")
    return 1e3 * statistics.median(ds) if ds else None
