"""Layer: routing and scheduling. Median, over the window's ticks, of the
time from the end of ``engine.collect.wait`` (the tick's tokens are on the
host: the device has nothing queued) to the start of the next
``engine.chunk.launch`` or ``engine.decode.launch``: the host path the
device waits for. The inside twin of ``tick_host_ms``, which adds the
launch's own latency. Source: program_span."""

import bisect
import statistics

from perfbench.metrics import _spans


def read(outcome):
    waits = _spans.in_window(outcome, "engine.collect.wait")
    starts = sorted(e.t0 for name in ("engine.chunk.launch",
                                      "engine.decode.launch")
                    for e in _spans.in_window(outcome, name))
    gaps = []
    for w in waits:
        i = bisect.bisect_left(starts, w.t1)
        if i < len(starts):
            gaps.append(starts[i] - w.t1)
    return 1e3 * statistics.median(gaps) if gaps else None
