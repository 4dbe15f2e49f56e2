"""Layer: trainer loop. Share of the window the trainer's own loop spent
in its ``next`` on the loader, in percent: its ``train.data_wait`` spans,
clipped to the window. It is the raw span share. Whatever the caller's
loader does inside ``next`` is in it: a loader that holds each ``next``
back until the device has caught up (this harness's does) makes this read
the device's pace less the host's own work a step: the host's slack, not
a loader's cost (PERF.md section 3). Source: program_span."""

from perfbench.metrics import _spans


def read(outcome):
    tr = _spans.stream()
    if tr is None:
        return None
    t0, t1 = _spans.window(outcome)
    waits = tr.events("train.data_wait", t0, t1)
    if not waits:
        return None
    total = sum(min(e.t1, t1) - max(e.t0, t0) for e in waits)
    return 100.0 * total / (t1 - t0)
