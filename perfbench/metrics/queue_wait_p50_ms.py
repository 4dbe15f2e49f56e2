"""Layer: routing and scheduling. Median ``req.queue`` (submit to
admission, booked by the scheduler at admission) of the requests admitted
in the window: the inside twin of ``backlog_ttft_p50_ms``; what separates
them is prefill under chunking. Source: program_span."""

from perfbench.metrics import _spans


def read(outcome):
    tr = _spans.stream()
    if tr is None:
        return None
    t0, t1 = _spans.window(outcome)
    return _spans.median_ms([e for e in tr.events("req.queue", t0, t1)
                             if t0 < e.t1 <= t1])
