"""Layer: routing and scheduling. Median ``router.gate`` span in the
window: the admission gate's decision for one submitted request, on the
tick's path under a backlog. Source: program_span."""

from perfbench.metrics import _spans


def read(outcome):
    return _spans.median_ms(_spans.in_window(outcome, "router.gate"))
