"""Layer: trainer loop. Median host time of one ``train.step_dispatch``
span in the window: placing the batch is outside it, the step's call is
inside. Source: program_span."""

from perfbench.metrics import _spans


def read(outcome):
    return _spans.median_ms(_spans.in_window(outcome, "train.step_dispatch"))
