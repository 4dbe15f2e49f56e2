"""Layer: programs. Median, over the window's ticks, of the first launch's
``engine.chunk.call`` or ``engine.decode.call`` span: the jitted function
from its call to its return, which flattens parameters, cache and operands
(the span's ``leaves``), books the donations and enqueues the program. It is
what a flat or ahead-of-time-compiled call would hide, and operands on the
device would not. ``perfbench/metrics/_launch_path.py`` says what a tick and
its first launch are. Source: program_span."""

from perfbench.metrics import _launch_path


def read(outcome):
    return _launch_path.first_launch_part_ms(outcome, "call")
