"""Layer: programs. Median, over the window's ticks, of the first launch's
``engine.chunk.build`` or ``engine.decode.build`` span: the program's
operands assembled on the host in numpy (the chunk's seven arrays and a loop
over its jobs; the tick's table masked by its live lanes), before the launch
span opens. It lies inside ``tick_exposed_host_ms``' interval.
``perfbench/metrics/_launch_path.py`` says what a tick and its first launch
are. Source: program_span."""

from perfbench.metrics import _launch_path


def read(outcome):
    return _launch_path.first_launch_part_ms(outcome, "build")
