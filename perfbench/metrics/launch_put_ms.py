"""Layer: programs. Median, over the window's ticks, of the first launch's
``engine.chunk.put`` or ``engine.decode.put`` span: the one
``jax.device_put`` of the host-built operands (three to seven small arrays;
the span's ``arrays`` and ``bytes`` say latency or bandwidth). It is what
operands kept on the device would hide. ``perfbench/metrics/_launch_path.py``
says what a tick and its first launch are. Source: program_span."""

from perfbench.metrics import _launch_path


def read(outcome):
    return _launch_path.first_launch_part_ms(outcome, "put")
