"""Layer: programs. Seconds of set-up spent in ``program.load`` spans:
each the first compile of a program, or its load from the persistent
cache (``compilecache/aot.py::program_load``; a load inside a load is
recorded once). Source: program_span."""

from perfbench.metrics import _spans


def read(outcome):
    loads = _spans.in_setup(outcome, "program.load")
    return sum(e.t1 - e.t0 for e in loads) if loads else None
