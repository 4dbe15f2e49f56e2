"""Layer: trainer loop / routing and scheduling. Seconds of set-up inside
``trainer.build`` or ``router.build`` (loader, state or pool, placement),
less the ``program.load`` spans that lie inside it on the same thread:
those are ``setup_program_load_s``. Source: program_span."""

from perfbench.metrics import _spans


def read(outcome):
    builds = (_spans.in_setup(outcome, "trainer.build")
              + _spans.in_setup(outcome, "router.build"))
    if not builds:
        return None
    loads = _spans.in_setup(outcome, "program.load")
    total = 0.0
    for b in builds:
        total += (b.t1 - b.t0) - sum(
            e.t1 - e.t0 for e in loads
            if e.tid == b.tid and e.t0 >= b.t0 and e.t1 <= b.t1)
    return total
