"""Layer: routing and scheduling. Mean share of the K/V pool's blocks that
requests hold, in percent: 1 - ``free_blocks`` / blocks over the window's
``sched.admit`` spans (``free_blocks``: the allocator's free list after
the tick's admissions; blocks: ``pool.alloc``'s, less the trash block).
Beside ``decode_occupancy`` it says whether the slots or the pool bound
the batch. A program whose spans carry no ``free_blocks`` reports
nothing. Source: program_span."""

import statistics

from perfbench.metrics import _spans


def read(outcome):
    pools = [e for e in _spans.in_setup(outcome, "pool.alloc")
             if e.args and "blocks" in e.args]
    free = [e.args["free_blocks"]
            for e in _spans.in_window(outcome, "sched.admit")
            if e.args and "free_blocks" in e.args]
    if not pools or not free:
        return None
    usable = pools[-1].args["blocks"] - 1
    return 100.0 * (1.0 - statistics.fmean(free) / usable)
