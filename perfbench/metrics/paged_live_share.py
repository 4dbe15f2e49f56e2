"""Layer: kernels. Share of the fused paged-attention kernel's grid steps
that hold a live position, in percent: the mean over the window's
``engine.decode.launch`` spans of ``live_tiles`` (the sum over a tick's
active lanes of the tiles up to the lane's position) over ``pool.alloc``'s
``table_tiles`` (slots x tiles a table row: the grid steps a layer's read
takes, ``ops/paged_flash.py``). Higher is better: a dead step does no
arithmetic and fetches nothing, but it is still a step. A program whose
spans carry neither argument (one from before the kernel staged tiles)
reports nothing. Source: program_span."""

import statistics

from perfbench.metrics import _spans


def read(outcome):
    pools = [e for e in _spans.in_setup(outcome, "pool.alloc")
             if e.args and e.args.get("table_tiles")]
    live = [e.args["live_tiles"]
            for e in _spans.in_window(outcome, "engine.decode.launch")
            if e.args and "live_tiles" in e.args]
    if not pools or not live:
        return None
    return 100.0 * statistics.fmean(live) / pools[-1].args["table_tiles"]
