"""Layer: programs. How much of what a decode tick has to move is the
Mamba-2 blocks' recurrent state, in percent: twice ``state_rows`` (the lanes
whose state the tick updates, ``engine.decode.launch``'s argument, a mean
over the window) times one slot's float32 state (``pool.alloc``'s
``state_bytes`` over its rows), the state read and written once, over all the
bytes ``harness/opcount_mamba_moe.mamba_moe_decode_tick_need`` counts for the
tick. It says how much of the tick the state that is a request's costs beside
the weights, the experts and the K/V rows. A program whose spans carry no
``state_rows`` or ``state_bytes`` (a parent from before such a state), or a
configuration of another kind, reports nothing. Source: program_span."""

import statistics

from perfbench.metrics import _mamba_moe, _spans


def read(outcome):
    pool = _mamba_moe.pool_args(outcome, "state_bytes")
    rows = [e.args["state_rows"]
            for e in _spans.in_window(outcome, "engine.decode.launch")
            if e.args and "state_rows" in e.args]
    need = _mamba_moe.tick_need(outcome)
    if pool is None or not rows or need is None:
        return None
    slots = outcome["counters"]["slots"]
    slot_state = pool["state_bytes"] / (slots + 1)  # and the trash row
    return 100.0 * 2.0 * statistics.fmean(rows) * slot_state / need["bytes"]
