"""Layer: programs. How much of what a decode tick has to move is the live
context's key and value rows, in percent: the live context's sum (the
scheduler's own counter over the traced ticks) plus a row a live lane, times
``pool.alloc``'s ``kv_row_bytes`` (a token's key and value rows in one layer
that owns such pools) and ``pool_layers``, over all the bytes
``harness/opcount_gdn_moe.gdn_moe_decode_tick_need`` counts for the tick. It
grows with the contexts where the state's share
(``gdn_state_bytes_per_tick_share``) does not: beside it, it says which of
the two caches a longer document costs. A program whose ``pool.alloc``
carries no ``kv_row_bytes`` (a parent from before the argument), or a
configuration of another kind, reports nothing. Source: program_span."""

import statistics

from perfbench.metrics import _gdn_moe


def read(outcome):
    pool = _gdn_moe.pool_args(outcome, "kv_row_bytes")
    need = _gdn_moe.tick_need(outcome)
    if pool is None or need is None:
        return None
    counters = outcome["counters"]
    ticks = [t for t in (counters.get("traced_ticks") or counters["ticks"])
             if t[1] > 0]
    rows = statistics.fmean(c + n for _, n, c in ticks)
    return (100.0 * rows * pool["kv_row_bytes"] * pool["pool_layers"]
            / need["bytes"])
