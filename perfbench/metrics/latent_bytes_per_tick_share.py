"""Layer: programs. How much of what a decode tick has to move is the live
context's latent rows, in percent: the live context's sum (the scheduler's
own counter over the traced ticks) plus a row a live lane, times
``pool.alloc``'s ``latent_row_bytes`` (a token's ONE row in a layer that owns
a latent pool) and ``pool_layers``, over all the bytes
``harness/opcount_mla_moe.mla_moe_decode_tick_need`` counts for the tick:
``kv_bytes_per_tick_share``'s twin for a stack whose every layer is latent
and whose pool is the only cache. It grows with the contexts where the
weights' and the experts' share does not: it says what a longer document
costs this stack. A program whose ``pool.alloc`` carries no
``latent_row_bytes`` (a parent from before the argument), or a configuration
of another kind, reports nothing. Source: program_span."""

import statistics

from perfbench.metrics import _mla_moe, _spans


def read(outcome):
    pools = [e.args for e in _spans.in_setup(outcome, "pool.alloc")
             if e.args and e.args.get("latent_row_bytes")]
    need = _mla_moe.tick_need(outcome)
    if not pools or need is None:
        return None
    rows = statistics.fmean(c + n for _, n, c in _mla_moe.live_ticks(outcome))
    return (100.0 * rows * pools[-1]["latent_row_bytes"]
            * pools[-1]["pool_layers"] / need["bytes"])
