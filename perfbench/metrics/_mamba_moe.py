"""Shared by the readers of the state-space hybrid's tick (blocks of one
sublayer by a pattern string: Mamba-2 blocks whose state is a request's, an
attention block over a real K/V pool, sigmoid-routed two-matrix experts of
which the chip holds a part): the tick's need
(``harness/opcount_mamba_moe.mamba_moe_decode_tick_need``) from the
scheduler's own counters over the traced ticks (live slots, live context) and
the window's ``sched.collect.process`` spans (``experts_hit``, ``routed``). A
configuration whose ``program`` block is of another kind, or a program whose
spans carry neither argument (a parent from before such a stack), gives
``None``, and every reader built on it reports nothing."""

import statistics

from perfbench.harness import opcount_mamba_moe
from perfbench.metrics import _gdn_moe, _spans


def tick_need(outcome):
    program = outcome["config"].get("program") or {}
    if "M" not in (program.get("layer_pattern") or ""):
        return None
    counters = outcome["counters"]
    ticks = [t for t in (counters.get("traced_ticks") or counters["ticks"])
             if t[1] > 0]
    spans = [e.args for e in _spans.in_window(outcome,
                                              "sched.collect.process")
             if e.args and "experts_hit" in e.args and "routed" in e.args]
    if not ticks or not spans:
        return None
    return opcount_mamba_moe.mamba_moe_decode_tick_need(
        program, statistics.fmean(n for _, n, _ in ticks),
        statistics.fmean(c for _, _, c in ticks),
        statistics.fmean(a["experts_hit"] for a in spans),
        statistics.fmean(a["routed"] for a in spans))


#: the last ``pool.alloc`` span's arguments where it carries a non-zero key
pool_args = _gdn_moe.pool_args
