"""Shared by the readers of the gated-delta-rule decoder's tick (delta-rule
layers whose state is a request's, a full layer over a real K/V pool,
softmax-routed experts of which the chip holds a part): the tick's need
(``harness/opcount_gdn_moe.gdn_moe_decode_tick_need``) from the scheduler's
own counters over the traced ticks (live slots, live context) and the
window's ``sched.collect.process`` spans (``experts_hit``, ``routed``). A
configuration whose ``program`` block is of another kind, or a program whose
spans carry neither argument (a parent from before such a stack), gives
``None``, and every reader built on it reports nothing."""

import statistics

from perfbench.harness import opcount_gdn_moe
from perfbench.metrics import _spans


def tick_need(outcome):
    program = outcome["config"].get("program") or {}
    if program.get("attn_kind") != "gdn" or not program.get("n_experts"):
        return None
    counters = outcome["counters"]
    ticks = [t for t in (counters.get("traced_ticks") or counters["ticks"])
             if t[1] > 0]
    spans = [e.args for e in _spans.in_window(outcome,
                                              "sched.collect.process")
             if e.args and "experts_hit" in e.args and "routed" in e.args]
    if not ticks or not spans:
        return None
    return opcount_gdn_moe.gdn_moe_decode_tick_need(
        program, statistics.fmean(n for _, n, _ in ticks),
        statistics.fmean(c for _, _, c in ticks),
        statistics.fmean(a["experts_hit"] for a in spans),
        statistics.fmean(a["routed"] for a in spans))


def pool_args(outcome, key: str):
    """The last ``pool.alloc`` span's arguments where it carries a non-zero
    ``key``, else None."""
    pools = [e.args for e in _spans.in_setup(outcome, "pool.alloc")
             if e.args and e.args.get(key)]
    return pools[-1] if pools else None
