"""Shared by the readers of the latent-attention expert decoder's tick
(every layer latent attention over a pool of one row a token that is the
only cache, every expert held): the tick's need
(``harness/opcount_mla_moe.mla_moe_decode_tick_need``) from the scheduler's
own counters over the traced ticks (live slots, live context) and the
window's ``sched.collect.process`` spans (``experts_hit``, ``routed``). A
configuration whose ``program`` block is of another kind, or a program whose
spans carry neither argument (a parent from before such a stack), gives
``None``, and every reader built on it reports nothing."""

import statistics

from perfbench.harness import opcount_mla_moe
from perfbench.metrics import _spans


def live_ticks(outcome) -> list:
    """The traced ticks (the window's where none was traced) that decoded."""
    counters = outcome["counters"]
    return [t for t in (counters.get("traced_ticks") or counters["ticks"])
            if t[1] > 0]


def tick_need(outcome):
    program = outcome["config"].get("program") or {}
    if program.get("attn_kind") != "mla" or not program.get("n_experts"):
        return None
    ticks = live_ticks(outcome)
    spans = [e.args for e in _spans.in_window(outcome,
                                              "sched.collect.process")
             if e.args and "experts_hit" in e.args and "routed" in e.args]
    if not ticks or not spans:
        return None
    return opcount_mla_moe.mla_moe_decode_tick_need(
        program, statistics.fmean(n for _, n, _ in ticks),
        statistics.fmean(c for _, _, c in ticks),
        statistics.fmean(a["experts_hit"] for a in spans),
        statistics.fmean(a["routed"] for a in spans))
