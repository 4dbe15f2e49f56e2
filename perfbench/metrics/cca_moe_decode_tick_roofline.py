"""Layer: kernels. The decode tick of a CCA + top-1-expert decoder as a
share of its roofline: the least time the chip could take for what the
algorithm needs in one tick (``harness/opcount_cca_moe.
cca_moe_decode_tick_need``: attention, router and norm weights once, the
three matrices of every expert that took a token, the tied head once, the
live K/V once a layer, the live slots' tails) over the tick's median
device time. Live slots and live context are the scheduler's own counters
over the traced ticks; ``experts_hit`` is the mean over the window's
``sched.collect.process`` spans. A configuration whose ``program`` block
is of another kind, or a program whose spans carry no ``experts_hit`` (a
parent from before the expert layer), reports nothing. Above 100% raises.
Source: device_trace."""

import statistics

from perfbench.harness import device, opcount, opcount_cca_moe
from perfbench.metrics import _programs, _spans


def read(outcome):
    program = outcome["config"].get("program") or {}
    if program.get("attn_kind") != "cca" or not program.get("n_experts"):
        return None
    ds = _programs.durations(outcome, "decode_tick")
    ticks = [t for t in outcome["counters"]["traced_ticks"] if t[1] > 0]
    hit = [e.args["experts_hit"]
           for e in _spans.in_window(outcome, "sched.collect.process")
           if e.args and "experts_hit" in e.args]
    if not ds or not ticks or not hit:
        return None
    flops, bytes_ = opcount_cca_moe.cca_moe_decode_tick_need(
        program, statistics.fmean(n for _, n, _ in ticks),
        statistics.fmean(c for _, _, c in ticks), statistics.fmean(hit))
    least, _ = opcount.least_time_s(
        flops, bytes_, device.peaks(outcome["device"]["kind"]))
    return opcount.share_percent(least, statistics.median(ds),
                                 "cca_moe_decode_tick_roofline")
