"""Layer: kernels. The decode tick of a latent-attention expert decoder
(every layer latent attention over a pool of one row a token that is the
only cache, sigmoid-routed experts all held) as a share of its roofline: the
least time the chip could take for what the algorithm needs in one tick
(``harness/opcount_mla_moe.mla_moe_decode_tick_need``: the non-expert weights
once, the three matrices of every expert that took a pair, the live
context's latent rows once a layer and a row a live lane written, the head
once, the live lanes' logits) over the tick's median device time. Live slots
and live context are the scheduler's own counters over the traced ticks;
``experts_hit`` and ``routed`` are means over the window's
``sched.collect.process`` spans. A configuration whose ``program`` block is
of another kind, or a program whose spans carry neither (a parent from
before such a stack), reports nothing. Above 100% raises. Source:
device_trace."""

import statistics

from perfbench.harness import device, opcount
from perfbench.metrics import _mla_moe, _programs


def read(outcome):
    need = _mla_moe.tick_need(outcome)
    ds = _programs.durations(outcome, "decode_tick")
    if need is None or not ds:
        return None
    least, _ = opcount.least_time_s(
        need["flops"], need["bytes"],
        device.peaks(outcome["device"]["kind"]))
    return opcount.share_percent(least, statistics.median(ds),
                                 "mla_moe_decode_tick_roofline")
