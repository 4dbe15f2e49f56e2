"""Layer: kernels. The decode tick of a state-space hybrid (Mamba-2 blocks,
expert blocks and an attention block over a real K/V pool, ONE sublayer a
block, sigmoid-routed two-matrix experts of which the chip holds a part) as a
share of its roofline: the least time the chip could take for what the
algorithm needs in one tick
(``harness/opcount_mamba_moe.mamba_moe_decode_tick_need``: the non-expert
weights once, both matrices of every held expert that took a pair, the live
lanes' Mamba-2 state read and written once in float32 with their convolution
inputs, the live key and value rows once and a new row a lane, the head slice
once) over the tick's median device time. Live slots and live context are the
scheduler's own counters over the traced ticks; ``experts_hit`` and
``routed`` are means over the window's ``sched.collect.process`` spans. A
configuration whose ``program`` block is of another kind, or a program whose
spans carry neither (a parent from before such a stack), reports nothing.
Above 100% raises. Source: device_trace."""

import statistics

from perfbench.harness import device, opcount
from perfbench.metrics import _mamba_moe, _programs


def read(outcome):
    need = _mamba_moe.tick_need(outcome)
    ds = _programs.durations(outcome, "decode_tick")
    if need is None or not ds:
        return None
    least, _ = opcount.least_time_s(
        need["flops"], need["bytes"],
        device.peaks(outcome["device"]["kind"]))
    return opcount.share_percent(least, statistics.median(ds),
                                 "mamba_moe_decode_tick_roofline")
