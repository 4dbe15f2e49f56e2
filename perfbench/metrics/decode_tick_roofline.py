"""Layer: kernels. The decode tick's share of its roofline: the least time
the chip could take for what the ALGORITHM needs in one tick (every
weight read once, the live context's K and V read once, one row a live
slot written; ``harness/opcount.decode_tick_need``), over the tick's
median device time. Live slots and live context are the scheduler's own
counters, averaged over the traced ticks. Bytes the compiled program
moves beyond the need lower the share; they are never in the numerator.
Above 100% raises."""

import statistics

from perfbench.harness import device, opcount
from perfbench.metrics import _programs


def read(outcome):
    ds = _programs.durations(outcome, "decode_tick")
    ticks = [t for t in outcome["counters"]["traced_ticks"] if t[1] > 0]
    if not ds or not ticks:
        return None
    slots = statistics.fmean(n for _, n, _ in ticks)
    context = statistics.fmean(c for _, _, c in ticks)
    flops, bytes_ = opcount.decode_tick_need(outcome["config"], slots,
                                             context)
    least, _ = opcount.least_time_s(
        flops, bytes_, device.peaks(outcome["device"]["kind"]))
    return opcount.share_percent(least, statistics.median(ds),
                                 "decode_tick_roofline")
