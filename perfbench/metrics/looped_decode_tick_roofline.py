"""Layer: kernels. A LOOPED decoder's decode tick as a share of its
roofline: the least time the chip could take for what the algorithm needs
in one tick (``harness/opcount_looped.looped_decode_tick_need``: the
layers' weights once a pass, the head once, the live context's K and V
once in every (pass, layer) cache layer, one row a live slot written),
over the tick's median device time. Live slots and live context are the
scheduler's own counters, averaged over the traced ticks. A configuration
without a ``program`` block that loops (``ut_steps`` > 1) reports
nothing. Above 100% raises. Source: device_trace."""

import statistics

from perfbench.harness import device, opcount, opcount_looped
from perfbench.metrics import _programs


def read(outcome):
    program = outcome["config"].get("program") or {}
    if program.get("ut_steps", 1) < 2:
        return None
    ds = _programs.durations(outcome, "decode_tick")
    ticks = [t for t in outcome["counters"]["traced_ticks"] if t[1] > 0]
    if not ds or not ticks:
        return None
    slots = statistics.fmean(n for _, n, _ in ticks)
    context = statistics.fmean(c for _, _, c in ticks)
    flops, bytes_ = opcount_looped.looped_decode_tick_need(program, slots,
                                                           context)
    least, _ = opcount.least_time_s(
        flops, bytes_, device.peaks(outcome["device"]["kind"]))
    return opcount.share_percent(least, statistics.median(ds),
                                 "looped_decode_tick_roofline")
