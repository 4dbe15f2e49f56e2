"""Layer: programs. Share of a layer's experts that took at least one
token in a tick, in percent: ``experts_hit`` (a mean over the layers) over
the number of experts, the mean over the window's ``sched.collect.process``
spans: the share of the expert weights a tick must read, so lower is
better (``cca_moe_decode_tick_roofline`` takes it as an input). A program
whose spans carry no ``experts_hit`` reports nothing. Source:
program_span."""

import statistics

from perfbench.metrics import _spans


def read(outcome):
    experts = (outcome["config"].get("program") or {}).get("n_experts")
    if not experts:
        return None
    hit = [e.args["experts_hit"]
           for e in _spans.in_window(outcome, "sched.collect.process")
           if e.args and "experts_hit" in e.args]
    return 100.0 * statistics.fmean(hit) / experts if hit else None
