"""Layer: programs. How uneven a tick's routing is: the fullest expert's
tokens (``expert_tokens_peak``, a mean over the layers) times the number
of experts over the live lanes routed (``routed``), the mean over the
window's ``sched.collect.process`` spans. 1.0 is an even split; the
fullest expert sets how long a grouped product's longest group is. A
program whose spans carry neither argument reports nothing. Source:
program_span."""

import statistics

from perfbench.metrics import _spans


def read(outcome):
    experts = (outcome["config"].get("program") or {}).get("n_experts")
    if not experts:
        return None
    ratios = [e.args["expert_tokens_peak"] * experts / e.args["routed"]
              for e in _spans.in_window(outcome, "sched.collect.process")
              if e.args and e.args.get("routed")
              and "expert_tokens_peak" in e.args]
    return statistics.fmean(ratios) if ratios else None
