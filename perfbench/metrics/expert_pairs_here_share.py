"""Layer: programs. Share of a tick's (lane, expert) pairs that landed on
an expert this chip holds, in percent: ``routed`` over ``pairs`` (live
lanes times experts a token), the mean over the window's
``sched.collect.process`` spans. A chip that holds a quarter of the experts
reads 25% under an even router; it is the number that ties the cell's
expert load to its deployment's. 100% where every expert is held. A program
whose spans carry no ``pairs`` (a parent from before a token took more than
one expert) reports nothing. Source: program_span."""

import statistics

from perfbench.metrics import _spans


def read(outcome):
    shares = [e.args["routed"] / e.args["pairs"]
              for e in _spans.in_window(outcome, "sched.collect.process")
              if e.args and e.args.get("pairs") and "routed" in e.args]
    return 100.0 * statistics.fmean(shares) if shares else None
