"""Layer: routing and scheduling. Share of the window's ``router.step``
spans (the program's own, ``fleet/router.py``) that were entered with a
tick in flight, in percent: ``in_flight`` is how many replicas had a
launched tick's tokens pending when the step began, so a step that reads 1
or more collected a tick the device ran through the caller's submits and
booking, and a step that reads 0 launched into an idle device. Higher is
better: 0 on a loop that fetches each tick's tokens inside its launch, 100
once the router keeps a tick in flight. A program whose spans carry no
such argument (one from before the router's loop said so) reports
nothing. Source: program_span."""

from perfbench.metrics import _spans


def read(outcome):
    flights = [e.args["in_flight"]
               for e in _spans.in_window(outcome, "router.step")
               if e.args and "in_flight" in e.args]
    if not flights:
        return None
    return 100.0 * sum(1 for n in flights if n >= 1) / len(flights)
