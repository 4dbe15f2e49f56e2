"""Shared by the readers of the tick's launch path (PR 38): the program's
``engine.{chunk,decode}.{build,put,call}`` spans, three for each of the two
programs a tick launches (``serving/engine.py``: the operands built on the
host, their one ``jax.device_put``, the jitted call to its return).

A TICK is one ``router.step`` span wholly inside the window that holds an
``engine.collect.wait`` (the last tick's tokens arriving on the host: from
its end the device has nothing queued). Its LAUNCHES are the ``call`` spans
that open after that wait closes and close inside the step, in order: the
chunk program's where the tick carries one, then the decode tick's. A
launch's ``put`` and ``build`` are the last of its program's that closed
before the call opened, after the wait. A step whose wait FOLLOWS its
launches (the synchronous reference loop) has no launch in this sense.

A program without these spans (a parent from before them) gives ticks with
no launch, and every reader built on this reports nothing."""

import bisect
import statistics

from perfbench.metrics import _spans

PROGRAMS = ("chunk", "decode")
NAMES = {"router.step", "engine.collect.wait"} | {
    f"engine.{prog}.{part}" for prog in PROGRAMS
    for part in ("build", "put", "call")}


def _last_before(records: list, starts: list, lo: float, hi: float):
    """The last of ``records`` (sorted by ``t0``; ``starts`` their ``t0``)
    that lies inside ``[lo, hi]``."""
    i = bisect.bisect_right(starts, hi) - 1
    if i >= 0 and records[i].t0 >= lo and records[i].t1 <= hi:
        return records[i]
    return None


def ticks(outcome) -> list:
    """``[{"wait_end": seconds, "launches": [{"program", "build", "put",
    "call"}, ...]}, ...]``: a tick of the window each, the records of its
    launches in the order they opened (``build`` or ``put`` is ``None``
    where the ring holds none for that launch)."""
    tr = _spans.stream()
    if tr is None:
        return []
    lo, hi = _spans.window(outcome)
    by = {name: [] for name in NAMES}
    for e in tr.events(t_lo=lo, t_hi=hi):
        if e.name in by and e.t0 >= lo and e.t1 <= hi:
            by[e.name].append(e)
    starts = {}
    for name, records in by.items():
        records.sort(key=lambda e: e.t0)
        starts[name] = [e.t0 for e in records]
    calls = sorted((e for prog in PROGRAMS
                    for e in by[f"engine.{prog}.call"]), key=lambda e: e.t0)
    call_starts = [e.t0 for e in calls]
    waits, wait_starts = (by["engine.collect.wait"],
                          starts["engine.collect.wait"])
    out = []
    for step in by["router.step"]:
        i = bisect.bisect_left(wait_starts, step.t0)
        if i == len(waits) or waits[i].t1 > step.t1:
            continue  # the step collected nothing
        wait_end = waits[i].t1
        launches = []
        for call in calls[bisect.bisect_left(call_starts, wait_end):]:
            if call.t1 > step.t1:
                break
            prog = call.name.split(".")[1]
            launch = {"program": prog, "call": call}
            for part in ("build", "put"):
                name = f"engine.{prog}.{part}"
                launch[part] = _last_before(by[name], starts[name],
                                            wait_end, call.t0)
            launches.append(launch)
        out.append({"wait_end": wait_end, "launches": launches})
    return out


def median_ms(seconds: list):
    return 1e3 * statistics.median(seconds) if seconds else None


def first_launch_part_ms(outcome, part: str):
    """Median, over the window's ticks, of the first launch's ``part``
    span, in ms."""
    firsts = [t["launches"][0][part] for t in ticks(outcome)
              if t["launches"]]
    return median_ms([e.t1 - e.t0 for e in firsts if e is not None])
