"""The process's one span stream.

Every component records into ``tracer()``: host spans at the layer
boundaries (trainer build, program load, the serving tick's phases, the
admission gate). Nothing is threaded through constructors and nothing
switches it off. Counts stay where the program already keeps them
(``Scheduler.metrics()``, ``FleetRouter.metrics()``, the ``cache_hit``
argument of every ``program.load``):

- a record is ``(id, parent_id, name, t0, t1, tid, rid, args)``: ``id``
  unique in the process, ``parent_id`` the enclosing span on that thread
  (or the explicit ``cause=``), ``rid`` the request where there is one,
  ``t0``/``t1`` absolute ``time.perf_counter()`` seconds: the clock of
  ``telemetry.reqtrace`` and of whoever drives the program, so
  intervals can be laid beside theirs;
- records live in a ring of ``RING_RECORDS`` (the flight-recorder
  pattern of ``telemetry/flightrec.py``): a server that runs for days
  holds a bounded window, the newest;
- every span is also entered as ``jax.profiler.TraceAnnotation
  ("pdt:<name>")``: a no-op without a profiler session, and with one the
  span lies on the device trace's clock, beside the XLA operations. The
  trainers' step span is a ``StepTraceAnnotation("train", step_num=)``.

"Off" is therefore: recording into the ring, no profiler session,
nothing written. "On" is a profiler session (the mirror is live) and
``save()``, which writes the ring as Chrome-trace JSON (the recipes'
``--trace-dir`` says where). ``tests/test_spans.py`` holds the cost:
under 3 us a span, at most 24 spans a serving tick and 4 a training step.
Of a tick's 14, six split its two launches where the device waits:
``engine.{chunk,decode}.build`` (operands assembled on the host), then
inside ``engine.*.launch`` ``.put`` (their one transfer) and ``.call``
(the jitted function to its return).
"""

from __future__ import annotations

import collections
import itertools
import json
import os
import threading
import time
from typing import Dict, List, NamedTuple, Optional

from jax.profiler import StepTraceAnnotation, TraceAnnotation

#: records the ring holds. A chat-backlog tick is 0.22 s: an hour is
#: 16,400 ticks at up to 24 spans, and 13,000 requests at 3 (the budget
#: tests/test_spans.py holds). Full, the ring is about 100 MB of tuples.
RING_RECORDS = 1 << 19

#: prefix of the spans' names in the profiler's trace
MIRROR_PREFIX = "pdt:"


class Record(NamedTuple):
    """One finished span (or one interval booked by ``record``)."""

    id: int
    parent_id: Optional[int]
    name: str
    t0: float
    t1: float
    tid: int
    rid: Optional[int]
    args: Optional[dict]


class Span:
    """One open span: a context manager that books itself on exit.
    ``args`` may be filled in while it is open (``program.load`` learns
    ``cache_hit`` only when the load is over)."""

    __slots__ = ("_tracer", "_mirror", "_stack", "id", "parent_id", "name",
                 "rid", "args", "t0")

    def __init__(self, tracer, mirror, name, rid, cause, args):
        self._tracer = tracer
        self._mirror = mirror
        self.id = next(tracer._ids)
        self.parent_id = cause
        self.name = name
        self.rid = rid
        self.args = args

    def __enter__(self) -> "Span":
        stack = self._stack = self._tracer._open()
        if self.parent_id is None and stack:
            self.parent_id = stack[-1].id
        stack.append(self)
        self._mirror.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        self._mirror.__exit__(*exc)
        self._stack.pop()  # spans are context managers: exits are LIFO
        self._tracer._ring.append(
            (self.id, self.parent_id, self.name, self.t0, t1,
             threading.get_ident(), self.rid, self.args or None))
        return False


class SpanTracer:
    """The recorder. One per process (``tracer()``); tests may build
    their own, e.g. with a small ``maxlen``."""

    def __init__(self, maxlen: int = RING_RECORDS):
        self._ring: collections.deque = collections.deque(maxlen=maxlen)
        self._ids = itertools.count(1)  # next() is atomic under the GIL
        self._local = threading.local()  # .stack: this thread's open spans

    def _open(self) -> list:
        try:
            return self._local.stack
        except AttributeError:
            stack = self._local.stack = []
            return stack

    # ---- recording -------------------------------------------------------

    def span(self, name: str, *, rid: Optional[int] = None,
             cause: Optional[int] = None, **args) -> Span:
        """A span around a ``with`` block. Its parent is the enclosing
        span on this thread, or ``cause`` (a span's ``id``) where the
        work was caused elsewhere: on another thread, or earlier."""
        return Span(self, TraceAnnotation(MIRROR_PREFIX + name), name, rid,
                    cause, args)

    def step(self, name: str, step: int) -> Span:
        """A trainer's step span: in the profiler it is the step marker
        (``StepTraceAnnotation``), so xprof groups the device's work by
        step."""
        return Span(self, StepTraceAnnotation("train", step_num=step), name,
                    None, None, {"step": step})

    def record(self, name: str, t0: float, t1: float, *,
               rid: Optional[int] = None, cause: Optional[int] = None,
               **args) -> int:
        """Book an interval after the fact (a queue wait is known only
        at admission). ``t0``/``t1`` are ``time.perf_counter()`` seconds.
        Its parent is ``cause``, else the span open on this thread."""
        sid = next(self._ids)
        if cause is None:
            stack = self._open()
            cause = stack[-1].id if stack else None
        self._ring.append((sid, cause, name, t0, t1, threading.get_ident(),
                           rid, args or None))
        return sid

    def current(self) -> Optional[Span]:
        """The innermost span open on the calling thread."""
        stack = self._open()
        return stack[-1] if stack else None

    # ---- reading ---------------------------------------------------------

    def events(self, name: Optional[str] = None,
               t_lo: Optional[float] = None,
               t_hi: Optional[float] = None) -> List[Record]:
        """Finished records, oldest first; with ``name`` only that name,
        with ``t_lo``/``t_hi`` only those that overlap the interval."""
        while True:
            try:
                raw = list(self._ring)
                break
            except RuntimeError:  # an append raced the copy
                continue
        return [Record(*r) for r in raw
                if (name is None or r[2] == name)
                and (t_lo is None or r[4] > t_lo)
                and (t_hi is None or r[3] < t_hi)]

    def self_time(self, name: str, t_lo: Optional[float] = None,
                  t_hi: Optional[float] = None) -> float:
        """Seconds inside ``[t_lo, t_hi]`` that spans called ``name``
        were open less what their child spans cover there."""
        lo = float("-inf") if t_lo is None else t_lo
        hi = float("inf") if t_hi is None else t_hi
        events = self.events(t_lo=t_lo, t_hi=t_hi)
        children: Dict[int, list] = {}
        for e in events:
            if e.parent_id is not None:
                children.setdefault(e.parent_id, []).append(e)
        total = 0.0
        for e in events:
            if e.name != name:
                continue
            a, b = max(e.t0, lo), min(e.t1, hi)
            total += b - a
            end = a  # the union of the children, clipped to [a, b]
            for c in sorted(children.get(e.id, ()), key=lambda c: c.t0):
                s, t = max(c.t0, end), min(c.t1, b)
                if t > s:
                    total -= t - s
                    end = t
        return total

    def chrome_trace(self) -> dict:
        """The ring as a Chrome trace: one complete ("X") event a
        record, microseconds since the oldest record, ``tid`` the
        recording thread; ``id``, ``parent_id`` and ``rid`` in ``args``."""
        events = self.events()
        base = min((e.t0 for e in events), default=0.0)
        pid = os.getpid()
        out = [{"name": "process_name", "ph": "M", "pid": pid,
                "args": {"name": "pytorch_distributed_tpu host"}}]
        for e in events:
            args = dict(e.args or {}, id=e.id)
            if e.parent_id is not None:
                args["parent_id"] = e.parent_id
            if e.rid is not None:
                args["rid"] = e.rid
            out.append({"name": e.name, "ph": "X", "ts": (e.t0 - base) * 1e6,
                        "dur": (e.t1 - e.t0) * 1e6, "pid": pid, "tid": e.tid,
                        "args": args})
        return {"traceEvents": out, "displayTimeUnit": "ms"}

    def save(self, path: str) -> str:
        """Write the Chrome trace JSON to ``path`` (dirs created)."""
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path

    def clear(self) -> None:
        """Empty the ring."""
        self._ring.clear()


_TRACER = SpanTracer()


def tracer() -> SpanTracer:
    """The process's one recorder."""
    return _TRACER
