"""Request-lifecycle causal tracing: one trace per request, across owners.

``SpanTracer`` (telemetry/spans.py) records wall-clock *phases of the
host loop* — but its spans carry no request identity, so the per-request
JSONL records (``kind="request"/"preempt"/"swap"``) cannot be joined
into a causal timeline: which replica served rid 17, how long it sat in
the queue, whether the handoff or the preemption ate its tail latency.
This module is that join layer. A request's whole lifecycle — SLOGate
admission decision, queue wait, chunked prefill, the disaggregated
prefill→decode handoff, decode windows, preempt→park→restore, retire —
becomes ONE trace:

- ``trace`` id = the fleet-wide rid (requests keep their rid across
  replicas and the handoff, so the trace follows them for free);
- ``span`` ids are process-monotone; every span names its ``parent``
  (the root "request" span has none), so the trace is a tree by
  construction;
- ``seq`` is a global logical clock bumped once per emitted record —
  the one-loop fleet simulation ticks replicas from a single host loop,
  so seq order IS causal step-domain order even where wall clocks of
  two spans are too close to distinguish;
- every record is one versioned ``kind="span"`` line on the caller's
  ``MetricsLogger`` sink — same rotation and SIGKILL-durability story as
  the flight-recorder mirror: a killed process leaves every *begin*
  already on disk, which is exactly how a post-mortem finds the phase a
  request died in.

Record shapes (all carry ``kind="span"``, ``v=1``, ``trace``, ``span``,
``seq``, ``t`` [monotone seconds]):

- ``ev="begin"``: ``name``, ``parent`` (absent on the root), optional
  ``replica``, plus free-form attributes;
- ``ev="end"``: closes ``span``; ``dur_s`` plus attributes measured at
  close (e.g. a swap's measured wall next to its predicted cost);
- ``ev="event"``: an instant — gate decisions, prefill chunks, KV block
  transitions, restores; parented like a span;
- ``ev="link"``: a causal arrow between two spans that is NOT a parent
  edge (the handoff span → the adopted decode window); rendered as a
  Chrome-trace flow arrow.

``validate_trace`` is the completeness checker behind
``scripts/explain_request.py --assert-complete``: every begin closed
exactly once, parent links resolving to earlier spans of the same trace
(acyclic by the seq order), exactly one root, no orphan events, links
landing on known spans. ``chrome_trace`` renders the records for
Perfetto/chrome://tracing — one process ("request <rid>") per trace,
one thread row per replica, flow arrows across the handoff.

What seq does and does not guarantee: records emitted by the one host
loop are totally ordered, and that order embeds every happens-before
the loop enforces (admit before prefill, export before adopt). It says
NOTHING about wall-clock overlap on real hardware — two replicas'
device work is concurrent even though their host-side records
interleave — which is why spans carry ``t`` too, and why the async
fleet host (ROADMAP item 3) gates on this layer: wall attribution per
request has to exist before the loop goes event-driven.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Dict, Iterable, Iterator, List, Optional

#: schema version stamped into every record (bump on breaking change)
SPAN_SCHEMA_VERSION = 1

#: record keys owned by the tracer — span attributes must not shadow them
RESERVED_KEYS = frozenset(
    {"kind", "v", "ev", "trace", "span", "parent", "name", "seq", "t",
     "dur_s", "replica", "ts"}
)


class ReqTracer:
    """Per-request span emitter over a ``MetricsLogger``-shaped sink.

    ``sink`` needs one method, ``log(**record)`` (``None`` keeps records
    in memory only — ``self.records``). A disabled tracer costs one
    truthiness check per call site, so
    every lifecycle owner threads one through unconditionally.

    Thread-safe: id/seq allocation, open-span bookkeeping, and the sink
    write happen under one lock, so ``seq`` order on disk matches
    allocation order even if a worker thread (ROADMAP item 3) emits
    concurrently with the main loop.
    """

    def __init__(self, sink=None, enabled: bool = True,
                 keep: Optional[bool] = None):
        self.enabled = bool(enabled)
        self.sink = sink
        #: in-memory mirror of every record (tests, in-process export);
        #: defaults to on only when there is no sink to hold them
        self.keep = (sink is None) if keep is None else bool(keep)
        self.records: List[dict] = []
        self._lock = threading.Lock()
        self._seq = 0
        self._next_span = 1
        self._open: Dict[int, dict] = {}  # span_id -> begin record
        self._roots: Dict[int, int] = {}  # trace (rid) -> root span id

    # -- emission ----------------------------------------------------------

    def _emit(self, record: dict) -> None:
        # caller holds the lock: seq order and sink order must agree
        record["seq"] = self._seq
        self._seq += 1
        if self.keep:
            self.records.append(record)
        if self.sink is not None:
            self.sink.log(**record)

    @staticmethod
    def _clean(attrs: dict) -> dict:
        bad = RESERVED_KEYS.intersection(attrs)
        if bad:
            raise ValueError(
                f"span attributes {sorted(bad)} shadow reserved record "
                f"keys {sorted(RESERVED_KEYS)}"
            )
        return {k: v for k, v in attrs.items() if v is not None}

    # -- spans -------------------------------------------------------------

    def open_root(self, rid: int, **attrs) -> int:
        """Open (or return) the trace's root "request" span. Idempotent:
        the gate decision opens it in a fleet, ``Scheduler.submit``
        opens it standalone — whichever runs first wins and the other
        sees the existing root."""
        if not self.enabled:
            return 0
        with self._lock:
            root = self._roots.get(rid)
            if root is not None:
                return root
        return self.begin(rid, "request", parent=0, **attrs)

    def root(self, rid: int) -> int:
        """The trace's root span id (0 when none is open yet)."""
        if not self.enabled:
            return 0
        with self._lock:
            return self._roots.get(rid, 0)

    def begin(self, rid: int, name: str, *, parent: Optional[int] = None,
              replica: Optional[int] = None, t: Optional[float] = None,
              **attrs) -> int:
        """Open a span; returns its id (0 when disabled). ``parent=None``
        defaults to the trace's root; ``parent=0`` makes THIS span the
        root. ``t`` backdates the start (a caller that only commits a
        span once it succeeded — the handoff — passes the wall it
        captured up front)."""
        if not self.enabled:
            return 0
        attrs = self._clean(attrs)
        with self._lock:
            if parent is None:
                parent = self._roots.get(rid, 0)
            span = self._next_span
            self._next_span += 1
            rec = {
                "kind": "span", "v": SPAN_SCHEMA_VERSION, "ev": "begin",
                "trace": rid, "span": span, "name": name,
                "t": time.perf_counter() if t is None else t,
            }
            if parent:
                rec["parent"] = parent
            if replica is not None:
                rec["replica"] = replica
            rec.update(attrs)
            self._open[span] = rec
            if not parent:
                self._roots[rid] = span
            self._emit(rec)
            return span

    def end(self, span: int, **attrs) -> None:
        """Close a span (no-op for id 0 / unknown ids — a disabled
        tracer hands out 0s, and double-close must not corrupt the
        stream)."""
        if not self.enabled or not span:
            return
        attrs = self._clean(attrs)
        with self._lock:
            begin = self._open.pop(span, None)
            if begin is None:
                return
            now = time.perf_counter()
            rec = {
                "kind": "span", "v": SPAN_SCHEMA_VERSION, "ev": "end",
                "trace": begin["trace"], "span": span, "t": now,
                "dur_s": round(now - begin["t"], 9),
            }
            rec.update(attrs)
            # Closing the root retires the trace: drop the rid→root
            # entry so _roots stays O(open traces), not O(rids ever)
            # (round 21 census finding — 100k sessions held 100k ints
            # here). A later open_root for a *harvested* rid still
            # finds its entry because abandon() deliberately leaves
            # dead-replica roots open; only a closed root is purged.
            trace = begin["trace"]
            if self._roots.get(trace) == span:
                del self._roots[trace]
            self._emit(rec)

    @contextlib.contextmanager
    def span(self, rid: int, name: str, **kw) -> Iterator[int]:
        """``begin``/``end`` as a context manager; yields the span id."""
        span = self.begin(rid, name, **kw)
        try:
            yield span
        finally:
            self.end(span)

    def event(self, rid: int, name: str, *, parent: Optional[int] = None,
              replica: Optional[int] = None, **attrs) -> int:
        """An instant record (gate decision, chunk, KV transition,
        restore) — gets its own span id so links can target it, but
        needs no close."""
        if not self.enabled:
            return 0
        attrs = self._clean(attrs)
        with self._lock:
            if parent is None:
                parent = self._roots.get(rid, 0)
            span = self._next_span
            self._next_span += 1
            rec = {
                "kind": "span", "v": SPAN_SCHEMA_VERSION, "ev": "event",
                "trace": rid, "span": span, "name": name,
                "t": time.perf_counter(),
            }
            if parent:
                rec["parent"] = parent
            if replica is not None:
                rec["replica"] = replica
            rec.update(attrs)
            self._emit(rec)
            return span

    def link(self, rid: int, src: int, dst: int, name: str = "flow") -> None:
        """A causal arrow between two spans of ``rid``'s trace that is
        not a parent edge — the handoff span → the decode window it
        enabled on the other replica. Rendered as a Perfetto flow
        arrow."""
        if not self.enabled or not src or not dst:
            return
        with self._lock:
            self._emit({
                "kind": "span", "v": SPAN_SCHEMA_VERSION, "ev": "link",
                "trace": rid, "span": src, "dst": dst, "name": name,
                "t": time.perf_counter(),
            })

    # -- live introspection (pdt_top's in-process twin reads the JSONL) ----

    def open_spans(self) -> List[dict]:
        with self._lock:
            return [dict(r) for r in self._open.values()]

    def open_traces(self) -> List[int]:
        """Traces whose ROOT span is still open — the in-flight
        requests."""
        with self._lock:
            return sorted(
                rid for rid, span in self._roots.items()
                if span in self._open
            )

    def census_decls(self):
        from .census import Decl

        return [
            Decl("records", lambda t: "unbounded" if t.keep else "fixed",
                 cap=lambda t: None if t.keep else 0,
                 why="keep-mode retains every record for in-process "
                     "assertions (tests/forensics); streaming mode "
                     "(sink set, keep=False) holds none"),
            Decl("_open", "live", per_live=8,
                 why="open begin records; a live request holds at most a "
                     "handful of concurrently-open spans (root, queue, "
                     "prefill/decode window, swap, handoff)"),
            Decl("_roots", "live",
                 why="rid→root map, purged when the root closes "
                     "(round 21); harvested rids' roots stay open by "
                     "design until the router resolves them"),
        ]


#: Shared no-op request tracer: lifecycle owners thread
#: one through without caring whether anyone is listening.
NULL_REQTRACER = ReqTracer(enabled=False)


# ---------------------------------------------------------------------------
# stream-side analysis: completeness, trees, Perfetto export
# ---------------------------------------------------------------------------


def span_records(records: Iterable[dict],
                 rid: Optional[int] = None) -> List[dict]:
    """The ``kind="span"`` records (of one trace, when ``rid`` is
    given), in seq order — the stable causal order, independent of file
    interleaving."""
    out = [
        r for r in records
        if r.get("kind") == "span" and (rid is None or r.get("trace") == rid)
    ]
    out.sort(key=lambda r: r.get("seq", 0))
    return out


def trace_rids(records: Iterable[dict]) -> List[int]:
    return sorted({
        r["trace"] for r in records
        if r.get("kind") == "span" and "trace" in r
    })


def validate_trace(records: Iterable[dict],
                   rid: Optional[int] = None) -> List[str]:
    """Completeness/causality errors for one trace (or every trace when
    ``rid`` is None). Empty list == the stream is a closed, acyclic,
    fully-parented span forest — the ``--assert-complete`` CI gate."""
    errors: List[str] = []
    for r in (trace_rids(records) if rid is None else [rid]):
        errors.extend(_validate_one(span_records(records, r), r))
    return errors


def _validate_one(recs: List[dict], rid: int) -> List[str]:
    errors: List[str] = []
    if not recs:
        return [f"trace {rid}: no span records"]
    begun: Dict[int, dict] = {}
    ended: Dict[int, dict] = {}
    events: Dict[int, dict] = {}
    roots: List[int] = []
    last_seq = -1
    for r in recs:
        seq = r.get("seq", -1)
        if seq <= last_seq:
            errors.append(
                f"trace {rid}: seq not strictly increasing at span "
                f"{r.get('span')} ({seq} after {last_seq})"
            )
        last_seq = seq
        ev = r.get("ev")
        span = r.get("span")
        if ev == "begin":
            if span in begun:
                errors.append(f"trace {rid}: span {span} begun twice")
            begun[span] = r
            parent = r.get("parent")
            if not parent:
                roots.append(span)
            elif parent not in begun and parent not in events:
                errors.append(
                    f"trace {rid}: span {span} ({r.get('name')}) parent "
                    f"{parent} not opened earlier in this trace"
                )
        elif ev == "end":
            if span not in begun:
                errors.append(f"trace {rid}: end for unopened span {span}")
            if span in ended:
                errors.append(f"trace {rid}: span {span} ended twice")
            ended[span] = r
        elif ev == "event":
            events[span] = r
            parent = r.get("parent")
            if parent and parent not in begun and parent not in events:
                errors.append(
                    f"trace {rid}: event {span} ({r.get('name')}) parent "
                    f"{parent} not opened earlier in this trace"
                )
        elif ev == "link":
            known = set(begun) | set(events)
            for end_key in ("span", "dst"):
                if r.get(end_key) not in known:
                    errors.append(
                        f"trace {rid}: link endpoint {r.get(end_key)} "
                        f"unknown"
                    )
        else:
            errors.append(f"trace {rid}: unknown ev {ev!r}")
    for span, r in begun.items():
        if span not in ended:
            errors.append(
                f"trace {rid}: span {span} ({r.get('name')}) never closed"
            )
    if len(roots) != 1:
        errors.append(
            f"trace {rid}: expected exactly one root span, found "
            f"{len(roots)}"
        )
    return errors


class SpanNode:
    """One span (or instant event) with its children, for rendering."""

    __slots__ = ("record", "end", "children")

    def __init__(self, record: dict, end: Optional[dict] = None):
        self.record = record
        self.end = end
        self.children: List["SpanNode"] = []

    @property
    def name(self) -> str:
        return self.record.get("name", "?")

    @property
    def is_event(self) -> bool:
        return self.record.get("ev") == "event"

    @property
    def t0(self) -> float:
        return self.record.get("t", 0.0)

    @property
    def t1(self) -> Optional[float]:
        return self.end.get("t") if self.end is not None else None

    @property
    def dur_s(self) -> Optional[float]:
        return self.end.get("dur_s") if self.end is not None else None

    def attrs(self) -> dict:
        out = {
            k: v for k, v in self.record.items()
            if k not in RESERVED_KEYS and k != "dst"
        }
        if self.end is not None:
            out.update({
                k: v for k, v in self.end.items()
                if k not in RESERVED_KEYS and k != "dst"
            })
        return out


def build_tree(records: Iterable[dict], rid: int) -> Optional[SpanNode]:
    """The trace's span tree (children in seq order). Returns None when
    the trace has no root; tolerates incomplete traces — explain must
    render the trace of a crashed run too."""
    recs = span_records(records, rid)
    ends = {r["span"]: r for r in recs if r.get("ev") == "end"}
    nodes: Dict[int, SpanNode] = {}
    root: Optional[SpanNode] = None
    for r in recs:
        if r.get("ev") not in ("begin", "event"):
            continue
        node = SpanNode(r, ends.get(r["span"]))
        nodes[r["span"]] = node
        parent = nodes.get(r.get("parent"))
        if parent is not None:
            parent.children.append(node)
        elif r.get("ev") == "begin" and not r.get("parent"):
            root = node
    return root


def chrome_trace(records: Iterable[dict]) -> dict:
    """Render span records as Chrome-trace JSON (Perfetto-loadable).

    Each trace (request) is a *process* named ``request <rid>``; each
    replica that touched it is a thread row inside it, so the
    cross-replica handoff reads as the request's own timeline switching
    rows; ``ev="link"`` records become flow arrows between their
    endpoint spans. Instant events render as thread-scoped ``i``
    events. Spans still open at export time render to the stream's last
    timestamp with ``open: true`` — a crashed run's last phase stays
    visible instead of vanishing."""
    recs = span_records(records)
    if not recs:
        return {"traceEvents": [], "displayTimeUnit": "ms"}
    span_ts = [r.get("t", 0.0) for r in recs]
    t0 = min(span_ts)
    t_last = max(span_ts)

    def us(t: float) -> float:
        return (t - t0) * 1e6

    ends = {
        (r["trace"], r["span"]): r for r in recs if r.get("ev") == "end"
    }
    begins = {(r["trace"], r["span"]): r
              for r in recs if r.get("ev") in ("begin", "event")}
    events: List[dict] = []
    seen_tracks = set()
    for r in recs:
        trace = r.get("trace")
        tid = r.get("replica", 0) or 0
        if r.get("ev") in ("begin", "event") and (trace, tid) not in seen_tracks:
            seen_tracks.add((trace, tid))
            events.append({
                "name": "process_name", "ph": "M", "pid": trace,
                "args": {"name": f"request {trace}"},
            })
            events.append({
                "name": "thread_name", "ph": "M", "pid": trace, "tid": tid,
                "args": {"name": f"replica {tid}"},
            })
        args = {k: v for k, v in r.items() if k not in RESERVED_KEYS}
        args["seq"] = r.get("seq")
        if r.get("ev") == "begin":
            end = ends.get((trace, r["span"]))
            if end is not None:
                dur = us(end["t"]) - us(r["t"])
                args.update({
                    k: v for k, v in end.items() if k not in RESERVED_KEYS
                })
            else:
                dur = us(t_last) - us(r["t"])
                args["open"] = True
            events.append({
                "name": r.get("name", "?"), "ph": "X", "ts": us(r["t"]),
                "dur": max(dur, 0.0), "pid": trace, "tid": tid,
                "args": args,
            })
        elif r.get("ev") == "event":
            events.append({
                "name": r.get("name", "?"), "ph": "i", "s": "t",
                "ts": us(r["t"]), "pid": trace, "tid": tid, "args": args,
            })
        elif r.get("ev") == "link":
            src = begins.get((trace, r.get("span")))
            dst = begins.get((trace, r.get("dst")))
            if src is None or dst is None:
                continue
            flow_id = int(r.get("seq", 0))
            events.append({
                "name": r.get("name", "flow"), "cat": "handoff",
                "ph": "s", "id": flow_id, "ts": us(src["t"]),
                "pid": trace, "tid": src.get("replica", 0) or 0,
            })
            events.append({
                "name": r.get("name", "flow"), "cat": "handoff",
                "ph": "f", "bp": "e", "id": flow_id, "ts": us(dst["t"]),
                "pid": trace, "tid": dst.get("replica", 0) or 0,
            })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def save_chrome_trace(records: Iterable[dict], path: str) -> str:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(chrome_trace(records), f)
    return path
