"""Host–device overlap profiler: dispatch ledger + bubble attribution.

The observability stack so far explains what a request did (reqtrace),
what a program costs (costmodel), and where a run's wall went (goodput)
— but nothing measures where the **device sat idle**. The one-loop
``FleetRouter`` ticks replicas sequentially from a single host loop, so
replica B's decode waits on replica A's host work — ROADMAP item 3's
async refactor exists to remove exactly that serialization, and this
module is the measurement contract it will be verified against: a
per-replica device timeline whose every inter-launch gap is a **bubble**
attributed to its host cause.

``DispatchLedger`` wraps every compiled call site (the engine's
chunk/decode/export/import/swap programs, the trainers' train/eval
steps) and records, per launch:

- **host dispatch wall** ``[t0, t1]`` (``time.perf_counter``) — for an
  async dispatch this is enqueue time only; for a call that materializes
  its result (``sync=True``: the decode tick fetches its tokens) it is
  dispatch + device + sync, i.e. exact completion;
- **logical-clock window** ``[seq0, seq1]`` claimed from the SAME clock
  as the round-14 span stream (``ReqTracer.claim_seq``), so "what was
  the host doing between launch N and N+1" is answerable by selecting
  span records with ``seq`` in the gap — the causal join the bubble
  classifier rides;
- a **lagged fence** bound on device completion: when launch N is
  recorded, the ledger calls ``block_until_ready`` on launch N−k's
  registered handle (the PR 4 LAGGED ring idiom — by then the work is
  almost surely done, so the fence returns immediately and the hot path
  never stalls; ``hot_fences`` counts violations of the lag and is zero
  by construction, the no-sync guard tests assert it).

What the fences do and do not bound (ANALYSIS.md "Host–device
overlap"): a fence that RETURNS IMMEDIATELY (wait below
``FENCE_BLOCK_EPS_S``) only proves completion happened somewhere in
``[t1, fence_return]`` — the ledger then uses the ``t1`` lower bound,
so device-busy is a LOWER bound and bubbles an UPPER bound on an async
backend. A fence that actually BLOCKS pins completion exactly (the
device was still running; the fence return IS the completion). On the
CPU backend dispatch is effectively synchronous (``t1`` ≈ completion),
so CPU timelines are exact — the same honesty split as
``gather_ab_backend``. Launches whose outputs are donated into later
programs (chunk prefill, kv_import, kv_swap_in) register no handle —
their buffers are invalid by fence time — and their completion rides
the ``t1`` lower bound tightened by the next synchronous launch on the
same replica stream (the decode tick, every scheduler step).

Bubble classification (``classify_bubbles``): per replica, launches
sort by ``t0``; completion ``c_i = max(done_i or t1_i, c_{i-1})``
(in-order execution per stream); the busy slice is ``[max(t0_i,
c_{i-1}), c_i]`` and the gap to the next launch ``[c_i, t0_{i+1}]`` is
a bubble. Its cause is the overlapping host activity with the largest
share of the gap:

- another replica's dispatch wall     → ``other-replica-tick`` (the
  host loop serialized behind that replica's tick — what the async
  refactor removes; a sync launch's wall contains its execution, so
  the synchronous loop's attribution reads as before)
- another replica's busy slice beyond its dispatch wall
                                      → ``shared-device-wait`` (round
  16: the shared device executing someone else — unavoidable at N
  replicas per device, gone on real N-device hardware)
- a ledger host mark (``host(...)``)  → the mark's name, one of
  ``tokenize/detokenize``, ``admission/gate``, ``jsonl-emit``,
  ``handoff-pump``, ``swap-decision``, ``sampling-prep``,
  ``metrics-refresh`` — marks recorded on a worker thread (round 16:
  the async host runtime's ``HostWorkerPool``) carry the thread name
  and classify as ``<name>@<thread>``, so host work OVERLAPPED onto a
  worker stops being misattributed to ``idle-no-work``
- a ``kind="span"`` record whose ``seq`` falls inside the gap's logical
  window (the PR 12 join), mapped through ``_SPAN_CAUSES``
- nothing                             → ``idle-no-work``

Round 16 (async host runtime): ``launch`` tokens can be **collected** —
``DispatchLedger.complete(token)`` pins an async launch's completion at
its lagged materialization site (the dispatch-then-collect loop's
collect phase) exactly like a fence would, without waiting for the
lagged window. And because N single-process replicas on a CPU host
share ONE device, per-replica busy slices measured from dispatch
windows legitimately overlap each other (a launch waits behind the
other replica's program INSIDE its dispatch→completion window) —
summing per-replica busy would double-count the shared device.
``fleet_busy_summary`` reports the interval-UNION busy fraction next
to the per-replica ones (the ``gather_ab_backend`` honesty pattern:
per-replica fractions are scheduling health, the union is true device
utilization), and ``finalize`` emits it as a ``replica=-1`` summary
record.

Everything lands as ``kind="overlap"`` JSONL (schema-registered) on the
caller's ``MetricsLogger``: ``ev="launch"``/``ev="host"`` batched off
the hot path (buffered, emitted every ``emit_every`` records inside a
self-marked ``jsonl-emit`` window), ``ev="bubble"`` and ``ev="summary"``
at ``finalize()``. ``scripts/telemetry_report.py`` renders the section,
``scripts/pdt_top.py`` tails the live row, ``scripts/bench_serving.py
--wall-clock`` is the fleet bench that gates on it, and the Perfetto
exporter (``reqtrace.chrome_trace``) renders one device track per
replica with dispatch→device flow arrows.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, Iterable, List, Optional, Tuple

#: a lagged fence that waited less than this was a no-op (the work had
#: already finished): completion collapses to the dispatch-return lower
#: bound instead of the (much later) fence timestamp
FENCE_BLOCK_EPS_S = 2e-4

#: chrome-trace pid base for the synthetic per-replica device processes
#: (request traces use the rid as pid; this keeps the spaces disjoint)
DEVICE_PID_BASE = 1_000_000_000

#: the bubble-cause vocabulary (host marks use these names verbatim)
CAUSE_OTHER_REPLICA = "other-replica-tick"
#: round 16: the other replica's program EXECUTING on the shared device
#: while this replica's gap is open — distinct from other-replica-tick,
#: which is the other replica's host-side DISPATCH WALL occupying the
#: loop (the serialization the async refactor removes). On one shared
#: device a sync launch's dispatch wall contains its execution, so the
#: sync loop's attribution is unchanged; under async dispatch the walls
#: collapse to microseconds and the execution time shows up here — the
#: part that vanishes on real N-device hardware (backend honesty).
CAUSE_SHARED_DEVICE = "shared-device-wait"
CAUSE_IDLE = "idle-no-work"
HOST_CAUSES = (
    "tokenize/detokenize",
    "admission/gate",
    "jsonl-emit",
    "handoff-pump",
    "swap-decision",
    "sampling-prep",
    "metrics-refresh",
    "tick-collect",
)

#: span names (round-14 ``kind="span"`` stream) → bubble cause, for gaps
#: no ledger mark explains — the logical-clock join against PR 12
_SPAN_CAUSES = {
    "queued": "admission/gate",
    "gate": "admission/gate",
    "handoff": "handoff-pump",
    "handoff_wait": "handoff-pump",
    "preempt": "swap-decision",
    "swap_out": "swap-decision",
    "swap_in": "swap-decision",
    "parked": "swap-decision",
}


class _LaunchToken:
    """Yielded by ``DispatchLedger.launch``: the call site sets
    ``handle`` to a (non-donated) output array/pytree inside the with
    block so the lagged fence has something to block on later. The
    ledger fills ``rec``/``entry`` on exit so an async call site can
    hold the token and pin completion itself at its collect site
    (``DispatchLedger.complete`` — the round-16 dispatch-then-collect
    loop)."""

    __slots__ = ("handle", "rec", "entry")

    def __init__(self):
        self.handle = None
        self.rec = None
        self.entry = None


class DispatchLedger:
    """Per-launch dispatch ledger over a ``MetricsLogger``-shaped sink.

    ``sink`` needs one method, ``log(**record)`` (None keeps records in
    memory only). ``seq_source`` is any object with ``claim_seq()`` —
    pass the run's ``ReqTracer`` so launch windows and span records
    share one logical clock (the bubble classifier's join key); without
    one the ledger keeps a private counter. A disabled ledger
    (``NULL_LEDGER``) costs one truthiness check per call site.

    Thread-safe: record appends and seq claims happen under one lock
    (the background-warmup thread never launches through the ledger,
    but ROADMAP item 3's worker threads will).
    """

    def __init__(self, sink=None, seq_source=None, *, lag: int = 4,
                 emit_every: int = 64, enabled: bool = True):
        if lag < 1:
            raise ValueError(f"lag must be >= 1, got {lag}")
        self.enabled = bool(enabled)
        self.sink = sink
        self.seq_source = seq_source
        self.lag = lag
        self.emit_every = emit_every
        self._lock = threading.Lock()
        self._seq = 0
        #: every record in emission order (in-memory mirror; also the
        #: source ``finalize`` classifies from)
        self.records: List[dict] = []
        self._unemitted = 0
        # per-replica launch bookkeeping for the lagged fence: list of
        # (record, handle); handles dropped once fenced so the ledger
        # never pins more than ``lag`` launch outputs alive per replica
        self._streams: Dict[int, List[list]] = {}
        #: fences that targeted a launch NEWER than current−lag — a
        #: hot-path sync. Structurally impossible; the no-sync guard
        #: test asserts it stayed zero.
        self.hot_fences = 0
        #: fences whose target buffer was already donated away (no
        #: handle should have been registered — loud counter, not crash)
        self.dead_fences = 0
        self.fences = 0
        self._finalized = False

    # ---- logical clock ---------------------------------------------------

    def _claim(self) -> int:
        if self.seq_source is not None:
            return self.seq_source.claim_seq()
        with self._lock:
            s = self._seq
            self._seq += 1
            return s

    # ---- the hot path ----------------------------------------------------

    @contextlib.contextmanager
    def launch(self, replica: int, program: str, sync: bool = False):
        """Record one compiled-program launch. Wrap exactly the dispatch
        (plus the result fetch for ``sync=True`` call sites — their
        ``t1`` is then true completion). Set ``token.handle`` to a
        non-donated output for the lagged fence; leave it None for
        launches whose outputs later programs donate."""
        if not self.enabled:
            yield _LaunchToken()
            return
        token = _LaunchToken()
        seq0 = self._claim()
        t0 = time.perf_counter()
        try:
            yield token
        finally:
            t1 = time.perf_counter()
            seq1 = self._claim()
            rec = {
                "kind": "overlap", "ev": "launch", "replica": replica,
                "program": program, "t0": t0, "t1": t1,
                "seq0": seq0, "seq1": seq1,
            }
            if sync:
                rec["done"] = t1
            with self._lock:
                stream = self._streams.setdefault(replica, [])
                entry = [rec, None if sync else token.handle]
                stream.append(entry)
                token.rec = rec
                token.entry = entry
                self._append(rec)
                # the lagged fence target: exactly one candidate per
                # launch (indices fence consecutively as the stream
                # grows), so handles older than the window are already
                # dropped — the ledger pins at most ``lag`` outputs.
                # The handle is taken IN PLACE (entry mutated, not
                # replaced) so a token's ``entry`` ref stays live and
                # ``complete`` / the fence can never double-target one
                # launch.
                fence_target = None
                fence_handle = None
                idx = len(stream) - 1 - self.lag
                if idx >= 0 and stream[idx][1] is not None:
                    fence_target = stream[idx]
                    fence_handle = fence_target[1]
                    fence_target[1] = None
            if fence_target is not None:
                self._fence(fence_target[0], fence_handle)

    def _fence(self, rec: dict, handle) -> None:
        """Block on a LAGGED launch's handle: returns immediately when
        the work already finished (the normal case — no hot-path stall);
        a blocking fence pins the launch's completion exactly."""
        import jax

        f0 = time.perf_counter()
        try:
            jax.block_until_ready(handle)
        except Exception:
            with self._lock:
                self.dead_fences += 1
            return
        f1 = time.perf_counter()
        wait = f1 - f0
        with self._lock:
            self.fences += 1
            rec["fenced"] = True
            rec["fence_wait_s"] = round(wait, 9)
            if wait > FENCE_BLOCK_EPS_S:
                # the device was still running: the fence return IS the
                # completion time (exact, not a bound)
                rec["done"] = f1

    def complete(self, token) -> None:
        """Pin an async launch's completion at its collect site (the
        round-16 dispatch-then-collect loop): blocks on the launch's
        handle like a lagged fence — by collect time the work is
        usually done and the wait is a no-op; a wait that actually
        blocked pins ``done`` exactly. Takes the handle out of the
        lagged-fence window so one launch is never fenced twice.
        No-op for sync launches, disabled ledgers, and already-fenced
        entries."""
        import jax

        if not self.enabled or token is None or token.rec is None:
            return
        with self._lock:
            handle = token.entry[1] if token.entry is not None else None
            if handle is not None:
                token.entry[1] = None
        if handle is None:
            return
        f0 = time.perf_counter()
        try:
            jax.block_until_ready(handle)
        except Exception:
            with self._lock:
                self.dead_fences += 1
            return
        f1 = time.perf_counter()
        with self._lock:
            self.fences += 1
            token.rec["collected"] = True
            token.rec["fence_wait_s"] = round(f1 - f0, 9)
            if f1 - f0 > FENCE_BLOCK_EPS_S:
                # the device was still running at collect: the wait's
                # return IS the completion time (exact, not a bound)
                token.rec["done"] = f1

    @contextlib.contextmanager
    def host(self, name: str, replica: int = -1):
        """Mark a host-work interval (tokenize/detokenize,
        admission/gate, jsonl-emit, handoff-pump, swap-decision,
        sampling-prep, metrics-refresh) — the attribution targets
        bubbles resolve to. ``replica=-1`` marks router-level work any
        replica's gap may land in. Marks recorded off the main thread
        (the async host runtime's workers) carry the thread name, so
        ``classify_bubbles`` can attribute overlapped worker work
        instead of calling it ``idle-no-work``."""
        if not self.enabled:
            yield
            return
        seq0 = self._claim()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            rec = {
                "kind": "overlap", "ev": "host", "replica": replica,
                "name": name, "t0": t0, "t1": t1, "seq0": seq0,
            }
            th = threading.current_thread()
            if th is not threading.main_thread():
                rec["thread"] = th.name
            with self._lock:
                rec["seq1"] = self._claim_locked()
                self._append(rec)

    def _claim_locked(self) -> int:
        # caller holds self._lock; claim without re-locking
        if self.seq_source is not None:
            return self.seq_source.claim_seq()
        s = self._seq
        self._seq += 1
        return s

    def _append(self, rec: dict) -> None:
        # caller holds the lock
        self.records.append(rec)
        self._unemitted += 1
        if self.sink is not None and self._unemitted >= self.emit_every:
            self._drain_locked()

    def _drain_locked(self) -> None:
        """Emit buffered records in one batch — amortized JSONL cost,
        itself recorded as a ``jsonl-emit`` host interval so the bytes
        the profiler writes show up in its own attribution."""
        if self.sink is None or self._unemitted == 0:
            return
        pending = self.records[len(self.records) - self._unemitted:]
        t0 = time.perf_counter()
        seq0 = self._claim_locked()
        for rec in pending:
            self.sink.log(**rec)
        mark = {
            "kind": "overlap", "ev": "host", "replica": -1,
            "name": "jsonl-emit", "t0": t0, "t1": time.perf_counter(),
            "seq0": seq0, "seq1": self._claim_locked(),
        }
        self.records.append(mark)
        self.sink.log(**mark)
        self._unemitted = 0

    # ---- finalization ----------------------------------------------------

    def finalize(self) -> List[dict]:
        """End of run: fence the tail of every stream (an end-of-run
        sync is allowed — the run is over), classify bubbles, emit
        everything still buffered plus one ``ev="bubble"`` record per
        gap and one ``ev="summary"`` per replica. Idempotent. Returns
        the bubble + summary records."""
        import jax

        with self._lock:
            if self._finalized:
                return []
            self._finalized = True
            tails = [
                (entry[0], entry[1])
                for stream in self._streams.values()
                for entry in stream if entry[1] is not None
            ]
        for rec, handle in tails:
            try:
                jax.block_until_ready(handle)
            except Exception:
                pass
        out: List[dict] = []
        with self._lock:
            bubbles = classify_bubbles(self.records)
            for b in bubbles:
                rec = {"kind": "overlap", "ev": "bubble", **b}
                self.records.append(rec)
                out.append(rec)
            summaries = busy_summary(self.records)
            for replica, summary in summaries.items():
                rec = {
                    "kind": "overlap", "ev": "summary",
                    "replica": replica, **summary,
                }
                self.records.append(rec)
                out.append(rec)
            if len(summaries) > 1:
                # shared-device honesty (round 16): the interval-UNION
                # busy fraction as a replica=-1 summary — per-replica
                # fractions overlap on a shared device and must not be
                # summed (module docstring)
                fleet = fleet_busy_summary(self.records)
                rec = {
                    "kind": "overlap", "ev": "summary", "replica": -1,
                    "union": True,
                    "launches": sum(s["launches"]
                                    for s in summaries.values()),
                    "busy_s": fleet["union_busy_s"],
                    "span_s": fleet["window_s"],
                    "window_s": fleet["window_s"],
                    "busy_frac": fleet["union_busy_frac"],
                }
                self.records.append(rec)
                out.append(rec)
            self._unemitted = (
                len(out) + self._unemitted if self.sink is not None else 0
            )
            # final drain writes bubbles + summaries + any buffered tail
            if self.sink is not None:
                pending = self.records[
                    len(self.records) - self._unemitted:
                ]
                for rec in pending:
                    self.sink.log(**rec)
                self._unemitted = 0
        return out

    def snapshot(self) -> List[dict]:
        """A consistent copy of the record list — worker threads append
        concurrently under the lock, so live readers (the fleet metrics
        rollup) must not iterate ``records`` bare."""
        with self._lock:
            return list(self.records)

    def census_decls(self):
        from pytorch_distributed_tpu.telemetry.census import Decl

        return [
            Decl("records", "unbounded",
                 why="O(launches) profiling log by design — the ledger "
                     "is enabled only for bounded bench windows; soaks "
                     "run NULL_LEDGER and take per-tick wall from "
                     "hostprof.ResourceMonitor instead"),
            Decl("_streams", "unbounded",
                 why="per-replica launch stream mirroring ``records`` "
                     "(same bound, same bench-window-only lifetime)"),
        ]


#: Shared no-op ledger: call sites thread one
#: through unconditionally.
NULL_LEDGER = DispatchLedger(enabled=False)


# ---------------------------------------------------------------------------
# stream-side analysis: timelines, bubbles, summaries
# ---------------------------------------------------------------------------


def overlap_records(records: Iterable[dict],
                    ev: Optional[str] = None) -> List[dict]:
    return [
        r for r in records
        if r.get("kind") == "overlap" and (ev is None or r.get("ev") == ev)
    ]


def device_timeline(records: Iterable[dict],
                    replica: Optional[int] = None
                    ) -> Dict[int, List[dict]]:
    """Per-replica device timeline from launch records: each entry is
    the launch record plus ``start``/``end`` — the busy slice under the
    in-order-execution model (``end = max(done or t1, prev end)``,
    ``start = max(t0, prev end)``). Exact on a synchronous backend;
    a lower bound on busy under true async dispatch (module docstring).
    """
    launches = overlap_records(records, "launch")
    by_rep: Dict[int, List[dict]] = {}
    for r in launches:
        if replica is not None and r.get("replica") != replica:
            continue
        by_rep.setdefault(r.get("replica", 0), []).append(r)
    out: Dict[int, List[dict]] = {}
    for rep, recs in by_rep.items():
        recs.sort(key=lambda r: r.get("t0", 0.0))
        prev_end = None
        slices = []
        for r in recs:
            end = r.get("done", r.get("t1", 0.0))
            if prev_end is not None:
                end = max(end, prev_end)
            start = r.get("t0", 0.0)
            if prev_end is not None:
                start = max(start, prev_end)
            slices.append({**r, "start": start, "end": end})
            prev_end = end
        out[rep] = slices
    return out


def _overlap_s(a0: float, a1: float, b0: float, b1: float) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def classify_bubbles(records: Iterable[dict],
                     min_gap_s: float = 0.0) -> List[dict]:
    """Every inter-launch gap on every replica stream, attributed to
    its host cause (module docstring: other-replica busy slices first,
    then ledger host marks, then the span-stream seq join, else
    idle-no-work). Returns plain dicts (no ``kind``/``ev``) sorted by
    gap start; ``DispatchLedger.finalize`` wraps them into
    ``ev="bubble"`` records."""
    records = list(records)
    timelines = device_timeline(records)
    hosts = overlap_records(records, "host")
    spans = [r for r in records if r.get("kind") == "span"]
    window = _global_window(timelines)
    bubbles: List[dict] = []
    for rep, slices in timelines.items():
        others = [
            s for r, ss in timelines.items() if r != rep for s in ss
        ]
        # gaps between adjacent launches, PLUS the edge idle inside the
        # fleet-wide window: before this replica's first launch and
        # after its last (a drained decode replica idling out the run's
        # tail is real lost device time — edge gaps make busy + bubbles
        # tile the window exactly)
        gaps: List[Tuple[float, float, Optional[dict], Optional[dict]]] = []
        if window is not None and slices:
            if slices[0].get("t0", 0.0) > window[0]:
                gaps.append((window[0], slices[0]["t0"], None, slices[0]))
        for cur, nxt in zip(slices, slices[1:]):
            gaps.append((cur["end"], nxt.get("t0", cur["end"]), cur, nxt))
        if window is not None and slices:
            if window[1] > slices[-1]["end"]:
                gaps.append((slices[-1]["end"], window[1], slices[-1],
                             None))
        for g0, g1, cur, nxt in gaps:
            gap = g1 - g0
            if gap <= min_gap_s:
                continue
            causes: Dict[str, float] = {}
            for s in others:
                # the other replica's host-side dispatch wall occupying
                # the loop is SERIALIZATION (other-replica-tick); its
                # device execution beyond that wall is the shared
                # device working for someone else (shared-device-wait).
                # A sync launch's wall contains its execution, so
                # synchronous-loop attribution is unchanged; an async
                # launch's wall is thin and the split becomes visible.
                d = _overlap_s(g0, g1, s.get("t0", 0.0),
                               s.get("t1", 0.0))
                b = _overlap_s(g0, g1, s["start"], s["end"])
                both = max(
                    0.0,
                    min(g1, s.get("t1", 0.0), s["end"])
                    - max(g0, s.get("t0", 0.0), s["start"]),
                )
                if d > 0:
                    causes[CAUSE_OTHER_REPLICA] = (
                        causes.get(CAUSE_OTHER_REPLICA, 0.0) + d
                    )
                if b - both > 0:
                    causes[CAUSE_SHARED_DEVICE] = (
                        causes.get(CAUSE_SHARED_DEVICE, 0.0) + b - both
                    )
            for h in hosts:
                ov = _overlap_s(g0, g1, h.get("t0", 0.0), h.get("t1", 0.0))
                if ov <= 0:
                    continue
                h_rep = h.get("replica", -1)
                if h_rep not in (-1, rep) and not h.get("thread"):
                    # ANOTHER replica's host work on the shared loop:
                    # this gap exists because the loop was doing that
                    # replica's tick — the definition of
                    # other-replica-tick (worker-thread marks are
                    # overlapped work, not loop serialization, and keep
                    # their own @thread cause below)
                    causes[CAUSE_OTHER_REPLICA] = (
                        causes.get(CAUSE_OTHER_REPLICA, 0.0) + ov
                    )
                else:
                    name = h.get("name", "?")
                    # worker-thread marks (round 16) keep the thread
                    # name in the cause: "jsonl-emit@pdt-host-0" says
                    # the gap overlapped OFFLOADED host work — visible
                    # overlap, not idle-no-work, and distinguishable
                    # from the same work blocking the main loop
                    if h.get("thread"):
                        name = f"{name}@{h['thread']}"
                    causes[name] = causes.get(name, 0.0) + ov
            # apportioned shares (round 16): the winner-take-all cause
            # stays (back-compat; the "dominant cause" cell), but each
            # MEASURED candidate also gets its proportional seconds,
            # with the uncovered remainder booked as idle-no-work —
            # under the async loop a gap is typically a MIX (the other
            # replica's host work + this replica's own collect +
            # unmarked glue), and assigning the whole gap to whichever
            # candidate is largest overstated it (the r06 96% reading
            # was safe only because sync walls covered gaps entirely).
            shares: Optional[Dict[str, float]] = None
            if causes:
                cov = sum(causes.values())
                scale = min(1.0, gap / cov) if cov > 0 else 0.0
                shares = {c: round(v * scale, 9)
                          for c, v in causes.items()}
                rem = gap - sum(shares.values())
                if rem > 1e-12:
                    shares[CAUSE_IDLE] = round(
                        shares.get(CAUSE_IDLE, 0.0) + rem, 9
                    )
            if not causes:
                # the PR 12 join: span records whose logical-clock seq
                # falls inside the gap's window tell what the host loop
                # was doing even where no ledger mark ran (pseudo
                # weights — winner only, no shares: a span is an
                # ordering witness, not a measured duration)
                s0 = cur.get("seq1") if cur is not None else None
                s1 = nxt.get("seq0") if nxt is not None else None
                if s0 is not None and s1 is not None:
                    for sp in spans:
                        if s0 < sp.get("seq", -1) < s1:
                            cause = _SPAN_CAUSES.get(sp.get("name", ""))
                            if cause:
                                causes[cause] = causes.get(cause, 0.0) + 1e-9
            cause = (
                max(causes.items(), key=lambda kv: kv[1])[0]
                if causes else CAUSE_IDLE
            )
            rec = {
                "replica": rep, "cause": cause,
                "gap_s": round(gap, 9), "t0": g0, "t1": g1,
                "after": cur.get("program") if cur is not None else None,
                "before": nxt.get("program") if nxt is not None else None,
                "seq0": cur.get("seq1") if cur is not None else None,
                "seq1": nxt.get("seq0") if nxt is not None else None,
            }
            if shares is not None:
                rec["shares"] = shares
            bubbles.append(rec)
    bubbles.sort(key=lambda b: b["t0"])
    return bubbles


def _global_window(timelines: Dict[int, List[dict]]
                   ) -> Optional[Tuple[float, float]]:
    """The fleet-wide measurement window: first dispatch start to last
    completion across every replica stream."""
    starts = [s[0].get("t0", s[0]["start"]) for s in timelines.values()
              if s]
    ends = [s[-1]["end"] for s in timelines.values() if s]
    if not starts:
        return None
    return min(starts), max(ends)


def busy_summary(records: Iterable[dict]) -> Dict[int, dict]:
    """Per-replica rollup: launches, busy seconds, the replica stream's
    own span, the fleet-wide window, and the busy fraction (busy /
    WINDOW — a replica that drained early and idled out the run's tail
    is idle for it, which is what makes fractions comparable across
    replicas). ``busy + Σ bubbles == window`` per replica by
    construction, so the bubbles tile the idle time exactly."""
    out: Dict[int, dict] = {}
    timelines = device_timeline(records)
    window = _global_window(timelines)
    for rep, slices in timelines.items():
        if not slices:
            continue
        busy = sum(s["end"] - s["start"] for s in slices)
        span = slices[-1]["end"] - slices[0]["start"]
        w = (window[1] - window[0]) if window is not None else span
        out[rep] = {
            "launches": len(slices),
            "busy_s": round(busy, 9),
            "span_s": round(span, 9),
            "window_s": round(w, 9),
            "busy_frac": round(busy / w, 6) if w > 0 else 1.0,
        }
    return out


def fleet_busy_summary(records: Iterable[dict]) -> dict:
    """Shared-device-honest fleet rollup: the interval UNION of every
    replica's busy slices over the fleet-wide window, next to the
    per-replica fractions. On a host where N replicas share one device
    (the CPU simulation — and any oversubscribed placement), a launch's
    dispatch→completion window includes time spent queued behind the
    other replica's program, so per-replica "busy" slices overlap and
    their SUM double-counts the device. The union is true device
    utilization; the per-replica fractions are per-stream scheduling
    health. The ``gather_ab_backend`` pattern: report both, marked.

    Returns ``{"replicas": {rep: busy_frac}, "union_busy_s",
    "window_s", "union_busy_frac"}`` (zeros when no launches)."""
    records = list(records)
    timelines = device_timeline(records)
    window = _global_window(timelines)
    per = {rep: s["busy_frac"] for rep, s in busy_summary(records).items()}
    if window is None:
        return {"replicas": per, "union_busy_s": 0.0, "window_s": 0.0,
                "union_busy_frac": 0.0}
    intervals = sorted(
        (s["start"], s["end"])
        for slices in timelines.values() for s in slices
        if s["end"] > s["start"]
    )
    merged: List[List[float]] = []
    for a, b in intervals:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    union = sum(b - a for a, b in merged)
    w = window[1] - window[0]
    return {
        "replicas": per,
        "union_busy_s": round(union, 9),
        "window_s": round(w, 9),
        "union_busy_frac": round(union / w, 6) if w > 0 else 0.0,
    }


def busy_within(records: Iterable[dict], replica: int,
                t0: float, t1: float) -> Tuple[float, float]:
    """``(busy_s, bubble_s)`` of ``replica``'s device inside the wall
    window ``[t0, t1]`` — the per-decode-window split
    ``scripts/explain_request.py`` annotates request phases with."""
    if t1 <= t0:
        return 0.0, 0.0
    slices = device_timeline(records, replica).get(replica, [])
    busy = sum(_overlap_s(t0, t1, s["start"], s["end"]) for s in slices)
    busy = min(busy, t1 - t0)
    return busy, (t1 - t0) - busy


def cause_histogram(records: Iterable[dict]) -> Dict[str, dict]:
    """``{cause: {count, gap_s}}`` from ``ev="bubble"`` records (the
    report's histogram; recompute with ``classify_bubbles`` when a
    stream carries launches but no finalize ran). Bubbles carrying
    apportioned ``shares`` (round 16) contribute their measured
    per-cause seconds; legacy/span-joined bubbles contribute their
    whole gap to the winning cause. ``count`` counts bubbles a cause
    appeared in, either way."""
    hist: Dict[str, dict] = {}
    bubbles = overlap_records(records, "bubble")
    if not bubbles:
        bubbles = classify_bubbles(records)
    for b in bubbles:
        shares = b.get("shares")
        if isinstance(shares, dict) and shares:
            for cause, sec in shares.items():
                h = hist.setdefault(cause, {"count": 0, "gap_s": 0.0})
                h["count"] += 1
                h["gap_s"] += sec
        else:
            h = hist.setdefault(b.get("cause", "?"),
                                {"count": 0, "gap_s": 0.0})
            h["count"] += 1
            h["gap_s"] += b.get("gap_s", 0.0)
    for h in hist.values():
        h["gap_s"] = round(h["gap_s"], 9)
    return hist
