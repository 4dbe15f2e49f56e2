"""The replica router: N schedulers behind one front-end, one host loop.

``FleetRouter`` owns ``n_replicas`` ``Scheduler`` + ``PagedEngine``
replicas — single-process, each committed to its own device slice of
``jax.devices()`` (round-robin; on a one-device host they share it and
the router degrades to a pure scheduling simulation, which is exactly
the CPU-backend proof ROADMAP item 3 prescribes — multi-process
collectives are a known jaxlib CPU gap). Requests enter through
``submit`` with an optional session id:

- **session affinity**: a session's first request pins it to the
  replica the SLO gate picks; later requests follow — and with
  ``prefix_cache=True`` replicas (round 17) this IS the prefix-cache
  key: a session lands where its shared prefix is resident in the
  replica-local radix index, so the lookup hits without any cross-
  replica index. The table is LRU-bounded (``affinity_cap``; evictions
  counted) and the gate's ``prefix_sticky_depth`` rung keeps sessions
  on a merely-busy affinity replica a few requests longer before a
  spill trades their prefix locality for latency;
- **SLO-aware admission** (``fleet.admission.SLOGate``): admit / spill /
  queue / shed against the live per-replica TTFT/queue-wait percentiles
  and queue depths; sheds are explicit per-request JSONL records with
  ``rejected: true`` and a reason;
- **one host loop, one tick in flight**: ``step()`` runs every replica
  once — decode replicas first, then prefill/mixed replicas, then the
  handoff pump — and for each it COLLECTS the tick launched by the
  previous step, then LAUNCHES the next (JAX async dispatch: nothing
  materializes) and returns with it in flight. So ``step()`` N returns
  tick N−1's tokens (the first returns none; ``drain()``/``idle`` wait
  for the last), and the device works through ``submit`` and whatever
  the caller does between two steps, and through the other replicas'
  host work. Per-request host work (JSONL, the gate's percentile math)
  rides a small ``HostWorkerPool`` whose threads end with the router.
  Per replica, collect(N−1) → dispatch(N) is the order in which a
  lone ``Scheduler.step()`` launches and collects its ticks, so greedy
  token streams are bit-identical to a lone scheduler's: that is what
  the parity tests hold the router to.

Disaggregated prefill/decode (``disaggregate=True``): the first
``n_prefill`` replicas run ``prefill_only`` schedulers — chunk programs
only, requests parked in ``ready`` when their prompt is in the pool —
and the rest run decode. The handoff pump moves each ready request's KV
blocks into the least-loaded decode replica
(``PagedEngine.export_chain`` → ``import_chain``: an explicit
``jax.device_put`` block transfer plus a block-table remap in the
target pool), after which the request decodes exactly as if it had
prefilled there — token-identical greedy streams, proven in
tests/test_fleet.py. Decode token gaps stop paying for prefill bursts:
a mixed replica's decode tick is data-dependent on the chunk program
that precedes it in the same step (shared pool, same device), while a
decode replica's tick depends only on its own pool.

Replica geometry (config, slots, block_len, chunk) is uniform across
the fleet — the handoff requires pool-compatible blocks, and uniform
replicas keep the registry story simple: ``registries()`` builds one
``compilecache.serving_registry`` per replica (per-mesh/per-device) and
``assert_registry_covers()`` runs the coverage guard across all of
them.

Failure plane (round 19; ANALYSIS.md "Failure model & recovery
guarantees"): every replica carries a health state machine —
``healthy → suspect → dead → draining → rejoining`` — driven by
exceptions escaping ``dispatch_tick``/``collect_tick``/the handoff
trio and by the serve-side watchdog's tick deadline
(``resilience.watchdog.FleetWatchdog``; a tick that overruns
``tick_deadline_s`` condemns its replica exactly like a crash — a
wedged device loop and a dead process are indistinguishable from the
control plane). A condemned replica is **drained of identity**: its
in-flight requests are harvested from their ``Request`` records
(``Scheduler.harvest_requests``), its device state torn down leak-free
(``Scheduler.abandon``; blocksan-verified), its affinity entries
invalidated, and the harvested requests re-dispatched to surviving
replicas with bounded deterministic backoff
(``resilience.retry.backoff_delays``) — each replay re-submits the
original prompt plus every token the router already DELIVERED, so the
prefix cache absorbs the replay cost and greedy client streams stay
append-consistent (token-identical to a fault-free run). An attempt
cap sheds the request with ``outcome="failed"`` instead of retrying
forever; a request whose deadline lapses anywhere in this machinery
expires with ``outcome="deadline"``. ``revive(i)`` re-admits a fresh
replica at a dead slot behind compile-cache warmup — survivors never
recompile (registry-fingerprint proof) and no request drops during
the rejoin.
"""

from __future__ import annotations

import logging
import time
import weakref
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from pytorch_distributed_tpu.fleet.admission import (
    ADMIT,
    PREEMPT,
    SHED,
    SPILL,
    Decision,
    SLOConfig,
    SLOGate,
    recommend_replicas,
    trace_decision,
)
from pytorch_distributed_tpu.resilience.retry import backoff_delays
from pytorch_distributed_tpu.resilience.watchdog import FleetWatchdog
from pytorch_distributed_tpu.serving.scheduler import Scheduler
from pytorch_distributed_tpu.telemetry import (
    LatencySeries,
    percentiles,
    spans,
)

logger = logging.getLogger("pytorch_distributed_tpu")

#: the replica health state machine (round 19). ``draining`` is the
#: instant between condemnation and the end of harvest+abandon —
#: observable in the ``kind="health"`` JSONL even though the one-loop
#: simulation passes through it synchronously; ``rejoining`` is a
#: revived replica warming its compile cache before taking traffic.
HEALTH_STATES = ("healthy", "suspect", "dead", "draining", "rejoining")

#: health states the router routes traffic to (suspect replicas keep
#: serving — one failed tick is a warning, not a death sentence)
_ROUTABLE = ("healthy", "suspect")


class FleetRouter:
    """Front-end over N single-process replicas.

    ``submit(prompt, max_new, session=...)`` routes (or sheds) one
    request and returns its fleet-wide rid; ``step()`` advances every
    replica one tick and returns ``[(rid, token)]``; ``drain()`` runs
    the fleet to empty. ``metrics()`` aggregates fleet percentiles,
    shed/spill rates, per-replica summaries, and the autoscaler's
    current recommendation.
    """

    def __init__(self, config, params, n_replicas: int = 2, *,
                 disaggregate: bool = False, n_prefill: int = 1,
                 decode_slots: Optional[int] = None,
                 handoffs_per_tick: Optional[int] = None,
                 slo: Optional[SLOConfig] = None, devices=None,
                 seed: int = 0, metrics_log=None,
                 flightrec=None, reqtrace=None, host_threads: int = 2,
                 affinity_cap: int = 4096,
                 fail_threshold: int = 2,
                 tick_deadline_s: Optional[float] = None,
                 redispatch_max_attempts: int = 3,
                 redispatch_base_delay_s: float = 0.05,
                 retain_results: bool = True,
                 **scheduler_kwargs):
        import jax

        from pytorch_distributed_tpu.serving.host_worker import (
            HostWorkerPool,
        )
        from pytorch_distributed_tpu.telemetry import (
            NULL_RECORDER,
            NULL_REQTRACER,
        )

        if n_replicas < 1:
            raise ValueError(f"n_replicas must be >= 1, got {n_replicas}")
        if disaggregate:
            if n_replicas < 2:
                raise ValueError("disaggregation needs >= 2 replicas")
            if not 1 <= n_prefill < n_replicas:
                raise ValueError(
                    f"n_prefill must be in [1, {n_replicas - 1}], "
                    f"got {n_prefill}"
                )
        if devices is None:
            devices = jax.devices()
        self.gate = SLOGate(slo)
        self.metrics_log = metrics_log
        # fleet forensics (ISSUE 8): routing decisions — sheds, spills,
        # handoffs — land in the shared flight-recorder ring, so a
        # post-mortem dump shows WHY requests went where before death
        self.flightrec = flightrec if flightrec is not None else NULL_RECORDER
        # request-lifecycle tracing (round 14): ONE shared ReqTracer
        # across every replica, so a request's spans stay one tree as it
        # crosses the admission gate, the prefill replica, the handoff,
        # and the decode replica
        self.reqtrace = reqtrace if reqtrace is not None else NULL_REQTRACER
        # the host loop: collect tick N-1, dispatch tick N, return with
        # N in flight, and ONE worker pool shared by every replica for
        # the host work off the critical path (JSONL emission, the
        # gate's percentile math)
        self.host_pool = HostWorkerPool(n_threads=host_threads)
        # the workers end with the router: nothing calls close(), and
        # a parked thread outlives whoever built the router
        weakref.finalize(self, self.host_pool.stop)
        # block-lifecycle sanitizer (analysis.blocksan; PDT_BLOCKSAN=1):
        # ONE sanitizer shared by every replica, so handoff pins and
        # violations aggregate fleet-wide and one assert_clean() covers
        # the whole pool population. None (the default) end to end.
        from pytorch_distributed_tpu.analysis.blocksan import maybe_sanitizer

        self.blocksan = maybe_sanitizer(metrics_log=metrics_log)
        # construction inputs are retained so ``revive()`` can rebuild a
        # dead replica slot from scratch with identical geometry — the
        # handoff and the registry fingerprint both require it
        self._config = config
        self._params = params
        self._devices = devices
        self._seed = seed
        self._disaggregate = disaggregate
        self._n_prefill = n_prefill
        self._decode_slots = decode_slots
        self._scheduler_kwargs = scheduler_kwargs
        self.replicas: List[Scheduler] = []
        self.roles: List[str] = []
        # everything else in this constructor is assignments: the build
        # is the replicas' (weights placed, pools allocated)
        with spans.tracer().span("router.build", replicas=n_replicas):
            for i in range(n_replicas):
                role = (
                    ("prefill" if i < n_prefill else "decode")
                    if disaggregate else "mixed"
                )
                self.roles.append(role)
                self.replicas.append(self._make_replica(i))
        self.disaggregated = disaggregate
        #: max KV handoffs per tick (None = unbounded). The handoff's
        #: host-driven gather/put/scatter runs between decode ticks in
        #: the one-loop simulation; budgeting it bounds how much a
        #: prefill burst can stretch resident streams' token gaps —
        #: trading a little TTFT for decode p95, same as a transfer-
        #: bandwidth cap would on real interconnect
        self.handoffs_per_tick = handoffs_per_tick
        #: replicas requests enter through (mixed, or prefill in disagg)
        self.entry_group = [
            i for i, r in enumerate(self.roles) if r != "decode"
        ]
        self.decode_group = [
            i for i, r in enumerate(self.roles) if r == "decode"
        ]
        self._next_rid = 0
        # session -> replica, LRU-bounded (round 17 fix: this mapping
        # grew one entry per session forever — a fleet fed from a
        # 100k-session trace leaked the table. An OrderedDict capped at
        # ``affinity_cap`` evicts the least-recently-ROUTED session;
        # an evicted session that returns simply re-pins wherever the
        # gate sends it, exactly like a new session. The cap also
        # bounds the prefix-locality loss: a session idle long enough
        # to fall off the affinity table has usually had its index
        # blocks LRU-evicted too.)
        if affinity_cap < 1:
            raise ValueError(f"affinity_cap must be >= 1, got {affinity_cap}")
        self.affinity_cap = affinity_cap
        self._affinity: "OrderedDict[int, int]" = OrderedDict()
        self._affinity_evictions = 0
        self.placement: Dict[int, int] = {}  # rid -> current replica
        self.rejected: Dict[int, str] = {}  # rid -> shed reason
        self.results: Dict[int, List[int]] = {}
        # round 21 (scale observatory): retention mode. The default
        # keeps every rid's token list forever — ``drain()`` returns
        # the full results dict, the redispatch replay reads it as the
        # authoritative delivered stream, and benches assert equality
        # on it; all O(sessions ever). ``retain_results=False`` is the
        # soak/streaming mode: callers consume ``step()``'s (rid, tok)
        # pairs live, and the router drops a rid's results/placement
        # entries once it retires — host state stays O(live requests).
        # Trade-off: a replica death then re-delivers the tokens the
        # retired-entry replay would have skipped, so streaming mode is
        # for fault-free soaks and dedup-capable consumers. ``rejected``
        # and ``failed`` keep only the most recent ``_REJECT_CAP``
        # entries in this mode (counters stay exact).
        self.retain_results = bool(retain_results)
        self._retired_pending: List[int] = []
        # round 22 (HTTP front door): optional FLEET-level retire hook,
        # ``on_retire(rid, outcome)`` — one call per terminal transition
        # (complete / cancelled / deadline / failed), fired on the host-
        # loop thread from every terminal path: scheduler retire, failed
        # re-dispatch, router-side deadline expiry, redispatch-noop. The
        # gateway uses it to close SSE streams with the true outcome.
        # It fires mid-collect, BEFORE the final token lands in
        # ``results`` — consumers must drain queued tokens first.
        self.on_retire: Optional[Callable[[int, str], None]] = None
        self._results_dropped = 0
        self._spilled = 0
        self._preempt_routes = 0
        self._handoff_count = 0
        self.handoff_lat = LatencySeries("handoff")
        self._start_time: Optional[float] = None
        self._tick = 0
        # the autoscaler signal is only meaningful UNDER load — a
        # drained fleet always says "hold" — so the router samples the
        # recommendation as it runs and keeps the high-water mark
        self._recommend_peak = len(self.entry_group)
        # ---- failure plane (round 19) ----
        if fail_threshold < 1:
            raise ValueError(
                f"fail_threshold must be >= 1, got {fail_threshold}"
            )
        if redispatch_max_attempts < 1:
            raise ValueError(
                "redispatch_max_attempts must be >= 1, "
                f"got {redispatch_max_attempts}"
            )
        #: consecutive failed ticks before suspect escalates to dead —
        #: one transient exception marks the replica suspect and is
        #: forgiven by the next clean tick; ``fail_threshold`` in a row
        #: condemns it
        self.fail_threshold = fail_threshold
        #: wall-clock budget for one replica tick; a tick that overruns
        #: it condemns the replica immediately (a wedged device loop has
        #: no exception to catch — the deadline IS its failure signal).
        #: None disables hang detection.
        self.tick_deadline_s = tick_deadline_s
        self.redispatch_max_attempts = redispatch_max_attempts
        self.redispatch_base_delay_s = redispatch_base_delay_s
        #: per-replica health records (the state machine lives here, not
        #: on the Scheduler: a dead replica's scheduler object is torn
        #: down and replaced, but its health history must survive)
        self.health: List[dict] = [
            {"state": "healthy", "consecutive": 0, "failures": 0,
             "last_error": None, "since_tick": 0,
             "redispatched_away": 0, "deaths": 0}
            for _ in range(n_replicas)
        ]
        #: rid -> immutable origin facts captured at FIRST death:
        #: the true original prompt (tokens[:orig_len] before any
        #: replay widened it), budget, session, absolute deadline, and
        #: the attempt counter. Replays after later deaths rebuild from
        #: here + the delivered-token record, never from the dying
        #: scheduler's view.
        self._origin: Dict[int, dict] = {}
        #: harvested requests awaiting re-dispatch: each entry
        #: {rid, not_before, src} — not_before is the deterministic
        #: backoff instant (resilience.retry.backoff_delays, seeded by
        #: rid so the chaos matrix replays bit-identically)
        self._pending_redispatch: List[dict] = []
        #: rid -> reason, requests shed AFTER admission: the re-dispatch
        #: attempt cap was exhausted. Disjoint from ``rejected`` (never
        #: admitted) — a failed rid may have streamed partial tokens.
        self.failed: Dict[int, str] = {}
        # monotonic twins of ``len(rejected)``/``len(failed)``: the
        # streaming-mode trim drops old REASONS, so the headline shed/
        # failed counts must not be derived from table length (round 21
        # fix — metrics() undercounted past _REJECT_CAP sheds)
        self._shed_total = 0
        self._failed_total = 0
        self._redispatched = 0
        self._deadline_expired_redispatch = 0
        self._deadline_sheds = 0
        self._ticking: Optional[int] = None
        # serve-side watchdog: one heartbeat per replica, beaten at the
        # top of each tick. The one-loop simulation can only wedge
        # inside the CURRENTLY ticking replica, so the stall handler
        # ignores every other (merely aging) heartbeat; the thread is
        # the live-stall observer, while step() itself re-checks the
        # tick wall clock after the fact so hang condemnation is
        # deterministic under test (no thread timing in the loop).
        self.watchdog: Optional[FleetWatchdog] = None
        if tick_deadline_s is not None:
            self.watchdog = FleetWatchdog(
                tick_deadline_s, on_stall=self._on_stall,
                flightrec=self.flightrec,
            )
            for i in range(n_replicas):
                self.watchdog.watch(f"replica{i}")

    def _make_replica(self, i: int) -> Scheduler:
        """Build replica ``i``'s Scheduler from the retained
        construction inputs — used by ``__init__`` and by ``revive()``
        (a revived slot gets a FRESH scheduler/engine/pool with the
        same geometry, device placement, and seed as the dead one, so
        the registry fingerprint and greedy streams are unchanged)."""
        role = self.roles[i]
        # one device per replica, round-robin over the host's slice
        # of jax.devices(); on a single-device host all replicas
        # share it (placement left implicit — bit-identical to a
        # plain Scheduler)
        dev = (
            self._devices[i % len(self._devices)]
            if len(self._devices) > 1 else None
        )
        # disaggregation sizes roles independently (the DistServe
        # argument): a request holds a prefill slot for
        # ceil(prompt/chunk) ticks but a decode slot for max_new
        # ticks, so decode replicas usually want MORE lanes — pool
        # block geometry stays uniform (the handoff requires it),
        # only the lane count differs
        kw = dict(self._scheduler_kwargs)
        if role == "decode" and self._decode_slots is not None:
            kw["n_slots"] = self._decode_slots
        with spans.tracer().span("sched.build", replica=i):
            s = Scheduler(
                self._config, self._params, replica_id=i,
                seed=self._seed + i, prefill_only=(role == "prefill"),
                device=dev, handoff=self._disaggregate,
                metrics_log=self.metrics_log,
                flightrec=self.flightrec, reqtrace=self.reqtrace,
                host_pool=self.host_pool,
                blocksan=self.blocksan, **kw,
            )
        s.on_retire = self._note_retire
        return s

    # ---- health plane ----

    def _set_health(self, i: int, state: str, reason: str) -> None:
        rec = self.health[i]
        prev = rec["state"]
        if state == prev:
            return
        rec["state"] = state
        rec["since_tick"] = self._tick
        logger.info(
            "fleet health: replica %d %s -> %s (%s)", i, prev, state,
            reason,
        )
        self.flightrec.record(
            "health", replica=i, state=state, prev=prev, reason=reason
        )
        if self.metrics_log is not None:
            self.metrics_log.log(
                kind="health", replica_id=i, state=state, prev=prev,
                reason=reason, tick=self._tick,
            )

    def _alive(self, group: List[int]) -> List[int]:
        """Members of ``group`` the router still routes to. Suspect
        replicas stay routable (their next clean tick clears them);
        dead, draining, and rejoining ones do not."""
        return [i for i in group if self.health[i]["state"] in _ROUTABLE]

    def _on_stall(self, name: str, stalled_s: float, dump: str) -> None:
        # live-stall observer (watchdog thread): only the CURRENTLY
        # ticking replica can genuinely wedge in the one-loop
        # simulation — every other heartbeat merely ages while it runs.
        # The handler just records; condemnation happens in _run_tick's
        # deterministic wall-clock re-check so tests never race the
        # poller thread.
        ticking = self._ticking
        if ticking is None or name != f"replica{ticking}":
            return
        logger.error(
            "fleet watchdog: replica %d tick stalled %.3fs "
            "(deadline %.3fs)", ticking, stalled_s, self.tick_deadline_s,
        )

    def _note_success(self, i: int) -> None:
        rec = self.health[i]
        rec["consecutive"] = 0
        if rec["state"] == "suspect":
            self._set_health(i, "healthy", "tick-recovered")

    # ---- retention plane (round 21) ----

    #: most-recent shed/failed entries kept in streaming-retention mode
    _REJECT_CAP = 1024

    def _note_retire(self, rid: int, outcome: str) -> None:
        """Scheduler retire hook (complete/cancel/deadline). Cleanup is
        deferred to ``_drop_retired`` at the END of the step: the
        retirement fires mid-collect, and the router appends the final
        token to ``results`` after collect returns — popping here would
        resurrect a one-token entry per retired rid."""
        # a completed rid can never be harvested again; its re-dispatch
        # origin facts are dead weight in EVERY retention mode (real
        # leak: one entry per redispatched-then-completed rid, forever)
        self._origin.pop(rid, None)
        if not self.retain_results:
            self._retired_pending.append(rid)
        if self.on_retire is not None:
            self.on_retire(rid, outcome)

    def _drop_retired(self) -> None:
        if self.retain_results or not self._retired_pending:
            return
        for rid in self._retired_pending:
            if self.results.pop(rid, None) is not None:
                self._results_dropped += 1
            self.placement.pop(rid, None)
        self._retired_pending.clear()

    def _trim_rejects(self) -> None:
        """Streaming mode: ``rejected``/``failed`` keep reasons for
        recent rids only (counters remain exact)."""
        if self.retain_results:
            return
        for table in (self.rejected, self.failed):
            while len(table) > self._REJECT_CAP:
                table.pop(next(iter(table)))

    def live_requests(self) -> int:
        """Fleet-wide in-flight request count: every replica's queued +
        resident + parked + mid-swap population, plus harvested rids
        awaiting re-dispatch — the census sweep's O(live) audit axis."""
        return (sum(s.live_requests() for s in self.replicas)
                + len(self._pending_redispatch))

    def census_decls(self):
        """Round 21 scale observatory: every long-lived container on
        the router declares its bound (telemetry/census.py). The
        rid-keyed tables are the interesting ones — unbounded by design
        under the default drain() contract, proven O(live) in
        streaming-retention mode."""
        from pytorch_distributed_tpu.telemetry.census import Decl

        def _retention(kind_live):
            return lambda r: kind_live if not r.retain_results \
                else "unbounded"

        return [
            Decl("replicas", "replicas", cap=lambda r: len(r.health),
                 why="one Scheduler per replica slot"),
            Decl("roles", "replicas", cap=lambda r: len(r.health),
                 why="role string per replica slot"),
            Decl("entry_group", "replicas", cap=lambda r: len(r.health),
                 why="subset of replica indices"),
            Decl("decode_group", "replicas", cap=lambda r: len(r.health),
                 why="subset of replica indices"),
            Decl("health", "replicas", cap=lambda r: len(r.health),
                 why="health record per replica slot, survives revive"),
            Decl("_affinity", "fixed", cap=lambda r: r.affinity_cap,
                 why="session→replica LRU, capped since round 17 (the "
                     "round-21 census proves the cap holds under soak)"),
            Decl("placement", _retention("live"),
                 why="rid→replica for in-flight rids; streaming mode "
                     "drops entries at retire, default mode keeps them "
                     "for the drain()/replay contract"),
            Decl("results", _retention("live"), per_live=1,
                 why="delivered-token record; the redispatch replay's "
                     "authoritative stream in default mode, dropped at "
                     "retire in streaming mode"),
            Decl("rejected",
                 lambda r: "unbounded" if r.retain_results else "fixed",
                 cap=lambda r: None if r.retain_results
                 else r._REJECT_CAP + 64,
                 why="shed reasons; streaming mode keeps the most "
                     "recent _REJECT_CAP (sheds counter stays exact)"),
            Decl("failed",
                 lambda r: "unbounded" if r.retain_results else "fixed",
                 cap=lambda r: None if r.retain_results
                 else r._REJECT_CAP + 64,
                 why="redispatch-exhausted reasons; bounded like "
                     "rejected in streaming mode"),
            Decl("_origin", "live",
                 why="origin facts for harvested rids only; popped on "
                     "shed/expire AND on retire (round 21 fix — "
                     "previously leaked one entry per "
                     "redispatched-then-completed rid)"),
            Decl("_pending_redispatch", "live",
                 why="harvested rids waiting out backoff"),
            Decl("_retired_pending", "fixed", cap=lambda r: 16384,
                 why="retired rids queued for end-of-step cleanup; "
                     "drained every step() / _drop_retired call"),
            Decl("_devices", "fixed", cap=lambda r: len(r._devices) or 1,
                 why="jax.devices() snapshot taken at construction"),
            Decl("_scheduler_kwargs", "fixed", cap=64,
                 why="constructor kwargs retained for revive()"),
            Decl("_params", "fixed", cap=None,
                 why="model parameter pytree shared by every replica; "
                     "immutable after construction (no bound to audit, "
                     "declared so the undeclared sweep knows it was "
                     "considered)"),
            Decl("handoff_lat.values", "fixed",
                 cap=lambda r: 2 * r.handoff_lat.window,
                 why="LatencySeries percentile window (round 21 cap)"),
        ]

    def census_owners(self):
        """The swept (name, object) set for ``StructCensus.register_many``
        — the router, each replica scheduler with its allocator/prefix
        index/host store/sentinel, and the shared telemetry objects."""
        owners = [("router", self)]
        for i, s in enumerate(self.replicas):
            owners.append((f"sched{i}", s))
            owners.append((f"alloc{i}", s.engine.allocator))
            if s.engine.prefix is not None:
                owners.append((f"prefix{i}", s.engine.prefix))
            owners.append((f"host_store{i}", s.host_store))
            if s.sentinel is not None:
                owners.append((f"sentinel{i}", s.sentinel))
            owners.append((f"prog_times{i}", s.prog_times))
        if self.reqtrace.enabled:
            owners.append(("reqtrace", self.reqtrace))
        if self.flightrec.enabled:
            owners.append(("flightrec", self.flightrec))
        return owners

    def _note_failure(self, i: int, exc: BaseException,
                      site: str = "tick") -> None:
        """One failed tick (or handoff touch): suspect on the first,
        condemned at ``fail_threshold`` consecutive."""
        rec = self.health[i]
        if rec["state"] in ("dead", "draining"):
            return
        rec["consecutive"] += 1
        rec["failures"] += 1
        rec["last_error"] = f"{type(exc).__name__}: {exc}"
        logger.warning(
            "fleet health: replica %d %s failure %d/%d: %s", i, site,
            rec["consecutive"], self.fail_threshold, rec["last_error"],
        )
        if rec["consecutive"] >= self.fail_threshold:
            self._condemn(i, f"{site}-failures:{rec['consecutive']}")
        else:
            self._set_health(i, "suspect", rec["last_error"])

    def _condemn(self, i: int, reason: str) -> None:
        """Declare replica ``i`` dead: harvest every in-flight request
        from its ``Request`` records, tear its device state down
        leak-free (``Scheduler.abandon``; the dead replica may lose
        tokens, never blocks), invalidate its affinity entries, and
        queue the survivors' replays with deterministic backoff."""
        rec = self.health[i]
        if rec["state"] in ("dead", "draining"):
            return
        self._set_health(i, "draining", reason)
        s = self.replicas[i]
        harvested = s.harvest_requests()
        s.abandon()
        now = time.perf_counter()
        for req in harvested:
            rid = req.rid
            if rid not in self._origin:
                # captured exactly ONCE, at FIRST death: here
                # tokens[:orig_len] IS the true original prompt. After
                # a re-dispatch the request's tokens already embed
                # previously delivered output, so a second capture
                # would double-count it in the next replay.
                self._origin[rid] = {
                    "prompt": np.asarray(
                        req.tokens[:req.orig_len], dtype=np.int32
                    ).copy(),
                    "max_new": req.max_new_tokens,
                    "session": req.session,
                    "deadline": req.deadline,
                    "attempts": 0,
                }
            origin = self._origin[rid]
            self.placement.pop(rid, None)
            if req.deadline <= now:
                self._expire_request(rid, "replica-death")
                continue
            origin["attempts"] += 1
            rec["redispatched_away"] += 1
            if origin["attempts"] > self.redispatch_max_attempts:
                self._fail_request(
                    rid,
                    f"redispatch-attempts-exhausted:"
                    f"{self.redispatch_max_attempts}",
                )
                continue
            # deterministic bounded backoff: the rid seeds the jitter so
            # a chaos-matrix replay re-derives the same delays, and the
            # attempt index walks the exponential schedule
            delays = backoff_delays(
                retries=self.redispatch_max_attempts,
                base_delay=self.redispatch_base_delay_s, seed=rid,
            )
            delay = delays[min(origin["attempts"] - 1, len(delays) - 1)]
            self._pending_redispatch.append(
                {"rid": rid, "not_before": now + delay, "src": i}
            )
            if self.reqtrace.enabled:
                self.reqtrace.event(
                    rid, "redispatch_queued", src=i,
                    attempt=origin["attempts"],
                    delay_s=round(delay, 6),
                )
        # affinity entries pinned to the dead replica are invalid — a
        # returning session re-pins wherever the gate sends it (its
        # prefix blocks died with the pool anyway)
        for sess in [s_ for s_, r in self._affinity.items() if r == i]:
            del self._affinity[sess]
        if self.watchdog is not None:
            self.watchdog.unwatch(f"replica{i}")
        rec["deaths"] += 1
        rec["consecutive"] = 0
        self._set_health(i, "dead", reason)

    def _fail_request(self, rid: int, reason: str) -> None:
        """Attempt cap exhausted: shed ``rid`` with outcome=failed —
        the post-admission twin of the gate's shed (the client may have
        seen partial tokens; the stream simply never completes)."""
        self.failed[rid] = reason
        self._failed_total += 1
        self._trim_rejects()
        self._origin.pop(rid, None)
        if not self.retain_results:
            self._retired_pending.append(rid)
        self.flightrec.record("request_failed", rid=rid, reason=reason)
        if self.reqtrace.enabled:
            root = self.reqtrace.open_root(rid)
            self.reqtrace.end(root, outcome="failed", reason=reason)
        if self.metrics_log is not None:
            self.metrics_log.log(
                kind="request", rid=rid, replica_id=-1, rejected=True,
                reject_reason=reason, outcome="failed",
                new_tokens=len(self.results.get(rid, ())),
            )
        if self.on_retire is not None:
            self.on_retire(rid, "failed")

    def _expire_request(self, rid: int, where: str) -> None:
        """Deadline lapsed while the request sat in the router's own
        machinery (harvested, or waiting out backoff) — the router is
        an enforcement point just like the scheduler tick."""
        self._deadline_expired_redispatch += 1
        self._origin.pop(rid, None)
        if not self.retain_results:
            self._retired_pending.append(rid)
        self.flightrec.record("deadline", rid=rid, where=where)
        if self.reqtrace.enabled:
            root = self.reqtrace.open_root(rid)
            self.reqtrace.end(
                root, outcome="deadline", reason=f"expired-{where}"
            )
        if self.metrics_log is not None:
            self.metrics_log.log(
                kind="request", rid=rid, replica_id=-1, rejected=True,
                reject_reason=f"deadline-expired-{where}",
                outcome="deadline",
                new_tokens=len(self.results.get(rid, ())),
            )
        if self.on_retire is not None:
            self.on_retire(rid, "deadline")

    def _pump_redispatch(self) -> None:
        """Re-submit harvested requests to surviving entry replicas.
        The replay prompt is the ORIGINAL prompt plus every token the
        router already DELIVERED for the rid (``self.results`` is the
        authoritative client-visible stream — produced-but-uncollected
        tokens died with the replica and are regenerated), so the
        surviving stream stays append-consistent and the prefix cache
        absorbs most of the replay's prefill. Re-admission bypasses the
        SLO gate: the request was already admitted once — replica loss
        must not demote it to a sheddable newcomer."""
        if not self._pending_redispatch:
            return
        now = time.perf_counter()
        alive = self._alive(self.entry_group)
        still_waiting: List[dict] = []
        for entry in self._pending_redispatch:
            rid = entry["rid"]
            origin = self._origin.get(rid)
            if origin is None:  # failed/expired since it was queued
                continue
            if origin["deadline"] <= now:
                self._expire_request(rid, "redispatch-wait")
                continue
            if not alive or now < entry["not_before"]:
                # backoff not elapsed, or no survivor to take it —
                # hold (a later revive() drains this queue)
                still_waiting.append(entry)
                continue
            delivered = self.results.get(rid, [])
            remaining = origin["max_new"] - len(delivered)
            if remaining <= 0:
                # every budgeted token was already delivered before the
                # replica died mid-retire — the stream is complete
                if self.reqtrace.enabled:
                    root = self.reqtrace.open_root(rid)
                    self.reqtrace.end(root, outcome="complete",
                                      reason="redispatch-noop")
                self._origin.pop(rid, None)
                if not self.retain_results:
                    self._retired_pending.append(rid)
                if self.on_retire is not None:
                    self.on_retire(rid, "complete")
                continue
            prompt = origin["prompt"]
            if delivered:
                prompt = np.concatenate(
                    [prompt, np.asarray(delivered, dtype=np.int32)]
                )
            target = min(
                alive,
                key=lambda j: (len(self.replicas[j].resident)
                               + len(self.replicas[j].queue)),
            )
            self.replicas[target].submit(
                prompt, int(remaining), session=origin["session"],
                rid=rid, deadline=origin["deadline"],
            )
            self.placement[rid] = target
            self._redispatched += 1
            if origin["session"] is not None:
                # re-pin the session where its replayed prefix now lives
                self._affinity[origin["session"]] = target
                self._affinity.move_to_end(origin["session"])
            self.flightrec.record(
                "redispatch", rid=rid, src=entry["src"], dst=target,
                attempt=origin["attempts"],
                replayed=len(delivered),
            )
            if self.reqtrace.enabled:
                self.reqtrace.event(
                    rid, "redispatch", src=entry["src"], dst=target,
                    attempt=origin["attempts"],
                    replayed=len(delivered),
                )
        self._pending_redispatch = still_waiting

    def revive(self, i: int, *, warmup: bool = True,
               background: bool = False) -> None:
        """Re-admit a fresh replica at dead slot ``i``: a new
        scheduler/engine/pool with the old slot's exact geometry,
        device, and seed, warmed through the compile cache BEFORE the
        rejoining→healthy flip so its first real tick pays no compile
        (and survivors, untouched, never recompile — the registry
        fingerprint proof in the chaos tests)."""
        rec = self.health[i]
        if rec["state"] != "dead":
            raise RuntimeError(
                f"revive: replica {i} is {rec['state']}, not dead"
            )
        self._set_health(i, "rejoining", "revive")
        self.replicas[i] = self._make_replica(i)
        if warmup:
            self.replicas[i].warmup(background=background)
        rec["consecutive"] = 0
        rec["last_error"] = None
        if self.watchdog is not None:
            self.watchdog.watch(f"replica{i}")
        self._set_health(i, "healthy", "revived")

    # ---- routing ----

    def _group_metrics(self, group: List[int]) -> Dict[int, dict]:
        # gate_metrics is the worker-refreshed snapshot + live cheap
        # counters, so a submit does not pay the O(n log n) percentile
        # math of metrics() on the critical path
        return {i: self.replicas[i].gate_metrics() for i in group}

    def submit(self, prompt: np.ndarray, max_new_tokens: int, *,
               session: Optional[int] = None,
               deadline_s: Optional[float] = None) -> int:
        """Route one request; returns its fleet rid. A shed request gets
        a rid too — ``rejected[rid]`` holds the reason and no tokens
        will ever stream for it (the explicit fast-reject contract).
        ``deadline_s`` is a relative latency budget: the gate sheds an
        already-expired one at admission, and the absolute instant it
        fixes travels on the ``Request`` through every replica hop —
        re-dispatch does NOT grant a fresh budget."""
        rid = self._next_rid
        self._next_rid += 1
        # dead/draining/rejoining replicas take no traffic: the gate
        # only ever sees alive entry replicas, and a fully-dead entry
        # group sheds explicitly instead of routing into a corpse
        alive = self._alive(self.entry_group)
        preferred = None
        if session is not None:
            preferred = self._affinity.get(session)
            if preferred is not None:
                self._affinity.move_to_end(session)  # LRU touch
            if preferred is not None and preferred not in alive:
                preferred = None  # pinned replica died; re-pin below
        if not alive:
            decision = Decision(SHED, -1, "fleet-unavailable")
        else:
            with spans.tracer().span("router.gate", rid=rid):
                decision = self.gate.route(
                    self._group_metrics(alive), preferred,
                    deadline_s=deadline_s,
                )
        if decision.action == SHED and decision.reason == "deadline-expired":
            self._deadline_sheds += 1
        if self.reqtrace.enabled:
            # the gate decision opens the request's root span — the
            # first causal fact of its lifecycle (a shed closes it
            # right here: complete trace, outcome=shed)
            trace_decision(
                self.reqtrace, rid, decision, session=session,
                preferred=preferred,
                prompt_len=int(np.asarray(prompt).size),
            )
        if decision.action == SHED:
            self.rejected[rid] = decision.reason
            self._shed_total += 1
            self._trim_rejects()
            self.flightrec.record("shed", rid=rid, reason=decision.reason)
            if self.metrics_log is not None:
                self.metrics_log.log(
                    kind="request", rid=rid,
                    replica_id=(preferred if preferred is not None else -1),
                    rejected=True, reject_reason=decision.reason,
                    session=session,
                    prompt_len=int(np.asarray(prompt).size),
                    new_tokens=0,
                )
            return rid
        target = decision.replica
        if session is not None and session not in self._affinity:
            self._affinity[session] = target
            while len(self._affinity) > self.affinity_cap:
                self._affinity.popitem(last=False)
                self._affinity_evictions += 1
        if decision.action == SPILL:
            self._spilled += 1
            self.flightrec.record(
                "spill", rid=rid, to=target, reason=decision.reason
            )
        elif decision.action == PREEMPT:
            # the pressure rung: park one LRU chain on the target, then
            # queue this request in the capacity it frees. A victim can
            # vanish between the gate's metrics read and now — the
            # request still queues there (backpressure, not failure).
            victim = self.replicas[target].preempt_lru(
                reason=decision.reason or "pressure"
            )
            self._preempt_routes += 1
            self.flightrec.record(
                "preempt_route", rid=rid, to=target, victim=victim,
                reason=decision.reason,
            )
        self.replicas[target].submit(
            prompt, max_new_tokens, session=session,
            spilled=(decision.action == SPILL), rid=rid,
            deadline_s=deadline_s,
        )
        self.placement[rid] = target
        return rid

    # ---- the host loop ----

    def _pump_handoffs(self) -> None:
        """Move every ready request's KV blocks prefill→decode. Targets
        are tried least-loaded-first; a full decode fleet leaves the
        request parked (blocks intact on the prefill replica) for the
        next tick — the same queue-don't-crash contract as admission."""
        budget = (
            self.handoffs_per_tick
            if self.handoffs_per_tick is not None else float("inf")
        )
        order = sorted(
            self._alive(self.decode_group),
            key=lambda i: (len(self.replicas[i].resident),
                           len(self.replicas[i].queue)),
        )
        preempted_this_pump = False
        for pi in self._alive(self.entry_group):
            ps = self.replicas[pi]
            for rid in ps.ready_rids():
                if budget <= 0:
                    return
                try:
                    # serve.handoff_export fires inside export_chain; a
                    # crash here kills the SOURCE replica — its parked
                    # ready set (this rid included) harvests into the
                    # re-dispatch queue, nothing adopted yet
                    req, export = ps.peek_ready(rid)
                except Exception as e:  # noqa: BLE001 — fault boundary
                    self._note_failure(pi, e, site="handoff_export")
                    break
                t0 = time.perf_counter()
                adopted_by = None
                for di in order:
                    if self.health[di]["state"] not in _ROUTABLE:
                        continue  # condemned earlier in this same pump
                    try:
                        # serve.handoff_import fires inside import_chain
                        # before any fresh block lands; a crash kills
                        # the TARGET replica while the source's export
                        # stays valid (the PR 16 failure-safe contract)
                        # — the next candidate simply retries the adopt
                        if self.replicas[di].adopt(req, export):
                            adopted_by = di
                            break
                    except Exception as e:  # noqa: BLE001
                        self._note_failure(di, e, site="handoff_import")
                        continue
                if adopted_by is None:
                    # no decode capacity this tick. Under the pressure
                    # tier, park ONE idle decode chain (LRU) so next
                    # tick's pump can adopt — the handoff twin of the
                    # SLO gate's preempt rung: a prefill-complete
                    # request stalling on a full decode pool is the same
                    # over-commit the admission path preempts for. One
                    # victim per pump (anti-thrash); the request stays
                    # parked here, blocks intact, and retries.
                    if not preempted_this_pump:
                        for di in order:
                            if not self.replicas[di].offload:
                                continue
                            victim = self.replicas[di].preempt_lru(
                                reason="handoff-pressure"
                            )
                            if victim is not None:
                                preempted_this_pump = True
                                self._preempt_routes += 1
                                self.flightrec.record(
                                    "preempt_route", rid=rid, to=di,
                                    victim=victim,
                                    reason="handoff-pressure",
                                )
                                break
                    break
                ps.complete_handoff(rid)
                wall = time.perf_counter() - t0
                self.handoff_lat.observe(wall)
                self.placement[rid] = adopted_by
                self._handoff_count += 1
                if self.reqtrace.enabled:
                    # the handoff as a span of its own (backdated to the
                    # export), plus a flow link to the decode window it
                    # enabled on the other replica — peek/adopt/complete
                    # become visible parent→child structure in the trace
                    h = self.reqtrace.begin(
                        rid, "handoff", replica=pi, t=t0, src=pi,
                        dst=adopted_by, blocks=export.n_blocks,
                        bytes=ps.engine.chain_bytes(export.n_blocks),
                    )
                    self.reqtrace.end(h, wall_s=round(wall, 6))
                    self.reqtrace.link(rid, h, req.span_decode,
                                       "handoff")
                self.flightrec.record(
                    "handoff", rid=rid, src=pi, dst=adopted_by
                )
                budget -= 1

    def _run_tick(self, i: int) -> List[Tuple[int, int]]:
        """Tick replica ``i`` under the failure plane: heartbeat the
        watchdog, catch any exception escaping the tick (→ suspect /
        condemned), and re-check the tick's wall clock against
        ``tick_deadline_s`` — a tick that overran the deadline condemns
        its replica for ``hang`` even though it eventually returned
        (the injected-hang simulation of a wedged device loop). Tokens
        a hung tick DID flush are still delivered: they left the
        replica before it was declared dead, and dropping them would
        strand requests that retired during the hung tick."""
        s = self.replicas[i]
        toks: List[Tuple[int, int]] = []
        self._ticking = i
        if self.watchdog is not None:
            self.watchdog.beat(f"replica{i}")
        t0 = time.perf_counter()
        try:
            toks.extend(s.collect_tick())
            s.dispatch_tick()
        except Exception as e:  # noqa: BLE001 — the fault boundary
            self._note_failure(i, e, site="tick")
        else:
            wall = time.perf_counter() - t0
            if (self.tick_deadline_s is not None
                    and wall >= self.tick_deadline_s):
                # deterministic hang condemnation: measured on the loop
                # itself, not the poller thread, so the chaos matrix
                # never races the watchdog's poll cadence
                self._condemn(i, f"tick-hang:{wall:.3f}s")
            else:
                self._note_success(i)
                if self.watchdog is not None:
                    self.watchdog.beat(f"replica{i}")
        finally:
            self._ticking = None
        return toks

    def step(self) -> List[Tuple[int, int]]:
        """One fleet tick: for each replica — decode replicas first,
        then prefill/mixed — COLLECT the tick the previous step launched
        (it has been in flight across the pump, the caller's work and
        its submits), then DISPATCH the next and leave it in flight;
        then the handoff pump. Returns the collected ticks' tokens, so
        step N returns tick N−1's. Per replica the order collect(N−1) →
        dispatch(N) is the order of a lone ``Scheduler.step()``'s
        launches and collects, so greedy token streams are bit-identical
        to a lone scheduler's; only cross-replica interleaving (and the
        wall clock) differs. The span's ``in_flight`` is how many
        replicas entered the step with a token-bearing tick pending: 0
        on the first step, the replicas that decode thereafter."""
        in_flight = sum(s.tick_in_flight for s in self.replicas)
        with spans.tracer().span("router.step", in_flight=in_flight):
            if self._start_time is None:
                self._start_time = time.perf_counter()
            out: List[Tuple[int, int]] = []
            # harvested requests replay FIRST, so a request re-dispatched at
            # tick N starts prefilling at tick N (once its backoff elapses)
            # — no extra tick of dead air between death and recovery
            self._pump_redispatch()
            # note: collect and dispatch interleave across replicas — while
            # replica i's freshly dispatched tick N is in flight, the loop
            # is already collecting replica i+1's tick N−1 and building its
            # tick N, so every replica's dispatch-side host work overlaps
            # some OTHER replica's device work
            for i in self._alive(self.decode_group + self.entry_group):
                out.extend(self._run_tick(i))
            if self.decode_group:
                self._pump_handoffs()
            for rid, tok in out:
                self.results.setdefault(rid, []).append(tok)
            self._drop_retired()
            self._tick += 1
            if self._tick % 16 == 0:  # sampled: metrics() per tick is waste
                self._recommend_peak = max(self._recommend_peak,
                                           self.recommend_replicas())
        return out

    @property
    def idle(self) -> bool:
        # Scheduler.idle counts parked and mid-swap requests as
        # in-flight work, so a drain never strands a preempted stream;
        # has_uncollected keeps the loop stepping until every
        # in-flight tick's tokens have been collected AND delivered;
        # pending re-dispatches are in-flight work too — a fleet with a
        # harvested request waiting out its backoff is NOT idle
        return (
            all(s.idle and not s.has_uncollected for s in self.replicas)
            and not self._pending_redispatch
        )

    def _settle_host_work(self) -> None:
        """Barrier: every replica's buffered observations shipped, and
        everything the workers were handed (JSONL, metric refreshes)
        run; a worker's error re-raises here."""
        for s in self.replicas:
            s.flush_host_work()
        self.host_pool.flush()

    def drain(self, max_steps: int = 100_000) -> Dict[int, List[int]]:
        """Step until every replica is empty; returns ``{rid: [tokens]}``
        for every request that produced output (shed rids absent)."""
        for _ in range(max_steps):
            if self.idle:
                self._settle_host_work()
                if self.blocksan is not None:
                    # fleet quiesce: every replica's ledger must equal
                    # its allocator with no chains, swap windows, or
                    # handoff pins outstanding (the drain retired or
                    # adopted everything; index-retained blocks are
                    # legitimately live)
                    for s in self.replicas:
                        if s._san is not None:
                            s._san.verify_quiesce()
                return dict(self.results)
            self.step()
        # drain diagnostics (satellite, round 19): name the stuck rids
        # by replica and state instead of a bare step count — the first
        # question a wedged-fleet post-mortem asks
        stuck = {
            f"r{i}": s.stuck_rids()
            for i, s in enumerate(self.replicas) if not s.idle
        }
        pending = sorted(e["rid"] for e in self._pending_redispatch)
        raise RuntimeError(
            f"fleet drain did not converge within {max_steps} steps; "
            f"stuck rids by replica/state: {stuck}; "
            f"awaiting redispatch: {pending}"
        )

    def cancel(self, rid: int, reason: str = "client-cancel") -> bool:
        """Fleet cancellation: abort ``rid`` on whichever replica holds
        it (queued, resident, parked, mid-swap, or handoff-ready).
        Returns False when no replica knows the rid — already retired,
        shed, or never submitted; cancellation is idempotent."""
        return any(s.cancel(rid, reason=reason) for s in self.replicas)

    # ---- compile-cache integration ----

    def registries(self):
        """One ``compilecache.serving_registry`` per replica — the
        programs each replica can ever compile, enumerated on ITS
        mesh/device placement."""
        from pytorch_distributed_tpu.compilecache import serving_registry

        return [
            serving_registry(s.engine, extra=(f"replica={s.replica_id}",
                                              f"role={role}"))
            for s, role in zip(self.replicas, self.roles)
        ]

    def assert_registry_covers(self) -> None:
        """Fleet-wide coverage guard: every compiled program on every
        replica must have been predicted by that replica's registry."""
        for reg, s in zip(self.registries(), self.replicas):
            reg.assert_covers(s.engine.compiled_program_names())

    def warmup(self, background: bool = False) -> None:
        """Compile every replica's programs before traffic (decode
        replicas only ever need the decode tick, but uniform warmup
        keeps role changes free)."""
        for s in self.replicas:
            s.warmup(background=background)

    # ---- metrics ----

    def recommend_replicas(self) -> int:
        """The autoscaler hook (``fleet.admission.recommend_replicas``)
        over the ENTRY group's live metrics — decode replicas scale with
        prefill replicas, not independently, in this round."""
        return recommend_replicas(
            len(self.entry_group),
            list(self._group_metrics(self.entry_group).values()),
            self.gate,
        )

    def metrics(self) -> dict:
        """Fleet rollup: totals, shed/spill rates, fleet-wide latency
        percentiles (replica series concatenated — every request appears
        in exactly one replica's series), handoff stats, the autoscaler
        recommendation, and flat per-replica key summaries."""
        per = [s.metrics() for s in self.replicas]
        submitted = self._next_rid
        shed = self._shed_total
        placed = submitted - shed
        elapsed = (
            time.perf_counter() - self._start_time
            if self._start_time is not None else 0.0
        )
        out: dict = {
            "replicas": len(self.replicas),
            "disaggregated": self.disaggregated,
            "submitted": submitted,
            "shed": shed,
            "spilled": self._spilled,
            "shed_rate": shed / submitted if submitted else 0.0,
            "spill_rate": self._spilled / placed if placed else 0.0,
            "completed": sum(m["completed"] for m in per),
            "tokens_out": sum(m["tokens_out"] for m in per),
            "tokens_per_s": (
                sum(m["tokens_out"] for m in per) / elapsed
                if elapsed else 0.0
            ),
            "handoffs": self._handoff_count,
            # pressure tier rollup (round 13): fleet-wide preemptions,
            # restores, parked chains, and swap traffic — shed stays the
            # headline failure count these exist to zero out
            "preempt_routes": self._preempt_routes,
            "preempts": sum(m["preempts"] for m in per),
            "restores": sum(m["restores"] for m in per),
            "parked": sum(m["parked"] for m in per),
            "swap_bytes": sum(m["swap_bytes"] for m in per),
            "swap_aborts": sum(m["swap_aborts"] for m in per),
            "preempt_rate": (
                sum(m["preempts"] for m in per) / placed if placed else 0.0
            ),
            # prefix-cache rollup (round 17): fleet-wide hit rate over
            # per-replica lookups (each admission looks up exactly once
            # on its replica, so concatenating series is exact), the
            # sharing/COW/eviction totals, and the affinity table's LRU
            # accounting (the round-17 unbounded-growth fix)
            "prefix_lookups": sum(m["prefix_lookups"] for m in per),
            "prefix_hits": sum(m["prefix_hits"] for m in per),
            "prefix_hit_rate": (
                sum(m["prefix_hits"] for m in per)
                / max(sum(m["prefix_lookups"] for m in per), 1)
            ),
            "prefix_covered_tokens": sum(
                m["prefix_covered_tokens"] for m in per
            ),
            "admitted_prefill_tokens": sum(
                m["admitted_prefill_tokens"] for m in per
            ),
            "prefix_cow_copies": sum(m["prefix_cow_copies"] for m in per),
            "prefix_evictions": sum(m["prefix_evictions"] for m in per),
            "prefix_shared_blocks": sum(
                m["prefix_shared_blocks"] for m in per
            ),
            "affinity_sessions": len(self._affinity),
            "affinity_evictions": self._affinity_evictions,
            # round 21 retention plane: how many retired rids had their
            # results/placement entries dropped (0 in the default
            # keep-everything mode) and the live-request axis the
            # census audits against
            "results_dropped": self._results_dropped,
            "live_requests": self.live_requests(),
            "cancelled": sum(m["cancelled"] for m in per),
            # failure-plane rollup (round 19): health census, replica
            # deaths, re-dispatch traffic, and the deadline ledger —
            # "deadline_misses" are scheduler-tick expiries (the request
            # was running), "deadline_sheds" died at the gate, and
            # "deadline_expired_redispatch" lapsed inside the router's
            # own recovery machinery
            "replicas_healthy": sum(
                1 for h in self.health if h["state"] in _ROUTABLE
            ),
            "replica_deaths": sum(h["deaths"] for h in self.health),
            "redispatched": self._redispatched,
            "redispatch_pending": len(self._pending_redispatch),
            "failed": self._failed_total,
            "deadline_misses": sum(m["deadline_misses"] for m in per),
            "deadline_sheds": self._deadline_sheds,
            "deadline_expired_redispatch":
                self._deadline_expired_redispatch,
            **(self.blocksan.summary()
               if self.blocksan is not None else {}),
            "recommended_replicas": self.recommend_replicas(),
            "recommended_replicas_peak": self._recommend_peak,
        }
        out.update(self.handoff_lat.summary("handoff"))
        for name in ("ttft", "token_lat", "queue_wait"):
            vals: List[float] = []
            for s in self.replicas:
                vals.extend(getattr(s, name).values)
            for q, v in percentiles(vals).items():
                out[f"{name}_{q}_s"] = v
        for i, m in enumerate(per):
            for k in ("tokens_out", "completed", "queue_depth",
                      "occupancy_mean", "goodput_frac", "preempts",
                      "restores"):
                out[f"r{i}_{k}"] = m[k]
            for k in ("ttft_p95_s", "queue_wait_p95_s"):
                if k in m:
                    out[f"r{i}_{k}"] = m[k]
            out[f"r{i}_role"] = self.roles[i]
            out[f"r{i}_health"] = self.health[i]["state"]
        return out

    def log_summary(self) -> None:
        """One ``kind="fleet_summary"`` JSONL record — the fleet half of
        what ``scripts/telemetry_report.py`` renders. Settles the host
        workers first so every offloaded per-request record lands
        before the summary that aggregates them."""
        self._settle_host_work()
        if self.metrics_log is not None:
            self.metrics_log.log(kind="fleet_summary", **self.metrics())
