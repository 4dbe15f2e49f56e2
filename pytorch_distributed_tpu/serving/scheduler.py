"""Continuous scheduler over the paged engine.

Policy (Orca-style continuous batching with chunked prefill):

- **FIFO admission, batched**: each ``step()`` admits up to
  ``admit_per_step`` queued requests — strictly in submit order, stopping
  at the first that cannot get a slot or a block chain (no head-of-line
  skipping: deterministic, starvation-free). All admitted-and-unfinished
  prompts advance by ONE chunk per step through a single compiled chunk
  program (``PagedEngine.run_chunks``), so a long prompt never stalls the
  decode lanes — it interleaves, chunk by chunk, with everyone else's
  decode ticks.
- **decode**: every fully-prefilled slot with budget advances one token
  per step; EOS (when configured) retires a slot early. Retirement frees
  the block chain immediately — the freed blocks are the next
  admission's allocation (LIFO).
- **OOM queues**: a request that cannot be served *now* (no free slot, or
  the pool cannot supply its chain) simply stays queued. ``submit``
  never raises for capacity reasons — only for requests that could never
  fit (``> max_seq_len``).

Metrics are exact host-side counters, no device sync beyond the token
fetch the caller already pays: slot occupancy, block-pool occupancy,
padding-waste fraction (allocated-but-unwritten block capacity),
admission latency (steps and wall seconds from submit to admission),
queue depth, and tokens/s — plus, from round 7 (ISSUE 4), the latency
percentiles a continuous batcher exists to control: TTFT (submit →
first materialized token), per-output-token latency (inter-token gap),
and queue wait (submit → admit), all exact host-side series from
timestamps the scheduler already holds (``telemetry.LatencySeries``).
Pass ``metrics_log`` (a ``MetricsLogger``) to stream one ``kind=
"request"`` JSONL record per retirement — the raw material
``scripts/telemetry_report.py`` computes percentiles from. A tick's
phases (``sched.expire``, ``sched.admit``, ``sched.chunk_plan``, the
engine's launches, ``engine.collect.wait``, ``sched.collect.process``)
and each request's queue wait (``req.queue``) are spans in the process's
stream (``telemetry.spans.tracer()``).

KV pressure tier (round 13; ANALYSIS.md "KV pressure & preemption"):
``offload=True`` arms the second tier — ``preempt(rid)`` parks a
decode-armed request (LRU-idle victims first via ``preempt_lru``),
choosing per request between swapping its chain to a host-RAM
``HostBlockStore`` (compiled gather → async d2h, finalized next tick)
and recomputing from the prompt (chain dropped now; the streamed tokens
re-prefill as prompt at restore) by a MEASURED cost comparison
(``telemetry.costmodel.swap_vs_recompute``: chain bytes through the
probed link vs resume chunks times the chunk program's measured wall).
``_restore_parked`` restores FIFO before each tick's admissions — a
preempted request resumes before its next decode, token-identical
either way. ``preempt_on_oom`` lets admission preempt one victim per
stuck queue head; the fleet ``SLOGate``'s preempt rung drives the same
entry point to turn sheds into preemptions.

Fleet integration (round 10; ``fleet/``, ANALYSIS.md "Serving fleet"):
one Scheduler is one *replica*. ``replica_id`` stamps every JSONL
record; ``device`` commits the replica's engine to its own sub-mesh
slice of ``jax.devices()``; ``begin_drain``/``drain_graceful`` stop
admission, finish in-flight requests, and hand the untouched queue back
for re-routing (zero leaked pool blocks — the scale-down primitive);
``prefill_only`` replicas park prefill-complete requests in ``ready``
instead of arming decode, and ``peek_ready``/``complete_handoff`` +
``adopt`` move a request's KV blocks into a decode replica's pool
(``PagedEngine.export_chain``/``import_chain``) — the disaggregated
prefill/decode split.

Host runtime (round 16; ANALYSIS.md "Async host runtime"): a tick is
a **dispatch/collect split** — ``dispatch_tick()`` runs admissions,
the chunk program, and a NON-BLOCKING decode launch
(``PagedEngine.decode_launch``: JAX async dispatch returns before
device completion; each launch moves one packed operand to the device),
parking a ``TickHandle``; ``collect_tick()`` materializes the parked
tick's tokens (its one fetch: the positions it writes back to the
decoded lanes are the engine's host count) and does all per-token host
work (TTFT, retirement, JSONL). ``fleet.FleetRouter`` drives the halves
LAGGED — collect tick N−1, then dispatch tick N, on every replica, and
return with N in flight — so the device works through the caller's
submits and the other replicas' host work. ``step()`` is the same two
halves back to back, collect(N−1) → dispatch(N) → collect(N): a lone
``Scheduler``'s tick, which returns tick N's tokens from the call that
launched it. Per replica the order of dispatches and collects is the
same in both, which is why their token streams are bit-identical. Any
entry point that mutates decode-armed state from OUTSIDE the tick
cycle (``preempt``/``preempt_lru``/``begin_drain``) collects the
pending tick first, so an in-flight decode can never race a chain
release.
``host_pool`` (a ``serving.host_worker.HostWorkerPool``) moves
per-request JSONL emission and the gate-metrics percentile math onto
worker threads; ``gate_metrics()`` is the router's routing view —
worker-refreshed percentile snapshot overlaid with LIVE cheap counters
(queue depth, occupancy, preemptible), so depth-bound SLO decisions
stay deterministic while the O(n log n) percentile work leaves the
critical path.

Lifecycle tracing (round 14; ANALYSIS.md "Request-lifecycle tracing"):
pass ``reqtrace`` (a ``telemetry.ReqTracer``) and every request becomes
one causal span tree — queued → prefill (per-chunk events naming the
bucket program) → decode windows → retire, with preempt/park/restore as
a sub-tree carrying the swap decision's predicted costs next to the
measured swap walls, ``handoff_wait`` bridging into the fleet router's
handoff span, and KV chain transitions (alloc/free/swap states)
annotated through the ``BlockAllocator.on_transition`` adapter.
``scripts/explain_request.py`` reconstructs any rid's story from the
resulting ``kind="span"`` JSONL.

Prefix sharing (round 17; ANALYSIS.md "Prefix sharing & copy-on-write"):
``prefix_cache=True`` arms the radix index over the block pool —
admission consults ``PagedEngine.admit_shared`` so a prompt whose
leading full blocks are already resident allocates only the suffix and
chunk-prefills only the uncovered tail (admission cost O(new tokens),
the PagedAttention sharing story), with the full-cover boundary block
copy-on-write duplicated so the final token's re-prefill regenerates
the logits row without touching shared state. Chains insert their full
prompt blocks as prefill crosses block boundaries; retirement decrefs,
and the index's LRU eviction of refcount-1 blocks is the engine's
first pool-pressure valve — it fires BEFORE ``preempt_on_oom`` parks a
live chain. Greedy streams stay token-identical to the no-sharing
engine (tests/test_prefix.py), and every hit lands a ``kind="prefix"``
JSONL record.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import numpy as np

from pytorch_distributed_tpu.compilecache.aot import attribute_compile
from pytorch_distributed_tpu.resilience.faults import fault_point
from pytorch_distributed_tpu.telemetry import (
    NULL_RECORDER,
    NULL_REQTRACER,
    AnomalySentinel,
    GoodputLedger,
    LatencySeries,
    ProgramTimes,
    percentiles,
    spans,
)


@dataclasses.dataclass
class Request:
    rid: int
    tokens: np.ndarray  # [L] int32 prompt
    max_new_tokens: int
    submit_step: int
    submit_time: float
    slot: int = -1  # -1 while queued
    prefill_done: int = 0  # tokens prefilled so far (chunk multiple)
    produced: int = 0
    admit_step: int = -1
    admit_time: float = float("nan")
    first_token_time: float = float("nan")
    # step-domain TTFT anchor: the scheduler tick that materialized the
    # first token. Wall latencies measure THIS machine; tick latencies
    # measure the schedule — the fleet benches evaluate SLOs in ticks so
    # the router A/B is invariant to how fast the simulating host turns
    # the crank (fleet replicas tick in lockstep, so cross-replica step
    # differences are well-defined even across a prefill→decode handoff)
    first_token_step: int = -1
    last_token_time: float = float("nan")
    # inter-token gaps AFTER the first token (the decode-tick latency
    # this request's stream observed; the first token's latency is TTFT)
    token_gaps: List[float] = dataclasses.field(default_factory=list)
    # True when a compile stall landed inside this request's lifetime: a
    # prefill chunk of its batch hit a not-yet-hot bucket program, or its
    # first decode tick compiled the decode program. Cold requests' TTFT
    # pollutes p99 with XLA compile time — the per-request JSONL carries
    # the flag so percentiles can be reported warm-only vs all (and the
    # warmup runtime exists to make every request warm).
    cold: bool = False
    # fleet routing provenance (fleet/router.py): the session the router
    # used for affinity, and whether this request was spilled off its
    # affinity replica by the SLO gate — both land in the JSONL record
    session: Optional[int] = None
    spilled: bool = False
    # ---- per-request deadline (round 19; ROADMAP item 5 rung) ----
    # absolute ``time.perf_counter()`` instant after which the request
    # expires through the cancel path with ``outcome="deadline"``. The
    # deadline is absolute (not remaining seconds) so it survives
    # re-dispatch to another replica unchanged — a request does not get
    # a fresh budget by losing its replica. ``inf`` == no deadline.
    deadline: float = float("inf")
    # replica hops: every replica that has owned this request, in order
    # (the re-dispatch chain ``scripts/explain_request.py`` renders)
    redispatches: int = 0
    # ---- pressure tier (round 13; offload schedulers only) ----
    # the submitted prompt's length — ``tokens`` grows on a recompute
    # restore (generated tokens re-prefill as prompt), so the JSONL's
    # prompt_len reports THIS, not len(tokens)
    orig_len: int = -1
    # tokens this request has streamed, kept only under offload: the
    # recompute path re-prefills them as prompt so the stream resumes
    # bit-exact from where it was preempted
    generated: Optional[List[int]] = None
    # preempt/restore accounting + the anti-thrash protection window
    # (a just-restored request cannot be re-victimized before this tick)
    preempts: int = 0
    protect_until: int = -1
    # ---- request-lifecycle trace spans (round 14; telemetry/reqtrace).
    # Span ids of this request's currently-open lifecycle spans (0 ==
    # none). They live on the Request because the request OBJECT crosses
    # replica boundaries on the disaggregated handoff — the span ids
    # travel with it, so the decode replica closes what the prefill
    # replica opened and the trace stays one tree.
    span_queue: int = 0
    span_prefill: int = 0
    span_ready: int = 0
    span_decode: int = 0
    span_preempt: int = 0
    span_parked: int = 0
    span_swap: int = 0

    @property
    def length(self) -> int:
        return int(len(self.tokens))


class TickHandle(NamedTuple):
    """One dispatched-but-uncollected scheduler tick (round 16).

    ``tokens`` is the decode program's token output — a DEVICE array,
    materialized at collect — or None when the tick had no active
    decode lane. ``positions`` is the engine's host count
    of every slot's position after the tick, as of the launch (nothing
    is fetched for it). ``lanes`` are the slots that were active
    at dispatch, in slot order — collect processes exactly these, and
    the no-external-mutation protocol (preempt/drain collect first)
    guarantees each is still resident at collect time."""

    tokens: object
    positions: object
    lanes: Tuple[int, ...]
    t_step0: float
    t_dec: float
    cold_decode: bool


class Scheduler:
    """Continuous paged-KV scheduler: ``submit`` enqueues, ``step``
    advances the whole system one tick, ``drain`` runs to empty.

    ``step()`` returns ``[(rid, token)]`` for the tokens produced this
    tick — request ids, not slots (slots recycle; rids don't).
    """

    def __init__(self, config, params, n_slots: int, *,
                 n_blocks: Optional[int] = None, block_len: int = 16,
                 prefill_chunk: int = 64, admit_per_step: int = 4,
                 temperature: float = 0.0, top_k: Optional[int] = None,
                 seed: int = 0, eos_id: Optional[int] = None, mesh=None,
                 metrics_log=None, replica_id: int = 0,
                 prefill_only: bool = False, device=None,
                 handoff: bool = False, flightrec=None,
                 anomaly_threshold: float = 8.0,
                 kv_dtype: Optional[str] = None,
                 offload: bool = False, preempt_on_oom: bool = False,
                 swap_policy: str = "auto", protect_ticks: int = 2,
                 host_store=None,
                 host_store_max_bytes: Optional[int] = None,
                 reqtrace=None, host_pool=None,
                 prefix_cache: bool = False, blocksan=None,
                 chunk_bucket_floor: Tuple[int, int] = (1, 1),
                 max_chunk_jobs: Optional[int] = None):
        from pytorch_distributed_tpu.serving.engine import PagedEngine
        from pytorch_distributed_tpu.serving.kv_pool import HostBlockStore

        if swap_policy not in ("auto", "swap", "recompute"):
            raise ValueError(
                f"swap_policy {swap_policy!r} must be auto|swap|recompute"
            )
        if preempt_on_oom and not offload:
            raise ValueError("preempt_on_oom needs offload=True")

        if eos_id is not None and not 0 <= eos_id < config.vocab_size:
            raise ValueError(
                f"eos_id {eos_id} outside [0, vocab_size={config.vocab_size})"
            )
        if admit_per_step < 1:
            raise ValueError(
                f"admit_per_step must be >= 1, got {admit_per_step}"
            )
        self.engine = PagedEngine(
            config, params, n_slots, n_blocks=n_blocks, block_len=block_len,
            prefill_chunk=prefill_chunk, temperature=temperature,
            top_k=top_k, mesh=mesh, device=device,
            handoff=(handoff or prefill_only), swap=offload,
            kv_dtype=kv_dtype, prefix_cache=prefix_cache,
            chunk_bucket_floor=chunk_bucket_floor,
            max_chunk_jobs=max_chunk_jobs,
        )
        # ---- prefix-sharing tier (round 17): radix reuse + COW ----
        self.prefix_cache = prefix_cache
        self._prefix_covered_tokens = 0
        # prompt tokens actually chunk-prefilled at admission (prefix
        # hits subtract their covered prefix) — the A/B's headline
        self._admitted_prefill_tokens = 0
        # ---- pressure tier (round 13): host offload + preemption ----
        self.offload = offload
        self.preempt_on_oom = preempt_on_oom
        self.swap_policy = swap_policy
        self.protect_ticks = protect_ticks
        self.host_store = (
            host_store if host_store is not None
            else HostBlockStore(max_bytes=host_store_max_bytes)
        )
        # rid -> (request, restore path): preempted requests awaiting
        # restore, FIFO (dict preserves insertion order)
        self.parked: Dict[int, Tuple[Request, str]] = {}
        # swap-outs whose d2h window is open: finalized at the top of
        # the next step() (and by begin_drain) — the real cross-tick
        # swapping-out state
        self._swapping: List[tuple] = []
        # slots whose chain is mid-swap-out: not reusable until finish
        self._swap_slots: set = set()
        self._preempts = 0
        self._restores = 0
        self._swap_outs = 0
        self._swap_ins = 0
        self._swap_aborts = 0
        self._swap_bytes = 0
        self._decision_swap = 0
        self._decision_recompute = 0
        self._oom_preempted_for: Optional[int] = None
        self.swap_lat = LatencySeries("swap")
        self.config = config
        self.n_slots = n_slots
        self.admit_per_step = admit_per_step
        self.eos_id = eos_id
        self.replica_id = replica_id
        self.prefill_only = prefill_only
        self.draining = False
        # prefill_only: requests whose prefill finished and are waiting
        # for the fleet router to hand their KV blocks to a decode
        # replica (rid -> the slot HERE holding them; slot + blocks stay
        # held until complete_handoff. The slot is recorded on this side
        # because adoption re-points req.slot at the decode replica's
        # slot — trusting it afterwards would free someone else's slot)
        self.ready: Dict[int, int] = {}
        self._handoffs = 0
        self._adopted = 0
        self._rng = jax.random.key(seed)
        self._next_rid = 0
        self._step_count = 0
        self.queue: deque = deque()
        self.resident: Dict[int, Request] = {}  # slot -> request
        self.positions = np.zeros(n_slots, np.int32)
        self.remaining = np.zeros(n_slots, np.int32)
        # ---- exact host-side metric counters ----
        self._tokens_out = 0
        self._completed = 0
        self._admitted = 0
        self._adm_latency_steps = 0
        self._adm_latency_s = 0.0
        self._occupancy_sum = 0.0  # mean-able over steps
        self._start_time: Optional[float] = None
        # ---- latency series (telemetry/latency.py; exact, host-side) ----
        self.metrics_log = metrics_log
        self.ttft = LatencySeries("ttft")
        # warm-only TTFT: requests whose lifetime saw no compile stall —
        # the honest SLO series (cold first-bucket requests excluded)
        self.ttft_warm = LatencySeries("ttft_warm")
        self.token_lat = LatencySeries("token_lat")
        self.queue_wait = LatencySeries("queue_wait")
        # wall cost of THIS replica's own step() on ticks that delivered
        # tokens — the replica-attributed token latency. In the fleet's
        # one-loop simulation the gap between two tokens includes every
        # OTHER replica's step too; this series is what the stream pays
        # on ITS replica (chunk-program interference included for mixed
        # replicas, excluded for pure-decode ones) — the disaggregation
        # A/B's honest metric (ANALYSIS.md "Serving fleet").
        self.tick_lat = LatencySeries("tick")
        self._cold_requests = 0
        # wall-time ledger: serving attributes its compile stalls (lazy
        # first-bucket compiles AND warmup compile time) so cold-vs-warm
        # starts compare on one number — goodput compile fraction
        self.goodput = GoodputLedger()
        self.goodput.start()
        # ---- attribution & forensics (ISSUE 8) ----
        # per-program measured wall for the cost-card join: the chunk
        # program of each tick's bucket, and the decode tick (whose
        # tokens materialize inside engine.decode, so its wall is honest
        # device+sync time, not bare dispatch)
        self.prog_times = ProgramTimes()
        self.flightrec = flightrec if flightrec is not None else NULL_RECORDER
        # ---- request-lifecycle tracing (round 14; telemetry/reqtrace) ----
        # rid-keyed span trees across every owner; the kv-transition
        # adapter below annotates block alloc/free/swap-state changes
        # with chain identity by mapping the allocator's owner slot back
        # to the resident rid
        self.reqtrace = reqtrace if reqtrace is not None else NULL_REQTRACER
        self._slot2rid: Dict[int, int] = {}
        if self.reqtrace.enabled:
            self.engine.set_kv_trace(self._kv_transition)
        # ---- block-lifecycle sanitizer (analysis.blocksan; round 18) ----
        # PDT_BLOCKSAN=1 installs a shadow ledger on the allocator; a
        # fleet router passes ONE sanitizer shared across replicas so
        # handoff pins and violations aggregate. Off (the default) this
        # is None end to end — the allocator hot path pays a single
        # attribute test per op.
        if blocksan is None:
            from pytorch_distributed_tpu.analysis.blocksan import (
                maybe_sanitizer,
            )
            blocksan = maybe_sanitizer(metrics_log=metrics_log,
                                       replica_id=replica_id)
        self.blocksan = blocksan
        self._san = (
            blocksan.attach(self.engine.allocator,
                            name=f"replica{replica_id}",
                            resolve_rid=self._slot2rid.get)
            if blocksan is not None else None
        )
        self._cancelled = 0
        self._deadline_misses = 0
        # ---- async host runtime (round 16) ----
        # the dispatched-but-uncollected tick (main-thread-only state:
        # only dispatch_tick/collect_tick and the early-collect hooks
        # in preempt/begin_drain touch it)
        self._pending_tick: Optional[TickHandle] = None
        # tokens collected outside the router's collect phase (an early
        # collect forced by preempt/drain) — delivered at the next
        # collect_tick so no token is ever dropped or double-delivered
        self._collected: List[Tuple[int, int]] = []
        # optional worker pool (serving.host_worker.HostWorkerPool):
        # per-request JSONL emission and the gate-metrics percentile
        # math run there; everything a worker touches is either
        # self-locked (logger/tracer), copied at enqueue, or the
        # snapshot below under its dedicated lock
        self.host_pool = host_pool
        self._gate_cache: Optional[dict] = None
        self._gate_lock = threading.Lock()
        #: ticks between gate-snapshot refreshes. Refreshing every
        #: collect measurably drags the loop (one task + two list
        #: copies per tick); the gate's percentile rungs tolerate
        #: staleness by design — the depth-bound rungs ride the LIVE
        #: overlays in gate_metrics and never go stale at all.
        self.gate_refresh_ticks = 32
        self._gate_refreshed_step = -(10**9)
        # batched sentinel feed (async mode): per-tick observations
        # buffer here (main thread) and ship to a worker as ONE task
        # per batch — a task per tick measurably dragged the loop
        # (queue hop + GIL churn ~2x/tick)
        self._tick_obs: List[Tuple[float, float, int]] = []
        self.tick_obs_batch = 32
        # anomaly sentinel over tick time / TTFT / queue depth; a recent
        # hit surfaces as metrics()["anomaly_recent"], which the fleet
        # SLOGate reads as a hot signal (spill around this replica)
        self.sentinel = (
            AnomalySentinel(
                threshold=anomaly_threshold, metrics_log=metrics_log,
                flightrec=self.flightrec, source=f"replica{replica_id}",
            )
            if anomaly_threshold and anomaly_threshold > 0 else None
        )
        self._last_anomaly_step = None
        #: ticks an anomaly stays "recent" for the SLO gate's hot signal
        self.anomaly_recent_ticks = 64
        if self.sentinel is not None:
            # scale floors: a detector over a near-constant series would
            # otherwise flag routine jitter (MAD ≈ 0 → any blip is ∞σ).
            # Time series floor at 10 ms — a stall must clear
            # threshold × 10 ms above baseline; queue depth floors at one
            # whole request.
            self.sentinel.detector("tick_time").abs_floor = 0.01
            self.sentinel.detector("ttft").abs_floor = 0.01
            self.sentinel.detector("queue_depth").abs_floor = 1.0
        # round 21 (scale observatory): optional retire hook,
        # ``on_retire(rid, outcome)``, fired on the main thread when a
        # request leaves the scheduler for good (complete / cancel /
        # deadline). The fleet router uses it to drop per-rid
        # bookkeeping in streaming-retention mode.
        self.on_retire: Optional[Callable[[int, str], None]] = None

    # ---- API ----

    def warmup(self, background: bool = True):
        """Compile every program this scheduler can ever run, BEFORE
        traffic (compilecache/: ANALYSIS.md "Cold start & compile cache").

        The decode tick and the smallest prefill bucket compile (and
        execute inert) in the foreground — serving can start the moment
        this returns, with the serve-critical path hot; the remaining
        buckets AOT-compile on a background thread into the persistent
        compilation cache. ``background=False`` compiles everything in
        the foreground with inert execution: zero cold requests, the
        strongest guarantee, at full upfront cost.

        Warmup compile time lands in the ledger's ``compile`` category
        and each program emits a ``kind="warmup"`` manifest record to
        ``metrics_log`` — so a cold start (fresh cache) and a warm start
        (populated cache) compare on the goodput compile fraction.
        Returns the ``WarmupRunner`` (``.wait()`` joins the background
        thread; ``.summary()`` aggregates the manifest).
        """
        from pytorch_distributed_tpu.compilecache import (
            WarmupRunner,
            serving_registry,
        )

        runner = WarmupRunner(
            serving_registry(self.engine),
            ledger=self.goodput,
            manifest=self.metrics_log,
        )
        return runner.run(background=background)

    def _kv_transition(self, event: str, owner: int, info: dict) -> None:
        """``BlockAllocator.on_transition`` adapter: chain transitions
        (alloc/free/swap states) become ``kv_*`` events in the owning
        request's lifecycle trace. ``owner`` is a slot id; the adapter
        resolves it through ``_slot2rid`` (written just before each
        allocating call, cleared when the chain frees) — transitions on
        slots no request owns (warmup probes, teardown resets) are
        silently unattributable and dropped."""
        rid = self._slot2rid.get(owner)
        if rid is None:
            return
        self.reqtrace.event(
            rid, f"kv_{event}", replica=self.replica_id, slot=owner, **info
        )
        if event == "free":
            self._slot2rid.pop(owner, None)

    def submit(self, prompt: np.ndarray, max_new_tokens: int, *,
               session: Optional[int] = None, spilled: bool = False,
               rid: Optional[int] = None,
               deadline_s: Optional[float] = None,
               deadline: Optional[float] = None) -> int:
        """Enqueue one request; returns its request id. Never raises for
        capacity — only for requests no configuration could serve, and
        for submission into a draining replica (the router must not
        route here once ``begin_drain`` ran).

        ``session``/``spilled`` are fleet routing provenance stamped into
        the per-request JSONL; ``rid`` lets the fleet router allocate
        request ids from ONE fleet-wide space so a request keeps its id
        across replicas and the prefill→decode handoff.

        ``deadline_s`` (seconds from now) or ``deadline`` (an absolute
        ``time.perf_counter()`` instant — what the router passes on
        re-dispatch so the clock never resets) arms per-request
        expiry: the deadline sweep at the top of every ``dispatch_tick``
        expires the request through the cancel path with
        ``outcome="deadline"`` whatever state it is in."""
        if self.draining:
            raise RuntimeError(
                f"replica {self.replica_id} is draining; route elsewhere"
            )
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        l = len(prompt)
        if l < 1:
            raise ValueError("prompt must contain at least one token")
        c = self.engine.chunk
        padded = -(-l // c) * c
        if padded > self.config.max_seq_len:
            raise ValueError(
                f"prompt ({l}) padded to {padded} exceeds max_seq_len "
                f"{self.config.max_seq_len}"
            )
        if l + max_new_tokens > self.config.max_seq_len:
            raise ValueError(
                f"prompt ({l}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_seq_len {self.config.max_seq_len}"
            )
        if rid is None:
            rid = self._next_rid
            self._next_rid += 1
        else:
            self._next_rid = max(self._next_rid, rid + 1)
        now = time.perf_counter()
        if deadline is None:
            deadline = (now + deadline_s if deadline_s is not None
                        else float("inf"))
        req = Request(
            rid=rid, tokens=prompt, max_new_tokens=max_new_tokens,
            submit_step=self._step_count, submit_time=now,
            session=session, spilled=spilled, orig_len=l,
            generated=[] if self.offload else None,
            deadline=deadline,
        )
        if self.reqtrace.enabled:
            # standalone schedulers open the root here; under a fleet the
            # gate decision already did (open_root is idempotent) and
            # this just hangs the queue-wait span under it
            root = self.reqtrace.open_root(rid, prompt_len=l,
                                           session=session)
            req.span_queue = self.reqtrace.begin(
                rid, "queued", parent=root, replica=self.replica_id,
                max_new=max_new_tokens,
            )
        self.queue.append(req)
        return rid

    def _free_slots(self) -> List[int]:
        # a slot whose chain is mid-swap-out is NOT free: its table row
        # and allocator chain are still live until the swap finalizes
        return [s for s in range(self.n_slots)
                if s not in self.resident and s not in self._swap_slots]

    def _admit(self) -> bool:
        """Admit up to ``admit_per_step`` queue-head requests that can be
        served now. Strict FIFO: the first request that cannot get a slot
        or a chain stops admission for this step. True where the queue's
        head had a slot and found too few blocks: the pool, not the
        slots, held it back."""
        if self.draining:
            return False
        free = self._free_slots()
        admitted = 0
        now = time.perf_counter()
        while self.queue and free and admitted < self.admit_per_step:
            req = self.queue[0]
            slot = free[0]
            # kv-trace attribution BEFORE the allocating call: the alloc
            # transition fires inside engine.admit and must resolve to
            # this rid (popped right back on the OOM path)
            self._slot2rid[slot] = req.rid
            if self.prefix_cache:
                # shared-prefix admission: the longest indexed full-block
                # match rides shared blocks and only the uncovered tail
                # prefills (None = pool OOM, the same queue signal)
                hit = self.engine.admit_shared(
                    slot, req.tokens, req.max_new_tokens
                )
                admitted_ok = hit is not None
            else:
                hit = None
                admitted_ok = self.engine.admit(
                    slot, req.length, req.max_new_tokens
                )
            if not admitted_ok:
                self._slot2rid.pop(slot, None)
                # pool OOM: queue (blocks free as others retire). Under
                # pressure mode, first preempt one LRU victim — its
                # blocks free now (recompute) or next tick (swap), so
                # capacity turns over instead of waiting on a retire.
                # ONE preemption per stuck queue head: restores outrank
                # admissions (strict arrival order — a parked request is
                # older than the queue head), so preempting every tick
                # would only carousel chains through the host store;
                # one boost per head keeps the pressure valve open
                # without the thrash.
                if (self.preempt_on_oom
                        and not self.parked and not self._swapping
                        and self._oom_preempted_for != req.rid):
                    if self.preempt_lru(reason="admission-oom") is not None:
                        self._oom_preempted_for = req.rid
                return True
            self.queue.popleft()
            free.pop(0)
            req.slot = slot
            req.admit_step = self._step_count
            req.admit_time = now
            # prefix hit: prefill resumes AT the covered frontier — only
            # the uncovered tail runs through the chunk programs
            req.prefill_done = hit.covered if hit is not None else 0
            self.resident[slot] = req
            self.positions[slot] = 0
            self.remaining[slot] = 0  # decode-armed after the last chunk
            self._admitted += 1
            self._adm_latency_steps += self._step_count - req.submit_step
            self._adm_latency_s += now - req.submit_time
            self.queue_wait.observe(now - req.submit_time)
            spans.tracer().record("req.queue", req.submit_time, now,
                                  rid=req.rid)
            self._admitted_prefill_tokens += req.length - req.prefill_done
            if hit is not None:
                self._prefix_covered_tokens += hit.covered
                self._log_prefix(req, hit)
            self.flightrec.record(
                "admit", rid=req.rid, slot=slot, replica=self.replica_id
            )
            if self.reqtrace.enabled:
                self.reqtrace.end(
                    req.span_queue, slot=slot,
                    queue_wait_s=round(now - req.submit_time, 6),
                )
                req.span_queue = 0
                req.span_prefill = self.reqtrace.begin(
                    req.rid, "prefill", replica=self.replica_id,
                    slot=slot,
                    chunks=-(-(req.length - req.prefill_done)
                             // self.engine.chunk),
                    prefix_covered=req.prefill_done or None,
                )
            admitted += 1
        return False

    # ---- pressure tier: preempt, park, restore (round 13) ----------------

    def _victims(self) -> List[Tuple[float, int, int]]:
        """Eligible preemption victims, LRU-idle first: decode-armed
        resident requests (mid-prefill chains and handoff-parked
        ``ready`` requests are not preemptible), outside their post-
        restore protection window, not already mid-swap. Sorted by last
        token wall time (admit time for lanes yet to produce) so the
        stream that has gone longest without a token — the idlest
        conversation — pays first."""
        if not self.offload:
            return []
        import math as _math

        out = []
        for slot, req in self.resident.items():
            if req.prefill_done < req.length or slot in self._swap_slots:
                continue
            if req.rid in self.ready:
                continue  # held for fleet handoff, not ours to park
            if self._step_count < req.protect_until:
                continue
            last = req.last_token_time
            if _math.isnan(last):
                last = req.admit_time
            out.append((last, req.rid, slot))
        out.sort()
        return out

    def _swap_decision(self, req: Request, slot: int):
        """The per-request swap-vs-recompute verdict: the chain's bytes
        through the measured link vs the resume-prefill's chunks times
        the chunk program's measured wall (``telemetry.costmodel``),
        then the hard constraints — a resume sequence the table cannot
        hold forces swap, a host store without room forces recompute.
        Returns None when neither path is viable (the request is simply
        not preemptible right now)."""
        import dataclasses as _dc

        from pytorch_distributed_tpu.telemetry.costmodel import (
            swap_vs_recompute,
        )

        chain_len = len(self.engine.allocator.chain(slot))
        bytes_to_move = self.engine.chain_bytes(chain_len)
        seq_len = req.length + len(req.generated or ())
        c = self.engine.chunk
        chunks = -(-seq_len // c)
        # the chunk program a recompute would run: the measured mean
        # wall of any hot chunk bucket (the cost-card join side —
        # buckets differ by padding, not asymptotics; None when nothing
        # has measured yet and the decision falls to its default)
        chunk_wall = None
        for prog, (n, s) in self.prog_times.items():
            if prog.startswith("chunk_prefill[") and n > 0:
                chunk_wall = s / n
                break
        decision = swap_vs_recompute(
            bytes_to_move, chunks=chunks, chunk_wall_s=chunk_wall,
        )
        if self.swap_policy != "auto":
            decision = _dc.replace(decision, choice=self.swap_policy,
                                   reason=f"forced-{self.swap_policy}")
        # hard constraints override the cost verdict
        padded = -(-seq_len // c) * c
        need = self.engine.blocks_for(seq_len,
                                      req.max_new_tokens - req.produced)
        can_recompute = (
            padded <= self.config.max_seq_len
            and need <= min(self.engine.table_width,
                            self.engine.allocator.n_blocks - 1)
        )
        store_ok = self.host_store.has_room(bytes_to_move)
        if decision.choice == "recompute" and not can_recompute:
            decision = _dc.replace(decision, choice="swap",
                                   reason="recompute-overflows-table")
        elif decision.choice == "swap" and not store_ok:
            if not can_recompute:
                return None
            decision = _dc.replace(decision, choice="recompute",
                                   reason="host-store-full")
        return decision

    def preempt_lru(self, reason: str = "pressure") -> Optional[int]:
        """Preempt the least-recently-served eligible victim; returns
        its rid (None when nothing is preemptible — the caller's cue
        that shedding really is the last resort)."""
        # async host loop: an in-flight tick may be decoding the victim
        # — collect it first so the victim's produced/generated state is
        # current and its chain release cannot race the launched program
        self._collect_pending_tick()
        for _, rid, _slot in self._victims():
            if self.preempt(rid, reason=reason) is not None:
                return rid
        return None

    def preempt(self, rid: int, reason: str = "pressure"):
        """Park request ``rid``: its decision picks swap (chain leaves
        for the host store through the compiled gather + d2h) or
        recompute (chain dropped now, the stream's tokens re-prefill as
        prompt at restore). Either way the lane stops decoding THIS tick
        and the request is restored — before its next decode — by
        ``_restore_parked`` once capacity allows. Returns the
        ``SwapDecision`` (None when the request is not preemptible)."""
        # same in-flight hazard as preempt_lru (direct callers exist)
        self._collect_pending_tick()
        slot = next(
            (s for s, r in self.resident.items() if r.rid == rid), None
        )
        if slot is None:
            raise ValueError(f"rid {rid} is not resident")
        req = self.resident[slot]
        if req.prefill_done < req.length:
            raise ValueError(f"rid {rid} is mid-prefill: not preemptible")
        decision = self._swap_decision(req, slot)
        if decision is None:
            return None
        if self.reqtrace.enabled:
            # the preempt sub-tree: the open decode window ends here
            # (outcome=preempted) and everything until the restore —
            # swap_out, parked, swap_in — nests under this span, with
            # the decision's predicted costs attached for the
            # predicted-vs-measured join
            self.reqtrace.end(req.span_decode, outcome="preempted")
            req.span_decode = 0
            req.span_preempt = self.reqtrace.begin(
                rid, "preempt", replica=self.replica_id, reason=reason,
                decision=decision.choice,
                decision_reason=decision.reason,
                predicted_swap_s=decision.swap_s,
                predicted_recompute_s=decision.recompute_s,
                bytes=decision.bytes_to_move, chunks=decision.chunks,
            )
        if decision.choice == "recompute":
            del self.resident[slot]
            self.remaining[slot] = 0
            self.engine.release(slot)
            self.parked[rid] = (req, "recompute")
            self._decision_recompute += 1
            if self.reqtrace.enabled:
                req.span_parked = self.reqtrace.begin(
                    rid, "parked", parent=req.span_preempt,
                    replica=self.replica_id, path="recompute",
                )
        else:
            if self.reqtrace.enabled:
                req.span_swap = self.reqtrace.begin(
                    rid, "swap_out", parent=req.span_preempt,
                    replica=self.replica_id,
                )
            pending = self.engine.swap_out_begin(slot)  # jaxlint: disable=lifecycle-span-imbalance -- cross-tick window protocol: the span closes in _finalize_swaps at the top of the next step() (and in begin_drain), never in this function; _swap_slots tracks the open window meanwhile
            del self.resident[slot]
            self.remaining[slot] = 0
            self._swap_slots.add(slot)
            self._swapping.append(
                (rid, req, pending, time.perf_counter(), decision)
            )
            self._decision_swap += 1
        req.preempts += 1
        self._preempts += 1
        self.flightrec.record(
            "preempt", rid=rid, slot=slot, reason=reason,
            decision=decision.choice, replica=self.replica_id,
        )
        if self.metrics_log is not None:
            self.metrics_log.log(
                kind="preempt", rid=rid, replica_id=self.replica_id,
                reason=reason, decision=decision.choice,
                decision_reason=decision.reason,
                predicted_swap_s=decision.swap_s,
                predicted_recompute_s=decision.recompute_s,
                bytes=decision.bytes_to_move, chunks=decision.chunks,
                produced=req.produced, queue_depth=len(self.queue),
            )
        return decision

    def _finalize_swaps(self) -> None:
        """Close every open swap-out window: materialize the d2h copy,
        commit the host chain, free the device chain. A failure at
        either hazard site (``kv.swap_out_d2h``, ``kv.host_write``)
        REVERTS the preemption — the chain never left, so the lane is
        re-armed and the stream continues bit-exact."""
        if not self._swapping:
            return
        pending, self._swapping = self._swapping, []
        for rid, req, pend, t0, decision in pending:
            slot = pend.slot
            try:
                chain = self.engine.swap_out_finish(
                    pend, self.host_store, rid
                )
            except OSError as e:
                # revert: chain untouched on device; re-arm the lane
                self.resident[slot] = req
                self.remaining[slot] = req.max_new_tokens - req.produced
                self._swap_slots.discard(slot)
                self._swap_aborts += 1
                if self.reqtrace.enabled:
                    self.reqtrace.end(req.span_swap, ok=False,
                                      error=str(e))
                    req.span_swap = 0
                    self.reqtrace.end(req.span_preempt, outcome="aborted")
                    req.span_preempt = 0
                    # reverted == decoding again: a fresh decode window
                    req.span_decode = self.reqtrace.begin(
                        rid, "decode", replica=self.replica_id, lane=slot,
                        resumed="swap-abort",
                    )
                self.flightrec.record(
                    "swap_abort", rid=rid, direction="out", error=str(e),
                    replica=self.replica_id,
                )
                if self.metrics_log is not None:
                    self.metrics_log.log(
                        kind="swap", rid=rid, replica_id=self.replica_id,
                        direction="out", ok=False, error=str(e),
                    )
                continue
            wall = time.perf_counter() - t0
            self._swap_slots.discard(slot)
            self.parked[rid] = (req, "swap")
            self._swap_outs += 1
            self._swap_bytes += chain.nbytes
            self.swap_lat.observe(wall)
            if self.reqtrace.enabled:
                # predicted next to measured: the decision audit trail
                self.reqtrace.end(
                    req.span_swap, ok=True, bytes=chain.nbytes,
                    wall_s=round(wall, 6),
                    predicted_s=decision.swap_s,
                )
                req.span_swap = 0
                req.span_parked = self.reqtrace.begin(
                    rid, "parked", parent=req.span_preempt,
                    replica=self.replica_id, path="swap",
                )
            self.flightrec.record(
                "swap", rid=rid, direction="out", bytes=chain.nbytes,
                replica=self.replica_id,
            )
            if self.metrics_log is not None:
                self.metrics_log.log(
                    kind="swap", rid=rid, replica_id=self.replica_id,
                    direction="out", ok=True, bytes=chain.nbytes,
                    wall_s=round(wall, 6),
                    predicted_s=decision.swap_s,
                )

    def _restore_parked(self) -> None:
        """Restore parked requests FIFO, before this tick's admissions
        (a preempted request outranks a queued one — it already earned
        its admission). Swap path: fresh chain + h2d + donated scatter,
        lane re-armed at its exact frontier. Recompute path: the
        stream's tokens join the prompt and the request re-prefills —
        the final chunk's logits row reproduces the exact next-token
        distribution, so greedy streams resume token-identical either
        way. A restore that cannot proceed (no slot, no chain, injected
        h2d fault) leaves the request parked and retries next tick."""
        for rid in list(self.parked):
            req, path = self.parked[rid]
            free = self._free_slots()
            if not free:
                break
            slot = free[0]
            t0 = time.perf_counter()
            if path == "swap":
                chain = self.host_store.get(rid)
                self._slot2rid[slot] = rid
                try:
                    if not self.engine.swap_in_chain(slot, chain):
                        self._slot2rid.pop(slot, None)
                        break  # no chain free: retry when blocks return
                except OSError as e:
                    self._slot2rid.pop(slot, None)
                    self._swap_aborts += 1
                    if self.reqtrace.enabled:
                        self.reqtrace.event(
                            rid, "swap_abort", parent=req.span_preempt,
                            replica=self.replica_id, direction="in",
                            error=str(e),
                        )
                    self.flightrec.record(
                        "swap_abort", rid=rid, direction="in",
                        error=str(e), replica=self.replica_id,
                    )
                    if self.metrics_log is not None:
                        self.metrics_log.log(
                            kind="swap", rid=rid,
                            replica_id=self.replica_id,
                            direction="in", ok=False, error=str(e),
                        )
                    break  # host copy intact; retry next tick
                self.host_store.pop(rid)
                wall = time.perf_counter() - t0
                self._swap_ins += 1
                self._swap_bytes += chain.nbytes
                self.swap_lat.observe(wall)
                if self.metrics_log is not None:
                    self.metrics_log.log(
                        kind="swap", rid=rid, replica_id=self.replica_id,
                        direction="in", ok=True, bytes=chain.nbytes,
                        wall_s=round(wall, 6),
                    )
                del self.parked[rid]
                req.slot = slot
                self.resident[slot] = req
                self.positions[slot] = req.length + req.produced
                self.remaining[slot] = req.max_new_tokens - req.produced
                if self.reqtrace.enabled:
                    span_in = self.reqtrace.begin(
                        rid, "swap_in", parent=req.span_preempt,
                        replica=self.replica_id, t=t0,
                    )
                    self.reqtrace.end(span_in, ok=True,
                                      bytes=chain.nbytes,
                                      wall_s=round(wall, 6))
                    req.span_decode = self.reqtrace.begin(
                        rid, "decode", replica=self.replica_id,
                        lane=slot, resumed="swap",
                    )
            else:  # recompute: generated tokens re-prefill as prompt
                seq = req.tokens
                if req.generated:
                    seq = np.concatenate([
                        req.tokens,
                        np.asarray(req.generated, np.int32),
                    ])
                self._slot2rid[slot] = rid
                if self.prefix_cache:
                    # the restore's re-prefill consults the index too: a
                    # request whose own prompt blocks are still retained
                    # re-prefills only its generated tail — recompute
                    # preemption gets cheaper with the cache on
                    hit = self.engine.admit_shared(
                        slot, seq, req.max_new_tokens - req.produced
                    )
                    restored_ok = hit is not None
                else:
                    hit = None
                    restored_ok = self.engine.admit(
                        slot, len(seq), req.max_new_tokens - req.produced
                    )
                if not restored_ok:
                    self._slot2rid.pop(slot, None)
                    break  # pool OOM: retry when blocks return
                del self.parked[rid]
                req.tokens = seq
                req.generated = []  # consumed into the prompt
                req.prefill_done = hit.covered if hit is not None else 0
                if hit is not None:
                    self._prefix_covered_tokens += hit.covered
                    self._log_prefix(req, hit)
                self._admitted_prefill_tokens += (
                    req.length - req.prefill_done
                )
                req.slot = slot
                self.resident[slot] = req
                self.positions[slot] = 0
                self.remaining[slot] = 0  # armed by its final chunk
                if self.reqtrace.enabled:
                    req.span_prefill = self.reqtrace.begin(
                        rid, "prefill", replica=self.replica_id,
                        slot=slot, resumed="recompute",
                        chunks=-(-(len(seq) - req.prefill_done)
                                 // self.engine.chunk),
                        prefix_covered=req.prefill_done or None,
                    )
            req.protect_until = self._step_count + self.protect_ticks
            self._restores += 1
            if self.reqtrace.enabled:
                self.reqtrace.end(req.span_parked)
                req.span_parked = 0
                self.reqtrace.event(
                    rid, "restore", parent=req.span_preempt,
                    replica=self.replica_id, slot=slot, path=path,
                )
                self.reqtrace.end(req.span_preempt)
                req.span_preempt = 0
            self.flightrec.record(
                "restore", rid=rid, slot=slot, path=path,
                replica=self.replica_id,
            )

    def _chunk_jobs(self):
        from pytorch_distributed_tpu.serving.engine import ChunkJob

        c = self.engine.chunk
        jobs = []
        pending = [(slot, req) for slot, req in sorted(self.resident.items())
                   if req.prefill_done < req.length]
        if len(pending) > self.engine.max_chunk_jobs:
            # more prompts than one chunk program takes: the oldest
            # first, the rest wait a tick
            pending = sorted(pending, key=lambda p: p[1].rid)
            pending = sorted(pending[:self.engine.max_chunk_jobs],
                             key=lambda p: p[0])
        for slot, req in pending:
            start = req.prefill_done
            seg = req.tokens[start:start + c]
            tokens = np.zeros((c,), np.int32)
            tokens[:len(seg)] = seg
            is_last = start + c >= req.length
            jobs.append(ChunkJob(
                slot=slot, tokens=tokens, start=start, is_last=is_last,
                last_idx=(req.length - 1 - start) if is_last else 0,
            ))
        return jobs

    def dispatch_tick(self) -> None:
        """The non-blocking half of one tick: restores/admissions → one
        prefill chunk per unfinished prompt (ONE compiled program) →
        the decode program LAUNCHED (not materialized). Parks a
        ``TickHandle`` for ``collect_tick``."""
        if self._pending_tick is not None:
            raise RuntimeError(
                "collect_tick() must drain the pending tick before "
                "another dispatch (one tick in flight per replica)"
            )
        # replica-death site: before ANY tick work, so a fault here
        # leaves the resident set exactly as the last collect left it —
        # the state the router's harvest/re-dispatch path must recover
        fault_point("serve.dispatch")
        if self._start_time is None:
            self._start_time = time.perf_counter()
        t_step0 = time.perf_counter()
        tr = spans.tracer()
        with tr.span("sched.expire"):
            self._expire_deadlines()
        if self.offload:
            # pressure tier: close last tick's swap-out windows (their
            # blocks return to the pool), then restore parked requests
            # BEFORE admitting new ones — a preempted request resumes
            # ahead of the queue, before its next decode tick
            self._finalize_swaps()
            self._restore_parked()
        with tr.span("sched.admit", queued=len(self.queue)) as admit:
            admit.args["waited"] = self._admit()
            admit.args["free_blocks"] = self.engine.allocator.available
        with tr.span("sched.chunk_plan"):
            jobs = self._chunk_jobs()
        if jobs:
            # cold bucket: this batch's (k_pad, wp) program has never
            # executed — the call below stalls for its compile (or a
            # persistent-cache load after an AOT-only warmup). Mark every
            # request riding the batch and book the stall as compile time.
            bucket = self.engine.bucket_for(jobs)
            cold_bucket = not self.engine.has_chunk_program(*bucket)
            if cold_bucket:
                for j in jobs:
                    self.resident[j.slot].cold = True
            t_chunk = time.perf_counter()
            with attribute_compile(self.goodput if cold_bucket else None):
                self.engine.run_chunks(jobs)
            if not cold_bucket:
                # cost-card join: warm dispatch wall attributed to THIS
                # bucket's program (cold calls excluded — their wall is
                # compile, already booked to goodput above)
                self.prog_times.observe(
                    self.engine.chunk_program_name(*bucket),
                    time.perf_counter() - t_chunk,
                )
            for j in jobs:
                req = self.resident[j.slot]
                if self.reqtrace.enabled:
                    self.reqtrace.event(
                        req.rid, "prefill_chunk",
                        parent=req.span_prefill,
                        replica=self.replica_id, start=j.start,
                        program=self.engine.chunk_program_name(*bucket),
                        cold=cold_bucket or None,
                    )
                req.prefill_done += self.engine.chunk
                if self.prefix_cache:
                    # insert on block-boundary fill: every full PROMPT
                    # block the chunk just completed becomes index-
                    # reachable NOW, so a same-prefix request later in
                    # this very burst hits before this one retires.
                    # Decode-written blocks stay un-indexed — only
                    # prefill-computed KV is proven token-stable
                    # (ANALYSIS.md "Prefix sharing & copy-on-write")
                    self.engine.prefix_insert(
                        j.slot, req.tokens,
                        upto=min(req.prefill_done, req.length),
                    )
                if req.prefill_done >= req.length:
                    # prefill complete: arm the decode lane at the
                    # prompt's true frontier — or, on a prefill-only
                    # replica, park the request (blocks + slot held) in
                    # ``ready`` for the router's decode handoff
                    self.positions[j.slot] = req.length
                    if self.reqtrace.enabled:
                        self.reqtrace.end(req.span_prefill)
                        req.span_prefill = 0
                    if self.prefill_only:
                        self.ready[req.rid] = j.slot
                        if self._san is not None:
                            # the chain is promised to a decode replica:
                            # freeing it before complete_handoff is a
                            # pinned-block violation only the sanitizer
                            # can see (the allocator has no pin notion)
                            self._san.pin(j.slot, "handoff")
                        if self.reqtrace.enabled:
                            req.span_ready = self.reqtrace.begin(
                                req.rid, "handoff_wait",
                                replica=self.replica_id,
                            )
                    else:
                        # produced > 0 only after a recompute restore:
                        # the re-prefilled stream resumes what is left
                        # of its original decode budget
                        self.remaining[j.slot] = (
                            req.max_new_tokens - req.produced
                        )
                        if self.reqtrace.enabled:
                            req.span_decode = self.reqtrace.begin(
                                req.rid, "decode",
                                replica=self.replica_id, lane=j.slot,
                            )
        active = self.remaining > 0
        self._occupancy_sum += len(self.resident) / self.n_slots
        self._step_count += 1
        if not active.any():
            self._pending_tick = TickHandle(
                None, None, (), t_step0, t_step0, False,
            )
            return
        if self.engine.temperature == 0.0:
            # greedy: _sample is a pure argmax and never reads the key
            # — the per-tick threefry split was ~14% of the serve
            # loop's host wall (round-16 profile) spent preparing an
            # unused input. The key still rides along (same program
            # signature, zero recompiles); sampled runs split as ever.
            sub = self._rng
        else:
            self._rng, sub = jax.random.split(self._rng)
        cold_decode = not self.engine.has_decode_program
        if cold_decode:
            # every active lane's token this tick arrives through the
            # decode program's first compile — those requests are cold
            for slot in np.nonzero(active)[0]:
                self.resident[int(slot)].cold = True
        t_dec = time.perf_counter()
        with attribute_compile(self.goodput if cold_decode else None):
            tokens, positions = self.engine.decode_launch(
                self.positions, active, sub
            )
        lanes = tuple(int(s) for s in np.nonzero(active)[0])
        self._pending_tick = TickHandle(
            tokens, positions, lanes, t_step0, t_dec, cold_decode,
        )

    def collect_tick(self) -> List[Tuple[int, int]]:
        """The blocking half: materialize the pending tick's tokens and
        run all per-token host work (TTFT/latency series, retirement,
        JSONL). Returns ``[(rid, token)]`` — including anything an
        early collect (preempt/drain) stashed since the last call.
        No-op without a pending tick."""
        # replica-death site: the tick's device tokens are lost with the
        # replica (the router-facing collect only — the early collects
        # inside preempt/cancel/drain are the same process surviving)
        fault_point("serve.collect")
        self._collect_pending_tick()
        out, self._collected = self._collected, []
        return out

    @property
    def tick_in_flight(self) -> bool:
        """True while a launched tick's tokens wait on the device for
        ``collect_tick`` (a tick with no active decode lane parks a
        handle too, with nothing to collect)."""
        h = self._pending_tick
        return h is not None and h.tokens is not None

    @property
    def has_uncollected(self) -> bool:
        """True while a token-bearing tick is in flight or collected
        tokens await delivery — the router's drain loop must keep
        stepping (``idle`` alone reads host state, which a pending tick
        is about to change)."""
        return bool(self._collected) or self.tick_in_flight

    def _collect_pending_tick(self) -> None:
        h = self._pending_tick
        if h is None:
            return
        self._pending_tick = None
        if h.tokens is None:
            self._observe_tick(h.t_step0)
            return
        tokens, positions = self.engine.decode_collect(
            h.tokens, h.positions
        )
        # ``positions`` is the engine's host count as of the launch (the
        # launched rows, plus one where the tick decoded): write back
        # ONLY the lanes this tick decoded, so that rows the host armed
        # since the launch (an adopted handoff chain, a restored swap)
        # are not clobbered by the launch's frozen copies
        lanes = np.asarray(h.lanes, np.int64)
        self.positions[lanes] = positions[lanes]
        # tokens materialized above, so this timestamp is
        # token-delivery time, not dispatch time
        now = time.perf_counter()
        if not h.cold_decode:
            # cost-card join: dispatch + device + sync — the honest
            # decode-tick cost (the sync lands here, at collect, where
            # the stream actually pays it)
            self.prog_times.observe(self.engine.DECODE_PROGRAM,
                                    now - h.t_dec)
        out: List[Tuple[int, int]] = []
        with spans.tracer().span("sched.collect.process") as process:
            counts = self.engine.tick_expert_counts
            if counts is not None and counts.size:
                # what the tick's expert layers took (fetched with its
                # tokens): the fullest expert's pairs and the experts
                # with a pair, each a mean over the layers; the (lane,
                # expert) pairs that landed on an expert held here in the
                # first expert layer (every live lane where all are held
                # and a lane takes one), and the pairs a layer routed in
                # all: live lanes x experts a token
                process.args.update(
                    expert_tokens_peak=float(counts.max(axis=1).mean()),
                    experts_hit=float((counts > 0).sum(axis=1).mean()),
                    routed=int(counts[0].sum()),
                    pairs=len(h.lanes) * self.engine.config.moe_top_k,
                )
            self._process_collected(h, tokens, now, out)
        self._collected.extend(out)
        if (self.host_pool is not None and out
                and self._step_count - self._gate_refreshed_step
                >= self.gate_refresh_ticks):
            self._gate_refreshed_step = self._step_count
            self._queue_gate_refresh()

    def _process_collected(self, h: TickHandle, tokens, now: float,
                           out: List[Tuple[int, int]]) -> None:
        """Per-token host work for one collected tick: latency series,
        stream bookkeeping, retirement (slot + chain release), JSONL."""
        for slot in h.lanes:
            req = self.resident[slot]
            token = int(tokens[slot])
            out.append((req.rid, token))
            if req.produced == 0:
                req.first_token_time = now
                req.first_token_step = self._step_count
                self.ttft.observe(now - req.submit_time)
                if self.sentinel is not None and not req.cold:
                    # warm TTFT only: a cold request's compile stall is a
                    # known cause, already attributed — not an anomaly
                    self._note_anomaly(self.sentinel.observe(
                        "ttft", now - req.submit_time, rid=req.rid,
                        tick=self._step_count,
                    ))
                if not req.cold:
                    self.ttft_warm.observe(now - req.submit_time)
            else:
                gap = now - req.last_token_time
                req.token_gaps.append(gap)
                self.token_lat.observe(gap)
            req.last_token_time = now
            req.produced += 1
            if req.generated is not None:
                # offload mode keeps the stream so a recompute restore
                # can re-prefill it as prompt
                req.generated.append(token)
            self._tokens_out += 1
            if (self.eos_id is not None and token == self.eos_id) or \
                    req.produced >= req.max_new_tokens:
                self.remaining[slot] = 0
                del self.resident[slot]
                self.engine.release(slot)
                if self._san is not None:
                    self._san.check_retire(slot, rid=req.rid,
                                           site="retire")
                self._completed += 1
                if req.cold:
                    self._cold_requests += 1
                self.flightrec.record(
                    "retire", rid=req.rid, tokens=req.produced,
                    replica=self.replica_id,
                )
                if self.reqtrace.enabled:
                    self.reqtrace.end(req.span_decode,
                                      tokens=req.produced)
                    req.span_decode = 0
                    self.reqtrace.end(
                        self.reqtrace.root(req.rid),
                        outcome="complete", new_tokens=req.produced,
                        preempts=req.preempts or None,
                    )
                self._log_request(req)
                if self.on_retire is not None:
                    self.on_retire(req.rid, "complete")
            else:
                self.remaining[slot] -= 1
        if out:
            self.tick_lat.observe(now - h.t_step0)
        if self._san is not None:
            # use-after-free sweep: every id the decode program can read
            # next tick must be ledger-live (the trash row aside)
            from pytorch_distributed_tpu.serving.kv_pool import TRASH_BLOCK
            self._san.check_tables(self.engine.tables,
                                   trash_block=TRASH_BLOCK)
        self._observe_tick(h.t_step0)

    def step(self) -> List[Tuple[int, int]]:
        """One whole tick, launched then collected in the same call
        (admissions → one prefill chunk per unfinished prompt → one
        decode token per ready lane → retirements). Returns ``[(rid,
        token)]``. A tick a lagged driver left pending is collected
        first, so mixing the two never drops a token."""
        out = self.collect_tick()
        self.dispatch_tick()
        return out + self.collect_tick()

    def _note_anomaly(self, hit: Optional[dict]) -> None:
        if hit is not None:
            self._last_anomaly_step = self._step_count

    def _observe_tick(self, t_step0: float) -> None:
        """Per-tick sentinel feed: tick wall and queue depth (every tick,
        both return paths of ``step``). With a host pool the median/MAD
        math (a measured ~15% of the serve loop's host wall) runs on a
        worker — the sentinel is internally locked, the fed values are
        captured here, and a hit latches ``_last_anomaly_step`` to the
        captured tick (a single int store; monotone-enough for the
        64-tick ``anomaly_recent`` window it feeds)."""
        if self.sentinel is None:
            return
        wall = time.perf_counter() - t_step0
        depth = float(len(self.queue))
        tick = self._step_count
        if self.host_pool is not None:
            self._tick_obs.append((wall, depth, tick))
            if len(self._tick_obs) >= self.tick_obs_batch:
                self.flush_host_work()
            return
        self._note_anomaly(self.sentinel.observe(
            "tick_time", wall, tick=tick,
        ))
        self._note_anomaly(self.sentinel.observe(
            "queue_depth", depth, tick=tick,
        ))

    def flush_host_work(self) -> None:
        """Ship the buffered per-tick sentinel observations to a worker
        as ONE task (in-order within the batch; a hit latches
        ``_last_anomaly_step`` to its tick — single int store, benign).
        The router calls this before its pool barrier so the tail of a
        drain is observed too. No-op without a pool or a buffer."""
        if self.host_pool is None or not self._tick_obs:
            return
        batch, self._tick_obs = self._tick_obs, []

        def work():
            last_hit = None
            for wall, depth, tick in batch:
                h1 = self.sentinel.observe("tick_time", wall, tick=tick)
                h2 = self.sentinel.observe("queue_depth", depth,
                                           tick=tick)
                if h1 is not None or h2 is not None:
                    last_hit = tick
            if last_hit is not None:
                self._last_anomaly_step = last_hit

        self.host_pool.submit(work)

    def _log_prefix(self, req: Request, hit) -> None:
        """One ``kind="prefix"`` JSONL record per shared-prefix
        admission (schema-registered; ``telemetry_report.py`` renders
        the hit-rate/covered-fraction section from these): what the
        index covered, how many blocks rode shared, and whether the
        boundary block was copy-on-write duplicated."""
        if self.metrics_log is None:
            return
        self.metrics_log.log(
            kind="prefix", rid=req.rid, replica_id=self.replica_id,
            prompt_len=req.length, covered=hit.covered,
            shared_blocks=hit.shared, cow=hit.cow,
            evicted=hit.evicted, session=req.session,
        )

    def _log_request(self, req: Request) -> None:
        """One ``kind="request"`` JSONL record per retirement — the raw
        per-request latencies ``telemetry_report.py`` aggregates. With a
        ``host_pool`` the serialization+write runs on a worker thread:
        a retired ``Request`` is never mutated again (it left
        ``resident`` in the same collect that enqueues this), so the
        closure captures an effectively-frozen object; the logger is
        self-locked."""
        if self.metrics_log is None:
            return
        if self.host_pool is not None:
            self.host_pool.submit(lambda: self._log_request_record(req))
            return
        self._log_request_record(req)

    def _log_request_record(self, req: Request) -> None:
        self.metrics_log.log(
            kind="request",
            rid=req.rid,
            replica_id=self.replica_id,
            rejected=False,
            session=req.session,
            spilled=req.spilled,
            prompt_len=req.orig_len if req.orig_len >= 0 else req.length,
            new_tokens=req.produced,
            preempts=req.preempts,
            cold=req.cold,
            queue_wait_s=round(req.admit_time - req.submit_time, 6),
            ttft_s=round(req.first_token_time - req.submit_time, 6),
            queue_wait_steps=req.admit_step - req.submit_step,
            ttft_steps=req.first_token_step - req.submit_step,
            token_gaps_s=[round(g, 6) for g in req.token_gaps],
        )

    @property
    def idle(self) -> bool:
        """Nothing queued, resident, parked, or mid-swap — the drain
        loops' (and the fleet router's) termination condition; a parked
        request is in-flight work, not absence of it."""
        return (not self.queue and not self.resident
                and not self.parked and not self._swapping)

    def stuck_rids(self) -> Dict[str, List[int]]:
        """Every in-flight rid by lifecycle state — the drain loops'
        non-convergence diagnostic (an empty dict == idle). A stuck
        drain that only reported counts forced a debugger session; the
        chaos matrix asserts on THIS surface instead."""
        out: Dict[str, List[int]] = {}
        if self.queue:
            out["queued"] = [r.rid for r in self.queue]
        prefill, decoding = [], []
        for req in self.resident.values():
            if req.rid in self.ready:
                continue
            (prefill if req.prefill_done < req.length
             else decoding).append(req.rid)
        if prefill:
            out["prefill"] = sorted(prefill)
        if decoding:
            out["decoding"] = sorted(decoding)
        if self.parked:
            out["parked"] = sorted(self.parked)
        if self._swapping:
            out["swapping"] = sorted(e[0] for e in self._swapping)
        if self.ready:
            out["handoff-ready"] = sorted(self.ready)
        return out

    def drain(self, max_steps: int = 100_000) -> Dict[int, List[int]]:
        """Step until queue and lanes are empty; returns
        ``{rid: [tokens]}``."""
        produced: Dict[int, List[int]] = {}
        for _ in range(max_steps):
            if self.idle:
                return produced
            for rid, tok in self.step():
                produced.setdefault(rid, []).append(tok)
        raise RuntimeError(
            f"drain did not converge within {max_steps} steps; "
            f"stuck rids by state: {self.stuck_rids()}"
        )

    # ---- graceful drain (fleet scale-down / replica removal) ----

    def begin_drain(self) -> None:
        """Stop admitting: ``submit`` raises, ``step`` skips admission.
        In-flight requests keep decoding to completion; the queue is
        frozen for ``drain_graceful`` to hand back to the router.

        Waits for in-flight swap-outs first (the drain-while-swapping
        race): a chain mid-d2h must either commit to the host store or
        revert to resident before any teardown path may free blocks —
        the allocator would refuse to free a ``swapping-out`` chain
        anyway (loudly), so closing the windows here keeps drains both
        safe AND quiet. Under the async loop a dispatched tick is
        collected first — its tokens stash for the next collect, so the
        drain starts from settled host state without dropping any."""
        self._collect_pending_tick()
        if self.offload:
            self._finalize_swaps()
        self.draining = True

    def drain_graceful(
        self, max_steps: int = 100_000
    ) -> Tuple[Dict[int, List[int]], List[Request]]:
        """Drain for scale-down: stop admitting, run every in-flight
        request to retirement, and return ``(produced, requeued)`` —
        the tokens the in-flight requests streamed, plus the queued
        (never-admitted) requests the router must re-route. After this
        returns, every pool block is back on the free list
        (``engine.allocator.in_use == 0``): retirement freed the
        in-flight chains and queued requests never held any.

        On a ``prefill_only`` replica the in-flight requests end parked
        in ``ready`` (their blocks intentionally held for handoff) — the
        router completes the handoffs, after which the pool is empty
        too."""
        self.begin_drain()
        requeued = list(self.queue)
        self.queue.clear()
        produced: Dict[int, List[int]] = {}
        for _ in range(max_steps):
            # parked/mid-swap requests are in-flight (they were already
            # admitted once): the drain restores and finishes them too
            if (not self.resident and not self.parked
                    and not self._swapping) or (
                self.prefill_only
                and not self.parked and not self._swapping
                and all(r.rid in self.ready
                        for r in self.resident.values())
            ):
                if self._san is not None and not self.ready:
                    # the documented post-condition, proven: ledger ≡
                    # allocator, no chains/windows/pins outstanding.
                    # (With chains still pinned in ``ready`` the router
                    # quiesces after completing the handoffs instead.)
                    self._san.verify_quiesce()
                return produced, requeued
            for rid, tok in self.step():
                produced.setdefault(rid, []).append(tok)
        raise RuntimeError(
            f"drain_graceful did not converge within {max_steps} "
            f"steps; stuck rids by state: {self.stuck_rids()}"
        )

    # ---- client cancellation (ROADMAP item 5's first rung) ----

    def cancel(self, rid: int, reason: str = "client-cancel",
               outcome: str = "cancelled") -> bool:
        """Abort request ``rid`` wherever it lives — queued, resident
        (mid-prefill or decoding), parked (either restore path), mid
        swap-out, or handoff-ready — freeing every resource it holds:
        device chain, host-store chain, slot, handoff pin. Closes the
        request's span tree with ``outcome`` (``"cancelled"`` for a
        client cancel; the deadline sweep passes ``"deadline"``).
        Returns True when the rid was found (False: already retired or
        unknown — a benign race, cancellation is idempotent).

        The blocksan cancellation-storm trace rides this path: after a
        storm over every lifecycle state, the ledger must equal the
        allocator with zero leaked blocks."""
        # an in-flight tick may be decoding the victim: collect first so
        # the chain release cannot race the launched program
        self._collect_pending_tick()
        for i, req in enumerate(self.queue):
            if req.rid == rid:
                del self.queue[i]
                self._finish_cancel(req, slot=None, reason=reason,
                                    outcome=outcome)
                return True
        if any(entry[0] == rid for entry in self._swapping):
            # close the open d2h window first: the chain either commits
            # to the host store (cancel the parked copy below) or
            # reverts to resident (release the chain below) — never
            # freed mid-window
            self._finalize_swaps()
        if rid in self.parked:
            req, path = self.parked.pop(rid)
            if path == "swap":
                self.host_store.pop(rid)
            self._finish_cancel(req, slot=None, reason=reason,
                                outcome=outcome)
            return True
        slot = next(
            (s for s, r in self.resident.items() if r.rid == rid), None
        )
        if slot is None:
            return False
        req = self.resident.pop(slot)
        self.ready.pop(rid, None)
        if self._san is not None:
            self._san.unpin(slot)
        self.remaining[slot] = 0
        self.engine.release(slot)
        self._slot2rid.pop(slot, None)
        if self._san is not None:
            self._san.check_retire(slot, rid=rid, site="cancel")
        self._finish_cancel(req, slot=slot, reason=reason,
                            outcome=outcome)
        return True

    def _expire_deadlines(self) -> None:
        """Per-tick deadline sweep (top of every ``dispatch_tick``):
        every live request whose absolute deadline has passed — queued,
        mid-prefill, decoding, parked (either path), mid swap-out, or
        handoff-ready — expires through the cancel machinery with
        ``outcome="deadline"``. Runs before restores/admissions so an
        expired parked request never burns a restore, and an expired
        queue head never burns a slot."""
        now = time.perf_counter()
        expired = [
            req.rid
            for bucket in (
                self.queue, self.resident.values(),
                (r for r, _ in self.parked.values()),
                (entry[1] for entry in self._swapping),
            )
            for req in bucket
            if req.deadline <= now
        ]
        for rid in expired:
            self.cancel(rid, reason="deadline-exceeded",
                        outcome="deadline")

    def _finish_cancel(self, req: Request, slot: Optional[int],
                       reason: str, outcome: str = "cancelled") -> None:
        """Shared cancellation tail: counters, flight record, span-tree
        closure (every open span ends, then the root, all with
        ``outcome`` — ``"cancelled"`` or ``"deadline"``)."""
        if outcome == "deadline":
            self._deadline_misses += 1
        else:
            self._cancelled += 1
        self.flightrec.record(
            "cancel", rid=req.rid, reason=reason, outcome=outcome,
            slot=slot if slot is not None else -1,
            tokens=req.produced, replica=self.replica_id,
        )
        if self.reqtrace.enabled:
            for name in ("span_decode", "span_prefill", "span_ready",
                         "span_swap", "span_parked", "span_preempt",
                         "span_queue"):
                sid = getattr(req, name)
                if sid:
                    self.reqtrace.end(sid, outcome=outcome)
                    setattr(req, name, 0)
            self.reqtrace.end(
                self.reqtrace.root(req.rid), outcome=outcome,
                new_tokens=req.produced, reason=reason,
            )
        if self.on_retire is not None:
            self.on_retire(req.rid, outcome)

    # ---- replica death: harvest + abandon (fleet failure plane) ----

    def harvest_requests(self) -> List[Request]:
        """Every in-flight ``Request`` this replica owns — queued,
        resident (mid-prefill, decoding, handoff-ready), parked, mid
        swap-out — in rid order. The router's failure plane calls this
        when the health plane declares the replica dead, BEFORE
        ``abandon`` tears it down: the records carry everything a
        re-dispatch needs (original prompt length, deadline, session,
        produced count, open span ids)."""
        reqs: Dict[int, Request] = {}
        for req in self.queue:
            reqs[req.rid] = req
        for req in self.resident.values():
            reqs[req.rid] = req
        for rid, (req, _path) in self.parked.items():
            reqs[rid] = req
        for entry in self._swapping:
            reqs[entry[0]] = entry[1]
        return [reqs[rid] for rid in sorted(reqs)]

    def abandon(self) -> None:
        """Tear down a replica the health plane declared dead: no tick
        of this scheduler ever runs again. The in-process analogue of
        the OS reclaiming a crashed worker — every device chain, open
        swap window, host-store chain, handoff pin, and queue entry is
        disposed of through the allocator's public API, and (under
        blocksan) the shadow ledger must agree the teardown leaked
        nothing (``verify_quiesce``). Tokens a dead replica produced
        but never delivered are LOST by design — the router's replay
        regenerates them; blocks are never lost.

        Each harvested request's open lifecycle spans end here with
        ``outcome="replica-lost"``; the ROOT stays open — the router
        decides its final outcome (re-dispatch → ``complete``, attempt
        cap → ``failed``, expired meanwhile → ``deadline``)."""
        if self.reqtrace.enabled:
            for req in self.harvest_requests():
                for name in ("span_decode", "span_prefill",
                             "span_ready", "span_swap", "span_parked",
                             "span_preempt", "span_queue"):
                    sid = getattr(req, name)
                    if sid:
                        self.reqtrace.end(sid, outcome="replica-lost")
                        setattr(req, name, 0)
                self.reqtrace.event(
                    req.rid, "replica_death", replica=self.replica_id,
                    produced=req.produced,
                )
        # a launched-but-uncollected tick is never collected: a dead
        # replica's device results are untrusted
        self._pending_tick = None
        self._collected.clear()
        self._tick_obs.clear()
        self.draining = True  # any straggler submit raises, loudly
        # open swap-out windows: close the allocator's swap state
        # WITHOUT committing (the d2h arrays are dropped), then the
        # chain frees like any other
        for entry in self._swapping:
            slot = entry[2].slot
            self.engine.allocator.clear_state(slot)
            self._swap_slots.discard(slot)
            self.engine.release(slot)
            self._slot2rid.pop(slot, None)
        self._swapping.clear()
        for rid, (req, path) in self.parked.items():
            if path == "swap":
                self.host_store.pop(rid)
        self.parked.clear()
        for slot in list(self.resident):
            req = self.resident.pop(slot)
            self.ready.pop(req.rid, None)
            if self._san is not None:
                self._san.unpin(slot)
            self.remaining[slot] = 0
            self.engine.release(slot)
            self._slot2rid.pop(slot, None)
            if self._san is not None:
                self._san.check_retire(slot, rid=req.rid,
                                       site="abandon")
        self.queue.clear()
        self.positions[:] = 0
        self.remaining[:] = 0
        self.flightrec.record("abandon", replica=self.replica_id)
        if self._san is not None:
            # the teardown gate: ledger ≡ allocator, no chain, window,
            # or pin outstanding — a dead replica may lose tokens,
            # never blocks
            self._san.verify_quiesce()

    # ---- prefill→decode handoff (fleet disaggregation) ----

    def ready_rids(self) -> List[int]:
        """Prefill-complete requests awaiting handoff, in rid order."""
        return sorted(self.ready)

    def peek_ready(self, rid: int):
        """``(request, KVExport)`` for a ready request, WITHOUT releasing
        it — the router calls ``adopt`` on the decode replica first and
        only then ``complete_handoff``, so a full decode pool leaves the
        request parked here, intact, for the next tick."""
        slot = self.ready[rid]
        return self.resident[slot], self.engine.export_chain(slot)

    def complete_handoff(self, rid: int) -> None:
        """The decode replica adopted the blocks: free this replica's
        copy (slot + chain) and account the handoff."""
        slot = self.ready.pop(rid)
        req = self.resident.pop(slot)
        if self.reqtrace.enabled:
            self.reqtrace.end(req.span_ready)
            req.span_ready = 0
        if self._san is not None:
            self._san.unpin(slot)  # adoption committed: free is legal now
        self.engine.release(slot)
        if self._san is not None:
            self._san.check_retire(slot, rid=rid, site="handoff-complete")
        self.remaining[slot] = 0
        self._handoffs += 1

    def adopt(self, req: Request, export) -> bool:
        """Adopt a prefill-complete request whose KV was exported from a
        prefill replica: allocate a slot + chain, import the blocks
        (``PagedEngine.import_chain`` — the cross-mesh ``device_put``),
        and arm the decode lane at the prompt frontier. Returns False
        (nothing changed, export still valid) when no slot or chain is
        available — the router retries next tick.

        The request keeps its fleet rid, submit timestamps, and
        admission timestamps from the prefill replica, so TTFT measured
        here is end-to-end (submit → queue → prefill → handoff → first
        decoded token)."""
        if self.prefill_only:
            raise RuntimeError("a prefill_only replica cannot adopt")
        if self.draining:
            return False
        free = self._free_slots()
        if not free:
            return False
        slot = free[0]
        self._slot2rid[slot] = req.rid
        if not self.engine.import_chain(slot, export):
            self._slot2rid.pop(slot, None)
            return False
        req.slot = slot
        req.prefill_done = req.length
        if req.admit_step < 0:  # adopted without a prior admission
            req.admit_step = self._step_count
            req.admit_time = time.perf_counter()
            self.queue_wait.observe(req.admit_time - req.submit_time)
        self.resident[slot] = req
        self.positions[slot] = req.length
        self.remaining[slot] = req.max_new_tokens
        self._admitted += 1
        self._adopted += 1
        if self.reqtrace.enabled:
            # the adopted decode window opens HERE, on this replica —
            # the router links the handoff span to it, so the trace
            # shows the request's timeline switching replicas
            req.span_decode = self.reqtrace.begin(
                req.rid, "decode", replica=self.replica_id, lane=slot,
                adopted=True,
            )
        return True

    # ---- cost cards (telemetry/costmodel.py) ----

    def log_cost_cards(self) -> list:
        """One ``kind="program_cost"`` JSONL record per registry program:
        the compiler's FLOP/byte statics joined with this scheduler's
        measured per-program tick wall (warm calls only — compile stalls
        are ledger ``compile`` time, not program cost). Building the
        statics AOT-compiles each not-yet-compiled bucket (a disk hit
        under the persistent cache), so call it once per run, after
        traffic — never inside the serve loop. Returns the records."""
        from pytorch_distributed_tpu.compilecache import serving_registry
        from pytorch_distributed_tpu.telemetry import log_cost_cards

        return log_cost_cards(
            serving_registry(self.engine), self.prog_times,
            self.metrics_log,
        )

    # ---- metrics ----

    def _queue_gate_refresh(self) -> None:
        """Refresh the gate-metrics snapshot OFF the critical path: the
        latency-series value lists are copied here on the main thread
        (cheap pointer copies); the worker does the O(n log n)
        percentile math and swaps the snapshot in under its lock. A
        stale refresh overwriting a newer one loses at most one tick of
        percentile drift — the live overlays in ``gate_metrics`` carry
        everything the depth-bound SLO rungs actually branch on."""
        vals = {
            "ttft": list(self.ttft.values),
            "queue_wait": list(self.queue_wait.values),
        }
        goodput_frac = self.goodput.report()["goodput_frac"]

        def work():
            snap = {"goodput_frac": goodput_frac}
            for name, v in vals.items():
                for q, val in percentiles(v, qs=(95,)).items():
                    snap[f"{name}_{q}_s"] = val
            with self._gate_lock:
                self._gate_cache = snap

        self.host_pool.submit(work)

    def gate_metrics(self) -> dict:
        """The SLO gate's routing view of this replica. Without a
        worker pool (a lone ``Scheduler``): the full (exact, O(n log n))
        ``metrics()``. Under the router's pool: the worker-refreshed
        percentile snapshot overlaid with LIVE cheap counters — queue
        depth, occupancy, draining, preemptible, anomaly — so every
        depth-bound decision the gate makes is byte-identical to what a
        lone scheduler's would be, and only the wall-clock percentile
        rungs see (at most ``gate_refresh_ticks`` of) staleness."""
        if self.host_pool is None:
            return self.metrics()
        with self._gate_lock:
            snap = dict(self._gate_cache) if self._gate_cache else {}
        snap.update(
            replica_id=self.replica_id,
            queue_depth=len(self.queue),
            occupancy=len(self.resident) / self.n_slots,
            occupancy_mean=(
                self._occupancy_sum / self._step_count
                if self._step_count else 0.0
            ),
            draining=self.draining,
            offload=self.offload,
            preemptible=len(self._victims()),
            anomaly_recent=self.anomaly_recent,
            prefix_cache=self.prefix_cache,
        )
        snap.setdefault("goodput_frac", 1.0)
        return snap

    @property
    def anomaly_recent(self) -> bool:
        """True while an anomaly lies within the last
        ``anomaly_recent_ticks`` ticks — the SLO gate's hot signal."""
        return (
            self._last_anomaly_step is not None
            and self._step_count - self._last_anomaly_step
            <= self.anomaly_recent_ticks
        )

    def live_requests(self) -> int:
        """In-flight requests this replica owns right now — queued,
        resident (prefill/decode/handoff-ready), parked, mid-swap-out.
        The census sweep's O(live) audit axis (round 21)."""
        return (len(self.queue) + len(self.resident) + len(self.parked)
                + len(self._swapping))

    def census_decls(self):
        """Bound declarations for every long-lived container on this
        scheduler (round 21 scale observatory; telemetry/census.py).
        The meta-test in tests/test_scale_obs.py fails if a container
        attr exists without a declaration — new per-request state must
        say how it is bounded."""
        from pytorch_distributed_tpu.telemetry.census import Decl

        return [
            Decl("queue", "live",
                 why="admission backlog; bounded by the SLO gate's "
                     "shed/backpressure ladder in a fleet, by the "
                     "caller's submit rate standalone"),
            Decl("resident", "fixed", cap=lambda s: s.n_slots,
                 why="slot-keyed; admission only fills free slots"),
            Decl("parked", "live",
                 why="preempted requests awaiting restore — a subset of "
                     "live requests; host_store byte budget bounds it "
                     "again from below"),
            Decl("_swapping", "fixed", cap=lambda s: s.n_slots,
                 why="open d2h windows; each holds a distinct slot"),
            Decl("_swap_slots", "fixed", cap=lambda s: s.n_slots,
                 why="slots mid-swap-out; subset of all slots"),
            Decl("ready", "fixed", cap=lambda s: s.n_slots,
                 why="handoff-ready rids each pin a slot HERE until "
                     "complete_handoff frees it"),
            Decl("_slot2rid", "fixed", cap=lambda s: s.n_slots,
                 why="slot-keyed reverse map; entries overwritten on "
                     "slot reuse, popped on free (audit candidate from "
                     "ISSUE 19 — proven slot-bounded, not rid-bounded)"),
            Decl("_collected", "fixed", cap=lambda s: 4 * s.n_slots,
                 why="early-collected tokens awaiting the next "
                     "collect_tick; at most a couple of ticks' worth "
                     "(≤ n_slots tokens each) can stash between drains"),
            Decl("_tick_obs", "fixed", cap=lambda s: 2 * s.tick_obs_batch,
                 why="sentinel feed batch, flushed every tick_obs_batch "
                     "observations"),
            Decl("_gate_cache", "fixed", cap=64,
                 why="one snapshot dict of gate percentile keys, "
                     "replaced wholesale each refresh"),
            # dotted reaches: bounded children whose containers would
            # otherwise escape the sweep
            Decl("ttft.values", "fixed", cap=lambda s: 2 * s.ttft.window,
                 why="LatencySeries percentile window (round 21 cap)"),
            Decl("ttft_warm.values", "fixed",
                 cap=lambda s: 2 * s.ttft_warm.window,
                 why="LatencySeries percentile window"),
            Decl("token_lat.values", "fixed",
                 cap=lambda s: 2 * s.token_lat.window,
                 why="LatencySeries percentile window"),
            Decl("queue_wait.values", "fixed",
                 cap=lambda s: 2 * s.queue_wait.window,
                 why="LatencySeries percentile window"),
            Decl("tick_lat.values", "fixed",
                 cap=lambda s: 2 * s.tick_lat.window,
                 why="LatencySeries percentile window"),
            Decl("swap_lat.values", "fixed",
                 cap=lambda s: 2 * s.swap_lat.window,
                 why="LatencySeries percentile window"),
            Decl("prog_times._acc", "fixed", cap=256,
                 why="per-program aggregates (closed program set)"),
            Decl("host_store._chains", "live",
                 why="one host copy per parked request"),
        ]

    def metrics(self) -> dict:
        """Exact host-side accounting; all counters, no device sync."""
        alloc_blocks = self.engine.allocator.in_use
        alloc_tokens = alloc_blocks * self.engine.block_len
        used_tokens = int(sum(
            # tokens actually written and live for the request: its
            # prefill frontier plus produced decode tokens
            min(r.prefill_done, r.length) + r.produced
            for r in self.resident.values()
        ))
        elapsed = (
            time.perf_counter() - self._start_time
            if self._start_time is not None else 0.0
        )
        return {
            "replica_id": self.replica_id,
            "draining": self.draining,
            "handoffs": self._handoffs,
            "adopted": self._adopted,
            "ready": len(self.ready),
            # the ledger's utilization view: share of this replica's wall
            # NOT lost to classified overheads (compile) — the
            # fleet autoscaler folds it in next to occupancy_mean
            "goodput_frac": self.goodput.report()["goodput_frac"],
            "steps": self._step_count,
            "queue_depth": len(self.queue),
            "occupancy": len(self.resident) / self.n_slots,
            "occupancy_mean": (
                self._occupancy_sum / self._step_count
                if self._step_count else 0.0
            ),
            "pool_blocks_in_use": alloc_blocks,
            "pool_frac_in_use": (
                alloc_blocks / (self.engine.allocator.n_blocks - 1)
            ),
            "padding_waste_frac": (
                1.0 - used_tokens / alloc_tokens if alloc_tokens else 0.0
            ),
            "admitted": self._admitted,
            "completed": self._completed,
            "cancelled": self._cancelled,
            "deadline_misses": self._deadline_misses,
            **(self.blocksan.summary()
               if self.blocksan is not None else {}),
            "tokens_out": self._tokens_out,
            "tokens_per_s": self._tokens_out / elapsed if elapsed else 0.0,
            "admission_latency_steps_mean": (
                self._adm_latency_steps / self._admitted
                if self._admitted else 0.0
            ),
            "admission_latency_s_mean": (
                self._adm_latency_s / self._admitted
                if self._admitted else 0.0
            ),
            # cold-start honesty: how many retired requests ate a compile
            # stall, and the compile seconds the ledger attributed —
            # warm-only TTFT is the SLO series, plain ttft includes cold
            "cold_requests": self._cold_requests,
            "compile_s": self.goodput.seconds("compile"),
            # pressure tier (round 13): what the SLO gate's preempt rung
            # reads (offload capability + eligible victims right now)
            # and the swap machinery's exact counters
            "offload": self.offload,
            "preemptible": len(self._victims()),
            "parked": len(self.parked),
            "preempts": self._preempts,
            "restores": self._restores,
            "swap_outs": self._swap_outs,
            "swap_ins": self._swap_ins,
            "swap_aborts": self._swap_aborts,
            "swap_bytes": self._swap_bytes,
            "decision_swap": self._decision_swap,
            "decision_recompute": self._decision_recompute,
            "host_store_bytes": (
                self.host_store.bytes_used if self.offload else 0
            ),
            # prefix-sharing tier (round 17): index hit rate, sharing
            # census, COW count, and the admitted-prefill-token sum the
            # --prefix A/B divides by requests (exact, host-side)
            **self.engine.prefix_metrics(),
            "prefix_covered_tokens": self._prefix_covered_tokens,
            "admitted_prefill_tokens": self._admitted_prefill_tokens,
            **self.swap_lat.summary("swap"),
            # anomaly sentinel (telemetry/anomaly.py): total hits and the
            # recency flag the fleet SLOGate treats as hot
            "anomaly_count": (
                self.sentinel.anomalies if self.sentinel is not None else 0
            ),
            "anomaly_recent": self.anomaly_recent,
            # latency percentiles — the SLO surface (exact, host-side)
            **self.ttft.summary("ttft"),
            **self.ttft_warm.summary("ttft_warm"),
            **self.token_lat.summary("token_lat"),
            **self.queue_wait.summary("queue_wait"),
            **self.tick_lat.summary("tick"),
        }
