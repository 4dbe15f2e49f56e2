"""The fleet's host loop (round 16): the router's dispatch-then-collect
token identity with a lone ``Scheduler`` (plain, disaggregated, and
pressure fleets), lagged-collect ordering, the early-collect protocol on
preempt/drain, the worker pool's barrier semantics, the no_recompile
guard under the lagged loop, a SIGKILL-mid-swap kill-matrix cell, and a
rules_threads-clean gate on every module with threads or thread-shared
state."""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_tpu.analysis import no_recompile
from pytorch_distributed_tpu.analysis.core import LintContext, parse_file
from pytorch_distributed_tpu.analysis.rules_threads import (
    check_threads,
    thread_inventory,
)
from pytorch_distributed_tpu.fleet import FleetRouter, SLOConfig
from pytorch_distributed_tpu.models.transformer import (
    TransformerLM,
    tiny_config,
)
from pytorch_distributed_tpu.resilience import faults
from pytorch_distributed_tpu.resilience.faults import FaultPlan, FaultSpec
from pytorch_distributed_tpu.serving import HostWorkerPool, Scheduler
from pytorch_distributed_tpu.telemetry import ReqTracer, validate_stream
from pytorch_distributed_tpu.utils.profiling import MetricsLogger

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCHED_KW = dict(n_slots=3, block_len=8, prefill_chunk=16,
                admit_per_step=4)


@pytest.fixture(scope="module")
def model():
    cfg = tiny_config(attention="dense", max_seq_len=64)
    params = TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return cfg, params


def _prompts(cfg, lens=(5, 16, 23, 31, 9, 17), seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, l).astype(np.int32)
            for l in lens]


def _fleet(cfg, params, **extra):
    kw = dict(SCHED_KW)
    kw.update(extra.pop("sched_kw", {}))
    return FleetRouter(
        cfg, params, n_replicas=2,
        slo=SLOConfig(spill_queue_depth=2, shed_queue_depth=10**6),
        **extra, **kw,
    )


def _lone_streams(cfg, params, prompts, max_new):
    """The step-domain reference: one ``Scheduler`` with an ample pool,
    each ``step()`` a tick launched and collected in the same call.
    Greedy streams do not depend on the schedule, so a fleet of any shape
    must serve these, rid for rid in submit order."""
    lone = Scheduler(cfg, params, **SCHED_KW)
    for p in prompts:
        lone.submit(p, max_new)
    return lone.drain()


# ---------------------------------------------------------------------------
# token identity: the router vs a lone scheduler, across fleet modes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", [
    pytest.param("plain", marks=pytest.mark.slow),
    "disagg",
    pytest.param("pressure", marks=pytest.mark.slow),
])
def test_async_sync_token_identity(model, mode):
    """Bit-identical greedy token streams between a lone scheduler's
    ``step()`` and the router's dispatch-then-collect, on the plain
    fleet, the disaggregated prefill/decode fleet, and the
    over-committed pressure fleet (where preempt/restore fires under a
    tick in flight)."""
    cfg, params = model
    extra = {}
    if mode == "disagg":
        extra = dict(disaggregate=True, decode_slots=4,
                     handoffs_per_tick=1)
    elif mode == "pressure":
        extra = dict(offload=True, preempt_on_oom=True,
                     swap_policy="swap", protect_ticks=0,
                     sched_kw=dict(n_blocks=10))
    want = _lone_streams(cfg, params, _prompts(cfg), 5)
    ra = _fleet(cfg, params, **extra)
    for i, p in enumerate(_prompts(cfg)):
        ra.submit(p, 5, session=i % 3)
    got = ra.drain()
    assert set(want) == set(got)
    for rid in want:
        assert want[rid] == got[rid], f"stream {rid} diverged"
    assert not ra.rejected
    if mode == "pressure":
        assert ra.metrics()["preempts"] >= 1
        assert ra.metrics()["restores"] >= 1
    if mode == "disagg":
        assert ra.metrics()["handoffs"] == len(want)
    # every pool block freed, worker pool drained
    for s in ra.replicas:
        assert s.engine.allocator.in_use == 0
        assert not s.has_uncollected


@pytest.mark.slow
def test_async_identity_on_bursty_trace(model):
    """The smoke-trace identity gate: a seeded bursty trace replayed
    through the router serves, rid for rid, what a lone scheduler
    serves of the same requests in the same order."""
    from pytorch_distributed_tpu.fleet import (
        clamp_trace,
        generate_trace,
        prompt_for,
        replay_trace,
    )

    cfg, params = model
    trace = clamp_trace(
        generate_trace(seed=5, duration_s=30.0, base_rate=0.6,
                       sessions=8, prompt_max=48, max_new_max=8),
        cfg.max_seq_len, SCHED_KW["prefill_chunk"],
    )
    r = _fleet(cfg, params)
    lone = Scheduler(cfg, params, **SCHED_KW)

    def submit(req):
        prompt = prompt_for(req, cfg.vocab_size)
        lone.submit(prompt, req.max_new)
        return r.submit(prompt, req.max_new, session=req.session)

    replay_trace(trace, submit, r.step, lambda: r.idle)
    assert not r.rejected
    assert dict(r.results) == lone.drain()


# ---------------------------------------------------------------------------
# lagged-collect ordering
# ---------------------------------------------------------------------------


def test_lagged_collect_one_tick_behind(model):
    """The async loop's contract: ``step()`` N returns the tokens of
    tick N−1 (collected lagged) while tick N is left in flight — a
    pending, uncollected ``TickHandle`` exists between steps, and the
    per-rid stream order is preserved."""
    cfg, params = model
    r = _fleet(cfg, params)
    rid = r.submit(np.arange(1, 10, dtype=np.int32), 3)
    first_out = r.step()
    # step 1 dispatched tick 1 (admission + first chunk); nothing was
    # in flight to collect, so no tokens can have been returned yet
    assert first_out == []
    seen = []
    pending_seen = 0
    for _ in range(16):
        if any(s._pending_tick is not None for s in r.replicas):
            pending_seen += 1
        seen.extend(tok for _rid, tok in r.step())
        if r.idle:
            break
    assert pending_seen > 0, "no tick was ever left in flight"
    assert r.results[rid] == seen[:len(r.results[rid])]
    # the lone scheduler's stream: same values
    assert _lone_streams(cfg, params, [np.arange(1, 10, dtype=np.int32)],
                         3)[0] == r.results[rid]


def test_early_collect_on_preempt_and_drain(model):
    """External mutations collect the pending tick first: preempt_lru
    mid-flight loses no tokens (they stash and deliver at the next
    collect), and begin_drain starts from settled state."""
    cfg, params = model
    r = _fleet(cfg, params, offload=True, preempt_on_oom=True,
               swap_policy="recompute", protect_ticks=0)
    rids = [r.submit(p, 4) for p in _prompts(cfg, lens=(9, 12, 7))]
    for _ in range(4):
        r.step()
    target = r.replicas[r.placement[rids[0]]]
    assert target._pending_tick is not None or target._collected == []
    victim = target.preempt_lru(reason="test")
    # the early collect drained the in-flight tick before parking
    assert target._pending_tick is None
    out = r.drain()
    assert victim is None or victim in out
    # token identity with the unpreempted lone scheduler
    assert out == _lone_streams(cfg, params,
                                _prompts(cfg, lens=(9, 12, 7)), 4)
    # graceful drain under the lagged loop: settled, zero leaked blocks
    r2 = _fleet(cfg, params)
    for p in _prompts(cfg, lens=(9, 12, 7)):
        r2.submit(p, 4)
    r2.step(); r2.step()
    sched = r2.replicas[0]
    sched.begin_drain()
    assert sched._pending_tick is None
    produced, requeued = sched.drain_graceful()
    assert sched.engine.allocator.in_use == 0
    r2.replicas[1].begin_drain()
    r2.replicas[1].drain_graceful()


# ---------------------------------------------------------------------------
# the default loop: a router built with no loop argument
# ---------------------------------------------------------------------------


def _backlog_router(cfg, params, **kw):
    """One replica, built as the benchmark's serving cells build it: no
    loop argument, results dropped at retire."""
    return FleetRouter(
        cfg, params, n_replicas=1, retain_results=False,
        slo=SLOConfig(spill_queue_depth=10**6, shed_queue_depth=10**6),
        **SCHED_KW, **kw,
    )


def _drive_backlog(router, cfg, *, first=4, total=10, max_new=5):
    """Drive a router the way the cell does: a closed backlog, a submit
    between two steps for every request that retired. Returns the
    streams by rid and what each ``step()`` returned."""
    stream = iter(_prompts(cfg, lens=(5, 16, 23, 31, 9, 17, 12, 7, 20, 11),
                           seed=3)[:total])
    streams, live, per_step = {}, set(), []
    for _ in range(first):
        live.add(router.submit(next(stream), max_new))
    for _ in range(400):
        out = router.step()
        per_step.append(out)
        for rid, tok in out:
            streams.setdefault(rid, []).append(int(tok))
            if len(streams[rid]) >= max_new:
                live.discard(rid)
                nxt = next(stream, None)
                if nxt is not None:
                    live.add(router.submit(nxt, max_new))
        if not live and router.idle:
            break
    assert not live and router.idle
    return streams, per_step


def test_the_default_loop_keeps_a_tick_in_flight(model):
    """A ``FleetRouter`` that is told nothing about its loop runs the
    lagged one: the first ``step()`` returns nothing and leaves a tick
    in flight, step N+1 returns the tokens tick N decoded, the
    ``router.step`` span says how many replicas entered with a tick
    pending (0, then 1), and the streams equal, request by request, a
    lone scheduler's on the same seeded traffic."""
    from pytorch_distributed_tpu.telemetry import spans

    cfg, params = model
    tracer = spans.tracer()
    router = _backlog_router(cfg, params)
    assert router.host_pool is not None
    sched = router.replicas[0]
    t_lo = time.perf_counter()
    got, per_step = _drive_backlog(router, cfg)
    assert per_step[0] == []
    steps = tracer.events("router.step", t_lo=t_lo)[:len(per_step)]
    flights = [e.args["in_flight"] for e in steps]
    assert flights[0] == 0
    # a step that entered with a token-bearing tick pending returns that
    # tick's tokens, and only such a step returns any
    assert [bool(out) for out in per_step] == [f == 1 for f in flights]
    assert sum(flights) >= len(flights) - 3 and set(flights) == {0, 1}
    # tick N's tokens come out of step N+1: the step that returns a
    # request's first token is one past the tick that armed its lane
    ref = Scheduler(cfg, params, **SCHED_KW)
    want, ref_steps = _drive_backlog(ref, cfg)
    assert ref.host_pool is None
    first = next(i for i, out in enumerate(per_step) if out)
    ref_first = next(i for i, out in enumerate(ref_steps) if out)
    assert first == ref_first + 1
    assert per_step[first] == ref_steps[ref_first]
    assert got == want and len(got) == 10
    assert all(len(v) == 5 for v in got.values())
    assert not sched.has_uncollected and not sched.tick_in_flight
    assert sched.engine.allocator.in_use == 0


def test_a_dropped_router_takes_its_threads_and_its_engine_with_it(model):
    """The pool's threads end with the router, and a tick left in flight
    goes with its scheduler: after the harness's ``del router;
    gc.collect()`` no ``pdt-host`` thread of that router is alive and
    nothing holds its engine (its weights and pools)."""
    import gc
    import weakref

    cfg, params = model
    router = _backlog_router(cfg, params)
    sched = router.replicas[0]
    sched.gate_refresh_ticks = 1  # a worker runs closures over the scheduler
    for p in _prompts(cfg):
        router.submit(p, 8)
    for _ in range(6):
        router.step()
    assert sched.tick_in_flight  # dropped mid-flight, as the cell drops it
    assert router.host_pool.submitted > 0
    deadline = time.monotonic() + 30
    while router.host_pool.pending and time.monotonic() < deadline:
        time.sleep(0.01)  # a worker mid-closure still holds the scheduler
    assert router.host_pool.pending == 0
    threads = list(router.host_pool._threads)
    assert threads and all(t.is_alive() for t in threads)
    assert all(t.name.startswith("pdt-host") for t in threads)
    engine = weakref.ref(sched.engine)
    dead_router = weakref.ref(router)
    router.replicas.clear()
    del router, sched
    gc.collect()
    assert dead_router() is None
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive()
    gc.collect()
    assert engine() is None


# ---------------------------------------------------------------------------
# worker pool semantics
# ---------------------------------------------------------------------------


def test_host_worker_pool_fifo_flush_and_errors():
    pool = HostWorkerPool(n_threads=2)
    done = []
    lock = threading.Lock()
    for i in range(32):
        pool.submit(lambda i=i: (time.sleep(0.001),
                                 lock.__enter__(), done.append(i),
                                 lock.__exit__(None, None, None)))
    pool.flush()
    assert sorted(done) == list(range(32))
    assert pool.pending == 0

    def boom():
        raise ValueError("worker task failed")

    pool.submit(boom)
    with pytest.raises(RuntimeError, match="host-worker task"):
        pool.flush()
    pool.flush()  # errors cleared at the barrier that reported them
    pool.close()
    with pytest.raises(RuntimeError, match="closed"):
        pool.submit(lambda: None)
    pool.close()  # idempotent


def test_worker_offloads_jsonl_and_gate_snapshot(model, tmp_path):
    """Behind the router per-request JSONL emission rides the worker
    pool, the gate snapshot refresh runs off-thread, and gate_metrics
    overlays live counters so depth-bound routing state is never
    stale."""
    cfg, params = model
    path = str(tmp_path / "async.jsonl")
    with MetricsLogger(path) as mlog:
        r = _fleet(cfg, params, metrics_log=mlog, reqtrace=ReqTracer(mlog))
        for s in r.replicas:
            s.gate_refresh_ticks = 1  # force a refresh on every collect
        for i, p in enumerate(_prompts(cfg)):
            r.submit(p, 4, session=i % 2)
        r.drain()
        r.log_summary()
    records = [json.loads(l) for l in open(path) if l.strip()]
    assert validate_stream(records) == []
    reqs = [rec for rec in records if rec.get("kind") == "request"]
    assert len(reqs) == len(_prompts(cfg))
    # a closure a retired request and a closure a refresh, all run
    assert r.host_pool.submitted > len(reqs)
    assert r.host_pool.completed == r.host_pool.submitted
    # gate snapshot landed, and the overlay carries the live counters
    gm = r.replicas[0].gate_metrics()
    assert gm["queue_depth"] == 0 and "preemptible" in gm
    assert "ttft_p95_s" in gm  # the worker-refreshed percentile side
    summaries = [rec for rec in records
                 if rec.get("kind") == "fleet_summary"]
    assert len(summaries) == 1 and summaries[0]["completed"] == len(reqs)


# ---------------------------------------------------------------------------
# guards: no recompiles, registry coverage
# ---------------------------------------------------------------------------


def test_async_loop_no_recompile(model):
    """``no_recompile`` stays green under the lagged loop:
    dispatch-then-collect adds zero program variants."""
    cfg, params = model
    r = _fleet(cfg, params)
    for i, p in enumerate(_prompts(cfg)):
        r.submit(p, 4, session=i % 2)
    for _ in range(6):
        r.step()
    for s in r.replicas:
        s.engine._decode_fn = no_recompile(s.engine._decode(),
                                           warmup_steps=1)
    for p in _prompts(cfg, lens=(10, 11), seed=1):
        r.submit(p, 4)
    r.drain()
    for s in r.replicas:
        stats = s.engine._decode_fn.stats
        assert stats.recompiles_after_warmup == 0


def test_registry_coverage_with_async_loop(model):
    cfg, params = model
    r = _fleet(cfg, params)
    for p in _prompts(cfg):
        r.submit(p, 3)
    r.drain()
    r.assert_registry_covers()


# ---------------------------------------------------------------------------
# kill matrix: SIGKILL mid-swap under the async loop
# ---------------------------------------------------------------------------


def _run_serve_child(save_dir, env_extra=None, timeout=300):
    env = dict(os.environ)
    env.pop(faults.ENV_PLAN, None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(__file__), "serve_child.py"),
         "--save-dir", str(save_dir), "--fleet-async"],
        env=env, capture_output=True, text=True, timeout=timeout,
    )


@pytest.mark.slow
@pytest.mark.crash
def test_kill_matrix_async_loop_sigkill_mid_swap(tmp_path, model):
    """The async-loop kill-matrix cell: run 1 (2-replica async fleet,
    forced swap preemptions, ticks in flight, workers holding queued
    telemetry) dies by SIGKILL inside the swap-out window; run 2
    relaunches clean and serves token streams identical to the
    unpreempted greedy reference."""
    from tests.serve_child import workload
    from tests.test_pressure import greedy_streams

    plan = FaultPlan([FaultSpec(site="kv.swap_out_d2h", kind="kill",
                                at=0)])
    r1 = _run_serve_child(tmp_path, {faults.ENV_PLAN: plan.to_json()})
    assert r1.returncode == -signal.SIGKILL, (
        f"child should die by SIGKILL; rc={r1.returncode}\n"
        f"stdout:{r1.stdout}\nstderr:{r1.stderr}"
    )
    assert not os.path.exists(os.path.join(str(tmp_path), "result.json"))
    r2 = _run_serve_child(tmp_path)
    assert r2.returncode == 0, (
        f"relaunch failed\nstdout:{r2.stdout}\nstderr:{r2.stderr}"
    )
    with open(os.path.join(str(tmp_path), "result.json")) as f:
        result = json.load(f)
    assert result["preempts"] >= 1 and result["swap_aborts"] == 0
    cfg, params = model
    prompts = workload(cfg)
    want = greedy_streams(cfg, params, prompts, 6)
    for i in range(len(prompts)):
        assert result["streams"][str(i)] == want[i], f"stream {i}"


# ---------------------------------------------------------------------------
# lint: every new/worker module rules_threads-clean
# ---------------------------------------------------------------------------


def test_rules_threads_clean_on_async_modules():
    """Every module with threads or thread-shared state on the serving
    path passes the concurrency lints with zero
    findings — locks (or documented lock-free protocols) on every
    shared structure."""
    ctx = LintContext(modules=[], mesh_axes=set(), axis_constants={})
    for rel in (
        "pytorch_distributed_tpu/serving/host_worker.py",
        "pytorch_distributed_tpu/serving/scheduler.py",
        "pytorch_distributed_tpu/fleet/router.py",
        "pytorch_distributed_tpu/telemetry/anomaly.py",
        "pytorch_distributed_tpu/utils/profiling.py",
    ):
        mod = parse_file(os.path.join(REPO, rel), REPO)
        findings = check_threads(mod, ctx)
        assert findings == [], [f.render() for f in findings]
    inv = thread_inventory(parse_file(
        os.path.join(REPO, "pytorch_distributed_tpu/serving/host_worker.py"),
        REPO,
    ))
    assert inv["threads"], "the worker pool's threads must be inventoried"
    assert inv["threads"][0]["kind"] == "self-method"
