"""Telemetry runtime (ISSUE 4): device metrics ring, spans, goodput,
latency percentiles, logger hardening, and the sync-free trainer path.

The two load-bearing proofs:
- the ring path adds NOTHING to the compiled step: a ``no_recompile``-
  guarded LM step (jit-cache growth + implicit-transfer guard) stays
  green with telemetry enabled;
- the logged metric series is bit-identical to the seed blocking
  ``float()`` path (same f32 scalars, one hop through the buffer).
"""

import gzip
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_tpu.telemetry import (
    DeviceMetricsRing,
    GoodputLedger,
    LatencySeries,
    spans as span_stream,
    percentiles,
)
from pytorch_distributed_tpu.telemetry.goodput import GOODPUT_CATEGORIES

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---- device metrics ring -------------------------------------------------


def test_ring_wraparound_drain_order_and_bit_exact_roundtrip():
    """2.5 windows through the ring: every record comes back, in push
    order, with the exact f32 bit pattern that went in."""
    vals = np.float32([0.1, 1 / 3, np.pi, 7e-8, 1234.5678, -0.0,
                       2.5e38, 1e-38, 42.0, 5.5])
    ring = DeviceMetricsRing(["loss", "tokens"], capacity=4)
    recs = []
    for i, v in enumerate(vals):
        recs += ring.append(
            {"loss": jnp.float32(v), "tokens": jnp.float32(i)}, step=i
        )
    # lagged drain: with 10 pushes at capacity 4, two windows filled but
    # only the first has been harvested so far
    assert len(recs) == 4
    recs += ring.flush()
    assert [r["step"] for r in recs] == list(range(10))
    for i, r in enumerate(recs):
        # bit-identical: f32 → f32 through the buffer, no re-rounding
        assert np.float32(r["loss"]) == vals[i]
        assert r["tokens"] == float(i)
    assert ring.buffered == 0
    assert ring.pushed == ring.drained == 10


def test_ring_lagged_window_semantics():
    """Filling window N returns window N-1 (whose async host copy is
    long done); nothing is returned before the first window fills."""
    ring = DeviceMetricsRing(["x"], capacity=3)
    outs = [ring.append({"x": jnp.float32(i)}, i=i) for i in range(7)]
    assert [len(o) for o in outs] == [0, 0, 0, 0, 0, 3, 0]
    assert [r["i"] for r in outs[5]] == [0, 1, 2]
    tail = ring.flush()
    assert [r["i"] for r in tail] == [3, 4, 5, 6]


def test_ring_validation():
    with pytest.raises(ValueError):
        DeviceMetricsRing(["a"], capacity=0)
    with pytest.raises(ValueError):
        DeviceMetricsRing([])
    with pytest.raises(ValueError):
        DeviceMetricsRing(["a", "a"])


def test_ring_replicated_sharding(devices8):
    """Metrics from a shard_map step are mesh-replicated global arrays;
    the ring buffer must live on the same devices or jit rejects the
    mix."""
    from pytorch_distributed_tpu.parallel import make_mesh
    from pytorch_distributed_tpu.parallel import mesh as mesh_lib

    mesh = make_mesh(devices8, data_parallel=2, seq_parallel=2,
                     model_parallel=2)
    sh = mesh_lib.replicated_sharding(mesh)
    ring = DeviceMetricsRing(["x"], capacity=2, sharding=sh)
    v = jax.device_put(jnp.float32(3.25), sh)
    recs = ring.append({"x": v}, step=0)
    recs += ring.append({"x": v}, step=1)
    recs += ring.flush()
    assert [r["x"] for r in recs] == [3.25, 3.25]


def test_no_recompile_guarded_lm_step_with_telemetry():
    """The acceptance gate: with the ring enabled, the compiled LM step
    adds ZERO host syncs and ZERO recompiles — the jit cache stops
    growing after warmup and the transfer guard never trips."""
    from pytorch_distributed_tpu.analysis import no_recompile
    from pytorch_distributed_tpu.models.transformer import tiny_config
    from pytorch_distributed_tpu.ops.optim import build_optimizer
    from pytorch_distributed_tpu.ops.schedules import warmup_cosine
    from pytorch_distributed_tpu.parallel import make_mesh
    from pytorch_distributed_tpu.parallel import mesh as mesh_lib
    from pytorch_distributed_tpu.train.lm import (
        create_lm_state,
        make_lm_train_step,
        shift_labels,
    )
    from pytorch_distributed_tpu.train.lm_trainer import shard_lm_batch

    mesh = make_mesh(jax.devices()[:1], data_parallel=1, seq_parallel=1,
                     model_parallel=1)
    cfg = tiny_config(attention="dense")
    tx = build_optimizer("adamw", warmup_cosine(1e-3, 10), weight_decay=0.0)
    state = create_lm_state(cfg, tx, jax.random.key(0))
    state = jax.device_put(state, mesh_lib.replicated_sharding(mesh))
    step = no_recompile(
        make_lm_train_step(mesh, config=cfg), warmup_steps=2
    )
    ring = DeviceMetricsRing(
        ["loss", "tokens"], capacity=2,
        sharding=mesh_lib.replicated_sharding(mesh),
    )
    rng = np.random.default_rng(0)
    recs = []
    for i in range(6):
        tokens = rng.integers(1, cfg.vocab_size, (2, 32)).astype(np.int32)
        labels, weights = shift_labels(tokens)
        batch = shard_lm_batch(mesh, {
            "tokens": tokens, "labels": labels, "weights": weights,
        })
        state, metrics = step(state, batch)  # raises GuardViolation on hazard
        recs += ring.append(metrics, step=i)
    recs += ring.flush()
    assert step.stats.recompiles_after_warmup == 0
    assert len(recs) == 6 and all(np.isfinite(r["loss"]) for r in recs)


# ---- spans ---------------------------------------------------------------


def test_span_nesting_and_chrome_trace_validity(tmp_path):
    t = span_stream.tracer()
    t.clear()
    with t.span("outer", step=1):
        time.sleep(0.002)
        with t.span("inner"):
            time.sleep(0.002)
        with t.span("inner"):
            pass
    path = t.save(os.fspath(tmp_path / "spans.trace.json"))
    data = json.load(open(path))  # valid JSON on disk
    events = data["traceEvents"]
    assert any(e["ph"] == "M" and e["name"] == "process_name"
               for e in events)
    spans = [e for e in events if e["ph"] == "X"]
    assert sorted(e["name"] for e in spans) == ["inner", "inner", "outer"]
    for e in spans:
        assert e["dur"] >= 0 and {"ts", "pid", "tid"} <= set(e)
    outer = next(e for e in spans if e["name"] == "outer")
    for inner in (e for e in spans if e["name"] == "inner"):
        # containment is what lets Perfetto rebuild the stack
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1
    assert outer["args"] == {"step": 1, "id": outer["args"]["id"]}
    for inner in (e for e in spans if e["name"] == "inner"):
        assert inner["args"]["parent_id"] == outer["args"]["id"]


def test_span_stream_records_without_a_listener():
    """There is no "off": the process's tracer records into its ring
    with no profiler session and nothing to write, and one process has
    one tracer."""
    t = span_stream.tracer()
    assert t is span_stream.tracer()
    before = len(t.events("x"))
    with t.span("x"):
        pass
    assert len(t.events("x")) == before + 1


# ---- goodput -------------------------------------------------------------


def test_goodput_classified_times_sum_to_wall():
    g = GoodputLedger()
    g.start()
    with g.timed("data_wait"):
        time.sleep(0.01)
    with g.timed("checkpoint"):
        time.sleep(0.005)
    t0 = time.perf_counter()
    time.sleep(0.002)
    g.add("stall", time.perf_counter() - t0)  # measured, like the watchdog
    r = g.report()
    classified = sum(r[f"{c}_s"] for c in GOODPUT_CATEGORIES)
    # seconds: productive is the remainder, so the classes sum to wall
    assert r["productive_s"] + classified == pytest.approx(r["wall_s"])
    # fractions sum to 1 by construction
    fracs = r["goodput_frac"] + sum(
        r[f"{c}_frac"] for c in GOODPUT_CATEGORIES
    )
    assert fracs == pytest.approx(1.0)
    assert r["data_wait_s"] >= 0.01 and r["checkpoint_s"] >= 0.005
    assert r["stall_s"] >= 0.002


def test_goodput_overcounted_classes_still_sum_to_one():
    g = GoodputLedger()
    g.start()
    g.add("compile", 1e6)  # pathological over-attribution
    r = g.report()
    assert r["goodput_frac"] == 0.0
    fracs = r["goodput_frac"] + sum(
        r[f"{c}_frac"] for c in GOODPUT_CATEGORIES
    )
    assert fracs == pytest.approx(1.0)


def test_goodput_rejects_unknown_category_and_negative():
    g = GoodputLedger()
    with pytest.raises(ValueError):
        g.add("naps", 1.0)
    with pytest.raises(ValueError):
        g.add("stall", -1.0)


def test_watchdog_feeds_stall_time_to_ledger():
    from pytorch_distributed_tpu.resilience.watchdog import Watchdog

    g = GoodputLedger()
    with Watchdog(0.15, poll_s=0.02, ledger=g) as w:
        w.beat()
        deadline = time.monotonic() + 5.0
        while w.stalls == 0 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert w.stalls == 1
        w.beat()  # clearing the stall attributes the whole gap
    assert g.seconds("stall") >= 0.15


# ---- latency -------------------------------------------------------------


def test_latency_percentiles_match_numpy_reference():
    rng = np.random.default_rng(0)
    vals = rng.exponential(0.05, size=257)
    s = LatencySeries("ttft")
    for v in vals:
        s.observe(v)
    out = s.summary("ttft")
    assert out["ttft_count"] == 257
    assert out["ttft_mean_s"] == pytest.approx(float(vals.mean()))
    assert out["ttft_max_s"] == pytest.approx(float(vals.max()))
    for q in (50, 95, 99):
        assert out[f"ttft_p{q}_s"] == pytest.approx(
            float(np.percentile(vals, q))
        )
    ps = percentiles(vals, qs=(50, 95))
    assert ps["p50"] == pytest.approx(float(np.percentile(vals, 50)))
    assert percentiles([]) == {}
    assert LatencySeries().summary("x") == {"x_count": 0}


def test_latency_edge_cases_empty_single_and_all_equal():
    """ISSUE 8 satellite: the degenerate series a short or idle run
    produces — empty, one sample, all-equal — summarize without NaNs,
    and every percentile of a constant/singleton series IS the value."""
    # empty: counts only, no stat keys to trip a renderer
    empty = LatencySeries("e").summary("e")
    assert empty == {"e_count": 0}
    assert percentiles([]) == {}
    assert percentiles([], qs=(1, 50, 99.9)) == {}
    # single sample: every percentile is the sample, spread is zero
    s = LatencySeries("one")
    s.observe(0.25)
    out = s.summary("one")
    assert out["one_count"] == 1
    assert out["one_mean_s"] == out["one_max_s"] == 0.25
    for q in (50, 95, 99):
        assert out[f"one_p{q}_s"] == 0.25
    assert percentiles([0.25], qs=(0, 50, 100)) == {
        "p0": 0.25, "p50": 0.25, "p100": 0.25
    }
    # all-equal: percentiles collapse to the value (no interpolation
    # artifacts), mean/max agree, nothing is NaN
    eq = LatencySeries("c")
    for _ in range(17):
        eq.observe(1.5)
    out = eq.summary("c")
    assert out["c_count"] == 17
    for k, v in out.items():
        if k != "c_count":
            assert v == 1.5, k
    # and a fractional q on an all-equal series is still exact
    assert percentiles([2.0] * 5, qs=(99.9,)) == {"p99.9": 2.0}


# ---- MetricsLogger hardening --------------------------------------------


def test_metrics_logger_reopen_appends_not_truncates(tmp_path):
    path = os.fspath(tmp_path / "m.jsonl")
    with __import__(
        "pytorch_distributed_tpu.utils.profiling", fromlist=["MetricsLogger"]
    ).MetricsLogger(path) as log:
        log.log(kind="train", step=1)
    # a reopened path APPENDS (a resumed run extends its history)
    from pytorch_distributed_tpu.utils.profiling import MetricsLogger

    with MetricsLogger(path) as log:
        log.log(kind="train", step=2)
    recs = [json.loads(l) for l in open(path)]
    assert [r["step"] for r in recs] == [1, 2]


def test_metrics_logger_durable_before_close(tmp_path):
    """Line-buffered: a crash after log() cannot lose the record."""
    from pytorch_distributed_tpu.utils.profiling import MetricsLogger

    path = os.fspath(tmp_path / "m.jsonl")
    log = MetricsLogger(path)
    log.log(kind="train", step=7)
    recs = [json.loads(l) for l in open(path)]  # read BEFORE close
    assert recs and recs[0]["step"] == 7
    log.close()
    log.close()  # idempotent


def test_metrics_logger_rank0_gating_internal(tmp_path, monkeypatch):
    from pytorch_distributed_tpu.utils import profiling

    path = os.fspath(tmp_path / "m.jsonl")
    monkeypatch.setattr(
        profiling.MetricsLogger, "_is_rank0", staticmethod(lambda: False)
    )
    log = profiling.MetricsLogger(path)
    log.log(kind="train", step=1)
    log.close()
    assert not os.path.exists(path)  # non-rank-0: gated inside the class
    log = profiling.MetricsLogger(path, rank0_only=False)
    log.log(kind="train", step=1)
    log.close()
    assert os.path.exists(path)  # per-process stream opts out


def test_metrics_logger_size_capped_rotation(tmp_path):
    """ISSUE 8 satellite: with ``max_bytes`` set, a long run's stream
    rotates to <path>.1 and keeps writing — total disk bounded by ~2x
    the cap, every record in exactly one generation, no torn lines."""
    from pytorch_distributed_tpu.utils.profiling import MetricsLogger

    path = os.fspath(tmp_path / "m.jsonl")
    with MetricsLogger(path, max_bytes=2048) as log:
        for i in range(200):
            log.log(kind="train", step=i, pad="x" * 64)
        rotations = log.rotations
    assert rotations >= 1
    assert os.path.exists(f"{path}.1")
    assert os.path.getsize(path) <= 2048 + 256  # cap + one record slack
    # both generations parse cleanly line by line (record-aligned
    # rotation: no torn records at the boundary)
    newest = [json.loads(l) for l in open(path)]
    rotated = [json.loads(l) for l in open(f"{path}.1")]
    steps = [r["step"] for r in rotated] + [r["step"] for r in newest]
    # the newest history is contiguous and ends at the last record
    assert steps == list(range(steps[0], 200))
    assert steps[-1] == 199


def test_metrics_logger_reopen_after_rotation_appends(tmp_path):
    """Rotation regression: a resumed run reopening a rotated stream
    appends to the ACTIVE generation and keeps rotating from there."""
    from pytorch_distributed_tpu.utils.profiling import MetricsLogger

    path = os.fspath(tmp_path / "m.jsonl")
    with MetricsLogger(path, max_bytes=512) as log:
        for i in range(20):
            log.log(step=i, pad="y" * 48)
    with MetricsLogger(path, max_bytes=512) as log:
        log.log(step=99)
    newest = [json.loads(l) for l in open(path)]
    assert newest[-1]["step"] == 99
    # the pre-reopen tail the resumed run appended AFTER is still there
    assert len(newest) >= 2 or os.path.exists(f"{path}.1")


# ---- trace_device_busy_s multi-run aggregation ---------------------------


def _write_trace_run(trace_dir, run, offset_us, durs_us):
    d = os.path.join(trace_dir, "plugins", "profile", run)
    os.makedirs(d, exist_ok=True)
    events = [{
        "ph": "M", "name": "process_name", "pid": 1,
        "args": {"name": "/device:TPU:0"},
    }]
    ts = offset_us
    for dur in durs_us:
        events.append({"ph": "X", "pid": 1, "tid": 1, "name": "op",
                       "ts": ts, "dur": dur})
        ts += dur + 10  # 10 us gaps
    with gzip.open(os.path.join(d, "host.trace.json.gz"), "wt") as f:
        json.dump({"traceEvents": events}, f)


def test_trace_device_busy_aggregates_across_runs(tmp_path):
    """The old code silently read only the newest ``plugins/profile/*``
    run; two runs must now aggregate (busy and span summed)."""
    from pytorch_distributed_tpu.utils.profiling import trace_device_busy_s

    d = os.fspath(tmp_path)
    _write_trace_run(d, "run_a", 0, [100, 200])  # busy 300, span 310
    one = trace_device_busy_s(d)
    assert one == pytest.approx((300e-6, 310e-6))
    _write_trace_run(d, "run_b", 50_000, [400])  # busy 400, span 400
    busy, span = trace_device_busy_s(d)
    assert busy == pytest.approx(700e-6)
    assert span == pytest.approx(710e-6)
    assert trace_device_busy_s(os.fspath(tmp_path / "empty")) is None


# ---- trainer integration: bit-identical series ---------------------------


def _lm_metrics(flush_every, save_dir):
    from pytorch_distributed_tpu.data.tokens import SyntheticTokens
    from pytorch_distributed_tpu.models.transformer import tiny_config
    from pytorch_distributed_tpu.parallel import make_mesh
    from pytorch_distributed_tpu.train import LMTrainer, LMTrainerConfig

    mesh = make_mesh(jax.devices()[:1], data_parallel=1, seq_parallel=1,
                     model_parallel=1)
    cfg = LMTrainerConfig(
        epochs=1, batch_size=2, lr=1e-2, save_dir=os.fspath(save_dir),
        num_workers=0, log_every=1, warmup_steps=0,
        flush_every=flush_every,
    )
    train = SyntheticTokens(size=12, seq_len=32, vocab_size=128)
    val = SyntheticTokens(size=8, seq_len=32, vocab_size=128, seed=9)
    t = LMTrainer(tiny_config(attention="dense"), train, val, cfg,
                  mesh=mesh)
    t.fit()
    t.metrics_log.close()
    return [json.loads(l)
            for l in open(os.path.join(save_dir, "metrics.jsonl"))]


def test_lm_trainer_ring_series_bit_identical_to_blocking(tmp_path):
    """The satellite acceptance: routing the log path through the drained
    device ring leaves the logged loss series BIT-identical to the seed
    blocking float() path, and emits a goodput record."""
    legacy = _lm_metrics(0, tmp_path / "legacy")
    ring = _lm_metrics(3, tmp_path / "ring")
    lt = [r for r in legacy if r["kind"] == "train"]
    rt = [r for r in ring if r["kind"] == "train"]
    assert len(lt) == len(rt) > 0
    for a, b in zip(lt, rt):
        assert (a["epoch"], a["step"]) == (b["epoch"], b["step"])
        assert a["loss"] == b["loss"]  # bit-identical, not approx
        assert a["tokens"] == b["tokens"]
    gp = [r for r in ring if r["kind"] == "goodput"]
    assert len(gp) == 1
    fracs = gp[0]["goodput_frac"] + sum(
        gp[0][f"{c}_frac"] for c in GOODPUT_CATEGORIES
    )
    assert fracs == pytest.approx(1.0)
    assert gp[0]["compile_s"] > 0  # first dispatch attributed


# ---- serving latency -----------------------------------------------------


def _tiny_scheduler(tmp_path=None, **kw):
    from pytorch_distributed_tpu.models.transformer import (
        TransformerLM,
        tiny_config,
    )
    from pytorch_distributed_tpu.serving import Scheduler

    cfg = tiny_config(attention="dense", max_seq_len=64)
    params = TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return cfg, Scheduler(cfg, params, n_slots=2, block_len=8,
                          prefill_chunk=8, **kw)


def test_scheduler_latency_percentiles_and_request_records(tmp_path):
    from pytorch_distributed_tpu.utils.profiling import MetricsLogger

    path = os.fspath(tmp_path / "serve.jsonl")
    tracer = span_stream.tracer()
    tracer.clear()
    with MetricsLogger(path) as mlog:
        cfg, s = _tiny_scheduler(metrics_log=mlog)
        rng = np.random.default_rng(0)
        for l in (5, 9, 14):
            s.submit(rng.integers(1, cfg.vocab_size, l).astype(np.int32),
                     4)
        streams = s.drain()
        m = s.metrics()
        mlog.log(kind="serving_summary", **m)
    assert len(streams) == 3
    # one TTFT per request; inter-token gaps exclude the first token
    assert m["ttft_count"] == 3
    assert m["token_lat_count"] == m["tokens_out"] - 3
    assert m["queue_wait_count"] == 3
    assert 0 <= m["ttft_p50_s"] <= m["ttft_p95_s"] <= m["ttft_max_s"]
    assert m["queue_wait_p50_s"] >= 0
    # spans from the scheduler's tick
    names = {e.name for e in tracer.events()}
    assert {"sched.admit", "engine.chunk.launch",
            "engine.decode.launch"} <= names
    # per-request JSONL records carry the raw material for the report
    recs = [json.loads(l) for l in open(path)]
    reqs = [r for r in recs if r["kind"] == "request"]
    assert len(reqs) == 3
    for r in reqs:
        assert r["ttft_s"] >= 0 and r["queue_wait_s"] >= 0
        assert len(r["token_gaps_s"]) == r["new_tokens"] - 1
    # numpy-reference check of the reported percentiles
    ttfts = np.asarray([r["ttft_s"] for r in reqs])
    assert m["ttft_p50_s"] == pytest.approx(
        float(np.percentile(s.ttft.values, 50))
    )
    assert np.percentile(ttfts, 50) == pytest.approx(
        m["ttft_p50_s"], abs=2e-6  # records round to 1 us
    )


# ---- telemetry_report ----------------------------------------------------


def test_telemetry_report_renders_goodput_and_latency(tmp_path):
    """From JSONL alone: a goodput breakdown summing to 1 and TTFT +
    per-token p50/p95 — the acceptance-criteria artifact."""
    train_path = os.fspath(tmp_path / "train.jsonl")
    with open(train_path, "w") as f:
        for step in range(4):
            f.write(json.dumps(
                {"kind": "train", "epoch": 0, "step": step,
                 "loss": 5.0 - step * 0.1, "tokens": 124.0}
            ) + "\n")
        f.write(json.dumps(
            {"kind": "epoch_timing", "epoch": 0, "steps": 4,
             "mean_ms": 12.5, "tokens_per_s": 9920.0}
        ) + "\n")
        f.write(json.dumps({
            "kind": "goodput", "wall_s": 10.0, "productive_s": 6.0,
            "goodput_frac": 0.6, "productive_frac": 0.6,
            "compile_s": 2.0, "compile_frac": 0.2,
            "data_wait_s": 1.0, "data_wait_frac": 0.1,
            "checkpoint_s": 1.0, "checkpoint_frac": 0.1,
            "rollback_s": 0.0, "rollback_frac": 0.0,
            "stall_s": 0.0, "stall_frac": 0.0,
        }) + "\n")
    serve_path = os.fspath(tmp_path / "serve.jsonl")
    rng = np.random.default_rng(1)
    ttfts, gaps = [], []
    with open(serve_path, "w") as f:
        for rid in range(8):
            t = float(rng.uniform(0.05, 0.5))
            g = [float(x) for x in rng.uniform(0.001, 0.02, 5)]
            ttfts.append(t)
            gaps += g
            f.write(json.dumps(
                {"kind": "request", "rid": rid, "prompt_len": 16,
                 "new_tokens": 6, "queue_wait_s": 0.01, "ttft_s": t,
                 "token_gaps_s": g}
            ) + "\n")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts/telemetry_report.py"),
         train_path, serve_path, "--json", "--require", "goodput,serving"],
        capture_output=True, text=True, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["goodput_frac"] == pytest.approx(0.6)
    frac_sum = out["goodput_frac"] + sum(
        out[f"goodput_{c}_frac"] for c in GOODPUT_CATEGORIES
    )
    assert frac_sum == pytest.approx(1.0)
    # the report rounds ms to 3 decimals
    assert out["serving_ttft_p50_ms"] == pytest.approx(
        float(np.percentile(ttfts, 50)) * 1e3, abs=1e-3
    )
    assert out["serving_token_lat_p95_ms"] == pytest.approx(
        float(np.percentile(gaps, 95)) * 1e3, abs=1e-3
    )
    assert out["train_last_loss"] == pytest.approx(4.7)
    # --require fails when a section is missing
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts/telemetry_report.py"),
         serve_path, "--require", "goodput"],
        capture_output=True, text=True, cwd=REPO,
    )
    assert proc.returncode != 0
