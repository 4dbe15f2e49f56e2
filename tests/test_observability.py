"""Performance attribution & forensics (ISSUE 8): cost cards, anomaly
sentinel, flight recorder, live exporter, and their wiring.

The load-bearing proofs:

- every program in a ``ProgramRegistry`` gets a cost card, and measured
  joins produce MFU/roofline numbers that match hand arithmetic;
- the anomaly sentinel flags a fault-injected hang DETERMINISTICALLY
  (seeded plan through the real trainer loop) and never before its
  warmup window;
- a SIGKILL'd kill-matrix child leaves a readable flight-recorder
  mirror whose last event precedes the kill site;
- the fleet SLOGate treats a recently-anomalous replica as hot.
"""

import functools
import json
import os
import subprocess
import sys
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_tpu.telemetry import (
    AnomalySentinel,
    CostCard,
    FlightRecorder,
    MetricsExporter,
    ProgramTimes,
    StreamingDetector,
    build_cost_cards,
    prometheus_text,
)
from pytorch_distributed_tpu.telemetry.costmodel import extract_costs
from pytorch_distributed_tpu.telemetry.flightrec import (
    read_dump,
    read_mirror,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---- anomaly sentinel ----------------------------------------------------


def test_detector_flags_spike_deterministically_after_warmup():
    det = StreamingDetector(window=16, threshold=8.0, min_samples=8,
                            context=4)
    # warmup: nothing can flag before min_samples observations exist
    base = [0.010, 0.011, 0.009, 0.010, 0.012, 0.010, 0.011, 0.010]
    hits = [det.observe(v) for v in base]
    assert hits == [None] * 8
    # the spike flags, with the right index and context window
    hit = det.observe(1.5)
    assert hit is not None
    assert hit["index"] == 8
    assert hit["value"] == 1.5
    assert hit["zscore"] > 8
    assert hit["median"] == pytest.approx(0.010, abs=1e-3)
    assert hit["context"] == [pytest.approx(v) for v in base[-4:]]
    # baseline values after the spike do NOT flag (the spike entered the
    # window but the median absorbed it)
    assert det.observe(0.010) is None
    assert det.anomalies == 1
    # replaying the same series flags the same index — determinism
    det2 = StreamingDetector(window=16, threshold=8.0, min_samples=8)
    replay = [det2.observe(v) for v in base + [1.5, 0.010]]
    assert [i for i, h in enumerate(replay) if h] == [8]


def test_detector_all_equal_series_uses_scale_floor():
    """MAD of a constant series is 0; the relative floor keeps z finite
    and only a genuine departure flags."""
    det = StreamingDetector(window=16, threshold=8.0, min_samples=4,
                            rel_floor=0.05)
    for _ in range(8):
        assert det.observe(2.0) is None
    # within threshold*rel_floor*|median| = 8*0.05*2 = 0.8 of the median
    assert det.observe(2.5) is None
    hit = det.observe(4.0)  # 2.0 above median > 0.8
    assert hit is not None and hit["zscore"] == pytest.approx(20.0, rel=0.1)


def test_sentinel_streams_jsonl_with_meta(tmp_path):
    from pytorch_distributed_tpu.utils.profiling import MetricsLogger

    path = os.fspath(tmp_path / "m.jsonl")
    with MetricsLogger(path) as mlog:
        s = AnomalySentinel(threshold=8.0, min_samples=4,
                            metrics_log=mlog, source="test")
        for _ in range(6):
            s.observe("lat", 0.01)
        assert s.observe("lat", 9.0, step=42) is not None
    assert s.anomalies == 1
    assert s.counts() == {"lat": 1}
    recs = [json.loads(l) for l in open(path)]
    assert len(recs) == 1
    r = recs[0]
    assert r["kind"] == "anomaly" and r["series"] == "lat"
    assert r["step"] == 42 and r["source"] == "test"
    assert r["value"] == 9.0 and len(r["context"]) > 0


def test_slo_gate_treats_recent_anomaly_as_hot():
    from pytorch_distributed_tpu.fleet import SLOGate

    gate = SLOGate()
    cool = {"queue_depth": 0, "occupancy": 0.1}
    hot = {"queue_depth": 0, "occupancy": 0.1, "anomaly_recent": True}
    assert gate.hot(cool) is None
    assert gate.hot(hot) == "anomaly"
    # routing: the anomalous affinity replica is spilled around
    d = gate.route({0: hot, 1: cool}, preferred=0)
    assert d.action == "spill" and d.replica == 1 and d.reason == "anomaly"


# ---- cost cards ----------------------------------------------------------


def test_extract_costs_from_real_compiled():
    comp = jax.jit(lambda x: (x @ x).sum()).lower(
        jnp.ones((64, 64), jnp.float32)
    ).compile()
    costs = extract_costs(comp)
    # 64^3 MACs * 2 flops minimum for the matmul alone
    assert costs["flops"] >= 2 * 64**3
    assert costs["bytes_accessed"] >= 64 * 64 * 4
    assert costs["argument_bytes"] == 64 * 64 * 4
    assert costs["peak_bytes"] > 0


def test_cost_card_join_arithmetic_and_roofline_class():
    # bandwidth-bound: intensity 2 F/B below ridge 10 F/B
    card = CostCard(program="p", flops=2e9, bytes_accessed=1e9,
                    calls=4, total_s=0.4)
    rec = card.record(peak_flops=1e12, peak_bytes_s=1e11)
    assert rec["mean_s"] == pytest.approx(0.1)
    assert rec["achieved_flops_s"] == pytest.approx(2e10)
    assert rec["mfu"] == pytest.approx(0.02)
    assert rec["hbm_frac"] == pytest.approx(0.1)
    assert rec["intensity_flop_b"] == pytest.approx(2.0)
    assert rec["ridge_flop_b"] == pytest.approx(10.0)
    assert rec["bound"] == "bandwidth"
    # compute-bound twin
    card2 = CostCard(program="q", flops=2e12, bytes_accessed=1e9,
                     calls=1, total_s=0.1)
    assert card2.record(1e12, 1e11)["bound"] == "compute"
    # no ceilings: achieved rates still emit, mfu/bound absent
    rec3 = card.record(None, None)
    assert "achieved_flops_s" in rec3
    assert "mfu" not in rec3 and "bound" not in rec3
    # unmeasured card: statics only, no rates
    rec4 = CostCard(program="r", flops=1.0).record(1e12, 1e11)
    assert rec4["calls"] == 0 and "mean_s" not in rec4


def test_extract_costs_dedupes_aliased_operand_bytes():
    """The round 20 double-count fix (PERF_NOTES §9): donated operands
    appear in BOTH argument and output totals, so peak_bytes subtracts
    the aliased overlap once and bytes_accessed_dedup removes it from
    the traffic number the roofline join divides by. Regression pinned
    against a fake compiled object with known analysis values."""

    class FakeMem:
        argument_size_in_bytes = 1000
        output_size_in_bytes = 700
        temp_size_in_bytes = 50
        alias_size_in_bytes = 600  # a donated pool counted twice above

    class FakeCompiled:
        def cost_analysis(self):
            return {"flops": 4000.0, "bytes accessed": 2000.0}

        def memory_analysis(self):
            return FakeMem()

    costs = extract_costs(FakeCompiled())
    assert costs["alias_bytes"] == 600
    assert costs["peak_bytes"] == 1000 + 700 + 50 - 600
    card = CostCard(program="fake", calls=2, total_s=0.2, **costs)
    assert card.bytes_accessed_dedup == pytest.approx(2000.0 - 600)
    # intensity and the roofline join use the DEDUPED traffic
    assert card.intensity == pytest.approx(4000.0 / 1400.0)
    rec = card.record(peak_flops=1e6, peak_bytes_s=1e5)
    assert rec["bytes_accessed"] == pytest.approx(2000.0)  # raw kept
    assert rec["bytes_accessed_dedup"] == pytest.approx(1400.0)
    assert rec["achieved_bytes_s"] == pytest.approx(1400.0 / 0.1)
    assert rec["hbm_frac"] == pytest.approx(1400.0 / 0.1 / 1e5)
    # no alias info → dedup degrades to the raw number, never negative
    plain = CostCard(program="p", flops=1.0, bytes_accessed=100.0)
    assert plain.bytes_accessed_dedup == pytest.approx(100.0)
    swamped = CostCard(program="s", bytes_accessed=100.0,
                       alias_bytes=1000)
    assert swamped.bytes_accessed_dedup == 0.0


def test_extract_costs_alias_on_real_donated_program():
    """A live donated buffer really shows up in alias_size_in_bytes and
    peak_bytes stays below the naive arg+out+temp sum (tolerant: if
    this jax build reports no aliasing, the dedup must be a no-op
    rather than wrong)."""

    @functools.partial(jax.jit, donate_argnums=0)
    def bump(x):
        return x + 1

    comp = bump.lower(jnp.ones((256, 256), jnp.float32)).compile()
    costs = extract_costs(comp)
    naive = (costs["argument_bytes"] + costs["output_bytes"]
             + costs["temp_bytes"])
    assert costs["peak_bytes"] == naive - costs["alias_bytes"]
    if costs["alias_bytes"]:
        assert costs["alias_bytes"] >= 256 * 256 * 4
        card = CostCard(program="bump", **costs)
        assert card.bytes_accessed_dedup < card.bytes_accessed


def _tiny_scheduler(**kw):
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


@pytest.mark.slow
def test_every_registry_program_has_a_cost_card(tmp_path):
    """The acceptance line: cards cover the registry exactly, and the
    measured decode tick joins into achieved rates."""
    from pytorch_distributed_tpu.compilecache import serving_registry
    from pytorch_distributed_tpu.utils.profiling import MetricsLogger

    path = os.fspath(tmp_path / "serve.jsonl")
    with MetricsLogger(path) as mlog:
        cfg, s = _tiny_scheduler(metrics_log=mlog)
        rng = np.random.default_rng(0)
        for l in (5, 9, 14, 7):
            s.submit(rng.integers(1, cfg.vocab_size, l).astype(np.int32), 4)
        s.drain()
        records = s.log_cost_cards()
    reg = serving_registry(s.engine)
    names = {r["program"] for r in records}
    assert names == set(reg.names)  # every program, nothing else
    by_name = {r["program"]: r for r in records}
    decode = by_name["decode_tick"]
    assert decode["calls"] > 0 and decode["flops"] > 0
    assert decode["achieved_flops_s"] > 0
    assert decode["bytes_accessed"] > 0 and decode["peak_bytes"] > 0
    # statics exist even for buckets traffic never touched
    unmeasured = [r for r in records if not r["calls"]]
    assert unmeasured and all(r.get("flops") for r in unmeasured)
    # the JSONL stream carries the same records
    jl = [json.loads(l) for l in open(path)
          if json.loads(l).get("kind") == "program_cost"]
    assert {r["program"] for r in jl} == names


def test_build_cost_cards_survives_aotless_and_failing_specs():
    from pytorch_distributed_tpu.compilecache import (
        ProgramRegistry,
        ProgramSpec,
    )

    def boom():
        raise RuntimeError("unanalyzable")

    reg = ProgramRegistry("fp")
    reg.add(ProgramSpec(name="no_aot", warm=lambda e: None))
    reg.add(ProgramSpec(name="bad_aot", warm=lambda e: None, aot=boom))
    times = ProgramTimes()
    times.observe("no_aot", 0.5)
    cards = build_cost_cards(reg, times)
    assert [c.program for c in cards] == ["no_aot", "bad_aot"]
    assert cards[0].flops is None and cards[0].calls == 1
    assert cards[1].flops is None  # failure -> card without statics


def test_program_times_accumulates():
    t = ProgramTimes()
    t.observe("a", 0.1)
    t.observe("a", 0.3)
    t.observe_total("b", 1.0, 10)
    t.observe("a", -1.0)  # rejected
    assert t.get("a") == (2, pytest.approx(0.4))
    assert t.get("b") == (10, 1.0)
    assert t.get("missing") == (0, 0.0)


# ---- flight recorder -----------------------------------------------------


def test_flightrec_ring_bound_dump_and_mirror(tmp_path):
    mirror = os.fspath(tmp_path / "fr.jsonl")
    fr = FlightRecorder(capacity=8, mirror_path=mirror)
    for i in range(20):
        fr.record("step", n=i)
    assert len(fr) == 8  # ring bounded
    snap = fr.snapshot()
    assert [e["n"] for e in snap] == list(range(12, 20))
    assert [e["seq"] for e in snap] == list(range(12, 20))
    # the mirror kept EVERYTHING (durable beyond the ring horizon)
    events = read_mirror(mirror)
    assert [e["n"] for e in events] == list(range(20))
    # atomic dump: header + the ring's events
    path = os.fspath(tmp_path / "dump.json")
    assert fr.dump(path, "test_reason") == path
    dump = read_dump(path)
    assert dump["reason"] == "test_reason"
    assert dump["first_seq"] == 12 and dump["last_seq"] == 19
    assert [e["n"] for e in dump["events"]] == list(range(12, 20))
    fr.close()


def test_flightrec_mirror_rotation_and_torn_tail(tmp_path):
    mirror = os.fspath(tmp_path / "fr.jsonl")
    fr = FlightRecorder(capacity=4, mirror_path=mirror,
                        mirror_max_bytes=1024)
    for i in range(100):
        fr.record("step", n=i, pad="z" * 32)
    fr.close()
    assert os.path.exists(f"{mirror}.1")
    # simulate the SIGKILL torn final line
    with open(mirror, "a") as f:
        f.write('{"seq": 9999, "kind": "to')
    events = read_mirror(mirror)
    ns = [e["n"] for e in events if "n" in e]
    assert ns == sorted(ns) and ns[-1] == 99  # ordered across rotation
    assert all(e.get("seq") != 9999 for e in events)  # torn line dropped


def test_flightrec_excepthook_dumps_then_chains(tmp_path):
    fr = FlightRecorder(capacity=4)
    fr.record("step", n=1)
    dump_path = os.fspath(tmp_path / "exc.json")
    seen = []
    prev = sys.excepthook
    sys.excepthook = lambda *a: seen.append(a)
    try:
        fr.install_excepthook(dump_path)
        try:
            raise ValueError("boom")
        except ValueError:
            sys.excepthook(*sys.exc_info())
        assert os.path.exists(dump_path)
        dump = read_dump(dump_path)
        assert dump["reason"] == "exception:ValueError"
        kinds = [e["kind"] for e in dump["events"]]
        assert "exception" in kinds and "step" in kinds
        assert len(seen) == 1  # previous hook still ran
    finally:
        fr.uninstall_excepthook()
        sys.excepthook = prev


def test_flightrec_disabled_is_free(tmp_path):
    from pytorch_distributed_tpu.telemetry import NULL_RECORDER

    NULL_RECORDER.record("step", n=1)
    assert len(NULL_RECORDER) == 0
    assert NULL_RECORDER.dump(os.fspath(tmp_path / "x.json"), "r") is None
    assert not os.path.exists(tmp_path / "x.json")


# ---- live exporter -------------------------------------------------------


def test_metrics_exporter_serves_prometheus_text():
    state = {"tokens_per_s": 123.5, "queue_depth": 4, "draining": False,
             "name": "skipme", "bad": float("nan")}
    with MetricsExporter(lambda: state, port=0) as ex:
        assert ex.port and ex.port > 0
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{ex.port}/metrics", timeout=5
        ).read().decode()
        assert "pdt_tokens_per_s 123.5" in body
        assert "pdt_queue_depth 4" in body
        assert "pdt_draining 0" in body
        assert "skipme" not in body and "pdt_bad" not in body
        health = urllib.request.urlopen(
            f"http://127.0.0.1:{ex.port}/healthz", timeout=5
        )
        assert health.status == 200
    # prometheus_text is the pure renderer the handler uses
    text = prometheus_text({"a_b": 1})
    assert "# TYPE pdt_a_b gauge" in text and "pdt_a_b 1" in text


# ---- scheduler integration ----------------------------------------------


def test_scheduler_metrics_expose_anomaly_signal():
    cfg, s = _tiny_scheduler()
    m = s.metrics()
    assert m["anomaly_count"] == 0 and m["anomaly_recent"] is False
    # inject recency directly: the signal is tick-windowed
    s._last_anomaly_step = 0
    s._step_count = 10
    assert s.metrics()["anomaly_recent"] is True
    s._step_count = s.anomaly_recent_ticks + 5
    assert s.metrics()["anomaly_recent"] is False


# ---- trainer integration: deterministic hang → anomaly + cost cards ------


def _lm_fit(tmp_path, monkeypatch, fault_plan=None, watcher=None,
            **cfg_over):
    from pytorch_distributed_tpu.data.tokens import SyntheticTokens
    from pytorch_distributed_tpu.models.transformer import tiny_config
    from pytorch_distributed_tpu.parallel import make_mesh
    from pytorch_distributed_tpu.resilience import faults
    from pytorch_distributed_tpu.train import LMTrainer, LMTrainerConfig

    if fault_plan is not None:
        monkeypatch.setattr(faults, "_plan", None)
        faults.install_plan(fault_plan)
    mesh = make_mesh(jax.devices()[:1], data_parallel=1, seq_parallel=1,
                     model_parallel=1)
    cfg = LMTrainerConfig(
        epochs=1, batch_size=2, lr=1e-2, save_dir=os.fspath(tmp_path),
        num_workers=0, log_every=1, warmup_steps=0, **cfg_over,
    )
    train = SyntheticTokens(size=24, seq_len=32, vocab_size=128)
    val = SyntheticTokens(size=8, seq_len=32, vocab_size=128, seed=9)
    t = LMTrainer(tiny_config(attention="dense"), train, val, cfg,
                  mesh=mesh, suspend_watcher=watcher)
    t.fit()
    t.metrics_log.close()
    t.flightrec.close()
    if fault_plan is not None:
        faults.install_plan(None)
    return t, [json.loads(l)
               for l in open(os.path.join(tmp_path, "metrics.jsonl"))]


@pytest.mark.slow
def test_trainer_hang_injection_flags_anomaly_and_cost_cards(
    tmp_path, monkeypatch
):
    """ISSUE 8 acceptance: a seeded ``train.step`` hang is flagged by
    the sentinel (kind="anomaly" with the hang's magnitude), the flight
    recorder mirror holds the step history, and fit-end cost cards
    carry a measured MFU join for the train step."""
    from pytorch_distributed_tpu.resilience.faults import (
        FaultPlan,
        FaultSpec,
    )

    monkeypatch.setenv("PDT_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("PDT_PEAK_GBS", "100")
    # 12 steps; hang 1.0s at occurrence 10 — past the sentinel's
    # min_samples warmup, so the flag is guaranteed, not probabilistic
    plan = FaultPlan([FaultSpec(site="train.step", kind="hang", at=10,
                                seconds=1.0)])
    t, recs = _lm_fit(tmp_path, monkeypatch, fault_plan=plan,
                      cost_cards=True)
    anomalies = [r for r in recs if r.get("kind") == "anomaly"
                 and r.get("series") == "step_time"]
    assert anomalies, "injected hang was not flagged"
    assert any(r["value"] >= 1.0 for r in anomalies)
    # replaying the plan on a fresh run flags again — deterministic
    assert t.sentinel.anomalies >= 1
    # flight recorder: mirror holds the full step history
    events = read_mirror(os.path.join(tmp_path, "flightrec.jsonl"))
    steps = [e for e in events if e["kind"] == "step"]
    assert len(steps) == 12
    # cost cards: train step measured, eval step static-only
    cards = {r["program"]: r for r in recs
             if r.get("kind") == "program_cost"}
    assert set(cards) == {"lm_train_step", "lm_eval_step"}
    train_card = cards["lm_train_step"]
    assert train_card["calls"] == 12
    assert train_card["flops"] > 0 and train_card["mfu"] > 0
    assert train_card["bound"] in ("compute", "bandwidth")
    # the report renders + gates on both new sections
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts/telemetry_report.py"),
         os.path.join(tmp_path, "metrics.jsonl"), "--json",
         "--require", "cost,anomaly"],
        capture_output=True, text=True, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr
    assert "program cost / roofline" in proc.stdout
    assert "anomalies" in proc.stdout
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["cost_programs"] == 2
    assert out["cost_measured_programs"] >= 1
    assert out["anomalies"] >= 1


@pytest.mark.slow
def test_trainer_suspend_dumps_flight_recorder(tmp_path, monkeypatch):
    """The suspend trigger: a latched suspend leaves an atomic ring dump
    (reason=suspend) before the run yields."""
    from pytorch_distributed_tpu.resilience.faults import (
        FaultPlan,
        FaultSpec,
    )

    from pytorch_distributed_tpu.utils.suspend import SuspendWatcher

    class YieldlessWatcher(SuspendWatcher):
        """Real latch semantics, but yielding returns instead of
        sys.exit so the test can assert on the artifacts."""

        def __init__(self):
            super().__init__(install_handlers=False)

        def go_suspend(self, exit_code: int = 0) -> None:
            self._event.clear()  # un-latch so the run finishes

    plan = FaultPlan([FaultSpec(site="train.step", kind="suspend", at=3)])
    t, recs = _lm_fit(tmp_path, monkeypatch, fault_plan=plan,
                      watcher=YieldlessWatcher())
    dump_path = os.path.join(tmp_path, "flightrec_dump.json")
    assert os.path.exists(dump_path)
    dump = read_dump(dump_path)
    assert dump["reason"] == "suspend"
    kinds = [e["kind"] for e in dump["events"]]
    assert "suspend" in kinds and "step" in kinds


# ---- kill-matrix: the mirror survives SIGKILL ----------------------------


@pytest.mark.crash
@pytest.mark.slow
def test_kill_matrix_child_leaves_readable_flightrec_mirror(tmp_path):
    """ISSUE 8 acceptance: SIGKILL the crash child at a train.step fault
    point; the relaunch-visible mirror must parse, and its last step
    event must PRECEDE the kill site (no event from the step the kill
    interrupted)."""
    kill_at = 2
    plan = json.dumps({"faults": [
        {"site": "train.step", "kind": "kill", "at": kill_at}
    ]})
    env = dict(os.environ, PDT_FAULT_PLAN=plan, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests/crash_child.py"),
         "--save-dir", os.fspath(tmp_path)],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300,
    )
    assert proc.returncode == -9, proc.stderr  # SIGKILL'd, as planned
    events = read_mirror(os.path.join(tmp_path, "flightrec.jsonl"))
    assert events, "kill left no readable mirror"
    # seqs are monotone — the mirror is a valid prefix of the run
    seqs = [e["seq"] for e in events]
    assert seqs == sorted(seqs)
    steps = [e["n"] for e in events if e["kind"] == "step"]
    # the kill fired in _pre_step of occurrence `kill_at`, so exactly
    # the prior steps' events exist: n = 1..kill_at, nothing beyond
    assert steps and max(steps) == kill_at
    # checkpoint saves before the kill are on record too
    assert any(e["kind"] == "ckpt_save" for e in events)


# ---- bench_regression ----------------------------------------------------


def test_bench_regression_directions_and_bands():
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        from bench_regression import compare, direction
    finally:
        sys.path.pop(0)

    prev = {"lm_tok_s": 1000.0, "serving_ttft_p95_ms": 100.0,
            "ckpt_save_s": 10.0, "batch_size": 128, "platform": "tpu"}
    # throughput drop + latency rise outside band -> both regress
    res = compare(
        {"lm_tok_s": 800.0, "serving_ttft_p95_ms": 150.0,
         "ckpt_save_s": 11.0, "batch_size": 128, "platform": "tpu"},
        prev,
    )
    keys = {r["key"] for r in res["regressions"]}
    assert keys == {"lm_tok_s", "serving_ttft_p95_ms"}
    # ckpt keys ride the wide disk-weather band: +10% is NOT a regression
    assert res["within"] >= 1
    # improvements within direction semantics
    res2 = compare({"lm_tok_s": 1300.0, "serving_ttft_p95_ms": 80.0},
                   prev)
    assert not res2["regressions"]
    assert {r["key"] for r in res2["improvements"]} == {
        "lm_tok_s", "serving_ttft_p95_ms"
    }
    # per-key override narrows the band
    res3 = compare({"ckpt_save_s": 12.0}, prev,
                   overrides={"ckpt_save_s": 0.1})
    assert [r["key"] for r in res3["regressions"]] == ["ckpt_save_s"]
    # direction classification
    assert direction("lm_tok_s") == "up"
    assert direction("decode_p95_ms") == "down"
    assert direction("batch_size") is None
    assert direction("padding_waste_frac") is None


def test_bench_regression_cli_roundtrip(tmp_path):
    cur = tmp_path / "cur.json"
    prev = tmp_path / "prev.json"
    prev.write_text(json.dumps({"parsed": {"lm_tok_s": 1000.0}}))
    cur.write_text(json.dumps({"parsed": {"lm_tok_s": 500.0}}))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts/bench_regression.py"),
         os.fspath(cur), os.fspath(prev), "--json"],
        capture_output=True, text=True, cwd=REPO,
    )
    assert proc.returncode == 1  # regression -> the gate trips
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["regression_keys"] == ["lm_tok_s"]
    # same comparison inside the band passes
    cur.write_text(json.dumps({"parsed": {"lm_tok_s": 980.0}}))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts/bench_regression.py"),
         os.fspath(cur), os.fspath(prev)],
        capture_output=True, text=True, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ---- pdt_top -------------------------------------------------------------


def test_pdt_top_once_renders_all_sections(tmp_path):
    path = tmp_path / "run.jsonl"
    with open(path, "w") as f:
        f.write(json.dumps({"kind": "train", "epoch": 0, "step": 3,
                            "loss": 4.5}) + "\n")
        f.write(json.dumps({"kind": "goodput", "goodput_frac": 0.9,
                            "compile_frac": 0.05, "data_wait_frac": 0.03,
                            "stall_frac": 0.0}) + "\n")
        f.write(json.dumps({"kind": "request", "rid": 0, "new_tokens": 4,
                            "ttft_s": 0.12,
                            "token_gaps_s": [0.01, 0.02]}) + "\n")
        f.write(json.dumps({"kind": "anomaly", "series": "tick_time",
                            "zscore": 12.3, "value": 1.0}) + "\n")
        f.write(json.dumps({"kind": "program_cost", "program": "decode",
                            "calls": 8, "mean_s": 0.004, "total_s": 0.032,
                            "mfu": 0.12, "bound": "bandwidth"}) + "\n")
        f.write('{"torn tail')  # must not crash the tailer
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts/pdt_top.py"),
         os.fspath(path), "--once"],
        capture_output=True, text=True, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr
    out = proc.stdout
    assert "loss 4.5000" in out
    assert "goodput  0.900" in out
    assert "ttft" in out
    assert "tick_time=1" in out
    assert "decode" in out and "[bandwidth]" in out
