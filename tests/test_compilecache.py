"""Cold-start elimination (round 8 tentpole): program registry coverage,
AOT export round-trip, persistent-cache hits, warmup runner ordering,
scheduler cold-request honesty, and the double-fit zero-new-jit-entries
regression the ISSUE's satellite calls for.

The registry's contract is the dual of ``analysis.guards.no_recompile``:
the guard fails when a program compiles that *shouldn't have*; the
registry predicts every program that *will* — and ``assert_covers`` ties
the two together by failing when the live jit caches hold anything the
enumeration missed.
"""

import contextlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_tpu.compilecache import (
    CacheHitCounter,
    CoverageError,
    ProgramRegistry,
    ProgramSpec,
    WarmupRunner,
    enable_persistent_cache,
    export_program,
    load_exported,
    run_fingerprint,
    save_exported,
    serving_registry,
)
from pytorch_distributed_tpu.compilecache.aot import (
    _reset_jax_cache_state,
    artifact_path,
)
from pytorch_distributed_tpu.models.transformer import (
    TransformerLM,
    tiny_config,
)
from pytorch_distributed_tpu.serving import PagedEngine, Scheduler
from pytorch_distributed_tpu.utils.profiling import MetricsLogger


def _lm(max_seq_len=96):
    cfg = tiny_config(attention="dense", max_seq_len=max_seq_len)
    params = TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return cfg, params


@contextlib.contextmanager
def _persistent_cache(tmp_path):
    """enable_persistent_cache with the global jax config restored after —
    the suite must not keep writing executables into a dead tmp dir."""
    prev_dir = getattr(jax.config, "jax_compilation_cache_dir", None)
    prev_min_t = getattr(
        jax.config, "jax_persistent_cache_min_compile_time_secs", 1.0
    )
    prev_min_b = getattr(
        jax.config, "jax_persistent_cache_min_entry_size_bytes", 0
    )
    try:
        yield enable_persistent_cache(os.fspath(tmp_path))
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", prev_min_t
        )
        jax.config.update(
            "jax_persistent_cache_min_entry_size_bytes", prev_min_b
        )
        _reset_jax_cache_state()  # unbind the tmp dir from the singleton


# ---------------------------------------------------------------------------
# fingerprint + registry (pure host logic — fast tier)
# ---------------------------------------------------------------------------


def test_run_fingerprint_stable_and_sensitive():
    a = run_fingerprint(extra=("cfg_a",))
    assert a == run_fingerprint(extra=("cfg_a",))  # deterministic
    assert a != run_fingerprint(extra=("cfg_b",))  # config keys the cache
    assert a != run_fingerprint()  # extras are part of the key
    assert len(a) == 16 and int(a, 16) >= 0  # short stable hex


def test_registry_rejects_duplicates_and_reports_names():
    reg = ProgramRegistry("fp")
    reg.add(ProgramSpec("a", warm=lambda e: None))
    with pytest.raises(ValueError, match="duplicate"):
        reg.add(ProgramSpec("a", warm=lambda e: None))
    reg.add(ProgramSpec("b", warm=lambda e: None, priority=0))
    assert reg.names == ["a", "b"] and len(reg) == 2
    assert reg.predicts("a") and not reg.predicts("c")


def test_coverage_guard_unpredicted_and_over_budget():
    reg = ProgramRegistry()
    reg.add(ProgramSpec("step", warm=lambda e: None, expect_entries=2))
    reg.assert_covers([])  # fewer live programs than predicted is fine
    reg.assert_covers(["step", "step"])  # at budget
    with pytest.raises(CoverageError, match="outside the registry"):
        reg.assert_covers(["step", "rogue"])
    with pytest.raises(CoverageError, match="retraced past"):
        reg.assert_covers(["step"] * 3)


# ---------------------------------------------------------------------------
# serving registry enumeration vs the engine's live bucketing
# ---------------------------------------------------------------------------


def test_serving_registry_enumerates_every_engine_bucket():
    cfg, params = _lm()
    engine = PagedEngine(cfg, params, n_slots=3, block_len=16,
                         prefill_chunk=32)
    reg = serving_registry(engine)
    assert reg.predicts(engine.DECODE_PROGRAM)
    # every bucket bucket_for can produce must be enumerated: job counts
    # 1..n_slots at every admissible chunk start
    class _Job:
        def __init__(self, start):
            self.start = start

    starts = range(0, cfg.max_seq_len - engine.chunk + 1, engine.chunk)
    for k in range(1, engine.n_slots + 1):
        for start in starts:
            k_pad, wp = engine.bucket_for([_Job(start)] * k)
            assert (k_pad, wp) in engine.chunk_buckets()
            assert reg.predicts(engine.chunk_program_name(k_pad, wp))
    # priority: decode + smallest bucket are serve-critical (foreground)
    by_name = {s.name: s for s in reg}
    assert by_name[engine.DECODE_PROGRAM].priority == 0
    smallest = min(engine.chunk_buckets())
    assert by_name[engine.chunk_program_name(*smallest)].priority == 0


def test_serving_coverage_guard_passes_after_traffic():
    cfg, params = _lm()
    s = Scheduler(cfg, params, n_slots=2, block_len=16, prefill_chunk=32)
    reg = serving_registry(s.engine)
    rng = np.random.default_rng(0)
    for n in (6, 20, 40):
        s.submit(rng.integers(1, cfg.vocab_size, size=n).astype(np.int32), 4)
    s.drain()
    assert s.engine.compiled_program_names()  # something really compiled
    reg.assert_covers(s.engine.compiled_program_names())


@pytest.mark.parametrize("floor,buckets", [
    ((1, 1), 12),  # k in 1, 2, 4 and w in 1, 2, 4, 6
    ((2, 4), 4),  # k in 2, 4 and w in 4, 6
    ((8, 64), 1),  # clipped to the engine's widest bucket: (4, 6)
])
def test_a_bucket_floor_leaves_fewer_chunk_programs(floor, buckets):
    """``chunk_bucket_floor`` widens every bucket to at least (jobs,
    width): ``bucket_for``, the enumeration, the registry and ``warm_chunk``
    agree on the program that runs a batch of jobs."""
    cfg, params = _lm()
    engine = PagedEngine(cfg, params, n_slots=3, block_len=16,
                         prefill_chunk=32, chunk_bucket_floor=floor)
    assert len(engine.chunk_buckets()) == buckets
    k_floor, w_floor = min(floor[0], 4), min(floor[1], engine.table_width)
    assert min(k for k, _ in engine.chunk_buckets()) == k_floor
    assert min(w for _, w in engine.chunk_buckets()) == w_floor
    reg = serving_registry(engine)

    class _Job:
        def __init__(self, start):
            self.start = start

    for k in range(1, engine.n_slots + 1):
        for start in range(0, cfg.max_seq_len - engine.chunk + 1,
                           engine.chunk):
            bucket = engine.bucket_for([_Job(start)] * k)
            assert bucket in engine.chunk_buckets()
            assert reg.predicts(engine.chunk_program_name(*bucket))
    # asked for the narrowest bucket, the warm-up warms the one that runs
    engine.warm_chunk(1, 1)
    assert engine.has_chunk_program(*min(engine.chunk_buckets()))
    assert engine.compiled_program_names() == [
        engine.chunk_program_name(*min(engine.chunk_buckets()))]


def test_a_bucket_floor_serves_the_same_streams():
    cfg, params = _lm()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
               for n in (6, 20, 40, 33)]
    streams = []
    for floor in ((1, 1), (2, 4)):
        s = Scheduler(cfg, params, n_slots=2, block_len=16,
                      prefill_chunk=32, chunk_bucket_floor=floor)
        rids = [s.submit(p, 4) for p in prompts]
        out = s.drain()
        streams.append([[int(t) for t in out[r]] for r in rids])
        names = [n for n in s.engine.compiled_program_names()
                 if n.startswith("chunk_prefill")]
        assert set(names) <= {s.engine.chunk_program_name(*b)
                              for b in s.engine.chunk_buckets()}
        assert s.engine.allocator.in_use == 0
    assert streams[0] == streams[1]
    assert names == ["chunk_prefill[k=2,w=4]"]


def test_a_cap_on_chunk_jobs_bounds_the_programs_and_keeps_the_streams():
    """``max_chunk_jobs``: the scheduler prefills the oldest prompts first
    and the rest wait a tick, so no chunk program is wider than the cap
    and every stream is what it was."""
    cfg, params = _lm()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
               for n in (40, 6, 70, 33, 20)]
    streams = {}
    for cap in (None, 1, 2):
        s = Scheduler(cfg, params, n_slots=4, block_len=16,
                      prefill_chunk=32, max_chunk_jobs=cap)
        assert max(k for k, _ in s.engine.chunk_buckets()) == (cap or 4)
        rids = [s.submit(p, 4) for p in prompts]
        sizes, run_chunks = [], s.engine.run_chunks
        s.engine.run_chunks = lambda jobs: (sizes.append(len(jobs)),
                                            run_chunks(jobs))
        out = s.drain()
        streams[cap] = [[int(t) for t in out[r]] for r in rids]
        assert max(sizes) == (cap or 4)  # four prompts are admitted at once
        ks = {int(n.split("k=")[1].split(",")[0])
              for n in s.engine.compiled_program_names()
              if n.startswith("chunk_prefill")}
        assert max(ks) == (cap or 4)
        serving_registry(s.engine).assert_covers(
            s.engine.compiled_program_names())
        assert s.engine.allocator.in_use == 0
    assert streams[None] == streams[1] == streams[2]
    with pytest.raises(ValueError, match="max_chunk_jobs"):
        s.engine.run_chunks([None] * 3)
    with pytest.raises(ValueError, match="max_chunk_jobs"):
        PagedEngine(cfg, params, n_slots=2, max_chunk_jobs=0)


@pytest.mark.parametrize("floor", [(3, 4), (4, 0), (0, 1)])
def test_a_bucket_floor_is_two_powers_of_two(floor):
    cfg, params = _lm()
    with pytest.raises(ValueError, match="chunk_bucket_floor"):
        PagedEngine(cfg, params, n_slots=2, chunk_bucket_floor=floor)


# ---------------------------------------------------------------------------
# scheduler cold-request honesty + warmup
# ---------------------------------------------------------------------------


def test_scheduler_cold_flag_lands_in_metrics_and_jsonl(tmp_path):
    cfg, params = _lm()
    path = os.fspath(tmp_path / "serve.jsonl")
    with MetricsLogger(path) as mlog:
        s = Scheduler(cfg, params, n_slots=2, block_len=16,
                      prefill_chunk=32, metrics_log=mlog)
        rng = np.random.default_rng(0)
        for _ in range(4):
            s.submit(rng.integers(1, cfg.vocab_size, size=8)
                     .astype(np.int32), 4)
        s.drain()
        m = s.metrics()
    # the first batch compiled its bucket + the decode tick mid-traffic
    assert m["cold_requests"] >= 1
    assert m["compile_s"] > 0  # the stall was attributed to the ledger
    reqs = [json.loads(line) for line in open(path)
            if json.loads(line).get("kind") == "request"]
    assert len(reqs) == 4 and any(r["cold"] for r in reqs)
    # warm-only TTFT excludes exactly the cold requests
    assert m["ttft_warm_count"] == len(reqs) - m["cold_requests"]
    assert m["ttft_count"] == len(reqs)


def test_scheduler_warmup_eliminates_cold_requests(tmp_path):
    cfg, params = _lm()
    path = os.fspath(tmp_path / "serve.jsonl")
    with MetricsLogger(path) as mlog:
        s = Scheduler(cfg, params, n_slots=2, block_len=16,
                      prefill_chunk=32, metrics_log=mlog)
        runner = s.warmup(background=False)
        assert runner.summary()["programs"] == len(serving_registry(s.engine))
        rng = np.random.default_rng(0)
        for _ in range(4):
            s.submit(rng.integers(1, cfg.vocab_size, size=8)
                     .astype(np.int32), 4)
        s.drain()
        m = s.metrics()
    assert m["cold_requests"] == 0
    records = [json.loads(line) for line in open(path)]
    reqs = [r for r in records if r.get("kind") == "request"]
    assert reqs and not any(r["cold"] for r in reqs)
    # one kind="warmup" manifest record per registry program
    warms = [r for r in records if r.get("kind") == "warmup"]
    assert {r["program"] for r in warms} == set(
        serving_registry(s.engine).names
    )
    # warmed = predicted: the guard closes over the whole run
    serving_registry(s.engine).assert_covers(
        s.engine.compiled_program_names()
    )


def test_scheduler_warmup_background_leaves_serve_critical_hot():
    cfg, params = _lm()
    s = Scheduler(cfg, params, n_slots=2, block_len=16, prefill_chunk=32)
    runner = s.warmup(background=True)
    # the foreground portion (decode tick + smallest bucket) is hot
    # before run() returns — the scheduler can start serving immediately
    assert s.engine.has_decode_program
    smallest = min(s.engine.chunk_buckets())
    assert s.engine.has_chunk_program(*smallest)
    runner.wait(timeout=300)
    recs = runner.records
    assert {r["program"] for r in recs} == set(
        serving_registry(s.engine).names
    )
    bg = [r for r in recs if r["background"]]
    assert bg and all(r["priority"] > 0 for r in bg)


# ---------------------------------------------------------------------------
# warmup runner (fake specs — ordering, manifest, ledger split)
# ---------------------------------------------------------------------------


def test_warmup_runner_priority_order_and_summary():
    order = []
    reg = ProgramRegistry("fp123")
    reg.add(ProgramSpec("late", warm=lambda e: order.append(("late", e)),
                        priority=1))
    reg.add(ProgramSpec("first", warm=lambda e: order.append(("first", e)),
                        priority=0))
    runner = WarmupRunner(reg).run(background=False)
    assert [n for n, _ in order] == ["first", "late"]
    assert all(e for _, e in order)  # foreground warms execute inert
    s = runner.summary()
    assert s["programs"] == 2 and s["fingerprint"] == "fp123"
    assert s["cache_hits"] + s["fresh"] == 2


def test_warmup_runner_background_is_aot_only():
    events = []
    reg = ProgramRegistry()
    reg.add(ProgramSpec("fg", warm=lambda e: events.append(("fg", e)),
                        priority=0))
    reg.add(ProgramSpec("bg", warm=lambda e: events.append(("bg", e)),
                        priority=1))
    runner = WarmupRunner(reg).run(background=True)
    runner.wait(timeout=60)
    assert dict(events) == {"fg": True, "bg": False}  # bg never executes
    recs = {r["program"]: r for r in runner.records}
    assert recs["fg"]["background"] is False
    assert recs["bg"]["background"] is True


def test_warmup_runner_ledger_attribution_foreground_only():
    from pytorch_distributed_tpu.telemetry import GoodputLedger

    ledger = GoodputLedger()
    ledger.start()
    reg = ProgramRegistry()
    reg.add(ProgramSpec("fg", warm=lambda e: None, priority=0))
    reg.add(ProgramSpec("bg", warm=lambda e: None, priority=1))
    runner = WarmupRunner(reg, ledger=ledger).run(background=True)
    runner.wait(timeout=60)
    fg = [r for r in runner.records if not r["background"]][0]
    # the foreground compile's wall time is fully classified (compile +
    # trace); background compiles never stall the run, so never book time
    booked = ledger.seconds("compile") + ledger.seconds("trace")
    assert booked == pytest.approx(fg["seconds"], rel=0.5, abs=0.05)


# ---------------------------------------------------------------------------
# AOT artifacts: export round-trip + corruption fall-through
# ---------------------------------------------------------------------------


def test_aot_export_roundtrip_token_identical(tmp_path):
    """Serialize → reload under a fresh fingerprint lookup → greedy decode
    must be token-identical to the in-process JIT path (the satellite's
    round-trip gate)."""
    cfg, params = _lm(max_seq_len=48)
    model = TransformerLM(cfg)
    L = cfg.max_seq_len

    jit_fn = jax.jit(lambda p, toks: model.apply({"params": p}, toks))
    avals = (
        jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                     params),
        jax.ShapeDtypeStruct((1, L), jnp.int32),
    )
    fp = run_fingerprint(extra=(cfg,))
    exported = export_program(jit_fn, *avals)
    path = save_exported(os.fspath(tmp_path), "lm_logits", fp, exported)
    assert os.path.exists(path) and fp in os.path.basename(path)
    # a different environment fingerprint is a MISS, never a wrong program
    assert load_exported(os.fspath(tmp_path), "lm_logits", "0" * 16) is None
    reloaded = load_exported(os.fspath(tmp_path), "lm_logits", fp)
    assert reloaded is not None

    prompt = np.random.default_rng(3).integers(
        1, cfg.vocab_size, size=8
    ).astype(np.int32)

    def greedy(call, steps=10):
        toks = np.zeros((1, L), np.int32)
        toks[0, : len(prompt)] = prompt
        n = len(prompt)
        for _ in range(steps):
            logits = np.asarray(call(params, jnp.asarray(toks)))
            toks[0, n] = int(logits[0, n - 1].argmax())
            n += 1
        return toks[0, len(prompt):n].copy()

    np.testing.assert_array_equal(greedy(jit_fn), greedy(reloaded.call))


def test_load_exported_corruption_falls_through(tmp_path, caplog):
    cache = os.fspath(tmp_path)
    # missing: plain miss, no log noise
    assert load_exported(cache, "ghost", "ab" * 8) is None
    # garbage blob: logged warning + None — never a crash
    path = artifact_path(cache, "bad", "cd" * 8)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as f:
        f.write(b"definitely not a serialized jax.export program")
    with caplog.at_level("WARNING", logger="pytorch_distributed_tpu"):
        assert load_exported(cache, "bad", "cd" * 8) is None
    assert any("corrupt" in r.message or "stale" in r.message
               for r in caplog.records)
    # truncated real artifact: same fall-through
    cfg, params = _lm(max_seq_len=32)
    jit_fn = jax.jit(
        lambda p, t: TransformerLM(cfg).apply({"params": p}, t)
    )
    avals = (
        jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                     params),
        jax.ShapeDtypeStruct((1, 32), jnp.int32),
    )
    good = save_exported(cache, "torn", "ef" * 8,
                         export_program(jit_fn, *avals))
    blob = open(good, "rb").read()
    with open(good, "wb") as f:
        f.write(blob[: len(blob) // 2])
    assert load_exported(cache, "torn", "ef" * 8) is None


def test_persistent_cache_hit_counter(tmp_path):
    """First compile writes the persistent cache; after clearing the
    in-memory jit caches, recompiling the same program is a disk hit the
    monitoring listener observes — the mechanism CacheHitCounter, the
    warmup manifest's cache_hit flag, and --expect-hits all share."""
    with _persistent_cache(tmp_path / "cc"):
        fn = jax.jit(lambda x: x * 2.0 + 1.0)
        x = jnp.arange(8, dtype=jnp.float32)
        with CacheHitCounter() as cold:
            np.testing.assert_allclose(np.asarray(fn(x)),
                                       np.arange(8) * 2.0 + 1.0)
        jax.clear_caches()
        with CacheHitCounter() as warm:
            fn(x)
        assert warm.hits >= cold.hits + 1


# ---------------------------------------------------------------------------
# trainers: double-fit regression + registry coverage + warmup manifest
# ---------------------------------------------------------------------------


def _resnet_trainer(tmp_path, devices8, **cfg_over):
    from pytorch_distributed_tpu.data import SyntheticImageClassification
    from pytorch_distributed_tpu.models.resnet import BasicBlock, ResNet
    from pytorch_distributed_tpu.parallel import make_mesh
    from pytorch_distributed_tpu.train import Trainer, TrainerConfig

    cfg = TrainerConfig(
        epochs=1, batch_size=2, lr=0.05, save_dir=os.fspath(tmp_path),
        log_every=0, num_workers=0, prefetch=1, **cfg_over,
    )
    model = ResNet(stage_sizes=(1, 1), block_cls=BasicBlock,
                   num_classes=10, num_filters=8)
    return Trainer(
        model,
        SyntheticImageClassification(size=64, image_size=16, num_classes=10),
        SyntheticImageClassification(size=32, image_size=16, num_classes=10,
                                     seed=1),
        cfg, mesh=make_mesh(devices8), input_shape=(1, 16, 16, 3),
    )


def test_trainer_double_fit_zero_new_jit_entries(tmp_path, devices8):
    """Two consecutive fit() runs, same process, identical config: the
    second run must add ZERO jit-cache entries — the same cache-growth
    probe no_recompile watches, extended across whole fit runs."""
    trainer = _resnet_trainer(tmp_path, devices8)
    trainer.fit()
    before = trainer.compiled_program_names()
    assert "train_step" in before and "eval_step" in before
    trainer.assert_registry_covers()  # acceptance: trainers' half
    trainer.fit()
    assert trainer.compiled_program_names() == before
    trainer.assert_registry_covers()


def test_trainer_warmup_populates_cache_and_manifest(tmp_path, devices8):
    with _persistent_cache(tmp_path / "cc") as cache_dir:
        trainer = _resnet_trainer(
            tmp_path / "run", devices8, warmup=True,
            metrics_out=os.fspath(tmp_path / "metrics.jsonl"),
        )
        trainer.fit()
        trainer.assert_registry_covers()
    records = [json.loads(line)
               for line in open(tmp_path / "metrics.jsonl")]
    warms = [r for r in records if r.get("kind") == "warmup"]
    assert {r["program"] for r in warms} == {"train_step", "eval_step"}
    assert all(r["fingerprint"] for r in warms)
    # the AOT lower+compile really wrote executables to disk
    cache_files = [f for _, _, fs in os.walk(cache_dir) for f in fs]
    assert cache_files, "persistent cache dir is empty after warmup"


@pytest.mark.slow
def test_lm_trainer_warmup_registry_coverage(tmp_path, devices8):
    from pytorch_distributed_tpu.data import SyntheticTokens
    from pytorch_distributed_tpu.parallel import make_mesh
    from pytorch_distributed_tpu.train import LMTrainer, LMTrainerConfig

    mesh = make_mesh(devices8[:4], data_parallel=2, seq_parallel=2)
    with _persistent_cache(tmp_path / "cc"):
        cfg = LMTrainerConfig(
            epochs=1, batch_size=2, save_dir=os.fspath(tmp_path / "run"),
            num_workers=0, log_every=0, warmup_steps=0, warmup=True,
            metrics_out=os.fspath(tmp_path / "metrics.jsonl"),
        )
        trainer = LMTrainer(
            tiny_config(attention="ring"),
            SyntheticTokens(16, 32, 128),
            SyntheticTokens(8, 32, 128, seed=1),
            cfg, mesh=mesh,
        )
        trainer.fit()
        trainer.assert_registry_covers()
    records = [json.loads(line)
               for line in open(tmp_path / "metrics.jsonl")]
    warms = [r for r in records if r.get("kind") == "warmup"]
    assert {r["program"] for r in warms} == {"lm_train_step",
                                             "lm_eval_step"}
