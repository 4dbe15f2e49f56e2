"""Resilience runtime: fault injection, guards, fallback restore, and the
crash-recovery kill-matrix.

Fast tier: the deterministic fault plan, bounded retry, stepguard
skip/rollback semantics (through the real compiled steps), watchdog stall
handling, checkpoint validation + fallback restore, and retention
boundaries. Slow tier (``@slow @crash``): the subprocess kill-matrix —
SIGKILL a real training run at each checkpoint hazard site
{mid-shard-write, pre-manifest-commit, post-commit}, relaunch, and assert
it resumes from a complete checkpoint with monotonic step count and
finite loss.
"""

import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_tpu.resilience import faults
from pytorch_distributed_tpu.resilience.faults import (
    FaultPlan,
    FaultSpec,
    InjectedFault,
    poison_batch,
)
from pytorch_distributed_tpu.resilience.retry import (
    backoff_delays,
    retry_call,
)
from pytorch_distributed_tpu.resilience.stepguard import (
    RollbackRequested,
    StepGuard,
    finite_ok,
)
from pytorch_distributed_tpu.resilience.watchdog import Watchdog
from pytorch_distributed_tpu.utils.checkpoint import (
    MANIFEST,
    Checkpointer,
    validate_checkpoint,
)
from pytorch_distributed_tpu.utils.suspend import SuspendWatcher


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    """Every test starts and ends without an installed fault plan."""
    faults.clear_plan()
    yield
    faults.clear_plan()


def plan(*specs) -> FaultPlan:
    return faults.install_plan(FaultPlan([FaultSpec(**s) for s in specs]))


# ---------------------------------------------------------------------------
# fault plan


def test_fault_plan_json_roundtrip_and_occurrence_window():
    p = FaultPlan.from_json(
        '{"faults": [{"site": "s", "kind": "raise", "at": 1, "times": 2}]}'
    )
    p2 = FaultPlan.from_json(p.to_json())
    assert [s.site for s in p2.specs] == ["s"]
    # occurrences 0, 3+ pass; 1 and 2 fire
    assert p2.tick("s") is None
    assert p2.tick("s").kind == "raise"
    assert p2.tick("s").kind == "raise"
    assert p2.tick("s") is None
    assert p2.fired == [("s", 1, "raise"), ("s", 2, "raise")]
    # unknown sites never match and don't disturb the counter
    assert p2.tick("other") is None


def test_fault_plan_from_env_file(tmp_path, monkeypatch):
    path = tmp_path / "plan.json"
    path.write_text('{"faults": [{"site": "x", "kind": "hang"}]}')
    monkeypatch.setenv(faults.ENV_PLAN, f"@{path}")
    faults.clear_plan()  # force the env re-read
    p = faults.active_plan()
    assert p is not None and p.specs[0].site == "x"


def test_fault_point_raises_injected():
    plan({"site": "data.fetch", "kind": "raise"})
    with pytest.raises(InjectedFault):
        faults.fault_point("data.fetch")
    # windows are bounded: the next occurrence passes
    assert faults.fault_point("data.fetch") is None


def test_fault_spec_validates():
    with pytest.raises(ValueError):
        FaultSpec(site="s", kind="explode")
    with pytest.raises(ValueError):
        FaultSpec(site="s", kind="raise", times=0)


def test_poison_batch_nans_floats_only():
    batch = {"tokens": np.arange(4, dtype=np.int32),
             "weights": np.ones(4, np.float32)}
    out = poison_batch(batch)
    assert np.isnan(out["weights"]).all()
    np.testing.assert_array_equal(out["tokens"], batch["tokens"])
    with pytest.raises(ValueError):
        poison_batch({"tokens": np.arange(4, dtype=np.int32)})


# ---------------------------------------------------------------------------
# retry


def test_backoff_delays_deterministic_bounded():
    a = backoff_delays(retries=4, base_delay=0.1, max_delay=0.5, seed=7)
    b = backoff_delays(retries=4, base_delay=0.1, max_delay=0.5, seed=7)
    assert a == b  # seeded: same schedule every run
    assert a != backoff_delays(retries=4, base_delay=0.1, max_delay=0.5,
                               seed=8)
    assert all(0 < d <= 0.5 for d in a)


def test_retry_call_recovers_then_exhausts(monkeypatch):
    import pytorch_distributed_tpu.resilience.retry as retry_mod

    sleeps = []
    monkeypatch.setattr(retry_mod.time, "sleep", sleeps.append)
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    assert retry_call(flaky, retries=3) == "ok"
    assert calls["n"] == 3 and len(sleeps) == 2

    def always():
        raise OSError("permanent")

    with pytest.raises(OSError, match="permanent"):
        retry_call(always, retries=2)

    class Structural(OSError):
        pass

    def structural():
        raise Structural("no point retrying")

    with pytest.raises(Structural):
        retry_call(structural, retries=3, no_retry_on=(Structural,))
    # TypeError is not in retry_on: first raise propagates
    calls["n"] = 0

    def bug():
        calls["n"] += 1
        raise TypeError("bug")

    with pytest.raises(TypeError):
        retry_call(bug, retries=3)
    assert calls["n"] == 1


def test_record_reader_retries_transient_pread(tmp_path, monkeypatch):
    from pytorch_distributed_tpu.data.packed_record import (
        PackedRecordReader,
        PackedRecordWriter,
    )

    path = tmp_path / "r.tprc"
    with PackedRecordWriter(path) as w:
        w.write(b"hello")
    reader = PackedRecordReader(path, use_native=False)
    monkeypatch.setattr(
        "pytorch_distributed_tpu.resilience.retry.time.sleep", lambda s: None
    )
    real = reader._py.read
    fails = {"n": 2}

    def flaky(i, verify_crc=True):
        if fails["n"]:
            fails["n"] -= 1
            raise OSError("pread failover")
        return real(i, verify_crc)

    monkeypatch.setattr(reader._py, "read", flaky)
    assert reader.read(0) == b"hello"  # two failures absorbed
    reader.close()


# ---------------------------------------------------------------------------
# data loader: fetch faults + teardown


def _range_loader(**kw):
    from pytorch_distributed_tpu.data.loader import DataLoader

    class Toy:
        def __len__(self):
            return 16

        def __getitem__(self, i):
            return np.full((2, 2, 3), i, np.float32), i % 4

    return DataLoader(Toy(), batch_size=4, num_workers=0, **kw)


def test_loader_retries_fetch_faults(monkeypatch):
    monkeypatch.setattr(
        "pytorch_distributed_tpu.resilience.retry.time.sleep", lambda s: None
    )
    p = plan({"site": "data.fetch", "kind": "raise", "at": 1, "times": 2})
    batches = list(_range_loader(prefetch=1).iter_batches(0))
    assert len(batches) == 4  # both injected failures absorbed by retry
    assert len(p.fired) == 2
    # the re-fetched batch is bit-identical (deterministic RNG/data)
    clean = list(_range_loader(prefetch=1).iter_batches(0))
    np.testing.assert_array_equal(batches[1]["image"], clean[1]["image"])


def test_loader_fetch_fault_beyond_retries_raises(monkeypatch):
    monkeypatch.setattr(
        "pytorch_distributed_tpu.resilience.retry.time.sleep", lambda s: None
    )
    plan({"site": "data.fetch", "kind": "raise", "times": 50})
    with pytest.raises(InjectedFault):
        list(_range_loader(prefetch=1).iter_batches(0))
    faults.clear_plan()
    # prefetch path: the producer thread surfaces the failure too
    plan({"site": "data.fetch", "kind": "raise", "times": 50})
    with pytest.raises(InjectedFault):
        list(_range_loader(prefetch=2).iter_batches(0))


def test_loader_teardown_joins_producer_and_cancels_futures():
    """Abandoning a prefetching iterator mid-epoch must leave no live
    producer thread (blocking join, not a poll loop) and no queued decode
    futures."""
    loader = _range_loader(prefetch=2)
    loader.num_workers = 2  # exercise the pool-backed path
    before = {t.ident for t in threading.enumerate()}
    it = loader.iter_batches(0)
    next(it)
    it.close()  # generator finally: drain, join, shutdown(cancel_futures)
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        leaked = {t.ident for t in threading.enumerate()} - before
        if not leaked:
            break
        time.sleep(0.01)
    assert not leaked, f"leaked threads: {leaked}"


# ---------------------------------------------------------------------------
# stepguard


def test_finite_ok_under_jit():
    @jax.jit
    def check(loss, g):
        return finite_ok(loss, {"w": g})

    assert bool(check(jnp.float32(1.0), jnp.ones(3)))
    assert not bool(check(jnp.float32(np.nan), jnp.ones(3)))
    assert not bool(check(jnp.float32(1.0), jnp.array([1.0, np.inf, 0.0])))
    # integer leaves don't participate in the finite check
    assert bool(finite_ok(jnp.float32(0.0), {"i": jnp.arange(3)}))


def test_stepguard_counts_and_rolls_back():
    g = StepGuard(max_bad_steps=3, lag=1)
    good, bad = jnp.float32(1.0), jnp.float32(0.0)
    g.observe(good)
    g.observe(bad)   # reads the lagged good
    g.observe(bad)   # reads bad #1
    g.observe(bad)   # reads bad #2
    assert g.bad_consecutive == 2 and g.bad_total == 2
    with pytest.raises(RollbackRequested):
        g.flush()    # bad #3 trips the limit
    assert g.rollbacks == 1 and g.bad_consecutive == 0
    # a good step resets the streak
    g2 = StepGuard(max_bad_steps=2, lag=0)
    g2.observe(bad)
    g2.observe(good)
    g2.observe(bad)
    assert g2.bad_consecutive == 1 and g2.bad_total == 2
    g2.reset()
    assert g2.bad_consecutive == 0


def test_stepguard_without_limit_never_raises():
    g = StepGuard(max_bad_steps=0, lag=0)
    for _ in range(10):
        g.observe(jnp.float32(0.0))
    assert g.bad_total == 10
    g.observe(None)  # steps without the metric are ignored
    assert g.bad_total == 10


# ---------------------------------------------------------------------------
# trainers under injected NaN (the real compiled steps)


def test_nan_steps_skip_update_and_freeze_params(tmp_path, devices8):
    """Every train step poisoned: with the guard, params at the end equal
    params at the start bit-for-bit (each bad step selected the old
    state), step still advanced per consumed batch, and no host-side NaN
    ever reached the parameters."""
    from test_train import make_trainer

    plan({"site": "train.step", "kind": "nan", "times": 10_000})
    trainer = make_trainer(tmp_path, devices8, epochs=1,
                           nan_guard=True)
    before = jax.device_get(trainer.state.params)
    steps = len(trainer.train_loader)
    trainer.fit()
    after = jax.device_get(trainer.state.params)
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert trainer.guard.bad_total == steps
    assert int(jax.device_get(trainer.state.step)) == steps  # step advanced


def test_single_nan_step_recovers_and_counts(tmp_path, devices8):
    from test_train import make_trainer

    p = plan({"site": "train.step", "kind": "nan", "at": 2})
    trainer = make_trainer(tmp_path, devices8, epochs=1, nan_guard=True)
    out = trainer.fit()
    assert p.fired == [("train.step", 2, "nan")]
    assert trainer.guard.bad_total == 1
    assert np.isfinite(out["loss"])
    for leaf in jax.tree.leaves(jax.device_get(trainer.state.params)):
        assert np.isfinite(np.asarray(leaf)).all()


def test_consecutive_nans_roll_back_to_checkpoint(tmp_path, devices8):
    """K consecutive bad steps trigger rollback-to-last-good-checkpoint:
    the run restores an interval save, replays, and finishes finite."""
    from test_train import make_trainer

    plan({"site": "train.step", "kind": "nan", "at": 3, "times": 6})
    trainer = make_trainer(
        tmp_path, devices8, epochs=1, nan_guard=True, max_bad_steps=3,
        save_every_n_steps=1, keep_last_ckpts=2,
    )
    out = trainer.fit()
    assert trainer.rollbacks >= 1
    assert trainer.guard.bad_total >= 3
    assert np.isfinite(out["loss"])
    assert int(jax.device_get(trainer.state.step)) == len(
        trainer.train_loader
    )


def test_rollback_without_checkpoint_is_fatal(tmp_path, devices8):
    from test_train import make_trainer

    plan({"site": "train.step", "kind": "nan", "times": 10_000})
    trainer = make_trainer(tmp_path, devices8, epochs=1, nan_guard=True,
                           max_bad_steps=2)
    with pytest.raises(RuntimeError, match="no restorable checkpoint"):
        trainer.fit()


@pytest.mark.slow
def test_lm_trainer_nan_guard_on_tp_mesh(tmp_path, devices8):
    """The LM step's finite gate on a dp×sp×tp mesh: the pmin over every
    mesh axis must veto the update globally even though TP gradient
    shards differ per device."""
    from test_lm_trainer import make_lm_trainer

    p = plan({"site": "train.step", "kind": "nan", "at": 1})
    trainer = make_lm_trainer(tmp_path, devices8, epochs=1, nan_guard=True)
    out = trainer.fit()
    assert p.fired == [("train.step", 1, "nan")]
    assert trainer.guard.bad_total == 1
    assert np.isfinite(out["loss"])
    for leaf in jax.tree.leaves(jax.device_get(trainer.state.params)):
        assert np.isfinite(np.asarray(leaf)).all()


# ---------------------------------------------------------------------------
# watchdog


def test_watchdog_dumps_stacks_and_latches_suspend(tmp_path):
    dump = tmp_path / "stall.log"
    watcher = SuspendWatcher(install_handlers=False)
    stalls = []
    wd = Watchdog(0.2, watcher=watcher, dump_path=str(dump),
                  on_stall=stalls.append, poll_s=0.05)
    with wd:
        wd.beat()
        time.sleep(0.7)  # no beats: stall
        assert wd.stalls == 1  # one dump per stall, not one per poll
        wd.beat()  # re-arms
    assert watcher.receive_suspend_command()
    assert stalls and "pdt-watchdog" in stalls[0]  # all threads dumped
    text = dump.read_text()
    assert "watchdog stall #1" in text and "MainThread" in text


def test_watchdog_rejects_nonpositive_timeout():
    with pytest.raises(ValueError):
        Watchdog(0.0)


def test_hang_triggers_watchdog_then_suspend_checkpoint(tmp_path, devices8):
    """A synthetic hang inside the step loop: the watchdog dumps stacks
    and latches the suspend watcher; the loop (a SOFT stall — it
    recovers) then checkpoints and yields through the normal suspend
    path. The whole §3.5 contract, provoked by injection."""
    from test_train import make_trainer

    plan({"site": "train.step", "kind": "hang", "at": 2, "seconds": 1.2})
    trainer = make_trainer(
        tmp_path, devices8, epochs=1,
        watcher=SuspendWatcher(install_handlers=False),
        watchdog_timeout_s=0.3,
    )
    try:
        with pytest.raises(SystemExit):
            trainer.fit()
    finally:
        trainer.watchdog.stop()
    assert trainer.watchdog.stalls >= 1
    assert trainer.ckpt.latest_is_sharded()  # suspend save committed
    assert os.path.exists(
        os.path.join(str(tmp_path), "watchdog_stall.log")
    )


# ---------------------------------------------------------------------------
# checkpoint validation, fallback restore, retention


def _payload(step):
    return {
        "state": {"step": jnp.asarray(step, jnp.int32),
                  "w": jnp.full((4, 4), float(step))},
        "epoch": 0, "step": step,
    }


def _shard_files(d):
    return sorted(
        n for n in os.listdir(d) if n.startswith("shard-")
        and n.endswith(".npz")
    )


def test_validate_checkpoint_classifies_damage(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save_step_sharded(_payload(1), 1, keep_last=4, block=True)
    d = os.path.join(str(tmp_path), "step-00000001.ckpt")
    assert validate_checkpoint(d) == []
    # truncated shard (torn write): zip central directory lost
    shard = os.path.join(d, _shard_files(d)[0])
    blob = open(shard, "rb").read()
    with open(shard, "wb") as f:
        f.write(blob[: len(blob) // 2])
    assert any("unreadable" in p for p in validate_checkpoint(d))
    # missing shard file
    os.remove(shard)
    assert any("missing shard" in p for p in validate_checkpoint(d))
    # no manifest at all
    os.remove(os.path.join(d, MANIFEST))
    assert any("no manifest" in p.lower() for p in validate_checkpoint(d))


def test_newest_restorable_falls_back_past_torn_save(tmp_path):
    """The newest checkpoint fails validation (truncated shard / token
    mismatch) → resume scans back to the newest COMPLETE one instead of
    refusing (the fallback-restore contract)."""
    d = str(tmp_path)
    ck = Checkpointer(d)
    ck.save_step_sharded(_payload(1), 1, keep_last=4, block=True)
    ck.save_step_sharded(_payload(2), 2, keep_last=4, block=True)
    newest = os.path.join(d, "step-00000002.ckpt")
    assert ck.newest_restorable() == newest
    # truncate the newest save's shard
    shard = os.path.join(newest, _shard_files(newest)[0])
    blob = open(shard, "rb").read()
    with open(shard, "wb") as f:
        f.write(blob[: len(blob) // 2])
    assert ck.newest_restorable() == os.path.join(d, "step-00000001.ckpt")


def test_newest_restorable_rejects_token_mismatch(tmp_path):
    """A shard file from a DIFFERENT save behind this manifest (the torn
    state the save token exists to catch) fails validation and falls
    through to the older checkpoint."""
    d = str(tmp_path)
    ck = Checkpointer(d)
    ck.save_step_sharded(_payload(1), 1, keep_last=4, block=True)
    ck.save_step_sharded(_payload(2), 2, keep_last=4, block=True)
    old = os.path.join(d, "step-00000001.ckpt")
    new = os.path.join(d, "step-00000002.ckpt")
    # splice save 1's shard under save 2's expected filename
    shutil.copyfile(
        os.path.join(old, _shard_files(old)[0]),
        os.path.join(new, _shard_files(new)[0]),
    )
    assert any("token" in p for p in validate_checkpoint(new))
    assert ck.newest_restorable() == old


def test_retention_exact_boundaries_and_inflight_survival(tmp_path):
    """keep_last GC: exactly N completed checkpoints survive, and an
    in-flight (uncommitted) save is never counted or collected — the GC
    runs only after the new manifest landed."""
    d = str(tmp_path)
    ck = Checkpointer(d)
    for s in (1, 2, 3):
        ck.save_step_sharded(_payload(s), s, keep_last=2, block=True)
    names = sorted(
        n for n in os.listdir(d) if n.startswith("step-")
    )
    assert names == ["step-00000002.ckpt", "step-00000003.ckpt"]
    # in-flight: non-blocking save — before wait() commits it, every
    # already-completed checkpoint must still be present
    ck.save_step_sharded(_payload(4), 4, keep_last=1, block=False)
    assert os.path.exists(os.path.join(d, "step-00000002.ckpt"))
    assert os.path.exists(os.path.join(d, "step-00000003.ckpt"))
    ck.wait()  # commit + GC
    names = sorted(
        n for n in os.listdir(d)
        if n.startswith("step-")
        and os.path.exists(os.path.join(d, n, MANIFEST))
    )
    assert names == ["step-00000004.ckpt"]


def test_trainer_resume_falls_back_when_newest_corrupt(tmp_path, devices8):
    """End-to-end fallback: a fit leaves interval saves; the newest one is
    torn after the fact; a fresh trainer resumes from the older complete
    checkpoint instead of refusing."""
    from test_train import make_trainer

    t1 = make_trainer(tmp_path, devices8, epochs=1,
                      save_every_n_steps=2, keep_last_ckpts=2)
    t1.fit()
    ck = Checkpointer(str(tmp_path))
    steps = ck.step_checkpoints()
    assert len(steps) == 2
    newest = steps[-1][1]
    shard = os.path.join(newest, _shard_files(newest)[0])
    blob = open(shard, "rb").read()
    with open(shard, "wb") as f:
        f.write(blob[: len(blob) // 2])
    t2 = make_trainer(tmp_path, devices8, epochs=1,
                      save_every_n_steps=2, keep_last_ckpts=2)
    assert t2.try_resume()
    assert int(jax.device_get(t2.state.step)) == steps[0][0]


# ---------------------------------------------------------------------------
# the kill-matrix (slow): SIGKILL at each checkpoint hazard site, relaunch,
# assert recovery. scripts/ci_check.sh --resilience-smoke runs the
# shard_write cell alone.

KILL_SITES = ["ckpt.shard_write", "ckpt.pre_commit", "ckpt.post_commit"]


def _run_child(save_dir, env_extra=None, timeout=300):
    env = dict(os.environ)
    env.pop(faults.ENV_PLAN, None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(__file__), "crash_child.py"),
         "--save-dir", str(save_dir)],
        env=env, capture_output=True, text=True, timeout=timeout,
    )


def _progress(save_dir):
    path = os.path.join(str(save_dir), "progress.jsonl")
    with open(path) as f:
        return [json.loads(line) for line in f]


@pytest.mark.slow
@pytest.mark.crash
@pytest.mark.parametrize("site", KILL_SITES, ids=lambda s: s.split(".")[1])
def test_kill_matrix_sigkill_then_resume(tmp_path, site):
    """Run 1 dies by SIGKILL at the injected checkpoint hazard; the
    directory must hold a complete (old or new, never corrupt)
    checkpoint; run 2 resumes from it and finishes with monotonic global
    step and finite loss."""
    fault = FaultPlan([
        # occurrence 2: at least two saves committed before the kill, so
        # recovery has a guaranteed fallback even at mid-write
        FaultSpec(site=site, kind="kill", at=2)
    ])
    r1 = _run_child(tmp_path, {faults.ENV_PLAN: fault.to_json()})
    assert r1.returncode == -signal.SIGKILL, (
        f"child should die by SIGKILL at {site}; "
        f"rc={r1.returncode}\nstdout:{r1.stdout}\nstderr:{r1.stderr}"
    )
    assert not os.path.exists(os.path.join(str(tmp_path), "result.json"))
    steps_run1 = [r["gstep"] for r in _progress(tmp_path)]
    assert steps_run1  # it trained before dying

    # the invariant the whole checkpointer design promises: whatever the
    # kill point, a complete restorable checkpoint exists and validates
    ck = Checkpointer(str(tmp_path))
    restorable = ck.newest_restorable()
    assert restorable is not None
    assert validate_checkpoint(restorable) == []

    r2 = _run_child(tmp_path)
    assert r2.returncode == 0, (
        f"relaunch failed\nstdout:{r2.stdout}\nstderr:{r2.stderr}"
    )
    with open(os.path.join(str(tmp_path), "result.json")) as f:
        result = json.load(f)
    assert result["resumed"], "run 2 must restore a checkpoint"
    assert np.isfinite(result["val_loss"])

    records = _progress(tmp_path)
    pid2 = records[-1]["pid"]
    steps_run2 = [r["gstep"] for r in records if r["pid"] == pid2]
    # monotonic step count within the resumed run, no gaps
    assert steps_run2 == list(
        range(steps_run2[0], steps_run2[0] + len(steps_run2))
    )
    # resumed at (not past) work already done: first step of run 2
    # continues from a checkpoint at or before run 1's last step
    assert steps_run2[0] <= steps_run1[-1] + 1
    # and the full run completed: 2 epochs x 2 steps at the child config
    assert result["final_step"] == 4
    assert all(np.isfinite(r["loss"]) for r in records if r["pid"] == pid2)
