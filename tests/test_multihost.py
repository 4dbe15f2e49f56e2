"""The multi-process/multi-host path, exercised for real on localhost.

Round-1 VERDICT missing #1: the TPU equivalent of the reference's core
artifact — multi-node DDP with env rendezvous, cross-host all-reduce,
rank-0 checkpointing, and the suspend agreement
(``restnet_ddp.py:87-99,154-155``) — had zero coverage. These tests spawn
TWO real ``jax.distributed`` processes on the CPU backend (4 virtual
devices each → an 8-device global mesh) and run the actual Trainer/DDP
code path end to end.

Slow (16-37 s each here, ~110 s together: two CPU compiles per launch);
marked ``multihost`` and ``slow``, so the fast tier leaves them out.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import time

import pytest

# Every case below spawns a real 2-process jax.distributed run on the CPU
# backend. An earlier jaxlib's CPU client could not compile cross-process
# programs and these were xfail; jaxlib 0.9.0's can, and they pass — as
# real runs, 16-37 s each, which the fast tier (870 s cap) has no room for.
pytestmark = [pytest.mark.multihost, pytest.mark.slow]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHILD = os.path.join(REPO, "tests", "multihost_child.py")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def launch(rank: int, port: int, mode: str, save_dir: str,
           extra_env=None) -> subprocess.Popen:
    env = {
        k: v
        for k, v in os.environ.items()
        # A parent pytest env pins JAX to 8 devices / a platform; children
        # configure their own backend (multihost_child.py header).
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS", "PYTHONPATH")
    }
    env.update(
        MASTER_IP="127.0.0.1",
        MASTER_PORT=str(port),
        WORLD_SIZE="2",
        RANK=str(rank),
    )
    env.update(extra_env or {})
    return subprocess.Popen(
        [sys.executable, CHILD, mode, save_dir],
        env=env,
        cwd=REPO,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
    )


def communicate(procs, timeout=600):
    outs = []
    deadline = time.monotonic() + timeout
    for p in procs:
        try:
            out, err = p.communicate(timeout=max(deadline - time.monotonic(), 1))
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append((p.returncode, out, err))
    return outs


def result_line(stdout: str) -> dict:
    for line in reversed(stdout.strip().splitlines()):
        try:
            return json.loads(line)
        except json.JSONDecodeError:
            continue
    raise AssertionError(f"no JSON result in child stdout:\n{stdout}")


def test_two_process_rendezvous_and_agreement(tmp_path):
    """Env-contract rendezvous works; training state agrees bit-for-bit
    across hosts (the gradient psum really is global); rank-0-only
    checkpoint/metrics writes (``restnet_ddp.py:36,145``)."""
    port = free_port()
    save = os.fspath(tmp_path / "ddp")
    procs = [launch(r, port, "train", save) for r in (0, 1)]
    results = communicate(procs)
    for rc, out, err in results:
        assert rc == 0, f"child failed rc={rc}\nstdout:{out}\nstderr:{err}"
    r0, r1 = (result_line(out) for _, out, _ in results)
    assert r0["world"] == r1["world"] == 2
    # Replicated-state agreement: identical params and identical global
    # (psum'd) validation metrics on both hosts.
    assert r0["param_l1"] == r1["param_l1"]
    assert r0["val_loss"] == r1["val_loss"]
    assert r0["acc1"] == r1["acc1"]
    assert r0["final_step"] == r1["final_step"] > 0
    # rank-0-gated artifacts: exactly one process wrote them
    assert os.path.exists(os.path.join(save, "best.ckpt"))
    assert os.path.exists(os.path.join(save, "metrics.jsonl"))


def test_multihost_suspend_agreement_and_resume(tmp_path):
    """SIGTERM delivered to ONE (non-primary) host must make BOTH hosts
    checkpoint and yield together (suspend_sync_every=1 any-reduce,
    trainer._maybe_suspend), and a relaunch must resume mid-run
    (``restnet_ddp.py:127-132`` + SURVEY.md §3.5)."""
    port = free_port()
    save = os.fspath(tmp_path / "suspend")
    os.makedirs(save, exist_ok=True)
    procs = [launch(r, port, "suspend", save) for r in (0, 1)]

    # wait until both ranks have taken at least one optimizer step
    deadline = time.monotonic() + 420
    sentinels = [os.path.join(save, f"started.{r}") for r in (0, 1)]
    while time.monotonic() < deadline:
        if all(os.path.exists(s) for s in sentinels):
            break
        if any(p.poll() is not None for p in procs):
            results = communicate(procs, timeout=5)
            raise AssertionError(f"child exited before starting: {results}")
        time.sleep(0.5)
    else:
        for p in procs:
            p.kill()
        raise AssertionError("children never reached the training loop")

    procs[1].send_signal(signal.SIGTERM)  # the NON-primary host is preempted
    results = communicate(procs, timeout=300)
    for rc, out, err in results:
        # go_suspend exits 0 after the checkpoint is on disk
        assert rc == 0, f"suspend path failed rc={rc}\nstdout:{out}\nstderr:{err}"
        assert "suspend" in err.lower() or "suspend" in out.lower(), (out, err)
    assert os.path.exists(os.path.join(save, "latest.ckpt"))

    # relaunch: both hosts must resume from the checkpoint, not epoch 0 step 0
    port2 = free_port()
    procs = [launch(r, port2, "train", save) for r in (0, 1)]
    results = communicate(procs)
    for rc, out, err in results:
        assert rc == 0, f"resume failed rc={rc}\nstdout:{out}\nstderr:{err}"
    outs = [out for _, out, _ in results]
    assert any("resumed from" in o for o in outs), outs
    r0, r1 = (result_line(o) for o in outs)
    assert r0["param_l1"] == r1["param_l1"]


def test_lm_trainer_two_process_tp_sharded_checkpoint(tmp_path):
    """LMTrainer with ring attention + tensor parallelism spanning two
    processes: TP-sharded leaves are NOT locally addressable, so the
    checkpoint payload's gather_global must run its cross-process
    process_allgather on all ranks (the exact path that would deadlock if
    the gather were rank-0-gated). Asserts cross-host agreement of the
    gathered params and psum'd metrics, and that best.ckpt landed."""
    port = free_port()
    save = os.fspath(tmp_path / "lm")
    procs = [launch(r, port, "lm", save) for r in (0, 1)]
    results = communicate(procs)
    for rc, out, err in results:
        assert rc == 0, f"lm child failed rc={rc}\nstdout:{out}\nstderr:{err}"
    r0, r1 = (result_line(out) for _, out, _ in results)
    assert r0["world"] == r1["world"] == 2
    assert r0["param_l1"] == r1["param_l1"]
    assert r0["val_loss"] == r1["val_loss"]
    assert r0["final_step"] == r1["final_step"] > 0
    assert r0["sharded_ckpt_ok"] and r1["sharded_ckpt_ok"]
    assert os.path.isdir(os.path.join(save, "best.ckpt"))
    assert os.path.isdir(os.path.join(save, "latest.ckpt"))
    import glob

    for r in (0, 1):
        # r4 layout: token-named shard files (shard-<token>-NNNNN.npz)
        assert glob.glob(
            os.path.join(save, "latest.ckpt", f"shard-*-{r:05d}.npz")
        )


def test_suspend_sync_gt_one_defers_without_deadlock(tmp_path):
    """suspend_sync_every=3: a SIGTERM landing at a non-agreement step must
    be DEFERRED (latched) to the next agreement step, not acted on locally
    — acting locally sends one host into the collective checkpoint gather
    while the other runs the next train step (permanent hang). Regression
    for the r2 code-review finding."""
    port = free_port()
    save = os.fspath(tmp_path / "sync3")
    os.makedirs(save, exist_ok=True)
    procs = [
        launch(r, port, "suspend", save, extra_env={"SUSPEND_SYNC": "3"})
        for r in (0, 1)
    ]
    deadline = time.monotonic() + 420
    sentinels = [os.path.join(save, f"started.{r}") for r in (0, 1)]
    while time.monotonic() < deadline:
        if all(os.path.exists(s) for s in sentinels):
            break
        if any(p.poll() is not None for p in procs):
            raise AssertionError(f"child died early: {communicate(procs, 5)}")
        time.sleep(0.5)
    else:
        for p in procs:
            p.kill()
        raise AssertionError("children never reached the training loop")
    procs[1].send_signal(signal.SIGTERM)
    results = communicate(procs, timeout=300)  # would time out on deadlock
    for rc, out, err in results:
        assert rc == 0, f"rc={rc}\nstdout:{out}\nstderr:{err}"
    assert os.path.exists(os.path.join(save, "latest.ckpt"))


def test_multihost_crash_mid_save_keeps_previous_checkpoint(tmp_path):
    """VERDICT r3 #1 done-condition: a mid-save crash (data files written
    on both ranks, manifest never committed) must leave the PREVIOUS
    checkpoint restorable by a fresh 2-process job — the token-named file
    layout means an interrupted save never clobbers the committed one."""
    port = free_port()
    save = os.fspath(tmp_path / "crash")
    os.makedirs(save, exist_ok=True)
    procs = [launch(r, port, "lm_crash_save", save) for r in (0, 1)]
    results = communicate(procs)
    for rc, out, err in results:
        assert rc == 0, f"child failed rc={rc}\nstdout:{out}\nstderr:{err}"
    for _, out, _ in results:
        assert result_line(out)["crash_save_done"]

    # orphaned second-save data files exist next to the committed save
    import glob

    assert len(glob.glob(os.path.join(save, "latest.ckpt", "shard-*.npz"))) == 4

    port2 = free_port()
    procs = [launch(r, port2, "lm_crash_resume", save) for r in (0, 1)]
    results = communicate(procs)
    for rc, out, err in results:
        assert rc == 0, f"child failed rc={rc}\nstdout:{out}\nstderr:{err}"
    for _, out, _ in results:
        r = result_line(out)
        # the COMPLETE save (epoch 1, step 5) survives; the crashed one
        # (epoch 2, step 9) is invisible
        assert r["resumed"] and r["epoch"] == 1 and r["step"] == 5, r
