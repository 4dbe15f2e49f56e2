"""Every cell's harness end to end on the CPU at its toy size, the refusal
to measure without a TPU, the control, and the timed path broken."""

import json
import os
import subprocess
import sys

import pytest

from conftest import run_cell, with_unshipped

CELLS = ["gpt2-medium.pretrain", "gpt2-medium.chat-backlog"]


def test_the_manifest_lists_these_cells(manifest):
    assert [w["name"] for w in manifest["workloads"]] == CELLS


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_end_to_end_at_toy_size(root, manifest, workload):
    rc, line, out, err = run_cell(root, workload)
    assert rc == 0, err[-3000:]
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True, out[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    cell = next(w for w in manifest["workloads"] if w["name"] == workload)
    assert line["device"]["platform"] == "cpu"  # and says so
    assert line["device"]["count"] == cell["chips"]
    want = {m["name"] for m in manifest["end_to_end"]
            if workload in m.get("workloads", [workload])}
    assert set(line["metrics"]) == want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    # each number compared is printed beside its limit
    assert out.count("\ncheck ") + out.startswith("check ") >= 3
    assert "limit" in out


def test_the_unshipped_four_chip_cell_runs_on_four_virtual_devices(
        root, tmp_path):
    """``resnet50.ddp4`` is not in BENCHMARK.json (PERF.md section 7); its
    files are kept, and added as data they run: packed records, the C
    collate, a 4-way data mesh, the all-reduce. Its limits are unproven,
    so ``correct`` is not asserted; the loss against the reference is."""
    copy, manifest = with_unshipped(root, tmp_path, "resnet50.ddp4")
    rc, line, out, err = run_cell(copy, "resnet50.ddp4")
    assert rc == 0, err[-3000:]
    assert line["device"] == dict(line["device"], platform="cpu", count=4)
    assert set(line["metrics"]) == {"train_images_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    checks = {c["name"]: c for c in line["checks"]}
    assert all(checks[f"loss_step{i}_rel_gap"]["ok"] for i in range(3))
    assert checks["compilations_in_window"]["ok"]
    # a measuring run of it without a TPU fails like any other
    rc, line, out, err = run_cell(copy, "resnet50.ddp4", tiny=False)
    assert rc != 0 and line is None and "TPU" in err


def test_the_same_seed_gives_the_same_work(root):
    a = run_cell(root, "gpt2-medium.pretrain", seed="99")[1]
    b = run_cell(root, "gpt2-medium.pretrain", seed="99")[1]
    c = run_cell(root, "gpt2-medium.pretrain", seed="100")[1]
    assert a["info"]["program_losses"] == b["info"]["program_losses"]
    assert a["info"]["program_losses"] != c["info"]["program_losses"]


@pytest.mark.parametrize("workload", CELLS)
def test_a_measuring_run_without_a_tpu_fails_and_prints_no_result(
        root, workload):
    rc, line, out, err = run_cell(root, workload, tiny=False)
    assert rc != 0
    assert line is None and "{" not in out
    assert "TPU" in err


def test_unknown_workload_is_refused(root):
    rc, line, _, err = run_cell(root, "no-such.cell")
    assert rc != 0 and line is None and "no workload" in err


@pytest.mark.parametrize("workload", CELLS)
def test_the_lower_precision_control_fails_a_limit(root, workload):
    rc, line, out, err = run_cell(root, workload, "--control", "fp8")
    assert rc == 0, err[-3000:]
    control = line["info"]["control"]
    assert any(not c["ok"] for c in control), control
    assert line["correct"] is True  # the program itself is sound


@pytest.mark.parametrize("fault,workload,failed_check", [
    ("unchanged-step", "gpt2-medium.pretrain",
     "param_change_norm_worst_leaf_gap"),
    ("half-batch", "gpt2-medium.pretrain", "loss_step0_rel_gap"),
    ("altered-token", "gpt2-medium.chat-backlog", "served_logit_gap_max"),
])
def test_a_broken_timed_path_comes_out_not_correct(root, fault, workload,
                                                   failed_check):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(root, "perfbench", "tests",
                                      "broken_child.py"), fault, workload],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    bad = {c["name"] for c in line["checks"] if not c["ok"]}
    assert failed_check in bad, line["checks"]


def test_a_traced_toy_run_without_a_device_plane_is_an_error(root):
    # the CPU backend has no device plane: a traced run must not invent
    # busy time, it fails
    rc, line, _, err = run_cell(root, "gpt2-medium.pretrain", trace="1")
    assert rc != 0 and line is None
    assert "no operation ran on a device" in err
