"""Tests of the benchmark's own code. Run with
``python -m pytest perfbench/tests -q`` from the root of the checkout; they
need no accelerator. Cells run in child processes (``run.py --tiny 1``),
each with the virtual CPU devices its cell asks for."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def run_cell(root, workload, *extra, tiny=True, seconds="1", seed="4300000007",
             trace="0", env=None):
    """One ``run.py`` call in ``root``; returns (exit code, last stdout
    line parsed as JSON or None, stdout, stderr)."""
    full_env = dict(os.environ)
    full_env.pop("XLA_FLAGS", None)
    full_env["JAX_PLATFORMS"] = "cpu"
    full_env.update(env or {})
    cmd = [sys.executable, os.path.join(root, "perfbench", "run.py"),
           "--workload", workload, "--seed", seed, "--seconds", seconds,
           "--trace", trace] + (["--tiny", "1"] if tiny else []) + list(extra)
    p = subprocess.run(cmd, cwd=root, env=full_env, capture_output=True,
                       text=True, timeout=600)
    lines = [l for l in p.stdout.strip().splitlines() if l.strip()]
    line = None
    if lines and lines[-1].startswith("{"):
        line = json.loads(lines[-1])
    return p.returncode, line, p.stdout, p.stderr


def copy_checkout(root, tmp_path):
    """A throw-away copy of what the benchmark needs of a checkout;
    returns (its path, its manifest as read)."""
    copy = tmp_path / "checkout"
    copy.mkdir()
    shutil.copytree(os.path.join(root, "perfbench"), copy / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(root, "pytorch_distributed_tpu"),
               copy / "pytorch_distributed_tpu")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return copy, json.load(f)


def with_unshipped(root, tmp_path, name):
    """A copy in which the cell kept under ``perfbench/unshipped/<name>``
    is added the way a later PR would add it: its files copied beside the
    others, its entries appended. Returns (path, manifest)."""
    copy, manifest = copy_checkout(root, tmp_path)
    src = copy / "perfbench" / "unshipped" / name
    for kind in ("cells", "traffic", "configs", "metrics"):
        for f in os.listdir(src / kind):
            shutil.copy(src / kind / f, copy / "perfbench" / kind / f)
    with open(src / "entries.json") as f:
        entries = json.load(f)
    manifest["configs"].append(entries["config"])
    manifest["workloads"].append(entries["workload"])
    manifest["end_to_end"] += entries["end_to_end"]
    manifest["per_layer"] += entries["per_layer"]
    with open(copy / "BENCHMARK.json", "w") as f:
        json.dump(manifest, f)
    return str(copy), manifest


@pytest.fixture(scope="session")
def root():
    return ROOT


@pytest.fixture(scope="session")
def manifest(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)
