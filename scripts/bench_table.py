"""Reproduce the reference's result.png-shaped comparison table.

The reference publishes five rows — single GPU fp32, nn.DataParallel,
multi-process DDP, AMP+DDP, AMP×4 nodes — with epoch time, GPU util and
memory (``/root/reference/result.png``, ``README.md:27-40``). This script
produces the TPU-native analog and writes BENCH_TABLE.md:

- real-chip rows (run with the TPU visible): single-chip fp32 and bf16
  ResNet-50, measured with the same pipelined-dispatch method as bench.py;
- scaling-shape rows (run on 8 virtual CPU devices): the SAME compiled SPMD
  train step over a 1-device vs 8-device mesh, tiny ResNet — demonstrating
  the DP/DDP/AMP code paths and their scaling efficiency where no 8-chip
  hardware is reachable. CPU img/s is not comparable to TPU img/s and is
  reported only as a 8-dev/1-dev ratio.

Single/DP/DDP collapse into one program here (SURVEY.md §7): the mesh is
the difference, so the "DP row" exercises exactly what an 8-chip pod runs.

Usage:
    python scripts/bench_table.py            # orchestrates all rows
    python scripts/bench_table.py --row X    # child mode, one JSON line
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE_ROWS = [
    # (config, epoch_s, util_pct, mem_gb) transcribed from result.png
    ("single GPU fp32 (bs400)", 1786.78, 99.5, 39.92),
    ("nn.DataParallel 8 GPU", 984.58, 59.8, 39.92),
    ("DDP 8 GPU", 239.40, 99.5, 39.92),
    ("AMP+DDP 8 GPU", 230.98, 88.8, 24.48),
    ("AMP+DDP 32 GPU", 54.50, 79.2, 24.48),
]
IMAGENET_TRAIN = 1_281_167


def run_row(row: str) -> dict:
    sys.path.insert(0, REPO)
    import jax

    assert jax.devices(), "no devices"
    if row.startswith("tpu_") and jax.devices()[0].platform != "tpu":
        raise RuntimeError(
            f"row {row} needs a TPU, found {jax.devices()[0].platform}"
        )
    if row.startswith("cpu_") and len(jax.devices()) < 8:
        raise RuntimeError(
            f"expected 8 virtual CPU devices, got {jax.devices()}"
        )
    import jax.numpy as jnp

    import bench
    from pytorch_distributed_tpu.parallel import make_mesh, single_device_mesh

    tiny = row.startswith("cpu_")
    dtype = jnp.bfloat16 if ("bf16" in row or "amp" in row) else jnp.float32
    per_dev_bs = 16 if tiny else int(os.environ.get("BENCH_BS", "128"))
    mesh = make_mesh() if "8dev" in row else single_device_mesh()
    n_dev = int(mesh.devices.size)
    bs = per_dev_bs * n_dev
    # Same build/timing/round-trip-correction path as the headline bench,
    # including its fused-bottleneck default (BENCH_FUSED).
    fused = os.environ.get("BENCH_FUSED", "1") == "1" and not tiny
    img_s, step_s, _ = bench.run(
        bs, tiny, dtype=dtype, mesh=mesh, measure_duty=False,
        warmup=5, iters=10 if tiny else 30, fused=fused,
    )
    return {"row": row, "n_dev": n_dev, "batch_size": bs,
            "img_s": round(img_s, 2), "step_ms": round(step_s * 1e3, 2),
            "platform": jax.devices()[0].platform}


def child(row: str, cpu: bool) -> dict:
    env = dict(os.environ)
    if cpu:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--row", row],
        env=env, capture_output=True, text=True, timeout=1200, cwd=REPO)
    for line in reversed(out.stdout.strip().splitlines()):
        try:
            return json.loads(line)
        except json.JSONDecodeError:
            continue
    raise RuntimeError(f"row {row} failed:\n{out.stdout}\n{out.stderr}")


def main() -> None:
    if "--row" in sys.argv:
        row = sys.argv[sys.argv.index("--row") + 1]
        print(json.dumps(run_row(row)))
        return

    # This parent never imports jax, so each chip child has the chip to
    # itself; a row that fails fails the table (a table with a missing
    # row reads as a run that passed).
    results = {}
    for row in ("tpu_single_fp32", "tpu_single_bf16", "cpu_single_fp32",
                "cpu_8dev_fp32", "cpu_8dev_bf16_amp"):
        results[row] = child(row, cpu=row.startswith("cpu_"))
        print(f"{row}: {results[row]['img_s']} img/s", file=sys.stderr)

    lines = [
        "# BENCH_TABLE — reference result.png comparison (round 2)",
        "",
        "## Reference (8×A100 cluster, ImageNet epoch)",
        "",
        "| config | epoch (s) | util % | mem (GB) | derived img/s |",
        "|---|---|---|---|---|",
    ]
    for cfg, es, util, mem in BASELINE_ROWS:
        lines.append(f"| {cfg} | {es:.0f} | {util} | {mem} | {IMAGENET_TRAIN/es:.0f} |")
    lines += [
        "",
        "## This framework — real TPU v5e chip (measured)",
        "",
        "| config | devices | img/s | projected ImageNet epoch (s) | vs ref single-GPU |",
        "|---|---|---|---|---|",
    ]
    ref_single = IMAGENET_TRAIN / BASELINE_ROWS[0][1]
    for row, label in (("tpu_single_fp32", "single chip fp32"),
                       ("tpu_single_bf16", "single chip bf16 (AMP row analog)")):
        r = results[row]
        lines.append(
            f"| {label} | {r['n_dev']} | {r['img_s']:.0f} | "
            f"{IMAGENET_TRAIN / r['img_s']:.0f} | {r['img_s']/ref_single:.2f}× |")
    lines += [
        "",
        "## Code-path rows — 8 virtual CPU devices (same SPMD program a pod runs)",
        "",
        "All 8 virtual devices share ONE physical CPU core, so the ratio is",
        "bounded by the core, not by the parallelism — these rows prove the",
        "DP/DDP/AMP train-step code paths compile and execute over an 8-way",
        "mesh (global batch ×8), not hardware scaling. True multi-chip",
        "scaling needs a pod; the dryrun_multichip entry point and",
        "tests/test_multihost.py validate the program + rendezvous sides.",
        "",
        "| config | devices | global batch | img/s (1-core bound) |",
        "|---|---|---|---|",
    ]
    for row, label in (("cpu_single_fp32", "single device (tiny)"),
                       ("cpu_8dev_fp32", "DP/DDP mesh ×8 (tiny)"),
                       ("cpu_8dev_bf16_amp", "AMP + DP mesh ×8 (tiny)")):
        r = results[row]
        lines.append(f"| {label} | {r['n_dev']} | {r['batch_size']} | "
                     f"{r['img_s']:.0f} |")
    lines += [
        "",
        "Method: pipelined async dispatch, one scalar sync (see PERF_NOTES.md);",
        "projected epoch = 1,281,167 images / measured img/s, the same derivation",
        "BASELINE.md applies to result.png. Multi-process DDP is the identical",
        "program over a multi-host mesh (tests/test_multihost.py exercises the",
        "2-process rendezvous path).",
        "",
    ]
    path = os.path.join(REPO, "BENCH_TABLE.md")
    with open(path, "w") as f:
        f.write("\n".join(lines))
    print(f"wrote {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
