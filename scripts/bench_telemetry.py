"""Telemetry overhead micro-bench (ISSUE 4 acceptance: post-warmup step
time with the device metrics ring within noise — ≤2% — of telemetry
off, while the legacy blocking float() path shows the sync tax).

Three modes over the SAME compiled tiny-LM train step, post-warmup,
logging at the trainers' cadence (``--log-every``, default 100 — the
TrainerConfig default):

- ``off``       step only (the floor);
- ``ring``      step + a DeviceMetricsRing push at each log interval
                with lagged window drains (the new trainer path);
- ``blocking``  step + the seed path's ``float(metrics["loss"])`` at
                each log interval — the host sync this PR removes.

Reports mean post-warmup step ms per mode and the ring-vs-off delta
(the ≤2% acceptance gate). CPU-runnable; on device backends the
blocking tax grows with the dispatch round-trip (~95 ms on the 2026-07
runtime, PERF_NOTES.md; not re-measured) while the ring cost stays one
tiny async dispatch per log event.

Usage: python scripts/bench_telemetry.py [--steps 600] [--log-every 100]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import jax  # noqa: E402
import numpy as np  # noqa: E402


def _build():
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
    tx = build_optimizer("adamw", warmup_cosine(1e-3, 10_000),
                         weight_decay=0.0)

    def make_state():
        # fresh per timed run: the step donates its state argument
        state = create_lm_state(cfg, tx, jax.random.key(0))
        return jax.device_put(state, mesh_lib.replicated_sharding(mesh))

    step = make_lm_train_step(mesh, config=cfg)
    rng = np.random.default_rng(0)
    tokens = rng.integers(1, cfg.vocab_size, (2, 32)).astype(np.int32)
    labels, weights = shift_labels(tokens)
    batch = shard_lm_batch(mesh, {
        "tokens": tokens, "labels": labels, "weights": weights,
    })
    return mesh, make_state, step, batch


def _run(mode: str, mesh, state, step, batch, steps: int,
         log_every: int) -> float:
    from pytorch_distributed_tpu.parallel import mesh as mesh_lib
    from pytorch_distributed_tpu.telemetry import DeviceMetricsRing

    ring = None
    if mode == "ring":
        ring = DeviceMetricsRing(
            ["loss", "tokens"], capacity=8,
            sharding=mesh_lib.replicated_sharding(mesh),
        )
    # warmup (compile + donation settle + ring program), outside the
    # timed window
    for i in range(5):
        state, metrics = step(state, batch)
        if mode == "ring" and i == 0:
            ring.append(metrics, step=-1)
    if mode == "ring":
        ring.flush()
    float(metrics["loss"])  # drain before the clock starts
    t0 = time.perf_counter()
    for i in range(steps):
        state, metrics = step(state, batch)
        if i % log_every == 0:
            if mode == "ring":
                ring.append(metrics, step=i)
            elif mode == "blocking":
                float(metrics["loss"])  # the seed path's per-log sync
    if mode == "ring":
        ring.flush()
    float(jax.device_get(state.step))  # drain the dispatch queue
    return (time.perf_counter() - t0) / steps * 1e3


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--steps", type=int, default=600)
    p.add_argument("--repeats", type=int, default=3)
    p.add_argument("--log-every", type=int, default=100,
                   help="log cadence (TrainerConfig default 100)")
    args = p.parse_args()

    mesh, make_state, step, batch = _build()
    out: dict = {"telemetry_bench_steps": args.steps,
                 "telemetry_bench_log_every": args.log_every,
                 "device": str(jax.devices()[0])}
    for mode in ("off", "ring", "blocking"):
        ms = [
            _run(mode, mesh, make_state(), step, batch, args.steps,
                 args.log_every)
            for _ in range(args.repeats)
        ]
        out[f"telemetry_step_ms_{mode}"] = round(float(np.median(ms)), 4)
    off = out["telemetry_step_ms_off"]
    out["telemetry_ring_overhead_frac"] = round(
        (out["telemetry_step_ms_ring"] - off) / off, 4
    )
    out["telemetry_blocking_overhead_frac"] = round(
        (out["telemetry_step_ms_blocking"] - off) / off, 4
    )
    print(json.dumps(out))


if __name__ == "__main__":
    main()
