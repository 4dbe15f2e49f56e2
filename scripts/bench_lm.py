"""Transformer LM training throughput on the real chip.

Runs the REAL compiled LM train step (train/lm.py: shard_map over the
mesh, psum gradient combine, AdamW) on a GPT-2-small-shaped model with the
Pallas flash-attention kernel, measures tokens/s with the pipelined-
dispatch method (PERF_NOTES.md), and reports model FLOPs utilization via
the standard 6·N·tokens/s estimate. Also times the dense-attention variant
for the kernel's end-to-end contribution.

The reference has no LM at all (SURVEY.md §5: long-context ABSENT) — this
benchmarks capability the framework adds on top of parity.

Usage: python scripts/bench_lm.py [--quick]
Prints one JSON line per config.
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from pytorch_distributed_tpu.models.transformer import TransformerConfig
from pytorch_distributed_tpu.ops.optim import build_optimizer
from pytorch_distributed_tpu.parallel import make_mesh
from pytorch_distributed_tpu.train.lm import (
    create_lm_state,
    make_lm_train_step,
    shard_lm_state,
    shift_labels,
)

from pytorch_distributed_tpu.telemetry.costmodel import DEVICE_CEILINGS

# one definition of the value-fetch round-trip correction for every bench
from bench import measure_roundtrip_s  # noqa: E402


def peak_flops() -> float:
    """bf16 peak FLOP/s of the device this runs on, from the one table
    (``telemetry.costmodel.DEVICE_CEILINGS``). A device that is not in
    it is an error: an MFU against another chip's peak is not an MFU."""
    kind = jax.devices()[0].device_kind
    if kind not in DEVICE_CEILINGS:
        raise RuntimeError(
            f"no peak FLOP/s on record for device kind {kind!r} "
            f"(known: {sorted(DEVICE_CEILINGS)}); MFU is undefined here"
        )
    return DEVICE_CEILINGS[kind][0]


def bench(attention: str, batch: int, seq: int, iters: int = 20,
          quiet: bool = False) -> dict:
    cfg = TransformerConfig(
        vocab_size=32000,
        num_layers=12,
        num_heads=12,
        embed_dim=768,
        max_seq_len=seq,
        dtype=jnp.bfloat16,
        attention=attention,
        block_size=512,
    )
    peak = peak_flops()  # before any work: an unknown device fails fast
    mesh = make_mesh(jax.devices()[:1])
    tx = build_optimizer("adamw", 3e-4, weight_decay=0.1)
    state = create_lm_state(cfg, tx, jax.random.key(0), init_len=seq)
    n_params = state.param_count()
    state, specs = shard_lm_state(mesh, state, cfg)
    step = make_lm_train_step(mesh, state_specs=specs, config=cfg)

    rng = np.random.default_rng(0)
    tokens = rng.integers(1, cfg.vocab_size, (batch, seq)).astype(np.int32)
    labels, weights = shift_labels(tokens)
    sh = NamedSharding(mesh, P("data", "seq"))
    b = {"tokens": jax.device_put(tokens, sh),
         "labels": jax.device_put(labels, sh),
         "weights": jax.device_put(weights, sh)}

    for _ in range(3):
        state, m = step(state, b)
    loss = float(m["loss"])
    assert np.isfinite(loss), loss
    # median of 3 windows (the BENCH_TABLE spread policy); ONE roundtrip
    # estimate for all windows, so every window subtracts the same
    # correction
    rt = measure_roundtrip_s()
    rates = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            state, m = step(state, b)
        float(m["loss"])
        dt = time.perf_counter() - t0
        dt = max(dt - rt, dt / 2) / iters
        rates.append(batch * seq / dt)
    tok_s = float(np.median(rates))
    dt = batch * seq / tok_s
    # standard estimate: fwd+bwd ≈ 6 FLOPs/param/token + attention term
    attn_flops = 12 * cfg.num_layers * cfg.embed_dim * seq  # per token
    mfu = (6 * n_params + attn_flops) * tok_s / peak
    out = {
        "model": "gpt2-small-shaped", "params_m": round(n_params / 1e6, 1),
        "attention": attention, "batch": batch, "seq": seq,
        "step_ms": round(dt * 1e3, 2), "tokens_per_s": round(tok_s),
        "tokens_per_s_min": round(min(rates)),
        "tokens_per_s_max": round(max(rates)),
        "mfu": round(mfu, 3), "loss": round(loss, 3),
        "device": str(jax.devices()[0]),
    }
    if not quiet:  # bench.py reuses this and must print ONE json line total
        print(json.dumps(out))
    return out


def main():
    quick = "--quick" in sys.argv
    configs = [("flash", 8, 1024)]
    if not quick:
        configs += [("dense", 8, 1024), ("flash", 4, 4096), ("blockwise", 4, 4096)]
    for attention, batch, seq in configs:
        try:
            bench(attention, batch, seq)
        except Exception as e:
            print(json.dumps({"attention": attention, "batch": batch,
                              "seq": seq, "error": str(e)[:200]}))


if __name__ == "__main__":
    main()
