"""Attention kernel bench + on-TPU validation.

Round 1's flash kernel had only ever run in interpret mode on CPU; this
compiles BOTH Pallas kernels (forward + the round-2 backward pair) for the
real chip, checks numerical parity against the XLA dense/blockwise paths
on-device, and times fwd and fwd+bwd for all three at growing sequence
lengths. Timing follows PERF_NOTES.md: chained in-jit iterations
(differential k2−k1 slope, scalar-fetch sync), which removes every fixed
per-call cost from the kernel's time.

Usage: python scripts/bench_attention.py [--quick]
Prints one JSON line per (impl, L) cell plus parity results.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from pytorch_distributed_tpu.ops.attention import (
    blockwise_attention,
    dense_attention,
)
from pytorch_distributed_tpu.ops.flash_attention import flash_attention


def difftime(f, k1=10, k2=110):
    """Slope of wall time vs in-jit trip count: removes the fixed
    value-fetch round-trip and dispatch costs. ``f(n)`` must run n chained
    iterations inside one jit (dynamic trip count → single compile).

    Guarded against sub-resolution timings (the r2 bench shipped a 0.0 ms
    / 7.5M-TFLOP row from exactly this failure): the trip-count delta is
    doubled until the measured window exceeds 20 ms, and a slope at the
    floor raises instead of publishing garbage."""

    def measure(k):
        best = 1e9
        for _ in range(3):
            t0 = time.perf_counter()
            float(f(k))
            best = min(best, time.perf_counter() - t0)
        return best

    float(f(k1))  # compile + warm
    t1 = measure(k1)
    for _ in range(8):
        t2 = measure(k2)
        if t2 - t1 > 0.02:
            break
        k2 *= 2  # window too small for the clock's noise: widen
    slope = (t2 - t1) / (k2 - k1)
    if slope <= 1e-7:
        raise RuntimeError(
            f"sub-resolution timing (window {t2 - t1:.4f}s over {k2 - k1} "
            "trips) — refusing to report a garbage TFLOP/s number"
        )
    return slope


def attn_flops(b, h, l, d, causal):
    # QK^T + PV, fwd; bwd ≈ 2.5x fwd (dQ, dK, dV + recomputed S/P)
    f = 2 * 2 * b * h * l * l * d
    return f / 2 if causal else f


def bench_impl(name, fn, b, h, l, d, causal, mode, quiet=False):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, l, h, d)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(b, l, h, d)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(b, l, h, d)), jnp.bfloat16)

    if mode == "fwd":
        def body_of(q):
            return jnp.sum(fn(q, k, v).astype(jnp.float32))
    else:
        def body_of(q):
            # ALL THREE grads, consumed — argnums=0 alone would let XLA
            # dead-code-eliminate the entire dK/dV kernel
            gq, gk, gv = jax.grad(
                lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32)),
                argnums=(0, 1, 2),
            )(q, k, v)
            return (jnp.sum(gq.astype(jnp.float32))
                    + jnp.sum(gk.astype(jnp.float32))
                    + jnp.sum(gv.astype(jnp.float32)))

    @jax.jit
    def chained(n):
        def body(i, s):
            # perturb q by the carry so iterations chain; sum the result
            # into the carry so nothing is dead code
            qq = (q.astype(jnp.float32) + s * 1e-30).astype(jnp.bfloat16)
            return s + body_of(qq) * jnp.float32(1e-30)
        return lax.fori_loop(0, n, body, jnp.float32(0))

    dt = difftime(chained)
    fl = attn_flops(b, h, l, d, causal) * (1.0 if mode == "fwd" else 3.5)
    tflops = round(fl / dt / 1e12, 1)
    if not quiet:  # bench.py reuses this and must print ONE json line total
        print(json.dumps({
            "impl": name, "mode": mode, "L": l, "ms": round(dt * 1e3, 3),
            "tflops": tflops,
        }))
    return dt, tflops


def parity_on_device(b=2, h=4, l=512, d=64):
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(b, l, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, l, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, l, h, d)), jnp.float32)

    out_f = jax.jit(functools.partial(flash_attention, causal=True))(q, k, v)
    out_d = jax.jit(functools.partial(dense_attention, causal=True))(q, k, v)
    fwd_err = float(jnp.max(jnp.abs(out_f - out_d)))

    gf = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v, causal=True) ** 2),
        argnums=(0, 1, 2)))(q, k, v)
    gd = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(dense_attention(q, k, v, causal=True) ** 2),
        argnums=(0, 1, 2)))(q, k, v)
    bwd_err = max(
        float(jnp.max(jnp.abs(a - b2))) for a, b2 in zip(gf, gd)
    )
    scale_ref = float(jnp.max(jnp.abs(gd[0])))
    print(json.dumps({
        "parity": "flash_vs_dense_on_device",
        "platform": jax.devices()[0].platform,
        "fwd_max_abs_err": round(fwd_err, 6),
        "bwd_max_abs_err": round(bwd_err, 6),
        "bwd_ref_scale": round(scale_ref, 3),
    }))
    # On-TPU tolerance is set by the MXU's default fp32 matmul precision
    # (bf16-decomposed passes, ~1e-3 relative), not by the kernel math —
    # interpret-mode CPU tests (tests/test_attention.py) pin the math to
    # 1e-5. 1% relative here catches real math regressions.
    out_scale = float(jnp.max(jnp.abs(out_d)))
    assert fwd_err < 1e-2 * max(out_scale, 1.0), (fwd_err, out_scale)
    assert bwd_err < 1e-2 * max(scale_ref, 1.0), (bwd_err, scale_ref)


def sweep_bwd(bwd_impl: str = "split"):
    """Round-4 sweep (VERDICT r3 weak #3): the backward kernels' tiling at
    L >= 4096, independent of the forward's (512, 1024). fwdbwd numbers
    include the fixed fwd kernel, so compare rows, not absolutes.
    ``bwd_impl`` is pinned EXPLICITLY (default the r4-era split kernels,
    this sweep's historical subject) because flash_attention's default
    became "fused" in r5 — pass --sweep-bwd-fused to sweep the fused
    kernel's tiling instead."""
    b, h, d = 2, 4, 128
    for l in (4096, 8192):
        rows = []
        for bq in (256, 512, 1024):
            for bk in (512, 1024, 2048):
                fn = functools.partial(
                    flash_attention, causal=True,
                    bwd_block_q=bq, bwd_block_k=bk, bwd_impl=bwd_impl,
                )
                try:
                    dt, tf = bench_impl(
                        f"flash_bwd[{bq},{bk}]", fn, b, h, l, d, True,
                        "fwdbwd",
                    )
                    rows.append((tf, bq, bk))
                except Exception as e:
                    print(json.dumps({"impl": f"flash_bwd[{bq},{bk}]",
                                      "L": l, "error": str(e)[:120]}))
        if rows:
            tf, bq, bk = max(rows)
            print(json.dumps({"sweep_bwd_best": {"L": l, "bwd_block_q": bq,
                                                 "bwd_block_k": bk,
                                                 "bwd_impl": bwd_impl,
                                                 "tflops": tf}}))


def main():
    if "--sweep-bwd" in sys.argv:
        sweep_bwd()
        return
    if "--sweep-bwd-fused" in sys.argv:
        sweep_bwd(bwd_impl="fused")
        return
    quick = "--quick" in sys.argv
    parity_on_device()
    b, h, d = (2, 4, 128)
    lengths = (1024, 2048) if quick else (1024, 2048, 4096, 8192)
    impls = [
        ("flash", functools.partial(flash_attention, causal=True)),
        ("blockwise", functools.partial(blockwise_attention, causal=True,
                                        block_size=512)),
        ("dense", functools.partial(dense_attention, causal=True)),
    ]
    for l in lengths:
        for mode in ("fwd", "fwdbwd"):
            for name, fn in impls:
                if name == "dense" and l > 4096:
                    continue  # O(L^2) HBM materialization
                try:
                    bench_impl(name, fn, b, h, l, d, True, mode)
                except Exception as e:
                    print(json.dumps({"impl": name, "mode": mode, "L": l,
                                      "error": str(e)[:120]}))


if __name__ == "__main__":
    main()
