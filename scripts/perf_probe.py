"""Perf bisection probe for the ResNet-50 train step on the real chip.

Round-2 investigation of the round-1 review's first weakness (16% MFU,
throughput flat with batch size). Times each sub-computation of the step independently so the
cost can be attributed: pure matmul ceiling, forward, forward+backward,
full step, step-without-metrics. Run on the TPU (not under tests/conftest).

Usage: python scripts/perf_probe.py [probe ...]
Probes: matmul fwd fwdbwd full nometrics sweep
"""

from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

RESNET50_FWD_GFLOP = 4.1  # per 224x224 image, standard count
RESNET50_STEP_GFLOP = 12.3  # fwd + bwd ~= 3x fwd


def timeit(fn, *args, iters=20, warmup=5):
    """Free-running chain timing with one final value fetch (cannot lie)."""
    out = None
    for _ in range(warmup):
        out = fn(*args)
    np.asarray(jax.device_get(jax.tree.leaves(out)[0])).ravel()[:1]
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    np.asarray(jax.device_get(jax.tree.leaves(out)[0])).ravel()[:1]
    return (time.perf_counter() - t0) / iters


def probe_matmul():
    """Achievable bf16 matmul TFLOP/s — the MXU ceiling."""
    for n in (4096, 8192):
        a = jnp.ones((n, n), jnp.bfloat16)
        b = jnp.ones((n, n), jnp.bfloat16)

        @jax.jit
        def mm(a, b):
            return jax.lax.dot(a, b, preferred_element_type=jnp.float32).astype(
                jnp.bfloat16
            )

        dt = timeit(mm, a, b)
        tflops = 2 * n**3 / dt / 1e12
        print(f"matmul {n}x{n}x{n} bf16: {dt * 1e3:.2f} ms  {tflops:.1f} TFLOP/s")


def build_state(batch_size, dtype=jnp.bfloat16):
    from pytorch_distributed_tpu.models import resnet50
    from pytorch_distributed_tpu.ops.optim import sgd_with_weight_decay
    from pytorch_distributed_tpu.parallel import (
        replicated_sharding,
        shard_batch,
        single_device_mesh,
    )
    from pytorch_distributed_tpu.train.state import TrainState

    model = resnet50(dtype=dtype)
    mesh = single_device_mesh()
    tx = sgd_with_weight_decay(0.1, momentum=0.9, weight_decay=1e-4)
    state = TrainState.create(model, tx, jax.random.key(0), (1, 224, 224, 3))
    state = jax.device_put(state, replicated_sharding(mesh))
    rng = np.random.default_rng(0)
    batch = shard_batch(
        mesh,
        {
            "image": rng.normal(size=(batch_size, 224, 224, 3)).astype(np.float32),
            "label": rng.integers(0, 1000, batch_size).astype(np.int32),
        },
    )
    return mesh, state, batch


def report(name, bs, dt, gflop_per_img, peak=197.0):
    tflops = bs * gflop_per_img * 1e9 / dt / 1e12
    print(
        f"{name:12s} bs={bs:4d}: {dt * 1e3:7.2f} ms  {bs / dt:7.0f} img/s  "
        f"{tflops:6.1f} TFLOP/s  ({100 * tflops / peak:.0f}% of {peak:.0f})"
    )


def probe_fwd(bs):
    mesh, state, batch = build_state(bs)

    @jax.jit
    def fwd(state, batch):
        variables = {"params": state.params, "batch_stats": state.batch_stats}
        out, _ = state.apply_fn(
            variables, batch["image"], train=True, mutable=["batch_stats"]
        )
        return out

    dt = timeit(fwd, state, batch)
    report("fwd", bs, dt, RESNET50_FWD_GFLOP)


def probe_fwdbwd(bs):
    from pytorch_distributed_tpu.ops.losses import cross_entropy_loss

    mesh, state, batch = build_state(bs)

    @jax.jit
    def fwdbwd(state, batch):
        def loss_fn(params):
            variables = {"params": params, "batch_stats": state.batch_stats}
            out, mut = state.apply_fn(
                variables, batch["image"], train=True, mutable=["batch_stats"]
            )
            return cross_entropy_loss(out, batch["label"]), mut

        grads, _ = jax.grad(loss_fn, has_aux=True)(state.params)
        return grads

    dt = timeit(fwdbwd, state, batch)
    report("fwd+bwd", bs, dt, RESNET50_STEP_GFLOP)


def probe_full(bs):
    from pytorch_distributed_tpu.train.step import make_train_step

    mesh, state, batch = build_state(bs)
    step = make_train_step(mesh)

    def run(state, batch):
        return step(state, batch)

    # donation: chain state through
    for _ in range(5):
        state, m = step(state, batch)
    float(m["loss"])
    t0 = time.perf_counter()
    iters = 20
    for _ in range(iters):
        state, m = step(state, batch)
    float(m["loss"])
    dt = (time.perf_counter() - t0) / iters
    report("full step", bs, dt, RESNET50_STEP_GFLOP)


def probe_nometrics(bs):
    from pytorch_distributed_tpu.ops.losses import cross_entropy_loss

    mesh, state, batch = build_state(bs)

    @jax.jit
    def step(state, batch):
        def loss_fn(params):
            variables = {"params": params, "batch_stats": state.batch_stats}
            out, mut = state.apply_fn(
                variables, batch["image"], train=True, mutable=["batch_stats"]
            )
            return cross_entropy_loss(out, batch["label"]), mut

        grads, mut = jax.grad(loss_fn, has_aux=True)(state.params)
        updates, opt_state = state.tx.update(grads, state.opt_state, state.params)
        params = jax.tree.map(jnp.add, state.params, updates)
        return state.replace(
            params=params,
            opt_state=opt_state,
            batch_stats=mut["batch_stats"],
            step=state.step + 1,
        )

    state2 = step(state, batch)
    for _ in range(4):
        state2 = step(state2, batch)
    np.asarray(jax.device_get(jax.tree.leaves(state2.params)[0])).ravel()[:1]
    t0 = time.perf_counter()
    iters = 20
    for _ in range(iters):
        state2 = step(state2, batch)
    np.asarray(jax.device_get(jax.tree.leaves(state2.params)[0])).ravel()[:1]
    dt = (time.perf_counter() - t0) / iters
    report("no-metrics", bs, dt, RESNET50_STEP_GFLOP)


def main():
    probes = sys.argv[1:] or ["matmul", "fwd", "fwdbwd", "nometrics", "full"]
    print(f"device: {jax.devices()[0]}")
    for p in probes:
        if p == "matmul":
            probe_matmul()
        elif p == "fwd":
            for bs in (128, 256):
                probe_fwd(bs)
        elif p == "fwdbwd":
            for bs in (128, 256):
                probe_fwdbwd(bs)
        elif p == "full":
            for bs in (128, 256):
                probe_full(bs)
        elif p == "nometrics":
            for bs in (128, 256):
                probe_nometrics(bs)
        elif p == "sweep":
            for bs in (64, 128, 256, 512, 1024):
                probe_full(bs)


if __name__ == "__main__":
    main()
