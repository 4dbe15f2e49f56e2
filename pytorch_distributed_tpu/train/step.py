"""The compiled SPMD train/eval step — where DDP's whole machinery collapses.

In the reference, one training step is Python orchestrating five subsystems
(hot loop ``restnet_ddp.py:21-33``, SURVEY.md §3.2): H2D copy → DDP forward
→ loss → backward with the C++ Reducer firing bucketed NCCL all-reduces
overlapped with grad computation → optimizer step. Here the *entire* body —
forward, loss, backward, cross-replica gradient combine, optimizer update,
BN stats, metric reduction — is one XLA program built with ``shard_map``
over the mesh's data axis and compiled once by ``jit``:

- the gradient ``pmean`` is visible to XLA's latency-hiding scheduler, which
  overlaps it with the remaining backward (what DDP's bucketing
  hand-implements in C++, D7);
- BatchNorm normalizes with *per-replica* batch statistics, exactly DDP's
  unsynced-BN training dynamics (SURVEY.md §7 hard part (c)); the running
  stats are pmean'd across replicas each step so the state stays replicated
  and deterministic (the reference instead checkpoints rank 0's arbitrary
  local copy, ``restnet_ddp.py:38``);
- mixed precision is the state's scaler + the model's compute dtype: bf16
  needs no scaler (NoOpLossScaler compiles away); with DynamicLossScaler the
  GradScaler skip-on-nonfinite contract (``resnet_ddp_apex.py:30-33``) runs
  entirely on device — no per-step host sync, unlike torch's scaler;
- one code path serves all four reference recipes: a 1-device mesh is
  ``resnet_single_gpu``, an 8-device local mesh is ``resnet_dp`` (without
  the per-step scatter/replicate cost of D5), a multi-host mesh is
  ``restnet_ddp`` — the difference is the Mesh, not the code.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from pytorch_distributed_tpu.ops.losses import cross_entropy_loss
from pytorch_distributed_tpu.ops.metrics import ClassificationMetrics
from pytorch_distributed_tpu.ops.precision import NoOpLossScaler, all_finite
from pytorch_distributed_tpu.ops.optim import clip_grads_by_global_norm
from pytorch_distributed_tpu.parallel.mesh import DATA_AXIS, shard_map
from pytorch_distributed_tpu.resilience.stepguard import finite_ok, guard_state
from pytorch_distributed_tpu.train.state import TrainState


def prepare_image(image):
    """Device-side normalization for uint8 batches (the raw fast path).

    The raw input pipeline (``data.raw``) ships uint8 pixels — 4x fewer
    host→device bytes — and this applies exactly the host ``Normalize``
    math (``data/transforms.py``: /255, -mean, /std, fp32) inside the
    compiled step, where it fuses into the stem conv. Float batches are
    already normalized on host and pass through untouched.
    """
    if image.dtype != jnp.uint8:
        return image
    from pytorch_distributed_tpu.data.transforms import IMAGENET_MEAN, IMAGENET_STD

    return (image.astype(jnp.float32) / 255.0 - IMAGENET_MEAN) / IMAGENET_STD


def make_train_step(
    mesh: Mesh,
    axis: str = DATA_AXIS,
    label_smoothing: float = 0.0,
    state_specs: Optional[TrainState] = None,
    grad_clip_norm: float = 0.0,
    nan_guard: bool = False,
) -> Callable[[TrainState, dict], Tuple[TrainState, dict]]:
    """Build the compiled training step for a mesh.

    Returns ``step(state, batch) -> (state, metrics)`` where ``batch`` is a
    global array dict sharded batch-dim over ``axis`` (see
    ``parallel.shard_batch``) and metrics are replicated scalars
    {loss, correct1, correct5, count, grads_finite}.

    ``state_specs`` (from ``parallel.fsdp.shard_fsdp_state``) switches on
    the FSDP/ZeRO-3 path: parameters and optimizer state live sharded over
    ``axis``; the step all_gathers params before the forward and
    psum_scatters gradients back to their owners — same math as replicated
    DP (all_gather∘psum_scatter ≡ pmean), ~axis-size less state memory.

    ``nan_guard`` adds the resilience finite gate (resilience.stepguard):
    a step whose global loss or combined gradients are non-finite keeps
    the pre-step params/opt/BN state (``lax.cond`` select on device — no
    host sync) while ``step`` still advances, and the replicated
    ``step_good`` metric reports the verdict for the host rollback policy.
    """
    fsdp = state_specs is not None
    if fsdp:
        from pytorch_distributed_tpu.parallel.fsdp import (
            gather_params,
            scatter_grads,
        )

    def _local_step(state: TrainState, batch: dict):
        def loss_fn(params):
            variables = {"params": params}
            if state.batch_stats:
                variables["batch_stats"] = state.batch_stats
            outputs, mutated = state.apply_fn(
                variables, prepare_image(batch["image"]), train=True,
                mutable=["batch_stats"],
            )
            loss = cross_entropy_loss(
                outputs, batch["label"], label_smoothing=label_smoothing
            )
            return state.scaler.scale_loss(loss), (loss, outputs, mutated)

        full_params = (
            gather_params(state.params, state_specs.params, axis)
            if fsdp
            else state.params
        )
        grads, (loss, logits, mutated) = jax.grad(loss_fn, has_aux=True)(full_params)
        grads = state.scaler.unscale_grads(grads)
        # The DP gradient combine: per-replica mean-loss grads averaged over
        # the axis ≙ DDP's allreduce-and-divide (restnet_ddp.py:29 via D7).
        # FSDP: the same mean, delivered shard-wise (reduce-scatter).
        if fsdp:
            grads = scatter_grads(grads, state_specs.params, axis)
        else:
            grads = jax.lax.pmean(grads, axis_name=axis)

        if grad_clip_norm:
            # torch ordering (clip_grad_norm_ after scaler.unscale_): the
            # threshold must see TRUE gradient magnitudes, so this sits
            # after unscale_grads and after the cross-replica combine.
            # Non-finite grads survive clipping as NaN (inf * 0) and the
            # scaler's finite gate below still skips the step.
            grads, _ = clip_grads_by_global_norm(
                grads, grad_clip_norm,
                state_specs.params if fsdp else None,
            )

        new_batch_stats = mutated.get("batch_stats", state.batch_stats)
        if new_batch_stats:
            new_batch_stats = jax.lax.pmean(new_batch_stats, axis_name=axis)

        if isinstance(state.scaler, NoOpLossScaler):
            # bf16/fp32 path: no scaler, no finite gate, no extra compute.
            updates, new_opt_state = state.tx.update(
                grads, state.opt_state, state.params
            )
            new_params = jax.tree.map(jnp.add, state.params, updates)
            new_scaler = state.scaler
            finite = jnp.asarray(True)
        else:
            # GradScaler contract (resnet_ddp_apex.py:30-33): on non-finite
            # grads skip the whole update (params, momentum, schedule count)
            # and back off the scale — computed on device, no host sync.
            # The flag must be GLOBAL: under FSDP each device only sees its
            # gradient shards, so a local inf would make devices disagree on
            # skipping and silently diverge params/opt/scaler state.
            finite = (
                jax.lax.pmin(all_finite(grads).astype(jnp.int32), axis) > 0
            )
            updates, new_opt_state = state.tx.update(
                grads, state.opt_state, state.params
            )
            new_params = jax.tree.map(
                lambda p, u: jnp.where(finite, p + u, p), state.params, updates
            )
            new_opt_state = jax.tree.map(
                lambda new, old: jnp.where(finite, new, old)
                if jnp.issubdtype(jnp.asarray(new).dtype, jnp.inexact)
                or jnp.issubdtype(jnp.asarray(new).dtype, jnp.integer)
                else new,
                new_opt_state,
                state.opt_state,
            )
            new_scaler = state.scaler.update(finite)

        new_state = state.replace(
            step=state.step + 1,
            params=new_params,
            batch_stats=new_batch_stats,
            opt_state=new_opt_state,
            scaler=new_scaler,
        )

        batch_metrics = ClassificationMetrics.from_step(
            cross_entropy_loss(logits, batch["label"], reduction="sum"),
            logits,
            batch["label"],
        )
        batch_metrics = jax.lax.psum(batch_metrics, axis_name=axis)
        metrics = {
            "loss": batch_metrics.loss_sum / jnp.maximum(batch_metrics.count, 1.0),
            "correct1": batch_metrics.correct1,
            "correct5": batch_metrics.correct5,
            "count": batch_metrics.count,
            "grads_finite": finite.astype(jnp.float32),
        }
        if nan_guard:
            # The resilience finite gate. pmin over the axis: under FSDP
            # each device checks only its gradient shards, and devices
            # disagreeing on `good` would silently diverge params — the
            # same global-agreement argument as the fp16 scaler gate.
            good = (
                jax.lax.pmin(
                    finite_ok(metrics["loss"], grads).astype(jnp.int32),
                    axis,
                )
                > 0
            )
            # step always advances (a skip is a consumed batch); the fp16
            # scaler still backs off on the skipped step
            keep = (
                ("step",)
                if isinstance(state.scaler, NoOpLossScaler)
                else ("step", "scaler")
            )
            new_state = guard_state(good, new_state, state, keep=keep)
            metrics["step_good"] = good.astype(jnp.float32)
        return new_state, metrics

    state_spec = state_specs if fsdp else P()
    metrics_spec = P()
    sharded = shard_map(
        _local_step,
        mesh=mesh,
        in_specs=(state_spec, P(axis)),
        out_specs=(state_spec, metrics_spec),
        check_vma=False,
    )
    # the profiler's module is jit_<__name__>: the registry's name
    sharded.__name__ = "train_step"
    return jax.jit(sharded, donate_argnums=(0,))


def make_eval_step(
    mesh: Mesh, axis: str = DATA_AXIS, state_specs: Optional[TrainState] = None
) -> Callable[[TrainState, dict, ClassificationMetrics], ClassificationMetrics]:
    """Build the compiled validation step (ref ``validate``,
    ``restnet_ddp.py:50-61``).

    ``eval_step(state, batch, metrics) -> metrics``: forward with running BN
    stats, top-1/5 counts psum'd over the axis, accumulated into the
    device-resident ``metrics`` pytree — no host sync per batch. Every
    replica (and host) ends with the global sums, a strict superset of the
    reference's reduce-to-rank-0 (``restnet_ddp.py:63-64``).
    """

    fsdp = state_specs is not None
    if fsdp:
        from pytorch_distributed_tpu.parallel.fsdp import gather_params

    def _local_eval(state: TrainState, batch: dict, metrics: ClassificationMetrics):
        params = (
            gather_params(state.params, state_specs.params, axis)
            if fsdp
            else state.params
        )
        variables = {"params": params}
        if state.batch_stats:
            variables["batch_stats"] = state.batch_stats
        logits = state.apply_fn(variables, prepare_image(batch["image"]), train=False)
        batch_metrics = ClassificationMetrics.from_step(
            cross_entropy_loss(logits, batch["label"], reduction="sum"),
            logits,
            batch["label"],
        )
        return metrics.merge(jax.lax.psum(batch_metrics, axis_name=axis))

    sharded = shard_map(
        _local_eval,
        mesh=mesh,
        in_specs=(state_specs if fsdp else P(), P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )
    # the profiler's module is jit_<__name__>: the registry's name
    sharded.__name__ = "eval_step"
    return jax.jit(sharded, donate_argnums=(2,))
