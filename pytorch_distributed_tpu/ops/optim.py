"""Optimizers.

Replaces ``torch.optim.SGD(params, lr, momentum=0.9, weight_decay=1e-4)``
(``resnet_single_gpu.py:108``, ``restnet_ddp.py:122``) with an optax chain
that reproduces torch's exact update rule:

    g = g + wd * p            (decoupled *into* the gradient, torch-style)
    buf = mu * buf + g        (dampening 0, nesterov False — torch defaults)
    p = p - lr * buf

i.e. ``add_decayed_weights`` *before* the momentum trace, and optax's
``trace`` (not ``sgd``'s scaled variant) so the momentum buffer matches
torch's bit-for-bit given the same inputs — verified against torch CPU in
tests/test_ops.py.
"""

from __future__ import annotations

from typing import Callable, Union

import jax
import jax.numpy as jnp
import optax

ScalarOrSchedule = Union[float, Callable]


def spec_axes(spec) -> tuple:
    """Mesh axis names a PartitionSpec shards over (order-preserving).
    The one shared extraction for every 'reduce over the axes this leaf
    is / is not sharded on' site (here and train/lm.py's grad combine)."""
    named: list = []
    if spec is None:
        return ()
    for part in spec:
        if part is None:
            continue
        for a in part if isinstance(part, tuple) else (part,):
            if a not in named:
                named.append(a)
    return tuple(named)


def sharded_global_norm(tree, specs=None) -> jnp.ndarray:
    """Global L2 norm of a gradient pytree, correct INSIDE ``shard_map``.

    The subtlety the reference never faced (SGD ResNet needed no clipping,
    ``restnet_ddp.py:122``): under this framework's shard_map steps, a
    leaf's gradient is complete-but-LOCAL for the mesh axes its
    PartitionSpec names (TP's Megatron shards over ``model``, FSDP's
    scatter over ``data``, PP's stage stacks over ``stage``) and
    replicated over the rest. So each leaf's local square-sum is psum'd
    over exactly the axes its spec names — sharded leaves recombine,
    replicated leaves contribute once — and every device agrees on the
    result. With ``specs=None`` (fully-replicated grads, or outside
    shard_map) this reduces to the plain ``optax.global_norm``.

    Accumulates in float32 regardless of gradient dtype. Square-sums are
    BUCKETED by sharded-axis set before reducing — one scalar psum per
    distinct axis set (typically <=3), not one per leaf (XLA only merges
    collectives with identical replica groups, so per-leaf scalar psums
    would stay separate in the hot step).
    """
    buckets: dict = {}

    def add(g, spec):
        ax = spec_axes(spec)
        sq = jnp.sum(jnp.square(g.astype(jnp.float32)))  # jaxlint: disable=precision-cast -- global-norm square-sums accumulate in fp32 for every policy
        buckets[ax] = buckets.get(ax, jnp.float32(0.0)) + sq

    if specs is None:
        for g in jax.tree.leaves(tree):
            add(g, None)
    else:
        jax.tree.map(add, tree, specs)
    total = jnp.float32(0.0)
    for ax, sq in buckets.items():
        total = total + (jax.lax.psum(sq, ax) if ax else sq)
    return jnp.sqrt(total)


def clip_grads_by_global_norm(grads, max_norm: float, specs=None):
    """Clip a gradient pytree to ``max_norm`` global L2 norm (sharding-
    aware; see ``sharded_global_norm``). Returns ``(clipped, pre_norm)``.
    Same semantics as ``optax.clip_by_global_norm``:
    ``g * max_norm / max(norm, max_norm)`` — identity when under the
    threshold, never up-scales."""
    gnorm = sharded_global_norm(grads, specs)
    scale = max_norm / jnp.maximum(gnorm, max_norm)
    clipped = jax.tree.map(lambda g: (g * scale).astype(g.dtype), grads)
    return clipped, gnorm


def clip_by_global_norm(
    max_norm: float, param_specs=None
) -> optax.GradientTransformation:
    """optax transformation form of ``clip_grads_by_global_norm`` for use
    in chains. ``param_specs``: params-shaped PartitionSpec tree when the
    chain runs inside shard_map on sharded gradients; None for replicated
    /pjit use. Stateless — adding it to a chain does not change the
    optimizer state's structure."""

    def init_fn(params):
        del params
        return optax.EmptyState()

    def update_fn(updates, state, params=None):
        del params
        clipped, _ = clip_grads_by_global_norm(updates, max_norm, param_specs)
        return clipped, state

    return optax.GradientTransformation(init_fn, update_fn)


def sgd_with_weight_decay(
    learning_rate: ScalarOrSchedule,
    momentum: float = 0.9,
    weight_decay: float = 1e-4,
    nesterov: bool = False,
) -> optax.GradientTransformation:
    """torch.optim.SGD-equivalent update rule (see module docstring)."""
    parts = []
    if weight_decay:
        parts.append(optax.add_decayed_weights(weight_decay))
    if momentum:
        parts.append(optax.trace(decay=momentum, nesterov=nesterov))
    parts.append(optax.scale_by_learning_rate(learning_rate))
    return optax.chain(*parts)


_REGISTRY = {}


def register_optimizer(name: str):
    def deco(fn):
        _REGISTRY[name] = fn
        return fn

    return deco


register_optimizer("sgd")(sgd_with_weight_decay)
@register_optimizer("adamw")
def adamw(learning_rate, weight_decay=1e-4, **kw):
    """``optax.adamw`` whose update runs in ``jax.named_scope("adamw")``,
    so a profile shows the optimizer's share of a step."""
    tx = optax.adamw(learning_rate, weight_decay=weight_decay, **kw)

    def update(updates, state, params=None):
        with jax.named_scope("adamw"):
            return tx.update(updates, state, params)

    return optax.GradientTransformation(tx.init, update)


def build_optimizer(name: str, learning_rate: ScalarOrSchedule, **kwargs):
    """Construct a registered optimizer by name (config-driven entry point)."""
    try:
        factory = _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown optimizer {name!r}; known: {sorted(_REGISTRY)}")
    return factory(learning_rate, **kwargs)
