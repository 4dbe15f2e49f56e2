"""The optimizers' update rules, written out (float32)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def warmup_cosine_lr(step: int, base: float, total: int, warmup: int,
                     final: float) -> float:
    if step < warmup:
        return base * step / max(float(warmup), 1.0)
    progress = min(max((step - warmup) / max(float(total - warmup), 1.0),
                       0.0), 1.0)
    return final + 0.5 * (base - final) * (1 + math.cos(math.pi * progress))


def adamw_init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"mu": zeros, "nu": zeros, "count": jnp.float32(0)}


def adamw_step(params, grads, state, lr: float, weight_decay: float,
               b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8):
    """optax.adamw: decoupled decay on every leaf, bias-corrected moments."""
    count = state["count"] + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["mu"], grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["nu"],
                      grads)
    c1, c2 = 1 - b1 ** count, 1 - b2 ** count
    new = jax.tree.map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                  + weight_decay * p),
        params, mu, nu)
    return new, {"mu": mu, "nu": nu, "count": count}


def sgd_init(params):
    return {"trace": jax.tree.map(jnp.zeros_like, params)}


def sgd_step(params, grads, state, lr: float, momentum: float,
             weight_decay: float):
    """torch.optim.SGD: decay added to the gradient, then momentum."""
    trace = jax.tree.map(lambda t, g, p: momentum * t + g + weight_decay * p,
                         state["trace"], grads, params)
    new = jax.tree.map(lambda p, t: p - lr * t, params, trace)
    return new, {"trace": trace}
