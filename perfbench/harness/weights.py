"""Weights and lower-precision casts the benchmark makes itself."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from any whole number up to 64 bits."""
    seed = int(seed) & 0xFFFFFFFFFFFFFFFF
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


@functools.lru_cache(maxsize=8)
def _maker(treedef, leaves: tuple, rule, dtype):
    """The jitted filler of one tree shape (compiled once a process). One
    draw of all the normals, cut into the leaves: a draw per leaf compiles
    for more than a minute on the chip."""
    sizes = [math.prod(shape) for _, shape, _ in leaves]

    @jax.jit
    def make(key):
        flat = jax.random.normal(key, (sum(sizes),), jnp.float32)
        out, at = [], 0
        for (names, shape, leaf_dtype), size in zip(leaves, sizes):
            n = flat[at:at + size].reshape(shape)
            out.append(rule(names, shape)(n).astype(dtype or leaf_dtype))
            at += size
        return out

    return make


def seeded_tree(seed: int, shapes, rule, dtype=None):
    """One jitted call that fills the pytree ``shapes`` (of
    ShapeDtypeStructs) on the device: leaf i is ``rule(path, shape)(n)``
    of its share ``n`` of one standard-normal draw from the seed, in
    ``dtype`` (default: the leaf's own)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    leaves = tuple(
        (tuple(str(getattr(p, "key", getattr(p, "name", p))) for p in path),
         tuple(s.shape), jnp.dtype(s.dtype)) for path, s in flat)
    make = _maker(treedef, leaves, rule, jnp.dtype(dtype) if dtype else None)
    return jax.tree_util.tree_unflatten(treedef, make(seed_key(seed)))


def _straight_through(x, lowered):
    """``lowered`` going forward, the identity going backward (a cast to
    a narrower type has no useful derivative of its own)."""
    return x + jax.lax.stop_gradient(lowered - x)


def fp8_cast(x):
    """Per-tensor scaled float8 (e4m3) and back: the nearest precision
    below bfloat16 that a later PR would be tempted by."""
    x = x.astype(jnp.float32)
    scale = jax.lax.stop_gradient(
        jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0)
    return _straight_through(
        x, (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale)


CASTS = {"fp8": fp8_cast}
