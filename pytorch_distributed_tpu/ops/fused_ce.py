"""Fused (blockwise) linear + softmax cross-entropy.

The LM's loss tail used to be ``lm_head`` Dense → fp32 ``[B, L, V]`` logits
→ ``log_softmax`` (``models/transformer.py`` + ``ops/losses.py``): at
bs8/L1024/V32k that is a ~1.0 GB fp32 tensor (double it for the backward
cotangent), which capped batch×length (bs8/L4096 failed to compile,
BENCH_LM.md) and spent HBM bandwidth on a tensor whose only purpose is a
per-token scalar. This op computes the SAME weighted loss sum without the
full logits ever existing:

- ``lax.scan`` over token blocks of ``block_n`` rows; each iteration runs
  one ``[block_n, E] × [E, V]`` matmul (bf16 operands on the MXU, fp32
  accumulation via ``preferred_element_type``) and immediately reduces it
  to ``lse`` / label-logit scalars — peak extra HBM is one
  ``[block_n, V]`` fp32 block (~131 MB at block_n=1024/V=32k; halve it
  with block_n=512), O(1) in sequence length;
- a ``custom_vjp`` whose residuals are the inputs plus the per-token
  ``lse``/``z`` vectors (``[N]`` fp32 — kilobytes); the backward recomputes
  each block's logits (one extra matmul pass — the classic recompute
  trade) and feeds ``softmax - onehot`` straight into the ``dx``/``dW``
  matmuls, so the backward's peak is the same single block;
- optional ``vocab_axis``: Megatron vocab-parallel heads pass their LOCAL
  kernel shard ``[E, V/tp]`` and the mesh axis name; the streamed softmax
  statistics combine across shards (pmax of block maxima, psum of the
  shifted exp-sums and of the masked label gather) and ``dx`` is psum'd
  the row-parallel way. Every shard returns the identical global loss sum.

Numerics note: the fused path accumulates the logits matmul in fp32
(``preferred_element_type``) where the unfused path materialized bf16
logits and upcast — the fused loss is therefore slightly MORE accurate
for bf16 models, not less. Parity is tested against ``ops.losses``
at fp32 (tests/test_fused_ce.py).

Reference precedent: none — the reference (583-line torch scripts) has no
LM. This is the "matching-or-beating" bar applied to our own
``transformer.py:548`` (VERDICT r4 next #1).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def _matmul_f32(a, b, cdt):
    """[M, E] x [E, V] with cdt (bf16) operands, fp32 accumulation."""
    return lax.dot_general(
        a.astype(cdt), b,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _fused_ce(block_n: int, cdt, vocab_axis: Optional[str],
              x, kernel, labels, weights):
    total, _ = _fused_ce_fwd(block_n, cdt, vocab_axis, x, kernel,
                             labels, weights)
    return total


def _block_stats(logits, loc_labels, v_local, vocab_axis):
    """(lse, z) for one block's logits [bn, V_local]; collective-combined
    when the vocab dim is sharded."""
    if vocab_axis is None:
        m = jnp.max(logits, axis=-1)
        lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[:, None]), axis=-1))
        z = jnp.take_along_axis(logits, loc_labels[:, None], axis=1)[:, 0]
        return lse, z
    m_l = jnp.max(logits, axis=-1)
    m = lax.pmax(m_l, vocab_axis)
    s = lax.psum(
        jnp.sum(jnp.exp(logits - m[:, None]), axis=-1), vocab_axis
    )
    lse = m + jnp.log(s)
    in_range = (loc_labels >= 0) & (loc_labels < v_local)
    safe = jnp.clip(loc_labels, 0, v_local - 1)
    z_l = jnp.take_along_axis(logits, safe[:, None], axis=1)[:, 0]
    z = lax.psum(jnp.where(in_range, z_l, 0.0), vocab_axis)
    return lse, z


def _local_labels(labels, v_local, vocab_axis):
    """Global vocab ids → this shard's local ids (may be out of range
    under vocab parallelism; ``_block_stats``/``_bwd`` mask)."""
    if vocab_axis is None:
        return labels.astype(jnp.int32)
    off = lax.axis_index(vocab_axis) * v_local
    return labels.astype(jnp.int32) - off


@jax.named_scope("fused_ce")
def _fused_ce_fwd(block_n, cdt, vocab_axis, x, kernel, labels, weights):
    n, e = x.shape
    v_local = kernel.shape[1]
    nb = n // block_n
    k_c = kernel.astype(cdt)
    xb = x.reshape(nb, block_n, e)
    lb = _local_labels(labels, v_local, vocab_axis).reshape(nb, block_n)
    wb = weights.astype(jnp.float32).reshape(nb, block_n)

    def body(carry, inp):
        x_i, l_i, w_i = inp
        logits = _matmul_f32(x_i, k_c, cdt)
        lse, z = _block_stats(logits, l_i, v_local, vocab_axis)
        return carry + jnp.sum((lse - z) * w_i), (lse, z)

    total, (lse, z) = lax.scan(
        body, jnp.zeros((), jnp.float32), (xb, lb, wb)
    )
    return total, (x, kernel, labels, weights, lse.reshape(n), z.reshape(n))


@jax.named_scope("fused_ce")
def _fused_ce_bwd(block_n, cdt, vocab_axis, res, g):
    x, kernel, labels, weights, lse, z = res
    n, e = x.shape
    v_local = kernel.shape[1]
    nb = n // block_n
    k_c = kernel.astype(cdt)
    xb = x.reshape(nb, block_n, e)
    lb = _local_labels(labels, v_local, vocab_axis).reshape(nb, block_n)
    wb = weights.astype(jnp.float32).reshape(nb, block_n)
    lse_b = lse.reshape(nb, block_n)

    def body(dw, inp):
        x_i, l_i, w_i, lse_i = inp
        logits = _matmul_f32(x_i, k_c, cdt)
        p = jnp.exp(logits - lse_i[:, None])  # this shard's softmax slice
        onehot = (
            l_i[:, None] == jnp.arange(v_local)[None, :]
        ).astype(jnp.float32)  # out-of-range local ids match nothing
        dlogits = (p - onehot) * (w_i * g)[:, None]
        dl = dlogits.astype(cdt)
        # dx = dlogits @ W^T (row-parallel: psum over vocab shards);
        # dW = x^T @ dlogits (stays local to this vocab shard).
        dx_i = lax.dot_general(
            dl, k_c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if vocab_axis is not None:
            dx_i = lax.psum(dx_i, vocab_axis)
        dw = dw + lax.dot_general(
            x_i.astype(cdt), dl, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return dw, dx_i.astype(x.dtype)

    dw, dx = lax.scan(
        body,
        jnp.zeros(kernel.shape, jnp.float32),
        (xb, lb, wb, lse_b),
    )
    # Cotangent dtypes must match the PRIMAL dtypes: weights arrive at
    # whatever dtype the caller passed (the fwd casts a fp32 COPY for the
    # math), and returning a hardcoded fp32 cotangent for e.g. bf16
    # weights fails deep inside the vjp trace with an opaque dtype
    # mismatch (ADVICE r5 #4). The per-token loss (lse - z) stays fp32
    # until this final cast.
    d_weights = ((lse - z) * g).astype(weights.dtype)
    return (
        dx.reshape(n, e),
        dw.astype(kernel.dtype),
        np.zeros(labels.shape, jax.dtypes.float0),
        d_weights,
    )


def _fused_ce_fwd_rule(block_n, cdt, vocab_axis, x, kernel, labels, weights):
    total, res = _fused_ce_fwd(block_n, cdt, vocab_axis, x, kernel,
                               labels, weights)
    return total, res


_fused_ce.defvjp(_fused_ce_fwd_rule, _fused_ce_bwd)


def fused_linear_cross_entropy(
    x: jax.Array,
    kernel: jax.Array,
    labels: jax.Array,
    weights: jax.Array,
    *,
    block_n: int = 512,
    compute_dtype=jnp.bfloat16,
    vocab_axis: Optional[str] = None,
) -> jax.Array:
    """Weighted softmax-CE SUM of ``(x @ kernel)`` against ``labels``.

    Args:
      x: ``[N, E]`` (or ``[B, L, E]``) final hidden states (post-ln_f).
      kernel: ``[E, V]`` lm_head kernel — the LOCAL vocab shard
        ``[E, V/tp]`` when ``vocab_axis`` is set.
      labels: ``[N]``/``[B, L]`` int GLOBAL vocab ids.
      weights: ``[N]``/``[B, L]`` fp32 per-token loss weights (0 masks).
      block_n: token rows per scanned block; peak extra HBM is
        ``block_n * V_local`` fp32.
      compute_dtype: matmul operand dtype (the model's ``cfg.dtype``);
        accumulation is always fp32.
      vocab_axis: mesh axis the vocab dim is sharded over, for
        Megatron-style vocab-parallel heads (must be called inside
        shard_map over that axis).

    Returns the scalar fp32 weighted loss sum — identical (and replicated)
    on every vocab shard. Divide by the global token count outside.
    """
    if x.ndim == 3:
        x = x.reshape(-1, x.shape[-1])
    labels = labels.reshape(-1)
    weights = weights.reshape(-1)
    n = x.shape[0]
    bn = min(block_n, n)
    pad = (-n) % bn
    if pad:
        # zero-weight padding rows: zero loss, zero gradient contribution
        x = jnp.concatenate([x, jnp.zeros((pad, x.shape[1]), x.dtype)])
        labels = jnp.concatenate(
            [labels, jnp.zeros((pad,), labels.dtype)]
        )
        weights = jnp.concatenate(
            [weights, jnp.zeros((pad,), weights.dtype)]
        )
    # kernel is passed at its storage dtype (fp32 params): the bwd
    # accumulates dW in fp32 and returns it at that dtype — pre-casting
    # to bf16 here would bottleneck the weight gradient through bf16.
    cdt = jnp.dtype(compute_dtype)
    return _fused_ce(bn, cdt, vocab_axis, x, kernel, labels, weights)
