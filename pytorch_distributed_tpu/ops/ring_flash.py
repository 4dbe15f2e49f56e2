"""Ring attention at flash speed: sequence parallelism over the Pallas
kernels.

``parallel.sequence.ring_attention`` folds visiting KV shards with the XLA
online-softmax block (exact, but ~2.6x slower end-to-end than the Pallas
kernels at long L — BENCH_LM.md). This module runs the SAME ring schedule
with the flash kernels doing the per-shard work, made exact by a
ring-level ``jax.custom_vjp``:

Forward (one ring pass):
  each visiting shard is processed by the flash FORWARD kernel, which
  returns its block output and row logsumexp; blocks merge by the standard
  LSE combine ((m, l, acc) running state — mathematically the same
  recurrence the kernel runs internally, applied shard-wise). Causal runs
  use the contiguous-shard structure: a shard from a later ring position is
  fully masked (skipped — no FLOPs), an earlier one is fully visible
  (non-causal kernel), the diagonal runs the causal kernel.

Backward (a second ring pass; this is why the custom_vjp exists — the
merge weights depend on the per-shard LSEs, and differentiating through
them naively would need an lse-cotangent rule the kernel doesn't define):
  with the FINAL output O and GLOBAL row LSE saved as residuals, the
  FlashAttention-2 decomposition applies per KV shard independently:
  each visiting shard's (dQ-contribution, dK, dV) comes from the flash
  BACKWARD kernels with the global LSE and Δ = rowsum(dO ⊙ O), which the
  kernels take from the dO and final-O rows they stage. dQ
  accumulates locally; dK/dV accumulators TRAVEL WITH their shard around
  the ring, so after a full circle every shard's gradients are complete
  and home (one collective permutation per step, same overlap story as
  the forward).

The whole file accumulates in fp32 by construction — the ring merge state
(m, l, acc) and the travelling dq/dk/dv accumulators exist to keep bf16
block results exact across shards; every ``.astype(jnp.float32)`` here IS
the numerics contract, not a policy override (burned down from the lint
baseline into the file-level suppression below, PR 9).

Exactness: values match ``ring_attention``/dense to fp accumulation order;
gradients match dense attention's (tests/test_ring_flash.py, values and
all three grads). Requires equal-length shards with L_local a multiple of
the block sizes (the LM's standard configuration); anything else should
use ``ring_attention``.
"""

# jaxlint: disable-file=precision-cast -- ring kernel accumulators (o/dq/dk/dv, LSE merge state) are fp32 by construction; every cast merges bf16 block results into them

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from pytorch_distributed_tpu.ops.attention import NEG_INF
from pytorch_distributed_tpu.ops.flash_attention import (
    _flash_bwd,
    _flash_bwd_fused,
    _flash_fwd,
    _from3,
    _to3,
)
from pytorch_distributed_tpu.parallel.mesh import SEQ_AXIS


def _visit_bwd(q3, k_cur, v_cur, o3, lse3, do3, scale, causal_block,
               block_q, block_k, interpret, bwd_impl):
    """One visiting shard's (dQ-contribution, dK, dV) — the r5 fused
    single-pass kernel by default (5 big matmuls + one input pass per
    visit vs the split kernels' 7 and two; +20-29% measured standalone,
    BENCH_ATTENTION.md r5), the split pair via bwd_impl='split'."""
    if bwd_impl == "fused":
        return _flash_bwd_fused(
            q3, k_cur, v_cur, o3, lse3, do3, scale, causal_block,
            (block_q, block_k), k_cur.shape[1], interpret,
        )
    return _flash_bwd(
        q3, k_cur, v_cur, o3, lse3, do3, scale, causal_block,
        (block_q, block_k), (block_q, block_k), k_cur.shape[1],
        interpret,
    )


def _fit_block(requested: int, length: int) -> int:
    """Largest block <= requested that divides ``length`` (the ring path
    has no padding, so blocks must divide the shard exactly). Prefers
    128-multiples (lane alignment); falls back to any divisor, then to the
    shard itself — raising the tuned defaults must never make a
    previously-valid call fail."""
    cap = min(requested, length)
    if length % cap == 0:
        return cap
    for c in range(cap - cap % 128, 0, -128):
        if length % c == 0:
            return c
    for c in range(cap, 0, -1):
        if length % c == 0:
            return c
    return length


def _shard_fwd(q3, k3, v3, scale, causal_block, block_q, block_k, interpret):
    """Flash forward on one visiting shard → (o3, lse [BH, L, 1])."""
    o3, lse3 = _flash_fwd(
        q3, k3, v3, scale, causal_block, block_q, block_k, k3.shape[1],
        interpret,
    )
    return o3, lse3[:, :, :1]


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10)
)
def _ring_flash(q, k, v, axis, causal, scale, block_q, block_k, interpret,
                layout, bwd_impl):
    out, _ = _ring_flash_fwd(
        q, k, v, axis, causal, scale, block_q, block_k, interpret, layout,
        bwd_impl,
    )
    return out


def _ring_flash_fwd(q, k, v, axis, causal, scale, block_q, block_k, interpret,
                    layout, bwd_impl):
    if layout == "zigzag":
        return _ring_flash_zigzag_fwd(
            q, k, v, axis, scale, block_q, block_k, interpret
        )
    s = jax.lax.psum(1, axis)
    my = jax.lax.axis_index(axis)
    b, lq, h, d = q.shape
    q3, k3, v3 = _to3(q), _to3(k), _to3(v)
    bh = q3.shape[0]
    perm = [(i, (i + 1) % s) for i in range(s)]

    def fold(carry_state, k_cur, v_cur, step):
        m, l, acc = carry_state
        src = jax.lax.rem(my - step + s, s)

        def merge(o3, lse):
            m_new = jnp.maximum(m, lse)
            corr = jnp.exp(m - m_new)
            w = jnp.exp(lse - m_new)
            return (
                m_new,
                l * corr + w,
                acc * corr + o3.astype(jnp.float32) * w,
            )

        def diag(_):
            return merge(*_shard_fwd(q3, k_cur, v_cur, scale, True,
                                     block_q, block_k, interpret))

        def full(_):
            return merge(*_shard_fwd(q3, k_cur, v_cur, scale, False,
                                     block_q, block_k, interpret))

        def skip(_):
            return (m, l, acc)

        if not causal:
            return full(None)
        # contiguous equal shards: src>my fully masked, src<my fully
        # visible, src==my the causal diagonal
        return jax.lax.cond(
            src > my, skip,
            lambda x: jax.lax.cond(src == my, diag, full, x),
            None,
        )

    def body(carry, step):
        state, (k_cur, v_cur) = carry
        k_nxt, v_nxt = jax.lax.ppermute((k_cur, v_cur), axis, perm)
        state = fold(state, k_cur, v_cur, step)
        return (state, (k_nxt, v_nxt)), None

    init_state = (
        jnp.full((bh, lq, 1), NEG_INF, jnp.float32),
        jnp.zeros((bh, lq, 1), jnp.float32),
        jnp.zeros((bh, lq, d), jnp.float32),
    )
    if s > 1:
        (state, (k_last, v_last)), _ = jax.lax.scan(
            body, (init_state, (k3, v3)), jnp.arange(s - 1)
        )
    else:
        state, (k_last, v_last) = init_state, (k3, v3)
    m, l, acc = fold(state, k_last, v_last, s - 1)

    l_safe = jnp.maximum(l, 1e-37)
    o3 = (acc / l_safe).astype(q.dtype)
    lse = jnp.where(l > 0.0, m + jnp.log(l_safe), NEG_INF)  # [BH, L, 1]
    return _from3(o3, b, h), (q, k, v, o3, lse)


def _ring_flash_bwd(axis, causal, scale, block_q, block_k, interpret, layout,
                    bwd_impl, res, g):
    if layout == "zigzag":
        return _ring_flash_zigzag_bwd(
            axis, scale, block_q, block_k, interpret, res, g, bwd_impl
        )
    q, k, v, o3, lse = res
    b, lq, h, d = q.shape
    s = jax.lax.psum(1, axis)
    my = jax.lax.axis_index(axis)
    q3, k3, v3, do3 = _to3(q), _to3(k), _to3(v), _to3(g.astype(q.dtype))
    bh = q3.shape[0]
    lse3 = jnp.broadcast_to(lse, (bh, lq, 128))
    perm = [(i, (i + 1) % s) for i in range(s)]

    def shard_bwd(k_cur, v_cur, causal_block):
        return _visit_bwd(
            q3, k_cur, v_cur, o3, lse3, do3, scale, causal_block,
            block_q, block_k, interpret, bwd_impl,
        )

    def fold(dq_acc, dk_cur, dv_cur, k_cur, v_cur, step):
        src = jax.lax.rem(my - step + s, s)

        def run(causal_block, _):
            dq3, dk3, dv3 = shard_bwd(k_cur, v_cur, causal_block)
            return (
                dq_acc + dq3.astype(jnp.float32),
                dk_cur + dk3.astype(jnp.float32),
                dv_cur + dv3.astype(jnp.float32),
            )

        if not causal:
            return run(False, None)
        return jax.lax.cond(
            src > my,
            lambda _: (dq_acc, dk_cur, dv_cur),  # fully masked: no grads
            lambda x: jax.lax.cond(
                src == my, functools.partial(run, True),
                functools.partial(run, False), x,
            ),
            None,
        )

    def body(carry, step):
        dq_acc, (k_cur, v_cur, dk_cur, dv_cur) = carry
        # k/v rotate from their pre-fold values (the fold consumes k_cur);
        # the gradient accumulators rotate AFTER the fold so each shard's
        # dk/dv travels with it carrying this device's contribution
        k_nxt, v_nxt = jax.lax.ppermute((k_cur, v_cur), axis, perm)
        dq_acc, dk_new, dv_new = fold(dq_acc, dk_cur, dv_cur, k_cur, v_cur,
                                      step)
        dk_nxt, dv_nxt = jax.lax.ppermute((dk_new, dv_new), axis, perm)
        return (dq_acc, (k_nxt, v_nxt, dk_nxt, dv_nxt)), None

    zeros_kv = jnp.zeros((bh, k3.shape[1], d), jnp.float32)
    init = (jnp.zeros((bh, lq, d), jnp.float32), (k3, v3, zeros_kv, zeros_kv))
    if s > 1:
        (dq_acc, (k_last, v_last, dk_last, dv_last)), _ = jax.lax.scan(
            body, init, jnp.arange(s - 1)
        )
    else:
        dq_acc, (k_last, v_last, dk_last, dv_last) = init
    # final fold (no trailing rotation needed after it...) — the shard held
    # now is the one that must end at THIS device: after s-1 rotations each
    # device holds the shard originated at (my+1) mod s; one more rotation
    # inside the last fold step would complete the circle. Fold first, then
    # rotate once so every accumulator lands on its owner.
    dq_acc, dk_new, dv_new = fold(dq_acc, dk_last, dv_last, k_last, v_last,
                                  s - 1)
    dk_home, dv_home = jax.lax.ppermute((dk_new, dv_new), axis, perm)

    return (
        _from3(dq_acc.astype(q.dtype), b, h),
        _from3(dk_home.astype(k.dtype), b, h),
        _from3(dv_home.astype(v.dtype), b, h),
    )


def _ring_flash_zigzag_fwd(q, k, v, axis, scale, block_q, block_k, interpret):
    """Causal forward on the zigzag layout: rank r holds chunks
    (r, 2s-1-r); of the four (q-chunk, kv-chunk) pairs per visiting shard
    one is always visible, one never (omitted), and the two chunk-diagonal
    pairs carry runtime conds — every rank runs ~2 chunk kernels per step
    (the balance argument: parallel/sequence.py `_ring_attention_zigzag`)."""
    s = jax.lax.psum(1, axis)
    my = jax.lax.axis_index(axis)
    b, lq, h, d = q.shape
    c = lq // 2
    q3, k3, v3 = _to3(q), _to3(k), _to3(v)
    bh = q3.shape[0]
    q_lo, q_hi = q3[:, :c], q3[:, c:]
    perm = [(i, (i + 1) % s) for i in range(s)]

    def merge(state, o3, lse):
        m, l, acc = state
        m_new = jnp.maximum(m, lse)
        corr = jnp.exp(m - m_new)
        w = jnp.exp(lse - m_new)
        return (m_new, l * corr + w,
                acc * corr + o3.astype(jnp.float32) * w)

    def pair(state, qc, kc, vc, causal_block):
        return merge(state, *_shard_fwd(qc, kc, vc, scale, causal_block,
                                        block_q, block_k, interpret))

    def fold(states, k_cur, v_cur, step):
        st_lo, st_hi = states
        src = jax.lax.rem(my - step + s, s)
        k_lo, k_hi = k_cur[:, :c], k_cur[:, c:]
        v_lo, v_hi = v_cur[:, :c], v_cur[:, c:]
        # (q_lo, kv_lo): diag at src==my, full at src<my, masked after
        st_lo = jax.lax.cond(
            src > my, lambda st: st,
            lambda st: jax.lax.cond(
                src == my,
                lambda st2: pair(st2, q_lo, k_lo, v_lo, True),
                lambda st2: pair(st2, q_lo, k_lo, v_lo, False),
                st,
            ),
            st_lo,
        )
        # (q_hi, kv_lo): always fully visible
        st_hi = pair(st_hi, q_hi, k_lo, v_lo, False)
        # (q_hi, kv_hi): diag at src==my, full at src>my, masked before
        st_hi = jax.lax.cond(
            src < my, lambda st: st,
            lambda st: jax.lax.cond(
                src == my,
                lambda st2: pair(st2, q_hi, k_hi, v_hi, True),
                lambda st2: pair(st2, q_hi, k_hi, v_hi, False),
                st,
            ),
            st_hi,
        )
        return (st_lo, st_hi)

    def body(carry, step):
        states, (k_cur, v_cur) = carry
        k_nxt, v_nxt = jax.lax.ppermute((k_cur, v_cur), axis, perm)
        states = fold(states, k_cur, v_cur, step)
        return (states, (k_nxt, v_nxt)), None

    def zero_state():
        return (
            jnp.full((bh, c, 1), NEG_INF, jnp.float32),
            jnp.zeros((bh, c, 1), jnp.float32),
            jnp.zeros((bh, c, d), jnp.float32),
        )

    init = ((zero_state(), zero_state()), (k3, v3))
    if s > 1:
        (states, (k_last, v_last)), _ = jax.lax.scan(
            body, init, jnp.arange(s - 1)
        )
    else:
        states, (k_last, v_last) = init
    st_lo, st_hi = fold(states, k_last, v_last, s - 1)

    def finalize(state):
        m, l, acc = state
        l_safe = jnp.maximum(l, 1e-37)
        o3 = (acc / l_safe).astype(q.dtype)
        lse = jnp.where(l > 0.0, m + jnp.log(l_safe), NEG_INF)
        return o3, lse

    o_lo, lse_lo = finalize(st_lo)
    o_hi, lse_hi = finalize(st_hi)
    o3 = jnp.concatenate([o_lo, o_hi], axis=1)
    lse = jnp.concatenate([lse_lo, lse_hi], axis=1)
    return _from3(o3, b, h), (q, k, v, o3, lse)


def _ring_flash_zigzag_bwd(axis, scale, block_q, block_k, interpret, res, g,
                           bwd_impl):
    """Zigzag backward: per-pair FlashAttention-2 kernels with the global
    LSE; dq accumulates per local q chunk, dk/dv accumulators travel with
    their shard (same traveling scheme as the contiguous backward) with
    per-chunk slice updates."""
    q, k, v, o3, lse = res
    b, lq, h, d = q.shape
    c = lq // 2
    s = jax.lax.psum(1, axis)
    my = jax.lax.axis_index(axis)
    q3, k3, v3, do3 = _to3(q), _to3(k), _to3(v), _to3(g.astype(q.dtype))
    bh = q3.shape[0]
    lse3 = jnp.broadcast_to(lse, (bh, lq, 128))
    perm = [(i, (i + 1) % s) for i in range(s)]

    chunks = {
        "lo": (q3[:, :c], o3[:, :c], lse3[:, :c], do3[:, :c]),
        "hi": (q3[:, c:], o3[:, c:], lse3[:, c:], do3[:, c:]),
    }

    def pair_bwd(which, kc, vc, causal_block):
        qc, oc, lsec, doc = chunks[which]
        return _visit_bwd(
            qc, kc, vc, oc, lsec, doc, scale, causal_block,
            block_q, block_k, interpret, bwd_impl,
        )

    def fold(dq_acc, dkv_cur, k_cur, v_cur, step):
        src = jax.lax.rem(my - step + s, s)
        k_lo, k_hi = k_cur[:, :c], k_cur[:, c:]
        v_lo, v_hi = v_cur[:, :c], v_cur[:, c:]
        dq_lo, dq_hi = dq_acc
        dk_cur, dv_cur = dkv_cur

        def add_lo(dk, dkc):
            return dk.at[:, :c].add(dkc.astype(jnp.float32))

        def add_hi(dk, dkc):
            return dk.at[:, c:].add(dkc.astype(jnp.float32))

        # (q_lo, kv_lo)
        def run_ll(args, causal_block):
            dq_lo, dk_cur, dv_cur = args
            dq3, dk3, dv3 = pair_bwd("lo", k_lo, v_lo, causal_block)
            return (dq_lo + dq3.astype(jnp.float32), add_lo(dk_cur, dk3),
                    add_lo(dv_cur, dv3))

        dq_lo, dk_cur, dv_cur = jax.lax.cond(
            src > my, lambda a: a,
            lambda a: jax.lax.cond(
                src == my, functools.partial(run_ll, causal_block=True),
                functools.partial(run_ll, causal_block=False), a,
            ),
            (dq_lo, dk_cur, dv_cur),
        )
        # (q_hi, kv_lo): always runs
        dq3, dk3, dv3 = pair_bwd("hi", k_lo, v_lo, False)
        dq_hi = dq_hi + dq3.astype(jnp.float32)
        dk_cur, dv_cur = add_lo(dk_cur, dk3), add_lo(dv_cur, dv3)

        # (q_hi, kv_hi)
        def run_hh(args, causal_block):
            dq_hi, dk_cur, dv_cur = args
            dq3, dk3, dv3 = pair_bwd("hi", k_hi, v_hi, causal_block)
            return (dq_hi + dq3.astype(jnp.float32), add_hi(dk_cur, dk3),
                    add_hi(dv_cur, dv3))

        dq_hi, dk_cur, dv_cur = jax.lax.cond(
            src < my, lambda a: a,
            lambda a: jax.lax.cond(
                src == my, functools.partial(run_hh, causal_block=True),
                functools.partial(run_hh, causal_block=False), a,
            ),
            (dq_hi, dk_cur, dv_cur),
        )
        return (dq_lo, dq_hi), (dk_cur, dv_cur)

    def body(carry, step):
        dq_acc, (k_cur, v_cur, dk_cur, dv_cur) = carry
        k_nxt, v_nxt = jax.lax.ppermute((k_cur, v_cur), axis, perm)
        dq_acc, (dk_new, dv_new) = fold(dq_acc, (dk_cur, dv_cur), k_cur,
                                        v_cur, step)
        dk_nxt, dv_nxt = jax.lax.ppermute((dk_new, dv_new), axis, perm)
        return (dq_acc, (k_nxt, v_nxt, dk_nxt, dv_nxt)), None

    zeros_kv = jnp.zeros((bh, lq, d), jnp.float32)
    init = (
        (jnp.zeros((bh, c, d), jnp.float32),
         jnp.zeros((bh, c, d), jnp.float32)),
        (k3, v3, zeros_kv, zeros_kv),
    )
    if s > 1:
        (dq_acc, (k_last, v_last, dk_last, dv_last)), _ = jax.lax.scan(
            body, init, jnp.arange(s - 1)
        )
    else:
        dq_acc, (k_last, v_last, dk_last, dv_last) = init
    dq_acc, (dk_new, dv_new) = fold(dq_acc, (dk_last, dv_last), k_last,
                                    v_last, s - 1)
    # one more rotation lands each accumulator on its shard's home rank
    dk_home, dv_home = jax.lax.ppermute((dk_new, dv_new), axis, perm)

    dq3 = jnp.concatenate(dq_acc, axis=1)
    return (
        _from3(dq3.astype(q.dtype), b, h),
        _from3(dk_home.astype(k.dtype), b, h),
        _from3(dv_home.astype(v.dtype), b, h),
    )


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis: str = SEQ_AXIS,
    causal: bool = False,
    scale: Optional[float] = None,
    # (1024, 1024): the r5 composed on-chip A/B through the ring path —
    # 90.1/106.8 TFLOP/s fwdbwd at L 4096/8192 vs 87.8/103.4 at the old
    # (512, 1024) (both with the fused per-visit backward; the split
    # kernels measured 84-95 on the same harness). _fit_block clamps for
    # small shards.
    block_q: int = 1024,
    block_k: int = 1024,
    interpret: bool | None = None,
    layout: str = "contiguous",
    bwd_impl: str = "fused",
) -> jax.Array:
    """Ring attention with Pallas flash kernels per visiting shard (call
    under shard_map; same contract as ``parallel.sequence.ring_attention``:
    ``[B, L_local, H, D]`` shards of a contiguously-sharded sequence, or —
    with ``layout="zigzag"`` — shards holding chunks (r, 2s-1-r) of the
    2s-chunk decomposition (``parallel.sequence.zigzag_shard``), which
    balances the causal critical path across ranks.

    Requires equal-length shards with L_local (each half-chunk, for
    zigzag) a multiple of the clamped block sizes; use ``ring_attention``
    for anything irregular. Note ``base_offset`` is unsupported (the
    causal structure is derived from ring positions, which already encode
    absolute order).
    """
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"  # see flash_attention
    lq, lk = q.shape[1], k.shape[1]
    if lq != lk:
        raise ValueError(
            f"ring flash needs equal Q/KV shard lengths, got {lq} vs {lk}"
        )
    if layout not in ("contiguous", "zigzag"):
        raise ValueError(f"unknown layout {layout!r}")
    if layout == "zigzag":
        if not causal:
            raise ValueError(
                "zigzag layout only changes causal scheduling; use "
                "layout='contiguous' for non-causal attention"
            )
        if lq % 2:
            raise ValueError(f"zigzag needs an even shard length, got {lq}")
    if bwd_impl not in ("split", "fused"):
        raise ValueError(
            f"bwd_impl {bwd_impl!r} must be 'split' or 'fused'"
        )
    if layout == "zigzag":
        c = lq // 2
        block_q = _fit_block(block_q, c)
        block_k = _fit_block(block_k, c)
        return _ring_flash(q, k, v, axis, True, scale, block_q, block_k,
                           interpret, "zigzag", bwd_impl)
    block_q = _fit_block(block_q, lq)
    block_k = _fit_block(block_k, lk)
    return _ring_flash(q, k, v, axis, causal, scale, block_q, block_k,
                       interpret, "contiguous", bwd_impl)
