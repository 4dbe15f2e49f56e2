"""FlashAttention forward AND backward as Pallas TPU kernels.

The blockwise kernel (``ops.attention.blockwise_attention``) is the XLA-fused
reference; this is the hand-tiled fast path for the same math, built per the
TPU Pallas playbook (/opt/skills/guides/pallas_guide.md):

Forward (``_fwd_kernel``):
- grid (B·H, Lq/block_q, Lk/block_k), KV innermost and sequential
  ("arbitrary" dimension semantics — it carries the online-softmax
  recurrence); Q/K/V blocks staged HBM→VMEM by BlockSpec index maps;
- the running (m, l, acc) state lives in VMEM scratch, persisting across the
  KV sweep for each Q block; everything accumulates in fp32 while inputs can
  be bf16 feeding the MXU (``preferred_element_type=f32``);
- causal masking skips fully-masked KV blocks with ``pl.when`` (no FLOPs
  spent above the diagonal) and applies a multiplicative mask so
  fully-masked rows yield zeros;
- alongside O it emits the row logsumexp (LSE), which is what makes the
  one-pass backward possible.

Backward (FlashAttention-2 decomposition, two kernels — round-2, replacing
the rematerialized blockwise VJP):
  with P = exp(S - LSE),  Δ_i = Σ_j P_ij (dO V^T)_ij = rowsum(dO ⊙ O):
    dV = P^T dO
    dS = P ⊙ (dO V^T − Δ)·scale
    dQ = dS K          (``_bwd_dq_kernel``: per-Q-block, sweeps KV)
    dK = dS^T Q        (``_bwd_dkv_kernel``: per-KV-block, sweeps Q)
  Δ is one fused XLA elementwise pass outside the kernels; no O(L²) tensor
  ever exists in HBM and nothing is rematerialized through the slow path.

Arbitrary lengths: inputs are zero-padded to block multiples and the
kernels mask padded KEY positions explicitly (padded query rows compute
garbage that is sliced away), so any (Lq, Lk) works — the round-1
multiple-of-block restriction is gone.

Shapes follow the framework convention ``[B, L, H, D]``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Pallas is a hard dependency of THIS module only: the ops package exports
# flash_attention lazily, so environments without pallas keep every other
# attention path working and fail loudly only when flash is actually chosen.

from pytorch_distributed_tpu.ops.attention import NEG_INF


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, block_q: int, block_k: int, kv_len: int,
):
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    qi = pl.program_id(1)
    q_start = qi * block_q
    k_start = ki * block_k

    def _block():
        # Fold the softmax scale into Q: one [block_q, D] multiply instead
        # of a [block_q, block_k] one on the logits.
        q = (q_ref[0] * jnp.asarray(scale, q_ref.dtype))  # [block_q, D]
        k = k_ref[0]  # [block_k, D]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [block_q, block_k]
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        mask = k_pos < kv_len  # padded keys contribute nothing
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            mask = mask & (k_pos <= q_pos)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :1]  # [block_q, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)  # [block_q, block_k]
        p = p * mask  # fully-masked rows stay all-zero (l == 0 → out 0)
        corr = jnp.exp(m_prev - m_new)  # [block_q, 1]
        l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    if causal:
        # A KV block strictly above the diagonal contributes nothing — skip
        # its FLOPs entirely.
        pl.when(k_start <= q_start + block_q - 1)(_block)
    else:
        _block()

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:, :1], 1e-37)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        # LSE = m + log l; fully-masked rows get a huge negative (their
        # backward P = exp(s - lse) must still be ~0, not inf).
        lse = jnp.where(
            l_scr[:, :1] > 0.0, m_scr[:, :1] + jnp.log(l), NEG_INF
        )
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref[0].shape)


def _flash_fwd(q3, k3, v3, scale, causal, block_q, block_k, kv_len, interpret):
    """[BH, L, D] inputs → ([BH, Lq, D] out, [BH, Lq, 128] lse)."""
    bh, lq, d = q3.shape
    lk = k3.shape[1]
    grid = (bh, lq // block_q, lk // block_k)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, kv_len=kv_len,
    )
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )
    return pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((bh, lq, d), q3.dtype),
            jax.ShapeDtypeStruct((bh, lq, 128), jnp.float32),
        ],
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),  # running row max m
            pltpu.VMEM((block_q, 128), jnp.float32),  # running row sum l
            pltpu.VMEM((block_q, d), jnp.float32),  # un-normalized output
        ],
        interpret=interpret,
        name="flash_fwd",
        **kwargs,
    )(q3, k3, v3)


def _masked_p_ds(q, k, v, do, lse, delta, *, scale, causal,
                 q_start, k_start, block_q, block_k, kv_len):
    """The ONE masked-softmax-gradient block shared by every backward
    kernel: S = scale·QKᵀ (fp32 accum), the causal+padding mask,
    P = exp(S − LSE) via ``where`` (not ``*``) so a fully-masked row
    (LSE = −inf from the forward) yields 0, not inf·0 = NaN — defends
    offset/cross-attention callers the forward already defends — and
    dS = P ⊙ (dOVᵀ − Δ)·scale. Keeping it in one place means a masking
    or NaN-defense fix cannot diverge between bwd_impl='split' and
    'fused'."""
    sblk = jax.lax.dot_general(
        q * jnp.asarray(scale, q.dtype), k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [block_q, block_k]
    k_pos = k_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    mask = k_pos < kv_len
    if causal:
        q_pos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        mask = mask & (k_pos <= q_pos)
    pblk = jnp.where(mask, jnp.exp(sblk - lse), 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = pblk * (dp - delta) * jnp.asarray(scale, jnp.float32)
    return pblk, ds


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr,
    *, scale: float, causal: bool, block_q: int, block_k: int, kv_len: int,
):
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q_start = pl.program_id(1) * block_q
    k_start = ki * block_k

    def _block():
        k = k_ref[0]
        _p, ds = _masked_p_ds(
            q_ref[0], k, v_ref[0], do_ref[0], lse_ref[0][:, :1],
            delta_ref[0][:, :1], scale=scale, causal=causal,
            q_start=q_start, k_start=k_start, block_q=block_q,
            block_k=block_k, kv_len=kv_len,
        )
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        pl.when(k_start <= q_start + block_q - 1)(_block)
    else:
        _block()

    @pl.when(ki == n_k - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_scr, dv_scr,
    *, scale: float, causal: bool, block_q: int, block_k: int, kv_len: int,
):
    qi = pl.program_id(2)
    n_q = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    k_start = pl.program_id(1) * block_k
    q_start = qi * block_q

    def _block():
        q = q_ref[0]
        do = do_ref[0]
        p, ds = _masked_p_ds(
            q, k_ref[0], v_ref[0], do, lse_ref[0][:, :1],
            delta_ref[0][:, :1], scale=scale, causal=causal,
            q_start=q_start, k_start=k_start, block_q=block_q,
            block_k=block_k, kv_len=kv_len,
        )
        # dV += P^T dO
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # dK += dS^T Q
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        # Q blocks entirely ABOVE the diagonal see this KV block masked out.
        pl.when(q_start + block_q - 1 >= k_start)(_block)
    else:
        _block()

    @pl.when(qi == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_fused_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dqp_ref, dk_ref, dv_ref, dk_scr, dv_scr,
    *, scale: float, causal: bool, block_q: int, block_k: int, kv_len: int,
):
    """Single-pass backward (round 5, the r4-named kernel-family exit):
    grid (BH, KV, Q) with Q innermost. Computes S and dP ONCE per
    (q, kv) block and feeds all three products — where the split
    kernels spend 7 big matmuls (dQ pass: S, dP, dQ; dKV pass: S, dV,
    dP, dK) and read Q/K/V/dO twice, this spends the mathematical
    minimum 5 and reads once. dK/dV accumulate in VMEM across the
    inner Q sweep; dQ's cross-KV accumulation cannot live in VMEM in
    this grid order (non-consecutive revisits), so each (kv, q) step
    emits a PARTIAL dQ block to HBM (input dtype — see
    ``_flash_bwd_fused``) and one XLA reduction over the KV axis
    finishes it outside (traffic ≈ n_k · |dQ|, measured against the
    saved matmuls in BENCH_ATTENTION.md r5)."""
    qi = pl.program_id(2)
    n_q = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    k_start = pl.program_id(1) * block_k
    q_start = qi * block_q

    def _block():
        q = q_ref[0]
        k = k_ref[0]
        do = do_ref[0]
        p, ds = _masked_p_ds(
            q, k, v_ref[0], do, lse_ref[0][:, :1], delta_ref[0][:, :1],
            scale=scale, causal=causal, q_start=q_start, k_start=k_start,
            block_q=block_q, block_k=block_k, kv_len=kv_len,
        )
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dsc = ds.astype(q.dtype)
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            dsc, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dqp_ref[0, 0] = jax.lax.dot_general(
            dsc, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(dqp_ref.dtype)

    if causal:
        # fully-above-diagonal (q, kv) blocks contribute nothing — but
        # their dq partial block must still be ZEROED (the out buffer is
        # otherwise uninitialized memory)
        @pl.when(q_start + block_q - 1 < k_start)
        def _skip():
            dqp_ref[0, 0] = jnp.zeros_like(dqp_ref[0, 0])

        pl.when(q_start + block_q - 1 >= k_start)(_block)
    else:
        _block()

    @pl.when(qi == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd_fused(q3, k3, v3, o3, lse3, do3, scale, causal, blocks,
                     kv_len, interpret, delta3=None, partials_f32=False):
    """One fused kernel + one XLA reduction. ``blocks`` = (block_q,
    block_k) shared by the whole pass."""
    bh, lq, d = q3.shape
    lk = k3.shape[1]
    if delta3 is None:
        delta3 = compute_delta(do3, o3)
    bq, bk = blocks
    n_k = lk // bk
    # dQ partials at the INPUT dtype (default): halves the partial HBM
    # traffic. The same-process A/B (BENCH_ATTENTION.md r5) measured
    # input-dtype partials faster at BOTH 4096 and 8192 (108.6/113.9 vs
    # 104.6/107.4 TFLOP/s) — an earlier cross-run reading that suggested
    # fp32 wins at 4096 was run-to-run weather. The cross-partial sum always
    # accumulates in fp32; ``partials_f32`` remains as a sweep/precision
    # knob (each bf16 partial rounds before the sum).
    p_dtype = jnp.float32 if partials_f32 else q3.dtype
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )
    q_spec = pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0))
    row_spec = pl.BlockSpec((1, bq, 128), lambda b, j, i: (b, i, 0))
    kv_spec = pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0))
    dqp3, dk3, dv3 = pl.pallas_call(
        functools.partial(_bwd_fused_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, kv_len=kv_len),
        out_shape=[
            jax.ShapeDtypeStruct((bh, n_k, lq, d), p_dtype),
            jax.ShapeDtypeStruct((bh, lk, d), k3.dtype),
            jax.ShapeDtypeStruct((bh, lk, d), v3.dtype),
        ],
        grid=(bh, n_k, lq // bq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, j, i: (b, j, i, 0)),
            kv_spec,
            kv_spec,
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_fused",
        **kwargs,
    )(q3, k3, v3, do3, lse3, delta3)
    dq3 = jnp.sum(dqp3.astype(jnp.float32), axis=1).astype(q3.dtype)
    return dq3, dk3, dv3


def compute_delta(do3, o3):
    """Δ = rowsum(dO ⊙ O) broadcast to the [BH, Lq, 128] row layout LSE
    uses — shard-invariant, so ring callers compute it ONCE outside their
    ring loop and pass it in."""
    bh, lq, _ = o3.shape
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32), axis=-1)
    return jnp.broadcast_to(delta[:, :, None], (bh, lq, 128))


def _flash_bwd(q3, k3, v3, o3, lse3, do3, scale, causal, dq_blocks,
               dkv_blocks, kv_len, interpret, delta3=None):
    """Backward kernels with INDEPENDENTLY SPECIFIABLE tilings:
    ``dq_blocks`` / ``dkv_blocks`` are (block_q, block_k) for the dQ and
    dK/dV kernels. NOTE: isolated per-kernel sweeps suggested mixed
    tilings, but those do NOT compose — the composed A/B through the
    real vjp measured the 'per-kernel-optimal' mix 26% WORSE
    (BENCH_ATTENTION.md r4); ``flash_attention`` therefore passes the
    SAME tuple to both, length-selected. The two parameters exist for
    sweeps, not because mixed defaults won."""
    bh, lq, d = q3.shape
    lk = k3.shape[1]
    if delta3 is None:
        delta3 = compute_delta(do3, o3)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )

    bq, bk = dq_blocks
    q_spec = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0))
    row_spec = pl.BlockSpec((1, bq, 128), lambda b, i, j: (b, i, 0))
    kv_spec_q = pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0))
    dq3 = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, kv_len=kv_len),
        out_shape=jax.ShapeDtypeStruct((bh, lq, d), q3.dtype),
        grid=(bh, lq // bq, lk // bk),
        in_specs=[q_spec, kv_spec_q, kv_spec_q, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
        **kwargs,
    )(q3, k3, v3, do3, lse3, delta3)

    # dK/dV: grid puts the KV block second, Q innermost (the recurrence).
    bq, bk = dkv_blocks
    q_spec_i = pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0))
    row_spec_i = pl.BlockSpec((1, bq, 128), lambda b, j, i: (b, i, 0))
    kv_spec = pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0))
    dk3, dv3 = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, kv_len=kv_len),
        out_shape=[
            jax.ShapeDtypeStruct((bh, lk, d), k3.dtype),
            jax.ShapeDtypeStruct((bh, lk, d), v3.dtype),
        ],
        grid=(bh, lk // bk, lq // bq),
        in_specs=[q_spec_i, kv_spec, kv_spec, q_spec_i, row_spec_i, row_spec_i],
        out_specs=[kv_spec, kv_spec],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
        **kwargs,
    )(q3, k3, v3, do3, lse3, delta3)
    return dq3, dk3, dv3


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11, 12))
def _flash(q, k, v, scale, causal, block_q, block_k, kv_len, interpret,
           dq_blocks=None, dkv_blocks=None, bwd_impl="split",
           partials_f32=False):
    out, _ = _flash_vjp_fwd(
        q, k, v, scale, causal, block_q, block_k, kv_len, interpret,
        dq_blocks, dkv_blocks, bwd_impl, partials_f32,
    )
    return out


def _to3(x):
    b, l, h, d = x.shape
    return jnp.moveaxis(x, 2, 1).reshape(b * h, l, d)


def _from3(x3, b, h):
    bh, l, d = x3.shape
    return jnp.moveaxis(x3.reshape(b, h, l, d), 1, 2)


def _flash_vjp_fwd(q, k, v, scale, causal, block_q, block_k, kv_len,
                   interpret, dq_blocks=None, dkv_blocks=None,
                   bwd_impl="split", partials_f32=False):
    b, lq, h, d = q.shape
    o3, lse3 = _flash_fwd(
        _to3(q), _to3(k), _to3(v), scale, causal, block_q, block_k, kv_len,
        interpret,
    )
    return _from3(o3, b, h), (q, k, v, o3, lse3)


def _flash_vjp_bwd(scale, causal, block_q, block_k, kv_len, interpret,
                   dq_blocks, dkv_blocks, bwd_impl, partials_f32, res, g):
    q, k, v, o3, lse3 = res
    b, lq, h, d = q.shape
    # The backward tiles independently of the forward; flash_attention
    # computes the tuples (None only through direct _flash calls —
    # fall back to the forward tiling).
    dq_blocks = dq_blocks or (block_q, block_k)
    dkv_blocks = dkv_blocks or (block_q, block_k)
    if bwd_impl == "fused":
        dq3, dk3, dv3 = _flash_bwd_fused(
            _to3(q), _to3(k), _to3(v), o3, lse3, _to3(g.astype(q.dtype)),
            scale, causal, dq_blocks, kv_len, interpret,
            partials_f32=partials_f32,
        )
    else:
        dq3, dk3, dv3 = _flash_bwd(
            _to3(q), _to3(k), _to3(v), o3, lse3, _to3(g.astype(q.dtype)),
            scale, causal, dq_blocks, dkv_blocks, kv_len, interpret,
        )
    return _from3(dq3, b, h), _from3(dk3, b, h), _from3(dv3, b, h)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 1024,
    bwd_block_q: Optional[int] = None,
    bwd_block_k: Optional[int] = None,
    interpret: bool | None = None,
    bwd_impl: str = "fused",
    partials_f32: bool = False,
) -> jax.Array:
    """FlashAttention: ``softmax(QKᵀ·scale)V`` tiled through VMEM.

    Args:
      q, k, v: ``[B, L, H, D]``; any lengths — inputs are zero-padded to
        block multiples and padded key positions are masked in-kernel
        (round 1 required exact multiples).
      bwd_block_q/bwd_block_k: ONE backward tiling (sweep/debug
        override). When left None the backward auto-tiles: the default
        fused kernel takes (1024, 1024) fit to the padded length at
        EVERY length (the r5 composed winner); the split path keeps its
        r4 rules ((1024, 1024) at padded L >= 4096, the forward tiling
        below). Isolated per-kernel sweeps suggested MIXED tilings —
        measured 26% WORSE composed; see BENCH_ATTENTION.md round-4.
      interpret: run the kernels in the Pallas interpreter (CPU testing).
      bwd_impl: "fused" (default, round 5) — single-pass dQ+dK+dV
        kernel with HBM dQ partials, 61-118 TFLOP/s fwdbwd at 1k-16k vs
        the split kernels' 48-97 (BENCH_ATTENTION.md r5); "split" — the
        r4 two-kernel decomposition (still used per ring visit by
        ops/ring_flash.py). PRECISION NOTE for the fused path: each
        (q, kv) grid step emits a partial dQ block at the INPUT dtype, so
        for bf16 models every partial rounds to bf16 before the fp32
        cross-partial sum — a deliberate precision change from the split
        kernels' pure-fp32 dQ accumulation, measured faster at every
        length and loss-neutral in training (BENCH_ATTENTION.md r5).
      partials_f32: keep the fused backward's dQ partials in fp32
        (doubles their HBM traffic; bitwise matches the split kernels'
        dQ accumulation dtype). Ignored by bwd_impl="split", which is
        always fp32. Exposed for precision sweeps and debugging
        suspected dQ rounding (ADVICE r5 #2).

    Default block sizes come from an on-chip sweep (v5e, causal, D=128,
    scripts/bench_attention.py --sweep): (512, 1024) wins at every length
    1k-8k — 41/50 TFLOP/s fwd/fwdbwd at L=1024 (the r2 defaults (256, 512)
    managed 27/41) and 86/90 at L=8192 (was 49/59). Blocks are clamped to
    the sequence length, so short sequences degrade gracefully.
    """
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if interpret is None:
        # Mosaic kernels need the Pallas interpreter on ANY non-TPU
        # backend (a GPU backend would otherwise dispatch Mosaic natively
        # and fail to compile); auto-detect so CPU tests/dryruns run the
        # same call sites unmodified.
        interpret = jax.default_backend() != "tpu"
    lq, lk = q.shape[1], k.shape[1]
    block_q = min(block_q, max(lq, 1))
    block_k = min(block_k, max(lk, 1))
    # padded lengths must be multiples of BOTH the fwd and bwd tilings
    # (the bwd kernels read the same padded residuals); with power-of-two
    # blocks the max is the lcm. Explicit bwd overrides are clamped to the
    # FORWARD-padded length (not the raw one): short sequences then
    # degrade gracefully like the unswept path, while a larger override
    # at block-multiple lengths still rounds the padding up to cover it.
    lq_pad0 = lq + ((-lq) % block_q)
    lk_pad0 = lk + ((-lk) % block_k)
    bq_c = min(bwd_block_q, lq_pad0) if bwd_block_q else block_q
    bk_c = min(bwd_block_k, lk_pad0) if bwd_block_k else block_k
    pq_mult = max(block_q, bq_c)
    pk_mult = max(block_k, bk_c)
    if pq_mult % min(block_q, bq_c) or pk_mult % min(block_k, bk_c):
        raise ValueError(
            f"bwd blocks ({bwd_block_q}, {bwd_block_k}) and fwd blocks "
            f"({block_q}, {block_k}) must divide each other pairwise "
            "(shared zero-padding)"
        )
    pad_q = (-lq) % pq_mult
    pad_k = (-lk) % pk_mult
    lq_pad, lk_pad = lq + pad_q, lk + pad_k

    def _fit(cand: int, n: int) -> int:
        # largest block <= cand that divides the padded length (blocks
        # and padded lengths are powers-of-two multiples of each other)
        b = min(cand, n)
        while n % b:
            b //= 2
        return max(b, 1)

    def _fit_pair(bq_cand, bk_cand):
        # auto-tile, guarded (ADVICE r4 #3): odd caller-chosen forward
        # blocks can make _fit land on a sub-lane-aligned size (e.g. a
        # non-multiple-of-8 block at padded L >= 4096) that fails Mosaic
        # compile — fall back to the forward tiling instead.
        bq_f, bk_f = _fit(bq_cand, lq_pad), _fit(bk_cand, lk_pad)
        for bb in (bq_f, bk_f):
            if bb < 128 and bb % 8:
                return (block_q, block_k)
        return (bq_f, bk_f)

    if bwd_block_q or bwd_block_k:
        dq_blocks = dkv_blocks = (min(bq_c, lq_pad), min(bk_c, lk_pad))
    elif bwd_impl == "fused":
        # r5 composed A/B (same-process, scripts/bench_attention.py): the
        # fused single-pass backward at (1024, 1024) beats the split
        # kernels at EVERY length — 61/83/109/114/118 TFLOP/s fwdbwd at
        # 1k/2k/4k/8k/16k vs split's 48/69/90/92/97. Larger blocks fail
        # Mosaic compile (VMEM); _fit clamps short/odd lengths.
        dq_blocks = dkv_blocks = _fit_pair(1024, 1024)
    elif lk_pad >= 4096:
        # r4 sweep THROUGH the real vjp: (1024, 1024) for both backward
        # kernels is the (marginal) winner at L in {4096, 8192} — 89.8 /
        # 99.1 TFLOP/s fwdbwd vs 89.1 / 97.2 at the shared (512, 1024).
        # NOTE the per-kernel standalone sweep suggested mixed tilings
        # (dKV (512, 2048) "1.77x faster") that do NOT compose end-to-end
        # — (512,1024)/(512,2048) measured 65.5 TFLOP/s, far WORSE;
        # standalone pallas_call timings mislead about the composed
        # pipeline. Composed measurements only.
        dq_blocks = dkv_blocks = _fit_pair(1024, 1024)
    else:
        dq_blocks = dkv_blocks = (block_q, block_k)

    if bwd_impl not in ("split", "fused"):
        raise ValueError(
            f"bwd_impl {bwd_impl!r} must be 'split' (two kernels) or "
            "'fused' (single-pass dQ+dK+dV with HBM dQ partials)"
        )
    if pad_q or pad_k:
        padq = lambda x: jnp.pad(x, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        padk = lambda x: jnp.pad(x, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        out = _flash(
            padq(q), padk(k), padk(v), scale, causal, block_q, block_k, lk,
            interpret, dq_blocks, dkv_blocks, bwd_impl, partials_f32,
        )
        return out[:, :lq]
    return _flash(q, k, v, scale, causal, block_q, block_k, lk, interpret,
                  dq_blocks, dkv_blocks, bwd_impl, partials_f32)
