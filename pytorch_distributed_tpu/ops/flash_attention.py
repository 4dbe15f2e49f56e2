"""FlashAttention forward AND backward as Pallas TPU kernels.

The blockwise kernel (``ops.attention.blockwise_attention``) is the XLA-fused
reference; this is the hand-tiled fast path for the same math, built per the
TPU Pallas playbook (/opt/skills/guides/pallas_guide.md):

Layout: the kernels work on ``[B, L, heads·D]`` ROWS, the layout the
projections on either side produce and consume, so no array is transposed
to ``[B·H, L, D]`` and back. A block is ``g`` heads of a row side by side
(``_heads_a_block``: the ``128 // D`` that fill a 128-lane block, one head
of whole lane tiles, a whole row no wider than a block) and a step takes
them in turn: a head's scores contract the WHOLE block with the other
heads' lanes of the query zeroed (zeros in a contraction are exact, and a
64-deep contraction costs the matrix unit a 128-deep pass anyway), P·V,
dV, dK and dQ come out the block wide and the heads' lanes are put
together with a select or by summing products of masked operands. No
lane slice, no shuffle. ``flash_attention_qkv`` reads q, k and v at their
columns of the ONE ``[B, L, 3·H·D]`` qkv product; ``[B·H, L, D]`` callers
(``ops/ring_flash.py``) are ``heads`` = 1.

Forward (``_fwd_kernel``):
- grid (B, column block, Lq/block_q, Lk/block_k), KV innermost and
  sequential ("arbitrary" dimension semantics — it carries the
  online-softmax recurrence); Q/K/V blocks staged HBM→VMEM by BlockSpec
  index maps;
- the running (m, l) of each head and the block-wide acc live in VMEM
  scratch, persisting across the KV sweep for each Q block; everything
  accumulates in fp32 while inputs can be bf16 feeding the MXU
  (``preferred_element_type=f32``);
- causal masking skips fully-masked KV blocks with ``pl.when`` (no FLOPs
  spent above the diagonal) and applies a multiplicative mask so
  fully-masked rows yield zeros;
- alongside O it emits the row logsumexp (LSE) of every head
  (``[B·heads, Lq, 128]``), which is what makes the one-pass backward
  possible.

Backward (FlashAttention-2 decomposition):
  with P = exp(S - LSE),  Δ_i = Σ_j P_ij (dO V^T)_ij = rowsum(dO ⊙ O):
    dV = P^T dO
    dS = P ⊙ (dO V^T − Δ)·scale
    dQ = dS K
    dK = dS^T Q
  in one kernel (``_bwd_fused_kernel``, the default) or two
  (``_bwd_dq_kernel``: per-Q-block, sweeps KV; ``_bwd_dkv_kernel``:
  per-KV-block, sweeps Q). Δ is taken in the kernel from the dO and O rows
  a step stages; no O(L²) tensor ever exists in HBM and nothing is
  rematerialized through the slow path.

Arbitrary lengths: inputs are zero-padded to block multiples and the
kernels mask padded KEY positions explicitly (padded query rows compute
garbage that is sliced away), so any (Lq, Lk) works.

The public entries take the framework convention ``[B, L, H, D]``
(``flash_attention``) or the packed rows (``flash_attention_qkv``).
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


def _heads_a_block(heads: int, d: int) -> int:
    """How many of a ``[B, L, heads·d]`` row's heads one block of columns
    holds: the ``128 // d`` that fill a 128-lane block where ``d`` is
    narrower and the row a multiple of 128 lanes, one head where ``d``
    is whole lane tiles, every head of a row no wider than 128 lanes (a
    block is then the row; ``heads`` = 1 is the ``[B·H, L, D]`` callers').
    0 where the row cannot be cut so: the caller moves its heads to the
    batch axis."""
    if d % 128 == 0:
        return 1
    if 128 % d == 0 and (heads * d) % 128 == 0:
        return 128 // d
    return heads if heads * d <= 128 else 0


class _Blocks:
    """How the kernels cut operands ``[B, L, heads·d]`` — or, ``packed``,
    the qkv product ``[B, L, 3·heads·d]`` given as q, k AND v — into
    blocks: ``g`` heads (``w`` = g·d columns) a block, ``nb`` blocks a
    row, and the BlockSpecs on a grid (B, column block, ·, ·) whose last
    two axes are (Q, KV) where ``q_major``, (KV, Q) otherwise."""

    def __init__(self, x3, heads, packed, block_q, block_k, q_major=True):
        self.heads = heads
        self.d = x3.shape[-1] // ((3 if packed else 1) * heads)
        self.g = _heads_a_block(heads, self.d)
        self.w = self.g * self.d
        self.nb = heads // self.g
        # the column block at which q's, k's and v's rows begin
        self.cols = (0, self.nb, 2 * self.nb) if packed else (0, 0, 0)
        self.block_q, self.block_k, self.q_major = block_q, block_k, q_major

    def at(self, index):
        """An index map over (b, p, i, j) = (batch, column block, Q block,
        KV block), in the grid's own order of its last two axes."""
        if self.q_major:
            return index
        return lambda b, p, j, i: index(b, p, i, j)

    def q_rows(self, col=0):
        return pl.BlockSpec((1, self.block_q, self.w),
                            self.at(lambda b, p, i, j: (b, i, col + p)))

    def kv_rows(self, col=0):
        return pl.BlockSpec((1, self.block_k, self.w),
                            self.at(lambda b, p, i, j: (b, j, col + p)))

    def qkv(self):
        cq, ck, cv = self.cols
        return [self.q_rows(cq), self.kv_rows(ck), self.kv_rows(cv)]

    def lse_rows(self):
        """The block's heads' rows of ``[B·heads, Lq, 128]``."""
        return pl.BlockSpec(
            (self.g, self.block_q, 128),
            self.at(lambda b, p, i, j: (b * self.nb + p, i, 0)))


def _head_lanes(h: int, g: int, d: int):
    """[1, g·d] mask of head ``h``'s lanes in a block of ``g`` heads;
    None where the block is one head."""
    if g == 1:
        return None
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, g * d), 1)
    return (lane >= h * d) & (lane < (h + 1) * d)


def _only(sel, x):
    """``x`` with the other heads' lanes zeroed: a contraction over the
    whole block is then that head's (zeros are exact)."""
    return x if sel is None else jnp.where(sel, x, jnp.zeros_like(x))


def _visible(q_start, k_start, block_q, block_k, kv_len, causal):
    """[block_q, block_k] mask: real (unpadded) keys, at or before the
    query where causal."""
    k_pos = k_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    mask = k_pos < kv_len  # padded keys contribute nothing
    if causal:
        q_pos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        mask = mask & (k_pos <= q_pos)
    return mask


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, block_q: int, block_k: int, kv_len: int,
    g: int, d: int,
):
    ki = pl.program_id(3)
    n_k = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_start = pl.program_id(2) * block_q
    k_start = ki * block_k

    def _block():
        # Fold the softmax scale into Q: one [block_q, g·D] multiply
        # instead of a [block_q, block_k] one on the logits.
        q = (q_ref[0] * jnp.asarray(scale, q_ref.dtype))  # [block_q, g·D]
        k = k_ref[0]  # [block_k, g·D]
        v = v_ref[0]
        mask = _visible(q_start, k_start, block_q, block_k, kv_len, causal)
        acc = acc_scr[:]
        for h in range(g):  # the block's heads in turn, statistics a head
            sel = _head_lanes(h, g, d)
            s = jax.lax.dot_general(
                _only(sel, q), k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [block_q, block_k]
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_scr[h][:, :1]  # [block_q, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)  # [block_q, block_k]
            p = p * mask  # fully-masked rows stay all-zero (l == 0 → out 0)
            corr = jnp.exp(m_prev - m_new)  # [block_q, 1]
            l_new = l_scr[h][:, :1] * corr + jnp.sum(p, axis=-1,
                                                     keepdims=True)
            # P·V comes out the block wide; the head's lanes are its own
            upd = acc * corr + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc = upd if sel is None else jnp.where(sel, upd, acc)
            m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])
        acc_scr[:] = acc

    if causal:
        # A KV block strictly above the diagonal contributes nothing — skip
        # its FLOPs entirely.
        pl.when(k_start <= q_start + block_q - 1)(_block)
    else:
        _block()

    @pl.when(ki == n_k - 1)
    def _finalize():
        acc = acc_scr[:]
        out = acc
        for h in range(g):
            sel = _head_lanes(h, g, d)
            l = jnp.maximum(l_scr[h][:, :1], 1e-37)
            out_h = acc / l
            out = out_h if sel is None else jnp.where(sel, out_h, out)
            # LSE = m + log l; fully-masked rows get a huge negative (their
            # backward P = exp(s - lse) must still be ~0, not inf).
            lse = jnp.where(
                l_scr[h][:, :1] > 0.0, m_scr[h][:, :1] + jnp.log(l), NEG_INF
            )
            lse_ref[h] = jnp.broadcast_to(lse, lse_ref.shape[1:])
        o_ref[0] = out.astype(o_ref.dtype)


def _compiler_params(interpret):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
    )}


def _flash_fwd(q3, k3, v3, scale, causal, block_q, block_k, kv_len, interpret,
               heads=1, packed=False):
    """``[B, L, heads·D]`` operands → (``[B, Lq, heads·D]`` out,
    ``[B·heads, Lq, 128]`` lse); ``heads`` = 1 is ``[B·H, L, D]``. Packed,
    q3, k3 and v3 are ALL the ``[B, L, 3·heads·D]`` qkv product, read at
    its q, k and v columns."""
    blk = _Blocks(q3, heads, packed, block_q, block_k)
    b, lq = q3.shape[:2]
    lk = k3.shape[1]
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, kv_len=kv_len, g=blk.g, d=blk.d,
    )
    return pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((b, lq, heads * blk.d), q3.dtype),
            jax.ShapeDtypeStruct((b * heads, lq, 128), jnp.float32),
        ],
        grid=(b, blk.nb, lq // block_q, lk // block_k),
        in_specs=blk.qkv(),
        out_specs=[blk.q_rows(), blk.lse_rows()],
        scratch_shapes=[
            pltpu.VMEM((blk.g, block_q, 128), jnp.float32),  # row max m
            pltpu.VMEM((blk.g, block_q, 128), jnp.float32),  # row sum l
            pltpu.VMEM((block_q, blk.w), jnp.float32),  # un-normalized out
        ],
        interpret=interpret,
        name="flash_fwd",
        **_compiler_params(interpret),
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
    'fused'. ``q`` and ``do`` hold ONE head's lanes (``_only``); ``k``
    and ``v`` the whole block."""
    sblk = jax.lax.dot_general(
        q * jnp.asarray(scale, q.dtype), k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [block_q, block_k]
    mask = _visible(q_start, k_start, block_q, block_k, kv_len, causal)
    pblk = jnp.where(mask, jnp.exp(sblk - lse), 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = pblk * (dp - delta) * jnp.asarray(scale, jnp.float32)
    return pblk, ds


def _head_p_ds(refs, g, d, h, **block):
    """Head ``h`` of the block the refs hold: its lane mask, its query
    and cotangent rows (the other heads' lanes zeroed), and its (P, dS).
    Δ = rowsum(dO ⊙ O) over the head's lanes is taken here from the
    [block_q, g·D] rows the step has staged anyway: no [B·H, L, 128]
    array of it is written and read back."""
    q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref = refs
    sel = _head_lanes(h, g, d)
    q, do = _only(sel, q_ref[0]), _only(sel, do_ref[0])
    delta = jnp.sum(
        do.astype(jnp.float32) * o_ref[0].astype(jnp.float32),
        axis=-1, keepdims=True,
    )
    p, ds = _masked_p_ds(q, k_ref[0], v_ref[0], do, lse_ref[h][:, :1],
                         delta, **block)
    return sel, q, do, p, ds


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref, dq_scr,
    *, scale: float, causal: bool, block_q: int, block_k: int, kv_len: int,
    g: int, d: int,
):
    ki = pl.program_id(3)
    n_k = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q_start = pl.program_id(2) * block_q
    k_start = ki * block_k

    head = functools.partial(
        _head_p_ds, (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref), g, d,
        scale=scale, causal=causal, q_start=q_start, k_start=k_start,
        block_q=block_q, block_k=block_k, kv_len=kv_len,
    )

    def _block():
        for h in range(g):
            sel, _q, _do, _p, ds = head(h)
            k = _only(sel, k_ref[0])
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


def _dkv_add(dk_scr, dv_scr, q, do, p, ds):
    """dV += Pᵀ dO and dK += dSᵀ Q, the head's lanes of the block-wide
    accumulators (``q`` and ``do`` are zero in the others)."""
    dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dk_ref, dv_ref,
    dk_scr, dv_scr,
    *, scale: float, causal: bool, block_q: int, block_k: int, kv_len: int,
    g: int, d: int,
):
    qi = pl.program_id(3)
    n_q = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    k_start = pl.program_id(2) * block_k
    q_start = qi * block_q

    head = functools.partial(
        _head_p_ds, (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref), g, d,
        scale=scale, causal=causal, q_start=q_start, k_start=k_start,
        block_q=block_q, block_k=block_k, kv_len=kv_len,
    )

    def _block():
        for h in range(g):
            _sel, q, do, p, ds = head(h)
            _dkv_add(dk_scr, dv_scr, q, do, p, ds)

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
    q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
    dqp_ref, dk_ref, dv_ref, dk_scr, dv_scr,
    *, scale: float, causal: bool, block_q: int, block_k: int, kv_len: int,
    g: int, d: int,
):
    """Single-pass backward: grid (B, column block, KV, Q) with Q
    innermost. Computes S and dP ONCE per (q, kv) block and head and
    feeds all three products — where the split kernels spend 7 big
    matmuls (dQ pass: S, dP, dQ; dKV pass: S, dV, dP, dK) and read
    Q/K/V/dO twice, this spends the mathematical minimum 5 and reads
    once. dK/dV accumulate in VMEM across the inner Q sweep; dQ's
    cross-KV accumulation cannot live in VMEM in this grid order
    (non-consecutive revisits), so each (kv, q) step emits a PARTIAL dQ
    block to HBM (input dtype — see ``_flash_bwd_fused``) and one XLA
    reduction over the KV axis finishes it outside (traffic ≈
    n_k · |dQ|)."""
    qi = pl.program_id(3)
    n_q = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    k_start = pl.program_id(2) * block_k
    q_start = qi * block_q

    head = functools.partial(
        _head_p_ds, (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref), g, d,
        scale=scale, causal=causal, q_start=q_start, k_start=k_start,
        block_q=block_q, block_k=block_k, kv_len=kv_len,
    )

    def _block():
        dq = None
        for h in range(g):
            sel, q, do, p, ds = head(h)
            _dkv_add(dk_scr, dv_scr, q, do, p, ds)
            dq_h = jax.lax.dot_general(
                ds.astype(q.dtype), _only(sel, k_ref[0]),
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            )
            dq = dq_h if dq is None else dq + dq_h
        dqp_ref[0, 0] = dq.astype(dqp_ref.dtype)

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
                     kv_len, interpret, partials_f32=False, heads=1,
                     packed=False):
    """One fused kernel + one XLA reduction. ``blocks`` = (block_q,
    block_k) shared by the whole pass. Operands as ``_flash_fwd``'s;
    returns dq, dk, dv ``[B, L, heads·D]``."""
    bq, bk = blocks
    blk = _Blocks(q3, heads, packed, bq, bk, q_major=False)
    b, lq = q3.shape[:2]
    lk = k3.shape[1]
    n_k = lk // bk
    # dQ partials at the INPUT dtype (default): halves the partial HBM
    # traffic. The cross-partial sum always accumulates in fp32;
    # ``partials_f32`` remains as a sweep/precision knob (each bf16
    # partial rounds before the sum).
    p_dtype = jnp.float32 if partials_f32 else q3.dtype
    dqp3, dk3, dv3 = pl.pallas_call(
        functools.partial(_bwd_fused_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, kv_len=kv_len,
                          g=blk.g, d=blk.d),
        out_shape=[
            jax.ShapeDtypeStruct((b, n_k, lq, heads * blk.d), p_dtype),
            jax.ShapeDtypeStruct((b, lk, heads * blk.d), k3.dtype),
            jax.ShapeDtypeStruct((b, lk, heads * blk.d), v3.dtype),
        ],
        grid=(b, blk.nb, n_k, lq // bq),
        in_specs=blk.qkv() + [blk.q_rows(), blk.q_rows(), blk.lse_rows()],
        out_specs=[
            pl.BlockSpec((1, 1, bq, blk.w),
                         blk.at(lambda b, p, i, j: (b, j, i, p))),
            blk.kv_rows(),
            blk.kv_rows(),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, blk.w), jnp.float32),
            pltpu.VMEM((bk, blk.w), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_fused",
        **_compiler_params(interpret),
    )(q3, k3, v3, do3, o3, lse3)
    dq3 = jnp.sum(dqp3.astype(jnp.float32), axis=1).astype(q3.dtype)
    return dq3, dk3, dv3


def _flash_bwd(q3, k3, v3, o3, lse3, do3, scale, causal, dq_blocks,
               dkv_blocks, kv_len, interpret, heads=1, packed=False):
    """Backward kernels with INDEPENDENTLY SPECIFIABLE tilings:
    ``dq_blocks`` / ``dkv_blocks`` are (block_q, block_k) for the dQ and
    dK/dV kernels. NOTE: isolated per-kernel sweeps suggested mixed
    tilings, but those do NOT compose — the composed A/B through the
    real vjp measured the 'per-kernel-optimal' mix 26% WORSE (on the
    runtime this code was written on); ``flash_attention`` therefore
    passes the SAME tuple to both. The two parameters exist for sweeps,
    not because mixed defaults won."""
    b, lq = q3.shape[:2]
    lk = k3.shape[1]

    bq, bk = dq_blocks
    blk = _Blocks(q3, heads, packed, bq, bk)
    rows = lambda: blk.qkv() + [blk.q_rows(), blk.q_rows(), blk.lse_rows()]
    dq3 = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, kv_len=kv_len,
                          g=blk.g, d=blk.d),
        out_shape=jax.ShapeDtypeStruct((b, lq, heads * blk.d), q3.dtype),
        grid=(b, blk.nb, lq // bq, lk // bk),
        in_specs=rows(),
        out_specs=blk.q_rows(),
        scratch_shapes=[pltpu.VMEM((bq, blk.w), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
        **_compiler_params(interpret),
    )(q3, k3, v3, do3, o3, lse3)

    # dK/dV: grid puts the KV block third, Q innermost (the recurrence).
    bq, bk = dkv_blocks
    blk = _Blocks(q3, heads, packed, bq, bk, q_major=False)
    dk3, dv3 = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, kv_len=kv_len,
                          g=blk.g, d=blk.d),
        out_shape=[
            jax.ShapeDtypeStruct((b, lk, heads * blk.d), k3.dtype),
            jax.ShapeDtypeStruct((b, lk, heads * blk.d), v3.dtype),
        ],
        grid=(b, blk.nb, lk // bk, lq // bq),
        in_specs=rows(),
        out_specs=[blk.kv_rows(), blk.kv_rows()],
        scratch_shapes=[
            pltpu.VMEM((bk, blk.w), jnp.float32),
            pltpu.VMEM((bk, blk.w), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
        **_compiler_params(interpret),
    )(q3, k3, v3, do3, o3, lse3)
    return dq3, dk3, dv3


@functools.partial(jax.custom_vjp, nondiff_argnums=tuple(range(1, 11)))
def _flash(ops, heads, scale, causal, block_q, block_k, kv_len, interpret,
           bwd_blocks, bwd_impl, partials_f32):
    """``ops`` is (q, k, v), each ``[B, L, heads·D]``, or the one packed
    ``(qkv,)`` ``[B, L, 3·heads·D]``; out ``[B, Lq, heads·D]``."""
    out, _ = _flash_vjp_fwd(
        ops, heads, scale, causal, block_q, block_k, kv_len, interpret,
        bwd_blocks, bwd_impl, partials_f32,
    )
    return out


def _to3(x):
    b, l, h, d = x.shape
    return jnp.moveaxis(x, 2, 1).reshape(b * h, l, d)


def _from3(x3, b, h):
    bh, l, d = x3.shape
    return jnp.moveaxis(x3.reshape(b, h, l, d), 1, 2)


def _flash_vjp_fwd(ops, heads, scale, causal, block_q, block_k, kv_len,
                   interpret, bwd_blocks, bwd_impl, partials_f32):
    packed = len(ops) == 1
    o3, lse3 = _flash_fwd(
        *(ops * 3 if packed else ops), scale, causal, block_q, block_k,
        kv_len, interpret, heads=heads, packed=packed,
    )
    return o3, (ops, o3, lse3)


def _flash_vjp_bwd(heads, scale, causal, block_q, block_k, kv_len, interpret,
                   bwd_blocks, bwd_impl, partials_f32, res, g):
    ops, o3, lse3 = res
    packed = len(ops) == 1
    q3, k3, v3 = ops * 3 if packed else ops
    do3 = g.astype(q3.dtype)
    if bwd_impl == "fused":
        grads = _flash_bwd_fused(
            q3, k3, v3, o3, lse3, do3, scale, causal, bwd_blocks, kv_len,
            interpret, partials_f32=partials_f32, heads=heads, packed=packed,
        )
    else:
        grads = _flash_bwd(
            q3, k3, v3, o3, lse3, do3, scale, causal, bwd_blocks,
            bwd_blocks, kv_len, interpret, heads=heads, packed=packed,
        )
    # the packed operand's cotangent is ONE array: dq, dk and dv side by
    # side, as the qkv product's columns lie
    return ((jnp.concatenate(grads, axis=-1),) if packed else tuple(grads),)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    bwd_block_q: Optional[int] = None,
    bwd_block_k: Optional[int] = None,
    interpret: bool | None = None,
    bwd_impl: str = "fused",
    partials_f32: bool = False,
) -> jax.Array:
    """FlashAttention: ``softmax(QKᵀ·scale)V`` tiled through VMEM.

    Args:
      q, k, v: ``[B, L, H, D]``; any lengths — inputs are zero-padded to
        block multiples and padded key positions are masked in-kernel.
        The kernels read them as ``[B, L, H·D]`` rows (a view: no
        transpose) wherever the row cuts into lane blocks
        (``_heads_a_block``), else with the heads moved to the batch axis.
      block_q/block_k, bwd_block_q/bwd_block_k: the forward's tile and
        ONE backward tiling (sweep/debug overrides). Left None, ``_tiles``
        chooses both from the lengths and D, fit to the padded lengths.
      interpret: run the kernels in the Pallas interpreter (CPU testing).
      bwd_impl: "fused" (default) — single-pass dQ+dK+dV kernel with HBM
        dQ partials; "split" — the two-kernel decomposition.
        PRECISION NOTE for the fused path: each (q, kv) grid step emits a
        partial dQ block at the INPUT dtype, so for bf16 models every
        partial rounds to bf16 before the fp32 cross-partial sum — a
        deliberate precision change from the split kernels' pure-fp32 dQ
        accumulation.
      partials_f32: keep the fused backward's dQ partials in fp32
        (doubles their HBM traffic; bitwise matches the split kernels'
        dQ accumulation dtype). Ignored by bwd_impl="split", which is
        always fp32. Exposed for precision sweeps and debugging
        suspected dQ rounding.

    Blocks are clamped to the sequence length, so short sequences degrade
    gracefully.
    """
    b, lq, h, d = q.shape
    if _heads_a_block(h, d):
        heads = h
        ops = tuple(x.reshape(x.shape[0], x.shape[1], h * d)
                    for x in (q, k, v))
    else:
        heads, ops = 1, (_to3(q), _to3(k), _to3(v))
    out = _attend(
        ops, heads, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, bwd_block_q=bwd_block_q, bwd_block_k=bwd_block_k,
        interpret=interpret, bwd_impl=bwd_impl, partials_f32=partials_f32,
    )
    return out.reshape(q.shape) if heads == h else _from3(out, b, h)


def flash_attention_qkv(qkv: jax.Array, heads: int, *, causal: bool = False,
                        **kwargs) -> jax.Array:
    """Self-attention straight off the fused qkv product: ``qkv`` is
    ``[B, L, 3·H·D]`` (q's ``H·D`` columns, then k's, then v's, each head
    by head), the result ``[B, L, H·D]``, the layout the output projection
    takes. Where ``H·D`` cuts into 128-lane blocks the kernels read q, k
    and v at their columns of the ONE array and the backward returns one
    cotangent for it; a narrower row is sliced into three first. Keywords
    as ``flash_attention``'s."""
    b, l, hd = qkv.shape[0], qkv.shape[1], qkv.shape[2] // 3
    d = hd // heads
    g = _heads_a_block(heads, d)
    if g and (g * d) % 128 == 0:
        return _attend((qkv,), heads, causal=causal, **kwargs)
    q, k, v = (x.reshape(b, l, heads, d) for x in _split_qkv(qkv))
    return flash_attention(q, k, v, causal=causal, **kwargs).reshape(b, l, hd)


def _split_qkv(qkv):
    hd = qkv.shape[-1] // 3
    return tuple(qkv[..., i * hd:(i + 1) * hd] for i in range(3))


def _tiles(lq: int, lk: int, g: int):
    """The forward's and the backward's (block_q, block_k) where the
    caller names none, by the lengths and the heads a block (which follow
    from D). Chosen on the chip through the whole step program — a kernel
    timed alone misleads about the step it runs in — at the one shape a
    cell of the benchmark runs, gpt2-medium.pretrain's B = 16, L = 1,024,
    D = 64, two heads a block (PERF.md section 6, PR 43). There the
    forward takes the sequence as ONE (1,024, 1,024) tile: a six-layer
    step is 2.1 ms shorter than under (512, 1,024), the masked half of
    the square notwithstanding (smaller tiles that skip more of it are
    slower still: (512, 512) +0.4 ms a layer, (256, 256) +1.8). The fused
    backward takes (512, 512), 0.4-0.5 ms on six layers under
    (512, 1,024); (1,024, 1,024), which the kernel had, no longer fits 16
    MB of scoped VMEM beside two heads' statistics, and with the limit
    raised it is no faster. Both together: the cell's step 330.7 -> 322.4
    ms. A longer sequence of narrow heads keeps (1,024, 1,024) only where
    the grid is one step, so it takes (512, 1,024) both ways, the largest
    that compiles at 2k-8k (not timed); one head a block (D a multiple of
    128, ``[B·H, L, D]`` callers) keeps the tiles the kernels had, not
    timed on this runtime either."""
    if g == 1:
        return (512, 1024), (1024, 1024)
    if max(lq, lk) <= 1024 and lq % 512 == 0 and lk % 512 == 0:
        return (1024, 1024), (512, 512)
    return (512, 1024), (512, 1024)


def _attend(ops, heads, *, causal=False, scale=None, block_q=None,
            block_k=None, bwd_block_q=None, bwd_block_k=None, interpret=None,
            bwd_impl="fused", partials_f32=False):
    """Tiles, padding and the kernels for ``ops`` = (q, k, v)
    ``[B, L, heads·D]`` or the packed ``(qkv,)``: see ``_flash``."""
    if bwd_impl not in ("split", "fused"):
        raise ValueError(
            f"bwd_impl {bwd_impl!r} must be 'split' (two kernels) or "
            "'fused' (single-pass dQ+dK+dV with HBM dQ partials)"
        )
    d = ops[0].shape[-1] // ((3 if len(ops) == 1 else 1) * heads)
    scale = scale if scale is not None else d ** -0.5
    if interpret is None:
        # Mosaic kernels need the Pallas interpreter on ANY non-TPU
        # backend (a GPU backend would otherwise dispatch Mosaic natively
        # and fail to compile); auto-detect so CPU tests/dryruns run the
        # same call sites unmodified.
        interpret = jax.default_backend() != "tpu"
    lq, lk = ops[0].shape[1], ops[-1].shape[1]
    g = _heads_a_block(heads, d)
    (fwd_q, fwd_k), bwd_tile = _tiles(lq, lk, g)
    block_q = min(block_q or fwd_q, max(lq, 1))
    block_k = min(block_k or fwd_k, max(lk, 1))
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
        # auto-tile, guarded: odd caller-chosen forward blocks can make
        # _fit land on a sub-lane-aligned size (e.g. a
        # non-multiple-of-8 block at padded L >= 4096) that fails Mosaic
        # compile — fall back to the forward tiling instead.
        bq_f, bk_f = _fit(bq_cand, lq_pad), _fit(bk_cand, lk_pad)
        for bb in (bq_f, bk_f):
            if bb < 128 and bb % 8:
                return (block_q, block_k)
        return (bq_f, bk_f)

    if bwd_block_q or bwd_block_k:
        bwd_blocks = (min(bq_c, lq_pad), min(bk_c, lk_pad))
    elif bwd_impl == "fused" or g > 1 or lk_pad >= 4096:
        bwd_blocks = _fit_pair(*bwd_tile)  # _fit clamps short/odd lengths
    else:  # one head a block, split kernels, short: the forward's tiles
        bwd_blocks = (block_q, block_k)

    if len(ops) == 1 and pad_q != pad_k:
        ops = _split_qkv(ops[0])  # q and k, v pad to different lengths
    if pad_q or pad_k:
        pad = lambda x, n: jnp.pad(x, ((0, 0), (0, n), (0, 0)))
        ops = ((pad(ops[0], pad_q),) if len(ops) == 1 else
               (pad(ops[0], pad_q), pad(ops[1], pad_k), pad(ops[2], pad_k)))
    out = _flash(ops, heads, scale, causal, block_q, block_k, lk, interpret,
                 bwd_blocks, bwd_impl, partials_f32)
    return out[:, :lq] if pad_q else out
