"""Fused paged-attention Pallas kernel: flash-decode over a block-pooled
KV cache, reading the block tables directly from SMEM.

This is the ``gather_impl="pallas"`` spelling of
``ops.attention.paged_attention`` (the serving read path), and unnamed
the read of a decode tick on a TPU
(``ops.attention.default_gather_impl``). The dense
spelling gathers every request's block chain back into a logical
``[B, W·block_len, H_kv, D]`` sequence with ``jnp.take`` — materializing
the full gathered KV in HBM on every decode tick, the exact cost
PagedAttention (Kwon et al., SOSP 2023 — PAPERS.md) exists to avoid.
Here the gather never materializes: the block table rides in as a
scalar-prefetch operand (SMEM), and each KV block's BlockSpec *index
map* resolves ``tables[b, j]`` — so the pipeline DMAs pool blocks
HBM→VMEM in chain order directly, touching only the chain's blocks.

Structure (per the in-tree FlashAttention kernel,
``ops/flash_attention.py``, and the TPU Pallas playbook
``/opt/skills/guides/pallas_guide.md``):

- grid ``(B, W)`` with the block-chain sweep innermost and sequential
  ("arbitrary" semantics — it carries the online-softmax recurrence);
  the running (m, l, acc) state lives in VMEM scratch, persisting across
  the chain for each batch row, one slab per narrow head;
- every block's last two dims equal its array's, the one block shape
  Mosaic's tiling rule accepts at H_kv=12, D=64 (the interpreter does
  not check it — every shape was refused on the chip until PR 21): a
  staged K/V block is the whole ``[block_len, H_kv·D]`` pool block and
  a static loop over narrow heads takes each head's D lanes,
  ``k_ref[0, :, h·D:(h+1)·D]``; scale blocks are the block's whole
  ``[block_len, H_kv]`` sibling; positions
  ride as a ``[B, r_pad, 1]`` column and each row's query frontier as a
  second scalar-prefetch operand;
- GQA is folded into the row dimension: queries regroup to
  ``[B, H_kv, G·C, D]`` so each narrow head's whole query group shares
  one staged KV block — the widened K/V never exists, mirroring the
  dense spelling's grouped einsum. ``C == 1`` (decode tick) and
  ``C == chunk`` (chunked prefill) are the same kernel at different row
  counts;
- causal/frontier masking ``k_pos <= q_position`` per row; table
  entries past a request's allocation point at the trash block, whose
  logical positions exceed every live query position, so they mask out
  exactly like the dense spelling. Blocks entirely past the batch row's
  query frontier are skipped with ``pl.when`` (no FLOPs, no dequant);
- softmax statistics in fp32 regardless of pool/compute dtype;
- quantized pools (int8 or fp8) dequantize INSIDE the kernel: per-
  (block, slot, head) scale siblings (``serving.kv_pool.quantize_kv``)
  ride the same index maps as their pool, so the f32 K/V rows exist
  only in VMEM, block by block — HBM holds 1-byte values + scales (the
  2D/(D+4) int8 / 2D/(D+1) fp8 pool-capacity win). fp8 scale siblings
  are int8 power-of-two exponents: the in-VMEM multiplier is ``2**e``
  (exact), so the fp8 cast is the whole error budget;
- flash-decoding (round 20; FlashAttention-2's work partitioning,
  PAPERS.md §2, applied to decode): ``split_s`` > 1 splits the chain
  sweep across S grid workers, each owning ``ceil(W/S)`` chain blocks
  with its own (m, l, acc) VMEM partials, and a second-stage cross-
  worker log-sum-exp merge (fp32, outside the kernel) combines them —
  one long-context request (W large, B small) fills the chip instead
  of serializing on the innermost grid axis. ``split_s=None``
  auto-enables via ``auto_split_s`` when W/B crosses the threshold on a
  device of more than one core (a v5e's one core runs the workers in
  turn: 7-29% slower than the unsplit sweep at the served chunk
  shapes); ``pl.when`` frontier skipping applies per worker unchanged;
- the write side has a twin: ``paged_quantize_scatter`` computes
  per-row-per-head scales and the quantized rows in one kernel and
  places them with the in-place ``.at[rows].set`` the raw pools use (a
  single row of a row-major leaf is not a block Mosaic accepts),
  sharing ``serving.kv_pool.quantize_rows`` with the jnp spelling, so
  in the interpreter the two are bit-equivalent by construction (on
  the v5e: int8 and fp8 e4m3 bit-equal, e5m2 not — CHANGES.md PR 21);
- ``interpret=None`` auto-detects non-TPU backends and runs the Pallas
  interpreter, so CPU tier-1 executes the same call sites unmodified
  (the ``flash_attention`` convention).

Shapes follow the framework convention: q ``[B, C, H, D]``, pools
``[n_blocks, block_len, H_kv·D]`` (``serving.kv_pool.pool_leaf_shape``:
the leaf the chip keeps row-major, so a pool block is one contiguous
DMA and no copy of the pool enters the program), tables ``[B, W]``,
positions ``[B, C]``.
"""
# jaxlint: disable-file=precision-cast -- the kernel's softmax state (m, l, acc) is fp32 by the attention-path contract and int8 pool blocks dequantize to fp32 in VMEM; every cast here feeds that fp32 recurrence

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pytorch_distributed_tpu.ops.attention import NEG_INF, pool_heads

#: flash-decoding auto policy (``split_s=None``): split when one batch
#: row's chain is at least this many blocks per batch row — the shape
#: where the W grid axis serializes a mostly-idle chip.
SPLIT_THRESHOLD = 8
#: auto policy's worker-count cap (forced ``split_s=`` may exceed it)
MAX_SPLIT = 8


def device_cores() -> int:
    """TensorCores behind one device of the default backend (a TPU
    device's ``num_cores``: 1 on a v5e, 2 on a megacore chip); 1 where
    the backend does not say (the CPU)."""
    return int(getattr(jax.devices()[0], "num_cores", None) or 1)


def auto_split_s(w: int, b: int, *, threshold: int = SPLIT_THRESHOLD,
                 max_split: int = MAX_SPLIT,
                 cores: Optional[int] = None) -> int:
    """Flash-decoding worker count for a ``[B, W]`` block table: 1 (no
    split) on a device of one core, whose grid workers run one after
    another so the merge is only extra work (``cores=None`` asks
    ``device_cores``), and until ``W / B >= threshold`` — few long
    chains is the shape where the sequential chain sweep leaves a
    second core idle — then ``min(max_split, W)`` so every worker owns
    at least one block. Static shapes in, static count out: the
    decision is compiled into the program, and the serving registry's
    fingerprint carries a decode tick's count."""
    if cores is None:
        cores = device_cores()
    if cores < 2 or w // max(b, 1) < threshold:
        return 1
    return min(max_split, w)


def _attend_block(q_ref, qpos, k_ref, v_ref, ks_ref, vs_ref,
                  m_scr, l_scr, acc_scr, *, scale, k_start, h_kv, d,
                  quantized, fp8_scales):
    """One chain block's online-softmax update for every narrow head —
    the shared inner body of the single-worker and split-S kernels (one
    spelling, so the split path cannot drift from the sweep it
    partitions). The staged K/V block spans all of ``H_kv`` (the only
    pool block shape Mosaic's tiling rule accepts); the static head
    loop reads each head's ``[block_len, D]`` lanes out of it."""
    if quantized:
        # dequantize THIS block only, in VMEM: per-(slot, head) scale
        # siblings gathered by the same table-driven index map. fp8
        # pools carry int8 exponents — multiplier 2**e, exact in fp32
        # (kv_pool.scale_factors spelling).
        ks_all = ks_ref[0].astype(jnp.float32)  # [block_len, H_kv]
        vs_all = vs_ref[0].astype(jnp.float32)
        if fp8_scales:
            ks_all = jnp.exp2(ks_all)
            vs_all = jnp.exp2(vs_all)
    for h in range(h_kv):
        q = q_ref[0, h]  # [R, D]
        k = k_ref[0, :, h * d:(h + 1) * d]  # [block_len, D]
        v = v_ref[0, :, h * d:(h + 1) * d]
        if quantized:
            k = k.astype(jnp.float32) * ks_all[:, h:h + 1]
            v = v.astype(jnp.float32) * vs_all[:, h:h + 1]
            q = q.astype(jnp.float32)
        # fp32 logits on the MXU from the operands as stored, then the
        # softmax scale on the [R, block_len] logits in fp32: scaling Q
        # in its own dtype rounds it wherever the scale is no power of
        # two (D=128), which the dense spelling's fp32 scale never did.
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [R, block_len]
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # Frontier mask: key position j visible iff j <= the row's query
        # position. Trash-table entries (unallocated tail) carry logical
        # positions past every live frontier → fully masked, exactly the
        # dense spelling's argument. Padding rows (qpos == -1) mask
        # everything → l stays 0 → zeros out, sliced away by the caller.
        mask = k_pos <= qpos
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[h, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # fully-masked rows stay all-zero (l == 0 → out 0)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[h] = jnp.broadcast_to(
            l_scr[h, :, :1] * corr + jnp.sum(p, axis=-1, keepdims=True),
            l_scr.shape[1:],
        )
        # P rides the MXU in the pool's dtype: fp32 operands here double
        # a decode step's time on a v5e (1.92 against 1.01 ms a layer at
        # 64 slots, PERF.md section 6, PR 28)
        acc_scr[h] = acc_scr[h] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])


def _paged_kernel(
    tables_ref,  # scalar-prefetch [B, W] int32 (SMEM)
    front_ref,  # scalar-prefetch [B] int32: each row's query frontier
    q_ref, qpos_ref, k_ref, v_ref,  # + (ks_ref, vs_ref) when quantized
    *refs,
    scale: float, block_len: int, h_kv: int, d: int, quantized: bool,
    fp8_scales: bool, w: int, wc: int, split: bool,
):
    """Grid ``(B, S, ceil(W/S))``: worker s sweeps chain blocks
    ``[s*wc, min((s+1)*wc, W))`` with its own (m, l, acc) state. The
    single-worker sweep (``split=False``, S == 1) normalizes in place;
    flash-decoding workers (``split=True``) emit their partials UN-
    normalized — the caller's fp32 log-sum-exp merge combines them. One
    kernel, so the split path cannot drift from the sweep it partitions.
    Past-end grid steps of a ceil split clamp their index map to a real
    block and are skipped by the ``j < W`` guard."""
    del tables_ref  # consumed by the index maps
    ks_ref, vs_ref = refs[:2] if quantized else (None, None)
    *out_refs, m_scr, l_scr, acc_scr = refs[2:] if quantized else refs
    jj = pl.program_id(2)

    @pl.when(jj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    j = pl.program_id(1) * wc + jj  # logical chain index of this step
    k_start = j * block_len

    # A chain block entirely past this batch row's query frontier
    # contributes nothing — skip its FLOPs (and its dequant) entirely.
    @pl.when((j < w) & (k_start <= front_ref[pl.program_id(0)]))
    def _block():
        _attend_block(q_ref, qpos_ref[0], k_ref, v_ref, ks_ref, vs_ref,
                      m_scr, l_scr, acc_scr, scale=scale, k_start=k_start,
                      h_kv=h_kv, d=d, quantized=quantized,
                      fp8_scales=fp8_scales)

    @pl.when(jj == wc - 1)
    def _finalize():
        if split:
            o_ref, m_ref, l_ref = out_refs
            o_ref[0, 0] = acc_scr[...]
            m_ref[0, 0] = m_scr[...]
            l_ref[0, 0] = l_scr[...]
        else:
            (o_ref,) = out_refs
            l = jnp.maximum(l_scr[:, :, :1], 1e-37)
            o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_flash_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    q_positions: jax.Array,
    *,
    scale: Optional[float] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    split_s: Optional[int] = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused block-gather attention: decode/chunk queries against a
    block-pooled KV cache, no materialized gather.

    Args:
      q: ``[B, C, H, D]`` — C == 1 for a decode tick, C == chunk for
        chunked prefill.
      k_pool, v_pool: ``[n_blocks, block_len, H_kv·D]`` pooled cache
        (``H_kv <= H``, GQA); float dtypes, or int8/fp8 with
        ``k_scale``/``v_scale`` set.
      block_tables: ``[B, W]`` int32 — request b's logical positions
        ``[w·block_len, (w+1)·block_len)`` live in pool block
        ``block_tables[b, w]``.
      q_positions: ``[B, C]`` int32 absolute positions; key position j
        is visible to query i iff ``j <= q_positions[i]``.
      k_scale, v_scale: ``[n_blocks, block_len, H_kv]`` scale siblings
        for quantized pools (``serving.kv_pool.quantize_kv`` layout:
        fp32 multipliers for int8 pools, int8 power-of-two exponents
        for fp8 pools); None for float pools.
      split_s: flash-decoding worker count for the chain sweep. None
        auto-enables (``auto_split_s``: split when W/B crosses the
        threshold and the device has a second core), 1 forces the
        single-worker sweep, S > 1 splits the chain over S workers with un-normalized (m, l, acc) partials
        and a second-stage fp32 log-sum-exp merge. The combine is a
        different (but fp32) reduction order than the single sweep, so
        parity is bounded (≤ 1e-3 on fp32 logits), not bit-equal.
      interpret: force the Pallas interpreter; None auto-detects
        (interpreter on any non-TPU backend, like ``flash_attention``).

    Returns ``[B, C, H, D]`` in q's dtype; softmax statistics fp32.
    """
    b, w, d = q.shape[0], block_tables.shape[1], q.shape[3]
    if interpret is None:
        # Mosaic compiles only on TPU; every other backend runs the
        # interpreter so CPU tier-1 executes this exact call site.
        interpret = jax.default_backend() != "tpu"
    if split_s is not None and split_s < 1:
        raise ValueError(f"split_s must be >= 1, got {split_s}")
    s_workers = split_s if split_s is not None else auto_split_s(w, b)
    # What the backend and the device decide is resolved out here and
    # rides in as static arguments: the traced function is then keyed by
    # shapes and these alone, so the layers of a program share ONE trace
    # and ONE lowered function (a program of 24 layers otherwise traces
    # and lowers the kernel 24 times).
    return _paged_flash(
        q, k_pool, v_pool, block_tables, q_positions, k_scale, v_scale,
        scale=float(scale if scale is not None else d ** -0.5),
        s_workers=min(s_workers, w),  # every worker owns >= 1 chain block
        interpret=bool(interpret),
    )


@functools.partial(jax.jit,
                   static_argnames=("scale", "s_workers", "interpret"))
def _paged_flash(q, k_pool, v_pool, block_tables, q_positions, k_scale,
                 v_scale, *, scale: float, s_workers: int,
                 interpret: bool):
    """``paged_flash_attention`` with everything static decided (a
    pool that does not fit its queries or scales raises while tracing)."""
    from pytorch_distributed_tpu.serving.kv_pool import is_quantized_pool

    b, c, h, d = q.shape
    block_len, h_kv = pool_heads(k_pool, h, d)
    quantized = is_quantized_pool(k_pool.dtype)
    if quantized != (k_scale is not None):
        raise ValueError(
            "quantized (int8/fp8) pools need k_scale/v_scale and float "
            f"pools must not pass them (pool {k_pool.dtype}, k_scale "
            f"{'set' if k_scale is not None else 'None'})"
        )
    # fp8 pools carry int8 EXPONENT scale siblings (dequant 2**e); int8
    # pools carry fp32 multipliers — the scale dtype picks the spelling
    fp8_scales = bool(
        k_scale is not None and k_scale.dtype == jnp.dtype(jnp.int8)
    )
    group = h // h_kv
    w = block_tables.shape[1]
    split = s_workers > 1
    wc = -(-w // s_workers)  # chain blocks per worker (ceil split)

    # GQA fold: query head h = kv·group + g reads narrow head kv, so the
    # per-narrow-head row block is its whole query group × chunk. Rows
    # pad to a sublane multiple; padding rows carry position -1 (every
    # key masked → zero rows, sliced away below).
    r = group * c
    r_pad = -(-r // 8) * 8
    q4 = jnp.moveaxis(q.reshape(b, c, h_kv, group, d), 1, 3)  # [B,Hkv,G,C,D]
    q4 = q4.reshape(b, h_kv, r, d)
    q_positions = q_positions.astype(jnp.int32)
    qpos = jnp.broadcast_to(
        q_positions[:, None, :], (b, group, c)
    ).reshape(b, r)
    if r_pad != r:
        q4 = jnp.pad(q4, ((0, 0), (0, 0), (0, r_pad - r), (0, 0)))
        qpos = jnp.pad(qpos, ((0, 0), (0, r_pad - r)), constant_values=-1)

    # Every block's last two dims equal its array's (Mosaic's tiling
    # rule; the interpreter does not check it): positions ride as a
    # [B, r_pad, 1] column, pool blocks span all of H_kv, scale blocks
    # are the pool block's whole [block_len, H_kv] sibling. Index maps
    # take the grid position (b, s, j) and the two scalar-prefetch refs.
    def pool_block(b, s, j, tables, front):
        # the fused gather: the block table entry IS the index map —
        # the pipeline DMAs pool block tables[b, chain index] straight
        # into VMEM, no gathered copy in HBM. Grid steps past the real
        # chain (ceil-split tail) clamp to its last block; the kernel's
        # ``j < w`` guard keeps them out of the statistics.
        return tables[b, jnp.minimum(s * wc + j, w - 1)]

    row_spec = pl.BlockSpec((1, h_kv, r_pad, d),
                            lambda b, s, j, *_: (b, 0, 0, 0))
    pool_spec = pl.BlockSpec((1, block_len, h_kv * d),
                             lambda *a: (pool_block(*a), 0, 0))
    in_specs = [
        row_spec,
        pl.BlockSpec((1, r_pad, 1), lambda b, s, j, *_: (b, 0, 0)),
        pool_spec, pool_spec,
    ]
    operands = [q4, qpos[:, :, None], k_pool, v_pool]
    if quantized:
        in_specs += [pl.BlockSpec((1, block_len, h_kv),
                                  lambda *a: (pool_block(*a), 0, 0))] * 2
        operands += [k_scale, v_scale]
    if split:
        # each worker's un-normalized (acc, m, l), merged below
        parts = [(h_kv, r_pad, d), (h_kv, r_pad, 128), (h_kv, r_pad, 128)]
        out_specs = [
            pl.BlockSpec((1, 1) + p, lambda b, s, j, *_: (b, s, 0, 0, 0))
            for p in parts
        ]
        out_shape = [jax.ShapeDtypeStruct((b, s_workers) + p, jnp.float32)
                     for p in parts]
    else:
        out_specs = row_spec
        out_shape = jax.ShapeDtypeStruct((b, h_kv, r_pad, d), q.dtype)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )
    out = pl.pallas_call(
        functools.partial(
            _paged_kernel, scale=scale, block_len=block_len, h_kv=h_kv,
            d=d, quantized=bool(quantized), fp8_scales=fp8_scales, w=w,
            wc=wc, split=split,
        ),
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, s_workers, wc),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((h_kv, r_pad, 128), jnp.float32),  # row max m
                pltpu.VMEM((h_kv, r_pad, 128), jnp.float32),  # row sum l
                pltpu.VMEM((h_kv, r_pad, d), jnp.float32),  # un-normalized
            ],
        ),
        interpret=interpret,
        name="paged_decode_attn",
        **kwargs,
    )(block_tables.astype(jnp.int32), jnp.max(q_positions, axis=1),
      *operands)
    if not split:
        out4 = out
    else:
        acc_p, m_p, l_p = out
        # Second stage: cross-worker log-sum-exp merge, fp32. A worker
        # whose every block was masked/skipped holds (m=NEG_INF, l=0,
        # acc=0): NEG_INF is finite, so exp(m - m_star) is exp(0)=1 at
        # worst and its zero l/acc contribute nothing — all-masked rows
        # (padding) keep the single-sweep convention l=0 → out 0 via the
        # epsilon.
        m_w = m_p[..., 0]  # [B, S, H_kv, R] (broadcast columns, take one)
        l_w = l_p[..., 0]
        m_star = jnp.max(m_w, axis=1)
        alpha = jnp.exp(m_w - m_star[:, None])  # [B, S, H_kv, R]
        l_tot = jnp.sum(l_w * alpha, axis=1)  # [B, H_kv, R]
        acc = jnp.sum(acc_p * alpha[..., None], axis=1)  # [B, H_kv, R, D]
        out4 = (acc / jnp.maximum(l_tot, 1e-37)[..., None]).astype(q.dtype)
    out4 = out4[:, :, :r]  # drop row padding
    return jnp.moveaxis(
        out4.reshape(b, h_kv, group, c, d), 3, 1
    ).reshape(b, c, h, d)


def paged_quantize_scatter(
    k: jax.Array,
    v: jax.Array,
    blk: jax.Array,
    off: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    k_scale: jax.Array,
    v_scale: jax.Array,
    *,
    interpret: bool | None = None,
):
    """Quantize-on-scatter: write a chunk's KV rows into a quantized
    pool, computing each row's per-head scale and casting to the pool
    dtype in ONE kernel — the write-side twin of the fused gather above.
    The jnp spelling (``serving.kv_pool.quantize_kv`` + four
    ``.at[rows].set``) stays the dense/interpret reference; both call
    ``kv_pool.quantize_rows`` for the row math, so the two spellings
    produce bit-identical pools and greedy streams cannot diverge across
    the scatter implementation.

    Grid ``(B·L,)``: one step per written row, K and V together. The
    kernel emits the quantized rows and their scales DENSE
    (``[B·L, H_kv, D]`` and ``[B·L, H_kv, 1]``) and four plain
    ``.at[rows].set`` — in place on the donated pools, the same scatter
    the raw-pool path uses — put them at their (block, offset). The
    kernel cannot place them itself: one row of a
    ``[n_blocks, block_len, H_kv·D]`` leaf (or one ``[H_kv]`` scale row)
    is a block whose second-to-last dim is 1 against ``block_len``,
    which Mosaic's tiling rule refuses, and a leaf shaped so that the
    rule accepts a row (``[..., H_kv, D]``) is the one the chip lays
    out ``n_blocks``-minor and copies whole around every scatter
    (``kv_pool.pool_leaf_shape``). Duplicate destinations exist only
    for trash-block writes (inactive lanes), where any write order is
    harmless garbage.

    Args:
      k, v: ``[B, L, H_kv, D]`` rows to write (post-RoPE, compute
        dtype).
      blk, off: ``[B, L]`` int32 destination block ids / in-block
        offsets (``models.transformer.Attention`` derives them from the
        block table and ``position_offset``).
      k_pool, v_pool: ``[n_blocks, block_len, H_kv·D]`` quantized
        pools (int8 or fp8).
      k_scale, v_scale: ``[n_blocks, block_len, H_kv]`` scale siblings
        (fp32 multipliers for int8, int8 exponents for fp8 —
        ``kv_pool.pool_scale_dtype``).
      interpret: force the Pallas interpreter; None auto-detects.

    Returns the updated ``(k_pool, v_pool, k_scale, v_scale)``.
    """
    from pytorch_distributed_tpu.serving.kv_pool import (
        is_quantized_pool,
        quantize_rows,
    )

    if not is_quantized_pool(k_pool.dtype):
        raise ValueError(
            "paged_quantize_scatter writes quantized pools (int8/fp8); "
            f"got pool dtype {k_pool.dtype} — raw pools scatter with a "
            "plain .at[].set, there is nothing to fuse"
        )
    b, l, h_kv, d = k.shape
    n = b * l
    pool_dt = k_pool.dtype
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def _kernel(k_ref, v_ref, kq_out, vq_out, ks_out, vs_out):
        qk, sk = quantize_rows(k_ref[0].astype(jnp.float32), pool_dt)
        qv, sv = quantize_rows(v_ref[0].astype(jnp.float32), pool_dt)
        kq_out[0] = qk
        vq_out[0] = qv
        ks_out[0] = sk[:, None]
        vs_out[0] = sv[:, None]

    row_spec = pl.BlockSpec((1, h_kv, d), lambda i: (i, 0, 0))
    sc_spec = pl.BlockSpec((1, h_kv, 1), lambda i: (i, 0, 0))
    q_rows = jax.ShapeDtypeStruct((n, h_kv, d), pool_dt)
    sc_rows = jax.ShapeDtypeStruct((n, h_kv, 1), k_scale.dtype)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        )
    qk_rows, qv_rows, sk_rows, sv_rows = pl.pallas_call(
        _kernel,
        out_shape=[q_rows, q_rows, sc_rows, sc_rows],
        grid=(n,),
        in_specs=[row_spec, row_spec],
        out_specs=[row_spec, row_spec, sc_spec, sc_spec],
        interpret=interpret,
        name="paged_kv_write",
        **kwargs,
    )(k.reshape(n, h_kv, d), v.reshape(n, h_kv, d))
    rows = (blk.reshape(-1), off.reshape(-1))
    return (k_pool.at[rows].set(qk_rows.reshape(n, h_kv * d)),
            v_pool.at[rows].set(qv_rows.reshape(n, h_kv * d)),
            k_scale.at[rows].set(sk_rows[..., 0]),
            v_scale.at[rows].set(sv_rows[..., 0]))
