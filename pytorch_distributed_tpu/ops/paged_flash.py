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
scalar-prefetch operand (SMEM), the pools stay in HBM, and the kernel
copies pool block ``tables[b, chain index]`` HBM→VMEM itself, in chain
order, touching only the chain's live blocks.

Structure (per the in-tree FlashAttention kernel,
``ops/flash_attention.py``, and the TPU Pallas playbook
``/opt/skills/guides/pallas_guide.md``):

- a grid step is a TILE of ``T`` consecutive chain blocks: grid
  ``(B, S, ceil(ceil(W/T)/S))`` with the tile sweep innermost and
  sequential ("arbitrary" semantics — it carries the online-softmax
  recurrence); the running (m, l, acc) state lives in VMEM scratch,
  persisting across the chain for each batch row. ``T`` follows from
  shapes in one place, ``tile_blocks``: ``TILE_POSITIONS`` (128)
  positions' worth of blocks — a vector register's lanes of logits, so
  a tile's matrix products and state updates are paid once for 128
  positions, not once a 16-position block — never more than the table
  is wide, cut so that the staged tile stays under ``TILE_VMEM_BYTES``;
  a pool of 128-position blocks gets 1. Nothing can set it: no
  argument, config field, flag or variable;
- a live tile's heads are attended IN ONE PASS where there are several
  (``heads_folded``, the one rule, from the narrow heads, the query rows
  each brings and the lane tile: ``h_kv > 1 and h_kv * rows <= 128``;
  nothing can set it either). The staged tile is already
  ``[T·block_len, H_kv·D]``, every head's lanes side by side, so it is
  the STREAMED operand of one product against the lane's block-diagonal
  query (``_attend_tile_folded``): ``S^T = K · Qbd -> [128, N]`` float32,
  column ``n = r·H_kv + h`` holding head ``h``'s logits for its query
  row ``r`` — the same bfloat16 products summed in float32, the zeros
  adding nothing — 128 rows through the MXU a weight tile where a
  head's ``[8, D] x [D, 128]`` product streamed 8 (one real). Positions
  on the sublanes, (row, head) columns on the lanes: one frontier mask,
  one running max, sum and correction for all heads (a ``[128, N]``
  update and ``[1, N]`` state), ``P^T`` (the pool's dtype) against the
  whole V tile into one ``[N, H_kv·D]`` float32 accumulator, whose
  diagonal blocks are taken once a lane, at finalize (``_fold_out``):
  the output leaves lane-dense, ``[rows, H_kv·D]``. The block-diagonal
  query is built outside the kernel (``[B, N, H_kv·D]``, 32 KB a lane at
  16 heads of 64) and padded to 128 columns in VMEM once a lane. Where
  a static loop ran 16 dependent chains a tile (two 8-row products, a
  softmax over one vector register and three slab updates a head: 2.65
  us a live tile at 16 heads whatever the bytes), the folded tile costs
  0.88 us at 16 heads of 64 and 1.17 at 16 of 128 on a v5e, about what
  its bytes take to arrive (PERF.md section 6, PR 37). One narrow head
  (latent attention's tick: 32 rows over one 640-lane row) has no loop
  to remove and its rows already fill the streamed side: it keeps the
  loop's body (``_attend_tile``), as do rows too many for the lane tile
  (a chunk's);
- the tile's blocks reach VMEM by the kernel's own DMAs
  (``pltpu.make_async_copy``, the design of JAX's ``paged_attention``):
  two ``[T, block_len, H_kv·D]`` buffers a pool, the next live tile's
  copies in flight while this one is attended. A tile wholly past its
  row's query frontier is DEAD: no copy starts, nothing waits, no FLOPs
  — 0.1 us of grid step. (Staging the pool ``T`` times through the
  BlockSpec pipeline costs every grid step, dead ones too, 55 ns an
  operand of bookkeeping: 1 us at ``T`` = 8, PERF.md section 6, PR 30.)
  Where one core runs the grid in order and the sweep is unsplit, a
  lane's last live tile starts the NEXT lane's first (``carry``), so
  only the grid's first copy is waited for in the open;
- a chain index is clamped to the row's LAST LIVE block
  (``tile_entry``: ``min(entry, front // block_len, W - 1)``):
  admission reserves a request's whole chain, so an entry past the
  frontier names a real block of no use. A live tile's dead slabs hold
  the last live block again (finite rows of the lane's own, masked by
  their logical positions);
- a copied K/V block is the whole ``[block_len, H_kv·D]`` pool block
  (Mosaic's tiling rule accepts no narrower one at H_kv=12, D=64; the
  interpreter does not check it — every shape was refused on the chip
  until PR 21); the folded body reads the tile whole and the loop's
  takes each head's D lanes of it, ``k_buf[:, :, h·D:(h+1)·D]``, folded
  to ``[T·block_len, D]``; a quantized pool's ``[block_len, H_kv]`` scale
  siblings are too narrow a DMA for Mosaic and ride the BlockSpec
  pipeline, ``T`` operands a sibling under the same clamp (a dead
  step's index repeats, and the pipeline copies nothing for an index
  that did not change); positions
  ride as a ``[B, r_pad, 1]`` column (a ``[B, 1, 128]`` row of the
  columns' positions for the folded body) and each row's query frontier
  as a second scalar-prefetch operand;
- GQA is folded into the row dimension: queries regroup to
  ``[B, H_kv, G·C, D]`` so each narrow head's whole query group shares
  one staged KV block — the widened K/V never exists, mirroring the
  dense spelling's grouped einsum. ``C == 1`` (decode tick) and
  ``C == chunk`` (chunked prefill) are the same kernel at different row
  counts;
- causal/frontier masking ``k_pos <= q_position`` per row; table
  entries past a request's allocation point at the trash block, whose
  logical positions exceed every live query position, so they mask out
  exactly like the dense spelling. Tiles entirely past the batch row's
  query frontier are skipped with ``pl.when`` (no copy, no FLOPs, no
  dequant);
- softmax statistics in fp32 regardless of pool/compute dtype;
- quantized pools (int8 or fp8) dequantize INSIDE the kernel: per-
  (block, slot, head) scale siblings (``serving.kv_pool.quantize_kv``)
  follow the same table entries as their pool, so the f32 K/V rows exist
  only in VMEM, a tile at a time — HBM holds 1-byte values + scales (the
  2D/(D+4) int8 / 2D/(D+1) fp8 pool-capacity win). fp8 scale siblings
  are int8 power-of-two exponents: the in-VMEM multiplier is ``2**e``
  (exact), so the fp8 cast is the whole error budget. The folded body
  multiplies a head's COLUMNS of the float32 logits and of ``P`` by its
  scales (one per (position, head)) instead of the K and V rows;
- flash-decoding (round 20; FlashAttention-2's work partitioning,
  PAPERS.md §2, applied to decode): ``split_s`` > 1 splits the chain
  sweep across S grid workers, each owning ``ceil(ceil(W/T)/S)`` TILES
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
#: positions a grid step covers (``tile_blocks``): a vector register's
#: lanes of logits, so a head's two matrix products and three slab
#: updates are paid once for 128 positions (PERF.md section 6, PR 30)
TILE_POSITIONS = 128
#: most VMEM the staged tile may take (``tile_blocks``)
TILE_VMEM_BYTES = 4 << 20
#: the lane tile: the (query row, head) columns one folded product's
#: logits hold (``heads_folded``)
FOLD_COLUMNS = 128


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


def tile_blocks(w: int, block_len: int, row_bytes: int) -> int:
    """``T``, the consecutive chain blocks one grid step stages — the ONE
    place it is decided, from shapes alone: as many as make
    ``TILE_POSITIONS`` positions, never more than the table is wide, and
    cut so that the staged tile (``T`` blocks of ``block_len`` rows of
    ``row_bytes``: a position's K and V rows and their scale siblings,
    in two buffers each) stays under ``TILE_VMEM_BYTES``. A pool of 128-position blocks gets 1."""
    t = min(w, max(1, TILE_POSITIONS // block_len))
    return max(1, min(t, TILE_VMEM_BYTES // (2 * block_len * row_bytes)))


def heads_folded(h_kv: int, rows: int) -> int:
    """The narrow heads ONE product of a tile serves — the ONE place the
    kernel's body is decided, from shapes alone: all ``h_kv`` of them
    (the folded body, ``_attend_tile_folded``) where there are several
    and their ``rows`` query rows each fit the lane tile's
    ``FOLD_COLUMNS`` columns side by side; 1 (the body that loops over
    heads, ``_attend_tile``) where there is one narrow head, whose rows
    already fill the streamed side of its products and which has no loop
    to remove (latent attention's tick: 32 rows over one 640-lane row),
    or where the columns would not fit (a chunk's rows)."""
    return h_kv if h_kv > 1 and h_kv * rows <= FOLD_COLUMNS else 1


def tile_entry(tables, front, b, entry, *, block_len: int):
    """The pool block a tile's slab ``entry`` (a chain index of lane
    ``b``) is copied from — the fused gather: the block table entry names
    the DMA's source, so pool block ``tables[b, entry]`` goes straight
    into VMEM and no gathered copy exists in HBM. ``entry`` is clamped to
    the lane's LAST LIVE block (``front[b] // block_len``) and to the
    table: admission reserves a request's whole chain, so an entry past
    the frontier names a real block of no use, and a slab past it holds
    the last live block again — finite rows, masked by their LOGICAL
    positions, which lie past the frontier."""
    live = front[b] // block_len
    return tables[b, jnp.minimum(jnp.minimum(entry, live),
                                 tables.shape[1] - 1)]


def staged_row_bytes(*pools) -> int:
    """Bytes one position holds across the leaves a step stages (K, V
    and, where the pool is quantized, their scale siblings; ``None``
    entries skipped): ``tile_blocks``' ``row_bytes``."""
    return sum(x.shape[-1] * x.dtype.itemsize for x in pools
               if x is not None)


def _rows(x):  # [T, block_len, n] -> [T·block_len, n]
    return x.reshape(x.shape[0] * x.shape[1], x.shape[2])


def _tile_scales(refs, fp8_scales):
    """The per-(slot, head) scale siblings of a tile's ``T`` blocks,
    staged by the table-driven index maps, as float32 multipliers
    ``[T·block_len, H_kv]``: a quantized pool dequantizes THIS tile only,
    in VMEM. fp8 pools carry int8 exponents — multiplier 2**e, exact in
    fp32 (kv_pool.scale_factors spelling)."""
    scales = jnp.concatenate([r[0].astype(jnp.float32) for r in refs], 0)
    return jnp.exp2(scales) if fp8_scales else scales


def _attend_tile(q_ref, qpos, k_buf, v_buf, ks_refs, vs_refs,
                 m_scr, l_scr, acc_scr, *, scale, k_start, h_kv, d,
                 quantized, fp8_scales):
    """One tile's online-softmax update for every narrow head — the
    shared inner body of the single-worker and split-S kernels (one
    spelling, so the split path cannot drift from the sweep it
    partitions). The buffers hold the tile's ``T`` pool blocks as
    ``[T, block_len, H_kv·D]`` (a DMA moves a whole pool block); the
    static head loop reads each head's D lanes of all ``T`` and folds
    them onto the sublanes, so ONE ``[R, D] x [D, T·block_len]`` product,
    one softmax update and one ``[R, T·block_len] x [T·block_len, D]``
    product serve the tile."""
    if quantized:
        ks_all, vs_all = (_tile_scales(refs, fp8_scales)
                          for refs in (ks_refs, vs_refs))
    for h in range(h_kv):
        q = q_ref[0, h]  # [R, D]
        lanes = slice(h * d, (h + 1) * d)
        k = k_buf[:, :, lanes]  # [T, block_len, D]
        v = v_buf[:, :, lanes]
        if quantized:  # 1-byte rows widen before they fold (8-row tiles)
            k, v = k.astype(jnp.float32), v.astype(jnp.float32)
        k, v = _rows(k), _rows(v)  # [T·block_len, D]
        if quantized:
            k = k * ks_all[:, h:h + 1]
            v = v * vs_all[:, h:h + 1]
            q = q.astype(jnp.float32)
        # fp32 logits on the MXU from the operands as stored, then the
        # softmax scale on the [R, T·block_len] logits in fp32: scaling Q
        # in its own dtype rounds it wherever the scale is no power of
        # two (D=128), which the dense spelling's fp32 scale never did.
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [R, T·block_len]
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


def _column(row, n):
    """``[1, N] -> [n, 1]``: a per-column statistic's first ``n`` lanes
    laid down the sublanes (the accumulator's rows are the logits'
    columns), by a select against the diagonal and a lane sum."""
    shape = (n, row.shape[1])
    diag = (jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            == jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    return jnp.sum(jnp.where(diag, row, 0.0), axis=1, keepdims=True)


def _head_columns(scales, n_cols, h_kv):
    """``[P, H_kv] -> [P, n_cols]``: narrow head ``n % H_kv``'s scale in
    column ``n`` (the folded body's column order), one select a head."""
    head = jax.lax.broadcasted_iota(
        jnp.int32, (scales.shape[0], n_cols), 1) % h_kv
    out = jnp.zeros(head.shape, jnp.float32)
    for h in range(h_kv):
        out = jnp.where(head == h, scales[:, h:h + 1], out)
    return out


def _attend_tile_folded(qbd_ref, qpos, k_buf, v_buf, ks_refs, vs_refs,
                        m_scr, l_scr, acc_scr, *, scale, k_start, h_kv,
                        quantized, fp8_scales):
    """One tile's online-softmax update for EVERY narrow head in one pass
    (``heads_folded`` > 1). ``qbd_ref`` holds the lane's block-diagonal
    query, transposed: row ``n = r·H_kv + h`` is query row ``r`` of narrow
    head ``h`` in lanes ``[h·D, (h+1)·D)`` and zeros elsewhere, ``N``
    rows padded to the lane tile. The tile ``[P, H_kv·D]`` (``P`` =
    ``T·block_len`` positions) is the STREAMED operand of ONE product
    ``S^T = K · Qbd -> [P, N]`` float32: column ``n`` is head ``h``'s
    logits for its row ``r`` (the same bfloat16 products summed in
    float32; the zeros add nothing), where the head loop ran ``H_kv``
    products of 8 streamed rows each. Positions lie on the sublanes and
    (row, head) columns on the lanes, so the frontier mask, the running
    max, the sum and the correction are ONE ``[P, N]`` update and a
    ``[1, N]`` state. ``P^T`` (in the pool's dtype) against the whole V
    tile gives ``[N, H_kv·D]``: row ``n``'s lanes ``[h·D, (h+1)·D)`` are
    head ``h``'s output and the rest is waste the MXU does not feel at
    so few rows; the float32 accumulator keeps that shape, and the
    diagonal blocks are taken once a lane (``_fold_out``). A quantized
    pool's scales, one per (position, head), multiply the logits' and
    ``P``'s columns in float32 instead of the K and V rows."""
    k, v = _rows(k_buf[...]), _rows(v_buf[...])  # [P, H_kv·D]
    qbd = qbd_ref[...]  # [N, H_kv·D]
    if quantized:
        k, v = k.astype(jnp.float32), v.astype(jnp.float32)
        qbd = qbd.astype(jnp.float32)
    # fp32 logits on the MXU from the operands as stored, then the
    # softmax scale on the logits in fp32 (see ``_attend_tile``)
    s = jax.lax.dot_general(
        k, qbd, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # [P, N]
    if quantized:
        s = s * _head_columns(_tile_scales(ks_refs, fp8_scales),
                              s.shape[1], h_kv)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    # the frontier mask of ``_attend_tile``, a column a query row:
    # padding columns (qpos == -1) mask everything -> l stays 0
    mask = k_pos <= qpos
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=0, keepdims=True)
    m_scr[...] = m_new
    if quantized:
        p = p * _head_columns(_tile_scales(vs_refs, fp8_scales),
                              p.shape[1], h_kv)
    # P's live columns ride the MXU in the pool's dtype (fp32 operands
    # double a decode step on a v5e, PERF.md section 6, PR 28),
    # contracted with V over the positions of both: P^T · V
    n_acc = acc_scr.shape[0]
    acc_scr[...] = acc_scr[...] * _column(corr, n_acc) + jax.lax.dot_general(
        p[:, :n_acc].astype(v.dtype), v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _fold_out(acc, h_kv, d, rows, r_pad):
    """The diagonal blocks of the folded accumulator, once a lane:
    ``[N, H_kv·D] -> [r_pad, H_kv·D]``, query row ``r``'s output for
    narrow head ``h`` in lanes ``[h·D, (h+1)·D)`` — accumulator row
    ``r·H_kv + h``'s own ``D`` lanes. Lane-dense: a sublane sum a query
    row, no lane moves."""
    n = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 1)
    out_row = jax.lax.broadcasted_iota(jnp.int32, (r_pad, acc.shape[1]), 0)
    out = jnp.zeros((r_pad, acc.shape[1]), jnp.float32)
    for r in range(rows):
        # lanes past row n's head: 0 <= off < D only on rows
        # [r·H_kv, (r+1)·H_kv), each at its own head's lanes
        off = lane - (n - r * h_kv) * d
        own = jnp.sum(jnp.where((off >= 0) & (off < d), acc, 0.0), axis=0,
                      keepdims=True)
        out = jnp.where(out_row == r, own, out)
    return out


def _paged_kernel(
    tables_ref,  # scalar-prefetch [B, W] int32 (SMEM)
    front_ref,  # scalar-prefetch [B] int32: each row's query frontier
    q_ref, qpos_ref, k_pool, v_pool,  # the pools, in HBM
    *refs,  # T K-scale blocks and T V-scale blocks where quantized, ...
    scale: float, block_len: int, h_kv: int, d: int, quantized: bool,
    fp8_scales: bool, tile: int, n_tiles: int, wc: int, split: bool,
    carry: bool, fold_rows: int,
):
    """Grid ``(B, S, ceil(n_tiles/S))``: worker s sweeps tiles
    ``[s*wc, min((s+1)*wc, n_tiles))`` with its own (m, l, acc) state. The
    single-worker sweep (``split=False``, S == 1) normalizes in place;
    flash-decoding workers (``split=True``) emit their partials UN-
    normalized — the caller's fp32 log-sum-exp merge combines them. One
    kernel, so the split path cannot drift from the sweep it partitions.

    The pools stay in HBM and the kernel copies a tile's ``T`` blocks
    into one of two VMEM buffers a pool itself, the next live tile's in
    flight while this one is attended. (A quantized pool's scale
    siblings, ``H_kv`` lanes wide, are no DMA Mosaic slices: they ride
    the pipeline, ``T`` blocks a sibling, under the same clamp.) A tile
    wholly past its lane's frontier (and the tail of a ceil split) is
    DEAD: no copy starts for it and nothing waits. With ``carry`` (one
    worker, grid steps in order on one core) a lane's last live tile
    starts the NEXT lane's first, so only the grid's very first copy is
    waited for in the open; without it each sweep starts its own first
    tile.

    ``fold_rows`` > 0 (``heads_folded``: several narrow heads of that
    many query rows each) takes the folded body: ``q_ref`` is then the
    lane's block-diagonal query ``[N, H_kv·D]`` and ``qpos_ref`` a row of
    its columns' positions, the state is ``[1, 128]`` rows and one
    ``[N, H_kv·D]`` accumulator, and the output leaves lane-dense,
    ``[r_pad, H_kv·D]``; 0 takes the body that loops over heads."""
    ks_refs = vs_refs = None
    if quantized:
        ks_refs, vs_refs, refs = refs[:tile], refs[tile:2 * tile], refs[
            2 * tile:]
    n_out = 3 if split else 1
    out_refs, refs = refs[:n_out], refs[n_out:]
    if fold_rows:  # the query, padded to the lane tile's columns
        qbd_scr, refs = refs[0], refs[1:]
    m_scr, l_scr, acc_scr, k_buf, v_buf, sem, cur = refs
    pools, bufs = (k_pool, v_pool), (k_buf, v_buf)
    b, jj = pl.program_id(0), pl.program_id(2)
    j = pl.program_id(1) * wc + jj  # logical tile index of this step

    def live(lane, jt):
        return (jt < n_tiles) & (jt * tile * block_len <= front_ref[lane])

    def copies(lane, jt, slot, known=True):
        """The DMAs of tile ``jt`` of ``lane`` into buffer ``slot``;
        ``known=False`` for a wait, which needs their shapes only."""
        out = []
        for t in range(tile):
            blk = tile_entry(tables_ref, front_ref, lane, jt * tile + t,
                             block_len=block_len) if known else 0
            out += [pltpu.make_async_copy(pool.at[blk], buf.at[slot, t],
                                          sem.at[i, slot])
                    for i, (pool, buf) in enumerate(zip(pools, bufs))]
        return out

    def start(lane, jt, slot):
        for copy in copies(lane, jt, slot):
            copy.start()

    @pl.when(jj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        if fold_rows:
            qbd_scr[...] = jnp.zeros_like(qbd_scr)
            qbd_scr[:q_ref.shape[1]] = q_ref[0]

    # the copy nobody started for us: the grid's first tile, or without
    # ``carry`` each sweep's (a worker's range may lie past the frontier)
    @pl.when((jj == 0) & ((b == 0) if carry else True))
    def _first():
        cur[0] = 0

        @pl.when(live(b, j))
        def _():
            start(b, j, 0)

    # A tile entirely past this batch row's query frontier contributes
    # nothing — no copy, no FLOPs, no dequant.
    @pl.when(live(b, j))
    def _tile():
        slot = cur[0]
        more = (jj + 1 < wc) & live(b, j + 1)

        @pl.when(more)
        def _():
            start(b, j + 1, 1 - slot)

        if carry:  # a lane's first tile is live: front >= 0
            @pl.when(jnp.logical_not(more) & (b + 1 < pl.num_programs(0)))
            def _():
                start(b + 1, 0, 1 - slot)

        for copy in copies(b, j, slot, known=False):
            copy.wait()
        shared = dict(scale=scale, k_start=j * tile * block_len, h_kv=h_kv,
                      quantized=quantized, fp8_scales=fp8_scales)
        if fold_rows:
            _attend_tile_folded(qbd_scr, qpos_ref[0], k_buf.at[slot],
                                v_buf.at[slot], ks_refs, vs_refs, m_scr,
                                l_scr, acc_scr, **shared)
        else:
            _attend_tile(q_ref, qpos_ref[0], k_buf.at[slot], v_buf.at[slot],
                         ks_refs, vs_refs, m_scr, l_scr, acc_scr, d=d,
                         **shared)
        cur[0] = 1 - slot

    @pl.when(jj == wc - 1)
    def _finalize():
        acc = acc_scr[...]
        if not split:  # normalize in place; workers leave that to the merge
            l = jnp.maximum(l_scr[...], 1e-37)
            acc = acc / (_column(l, acc.shape[0]) if fold_rows
                         else l[:, :, :1])
        if fold_rows:
            acc = _fold_out(acc, h_kv, d, fold_rows, out_refs[0].shape[-2])
        if split:
            o_ref, m_ref, l_ref = out_refs
            o_ref[0, 0] = acc
            m_ref[0, 0] = m_scr[...]
            l_ref[0, 0] = l_scr[...]
        else:
            (o_ref,) = out_refs
            o_ref[0] = acc.astype(o_ref.dtype)


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
    block_len, h_kv = pool_heads(k_pool, q.shape[2], d)
    tile = tile_blocks(w, block_len, staged_row_bytes(
        k_pool, v_pool, k_scale, v_scale))
    # every worker owns >= 1 tile
    s_workers = min(split_s if split_s is not None else auto_split_s(w, b),
                    -(-w // tile))
    # What the backend, the device and the shapes decide is resolved out
    # here and rides in as static arguments: the traced function is then
    # keyed by shapes and these alone, so the layers of a program share
    # ONE trace and ONE lowered function (a program of 24 layers
    # otherwise traces and lowers the kernel 24 times).
    return _paged_flash(
        q, k_pool, v_pool, block_tables, q_positions, k_scale, v_scale,
        scale=float(scale if scale is not None else d ** -0.5),
        s_workers=s_workers, tile=tile,
        # every narrow head's rows in one product, or a head at a time
        fold=heads_folded(h_kv, q.shape[2] // h_kv * q.shape[1]) > 1,
        # grid steps run in order where one core runs them all
        carry=s_workers == 1 and device_cores() == 1,
        interpret=bool(interpret),
    )


@functools.partial(jax.jit, static_argnames=("scale", "s_workers", "tile",
                                             "fold", "carry", "interpret"))
def _paged_flash(q, k_pool, v_pool, block_tables, q_positions, k_scale,
                 v_scale, *, scale: float, s_workers: int, tile: int,
                 fold: bool, carry: bool, interpret: bool):
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
    n_tiles = -(-w // tile)  # grid steps a chain, T blocks each
    wc = -(-n_tiles // s_workers)  # tiles per worker (ceil split)

    # GQA fold: query head h = kv·group + g reads narrow head kv, so the
    # per-narrow-head row block is its whole query group × chunk.
    r = group * c
    r_pad = -(-r // 8) * 8
    q4 = jnp.moveaxis(q.reshape(b, c, h_kv, group, d), 1, 3)  # [B,Hkv,G,C,D]
    q4 = q4.reshape(b, h_kv, r, d)
    q_positions = q_positions.astype(jnp.int32)
    qpos = jnp.broadcast_to(
        q_positions[:, None, :], (b, group, c)
    ).reshape(b, r)
    if fold:
        # The block-diagonal query, transposed: row n = r·H_kv + h holds
        # head h's query row r in lanes [h·D, (h+1)·D), zeros elsewhere —
        # the one product's other operand (``_attend_tile_folded``). Its
        # N = H_kv·R rows pad to the packed sublane tile here and to the
        # lane tile's columns in VMEM; a column's position rides beside
        # it as a [B, 1, FOLD_COLUMNS] row, -1 (every key masked -> a
        # zero column) on the padding.
        n = h_kv * r
        n_acc = -(-n // 16) * 16
        own = jnp.eye(h_kv, dtype=bool)[None, None, :, :, None]
        qbd = jnp.where(own, jnp.swapaxes(q4, 1, 2)[:, :, :, None, :], 0)
        q_rows = jnp.pad(qbd.reshape(b, n, h_kv * d),
                         ((0, 0), (0, n_acc - n), (0, 0)))
        qpos = jnp.pad(jnp.repeat(qpos, h_kv, axis=1),
                       ((0, 0), (0, FOLD_COLUMNS - n)),
                       constant_values=-1)[:, None, :]
        row_spec = pl.BlockSpec((1, n_acc, h_kv * d),
                                lambda b, s, j, *_: (b, 0, 0))
        pos_spec = pl.BlockSpec((1, 1, FOLD_COLUMNS),
                                lambda b, s, j, *_: (b, 0, 0))
        # the state: [1, N] rows, one [N, H_kv·D] accumulator; the output
        # leaves lane-dense, [r_pad, H_kv·D], its diagonal blocks taken
        stat_block, acc_block, out_block = (
            (1, FOLD_COLUMNS), (n_acc, h_kv * d), (r_pad, h_kv * d))
    else:
        # Rows pad to a sublane multiple; padding rows carry position -1
        # (every key masked → zero rows, sliced away below).
        if r_pad != r:
            q4 = jnp.pad(q4, ((0, 0), (0, 0), (0, r_pad - r), (0, 0)))
            qpos = jnp.pad(qpos, ((0, 0), (0, r_pad - r)),
                           constant_values=-1)
        q_rows, qpos = q4, qpos[:, :, None]
        row_spec = pl.BlockSpec((1, h_kv, r_pad, d),
                                lambda b, s, j, *_: (b, 0, 0, 0))
        pos_spec = pl.BlockSpec((1, r_pad, 1), lambda b, s, j, *_: (b, 0, 0))
        # a slab per narrow head
        stat_block, acc_block, out_block = (
            (h_kv, r_pad, 128), (h_kv, r_pad, d), (h_kv, r_pad, d))

    # Queries and positions ride the pipeline, a batch row a block (its
    # last two dims equal the array's: Mosaic's tiling rule, which the
    # interpreter does not check; positions are a [B, r_pad, 1] column,
    # or a [B, 1, FOLD_COLUMNS] row beside a block-diagonal query).
    # The pools stay where they are: the kernel DMAs whole pool blocks,
    # [block_len, H_kv·D], into buffers of T blocks, two a pool. A scale
    # sibling's [block_len, H_kv] block is too narrow for such a DMA and
    # rides the pipeline: T operands a sibling, operand t staging the
    # tile's entry t (``tile_entry``: past the frontier its index
    # repeats, and the pipeline copies nothing for an index that did not
    # change). Index maps take the grid position (b, s, j) and the two
    # scalar-prefetch refs.
    def staged(scales):
        return [
            pl.BlockSpec(
                (1,) + scales.shape[1:],
                lambda b, s, j, tables, front, t=t: (tile_entry(
                    tables, front, b, (s * wc + j) * tile + t,
                    block_len=block_len), 0, 0))
            for t in range(tile)
        ]

    in_specs = [
        row_spec, pos_spec,
        pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=pl.ANY),
    ]
    operands = [q_rows, qpos, k_pool, v_pool]
    if quantized:
        in_specs += staged(k_scale) + staged(v_scale)
        operands += [k_scale] * tile + [v_scale] * tile
    if split:
        # each worker's un-normalized (acc, m, l), merged below
        parts = [out_block, stat_block, stat_block]
        out_specs = [
            pl.BlockSpec((1, 1) + p, lambda b, s, j, *_, z=(0,) * len(p):
                         (b, s) + z)
            for p in parts
        ]
        out_shape = [jax.ShapeDtypeStruct((b, s_workers) + p, jnp.float32)
                     for p in parts]
    else:
        out_specs = pl.BlockSpec((1,) + out_block, lambda b, s, j, *_,
                                 z=(0,) * len(out_block): (b,) + z)
        out_shape = jax.ShapeDtypeStruct((b,) + out_block, q.dtype)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )
    out = pl.pallas_call(
        functools.partial(
            _paged_kernel, scale=scale, block_len=block_len, h_kv=h_kv,
            d=d, quantized=bool(quantized), fp8_scales=fp8_scales,
            tile=tile, n_tiles=n_tiles, wc=wc, split=split, carry=carry,
            fold_rows=r if fold else 0,
        ),
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, s_workers, wc),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=(
                # the query's columns, padded to the lane tile
                [pltpu.VMEM((FOLD_COLUMNS, h_kv * d), q.dtype)] if fold
                else []) + [
                pltpu.VMEM(stat_block, jnp.float32),  # running max m
                pltpu.VMEM(stat_block, jnp.float32),  # running sum l
                pltpu.VMEM(acc_block, jnp.float32),  # un-normalized
                # a tile of K and of V, twice: [2, T, block_len, H_kv·D]
                pltpu.VMEM((2, tile) + k_pool.shape[1:], k_pool.dtype),
                pltpu.VMEM((2, tile) + v_pool.shape[1:], v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),  # [K or V, buffer]
                pltpu.SMEM((1,), jnp.int32),  # the buffer in use
            ],
        ),
        interpret=interpret,
        name="paged_decode_attn",
        **kwargs,
    )(block_tables.astype(jnp.int32),
      # a lane's first tile is always live (a row of padding alone,
      # position -1, still masks every key)
      jnp.maximum(jnp.max(q_positions, axis=1), 0), *operands)
    if split:
        acc_p, m_p, l_p = out
        # Second stage: cross-worker log-sum-exp merge, fp32. A worker
        # whose every block was masked/skipped holds (m=NEG_INF, l=0,
        # acc=0): NEG_INF is finite, so exp(m - m_star) is exp(0)=1 at
        # worst and its zero l/acc contribute nothing — all-masked rows
        # (padding) keep the single-sweep convention l=0 → out 0 via the
        # epsilon.
        if fold:  # [B, S, 1, N] rows, a column a (row, head)
            m_w, l_w = (x[:, :, 0, :n].reshape(b, s_workers, r, h_kv)
                        for x in (m_p, l_p))
            acc_p = acc_p[:, :, :r].reshape(b, s_workers, r, h_kv, d)
        else:  # [B, S, H_kv, R] (broadcast columns, take one)
            m_w, l_w = m_p[..., 0], l_p[..., 0]
        m_star = jnp.max(m_w, axis=1)
        alpha = jnp.exp(m_w - m_star[:, None])
        l_tot = jnp.sum(l_w * alpha, axis=1)
        acc = jnp.sum(acc_p * alpha[..., None], axis=1)
        out = (acc / jnp.maximum(l_tot, 1e-37)[..., None]).astype(q.dtype)
    # [B, R, H_kv, D] folded, [B, H_kv, r_pad, D] looped: drop the row
    # padding, then rows (g, c) and heads back to [B, C, H, D]
    out = (out[:, :r].reshape(b, group, c, h_kv, d).transpose(0, 2, 3, 1, 4)
           if fold else
           jnp.moveaxis(out[:, :, :r].reshape(b, h_kv, group, c, d), 3, 1))
    return out.reshape(b, c, h, d)


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
