"""Attention kernels: dense reference, blockwise (memory-efficient), and the
shared online-softmax combine that ring attention reuses.

The reference has no attention at all (a ResNet CNN,
``resnet_single_gpu.py:83``; SURVEY.md §5 "long-context: ABSENT") — this
module is part of the framework's first-class long-context support, built
TPU-first:

- all softmax statistics in fp32 regardless of compute dtype (bf16 QK^T
  products are fine; exp/sum are not);
- blockwise attention is a ``lax.scan`` over key/value blocks with an
  online-softmax accumulator (the Rabe-Staats / FlashAttention recurrence):
  O(L·block) activation memory instead of O(L²), static shapes, MXU-sized
  blocks; XLA autodiff differentiates the scan, and ``jax.checkpoint`` on
  the block body keeps backward memory flat;
- every kernel takes absolute position offsets for Q and KV, so the same
  code computes a causal mask inside one device's shard or across ring
  steps where the KV block came from another device
  (``parallel/sequence.py``);
- ``paged_attention`` is the serving engine's read path: decode/chunk
  queries against a block-pooled KV cache through a block table
  (``serving/kv_pool.py``) — a dense ``jnp.take``-over-blocks gather, or
  the fused Pallas kernel (``ops/paged_flash.py``) that copies the blocks
  its table names into VMEM itself and never materializes the gather; both
  spellings accept int8 pools with per-row scales.

Shapes follow the JAX convention: ``[batch, length, heads, head_dim]``.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30  # additive mask value; avoids -inf - -inf = nan in softmax


class SoftmaxState(NamedTuple):
    """Online-softmax accumulator carried across KV blocks (fp32).

    o: un-normalized weighted values  [B, Lq, H, D]
    m: running row max of logits      [B, Lq, H]
    l: running sum of exp(logit - m)  [B, Lq, H]
    """

    o: jax.Array
    m: jax.Array
    l: jax.Array

    @classmethod
    def zero(cls, batch, q_len, heads, head_dim) -> "SoftmaxState":
        return cls(
            o=jnp.zeros((batch, q_len, heads, head_dim), jnp.float32),
            m=jnp.full((batch, q_len, heads), NEG_INF, jnp.float32),
            l=jnp.zeros((batch, q_len, heads), jnp.float32),
        )

    def finalize(self, dtype) -> jax.Array:
        """Normalize. Rows that saw only masked keys produce zeros."""
        denom = jnp.maximum(self.l, 1e-37)[..., None]
        return (self.o / denom).astype(dtype)


def attend_block(
    state: SoftmaxState,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    scale: float,
    causal: bool,
    q_offset: jax.Array | int = 0,
    k_offset: jax.Array | int = 0,
) -> SoftmaxState:
    """Fold one KV block into the online-softmax state.

    This is the single source of truth for the attention recurrence — the
    blockwise kernel scans it over local KV blocks and ring attention folds
    it once per ring step with the visiting KV shard.
    """
    b, lq, h, d = q.shape
    lk = k.shape[1]
    # [B, H, Lq, Lk] logits in fp32
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    allowed = None
    if causal:
        q_pos = q_offset + jnp.arange(lq)
        k_pos = k_offset + jnp.arange(lk)
        allowed = k_pos[None, :] <= q_pos[:, None]  # [Lq, Lk]
        logits = jnp.where(allowed[None, None], logits, NEG_INF)

    m_block = jnp.max(logits, axis=-1)  # [B, H, Lq]
    m_block = jnp.transpose(m_block, (0, 2, 1))  # [B, Lq, H]
    m_new = jnp.maximum(state.m, m_block)
    # Avoid exp overflow for fully-masked rows: m_new >= NEG_INF.
    correction = jnp.exp(state.m - m_new)  # [B, Lq, H]
    p = jnp.exp(
        logits - jnp.transpose(m_new, (0, 2, 1))[..., None]
    )  # [B, H, Lq, Lk] fp32
    if allowed is not None:
        # Multiplicative zeroing so a FULLY-masked row contributes nothing
        # (additive NEG_INF alone would leave p = exp(0) = 1 uniform there):
        # l stays 0 and finalize() returns zeros, as documented.
        p = p * allowed[None, None]
    l_block = jnp.transpose(jnp.sum(p, axis=-1), (0, 2, 1))
    o_block = jnp.einsum(
        "bhqk,bkhd->bqhd", p, v.astype(jnp.float32),  # jaxlint: disable=precision-cast -- fp32 PV accumulation; o/l state is fp32 by kernel contract
        preferred_element_type=jnp.float32,
    )
    return SoftmaxState(
        o=state.o * correction[..., None] + o_block,
        m=m_new,
        l=state.l * correction + l_block,
    )


def dense_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    q_offset: jax.Array | int = 0,
    k_offset: jax.Array | int = 0,
) -> jax.Array:
    """Reference O(L²) attention (correctness baseline and short-seq path)."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    probs_mask = None
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[1])
        k_pos = k_offset + jnp.arange(k.shape[1])
        probs_mask = (k_pos[None, :] <= q_pos[:, None])[None, None]
        logits = jnp.where(probs_mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    if probs_mask is not None:
        # Fully-masked rows: zeros, not uniform (matches blockwise/ring).
        probs = probs * probs_mask
    return jnp.einsum(
        "bhqk,bkhd->bqhd", probs, v.astype(jnp.float32)  # jaxlint: disable=precision-cast -- fp32 PV matmul matches blockwise/ring accumulator dtype
    ).astype(q.dtype)


def pool_heads(k_pool: jax.Array, h: int, d: int):
    """``(block_len, H_kv)`` of a ``[n_blocks, block_len, H_kv·D]`` pool
    leaf read with ``h`` query heads of size ``d`` — the one place both
    gather spellings check a pool against their queries."""
    if k_pool.ndim != 3 or k_pool.shape[2] % d:
        raise ValueError(
            f"pool leaf {k_pool.shape} is not [n_blocks, block_len, "
            f"H_kv*{d}] (serving.kv_pool.pool_leaf_shape)"
        )
    block_len, h_kv = k_pool.shape[1], k_pool.shape[2] // d
    if h % h_kv:
        raise ValueError(
            f"query heads {h} not a multiple of pool KV heads {h_kv}"
        )
    return block_len, h_kv


#: the paged read's spellings (``paged_attention``'s ``gather_impl``)
GATHER_IMPLS = ("dense", "pallas")
#: most query rows a narrow head brings to a kernel step (group x chunk)
#: for the fused kernel to be the unnamed read: a decode tick's. Measured on
#: a v5e (PERF.md section 6, PR 28): at 8 rows a live
#: block cost the kernel 1.0 us and a dead one 0.1 us where the dense
#: gather pays 1.4 us for either, so a decode tick over capacity-wide
#: tables ran three to five times faster through the kernel; at a
#: chunk's 32 rows a block cost the kernel 2.8 us, and a chunk program's
#: table slice is cut to its prompts, so the dense gather won there.
#: Since PR 30 a grid step of the kernel is a tile of blocks (128
#: positions); its chunk-row numbers are in PERF.md section 6, for the
#: issue that may move this. At 16 rows (PR 44: 32 query heads over 2 K/V
#: heads of 128, the folded body's 32 columns; 256 lanes over tables of
#: 2,560 positions, 580 of them live in the mean) a whole decode tick took
#: 34.75 ms through the kernel and 43.60 ms through the dense gather, which
#: copies 2 x 671 MB of float32 a tick (one seed, one traced run each,
#: PERF.md section 6): the bound moved from 8 to 16. Nothing between 17 and
#: 31 rows has been read.
KERNEL_MAX_ROWS = 16
#: most bytes the dense gather may write for a program to take it whatever
#: its rows: the gather copies every row's whole table into HBM as float32
#: (``dense_gather_bytes``), and a tick of 256 lanes over 3,072 positions
#: of one 640-lane latent row would write 2 GB of it a layer
DENSE_GATHER_MAX_BYTES = 1 << 30


def dense_gather_bytes(rows: int, positions: int, row_width: int) -> int:
    """Bytes the dense spelling's float32 copy of one pool takes: ``rows``
    table rows of ``positions`` positions of ``row_width`` values."""
    return 4 * rows * positions * row_width


def default_gather_impl(rows: int = 1, dense_bytes: int = 0) -> str:
    """The paged read a program compiles when nobody names one, from what
    the code can see: the fused kernel (``ops.paged_flash``) where the
    backend is a TPU and a narrow head reads with at most
    ``KERNEL_MAX_ROWS`` query rows (a decode tick), or where the dense
    gather would write more than ``DENSE_GATHER_MAX_BYTES`` (a caller that
    knows its table says so: latent attention's tick reads one narrow head
    with every query head's row); the dense gather for wider row blocks
    (chunked prefill) and on every other backend, where the kernel would
    run in the Pallas interpreter."""
    if jax.default_backend() == "tpu" and (
            rows <= KERNEL_MAX_ROWS or dense_bytes > DENSE_GATHER_MAX_BYTES):
        return "pallas"
    return "dense"


def paged_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    q_positions: jax.Array,
    *,
    scale: Optional[float] = None,
    gather_impl: Optional[str] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    split_s: Optional[int] = None,
) -> jax.Array:
    """Decode/chunk-prefill attention against a block-pooled KV cache.

    The serving engine's cache is a fixed pool of KV blocks
    (``serving.kv_pool``); each request owns a chain of blocks recorded in
    its block-table row, so admission never copies resident requests' KV.
    This op is the read side: gather each request's blocks back into a
    logical [L, H_kv, D] sequence and attend causally at absolute
    positions.

    Args:
      q: ``[B, C, H, D]`` queries — C == 1 for a decode tick, C == chunk
        length for chunked prefill (both use this one op, so the two can
        never diverge on masking).
      k_pool, v_pool: ``[n_blocks, block_len, H_kv·D]`` pooled cache
        (``serving.kv_pool.pool_leaf_shape``: narrow head ``h`` at lanes
        ``[h·D, (h+1)·D)`` of a row; ``H_kv`` is the last axis over
        ``q``'s ``D``). Nothing here reshapes a pool: only the GATHERED
        rows are split into heads, so the leaf stays row-major on the
        chip and no pool-sized copy enters the program.
        ``H_kv < H`` is the GQA layout; query head h reads narrow head
        ``h // (H // H_kv)`` via a grouped einsum — the widened K/V never
        materializes (same trick as the dense decode path).
      block_tables: ``[B, W]`` int32 — request b's logical positions
        ``[w*block_len, (w+1)*block_len)`` live in pool block
        ``block_tables[b, w]``. Entries past the request's allocation
        should point at the engine's trash block; they are masked out
        (their logical positions exceed every query position).
      q_positions: ``[B, C]`` int32 absolute positions of the queries;
        key position j is visible to query i iff ``j <= q_positions[i]``.
      gather_impl: None — the backend and the rows decide
        (``default_gather_impl``: the fused kernel for a decode tick's
        rows on a TPU, the dense gather for a chunk's rows and on every
        other backend); a named spelling wins. ``"pallas"`` — the fused
        gather-attend kernel (``ops.paged_flash``): the kernel reads the
        block table from SMEM (scalar prefetch) and DMAs pool blocks
        HBM→VMEM in chain order, a tile of consecutive chain blocks (128
        positions) a grid step, so the gathered copy never exists and
        tiles past a row's frontier copy and compute nothing; runs the Pallas
        interpreter on non-TPU backends, so both spellings execute
        everywhere. ``"dense"`` — one ``jnp.take`` over the block dim,
        materializing every slot's whole table in HBM as float32 (the
        reference spelling: 147 of a 148 ms decode tick on a v5e,
        PERF.md section 5). Either spelling compiles inside the same
        engine programs, so the program-registry bucket enumeration
        (``compilecache.serving_registry`` over
        ``PagedEngine.chunk_buckets``) covers both. The model passes
        the rule's answer; only kernel-level callers and tests name one.
      k_scale, v_scale: per-(block, slot, head) dequantization scale
        siblings ``[n_blocks, block_len, H_kv]`` — required iff the
        pools are quantized (``serving.kv_pool`` ``kv_dtype="int8"``:
        fp32 multipliers; ``"fp8"``/``"fp8_e5m2"``: int8 power-of-two
        exponents, multiplier ``2**e`` via ``kv_pool.scale_factors``).
        Both spellings dequantize before the softmax statistics; the
        pallas kernel does it block-by-block in VMEM.
      split_s: flash-decoding worker count for the pallas spelling's
        chain sweep (``ops.paged_flash``): None auto-enables when W/B
        crosses the split threshold on a device of two cores, 1 forces
        the single-worker sweep, S > 1 forces S workers. The dense spelling has no chain sweep
        to split — it ignores this knob.

    Returns ``[B, C, H, D]`` in q's dtype. Softmax statistics in fp32.
    """
    from pytorch_distributed_tpu.serving.kv_pool import (
        is_quantized_pool,
        scale_factors,
    )

    b, c, h, d = q.shape
    block_len, h_kv = pool_heads(k_pool, h, d)
    group = h // h_kv
    if gather_impl is None:
        gather_impl = default_gather_impl(rows=group * c)
    elif gather_impl not in GATHER_IMPLS:
        raise ValueError(
            f"gather_impl {gather_impl!r} must be None (the backend and "
            "the rows decide), 'dense' (jnp.take gather) or 'pallas' "
            "(fused ops.paged_flash kernel)"
        )
    quantized = is_quantized_pool(k_pool.dtype)
    if bool(quantized) != (k_scale is not None):
        raise ValueError(
            "quantized (int8/fp8) pools need k_scale/v_scale and float "
            f"pools must not pass them (pool dtype {k_pool.dtype}, "
            f"k_scale {'set' if k_scale is not None else 'None'})"
        )
    if gather_impl == "pallas":
        from pytorch_distributed_tpu.ops.paged_flash import (
            paged_flash_attention,
        )

        return paged_flash_attention(
            q, k_pool, v_pool, block_tables, q_positions, scale=scale,
            k_scale=k_scale, v_scale=v_scale, split_s=split_s,
        )
    w = block_tables.shape[1]
    scale = scale if scale is not None else d ** -0.5
    # Gather the per-request logical KV sequences, then split the
    # GATHERED rows into heads: [B, W*block_len, H_kv, D].
    kg = jnp.take(k_pool, block_tables, axis=0).reshape(
        b, w * block_len, h_kv, d
    )
    vg = jnp.take(v_pool, block_tables, axis=0).reshape(
        b, w * block_len, h_kv, d
    )
    if k_scale is not None:
        # quantized pool: dequantize AFTER the gather (per-row-per-head
        # scale siblings ride the same take; scale_factors turns int8
        # exponents into 2**e multipliers for fp8 pools), keeping the
        # einsums below on fp32 values identical to what the pallas
        # kernel dequantizes in VMEM
        ks = jnp.take(scale_factors(k_scale), block_tables,
                      axis=0).reshape(b, w * block_len, h_kv)
        vs = jnp.take(scale_factors(v_scale), block_tables,
                      axis=0).reshape(b, w * block_len, h_kv)
        kg = kg.astype(jnp.float32) * ks[..., None]  # jaxlint: disable=precision-cast -- quantized-pool dequantization to the fp32 softmax-statistics dtype
        vg = vg.astype(jnp.float32) * vs[..., None]  # jaxlint: disable=precision-cast -- quantized-pool dequantization to the fp32 softmax-statistics dtype
    # Grouped logits directly against the narrow heads (query head
    # h = h_kv_idx*group + g), fp32 statistics like every other path.
    qg = (q.astype(jnp.float32) * scale).reshape(b, c, h_kv, group, d)  # jaxlint: disable=precision-cast -- fp32 softmax statistics by kernel contract
    s = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, kg.astype(jnp.float32)  # jaxlint: disable=precision-cast -- fp32 softmax statistics by kernel contract
    )  # [B, H_kv, G, C, W*bl]
    k_pos = jnp.arange(w * block_len)
    allowed = (
        k_pos[None, None, None, None, :]
        <= q_positions[:, None, None, :, None]
    )
    s = jnp.where(allowed, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = p * allowed  # fully-masked rows → zeros, matching dense/blockwise
    out = jnp.einsum(
        "bhgqk,bkhd->bqhgd", p, vg.astype(jnp.float32)  # jaxlint: disable=precision-cast -- fp32 PV accumulation matches the other attention paths
    )
    return out.reshape(b, c, h, d).astype(q.dtype)


def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    block_size: int = 512,
    q_offset: jax.Array | int = 0,
    k_offset: jax.Array | int = 0,
    remat: bool = True,
) -> jax.Array:
    """Memory-efficient attention: scan KV blocks with online softmax.

    O(Lq·block_size) live memory; with ``remat`` the scan body is
    rematerialized in backward, so training memory stays flat in sequence
    length. Block size should be MXU-friendly (multiple of 128 on TPU; it
    is clamped to the sequence length for small inputs).
    """
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    b, lq, h, d = q.shape
    lk = k.shape[1]
    bs = min(block_size, lk)
    if lk % bs:
        raise ValueError(f"kv length {lk} not divisible by block_size {bs}")
    n_blocks = lk // bs

    k_blocks = k.reshape(b, n_blocks, bs, h, d)
    v_blocks = v.reshape(b, n_blocks, bs, h, d)

    def body(state, inputs):
        i, kb, vb = inputs
        state = attend_block(
            state, q, kb, vb,
            scale=scale, causal=causal,
            q_offset=q_offset, k_offset=k_offset + i * bs,
        )
        return state, None

    if remat:
        body = jax.checkpoint(body)

    init = SoftmaxState.zero(b, lq, h, d)
    idx = jnp.arange(n_blocks)
    state, _ = jax.lax.scan(
        body, init, (idx, jnp.moveaxis(k_blocks, 1, 0), jnp.moveaxis(v_blocks, 1, 0))
    )
    return state.finalize(q.dtype)
