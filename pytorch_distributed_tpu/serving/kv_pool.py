"""Block-pooled KV cache: allocator, pool pytree, TP placement.

The paged layout (Kwon et al., SOSP 2023) stores every resident request's
KV in fixed-size blocks drawn from one shared pool
``[n_blocks, block_len, H_kv·D]`` per layer (``pool_leaf_shape``: a
row is one position's heads side by side, so a block is contiguous on
the chip; a 4-D ``[..., H_kv, D]`` leaf is laid out ``n_blocks``-minor
there and every scatter and gather copies the whole pool). A request's
logical positions ``[w*block_len, (w+1)*block_len)`` live in the pool
block its block-table row names at column ``w`` — so admission allocates fresh
blocks and writes ONLY the new prompt's KV (O(prompt)), never touching
resident requests' blocks, where the dense layout wrote a full
``max_seq_len`` row per admission (O(per-slot cache)).

A layer's cache need not be a K and a V pool. Latent attention keeps ONE
pool leaf, ``latent`` ``[n_blocks, block_len, row]``: a token's one row,
read as keys and as values. And a layer may keep state that is a
REQUEST's and not a block's (``SLOT_LEAVES``): per-slot leaves
``[n_slots + 1, ...]``, each of a dtype and trailing shape of its own —
the previous token's latents of a convolution tail (``tail``, a few KB in
the model's dtype), or a linear-attention layer's recurrent state
(``state``, float32, ``[H, D, D]``: megabytes a request) and its
convolutions' last inputs (``conv``). A layer of the second kind has no
pool at all. Whatever moves a request (export, import, swap, the host
tier) moves its blocks of every pool leaf and its row of every per-slot
leaf together (``map_cache``).

Block 0 is the TRASH block: never allocated, it absorbs the scatter
writes of inactive decode lanes (the engine zeroes retired slots' table
rows) so a recycled block can never be corrupted by a dead lane's
garbage write. Gathers through trash entries are masked by the causal
mask — an unallocated entry's logical positions exceed every live query
position.

Allocation is HOST-side and deterministic: a LIFO free list (freshly
freed blocks are reused first — warmer in cache) with an explicit
``None`` on insufficient capacity, so the scheduler queues the request
instead of crashing (the "deterministic OOM → queue" contract).

Round 13 (KV pressure tier; ANALYSIS.md "KV pressure & preemption"):
the pool gains a SECOND tier. A preempted request's chain can leave the
device — a compiled gather pulls its blocks, a d2h copy lands them in a
:class:`HostBlockStore` entry (:class:`HostChain`), and the device
blocks return to the free list — and come back later through h2d + a
donated scatter into a freshly allocated chain. While a chain is in
transit the allocator tracks it through an explicit per-chain state
machine (``resident → swapping-out → host → swapping-in → resident``):
``free``/``release_all`` REFUSE to free a chain mid-swap, so a drain or
teardown racing an in-flight swap is a loud error, never a corrupted
pool.

Round 17 (prefix sharing; ANALYSIS.md "Prefix sharing & copy-on-write"):
blocks gain REFCOUNTS and the pool a radix :class:`PrefixIndex`. A full
immutable block — every slot written with real prompt KV — can be
referenced by several chains at once (``alloc_mixed`` builds a chain
from shared blocks plus fresh suffix blocks) and by the index itself
(one reference per indexed block); ``free`` decrements, and a block
returns to the free list only at refcount zero. That single rule is
what pins a shared block through the round-13 state machine: a
preempted/swapped-out chain's ``free`` can never drag a block another
resident chain (or the index) still references. Chains only ever WRITE
forward of their covered prefix, so shared blocks are read-only by
construction; the one exception — a full-cover hit that must re-prefill
the final prompt token to regenerate its logits row — first duplicates
the boundary block via the engine's compiled ``kv_block_copy`` program
(copy-on-write). int8 pools compose for free: a block id names the same
row range in the int8 pools AND their fp32 scale siblings, so scale
blocks share and refcount in lockstep.
"""

from __future__ import annotations

import math
import threading
from typing import Callable, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp

TRASH_BLOCK = 0

#: chain swap states (``BlockAllocator.state``). A chain with no entry
#: is plain resident; the transit states bracket the d2h/h2d windows.
RESIDENT = "resident"
SWAPPING_OUT = "swapping-out"
SWAPPING_IN = "swapping-in"
SWAP_STATES = (SWAPPING_OUT, SWAPPING_IN)

#: pool dtypes ``init_paged_cache`` accepts: None keeps the model compute
#: dtype (the raw layout); "int8" stores quantized K/V plus per-
#: (block, slot, head) fp32 scales — ~2x the blocks at fixed pool bytes
#: (exactly 2D/(D+4) with fp32 scales); "fp8" (e4m3) / "fp8_e5m2" store
#: fp8 K/V plus per-row int8 power-of-two EXPONENT siblings — 2D/(D+1),
#: 1.97x at the GPT-2 head dim (ANALYSIS.md "Kernel tier 2").
KV_DTYPES = (None, "int8", "fp8", "fp8_e5m2")

#: fp8 storage dtypes by KV_DTYPES name. e4m3 ("fp8") is the default
#: recommendation: 3 mantissa bits halve the rounding error of e5m2's 2,
#: and the per-row exponent sibling supplies all the dynamic range e5m2
#: would otherwise buy.
FP8_DTYPES = {"fp8": jnp.float8_e4m3fn, "fp8_e5m2": jnp.float8_e5m2}


def kv_pool_dtype(kv_dtype: str):
    """Storage jnp dtype for a non-None ``KV_DTYPES`` name."""
    if kv_dtype == "int8":
        return jnp.int8
    if kv_dtype in FP8_DTYPES:
        return FP8_DTYPES[kv_dtype]
    raise ValueError(
        f"kv_dtype {kv_dtype!r} must be one of {KV_DTYPES} (None "
        "keeps the model compute dtype)"
    )


def is_quantized_pool(dtype) -> bool:
    """True iff ``dtype`` is a quantized pool storage dtype (int8 or
    fp8), i.e. the cache tree carries ``key_scale``/``value_scale``
    siblings and the attention read path must dequantize. The pool
    dtype IS the contract — no config flag to drift from it."""
    dt = jnp.dtype(dtype)
    return dt in (jnp.dtype(jnp.int8), jnp.dtype(jnp.float8_e4m3fn),
                  jnp.dtype(jnp.float8_e5m2))


def pool_scale_dtype(pool_dtype):
    """Scale-sibling dtype for a quantized pool dtype: fp32 multipliers
    for int8 pools (the PR 10 layout), int8 power-of-two exponents for
    fp8 pools — 1 byte per row per head, which is where the fp8 layout's
    2D/(D+1) capacity (vs int8's 2D/(D+4)) comes from."""
    return (jnp.float32 if jnp.dtype(pool_dtype) == jnp.dtype(jnp.int8)
            else jnp.int8)


def pool_leaf_shape(n_blocks: int, block_len: int, h_kv: int,
                    head_dim: int, *, scale: bool = False, passes: int = 1):
    """THE shape of a pool leaf, written once: a ``key``/``value`` leaf
    is ``[n_blocks, block_len, H_kv·D]`` — heads flattened into the row,
    head ``h`` at lanes ``[h·D, (h+1)·D)`` — and its scale sibling
    (``scale=True``, quantized pools) ``[n_blocks, block_len, H_kv]``.
    Axis 0 is the block axis and the LAST axis the (contiguous) head
    axis TP shards, for both. The TPU compiler keeps a leaf of this shape
    row-major; given ``[n_blocks, block_len, H_kv, D]`` it picks
    ``n_blocks`` minor-most (D=64 would pad to 128 lanes) and wraps
    every scatter and gather in copies of the whole leaf.

    A looped config (``TransformerConfig.ut_steps`` = ``passes`` > 1)
    keeps a K and a V entry per (pass, layer): its leaf is
    ``[n_blocks, passes, block_len, ...]``, a block ``passes`` times as
    large, so everything that moves blocks by their index on axis 0
    (export, import, swap, block copy, the host tier) moves every pass's
    share with it; the model reads pass ``t`` of block ``b`` as row
    ``b·passes + t`` of the same row-major buffer
    (``models.transformer.Attention``)."""
    lead = (n_blocks,) if passes == 1 else (n_blocks, passes)
    return lead + (block_len, h_kv if scale else h_kv * head_dim)


def _dense_to_pool(shape, n_blocks: int, block_len: int, **kw):
    """``pool_leaf_shape`` from a dense decode-cache leaf's shape at
    batch 1: ``[1, max_seq_len, H_kv, D]``, or ``[passes, 1, max_seq_len,
    H_kv, D]`` from a looped config."""
    passes = shape[0] if len(shape) == 5 else 1
    return pool_leaf_shape(n_blocks, block_len, *shape[-2:], passes=passes,
                           **kw)


#: names of the cache leaves that belong to a REQUEST and not to a block:
#: one row a slot, ``[n_slots + 1, ...]`` in a dtype and trailing shape of
#: the leaf's own. ``tail``: ``models.transformer.CCAttention``'s previous
#: token's latents; ``state`` and ``conv``: ``KDAttention``'s float32
#: recurrent state and its convolutions' last inputs. The last row is the
#: TRASH row, the slot that padding jobs and inactive lanes are given.
SLOT_LEAVES = ("tail", "state", "conv")


def is_slot_leaf(path) -> bool:
    """Whether a cache leaf's tree path names per-slot state."""
    return getattr(path[-1], "key", None) in SLOT_LEAVES


def map_cache(on_blocks, on_slots, cache, *rest):
    """``jax.tree.map`` over a paged cache that tells its two kinds of
    leaf apart: ``on_blocks`` for the pools, indexed by block on axis 0,
    ``on_slots`` for per-slot state, indexed by slot."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf, *more: (
            on_slots if is_slot_leaf(path) else on_blocks)(leaf, *more),
        cache, *rest)


def cache_bytes(cache) -> tuple:
    """(bytes in the pools, bytes in per-slot state) of a paged cache, of
    arrays or of their shapes."""
    totals = [0, 0]
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        totals[is_slot_leaf(path)] += leaf.size * leaf.dtype.itemsize
    return tuple(totals)


def scale_factors(scales: jax.Array) -> jax.Array:
    """fp32 dequantization multipliers from a scale sibling. int8 scale
    siblings (fp8 pools) hold power-of-two EXPONENTS: the multiplier is
    ``2**e`` — exact in fp32, so the scale multiply itself contributes
    zero rounding error and the fp8 cast is the whole error budget.
    fp32 siblings (int8 pools) are the multiplier already."""
    if scales.dtype == jnp.dtype(jnp.int8):
        return jnp.exp2(scales.astype(jnp.float32))  # jaxlint: disable=precision-cast -- int8 exponents widen to the fp32 dequant-multiplier dtype
    return scales


def quantize_rows(xf: jax.Array, pool_dtype):
    """Row-wise quantization math shared by the jnp spelling
    (``quantize_kv``) and the Pallas quantize-on-scatter kernel
    (``ops.paged_flash.paged_quantize_scatter``) — ONE function, so the
    two spellings are bit-equivalent by construction.

    ``xf`` is fp32 ``[..., H_kv, D]``. int8: symmetric, scale =
    amax/127, fp32 scales. fp8: per-row power-of-two exponent
    ``e = ceil(log2(amax / fmax))`` (row amax maps into the top octave
    of the format's range), values stored as ``x * 2**-e`` cast to fp8,
    exponents as int8. Returns ``(q, scales)``."""
    pool_dtype = jnp.dtype(pool_dtype)
    amax = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), 1e-8)
    if pool_dtype == jnp.dtype(jnp.int8):
        # Spelled as a reciprocal MULTIPLY, not amax/127: XLA rewrites
        # constant divisions to reciprocal multiplies under jit, so the
        # divide spelling produces 1-ulp-different scales between an
        # eager caller and the jitted Pallas scatter — the multiply is
        # the same op in both, keeping the spellings bit-equivalent.
        scales = amax * jnp.float32(1.0 / 127.0)
        q = jnp.clip(jnp.round(xf / scales[..., None]), -127, 127)
        return q.astype(jnp.int8), scales
    fmax = float(jnp.finfo(pool_dtype).max)
    e = jnp.clip(jnp.ceil(jnp.log2(amax / fmax)), -126.0, 126.0)
    q = (xf * jnp.exp2(-e)[..., None]).astype(pool_dtype)
    return q, e.astype(jnp.int8)


def quantize_kv(x: jax.Array, pool_dtype=jnp.int8):
    """Per-(token, head) quantization of a K or V chunk to a pool
    storage dtype (int8 default — the PR 10 signature; fp8 via
    ``pool_dtype=jnp.float8_e4m3fn``/``e5m2``).

    ``x`` is ``[..., H_kv, D]``; returns ``(q same shape, scales
    [..., H_kv])`` in the ``quantize_rows`` layout — one scale per
    written KV row, the granularity the paged scatter writes at (a
    per-BLOCK scalar cannot be maintained under incremental chunk/
    decode writes without requantizing the block's resident rows).
    Dequantization is ``q * scale_factors(scales)`` (``ops.paged_flash``
    does it in VMEM; the dense gather right after the take)."""
    xf = x.astype(jnp.float32)  # jaxlint: disable=precision-cast -- fp32 quantization statistics regardless of compute dtype
    return quantize_rows(xf, pool_dtype)


def blocks_needed(prompt_len: int, max_new_tokens: int, block_len: int,
                  chunk: int) -> int:
    """Blocks a request must own before admission: enough to hold the
    chunk-PADDED prefill writes (the final chunk's padding garbage lands
    in owned blocks, dead until decode overwrites it — same argument as
    the dense layout's right-padding) and the decode frontier
    ``prompt_len + max_new_tokens``."""
    return blocks_needed_suffix(0, prompt_len, max_new_tokens, block_len,
                                chunk)


def blocks_needed_suffix(covered: int, prompt_len: int,
                         max_new_tokens: int, block_len: int,
                         chunk: int) -> int:
    """``blocks_needed`` generalized to a prefix-cache hit: prefill
    starts at ``covered`` (a block multiple, or prompt_len-1 on the
    copy-on-write full-cover path), so the chunk padding extends from
    THERE — ``covered + ceil((L-covered)/chunk)*chunk`` — not from 0.
    The whole-chain block count (shared prefix blocks included); the
    caller allocates ``need - covered // block_len`` fresh ones."""
    padded_end = covered + math.ceil((prompt_len - covered) / chunk) * chunk
    return math.ceil(max(padded_end, prompt_len + max_new_tokens)
                     / block_len)


class BlockAllocator:
    """Free-list allocator over pool block ids ``1..n_blocks-1`` (0 is
    the trash block) with per-owner chain tracking and per-block
    REFCOUNTS (round 17: prefix sharing).

    ``alloc`` is all-or-nothing: it returns the chain or ``None`` with
    the free list untouched — the deterministic OOM signal the scheduler
    turns into queueing. ``free`` decrements every chained block's
    refcount and returns only the blocks that hit ZERO, LIFO, so the
    next allocation reuses the most recently freed blocks (asserted in
    tests/test_paged_serving.py). ``alloc_mixed`` builds a chain from
    already-referenced SHARED blocks (each incref'd) plus fresh suffix
    blocks — the prefix-cache admission; ``incref``/``decref`` are the
    :class:`PrefixIndex`'s own reference on the blocks it retains.
    Refcount violations (decref of a dead block == double free) are
    loud RuntimeErrors, never silent corruption."""

    def __init__(self, n_blocks: int):
        if n_blocks < 2:
            raise ValueError(
                f"n_blocks must be >= 2 (block 0 is the trash block), "
                f"got {n_blocks}"
            )
        self.n_blocks = n_blocks
        # LIFO: pop from the end; initialized so the FIRST allocations
        # hand out 1, 2, 3, ... (deterministic, test-friendly order).
        self._free: List[int] = list(range(n_blocks - 1, 0, -1))
        self._chains: Dict[int, List[int]] = {}
        # block id -> refcount; a block is live iff it has an entry.
        self._refs: Dict[int, int] = {}
        # exact sharing counters (the bench's pool-blocks-per-request)
        self.fresh_allocated = 0
        self.shared_reused = 0
        # owner -> transit state; absent == resident. The swap windows
        # (engine.swap_out_begin → swap_out_finish, swap_in_chain) set
        # and clear these; free()/release_all() refuse mid-swap owners.
        self._states: Dict[int, str] = {}
        #: optional transition observer ``(event, owner, info)`` fired on
        #: alloc / free / swap-state changes — chain identity for the
        #: round-14 request-lifecycle traces (``telemetry.reqtrace``; the
        #: scheduler installs an adapter mapping owner slot → rid). Must
        #: never raise into the allocator; observers are forensics.
        self.on_transition: Optional[Callable[[str, int, dict], None]] = None
        #: optional block-lifecycle sanitizer shadow
        #: (``analysis.blocksan``; installed by ``BlockSanitizer.attach``
        #: under ``PDT_BLOCKSAN=1``). Unlike ``on_transition`` it also
        #: sees every incref/decref, BEFORE the allocator's own checks,
        #: so a double free / pinned free is recorded even though the
        #: call still raises. ``None`` costs one attribute test per op.
        self.sanitizer = None

    def _notify(self, event: str, owner: int, **info) -> None:
        if self.on_transition is not None:
            self.on_transition(event, owner, info)

    def census_decls(self):
        from pytorch_distributed_tpu.telemetry.census import Decl

        return [
            Decl("_free", "fixed", cap=lambda a: a.n_blocks - 1,
                 why="free list over the fixed pool (block 0 is TRASH)"),
            Decl("_chains", "fixed", cap=lambda a: a.n_blocks - 1,
                 why="one chain per owner, every chain holds ≥ 1 block "
                     "of the fixed pool"),
            Decl("_refs", "fixed", cap=lambda a: a.n_blocks - 1,
                 why="refcount per allocated block of the fixed pool"),
            Decl("_states", "fixed", cap=lambda a: a.n_blocks - 1,
                 why="swap state per owner-with-chain (subset of "
                     "_chains); entries cleared on free/clear_state"),
        ]

    @property
    def available(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return (self.n_blocks - 1) - len(self._free)

    def chain(self, owner: int) -> List[int]:
        return list(self._chains.get(owner, ()))

    def owners(self) -> List[int]:
        """Owners currently holding a chain (drain accounting / teardown)."""
        return list(self._chains)

    # ---- chain swap states (round 13: the host-offload tier) ----

    def state(self, owner: int) -> str:
        """The chain's swap state — ``resident`` unless a swap window is
        open on it (owners without a chain are resident by definition:
        nothing to protect)."""
        return self._states.get(owner, RESIDENT)

    def set_state(self, owner: int, state: str) -> None:
        """Open a swap window on ``owner``'s chain. Only live chains can
        enter transit — state on a chainless owner is a caller bug."""
        if state not in SWAP_STATES:
            raise ValueError(
                f"state {state!r} must be one of {SWAP_STATES} "
                "(use clear_state to return to resident)"
            )
        if owner not in self._chains:
            raise ValueError(
                f"owner {owner} holds no chain to mark {state}"
            )
        self._states[owner] = state
        if self.sanitizer is not None:
            self.sanitizer.on_state(owner, state)
        self._notify("state", owner, state=state,
                     n_blocks=len(self._chains[owner]))

    def clear_state(self, owner: int) -> None:
        """Close the swap window (back to resident). Idempotent."""
        if self._states.pop(owner, None) is not None:
            if self.sanitizer is not None:
                self.sanitizer.on_state(owner, None)
            self._notify("state", owner, state=RESIDENT,
                         n_blocks=len(self._chains.get(owner, ())))

    def swapping(self) -> List[int]:
        """Owners with an open swap window — the set ``begin_drain``
        must wait on before teardown."""
        return sorted(self._states)

    def alloc(self, owner: int, n: int) -> Optional[List[int]]:
        """Allocate ``n`` fresh blocks for ``owner`` (a slot id). Returns
        the chain, or ``None`` (state unchanged) when fewer than ``n``
        blocks are free."""
        if n < 1:
            raise ValueError(f"alloc needs n >= 1, got {n}")
        return self.alloc_mixed(owner, [], n)

    def alloc_mixed(self, owner: int, shared: List[int],
                    n_new: int) -> Optional[List[int]]:
        """Build ``owner``'s chain from ``shared`` already-live blocks
        (each incref'd — the prefix-cache hit) followed by ``n_new``
        fresh ones. All-or-nothing: ``None`` with NOTHING incref'd when
        the free list cannot supply the fresh suffix. Sharing a block
        that is not currently referenced (evicted index entry, stale id)
        is a caller bug and raises."""
        if n_new < 0 or (n_new == 0 and not shared):
            raise ValueError(
                f"alloc_mixed needs shared blocks or n_new >= 1, got "
                f"shared={len(shared)} n_new={n_new}"
            )
        if owner in self._chains:
            raise ValueError(f"owner {owner} already holds a chain")
        if len(self._free) < n_new:
            return None  # deterministic OOM: the caller queues
        for b in shared:
            if b not in self._refs:
                raise ValueError(
                    f"cannot share block {b}: not live (evicted or "
                    "never allocated)"
                )
        for b in shared:
            self._refs[b] += 1
        fresh = [self._free.pop() for _ in range(n_new)]
        for b in fresh:
            self._refs[b] = 1
        self.fresh_allocated += n_new
        self.shared_reused += len(shared)
        chain = list(shared) + fresh
        self._chains[owner] = chain
        if self.sanitizer is not None:
            self.sanitizer.on_alloc(owner, list(shared), list(fresh))
        self._notify("alloc", owner, n_blocks=len(chain),
                     shared=len(shared), free=len(self._free))
        return list(chain)

    def ref(self, block: int) -> int:
        """The block's live refcount (0 = not allocated/indexed)."""
        return self._refs.get(block, 0)

    def incref(self, block: int) -> None:
        """Add one reference to a LIVE block — the PrefixIndex's claim
        on a block it retains past its chain's free."""
        if self.sanitizer is not None:
            self.sanitizer.on_incref(block)
        if block not in self._refs:
            raise ValueError(f"incref of dead block {block}")
        self._refs[block] += 1

    def decref(self, block: int) -> bool:
        """Drop one reference; at zero the block returns to the free
        list (True). Decref of a dead block is a DOUBLE FREE and raises
        — the invariant that makes shared-block recycling impossible to
        get silently wrong."""
        if self.sanitizer is not None:
            self.sanitizer.on_decref(block)
        n = self._refs.get(block)
        if n is None:
            raise RuntimeError(
                f"double free: block {block} has no live references"
            )
        if n == 1:
            del self._refs[block]
            self._free.append(block)
            return True
        self._refs[block] = n - 1
        return False

    @property
    def shared_blocks(self) -> int:
        """Blocks currently referenced more than once (chains and/or
        the prefix index) — the sharing the capacity A/B measures."""
        return sum(1 for n in self._refs.values() if n > 1)

    def free(self, owner: int) -> None:
        """Decref ``owner``'s chain; blocks reaching refcount zero
        return to the free list (LIFO reuse). Freeing an owner without
        a chain is a no-op — retirement paths may race a request that
        never got blocks. Freeing a chain with an OPEN SWAP WINDOW is
        refused loudly: the d2h/h2d in flight still reads/writes those
        blocks, and recycling them would corrupt whichever stream reuses
        them first (the drain-while-swapping race;
        tests/test_pressure.py). A block another chain or the prefix
        index still references SURVIVES this free — the pinning rule
        that lets a preempted chain leave without dragging shared
        prefix blocks."""
        state = self._states.get(owner)
        if self.sanitizer is not None:
            self.sanitizer.on_free(owner, state)
        if state is not None:
            raise RuntimeError(
                f"owner {owner}'s chain is {state}: finish or abort the "
                "swap before freeing (begin_drain waits on in-flight "
                "swaps for exactly this reason)"
            )
        chain = self._chains.pop(owner, None)
        if chain:
            freed = sum(self.decref(b) for b in reversed(chain))
            self._notify("free", owner, n_blocks=len(chain),
                         freed=freed, free=len(self._free))


def init_paged_cache(config, params, n_blocks: int, block_len: int,
                     kv_dtype: Optional[str] = None,
                     n_slots: Optional[int] = None):
    """Zero block-pooled KV cache for ``TransformerLM(config)``.

    A config whose attention keeps state a REQUEST (``config.slot_state``)
    gets per-slot leaves (``SLOT_LEAVES``) beside or instead of a layer's
    pools, each ``[n_slots + 1, ...]`` in the dtype and trailing shape the
    dense cache gives it at batch 1 (a convolution tail in the model's
    dtype, a float32 recurrent state; the last row is the trash row), and
    so needs ``n_slots``.

    Shapes come from ``eval_shape`` on the dense decode cache at batch 1
    (nothing is traced into a compiled program), then every
    ``[1, max_seq_len, H_kv, D]`` leaf is re-shaped into a
    ``[n_blocks, block_len, H_kv·D]`` pool (``pool_leaf_shape``; a looped
    config's ``[passes, 1, ...]`` leaf into ``[n_blocks, passes, ...]``; latent
    attention's one ``[1, max_seq_len, 1, row]`` leaf into ``[n_blocks,
    block_len, row]``) — the
    per-layer head count and dtype (GQA narrows H_kv; TP shards the
    flattened head axis by placement, ``H_kv/tp·D`` contiguous lanes a
    shard) carry over, so the pool works for every config the dense
    cache does.

    ``kv_dtype="int8"`` stores the pools quantized: each ``key``/
    ``value`` leaf becomes int8 and gains a ``key_scale``/``value_scale``
    sibling ``[n_blocks, block_len, H_kv]`` fp32 (the ``quantize_kv``
    layout — one scale per written row per head, so quantize-on-scatter
    and TP head-sharding both work unchanged). ``"fp8"`` (e4m3) /
    ``"fp8_e5m2"`` are the same layout at 1-byte values with 1-byte
    int8 EXPONENT siblings (``pool_scale_dtype``) — 2D/(D+1) capacity
    vs bf16 where int8+fp32 scales is 2D/(D+4). The attention read path
    dequantizes (in VMEM where it is the fused kernel, after the take
    where it gathers dense); ``models.transformer.Attention`` switches
    to quantize-on-scatter off the pool dtype alone, so the cache pytree
    IS the whole contract — no config flag to drift from it.
    """
    from pytorch_distributed_tpu.models.generate import init_cache

    if block_len < 1:
        raise ValueError(f"block_len must be >= 1, got {block_len}")
    if kv_dtype not in KV_DTYPES:
        raise ValueError(
            f"kv_dtype {kv_dtype!r} must be one of {KV_DTYPES} (None "
            "keeps the model compute dtype)"
        )
    shapes = jax.eval_shape(
        lambda p: init_cache(config, p, 1), params
    )
    if getattr(config, "slot_state", False) and (
            n_slots is None or kv_dtype is not None):
        raise ValueError(
            "this config keeps state a request beside its blocks (a "
            "convolution tail, a recurrent state): init_paged_cache needs "
            f"n_slots=, and a quantized pool (kv_dtype {kv_dtype!r}) is not "
            "supported with it")
    if getattr(config, "latent_row_width", 0) and kv_dtype is not None:
        raise ValueError(
            "latent attention's pool is one row a token, its own key and "
            f"value: a quantized pool (kv_dtype {kv_dtype!r}) has scale "
            "siblings a K/V head, which that row has not")
    if kv_dtype is None:
        return map_cache(
            lambda s: jnp.zeros(
                _dense_to_pool(s.shape, n_blocks, block_len), s.dtype),
            lambda s: jnp.zeros((n_slots + 1,) + s.shape[1:], s.dtype),
            shapes)

    from collections.abc import Mapping

    pool_dt = kv_pool_dtype(kv_dtype)
    sc_dt = pool_scale_dtype(pool_dt)

    def _quantized(node):
        # each layer's attention cache is a {"key": [1, L, H_kv, D],
        # "value": ...} pair; replace it with quantized pools + scale
        # siblings (fp32 multipliers for int8, int8 exponents for fp8)
        if isinstance(node, Mapping) and set(node) == {"key", "value"}:
            out = {}
            for name in ("key", "value"):
                s = node[name]
                out[name] = jnp.zeros(
                    _dense_to_pool(s.shape, n_blocks, block_len), pool_dt,
                )
                out[name + "_scale"] = jnp.zeros(
                    _dense_to_pool(s.shape, n_blocks, block_len, scale=True),
                    sc_dt,
                )
            return out
        if isinstance(node, Mapping):
            return {k: _quantized(node[k]) for k in node}
        raise ValueError(
            f"unexpected cache tree layout for kv_dtype={kv_dtype!r}: "
            "expected nested dicts ending in {'key', 'value'} leaf "
            f"pairs, got {type(node).__name__}"
        )

    return _quantized(shapes)


def pool_block_bytes(config, params, block_len: int,
                     kv_dtype: Optional[str] = None) -> int:
    """HBM bytes ONE pool block costs across every layer that owns a pool
    (K + V + any scale siblings, or latent attention's one row; a layer
    whose cache is per-slot state alone adds nothing) — the unit a fixed
    byte budget is divided by to compare pool dtypes' capacity. Pure
    ``eval_shape`` arithmetic; nothing is allocated."""
    shapes = jax.eval_shape(
        lambda p: init_paged_cache(config, p, 2, block_len,
                                   kv_dtype=kv_dtype, n_slots=1),
        params,
    )
    return cache_bytes(shapes)[0] // 2


def pool_slot_bytes(config, params) -> int:
    """HBM bytes ONE slot costs across every layer in state that belongs
    to a request and not to a block (``SLOT_LEAVES``, each in its own
    dtype): ``pool_block_bytes``' sibling. 0 for a config whose cache is
    block chains only."""
    shapes = jax.eval_shape(
        lambda p: init_paged_cache(config, p, 2, 1, n_slots=1), params)
    return cache_bytes(shapes)[1] // 2


def paged_cache_specs(config, cache):
    """TP placement for the pool: the HEAD axis — the last of every
    leaf, ``H_kv·D`` lanes of a value leaf (heads contiguous, so a shard
    holds ``H_kv/tp`` whole heads) and ``H_kv`` of a scale sibling —
    shards over the model axis, exactly the slice each shard's Attention
    computes; the dense cache's rule (``models.generate._cache_specs``)
    with its trailing D entry folded into the head axis."""
    from jax.sharding import PartitionSpec as P

    return jax.tree.map(
        lambda leaf: P(*[None] * (leaf.ndim - 1), config.model_axis), cache
    )


# ---------------------------------------------------------------------------
# prefix index (round 17: radix reuse over the block pool)
# ---------------------------------------------------------------------------


class _PrefixNode:
    """One full block in the radix tree: ``key`` is the block's token
    tuple (the edge from its parent), ``block`` the pool block id."""

    __slots__ = ("key", "block", "parent", "children", "last_used")

    def __init__(self, key, block, parent):
        self.key = key
        self.block = block
        self.parent = parent
        self.children: Dict[tuple, "_PrefixNode"] = {}
        self.last_used = 0


class PrefixIndex:
    """Radix index over FULL immutable pool blocks, keyed by token-ID
    paths (PagedAttention's prefix-sharing story, SOSP'23 §4.3 applied
    block-granular).

    Each node is one block: the edge from its parent is the tuple of
    ``block_len`` token ids written into it, so a path from the root
    spells a prefix in whole blocks. ``lookup`` walks a prompt block by
    block and returns the longest matched chain of block ids —
    admission increfs those via ``BlockAllocator.alloc_mixed`` and
    allocates only the suffix. ``insert`` retains blocks as their
    chains fill past block boundaries (one index reference each, via
    ``incref``); duplicate paths keep the FIRST block (a second chain
    prefilling the same prefix keeps exclusive ownership of its own
    copy, which frees normally at retire). Only full blocks enter:
    every slot holds real prefill-written KV, so an indexed block is
    immutable by the chains-write-forward rule.

    Eviction is LRU over refcount-1 LEAVES only: a block a resident
    chain still shares (ref > 1) is pinned, and an interior node must
    outlive its descendants (a matched path must be physically complete
    — attention reads the whole chain). ``evict`` is the pool-pressure
    valve the engine pulls BEFORE the round-13 pressure tier preempts a
    live chain: dropping cache is always cheaper than parking a
    stream."""

    def __init__(self, block_len: int, allocator: BlockAllocator):
        if block_len < 1:
            raise ValueError(f"block_len must be >= 1, got {block_len}")
        self.block_len = block_len
        self.allocator = allocator
        self._children: Dict[tuple, _PrefixNode] = {}  # root edges
        self._nodes = 0
        self._clock = 0
        # exact counters (Scheduler.metrics / kind="prefix" JSONL)
        self.lookups = 0
        self.hits = 0
        self.inserts = 0
        self.evictions = 0

    def __len__(self) -> int:
        """Indexed blocks (== index-held references)."""
        return self._nodes

    def census_decls(self):
        from pytorch_distributed_tpu.telemetry.census import Decl

        return [
            Decl(".", "fixed", cap=lambda ix: ix.allocator.n_blocks - 1,
                 why="every node holds an incref on a distinct live pool "
                     "block, so the radix tree cannot outgrow the pool — "
                     "the LRU evict path is how it shrinks under "
                     "pressure (the round-21 *proven* bound)"),
            Decl("_children", "fixed",
                 cap=lambda ix: ix.allocator.n_blocks - 1,
                 why="root edges are a subset of nodes"),
        ]

    @staticmethod
    def _key(tokens, start: int, stop: int) -> tuple:
        return tuple(int(t) for t in tokens[start:stop])

    def lookup(self, tokens) -> List[int]:
        """Longest full-block prefix of ``tokens`` present in the index
        — the matched chain of pool block ids, possibly empty. Bumps
        LRU recency along the matched path."""
        self._clock += 1
        self.lookups += 1
        bl = self.block_len
        out: List[int] = []
        children = self._children
        for i in range(len(tokens) // bl):
            node = children.get(self._key(tokens, i * bl, (i + 1) * bl))
            if node is None:
                break
            node.last_used = self._clock
            out.append(node.block)
            children = node.children
        if out:
            self.hits += 1
        return out

    def insert(self, tokens, chain: List[int], upto: int) -> int:
        """Retain the full blocks covering ``tokens[:upto]`` (floored to
        whole blocks) under their token path; ``chain`` maps block index
        to pool block id. New nodes incref their block (the index's own
        reference); an existing node keeps its block — dedup, nothing
        incref'd. Returns the number of newly indexed blocks."""
        self._clock += 1
        bl = self.block_len
        nb = min(upto, len(tokens)) // bl
        if nb > len(chain):
            raise ValueError(
                f"insert upto {upto} needs {nb} blocks but the chain "
                f"has {len(chain)}"
            )
        added = 0
        children = self._children
        parent = None
        for i in range(nb):
            key = self._key(tokens, i * bl, (i + 1) * bl)
            node = children.get(key)
            if node is None:
                self.allocator.incref(chain[i])
                node = _PrefixNode(key, chain[i], parent)
                children[key] = node
                self._nodes += 1
                added += 1
                self.inserts += 1
            node.last_used = self._clock
            children = node.children
            parent = node
        return added

    def _evictable(self) -> List[_PrefixNode]:
        out = []
        stack = list(self._children.values())
        while stack:
            node = stack.pop()
            if not node.children:
                if self.allocator.ref(node.block) == 1:
                    out.append(node)
            else:
                stack.extend(node.children.values())
        return out

    def evict(self, n: int) -> int:
        """Free up to ``n`` blocks: LRU-oldest refcount-1 leaves first,
        cascading into parents as they become leaves. Returns blocks
        actually returned to the free list (0 when everything left is
        pinned by a live chain or is an interior node)."""
        freed = 0
        while freed < n:
            leaves = self._evictable()
            if not leaves:
                break
            node = min(leaves, key=lambda nd: nd.last_used)
            self._remove(node)
            freed += 1
        return freed

    def _remove(self, node: _PrefixNode) -> None:
        siblings = (node.parent.children if node.parent is not None
                    else self._children)
        del siblings[node.key]
        self._nodes -= 1
        self.evictions += 1
        self.allocator.decref(node.block)

    def clear(self) -> int:
        """Drop every index reference (teardown / ``release_all``):
        blocks no chain shares return to the free list. Returns the
        count dropped."""
        dropped = 0
        stack = list(self._children.values())
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            self.allocator.decref(node.block)
            dropped += 1
        self._children = {}
        self._nodes = 0
        return dropped

    def metrics(self) -> dict:
        return {
            "prefix_index_blocks": self._nodes,
            "prefix_lookups": self.lookups,
            "prefix_hits": self.hits,
            "prefix_hit_rate": (
                self.hits / self.lookups if self.lookups else 0.0
            ),
            "prefix_inserts": self.inserts,
            "prefix_evictions": self.evictions,
        }


# ---------------------------------------------------------------------------
# host tier (round 13: pressure offload)
# ---------------------------------------------------------------------------


class HostChain(NamedTuple):
    """One request's KV chain at rest in host RAM: the pool pytree
    sliced to the chain (numpy leaves, logical positions in chain order)
    plus the slot's logits row — the next token's distribution, without
    which a swapped-in decode lane could not resume bit-exact. Block ids
    do NOT travel (same contract as the fleet handoff's ``KVExport``):
    swap-in allocates a fresh chain and remaps the table."""

    blocks: object  # cache-shaped pytree of numpy [n_blocks, block_len, ...]
    logits_row: object  # numpy [vocab_size]
    n_blocks: int
    block_len: int
    nbytes: int


class HostBlockStore:
    """Host-RAM tier for swapped-out chains, keyed by request id.

    Plain pageable host memory stands in for pinned buffers on this
    backend (jax's d2h lands in numpy either way); the store's job is
    bookkeeping with teeth: exact byte accounting, an optional
    ``max_bytes`` budget (``put`` returns False when a chain does not
    fit — the caller's cue to recompute instead), and a lock so a
    future threaded swap path inherits a safe store
    (``analysis/rules_threads.py`` vets the discipline)."""

    def __init__(self, max_bytes: Optional[int] = None):
        if max_bytes is not None and max_bytes < 1:
            raise ValueError(f"max_bytes must be >= 1, got {max_bytes}")
        self.max_bytes = max_bytes
        self._chains: Dict[int, HostChain] = {}
        self._bytes = 0
        self._lock = threading.Lock()

    @property
    def bytes_used(self) -> int:
        with self._lock:
            return self._bytes

    def has_room(self, nbytes: int) -> bool:
        """Whether a chain of ``nbytes`` would fit the budget now — the
        swap-vs-recompute decision consults this BEFORE gathering, so a
        full store steers preemption to recompute instead of failing the
        swap mid-flight."""
        if self.max_bytes is None:
            return True
        with self._lock:
            return self._bytes + nbytes <= self.max_bytes

    def put(self, rid: int, chain: HostChain) -> bool:
        """Store one chain; False (store unchanged) when over budget.
        Storing twice for one rid is a caller bug — a parked request has
        exactly one host copy."""
        with self._lock:
            if rid in self._chains:
                raise ValueError(f"rid {rid} already has a host chain")
            if (self.max_bytes is not None
                    and self._bytes + chain.nbytes > self.max_bytes):
                return False
            self._chains[rid] = chain
            self._bytes += chain.nbytes
            return True

    def get(self, rid: int) -> HostChain:
        with self._lock:
            return self._chains[rid]

    def pop(self, rid: int) -> HostChain:
        """Remove and return — called only AFTER a successful swap-in,
        so a failed h2d leaves the host copy intact and retryable."""
        with self._lock:
            chain = self._chains.pop(rid)
            self._bytes -= chain.nbytes
            return chain

    def __contains__(self, rid: int) -> bool:
        with self._lock:
            return rid in self._chains

    def __len__(self) -> int:
        with self._lock:
            return len(self._chains)

    def rids(self) -> List[int]:
        with self._lock:
            return sorted(self._chains)

    def census_decls(self):
        from pytorch_distributed_tpu.telemetry.census import Decl

        return [
            Decl("_chains", "live",
                 why="one host copy per PARKED request (a strict subset "
                     "of live requests); put() additionally refuses past "
                     "max_bytes when a byte budget is set"),
        ]
