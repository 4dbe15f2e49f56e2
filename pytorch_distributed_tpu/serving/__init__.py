"""Paged-KV serving engine (vLLM/PagedAttention + Orca continuous
batching, applied to the TP-capable JAX serving path).

The round-4/5 serving layer (``models.generate.ContinuousBatcher``) kept
one dense ``max_seq_len`` KV row per decode slot; every admission wrote a
full row — O(per-slot cache), the measured ~30% equilibrium throughput
tax at short outputs (BENCH_LM.md r5). This package replaces the dense
rows with a fixed pool of KV *blocks* plus per-slot block tables:

- ``kv_pool``   — the block allocator (free list, per-request chains,
  deterministic OOM → the caller queues instead of crashing) and the
  pooled cache pytree with its TP placement;
- ``engine``    — the compiled programs: k-batched chunk prefill (one
  insert program admits several requests) and the shared decode tick,
  both donating the pool so updates are in place;
- ``scheduler`` — the continuous scheduler: FIFO admission queue,
  chunked prefill interleaved with decode, slot accounting, and exact
  host-side metrics (occupancy, padding waste, admission latency, queue
  depth, tokens/s).

``models.generate.ContinuousBatcher`` delegates here by default
(``cache_layout="paged"``); the dense layout survives as
``cache_layout="dense"`` for parity tests. ANALYSIS.md "Serving engine"
documents the block layout and the admission path.

Round 12: the read path gains its fused Pallas kernel
(``ops.paged_flash``, no materialized gather: the decode tick's read on
a TPU; the ``jnp.take`` spelling stays the chunk programs' read, and
every program's on another backend:
``ops.attention.default_gather_impl`` chooses) and the pool an int8
quantized variant (``kv_dtype="int8"``, per-row scales, ~2x blocks at
fixed bytes) — ANALYSIS.md "Paged attention kernel & quantized KV".

Round 16: the host runtime — ``scheduler`` splits each tick into a
non-blocking ``dispatch_tick`` and a lagged ``collect_tick``
(``engine.decode_launch``/``decode_collect``), and ``host_worker``
provides the thread pool the off-critical-path host work (JSONL, gate
percentile math) runs on; ``fleet.FleetRouter`` drives the halves
lagged (collect N−1, dispatch N), a lone ``Scheduler.step()`` back to
back — ANALYSIS.md "Async host runtime".

PR 39: a tick crosses the host/device boundary once each way. A launch
of either tick program moves ONE packed int32 operand (a row a job or a
lane: ``engine._chunk_operand``, ``engine._decode_operand``) where it
moved three to seven arrays, and a collect fetches the tokens alone:
``decode``/``decode_launch`` hand back the positions as the host counts
them (launched, plus one on the active lanes).
"""

from pytorch_distributed_tpu.serving.kv_pool import (
    KV_DTYPES,
    SWAP_STATES,
    SWAPPING_IN,
    SWAPPING_OUT,
    TRASH_BLOCK,
    BlockAllocator,
    HostBlockStore,
    HostChain,
    PrefixIndex,
    blocks_needed,
    blocks_needed_suffix,
    init_paged_cache,
    paged_cache_specs,
    pool_block_bytes,
    pool_leaf_shape,
    quantize_kv,
)
from pytorch_distributed_tpu.serving.engine import (
    KVExport,
    PagedEngine,
    PendingSwap,
    PrefixHit,
)
from pytorch_distributed_tpu.serving.host_worker import HostWorkerPool
from pytorch_distributed_tpu.serving.scheduler import (
    Request,
    Scheduler,
    TickHandle,
)

__all__ = [
    "KV_DTYPES",
    "SWAP_STATES",
    "SWAPPING_IN",
    "SWAPPING_OUT",
    "TRASH_BLOCK",
    "BlockAllocator",
    "HostBlockStore",
    "HostChain",
    "PrefixIndex",
    "PrefixHit",
    "blocks_needed",
    "blocks_needed_suffix",
    "init_paged_cache",
    "paged_cache_specs",
    "pool_block_bytes",
    "pool_leaf_shape",
    "quantize_kv",
    "KVExport",
    "PagedEngine",
    "PendingSwap",
    "HostWorkerPool",
    "Request",
    "Scheduler",
    "TickHandle",
]
