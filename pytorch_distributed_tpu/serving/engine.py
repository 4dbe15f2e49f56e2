"""The paged serving engine: compiled chunk-prefill + decode programs.

Two program families over one block-pooled KV cache
(``serving.kv_pool``), both donating the pool and the logits buffer so
every update is in place:

- **chunk prefill** (``run_chunks``): processes one fixed-length chunk
  of up to ``k`` requests' prompts in ONE program — the batched
  admission insert. Each job carries its own start position and a slice
  of its block table, so the program's cost is O(k · chunk · prompt
  bucket): independent of the pool size, the slot count, and
  ``max_seq_len`` — the whole point of the paged layout (the dense
  layout's admission wrote a full ``max_seq_len`` row; see ISSUE r6 /
  ANALYSIS.md "Serving engine"). Programs are cached per (padded k,
  table-slice width) — both padded to powers of two to bound compile
  count.
- **decode** (``decode``): one token for every slot, exactly the dense
  ``_step_body`` shape but attending through the block table
  (``ops.attention.paged_attention`` — the fused ``ops.paged_flash``
  kernel where the backend is a TPU and the dense gather elsewhere;
  the chunk programs gather dense everywhere, their rows are a chunk's
  and their tables cut to the prompts: ``ops.attention.
  default_gather_impl`` chooses, a program at a time; with
  ``kv_dtype="int8"`` the pool is quantized with per-row scales).
  Inactive lanes' writes are routed to the trash block by host-side
  table masking, so recycled blocks can never be corrupted by a dead
  lane.

A tick crosses the host/device boundary once each way. Every host-built
operand of the two programs is int32 or a flag and a row a job or a
lane, so a launch packs them into ONE int32 matrix (``_chunk_operand``:
``tokens | table | start, slot, is_last, last_idx (, length)`` a job;
``_decode_operand``: ``masked table | position, active`` a lane), moves
it with one explicit ``jax.device_put`` (a transfer costs its arrays,
not its bytes) and the program slices it apart (``_columns`` is the one
layout both read); the warm-ups build theirs through the same builders.
A collect fetches the tick's tokens (and expert counts) in one
``device_get``; the positions a tick leaves behind are the launched ones
plus one on the active lanes, which the host counts itself.

Tensor parallelism reuses the dense serving path's machinery: params
placed by ``models.generate._tp_rules``, the pool head-sharded by
``kv_pool.paged_cache_specs``, programs wrapped in ``shard_map`` over the
model axis with replicated logits/sampling (token streams identical on
every shard).
"""

from __future__ import annotations

import contextlib
import dataclasses
import re
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_distributed_tpu.serving.kv_pool import (
    SWAPPING_IN,
    SWAPPING_OUT,
    TRASH_BLOCK,
    BlockAllocator,
    HostBlockStore,
    HostChain,
    PrefixIndex,
    blocks_needed,
    blocks_needed_suffix,
    cache_bytes,
    init_paged_cache,
    is_slot_leaf,
    map_cache,
    paged_cache_specs,
)
from pytorch_distributed_tpu.compilecache.aot import (
    program_load,
    program_load_if,
)
from pytorch_distributed_tpu.ops import attention as attention_ops
from pytorch_distributed_tpu.telemetry import spans


def _pow2_bucket(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def _columns(matrices: Dict[str, int], scalars: Tuple[str, ...]):
    """The layout of a launch's ONE int32 operand, a row a job or a lane:
    ``({field: its columns}, width)``. A matrix field (``{name: columns}``,
    in order) is a slice of the row, a scalar field the one column behind
    them. The builder and the program's body both read a row through
    ``_fields`` with this, so they cannot disagree about a column."""
    cols, at = {}, 0
    for name, width in matrices.items():
        cols[name] = slice(at, at + width)
        at += width
    for name in scalars:
        cols[name] = at
        at += 1
    return cols, at


def _fields(packed, cols: dict) -> dict:
    """``{field: packed[:, its columns]}``: views to fill on the host
    (NumPy), static slices of the operand inside a program."""
    return {name: packed[:, col] for name, col in cols.items()}


def _put_args(host: np.ndarray) -> dict:
    """``engine.*.put``'s arguments: how many host arrays the one transfer
    moves (one: the packed operand) and their bytes (latency or
    bandwidth)."""
    return {"arrays": 1, "bytes": host.nbytes}


def _expert_counts(stats, num_layers: int):
    """``[expert layers, n_experts]`` int32 from what the expert layers of
    one ``apply`` sowed (``moe_stats/expert_tokens``), in layer order."""
    rows = [stats[f"block{i}"]["moe"]["expert_tokens"][0]
            for i in range(num_layers)
            if "expert_tokens" in stats.get(f"block{i}", {}).get("moe", {})]
    return jnp.stack(rows) if rows else jnp.zeros((0, 0), jnp.int32)


class ChunkJob(NamedTuple):
    """One prompt chunk to prefill: ``tokens`` is the chunk (padded to
    the engine's chunk length with zeros), ``start`` its absolute
    position, ``last_idx`` the in-chunk index of the prompt's final real
    token (meaningful only when ``is_last``)."""

    slot: int
    tokens: np.ndarray  # [chunk] int32
    start: int
    is_last: bool
    last_idx: int


class PendingSwap(NamedTuple):
    """A swap-out mid-flight: the chain's blocks gathered on-device with
    their async d2h copy started (``swap_out_begin``), awaiting the host
    materialization + store commit (``swap_out_finish``). While one of
    these exists the chain is ``swapping-out`` in the allocator — its
    blocks stay owned and its slot must not be recycled."""

    slot: int
    chain_len: int
    blocks: object  # cache-shaped pytree, [n_pad, block_len, ...] device
    logits_row: object  # [vocab_size] device


class PrefixHit(NamedTuple):
    """One shared-prefix admission (``PagedEngine.admit_shared``):
    ``covered`` tokens ride existing pool blocks (prefill starts there),
    ``shared`` of the chain's blocks are incref'd index blocks, and
    ``cow`` marks the full-cover path that copy-on-write duplicated the
    boundary block before re-prefilling the final prompt token."""

    covered: int
    shared: int
    cow: bool
    evicted: int  # index blocks dropped to make room for this admission


class KVExport(NamedTuple):
    """One request's KV detached from its source pool — the unit of the
    fleet layer's prefill→decode handoff (``fleet/``; ANALYSIS.md
    "Serving fleet").

    ``blocks`` is the pool pytree sliced to the request's chain (each
    leaf ``[n_blocks, block_len, H_kv·D]``, logical positions in chain
    order) and ``logits_row`` the final-chunk logits — the distribution
    of the request's first decoded token, which the importing engine's
    decode tick samples from. Block ids do NOT travel: the importer
    allocates a fresh chain in its own pool and remaps the block table,
    so exporter and importer pools never need to agree on layout — only
    on geometry (``block_len`` and the cache tree structure, both checked
    on import)."""

    blocks: object  # pool pytree sliced to the chain: [n, block_len, ...]
    logits_row: object  # [vocab_size] f32
    n_blocks: int
    block_len: int


class PagedEngine:
    """Device state + compiled programs for paged continuous batching.

    The engine owns the pool cache, the logits buffer, the block
    allocator, and the block tables; it does NOT schedule — the caller
    (``serving.scheduler.Scheduler`` or the rewired
    ``models.generate.ContinuousBatcher``) decides what to admit and
    when to decode, and owns per-slot positions/budgets.

    Every program the engine can compile is enumerable AHEAD of traffic
    (``chunk_buckets`` + the decode tick): ``compilecache.serving_registry``
    builds the AOT/warmup registry from exactly these methods, so the
    registry and the lazy ``run_chunks`` bucketing can never drift — the
    coverage guard (``ProgramRegistry.assert_covers`` over
    ``compiled_program_names()``) fails if a compiled program ever appears
    that the enumeration did not predict.
    """

    #: registry name of the shared decode program
    DECODE_PROGRAM = "decode_tick"
    #: registry name of the copy-on-write block duplication program
    BLOCK_COPY_PROGRAM = "kv_block_copy"

    def __init__(self, config, params, n_slots: int, *,
                 n_blocks: Optional[int] = None, block_len: int = 16,
                 prefill_chunk: int = 128, temperature: float = 0.0,
                 top_k: Optional[int] = None, mesh=None, device=None,
                 handoff: bool = False, swap: bool = False,
                 kv_dtype: Optional[str] = None,
                 prefix_cache: bool = False,
                 chunk_bucket_floor: Tuple[int, int] = (1, 1),
                 max_chunk_jobs: Optional[int] = None):
        from pytorch_distributed_tpu.models.generate import (
            _validate_sampling,
            _validate_serving_config,
        )
        from pytorch_distributed_tpu.serving.kv_pool import KV_DTYPES

        _validate_serving_config(config, mesh)
        _validate_sampling(config, temperature, top_k)
        if prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got {prefill_chunk}")
        # kv_dtype="int8" swaps the pool for the quantized layout
        # (kv_pool.init_paged_cache); the model's scatter path keys off
        # the pool dtype, nothing else.
        if kv_dtype not in KV_DTYPES:
            raise ValueError(
                f"kv_dtype {kv_dtype!r} must be one of {KV_DTYPES}"
            )
        self.kv_dtype = kv_dtype
        if mesh is not None and device is not None:
            raise ValueError(
                "pass mesh= (TP sub-mesh) or device= (single-device "
                "replica placement), not both"
            )
        self.config = config
        # State that belongs to a request and not to a block (a tail or a
        # recurrent state a layer: kv_pool.SLOT_LEAVES) and expert layers
        # that must not route padding: the programs of such a config take
        # each row's slot and real length, and return the pairs every
        # expert took.
        self._per_request = bool(
            config.slot_state
            or (config.n_experts and config.moe_kind == "dropless"))
        if prefix_cache and config.slot_state:
            raise ValueError(
                f"prefix_cache=True with attention {config.attn_kinds}: a "
                "request that starts behind a shared prefix needs the "
                "state its layers held at the prefix's last token (a "
                "convolution tail, a recurrent state), and the index keeps "
                "no such snapshot at block boundaries (ROADMAP B-mech 6); "
                "serve this config with prefix_cache=False")
        self.n_slots = n_slots
        self.block_len = block_len
        self.chunk = prefill_chunk
        self.temperature = temperature
        self.top_k = top_k
        # Per-slot table width: enough blocks for a full-capacity request.
        self.table_width = -(-config.max_seq_len // block_len)
        # Fewer chunk buckets. Every bucket is a program to trace,
        # compile and load, and a chunk program that is bound by reading
        # the weights pays little for a padded job or a wider slice. No
        # chunk program is narrower than ``chunk_bucket_floor`` (jobs,
        # table-slice width), and none runs more than ``max_chunk_jobs``
        # jobs: the caller plans no more a tick (the scheduler prefills
        # the oldest prompts first and the rest wait a tick).
        if max_chunk_jobs is not None and max_chunk_jobs < 1:
            raise ValueError(
                f"max_chunk_jobs must be >= 1, got {max_chunk_jobs}")
        self.max_chunk_jobs = min(max_chunk_jobs or n_slots, n_slots)
        k_floor, w_floor = chunk_bucket_floor
        if min(k_floor, w_floor) < 1 or (k_floor, w_floor) != (
                _pow2_bucket(k_floor), _pow2_bucket(w_floor)):
            raise ValueError(
                f"chunk_bucket_floor {chunk_bucket_floor!r} must be two "
                f"powers of two"
            )
        self._chunk_floor = (
            min(k_floor, _pow2_bucket(self.max_chunk_jobs)),
            min(w_floor, self.table_width))
        if n_blocks is None:
            # Capacity parity with the dense layout (every slot can hold
            # max_seq_len), plus the trash block.
            n_blocks = n_slots * self.table_width + 1
        self.allocator = BlockAllocator(n_blocks)
        self.tables = np.full((n_slots, self.table_width), TRASH_BLOCK,
                              np.int32)

        tp = config.model_axis is not None
        init_cfg = (
            dataclasses.replace(config, model_axis=None, tp_size=1)
            if tp else config
        )
        # (the span is here and not in kv_pool: pool_block_bytes traces
        # init_paged_cache under eval_shape)
        with spans.tracer().span("pool.alloc", blocks=n_blocks) as alloc:
            self.cache = init_paged_cache(init_cfg, params, n_blocks,
                                          block_len, kv_dtype=kv_dtype,
                                          n_slots=n_slots)
            self.logits = jnp.zeros((n_slots, config.vocab_size),
                                    jnp.float32)
            # device bytes one block holds across every pool leaf (K +
            # V + scale siblings). A looped config's cache layers (an
            # entry per pass and layer) outnumber its weight layers; the
            # leaves count them all.
            pool_bytes, slot_bytes = cache_bytes(self.cache)
            self._per_block_bytes = pool_bytes // n_blocks
            # and what ONE slot holds in the per-slot leaves
            self._per_slot_bytes = slot_bytes // (n_slots + 1)
            leaves = jax.tree_util.tree_flatten_with_path(self.cache)[0]
            slot_leaves = sum(is_slot_leaf(path) for path, _ in leaves)
            # the float32 recurrent states among them, and the rest
            state_bytes = sum(
                leaf.size * leaf.dtype.itemsize for path, leaf in leaves
                if getattr(path[-1], "key", None) == "state")
            #: bytes ONE slot holds in float32 recurrent state
            self.slot_state_bytes = state_bytes // (n_slots + 1)
            # layers that own a pool (a layer of per-slot state alone has
            # none), and the cache layers they are (an entry a pass)
            pool_layers = len({path[:-1] for path, _ in leaves
                               if not is_slot_leaf(path)})
            cache_layers = pool_layers * config.ut_steps
            # a token's bytes in ONE layer that owns a key and a value
            # pool: twice a row of either (H_kv * D lanes, the same in
            # every such layer)
            kv_rows = [leaf.shape[-1] * leaf.dtype.itemsize
                       for path, leaf in leaves
                       if getattr(path[-1], "key", None) in ("key", "value")]
            kv_row_bytes = 2 * sum(kv_rows) // max(len(kv_rows), 1)
            # ``T``, the chain blocks a grid step of the tick's kernel
            # stages (``ops.paged_flash.tile_blocks``, from the bytes a
            # position holds in one cache layer on one shard: a latent
            # row is staged twice, as keys and as values); 1 where the
            # tick gathers dense
            self.tile_blocks = 1
            # and the narrow heads ONE product of a tile serves
            # (``ops.paged_flash.heads_folded``, from a shard's narrow
            # heads and the query rows each brings to a tick: all of
            # them where the kernel folds them, 1 where it loops over
            # heads, as for a latent row's one head); 1 where the tick
            # gathers dense
            self.heads_folded = 1
            if self.gather_impl == "pallas":
                from pytorch_distributed_tpu.ops.paged_flash import (
                    heads_folded,
                    tile_blocks,
                )

                self.tile_blocks = tile_blocks(
                    self.table_width, block_len,
                    (2 if config.latent_row_width else 1)
                    * self._per_block_bytes
                    // (cache_layers * block_len * config.tp_size))
                kv = 1 if config.latent_row_width else (
                    config.num_kv_heads or config.num_heads)
                self.heads_folded = heads_folded(
                    max(1, kv // config.tp_size), config.num_heads // kv)
            # the row tile the tick's grouped products compiled with, as
            # the model's module reckons it for a row a slot; 0 where the
            # config has no dropless experts or the product is XLA's
            # ``ragged_dot``
            from pytorch_distributed_tpu.models.moe import (
                program_grouped_rows,
            )

            self.grouped_rows = program_grouped_rows(config, n_slots)
            # which update of the recurrent states the tick compiles, as
            # the model's module decides it for the stack's layer kinds
            # (``"pallas"``: the kernel on the leaf, ``"xla"``: the
            # ``jax.numpy`` spelling, ``""``: the cache holds no state)
            from pytorch_distributed_tpu.models.transformer import (
                slot_state_update,
            )

            self.state_update = slot_state_update(config.attn_kinds)
            # ``read``: the paged read the programs compile;
            # ``table_blocks``: the blocks a decode tick's tables name,
            # live or not, which ``engine.decode.launch``'s
            # ``live_blocks`` is a share of; ``table_tiles``: the fused
            # kernel's grid steps a layer, ``tile_blocks`` entries each,
            # which ``live_tiles`` is a share of; ``heads_folded``: the
            # narrow heads one product of a tile serves; ``grouped_rows``:
            # the row tile of the tick's grouped products;
            # ``state_update``: the tick's update of the recurrent states;
            # ``tail_bytes``: the per-slot leaves' bytes but for the
            # float32 recurrent states, which are ``state_bytes``;
            # ``latent_row_bytes``: a token's ONE row where a layer keeps a
            # latent pool, ``kv_row_bytes``: a token's key and value rows
            # where a layer keeps those (0 where none does);
            # ``pool_layers``: the layers that own a pool
            alloc.args.update(
                weight_layers=config.num_layers,
                cache_layers=cache_layers,
                pool_layers=pool_layers,
                state_bytes=state_bytes,
                latent_row_bytes=config.latent_row_width
                * jnp.dtype(config.dtype).itemsize,
                kv_row_bytes=kv_row_bytes,
                block_bytes=self._per_block_bytes,
                read=self.gather_impl,
                table_blocks=n_slots * self.table_width,
                tile_blocks=self.tile_blocks,
                heads_folded=self.heads_folded,
                grouped_rows=self.grouped_rows,
                state_update=self.state_update,
                table_tiles=n_slots * -(-self.table_width
                                        // self.tile_blocks),
                tail_bytes=slot_bytes - state_bytes,
                slot_state_leaves=slot_leaves,
            )

        self._chunk_fns: Dict[Tuple[int, int], callable] = {}
        self._decode_fn = None
        # tokens each expert took, [expert layers, n_experts] int32: the
        # last collected tick's on the host (fetched with its tokens), the
        # last chunk program's on the device; None where the config has
        # no such program
        self.tick_expert_counts: Optional[np.ndarray] = None
        self.chunk_expert_counts = None
        self._tick_counts = None
        # prefill→decode handoff programs (fleet disaggregation), one
        # per pow2 chain-length bucket. Gated by ``handoff=`` so engines
        # that never hand off predict no kv_export/kv_import programs
        # (the registry coverage guard would flag them as rogue).
        self.handoff = handoff
        self._export_fns: Dict[int, callable] = {}
        self._import_fns: Dict[int, callable] = {}
        # host-offload swap programs (round 13 pressure tier), the
        # mirror of the handoff pair but pointed at host RAM instead of
        # another replica's pool: gated by ``swap=`` for the same
        # coverage-guard reason, one program pair per pow2 chain bucket.
        self.swap = swap
        self._swap_out_fns: Dict[int, callable] = {}
        self._swap_in_fns: Dict[int, callable] = {}
        # prefix-sharing tier (round 17): the radix index over full
        # blocks plus the one compiled copy-on-write program, gated by
        # ``prefix_cache=`` for the same coverage-guard reason as
        # handoff/swap — engines that never share predict no
        # kv_block_copy program.
        self.prefix_cache = bool(prefix_cache)
        self.prefix: Optional[PrefixIndex] = (
            PrefixIndex(block_len, self.allocator) if prefix_cache
            else None
        )
        self._copy_fn = None
        self._cow_copies = 0
        # buckets whose program has EXECUTED at least once (call path hot:
        # the next call pays zero compile/load) — run_chunks/decode and the
        # execute-mode warmups add to these; AOT-only warmup does not (the
        # first real call still pays a trace + persistent-cache load, so
        # the scheduler's cold-request accounting stays honest)
        self._hot_chunks: set = set()
        self._hot_decode = False
        if tp:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from pytorch_distributed_tpu.models.generate import _tp_rules
            from pytorch_distributed_tpu.parallel.tensor import (
                match_partition_rules,
            )

            self.mesh = mesh
            self._param_specs = match_partition_rules(_tp_rules(config),
                                                      params)
            self._cache_specs = paged_cache_specs(config, self.cache)
            with spans.tracer().span("weights.place"):
                self.params = jax.device_put(
                    params,
                    jax.tree.map(lambda s: NamedSharding(mesh, s),
                                 self._param_specs),
                )
            self.cache = jax.device_put(
                self.cache,
                jax.tree.map(lambda s: NamedSharding(mesh, s),
                             self._cache_specs),
            )
        else:
            self.mesh = None
            self.params = params
        # Fleet replica placement (fleet/router.py): commit this engine's
        # whole working set — params, pool, logits — to one device carved
        # out of jax.devices(), so N single-process replicas each dispatch
        # onto their own sub-mesh and their programs can overlap. The
        # compiled programs follow their committed inputs; a launch's
        # host-built operand (one packed array) stays uncommitted and is
        # free to land wherever the committed arguments already live.
        self.device = device
        if device is not None:
            with spans.tracer().span("weights.place"):
                self.params = jax.device_put(self.params, device)
            self.cache = jax.device_put(self.cache, device)
            self.logits = jax.device_put(self.logits, device)
        # what every program's call flattens beside its own operand; the
        # trees keep their structure, so it is counted here and not a tick
        # (``engine.*.call``'s ``leaves``)
        self._resident_leaves = len(jax.tree.leaves(
            (self.params, self.cache, self.logits)))

    @property
    def gather_impl(self) -> str:
        """The paged read the decode tick compiles: what
        ``ops.attention.default_gather_impl`` answers for a tick's rows
        (a chunk program asks with its own, wider, rows)."""
        cfg = self.config
        if cfg.latent_row_width:
            # latent attention: every head's row reads the one narrow head
            return attention_ops.default_gather_impl(
                cfg.num_heads, attention_ops.dense_gather_bytes(
                    self.n_slots, self.table_width * self.block_len,
                    cfg.latent_row_width))
        kv = cfg.num_kv_heads or cfg.num_heads
        return attention_ops.default_gather_impl(rows=cfg.num_heads // kv)

    # ---- program builders (cached per static shape) ----

    def _model(self):
        from pytorch_distributed_tpu.models.transformer import TransformerLM

        return TransformerLM(self.config)

    # A launch moves ONE array to the device: every host-built operand of
    # the two tick programs is int32 or a flag and a row a job or a lane,
    # so each launch packs them into one int32 matrix (a transfer costs
    # its arrays, not its bytes), and the program slices it apart.

    def _chunk_columns(self, wp: int):
        """A job's row of the chunk program's operand: ``tokens[chunk] |
        table[wp] | start, slot, is_last, last_idx`` and, where the
        config's state is a request's, ``length``."""
        return _columns(
            dict(tokens=self.chunk, table=wp),
            ("start", "slot", "is_last", "last_idx")
            + (("length",) if self._per_request else ()))

    def _decode_columns(self):
        """A lane's row of the decode tick's operand:
        ``table[table_width] | position, active``."""
        return _columns(dict(table=self.table_width),
                        ("position", "active"))

    def _chunk_operand(self, k_pad: int, wp: int,
                       jobs: Sequence["ChunkJob"] = ()) -> np.ndarray:
        """The (k_pad, wp) chunk program's operand on the host, a row a
        job; the rows past ``jobs`` are padding jobs (all of them in a
        warm-up): a table of trash blocks, and slot ``n_slots``, which is
        out of the logits buffer's bounds, so the scatter drops them."""
        cols, width = self._chunk_columns(wp)
        host = np.zeros((k_pad, width), np.int32)
        job = _fields(host, cols)
        job["table"][:] = TRASH_BLOCK
        job["slot"][:] = self.n_slots
        for i, j in enumerate(jobs):
            job["tokens"][i] = j.tokens
            job["table"][i] = self.tables[j.slot, :wp]
            job["start"][i] = j.start
            job["slot"][i] = j.slot
            job["is_last"][i] = j.is_last
            job["last_idx"][i] = j.last_idx
            if "length" in job:
                # a job's real positions in this chunk: all of it, or up
                # to the prompt's last token; a padding job has none
                job["length"][i] = (j.last_idx + 1 if j.is_last
                                    else self.chunk)
        return host

    def _decode_operand(self, positions: np.ndarray,
                        active: np.ndarray) -> np.ndarray:
        """The decode tick's operand on the host, a row a lane; a lane
        that is not active gets a table of trash blocks, so its writes
        can never touch a recycled block."""
        cols, width = self._decode_columns()
        host = np.empty((self.n_slots, width), np.int32)
        lane = _fields(host, cols)
        lane["table"][:] = np.where(active[:, None], self.tables,
                                    TRASH_BLOCK)
        lane["position"][:] = positions
        lane["active"][:] = active
        return host

    def _chunk_fn(self, k_pad: int, wp: int):
        key = (k_pad, wp)
        fn = self._chunk_fns.get(key)
        if fn is not None:
            return fn
        model = self._model()
        layers = self.config.num_layers
        cols, _ = self._chunk_columns(wp)

        def body(params, cache, logits, packed):
            job = _fields(packed, cols)
            slots, last_idx = job["slot"], job["last_idx"]
            # ``length`` (a column where the config's state is a
            # request's: ``_per_request``): each job's real positions in
            # this chunk, 0 for a padding job, whose slot ``n_slots`` is
            # the tail leaves' trash row
            lengths = job.get("length")
            per_request = dict(mutable=["cache"]) if lengths is None else dict(
                slots=slots, lengths=lengths, head_rows=last_idx,
                mutable=["cache", "moe_stats"])
            out, variables = model.apply(
                {"params": params, "cache": cache},
                job["tokens"],
                position_offset=job["start"],
                prefill=True,
                block_tables=job["table"],
                **per_request,
            )
            # logits at each prompt's LAST real token — the distribution
            # for its first decoded token; written only for final chunks.
            # Padding jobs carry slot == n_slots: the scatter drops them.
            row = jnp.take_along_axis(
                out, last_idx[:, None, None], axis=1
            )[:, 0] if lengths is None else out[:, 0]
            new_logits = logits.at[slots].set(
                jnp.where(job["is_last"][:, None] != 0, row, logits[slots])
            )
            if lengths is None:
                return variables["cache"], new_logits
            return variables["cache"], new_logits, _expert_counts(
                variables.get("moe_stats", {}), layers)

        if self.mesh is not None:
            from jax.sharding import PartitionSpec as P

            from pytorch_distributed_tpu.parallel.mesh import shard_map

            body = shard_map(
                body, mesh=self.mesh,
                in_specs=(self._param_specs, self._cache_specs, P(), P()),
                out_specs=(self._cache_specs, P()),
                check_vma=False,
            )
        fn = jax.jit(self._named(body, self.chunk_program_name(k_pad, wp)),
                     donate_argnums=(1, 2))
        self._chunk_fns[key] = fn
        return fn

    def _decode(self):
        if self._decode_fn is not None:
            return self._decode_fn
        from pytorch_distributed_tpu.models.generate import _sample

        model = self._model()
        temp, topk = self.temperature, self.top_k
        n_slots, layers = self.n_slots, self.config.num_layers
        per_request = self._per_request

        cols, _ = self._decode_columns()

        def body(params, cache, logits, packed, rng):
            lane = _fields(packed, cols)
            active = lane["active"] != 0
            tokens = _sample(logits, rng, temp, topk)
            # a lane that is not active reads and writes the tail leaves'
            # trash row (a slot in mid-prefill keeps its tail while the
            # tick runs over it) and is routed to no expert
            request = dict(
                slots=jnp.where(active, jnp.arange(n_slots), n_slots),
                lengths=lane["active"],
                mutable=["cache", "moe_stats"],
            ) if per_request else dict(mutable=["cache"])
            out, variables = model.apply(
                {"params": params, "cache": cache},
                tokens[:, None],
                position_offset=lane["position"],
                decode=True,
                block_tables=lane["table"],
                **request,
            )
            # Inactive lanes: cache writes already routed to the trash
            # block (host-masked tables); logits rows are dead state,
            # replaced by the slot's next final prefill chunk before they
            # are read. The positions the tick leaves behind are the
            # launched ones plus one on the active lanes: the host counts
            # them itself (``decode_launch``) and the program returns none.
            if per_request:
                return (variables["cache"], out[:, 0], tokens,
                        _expert_counts(variables.get("moe_stats", {}),
                                       layers))
            return variables["cache"], out[:, 0], tokens

        if self.mesh is not None:
            from jax.sharding import PartitionSpec as P

            from pytorch_distributed_tpu.parallel.mesh import shard_map

            body = shard_map(
                body, mesh=self.mesh,
                in_specs=(self._param_specs, self._cache_specs, P(), P(),
                          P()),
                out_specs=(self._cache_specs, P(), P()),
                check_vma=False,
            )
        self._decode_fn = jax.jit(self._named(body, self.DECODE_PROGRAM),
                                  donate_argnums=(1, 2))
        return self._decode_fn

    # ---- program enumeration + warmup (compilecache.serving_registry) ----

    @staticmethod
    def chunk_program_name(k_pad: int, wp: int) -> str:
        """Stable registry identity of one chunk-prefill bucket."""
        return f"chunk_prefill[k={k_pad},w={wp}]"

    @staticmethod
    def _named(body, program: str):
        """``body`` under the registry name ``program`` spelt as an
        identifier (``chunk_prefill[k=8,w=4]`` -> ``chunk_prefill_k8_w4``):
        the profiler calls a jitted module ``jit_<function name>``, so a
        trace says which program ran."""
        body.__name__ = re.sub(r"[^0-9A-Za-z]+", "_",
                               program.replace("=", "")).strip("_")
        return body

    def bucket_for(self, jobs: List["ChunkJob"]) -> Tuple[int, int]:
        """The (padded job count, table-slice width) bucket ``run_chunks``
        will compile/run for ``jobs`` — THE bucketing definition; the
        registry enumeration and the scheduler's cold-request accounting
        both read it from here."""
        max_end = max(j.start + self.chunk for j in jobs)
        return self._floored(
            _pow2_bucket(len(jobs)),
            min(_pow2_bucket(-(-max_end // self.block_len)),
                self.table_width))

    def _floored(self, k_pad: int, wp: int) -> Tuple[int, int]:
        """The bucket that runs a (k_pad, wp) bucket's jobs under
        ``chunk_bucket_floor``."""
        k_floor, w_floor = self._chunk_floor
        return max(k_pad, k_floor), max(wp, w_floor)

    def chunk_buckets(self) -> List[Tuple[int, int]]:
        """Every (k_pad, wp) bucket this engine can ever ask for: job
        counts are 1..max_chunk_jobs (at most one chunk job per resident
        slot, pow2-padded) and table-slice widths are the pow2 widths clipped to
        ``table_width`` — exactly the values ``bucket_for`` can produce,
        because admission rejects prompts whose padded length exceeds
        ``max_seq_len`` (so ``max_end`` never needs more than
        ``table_width`` blocks)."""
        ks, k = [], 1
        while k < self.max_chunk_jobs:
            ks.append(k)
            k <<= 1
        ks.append(_pow2_bucket(self.max_chunk_jobs))
        ws, w = [], 1
        while w < self.table_width:
            ws.append(w)
            w <<= 1
        ws.append(self.table_width)
        return sorted({self._floored(k, w) for k in ks for w in ws})

    @staticmethod
    def export_program_name(n_pad: int) -> str:
        return f"kv_export[n={n_pad}]"

    @staticmethod
    def import_program_name(n_pad: int) -> str:
        return f"kv_import[n={n_pad}]"

    def handoff_buckets(self) -> List[int]:
        """Every chain-length bucket the handoff programs can compile
        for — pow2 lengths clipped to ``table_width``, the exact range
        ``_chain_bucket`` can produce (admission bounds every chain by
        the table width). Empty unless the engine was built with
        ``handoff=True``, so non-fleet registries predict no handoff
        programs."""
        if not self.handoff:
            return []
        ns, n = [], 1
        while n < self.table_width:
            ns.append(n)
            n <<= 1
        ns.append(self.table_width)
        return sorted(set(ns))

    def warm_export(self, n_pad: int, execute: bool = True):
        """Compile (and inertly run) one export bucket: reading the
        trash block and slot 0's logits row mutates nothing. The
        ``execute=False`` branch returns the ``Compiled`` (cost-card
        statics, ``telemetry.costmodel``); the execute branch None."""
        fn = self._export_fn(n_pad)
        idx = jnp.full((n_pad,), TRASH_BLOCK, jnp.int32)
        slot = jnp.asarray(0, jnp.int32)
        if execute:
            fn(self.cache, self.logits, idx, slot)
            return None
        cache_aval, logits_aval = self._cache_logits_avals()
        return fn.lower(cache_aval, logits_aval, idx, slot).compile()

    def warm_import(self, n_pad: int, execute: bool = True):
        """Compile (and inertly run) one import bucket: every lane
        scatters into the trash block and the logits row targets the
        out-of-bounds ``n_slots`` sentinel (dropped), so live state is
        untouched. ``execute=False`` returns the ``Compiled``."""
        fn = self._import_fn(n_pad)
        blocks = self._zero_chain(n_pad)
        idx = jnp.full((n_pad,), TRASH_BLOCK, jnp.int32)
        slot = jnp.asarray(self.n_slots, jnp.int32)
        row = jnp.zeros((self.config.vocab_size,), self.logits.dtype)
        if execute:
            self.cache, self.logits = fn(
                self.cache, self.logits, blocks, idx, slot, row,
            )
            return None
        cache_aval, logits_aval = self._cache_logits_avals()
        return fn.lower(
            cache_aval, logits_aval, blocks, idx, slot, row
        ).compile()

    def _zero_chain(self, n_pad: int):
        """An all-zero chain of ``n_pad`` blocks (and one zero row of
        each per-slot leaf) in the cache's tree: the warm-ups' payload."""
        return map_cache(
            lambda pool: jnp.zeros((n_pad,) + pool.shape[1:], pool.dtype),
            lambda rows: jnp.zeros(rows.shape[1:], rows.dtype),
            self.cache,
        )

    @staticmethod
    def swap_out_program_name(n_pad: int) -> str:
        return f"kv_swap_out[n={n_pad}]"

    @staticmethod
    def swap_in_program_name(n_pad: int) -> str:
        return f"kv_swap_in[n={n_pad}]"

    def swap_buckets(self) -> List[int]:
        """Every chain-length bucket the swap programs can compile for —
        the same pow2-clipped range as the handoff buckets (both walk
        chains the admission contract bounded by ``table_width``). Empty
        unless the engine was built with ``swap=True``, so pressure-less
        registries predict no swap programs."""
        if not self.swap:
            return []
        ns, n = [], 1
        while n < self.table_width:
            ns.append(n)
            n <<= 1
        ns.append(self.table_width)
        return sorted(set(ns))

    def warm_swap_out(self, n_pad: int, execute: bool = True):
        """Compile (and inertly run) one swap-out gather bucket: reads
        the trash block and slot 0's logits row, mutating nothing — the
        same inert contract as ``warm_export``. ``execute=False``
        returns the ``Compiled`` (cost-card statics)."""
        fn = self._swap_out_fn(n_pad)
        idx = jnp.full((n_pad,), TRASH_BLOCK, jnp.int32)
        slot = jnp.asarray(0, jnp.int32)
        if execute:
            fn(self.cache, self.logits, idx, slot)
            return None
        cache_aval, logits_aval = self._cache_logits_avals()
        return fn.lower(cache_aval, logits_aval, idx, slot).compile()

    def warm_swap_in(self, n_pad: int, execute: bool = True):
        """Compile (and inertly run) one swap-in scatter bucket: every
        lane scatters into the trash block and the logits row targets
        the out-of-bounds ``n_slots`` sentinel (dropped) — live state is
        untouched. ``execute=False`` returns the ``Compiled``."""
        fn = self._swap_in_fn(n_pad)
        blocks = self._zero_chain(n_pad)
        idx = jnp.full((n_pad,), TRASH_BLOCK, jnp.int32)
        slot = jnp.asarray(self.n_slots, jnp.int32)
        row = jnp.zeros((self.config.vocab_size,), self.logits.dtype)
        if execute:
            self.cache, self.logits = fn(
                self.cache, self.logits, blocks, idx, slot, row,
            )
            return None
        cache_aval, logits_aval = self._cache_logits_avals()
        return fn.lower(
            cache_aval, logits_aval, blocks, idx, slot, row
        ).compile()

    def _block_copy_fn(self):
        """ONE compiled program duplicating one pool block across every
        cache leaf — the copy-on-write primitive. ``pool.at[dst].set(
        pool[src])`` tree-mapped over the cache, so int8 pools copy
        their fp32 scale siblings in the same program (scales share in
        lockstep by construction). Donates the cache: in place, no pool
        copy."""
        if self._copy_fn is not None:
            return self._copy_fn

        def body(cache, src, dst):
            return jax.tree.map(
                lambda pool: pool.at[dst].set(pool[src]), cache
            )

        if self.mesh is not None:
            from jax.sharding import PartitionSpec as P

            from pytorch_distributed_tpu.parallel.mesh import shard_map

            body = shard_map(
                body, mesh=self.mesh,
                in_specs=(self._cache_specs, P(), P()),
                out_specs=self._cache_specs,
                check_vma=False,
            )
        self._copy_fn = jax.jit(
            self._named(body, self.BLOCK_COPY_PROGRAM), donate_argnums=(0,))
        return self._copy_fn

    def _require_prefix(self):
        if not self.prefix_cache:
            raise RuntimeError(
                "this engine was built without prefix_cache=True — its "
                "registry does not predict the kv_block_copy program "
                "(prefix-enabled schedulers set it)"
            )

    def warm_block_copy(self, execute: bool = True):
        """Compile (and inertly run) the COW block copy: trash block
        onto itself — a self-copy of the garbage absorber, live state
        untouched. ``execute=False`` returns the ``Compiled`` (cost-card
        statics)."""
        self._require_prefix()
        fn = self._block_copy_fn()
        src = jnp.asarray(TRASH_BLOCK, jnp.int32)
        dst = jnp.asarray(TRASH_BLOCK, jnp.int32)
        if execute:
            self.cache = fn(self.cache, src, dst)
            return None
        cache_aval, _ = self._cache_logits_avals()
        return fn.lower(cache_aval, src, dst).compile()

    def has_chunk_program(self, k_pad: int, wp: int) -> bool:
        """True when the bucket's call path is hot (executed before)."""
        return (k_pad, wp) in self._hot_chunks

    @property
    def has_decode_program(self) -> bool:
        return self._hot_decode

    def compiled_program_names(self) -> List[str]:
        """Live program inventory for the registry coverage guard."""
        names = [self.chunk_program_name(k, w) for k, w in
                 sorted(self._chunk_fns)]
        if self._decode_fn is not None:
            names.append(self.DECODE_PROGRAM)
        names += [self.export_program_name(n) for n in
                  sorted(self._export_fns)]
        names += [self.import_program_name(n) for n in
                  sorted(self._import_fns)]
        names += [self.swap_out_program_name(n) for n in
                  sorted(self._swap_out_fns)]
        names += [self.swap_in_program_name(n) for n in
                  sorted(self._swap_in_fns)]
        if self._copy_fn is not None:
            names.append(self.BLOCK_COPY_PROGRAM)
        return names

    def _cache_logits_avals(self):
        sds = lambda x: jax.ShapeDtypeStruct(  # noqa: E731
            x.shape, x.dtype, sharding=x.sharding
        )
        return jax.tree.map(sds, self.cache), sds(self.logits)

    def warm_chunk(self, k_pad: int, wp: int, execute: bool = True):
        """Force the chunk program that runs a (k_pad, wp) bucket's jobs
        (the bucket itself unless ``chunk_bucket_floor`` widens it)
        compiled before traffic needs it. ``execute=False`` returns the ``Compiled`` (cost-card
        statics); the execute branch returns None.

        ``execute=True`` runs it once with inert inputs — every job is a
        padding job (slot ``n_slots``: the logits scatter drops it) whose
        table points at the trash block, so the pool's live blocks and
        the logits buffer are untouched — leaving the jit call path hot:
        the first real request into this bucket pays nothing. Only safe
        when the caller is not concurrently running programs that donate
        the same cache/logits buffers (i.e. before serving, or from the
        serving thread itself).

        ``execute=False`` AOT-compiles via ``lower(...).compile()`` — no
        buffer is touched, so a background thread can do it mid-traffic;
        it feeds the persistent compilation cache
        (``compilecache.aot.enable_persistent_cache``), turning the
        bucket's eventual first call from an XLA compile into a disk
        load.
        """
        k_pad, wp = self._floored(k_pad, wp)
        fn = self._chunk_fn(k_pad, wp)
        # every job a padding job: the served call's operand with no jobs
        packed = jax.device_put(self._chunk_operand(k_pad, wp))
        name = self.chunk_program_name(k_pad, wp)
        if execute:
            with program_load_if((k_pad, wp) not in self._hot_chunks, name):
                self.cache, self.logits, *_ = fn(
                    self.params, self.cache, self.logits, packed,
                )
            self._hot_chunks.add((k_pad, wp))
            return None
        cache_aval, logits_aval = self._cache_logits_avals()
        with program_load(name):
            return fn.lower(
                self.params, cache_aval, logits_aval, packed,
            ).compile()

    def warm_decode(self, execute: bool = True):
        """Force the decode tick compiled — same contract (and return
        convention) as ``warm_chunk``. The inert execution decodes with
        every lane inactive: cache writes go to the trash block and the
        logits buffer's garbage rows are rewritten by each slot's final
        prefill chunk before any real decode reads them."""
        fn = self._decode()
        # the served call's operand with no lane active
        packed = jax.device_put(self._decode_operand(
            np.zeros((self.n_slots,), np.int32),
            np.zeros((self.n_slots,), bool)))
        rng = jax.random.key(0)
        if self.device is not None:
            rng = jax.device_put(rng, self.device)
        if execute:
            with program_load_if(not self._hot_decode, self.DECODE_PROGRAM):
                self.cache, self.logits, *_ = fn(
                    self.params, self.cache, self.logits, packed, rng,
                )
            self._hot_decode = True
            return None
        cache_aval, logits_aval = self._cache_logits_avals()
        with program_load(self.DECODE_PROGRAM):
            return fn.lower(
                self.params, cache_aval, logits_aval, packed, rng,
            ).compile()

    # ---- slot-level operations ----

    def blocks_for(self, prompt_len: int, max_new_tokens: int) -> int:
        return blocks_needed(prompt_len, max_new_tokens, self.block_len,
                             self.chunk)

    def _san_site(self, label: str):
        """Label the allocator ops inside the ``with`` block for the
        block-lifecycle sanitizer's ledger (``analysis.blocksan``,
        ``PDT_BLOCKSAN=1``); a no-op context when detached."""
        san = self.allocator.sanitizer
        return san.site(label) if san is not None else contextlib.nullcontext()

    def set_kv_trace(self, observer) -> None:
        """Install ``observer(event, owner, info)`` on this engine's
        block allocator (``BlockAllocator.on_transition``): every chain
        alloc/free and swap-state change — wherever it originates
        (admission, retirement, handoff import, either swap direction) —
        reports through it. The round-14 request-lifecycle traces hang
        their KV chain-identity events off this hook; pass ``None`` to
        detach."""
        self.allocator.on_transition = observer

    def _alloc_evict(self, owner: int, shared: List[int],
                     n_new: int) -> Optional[List[int]]:
        """``alloc_mixed`` with the prefix index as the pressure valve:
        on OOM, evict enough LRU index-only blocks to cover the
        shortfall and retry ONCE. Dropping cache always precedes the
        round-13 pressure tier's preemption — only when the index has
        nothing refcount-1 left does the OOM propagate to the caller's
        queue/preempt ladder."""
        chain = self.allocator.alloc_mixed(owner, shared, n_new)
        if chain is None and self.prefix is not None:
            short = n_new - self.allocator.available
            if short > 0 and self.prefix.evict(short) > 0:
                chain = self.allocator.alloc_mixed(owner, shared, n_new)
        return chain

    def admit(self, slot: int, prompt_len: int, max_new_tokens: int) -> bool:
        """Allocate ``slot``'s block chain and write its table row — the
        O(1)-ish host half of admission (the device half is the chunk
        program). Returns False (state unchanged) when the pool cannot
        serve the chain: the deterministic OOM the scheduler queues on."""
        need = self.blocks_for(prompt_len, max_new_tokens)
        if need > self.table_width:
            raise ValueError(
                f"request needs {need} blocks > table width "
                f"{self.table_width} (max_seq_len {self.config.max_seq_len}"
                f" / block_len {self.block_len})"
            )
        with self._san_site("admit"):
            chain = self._alloc_evict(slot, [], need)
        if chain is None:
            return False
        self.tables[slot] = TRASH_BLOCK
        self.tables[slot, :need] = chain
        return True

    # ---- prefix-sharing admission (round 17; ANALYSIS.md "Prefix
    # sharing & copy-on-write") ----

    def admit_shared(self, slot: int, tokens,
                     max_new_tokens: int) -> Optional[PrefixHit]:
        """Admit through the prefix index: the longest full-block match
        of ``tokens`` rides shared (incref'd) blocks, only the suffix
        allocates fresh, and prefill starts at ``covered`` — admission
        costs O(new tokens), not O(prompt).

        Invariants that keep greedy streams token-identical to the
        no-sharing engine:

        - at least ONE prompt token always re-prefills, so the final
          chunk regenerates the slot's logits row exactly as a cold
          prefill would. On a FULL-cover match that token lives inside
          the last matched block — the copy-on-write case: the boundary
          block is duplicated (compiled ``kv_block_copy``) into a fresh
          block the chain owns exclusively, then position ``L-1`` is
          rewritten with bit-identical KV.
        - ``covered`` is capped so the chunk-padded tail
          (``covered + ceil((L-covered)/chunk)*chunk``) stays within
          ``max_seq_len`` — the same scatter-safety bound cold
          admission's padding obeys, so no table slice ever clips a
          live write.

        Returns the ``PrefixHit`` (``covered == 0`` on a miss — still a
        valid admission), or None on pool OOM with nothing incref'd —
        the same deterministic-OOM contract as ``admit``."""
        self._require_prefix()
        prompt_len = len(tokens)
        need0 = self.blocks_for(prompt_len, max_new_tokens)
        if need0 > self.table_width:
            raise ValueError(
                f"request needs {need0} blocks > table width "
                f"{self.table_width} (max_seq_len {self.config.max_seq_len}"
                f" / block_len {self.block_len})"
            )
        bl, c = self.block_len, self.chunk
        matched = self.prefix.lookup(tokens)
        covered = len(matched) * bl
        cow = False
        if covered >= prompt_len:
            # full cover: re-prefill the final token to regenerate the
            # logits row; with block_len 1 that token IS a whole block
            # (no COW), otherwise the boundary block is COW-duplicated
            covered = prompt_len - 1
            cow = covered % bl != 0
        # scatter-safety cap: the padded tail must fit max_seq_len
        while covered > 0 and (
            covered + -(-(prompt_len - covered) // c) * c
            > self.config.max_seq_len
        ):
            covered = (covered - 1) // bl * bl
            cow = False
        if covered <= 0:
            covered, cow = 0, False
        n_shared = covered // bl
        need = blocks_needed_suffix(covered, prompt_len, max_new_tokens,
                                    bl, c)
        evicted0 = self.prefix.evictions
        with self._san_site("admit-shared"):
            chain = self._alloc_evict(slot, matched[:n_shared],
                                      need - n_shared)
        if chain is None:
            return None
        self.tables[slot] = TRASH_BLOCK
        self.tables[slot, :need] = chain
        if cow:
            # duplicate the boundary block BEFORE any write lands in it:
            # positions [n_shared*bl, L-1) must be readable from a block
            # this chain owns exclusively
            self.cache = self._block_copy_fn()(
                self.cache,
                jnp.asarray(matched[n_shared], jnp.int32),
                jnp.asarray(chain[n_shared], jnp.int32),
            )
            self._cow_copies += 1
            if self.allocator.sanitizer is not None:
                self.allocator.sanitizer.note_cow(
                    slot, matched[n_shared], chain[n_shared])
        return PrefixHit(
            covered=covered, shared=n_shared, cow=cow,
            evicted=self.prefix.evictions - evicted0,
        )

    def prefix_insert(self, slot: int, tokens, upto: int) -> int:
        """Index ``slot``'s chain blocks covering ``tokens[:upto]``
        (floored to FULL blocks — every indexed slot holds real
        prefill-written KV). Called as prefill crosses block boundaries,
        so concurrent same-prefix requests hit before the donor even
        retires. Dedup keeps first-writer blocks; returns newly indexed
        blocks."""
        self._require_prefix()
        return self.prefix.insert(tokens, self.allocator.chain(slot), upto)

    def prefix_metrics(self) -> dict:
        """Exact sharing counters for ``Scheduler.metrics()`` — index
        state plus the allocator's shared-block census and the COW
        count."""
        out = {
            "prefix_cache": self.prefix_cache,
            "prefix_cow_copies": self._cow_copies,
            "prefix_shared_blocks": self.allocator.shared_blocks,
            "blocks_fresh_allocated": self.allocator.fresh_allocated,
            "blocks_shared_reused": self.allocator.shared_reused,
        }
        if self.prefix is not None:
            out.update(self.prefix.metrics())
        else:
            out.update(prefix_index_blocks=0, prefix_lookups=0,
                       prefix_hits=0, prefix_hit_rate=0.0,
                       prefix_inserts=0, prefix_evictions=0)
        return out

    def release(self, slot: int) -> None:
        """Free the slot's chain and point its table row at the trash
        block, so the shared decode program's garbage writes for this
        (now inactive) lane can never touch recycled blocks."""
        with self._san_site("release"):
            self.allocator.free(slot)
        self.tables[slot] = TRASH_BLOCK

    def release_all(self) -> None:
        """Free every live chain, drop the prefix index's retained
        blocks, and reset all tables — the scale-down teardown after a
        graceful drain (fleet/; by then every CHAIN is already freed,
        so this is a belt-and-braces reset plus the index teardown, not
        a leak plug). Order matters: chains first, so an index block a
        live chain still shared is decref'd exactly once per holder —
        the drain-with-live-sharers invariant the allocator enforces
        loudly."""
        for owner in self.allocator.owners():
            self.allocator.free(owner)
        if self.prefix is not None:
            self.prefix.clear()
        self.tables[:] = TRASH_BLOCK

    # ---- prefill→decode handoff (fleet/ disaggregation) ----

    def _chain_bucket(self, n: int) -> int:
        """Pow2 chain-length bucket (clipped to ``table_width``) shared
        by export and import so one compiled program pair serves every
        chain of similar length; padding lanes read/write the trash
        block."""
        return min(_pow2_bucket(n), self.table_width)

    def _require_handoff(self):
        if not self.handoff:
            raise RuntimeError(
                "this engine was built without handoff=True — its "
                "registry does not predict kv_export/kv_import programs "
                "(fleet routers enable it on every replica they own)"
            )

    def _export_fn(self, n_pad: int):
        fn = self._export_fns.get(n_pad)
        if fn is not None:
            return fn

        def body(cache, logits, idx, slot):
            # the chain's blocks, and the slot's row of per-slot state
            blocks = map_cache(lambda pool: pool[idx],
                               lambda rows: rows[slot], cache)
            return blocks, logits[slot]

        # pure read: nothing donated
        fn = jax.jit(self._named(body, self.export_program_name(n_pad)))
        self._export_fns[n_pad] = fn
        return fn

    def _import_fn(self, n_pad: int):
        fn = self._import_fns.get(n_pad)
        if fn is not None:
            return fn

        def body(cache, logits, blocks, idx, slot, row):
            cache = map_cache(
                lambda pool, b: pool.at[idx].set(b),
                lambda rows, r: rows.at[slot].set(r), cache, blocks
            )
            # out-of-bounds slot (warmup's n_slots sentinel) drops the
            # scatter — same inert trick as the chunk program's padding
            # (in a per-slot leaf it is the trash row)
            return cache, logits.at[slot].set(row)

        fn = jax.jit(self._named(body, self.import_program_name(n_pad)),
                     donate_argnums=(0, 1))
        self._import_fns[n_pad] = fn
        return fn

    def export_chain(self, slot: int) -> KVExport:
        """Detach ``slot``'s KV for transfer into another engine's pool.

        ONE compiled gather per chain-length bucket pulls the chain's
        blocks from every pool leaf plus the slot's logits row (the
        first decode token's distribution, written by the final prefill
        chunk); padding lanes read the trash block. Pure read — the slot
        stays resident until ``release``; the caller sequences export →
        ``import_chain`` on the target → release, so a failed import
        (target pool OOM) leaves the source intact and retryable."""
        from pytorch_distributed_tpu.resilience.faults import fault_point

        self._require_handoff()
        # replica-death site: before the chain is read out — the decode
        # side sees the failure mid-adopt, the export pin stays on this
        # source until the router's failure plane disposes of it
        fault_point("serve.handoff_export")
        chain = self.allocator.chain(slot)
        if not chain:
            raise ValueError(f"slot {slot} holds no block chain to export")
        n_pad = self._chain_bucket(len(chain))
        idx = np.full((n_pad,), TRASH_BLOCK, np.int32)
        idx[:len(chain)] = chain
        blocks, row = self._export_fn(n_pad)(
            self.cache, self.logits, jnp.asarray(idx),
            jnp.asarray(slot, jnp.int32),
        )
        return KVExport(
            blocks=blocks,
            logits_row=row,
            n_blocks=len(chain),
            block_len=self.block_len,
        )

    def import_chain(self, slot: int, export: KVExport) -> bool:
        """Adopt an exported chain into ``slot``: allocate a fresh chain,
        ``jax.device_put`` the blocks across meshes/devices onto this
        pool's placement (the only cross-replica data motion in the
        handoff), scatter them in with ONE compiled donated program, and
        remap the block table. Returns False (state unchanged) when the
        pool cannot supply the chain — the caller keeps the export and
        retries, exactly the deterministic-OOM contract of ``admit``."""
        from pytorch_distributed_tpu.resilience.faults import fault_point

        self._require_handoff()
        # replica-death site: before any fresh block is allocated here —
        # a failure leaves the source chain intact and re-exportable
        # (the PR 16 failure-safe handoff contract)
        fault_point("serve.handoff_import")
        if export.block_len != self.block_len:
            raise ValueError(
                f"cannot import block_len={export.block_len} blocks into "
                f"a block_len={self.block_len} pool"
            )
        with self._san_site("handoff-import"):
            chain = self._alloc_evict(slot, [], export.n_blocks)
        if chain is None:
            return False
        n_pad = self._chain_bucket(export.n_blocks)
        idx = np.full((n_pad,), TRASH_BLOCK, np.int32)
        idx[:export.n_blocks] = chain
        try:
            # the explicit block-transfer step (a no-op view when source
            # and target share a device). Padding lanes scatter into the
            # trash block, which absorbs anything.
            blocks = jax.tree.map(
                lambda b, pool: jax.device_put(b, pool.sharding),
                export.blocks, self.cache,
            )
            row = jax.device_put(export.logits_row, self.logits.sharding)
            self.cache, self.logits = self._import_fn(n_pad)(
                self.cache, self.logits, blocks, jnp.asarray(idx),
                jnp.asarray(slot, jnp.int32), row,
            )
        except BaseException:
            # the fresh chain was allocated but never committed to the
            # table: free it, or a failed cross-device transfer leaks
            # the whole chain (blocksan: leak-at-retire). The export is
            # untouched — the caller's retry contract holds.
            with self._san_site("handoff-import"):
                self.allocator.free(slot)
            self.tables[slot] = TRASH_BLOCK
            raise
        self.tables[slot] = TRASH_BLOCK
        self.tables[slot, :export.n_blocks] = chain
        return True

    # ---- host-offload swap (round 13 pressure tier) ----

    def _require_swap(self):
        if not self.swap:
            raise RuntimeError(
                "this engine was built without swap=True — its registry "
                "does not predict kv_swap_out/kv_swap_in programs "
                "(offload-enabled schedulers set it)"
            )

    def _swap_out_fn(self, n_pad: int):
        fn = self._swap_out_fns.get(n_pad)
        if fn is not None:
            return fn

        def body(cache, logits, idx, slot):
            # the chain's blocks, and the slot's row of per-slot state
            blocks = map_cache(lambda pool: pool[idx],
                               lambda rows: rows[slot], cache)
            return blocks, logits[slot]

        # pure read: nothing donated
        fn = jax.jit(self._named(body, self.swap_out_program_name(n_pad)))
        self._swap_out_fns[n_pad] = fn
        return fn

    def _swap_in_fn(self, n_pad: int):
        fn = self._swap_in_fns.get(n_pad)
        if fn is not None:
            return fn

        def body(cache, logits, blocks, idx, slot, row):
            cache = map_cache(
                lambda pool, b: pool.at[idx].set(b),
                lambda rows, r: rows.at[slot].set(r), cache, blocks
            )
            return cache, logits.at[slot].set(row)

        fn = jax.jit(self._named(body, self.swap_in_program_name(n_pad)),
                     donate_argnums=(0, 1))
        self._swap_in_fns[n_pad] = fn
        return fn

    def chain_bytes(self, n_blocks: int) -> int:
        """Device bytes ``n_blocks`` pool blocks hold across every cache
        leaf (K + V + scale siblings) plus the slot's row of every per-slot
        leaf and one logits row — the payload
        a swap moves, and the byte side of the swap-vs-recompute
        decision. Pure shape arithmetic on the pool as it was
        allocated."""
        row = self.logits.size * self.logits.dtype.itemsize // self.n_slots
        return n_blocks * self._per_block_bytes + self._per_slot_bytes + row

    def swap_out_begin(self, slot: int) -> PendingSwap:
        """Open a swap-out window on ``slot``'s chain: ONE compiled
        gather (per chain-length bucket) detaches the chain's blocks and
        the slot's logits row, and their async d2h copy starts. The
        chain stays allocated and marked ``swapping-out`` — nothing is
        freed until ``swap_out_finish`` commits the host copy, so a
        failure anywhere in the window leaves the stream resident and
        bit-intact."""
        self._require_swap()
        chain = self.allocator.chain(slot)
        if not chain:
            raise ValueError(f"slot {slot} holds no block chain to swap")
        with self._san_site("swap-out"):
            self.allocator.set_state(slot, SWAPPING_OUT)
        try:
            n_pad = self._chain_bucket(len(chain))
            idx = np.full((n_pad,), TRASH_BLOCK, np.int32)
            idx[:len(chain)] = chain
            blocks, row = self._swap_out_fn(n_pad)(
                self.cache, self.logits, jnp.asarray(idx),
                jnp.asarray(slot, jnp.int32),
            )
            for leaf in jax.tree.leaves(blocks) + [row]:
                try:
                    leaf.copy_to_host_async()  # overlap d2h with serving
                except AttributeError:
                    pass
        except BaseException:
            # a gather failure must not strand the slot inside an open
            # swap window — the allocator would then refuse every later
            # free of this chain (blocksan: pinned-block at retire)
            with self._san_site("swap-out"):
                self.allocator.clear_state(slot)
            raise
        return PendingSwap(slot=slot, chain_len=len(chain), blocks=blocks,
                           logits_row=row)

    def swap_out_finish(self, pending: PendingSwap, store: HostBlockStore,
                        rid: int) -> HostChain:
        """Close the swap-out window: materialize the d2h copy, commit
        the ``HostChain`` to ``store`` under ``rid``, then — and only
        then — free the device chain and trash the slot's table row.

        Hazard sites (``resilience.faults``): ``kv.swap_out_d2h`` before
        the host materialization, ``kv.host_write`` before the store
        commit. ANY failure up to the commit re-raises with the window
        closed and the chain still resident — the caller re-arms the
        lane and the stream continues as if nothing happened."""
        from pytorch_distributed_tpu.resilience.faults import fault_point

        slot = pending.slot
        try:
            fault_point("kv.swap_out_d2h")
            blocks = map_cache(
                lambda b: np.asarray(
                    jax.device_get(b))[:pending.chain_len],
                lambda r: np.asarray(jax.device_get(r)),
                pending.blocks,
            )
            row = np.asarray(jax.device_get(pending.logits_row))
            nbytes = row.nbytes + sum(
                b.nbytes for b in jax.tree.leaves(blocks)
            )
            chain = HostChain(blocks=blocks, logits_row=row,
                              n_blocks=pending.chain_len,
                              block_len=self.block_len, nbytes=nbytes)
            fault_point("kv.host_write")
            if not store.put(rid, chain):
                raise OSError(
                    f"host store rejected rid {rid}'s chain "
                    f"({nbytes} bytes over budget)"
                )
        except BaseException:
            # window closed, chain untouched: the stream stays resident
            with self._san_site("swap-out"):
                self.allocator.clear_state(slot)
            raise
        with self._san_site("swap-out"):
            self.allocator.clear_state(slot)
            self.release(slot)
        return chain

    def swap_in_chain(self, slot: int, chain: HostChain) -> bool:
        """Restore a host chain into ``slot``: allocate fresh blocks,
        h2d the payload onto the pool's placement, scatter with ONE
        donated program (per bucket), and remap the table. Returns False
        (state unchanged) when the pool cannot supply the chain — the
        caller keeps the host copy and retries, the ``admit`` contract.

        Hazard site ``kv.swap_in_h2d`` fires before any device write: a
        failure there frees the fresh chain and re-raises with the host
        copy intact — the restore is retryable, never half-applied."""
        from pytorch_distributed_tpu.resilience.faults import fault_point

        self._require_swap()
        if chain.block_len != self.block_len:
            raise ValueError(
                f"cannot swap block_len={chain.block_len} blocks into "
                f"a block_len={self.block_len} pool"
            )
        with self._san_site("swap-in"):
            ids = self._alloc_evict(slot, [], chain.n_blocks)
        if ids is None:
            return False
        self.allocator.set_state(slot, SWAPPING_IN)
        n_pad = self._chain_bucket(chain.n_blocks)
        try:
            fault_point("kv.swap_in_h2d")
            idx = np.full((n_pad,), TRASH_BLOCK, np.int32)
            idx[:chain.n_blocks] = ids

            def _padded(b, pool):
                if n_pad > b.shape[0]:  # padding lanes hit the trash block
                    pad = np.zeros((n_pad - b.shape[0],) + b.shape[1:],
                                   b.dtype)
                    b = np.concatenate([b, pad])
                return jax.device_put(b, pool.sharding)

            blocks = map_cache(
                _padded, lambda r, rows: jax.device_put(r, rows.sharding),
                chain.blocks, self.cache)
            row = jax.device_put(chain.logits_row, self.logits.sharding)
            self.cache, self.logits = self._swap_in_fn(n_pad)(
                self.cache, self.logits, blocks, jnp.asarray(idx),
                jnp.asarray(slot, jnp.int32), row,
            )
        except BaseException:
            with self._san_site("swap-in"):
                self.allocator.clear_state(slot)
                self.allocator.free(slot)
            self.tables[slot] = TRASH_BLOCK
            raise
        with self._san_site("swap-in"):
            self.allocator.clear_state(slot)
        self.tables[slot] = TRASH_BLOCK
        self.tables[slot, :chain.n_blocks] = ids
        return True

    def run_chunks(self, jobs: List[ChunkJob]) -> None:
        """ONE compiled program prefilling one chunk for each job.

        The job count pads to a power of two and the table slice to the
        narrowest power-of-two block count covering every job's chunk end
        — so the program's shapes (and cost) follow the PROMPT bucket,
        never the pool. Chunks of one prompt must be submitted in order
        (chunk n+1 attends to chunk n's writes through the pool)."""
        if not jobs:
            return
        if len(jobs) > self.max_chunk_jobs:
            raise ValueError(
                f"{len(jobs)} chunk jobs in one call; max_chunk_jobs is "
                f"{self.max_chunk_jobs}"
            )
        c = self.chunk
        for j in jobs:
            if len(j.tokens) != c:
                raise ValueError(
                    f"chunk job for slot {j.slot} has {len(j.tokens)} "
                    f"tokens; engine chunk length is {c}"
                )
        tr = spans.tracer()
        with tr.span("engine.chunk.build"):
            k_pad, wp = self.bucket_for(jobs)
            host = self._chunk_operand(k_pad, wp, jobs)
            fn = self._chunk_fn(k_pad, wp)
            name = self.chunk_program_name(k_pad, wp)
            put_args = _put_args(host)
        with tr.span("engine.chunk.launch", jobs=len(jobs),
                     bucket=(k_pad, wp)), \
                program_load_if((k_pad, wp) not in self._hot_chunks, name):
            # ONE explicit transfer of the one packed operand, inside
            # the launch span (dispatch cost; see the decode launch's
            # note on why it is explicit)
            with tr.span("engine.chunk.put", **put_args):
                packed = jax.device_put(host)
            with tr.span("engine.chunk.call",
                         leaves=self._resident_leaves + 1):
                self.cache, self.logits, *counts = fn(
                    self.params, self.cache, self.logits, packed,
                )
        if counts:
            self.chunk_expert_counts = counts[0]
        self._hot_chunks.add((k_pad, wp))

    def decode_launch(self, positions: np.ndarray, active: np.ndarray,
                      rng):
        """Launch one decode tick for every slot WITHOUT waiting for it:
        ``(device_tokens, new_positions)``. The program samples from the
        logits buffer and writes each active lane's token at its position;
        inactive lanes compute dead garbage routed to the trash block and
        keep their position. ``new_positions`` is a host array, a copy:
        the launched positions plus one on the active lanes, which is all
        the tick does to them, so nothing is fetched for it (rows the
        caller arms before the collect are not in it). The caller
        materializes the tokens later through ``decode_collect``, while
        this device (or another replica's) is already running the next
        program."""
        tr = spans.tracer()
        with tr.span("engine.decode.build"):
            positions = np.asarray(positions, np.int32)
            host = self._decode_operand(positions, active)
            # what the tick leaves behind: one more on a lane that decodes
            new_positions = positions + active
            fn = self._decode()
            if self.device is not None:
                # keys are computed arrays; pin them next to the replica's
                # committed working set so the program has one placement
                rng = jax.device_put(rng, self.device)
            # live_blocks: the blocks up to each active lane's position,
            # the part of the tables' ``table_blocks`` a tick has to read;
            # live_tiles: the kernel's grid steps that hold one of them
            # state_rows: the lanes whose recurrent state the tick reads
            # and writes (0 where the cache holds none)
            live = positions[active] // self.block_len
            lanes = int(np.count_nonzero(active))
            launch_args = dict(
                lanes=lanes,
                state_rows=lanes if self.slot_state_bytes else 0,
                live_blocks=int(np.sum(live + 1)),
                live_tiles=int(np.sum(live // self.tile_blocks + 1)))
            put_args = _put_args(host)
        # ONE explicit transfer of the one packed operand, inside the
        # launch span — it is dispatch cost, and it costs its arrays, not
        # its bytes (0.2 ms an array on a v5e's host, PERF.md): table,
        # positions and flags cross as one. A bare-np jit call would be an
        # IMPLICIT transfer the no_recompile guard rightly rejects.
        with tr.span("engine.decode.launch", **launch_args), \
                program_load_if(not self._hot_decode,
                                self.DECODE_PROGRAM):
            with tr.span("engine.decode.put", **put_args):
                packed = jax.device_put(host)
            # and the key: the call flattens it with the operand
            with tr.span("engine.decode.call",
                         leaves=self._resident_leaves + 2):
                self.cache, self.logits, tokens, *counts = fn(
                    self.params, self.cache, self.logits, packed, rng,
                )
            self._tick_counts = counts[0] if counts else None
        self._hot_decode = True
        return tokens, new_positions

    def decode_collect(self, tokens, new_positions):
        """Materialize a ``decode_launch``'s results: fetches the tokens
        to the host, the one fetch of a tick and where the host waits for
        the device. Returns ``(tokens [n_slots], new_positions)``, both on
        the host."""
        with spans.tracer().span("engine.collect.wait"):
            tokens = self._fetch_tick(tokens)
        return tokens, new_positions

    def decode(self, positions: np.ndarray, active: np.ndarray, rng):
        """One whole decode tick, launched then collected: ``(tokens
        [n_slots], new_positions)``, both on the host."""
        return self.decode_collect(
            *self.decode_launch(positions, active, rng))

    def _fetch_tick(self, tokens) -> np.ndarray:
        """The tick's tokens on the host and, in the same fetch (no
        second wait), the tokens its experts took
        (``tick_expert_counts``)."""
        if self._tick_counts is None:
            return np.asarray(tokens)
        tokens, self.tick_expert_counts = jax.device_get(
            (tokens, self._tick_counts))
        return tokens
