"""Paged-KV serving engine (round 6 tentpole): block allocator, paged
attention math, dense-vs-paged token parity (single device and TP=2),
chunked-prefill equivalence, scheduler policy + exact metrics, and the
admission-cost scaling micro-bench (cost-analysis bytes: paged flat in
pool size, dense growing with it)."""

import dataclasses
import importlib
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_tpu.models.generate import ContinuousBatcher, generate
from pytorch_distributed_tpu.models.transformer import (
    TransformerLM,
    tiny_config,
)
from pytorch_distributed_tpu.ops.attention import paged_attention
from pytorch_distributed_tpu.serving import (
    TRASH_BLOCK,
    BlockAllocator,
    PagedEngine,
    Scheduler,
    blocks_needed,
)
from pytorch_distributed_tpu.serving.engine import ChunkJob
from pytorch_distributed_tpu.serving.kv_pool import pool_leaf_shape
from pytorch_distributed_tpu.telemetry import spans


def setup(max_seq_len=96, **over):
    cfg = tiny_config(attention="dense", max_seq_len=max_seq_len, **over)
    params = TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return cfg, params


def greedy_reference(cfg, params, prompt, max_new):
    full = generate(
        cfg, params, jnp.asarray(prompt)[None, :], jax.random.key(1),
        max_new_tokens=max_new, temperature=0.0,
    )
    return np.asarray(full)[0, len(prompt):]


# ---------------------------------------------------------------------------
# block allocator (pure host logic — fast tier)
# ---------------------------------------------------------------------------


def test_allocator_alloc_free_reuse_oom():
    a = BlockAllocator(8)  # ids 1..7 usable, 0 is trash
    assert a.available == 7 and a.in_use == 0
    c0 = a.alloc(0, 3)
    assert c0 == [1, 2, 3]  # deterministic first-allocation order
    assert TRASH_BLOCK not in c0
    c1 = a.alloc(1, 3)
    assert c1 == [4, 5, 6]
    # OOM is a deterministic None with state UNCHANGED — the queue signal
    assert a.alloc(2, 2) is None
    assert a.available == 1 and a.chain(2) == []
    # free → LIFO reuse: the just-freed blocks come back first
    a.free(0)
    assert a.available == 4
    c2 = a.alloc(2, 2)
    assert c2 == [1, 2]
    # double-alloc for a live owner is a bug, not a silent leak
    with pytest.raises(ValueError, match="already holds"):
        a.alloc(1, 1)
    a.free(99)  # unknown owner: no-op
    with pytest.raises(ValueError, match="n_blocks"):
        BlockAllocator(1)


def test_blocks_needed_covers_padded_prefill_and_decode():
    # prompt 9 padded to chunk 16 → 1 block of 16; decode to 9+20=29 → 2
    assert blocks_needed(9, 20, block_len=16, chunk=16) == 2
    # chunk padding dominates: prompt 17 pads to 32 > 17+4
    assert blocks_needed(17, 4, block_len=16, chunk=16) == 2
    assert blocks_needed(1, 1, block_len=16, chunk=16) == 1


# ---------------------------------------------------------------------------
# paged attention math (pure op — fast tier)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("h_kv,c", [(4, 1), (4, 5), (2, 5)])
def test_paged_attention_matches_masked_reference(h_kv, c):
    """Gather-over-blocks attention == a straight masked softmax over the
    same logical sequences, including the GQA narrow-head layout."""
    b, h, d, bl, w = 2, 4, 8, 4, 3
    L = w * bl
    rng = np.random.default_rng(0)
    k_seq = rng.normal(size=(b, L, h_kv, d)).astype(np.float32)
    v_seq = rng.normal(size=(b, L, h_kv, d)).astype(np.float32)
    q = jnp.asarray(rng.normal(size=(b, c, h, d)).astype(np.float32))
    # per-request block chains laid out non-contiguously in the pool
    n_blocks = 1 + b * w
    pool_k = np.zeros((n_blocks, bl, h_kv, d), np.float32)
    pool_v = np.zeros((n_blocks, bl, h_kv, d), np.float32)
    tables = np.zeros((b, w), np.int32)
    order = rng.permutation(np.arange(1, n_blocks))
    for bi in range(b):
        for wi in range(w):
            blk = int(order[bi * w + wi])
            tables[bi, wi] = blk
            pool_k[blk] = k_seq[bi, wi * bl:(wi + 1) * bl]
            pool_v[blk] = v_seq[bi, wi * bl:(wi + 1) * bl]
    q_positions = np.stack([
        np.arange(L - c, L), np.arange(3, 3 + c)
    ])[:b].astype(np.int32)

    # rows were laid out per head; the pool stores them flattened
    leaf = pool_leaf_shape(n_blocks, bl, h_kv, d)
    out = paged_attention(
        q, jnp.asarray(pool_k.reshape(leaf)),
        jnp.asarray(pool_v.reshape(leaf)), jnp.asarray(tables),
        jnp.asarray(q_positions),
    )

    group = h // h_kv
    kw = np.repeat(k_seq, group, axis=2)  # widen narrow heads
    vw = np.repeat(v_seq, group, axis=2)
    ref = np.zeros((b, c, h, d), np.float32)
    for bi in range(b):
        for ci in range(c):
            p = int(q_positions[bi, ci])
            logits = np.einsum(
                "hd,khd->hk", np.asarray(q[bi, ci]) * d ** -0.5,
                kw[bi, :p + 1],
            )
            probs = np.exp(logits - logits.max(-1, keepdims=True))
            probs /= probs.sum(-1, keepdims=True)
            ref[bi, ci] = np.einsum("hk,khd->hd", probs, vw[bi, :p + 1])
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend,want", [
    ("cpu", "dense"), ("gpu", "dense"), ("tpu", "pallas"),
])
def test_the_backend_and_the_rows_decide_the_paged_read(
        monkeypatch, backend, want):
    """One rule, ``ops.attention.default_gather_impl``: a decode tick
    reads through the fused kernel where the backend is a TPU and
    through the dense gather on every other, and a chunk's wider rows
    gather dense everywhere. The engine reports its tick's read."""
    from pytorch_distributed_tpu.ops.attention import (
        KERNEL_MAX_ROWS,
        default_gather_impl,
    )

    cfg, params = setup()
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert default_gather_impl() == want
    assert default_gather_impl(KERNEL_MAX_ROWS) == want
    assert default_gather_impl(rows=32) == "dense"  # a chunk's rows
    eng = PagedEngine(cfg, params, 2, block_len=8, prefill_chunk=8)
    assert eng.gather_impl == want


def _serve_lm(monkeypatch):
    import importlib.util
    import os

    recipes = os.path.join(os.path.dirname(__file__), os.pardir, "recipes")
    monkeypatch.syspath_prepend(recipes)
    spec = importlib.util.spec_from_file_location(
        "serve_lm", os.path.join(recipes, "serve_lm.py"))
    serve_lm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(serve_lm)
    return serve_lm


@pytest.mark.parametrize("taker", [
    "TransformerConfig", "PagedEngine", "Scheduler", "ContinuousBatcher",
    "FleetRouter", "serve_lm",
])
def test_nothing_takes_a_paged_read_option(taker, monkeypatch, capsys):
    """Which program reads the pool, and with how many workers, is
    ``ops.attention.default_gather_impl``'s and
    ``ops.paged_flash.auto_split_s``'s to say: no config field, no
    constructor keyword and no flag names a spelling, a worker count or
    a tuned file."""
    from pytorch_distributed_tpu.fleet import FleetRouter

    cfg, params = setup()
    build = {
        "TransformerConfig": lambda **kw: dataclasses.replace(cfg, **kw),
        "PagedEngine": lambda **kw: PagedEngine(cfg, params, 2, **kw),
        "Scheduler": lambda **kw: Scheduler(cfg, params, 2, **kw),
        "ContinuousBatcher": lambda **kw: ContinuousBatcher(
            cfg, params, 2, **kw),
        "FleetRouter": lambda **kw: FleetRouter(
            cfg, params, n_replicas=1, n_slots=2, **kw),
    }
    for option, value in (("gather_impl", "dense"), ("split_s", 1),
                          ("autotune_dir", "tuned")):
        if taker == "serve_lm":
            flag = "--" + option.replace("_", "-")
            with pytest.raises(SystemExit) as refused:
                _serve_lm(monkeypatch)._parse(["--tiny", flag, str(value)])
            assert refused.value.code == 2  # argparse: unrecognized
            assert flag in capsys.readouterr().err
        else:
            with pytest.raises(TypeError, match=option):
                build[taker](**{option: value})


def test_paged_attention_gather_impl_flag():
    z = jnp.zeros((1, 1, 2, 4))
    pool = jnp.zeros(pool_leaf_shape(2, 4, 2, 4))
    t = jnp.zeros((1, 1), jnp.int32)
    p = jnp.zeros((1, 1), jnp.int32)
    with pytest.raises(ValueError, match="gather_impl"):
        paged_attention(z, pool, pool, t, p, gather_impl="nope")
    # round 12: "pallas" is no longer reserved — it dispatches to the
    # fused kernel (ops/paged_flash.py; parity in tests/test_paged_
    # kernel.py) and must agree with the dense spelling even on this
    # degenerate all-zeros pool
    out = paged_attention(z, pool, pool, t, p, gather_impl="pallas")
    ref = paged_attention(z, pool, pool, t, p, gather_impl="dense")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref))


# ---------------------------------------------------------------------------
# admission cost scaling (compiled cost analysis — deterministic, fast tier)
# ---------------------------------------------------------------------------


def _total_bytes(compiled):
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    return float(ca["bytes accessed"])


def _pool_shaped_ops(compiled, leaf):
    """``(name, opcode)`` of every instruction of a compiled program
    whose result has a pool leaf's shape, parameters left out."""
    shape = "[" + ",".join(map(str, leaf.shape)) + "]"
    ops = []
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?(\S+) = (\S+) ([\w-]+)\(", line)
        if m and shape in m.group(2) and m.group(3) != "parameter":
            ops.append((m.group(1), m.group(3)))
    return ops


def test_admission_cost_paged_flat_dense_grows():
    """THE tentpole claim, asserted without wall-clock flakiness: grow
    the KV capacity 8x (max_seq_len 256 → 2048 at fixed slots — the
    dense layout's pool is n_slots × max_seq_len rows) and compare each
    layout's compiled admission program. Dense admission writes a full
    per-slot row → XLA's bytes-accessed must grow. Paged admission
    touches O(prompt) blocks: the only instructions of its chunk program
    that are shaped like a pool leaf are the in-place scatters of the
    donated leaves (one a leaf — no copy, no transpose, no convert of a
    pool), and its temporaries stay flat. (Its ``bytes accessed`` is no
    witness: newer jaxlib counts a scatter's whole operand, 6.48x here,
    though the scatter writes 16 rows in place.) rope positions keep the
    param tree identical across capacities, so the cache is the only
    thing that scales."""

    def build(max_len):
        cfg = tiny_config(
            attention="dense", max_seq_len=max_len, pos_embedding="rope",
            num_heads=4, embed_dim=64,
        )
        params = TransformerLM(cfg).init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        return cfg, params

    prompt = np.arange(1, 10, dtype=np.int32)  # 9 tokens, bucket 16
    padded = np.zeros((1, 16), np.int32)
    padded[0, :len(prompt)] = prompt
    dense_bytes, paged_temp = {}, {}
    for max_len in (256, 2048):
        cfg, params = build(max_len)
        dense = ContinuousBatcher(
            cfg, params, n_slots=8, prefill_bucket=16, cache_layout="dense"
        )
        dense_bytes[max_len] = _total_bytes(dense._submit_one.lower(
            params, jnp.asarray(padded), jnp.asarray([9], jnp.int32),
            dense.cache, dense.logits, jnp.asarray(0),
        ).compile())
        eng = PagedEngine(cfg, params, n_slots=8, block_len=16,
                          prefill_chunk=16)
        assert eng.admit(0, len(prompt), 6)
        paged = eng._chunk_fn(1, 1).lower(
            params, eng.cache, eng.logits, eng._chunk_operand(1, 1, [
                ChunkJob(0, padded[0], 0, True, len(prompt) - 1)]),
        ).compile()
        paged_temp[max_len] = paged.memory_analysis().temp_size_in_bytes
        leaves = jax.tree.leaves(eng.cache)
        ops = _pool_shaped_ops(paged, leaves[0])
        leaked = [op for op in ops if "scatter" not in op[0]]
        assert not leaked, (
            f"an O(pool) term leaked into the chunk program at capacity "
            f"{max_len}: {leaked} are shaped like a pool leaf and are not "
            "its in-place scatter"
        )
        # the CPU compiler wraps each scatter in a fusion of its own
        assert {op for _n, op in ops} <= {"scatter", "fusion"}, ops
        assert sum(op == "scatter" for _n, op in ops) == len(leaves), ops

    dense_ratio = dense_bytes[2048] / dense_bytes[256]
    # measured ~3.2x on jaxlib 0.4.37; the threshold leaves slack for
    # compiler drift while keeping the asymptotic claim falsifiable
    assert dense_ratio > 1.5, (
        f"dense admission no longer scales with capacity ({dense_ratio:.2f}"
        "x) — if XLA learned to elide the row write, retire this bench "
        "and the paged engine's motivation section"
    )
    assert paged_temp[2048] <= 1.1 * paged_temp[256], (
        f"paged admission's temporaries grew from {paged_temp[256]} to "
        f"{paged_temp[2048]} bytes with pool capacity — an O(pool) term "
        "leaked into the chunk program"
    )


# ---------------------------------------------------------------------------
# smoke (fast tier — scripts/ci_check.sh --serving-smoke runs exactly this)
# ---------------------------------------------------------------------------


def test_serving_smoke():
    """One full paged cycle: submit → decode steps → drain; slots and
    blocks return to the pool."""
    cfg, params = setup(max_seq_len=64)
    b = ContinuousBatcher(cfg, params, n_slots=2, prefill_bucket=8)
    assert b.cache_layout == "paged"
    slot = b.submit(np.arange(1, 10, dtype=np.int32), 4)
    produced = []
    while any(b.remaining > 0):
        produced += b.step()
    assert len(produced) == 4 and all(s == slot for s, _t in produced)
    assert b.engine.allocator.in_use == 0  # chain returned
    assert (b.engine.tables[slot] == TRASH_BLOCK).all()
    assert b.free_slots() == [0, 1]


# ---------------------------------------------------------------------------
# scheduler policy + exact metrics (fast tier — tiny model)
# ---------------------------------------------------------------------------


def test_scheduler_oom_queues_fifo_and_drains():
    """A pool too small for everyone at once: admissions stop at the
    first request that cannot get its chain (strict FIFO), the rest wait
    in queue, and everything still completes as blocks free up."""
    cfg, params = setup(max_seq_len=64)
    # block_len 8, chunk 8: each request (l=9 → padded 16, +4 decode) needs
    # 2 blocks; pool of 5 usable blocks fits TWO resident requests
    s = Scheduler(cfg, params, n_slots=4, n_blocks=6, block_len=8,
                  prefill_chunk=8)
    prompt = np.arange(1, 10, dtype=np.int32)
    rids = [s.submit(prompt, 4) for _ in range(4)]
    s.step()
    m = s.metrics()
    assert m["admitted"] == 2  # 3rd request OOM'd → queued, 4th behind it
    assert m["queue_depth"] == 2
    assert m["pool_blocks_in_use"] == 4
    outs = s.drain()
    assert sorted(outs) == sorted(rids)
    assert all(len(v) == 4 for v in outs.values())
    ref = list(greedy_reference(cfg, params, prompt, 4))
    for r in rids:
        assert outs[r] == ref  # queueing never changes tokens
    m = s.metrics()
    assert m["completed"] == 4 and m["queue_depth"] == 0
    assert m["pool_blocks_in_use"] == 0 and m["occupancy"] == 0.0
    # later arrivals waited: admission latency in steps is exact
    assert m["admission_latency_steps_mean"] > 0


def test_scheduler_metrics_exact_accounting():
    cfg, params = setup(max_seq_len=64)
    s = Scheduler(cfg, params, n_slots=1, block_len=8, prefill_chunk=8)
    prompt = np.arange(1, 6, dtype=np.int32)
    r0 = s.submit(prompt, 3)
    r1 = s.submit(prompt, 2)
    outs = s.drain()
    m = s.metrics()
    assert m["tokens_out"] == 5 == len(outs[r0]) + len(outs[r1])
    assert m["admitted"] == m["completed"] == 2
    # one slot: r0 runs steps 0..3 (chunk step + 3 decode), r1 admitted
    # the step after r0 retires → latency is deterministic and positive
    assert s.resident == {} and not s.queue
    assert 0.0 <= m["occupancy_mean"] <= 1.0
    assert 0.0 <= m["padding_waste_frac"] <= 1.0
    assert m["tokens_per_s"] > 0
    # padding waste while resident: 5-token prompt in 8-token blocks
    s2 = Scheduler(cfg, params, n_slots=1, block_len=8, prefill_chunk=8)
    s2.submit(prompt, 2)
    s2.step()  # chunk runs; first token decoded
    w = s2.metrics()["padding_waste_frac"]
    # 1 block of 8 allocated (covers 5+2), 5+1 tokens written → 2/8 waste
    assert abs(w - 2 / 8) < 1e-9


def test_scheduler_eos_early_retirement_frees_blocks():
    cfg, params = setup(max_seq_len=64)
    prompt = np.arange(1, 10, dtype=np.int32)
    first = int(greedy_reference(cfg, params, prompt, 1)[0])
    s = Scheduler(cfg, params, n_slots=1, block_len=8, prefill_chunk=8,
                  eos_id=first)
    rid = s.submit(prompt, 10)
    outs = s.drain()
    assert outs[rid] == [first]  # retired after 1 of 10
    assert s.metrics()["pool_blocks_in_use"] == 0


def test_scheduler_submit_validation():
    cfg, params = setup(max_seq_len=32)
    s = Scheduler(cfg, params, n_slots=1, block_len=8, prefill_chunk=8)
    with pytest.raises(ValueError, match="at least one token"):
        s.submit(np.zeros((0,), np.int32), 2)
    with pytest.raises(ValueError, match="max_seq_len"):
        s.submit(np.arange(1, 30, dtype=np.int32), 8)


def test_engine_rejects_oversized_chunk_and_chain():
    cfg, params = setup(max_seq_len=32)
    eng = PagedEngine(cfg, params, n_slots=1, block_len=8, prefill_chunk=8)
    with pytest.raises(ValueError, match="chunk"):
        eng.run_chunks([ChunkJob(0, np.zeros(4, np.int32), 0, True, 0)])
    with pytest.raises(ValueError, match="table width"):
        eng.admit(0, 30, 30)  # needs > max_seq_len worth of blocks


# ---------------------------------------------------------------------------
# token parity + chunked prefill equivalence (slow tier, like test_serving)
# ---------------------------------------------------------------------------


def _drive_batcher(b, prompts, budgets):
    got, slot_of, pending = {}, {}, list(range(len(prompts)))
    while pending or any(b.remaining > 0):
        while pending and b.free_slots():
            i = pending.pop(0)
            slot_of[i] = b.submit(prompts[i], budgets[i])
            got[i] = []
        for slot, token in b.step():
            req = next(i for i, s in slot_of.items()
                       if s == slot and len(got[i]) < budgets[i])
            got[req].append(token)
    return got


@pytest.mark.slow
def test_paged_batcher_matches_dense_continuous():
    """Staggered admissions, slot reuse, mixed budgets: the paged engine
    must emit token-identical greedy streams to the dense layout."""
    cfg, params = setup()
    rng = np.random.default_rng(1)
    prompts = [
        rng.integers(1, cfg.vocab_size, (l,)).astype(np.int32)
        for l in (7, 13, 4, 21)
    ]
    budgets = [6, 10, 8, 5]
    dense = _drive_batcher(
        ContinuousBatcher(cfg, params, n_slots=2, prefill_bucket=8,
                          cache_layout="dense"),
        prompts, budgets,
    )
    paged = _drive_batcher(
        ContinuousBatcher(cfg, params, n_slots=2, prefill_bucket=8,
                          cache_layout="paged"),
        prompts, budgets,
    )
    assert dense == paged


@pytest.mark.slow
@pytest.mark.parametrize("kv_heads", [None, 2])
def test_paged_batcher_tp_matches_dense(kv_heads):
    """TP=2 CPU mesh: the paged TP batcher (head-sharded block pool,
    Megatron collectives inside the chunk/decode programs) matches the
    replicated DENSE batcher token-for-token — and really is sharded."""
    from pytorch_distributed_tpu.parallel import make_mesh

    rep = tiny_config(attention="dense", max_seq_len=96, num_heads=4,
                      num_kv_heads=kv_heads)
    tpcfg = dataclasses.replace(rep, model_axis="model", tp_size=2)
    params = TransformerLM(rep).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    mesh = make_mesh(jax.devices()[:2], data_parallel=1, seq_parallel=1,
                     model_parallel=2)
    rng = np.random.default_rng(2)
    prompts = [
        rng.integers(1, rep.vocab_size, (l,)).astype(np.int32)
        for l in (5, 11, 7)
    ]
    budgets = [6, 6, 6]
    dense_rep = _drive_batcher(
        ContinuousBatcher(rep, params, n_slots=2, prefill_bucket=8,
                          cache_layout="dense"),
        prompts, budgets,
    )
    paged_tp = ContinuousBatcher(tpcfg, params, n_slots=2, prefill_bucket=8,
                                 mesh=mesh, cache_layout="paged")
    assert _drive_batcher(paged_tp, prompts, budgets) == dense_rep
    # the pool really is head-sharded at rest
    leaf = jax.tree.leaves(paged_tp.cache)[0]
    assert next(iter(leaf.addressable_shards)).data.shape[2] == \
        leaf.shape[2] // 2


@pytest.mark.slow
def test_chunked_prefill_matches_whole_prefill():
    """A long prompt prefilled in 8-token chunks produces the same
    first-token logits path (hence identical greedy tokens) as one-shot
    prefill — the chunk boundary cannot change the math."""
    cfg, params = setup()
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, cfg.vocab_size, (29,)).astype(np.int32)
    ref = greedy_reference(cfg, params, prompt, 8)
    for bucket in (8, 16, 32):  # 4 chunks, 2 chunks, whole-prompt
        b = ContinuousBatcher(cfg, params, n_slots=1,
                              prefill_bucket=bucket)
        slot = b.submit(prompt, 8)
        got = []
        while any(b.remaining > 0):
            got += [t for _s, t in b.step()]
        np.testing.assert_array_equal(
            np.asarray(got, np.int32), ref, err_msg=f"bucket {bucket}"
        )


@pytest.mark.slow
def test_scheduler_interleaves_long_prefill_with_decode():
    """Chunked prefill is the point: while a LONG prompt prefills chunk
    by chunk, an already-resident request keeps decoding every step (the
    dense layout would have stalled it for the whole prefill)."""
    cfg, params = setup(max_seq_len=96)
    s = Scheduler(cfg, params, n_slots=2, block_len=8, prefill_chunk=8,
                  admit_per_step=1)
    short = np.arange(1, 6, dtype=np.int32)
    long = np.arange(1, 41, dtype=np.int32)  # 5 chunks of 8
    produced = {}

    def tick():
        events = s.step()
        for rid, tok in events:
            produced.setdefault(rid, []).append(tok)
        return dict(events)

    r_short = s.submit(short, 12)
    tick()  # short admitted + prefilled (1 chunk) + first token
    r_long = s.submit(long, 2)
    short_tokens_during_long_prefill = 0
    for _ in range(5):  # the long prompt's 5 prefill-chunk steps
        if r_short in tick():
            short_tokens_during_long_prefill += 1
    assert short_tokens_during_long_prefill == 5  # never stalled
    for rid, toks in s.drain().items():
        produced.setdefault(rid, []).extend(toks)
    assert produced[r_short] == list(greedy_reference(cfg, params, short, 12))
    assert produced[r_long] == list(greedy_reference(cfg, params, long, 2))


# ---------------------------------------------------------------------------
# a tick crosses the host/device boundary once each way (PR 39): a launch
# moves ONE packed int32 operand, a collect fetches the tokens alone
# ---------------------------------------------------------------------------

#: the kinds of stack the benchmark's cells serve, at their files' toy
#: widths: plain configs (gpt2-style, looped) and ones whose state is a
#: request's (``PagedEngine._per_request``: zaya's tail and dropless
#: experts, ling's recurrent state), whose chunk operand carries ``length``
KINDS = ("gpt2", "looped", "zaya", "ling")
TICK_KW = dict(n_slots=4, block_len=8, prefill_chunk=8)
#: one chunk program an engine (every launch pads to four jobs over the
#: whole table): the served backlogs below compile two programs, not ten
ONE_BUCKET = dict(chunk_bucket_floor=(4, 8), admit_per_step=3, **TICK_KW)
NEW = 5


def _toy(kind):
    if kind == "gpt2":
        return setup(max_seq_len=64)
    module = importlib.import_module(f"test_{kind}_lm")
    cfg = getattr(module, f"{kind}_config")()
    return cfg, module.seeded(cfg)


def _backlog(cfg):
    """Multi-chunk prompts among short ones; three admissions a tick, so
    the first chunk launch pads a job (3 -> 4) and a lane decodes while
    the others' slots are in mid-prefill."""
    rng = np.random.default_rng(0)
    return [rng.integers(1, min(cfg.vocab_size, 128), size=n).astype(np.int32)
            for n in (5, 13, 9, 20, 3)]


@pytest.fixture(scope="module", params=KINDS)
def served(request):
    """One backlog through the router's lagged loop and through the
    synchronous ``Scheduler.step``, beside ``generate``'s greedy streams
    (the dense cache: no paged program), and the lagged run's spans."""
    from pytorch_distributed_tpu.fleet import FleetRouter

    with jax.default_matmul_precision("highest"):
        cfg, params = _toy(request.param)
        prompts = _backlog(cfg)
        tr = spans.tracer()
        n0 = len(tr.events())
        router = FleetRouter(cfg, params, n_replicas=1, **ONE_BUCKET)
        rids = [router.submit(p, NEW) for p in prompts]
        out = router.drain()
        lagged = [list(map(int, out[r])) for r in rids]
        events = tr.events()[n0:]
        sched = Scheduler(cfg, params, **ONE_BUCKET)
        rids = [sched.submit(p, NEW) for p in prompts]
        out = sched.drain()
        sync = [list(map(int, out[r])) for r in rids]
        reference = [list(map(int, greedy_reference(cfg, params, p, NEW)))
                     for p in prompts]
    engine = router.replicas[0].engine
    assert engine.allocator.in_use == sched.engine.allocator.in_use == 0
    return dict(kind=request.param, engine=engine, events=events,
                lagged=lagged, sync=sync, reference=reference)


def test_greedy_streams_are_the_same_through_both_loops(served):
    """Token for token: the lagged loop (a tick in flight, positions
    written back at the next step's collect), the synchronous step, and
    the full-sequence reference, over a backlog with multi-chunk prompts,
    a padding job and inactive lanes."""
    assert served["lagged"] == served["sync"] == served["reference"]
    assert all(len(s) == NEW for s in served["lagged"])
    chunks = [e.args for e in served["events"]
              if e.name == "engine.chunk.launch"]
    assert any(a["jobs"] == 3 and a["bucket"][0] == 4 for a in chunks)
    assert len(chunks) >= 3  # the 20-token prompt alone takes three
    lanes = [e.args["lanes"] for e in served["events"]
             if e.name == "engine.decode.launch"]
    assert min(lanes) < TICK_KW["n_slots"] and max(lanes) > 1


def test_every_launch_of_a_served_backlog_moves_one_array(served):
    """``arrays`` on the put spans is the counter that says the mechanism
    engaged: 1 on every launch of both programs, for a plain engine and
    for one whose chunk operand carries ``length``; ``bytes`` is the one
    int32 matrix's."""
    engine = served["engine"]
    assert engine._per_request == (served["kind"] in ("zaya", "ling"))
    puts = {prog: [e.args for e in served["events"]
                   if e.name == f"engine.{prog}.put"]
            for prog in ("chunk", "decode")}
    assert puts["chunk"] and puts["decode"]
    assert {a["arrays"] for prog in puts for a in puts[prog]} == {1}
    n, w, c = engine.n_slots, engine.table_width, engine.chunk
    assert {a["bytes"] for a in puts["decode"]} == {4 * n * (w + 2)}
    scalars = 5 if engine._per_request else 4
    buckets = [e.args["bucket"] for e in served["events"]
               if e.name == "engine.chunk.launch"]
    assert [a["bytes"] for a in puts["chunk"]] == [
        4 * k * (c + wp + scalars) for k, wp in buckets]


def test_the_operands_rows_are_the_jobs_and_the_lanes():
    """The builders' layout, column for column: what the programs slice
    apart is what the host wrote, bit for bit."""
    cfg, params = setup(max_seq_len=64)
    eng = PagedEngine(cfg, params, **TICK_KW)
    assert eng.admit(2, 11, 4) and eng.admit(0, 3, 4)
    seg = np.arange(1, 9, dtype=np.int32)
    host = eng._chunk_operand(4, 2, [
        ChunkJob(2, seg, 8, True, 2),
        ChunkJob(0, seg[::-1].copy(), 0, False, 0)])
    assert host.dtype == np.int32 and host.shape == (4, 8 + 2 + 4)
    np.testing.assert_array_equal(host[0], [*seg, *eng.tables[2, :2],
                                            8, 2, 1, 2])
    np.testing.assert_array_equal(host[1], [*seg[::-1], *eng.tables[0, :2],
                                            0, 0, 0, 0])
    # padding jobs: trash tables, and the slot past the logits buffer
    for row in host[2:]:
        np.testing.assert_array_equal(
            row, [0] * 8 + [TRASH_BLOCK] * 2 + [0, eng.n_slots, 0, 0])
    positions = np.asarray([3, 0, 11, 7], np.int32)
    active = np.asarray([True, False, True, False])
    host = eng._decode_operand(positions, active)
    assert host.dtype == np.int32 and host.shape == (4, eng.table_width + 2)
    np.testing.assert_array_equal(host[:, :-2][active], eng.tables[active])
    assert (host[:, :-2][~active] == TRASH_BLOCK).all()
    np.testing.assert_array_equal(host[:, -2], positions)
    np.testing.assert_array_equal(host[:, -1], [1, 0, 1, 0])


@pytest.mark.parametrize("kind", ["gpt2", "zaya"])
def test_positions_are_counted_on_the_host(kind):
    """``decode`` and ``decode_launch``/``decode_collect`` return the
    launched positions plus one on the active lanes as a host array that
    exists BEFORE the tokens are fetched: the program returns no
    positions and the collect fetches the tokens alone."""
    with jax.default_matmul_precision("highest"):
        cfg, params = _toy(kind)
        eng = PagedEngine(cfg, params, **TICK_KW)
        prompt = _backlog(cfg)[0]
        assert eng.admit(1, len(prompt), 4)
        seg = np.zeros((8,), np.int32)
        seg[:len(prompt)] = prompt
        eng.run_chunks([ChunkJob(1, seg, 0, True, len(prompt) - 1)])
        positions = np.asarray([7, len(prompt), 0, 3], np.int32)
        active = np.asarray([False, True, False, False])
        launched = positions.copy()
        tokens, new = eng.decode(positions, active, jax.random.key(0))
        assert isinstance(tokens, np.ndarray) and isinstance(new, np.ndarray)
        np.testing.assert_array_equal(new, launched + active)
        np.testing.assert_array_equal(positions, launched)  # not in place
        dev_tokens, new2 = eng.decode_launch(new, active,
                                             jax.random.key(0))
        assert isinstance(dev_tokens, jax.Array)
        assert isinstance(new2, np.ndarray) and new2.dtype == np.int32
        np.testing.assert_array_equal(new2, launched + 2 * active)
        fetched = []
        fetch = eng._fetch_tick
        eng._fetch_tick = lambda t: fetched.append(t) or fetch(t)
        tokens, new3 = eng.decode_collect(dev_tokens, new2)
        assert new3 is new2 and fetched == [dev_tokens]
        assert isinstance(tokens, np.ndarray)
    # the tick's outputs: cache, logits, tokens (and the expert counts)
    out = jax.eval_shape(eng._decode(), eng.params, eng.cache, eng.logits,
                         eng._decode_operand(positions, active),
                         jax.random.key(0))
    assert len(out) == (4 if eng._per_request else 3)
    assert out[2].shape == (eng.n_slots,)


def test_a_lane_armed_between_launch_and_collect_keeps_the_hosts_row():
    """The write-back takes ONLY the lanes the tick decoded from the
    engine's count: a row the host arms while the tick is in flight (an
    adopted handoff chain, a restored swap) is not clobbered by the
    launch's frozen copy."""
    cfg, params = setup(max_seq_len=64)
    sched = Scheduler(cfg, params, **TICK_KW)
    sched.submit(np.arange(1, 6, dtype=np.int32), 4)
    sched.step()  # prefilled, armed at 5 and decoded once: lane 0 at 6
    assert sched.positions[0] == 6 and sched.remaining[0] > 0
    sched.dispatch_tick()
    handle = sched._pending_tick
    assert handle.lanes == (0,) and isinstance(handle.positions, np.ndarray)
    assert handle.positions is not sched.positions
    assert handle.positions[0] == 7 and sched.positions[0] == 6
    sched.positions[2] = 17  # armed since the launch
    sched.collect_tick()
    assert sched.positions[0] == 7 and sched.positions[2] == 17


@pytest.mark.parametrize("execute", [True, False])
@pytest.mark.parametrize("kind", ["gpt2", "zaya"])
def test_traffic_behind_a_warm_up_adds_no_program(kind, execute):
    """The warm-ups build their operand through the served call's
    builder, so their avals cannot drift: behind ``warm_chunk`` and
    ``warm_decode`` the served calls hit the entry the warm-up made
    (``execute=True``) or, behind an AOT compile, make the one entry whose
    operand the ``Compiled`` was lowered for; every armed call runs
    under ``no_recompile`` (the transfer stays explicit)."""
    from pytorch_distributed_tpu.analysis import no_recompile

    with jax.default_matmul_precision("highest"):
        cfg, params = _toy(kind)
        sched = Scheduler(cfg, params, **ONE_BUCKET)
        eng = sched.engine
        assert eng.chunk_buckets() == [(4, 8)]
        compiled = {"chunk": eng.warm_chunk(4, 8, execute=execute),
                    "decode": eng.warm_decode(execute=execute)}
        fns = {"chunk": eng._chunk_fn(4, 8), "decode": eng._decode()}
        assert {p: f._cache_size() for p, f in fns.items()} == dict.fromkeys(
            fns, 1 if execute else 0)
        eng._chunk_fns[4, 8] = no_recompile(
            fns["chunk"], warmup_steps=0 if execute else 1)
        eng._decode_fn = no_recompile(
            fns["decode"], warmup_steps=0 if execute else 1)
        for p in _backlog(cfg):
            sched.submit(p, NEW)
        out = sched.drain()
    assert len(out) == 5 and all(len(t) == NEW for t in out.values())
    assert {p: f._cache_size() for p, f in fns.items()} == dict.fromkeys(
        fns, 1)
    assert eng._decode_fn.stats.calls > 2
    assert eng._chunk_fns[4, 8].stats.calls > 2
    if not execute:
        # the AOT programs took the served operands' avals
        host = {"chunk": eng._chunk_operand(4, 8),
                "decode": eng._decode_operand(
                    np.zeros((4,), np.int32), np.zeros((4,), bool))}
        for prog, c in compiled.items():
            packed = c.args_info[0][3]
            assert (packed.shape, packed.dtype) == (
                host[prog].shape, host[prog].dtype), prog
