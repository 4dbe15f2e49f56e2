"""The ``qwen3-next`` stack through ``PagedEngine`` and ``Scheduler`` at a toy
size on the CPU: chunked prefill then decode against the reference's full
forward (``tests/test_qwen3_next_lm.py`` has the model, the reference and the
tolerance); a cache in which three layers of four own per-slot leaves and the
fourth a ``key`` and a ``value`` pool leaf (reuse, mid-prefill ticks, export /
import and swap); the paged kernel at two narrow heads of eight query rows
and a wide head; what the engine refuses; and the digests of the programs of
the nearest configuration the benchmark already had."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_ling_serving import (  # the engine's drivers, model-blind
    BLOCK,
    CHUNK,
    chunk_jobs,
    decode,
    engine,
    lowered_digest,
    prefill,
)
from test_paged_kernel import random_pool
from test_qwen3_next_lm import (  # noqa: F401  (model, highest: fixtures)
    CONV,
    GDN_LAYERS,
    HELD,
    HV,
    KV_HEADS,
    LAYERS,
    TAPS,
    TOL,
    TOP_K,
    A,
    D,
    full_logits,
    highest,
    model,
    prompts_of,
    qwen_config,
    reference_logits,
)

from perfbench.harness.weights import CASTS
from pytorch_distributed_tpu.models.transformer import GatedDeltaNet
from pytorch_distributed_tpu.ops import attention as attention_ops
from pytorch_distributed_tpu.ops.attention import paged_attention
from pytorch_distributed_tpu.ops.paged_flash import heads_folded
from pytorch_distributed_tpu.serving import Scheduler
from pytorch_distributed_tpu.serving.engine import ChunkJob, PagedEngine
from pytorch_distributed_tpu.serving.kv_pool import (
    HostBlockStore,
    init_paged_cache,
    pool_block_bytes,
    pool_slot_bytes,
)
from pytorch_distributed_tpu.telemetry import spans


def slot_state(eng, slot):
    """What ``slot`` holds beside its blocks: every delta-rule layer's state
    and convolution inputs, flattened."""
    return np.concatenate([
        np.asarray(eng.cache[f"block{i}"]["attn"][name][slot],
                   np.float32).ravel()
        for i in GDN_LAYERS for name in ("state", "conv")])


@pytest.mark.parametrize("lengths,gather_impl", [
    ((5, 11), "dense"), ((8, 3), "dense"), ((9, 17), "dense"),
    ((26, 10), "dense"), ((13, 21), "pallas")])
def test_chunked_prefill_then_decode_matches_the_full_forward(
        model, steer_paged_read, lengths, gather_impl):
    """Chunks of 8 against a convolution of 4 taps: prompts that end inside
    a chunk, on its edge, one and two past it (a window split over two
    chunks at every offset), up to four chunks; every chunk crosses the
    three state layers and the pool layer. At the prompt's last position
    and at every decoded one the LOGITS are the reference's full forward's,
    through the dense gather and through the kernel's folded body (two
    narrow heads of two query rows)."""
    cfg, params = model
    steer_paged_read(gather_impl)
    eng = engine(model)
    prompts = prompts_of(lengths)
    prefill(eng, prompts)
    got = [[np.asarray(eng.logits[s])] for s in range(2)]
    streams = [list(p) for p in prompts]
    for tokens, logits in decode(eng, list(lengths) + [0], 4):
        for s in range(2):
            streams[s].append(int(tokens[s]))
            got[s].append(logits[s])
    for s, p in enumerate(prompts):
        want = reference_logits(params, np.asarray(streams[s])[None])[0]
        assert np.abs(np.stack(got[s]) - want[len(p) - 1:]).max() <= TOL
    control = reference_logits(params, np.asarray(streams[0])[None],
                               CASTS["fp8"])[0]
    assert np.abs(control - reference_logits(
        params, np.asarray(streams[0])[None])[0]).max() > 100 * TOL


def test_the_scheduler_serves_it_and_streams_equal_the_full_forward(model):
    cfg, params = model
    sched = Scheduler(cfg, params, n_slots=3, n_blocks=25, block_len=BLOCK,
                      prefill_chunk=CHUNK)
    prompts = prompts_of([5, 13, 9, 20], seed=0)
    rids = [sched.submit(p, 5) for p in prompts]
    out = sched.drain()
    for rid, p in zip(rids, prompts):
        seq = list(p)
        for _ in range(5):
            seq.append(int(np.argmax(
                full_logits(cfg, params, np.asarray(seq)[None])[0, -1])))
        assert [int(t) for t in out[rid]] == seq[len(p):]
    assert sched.engine.allocator.in_use == 0
    # the tick's spans say what its experts took and whose state it moved
    process = [e.args for e in spans.tracer().events("sched.collect.process")
               if e.args and "pairs" in e.args][-1]
    assert process["pairs"] in (TOP_K, 2 * TOP_K, 3 * TOP_K)
    assert 0 <= process["routed"] <= process["pairs"]
    assert process["experts_hit"] <= min(HELD[1], process["routed"])
    launch = [e.args for e in spans.tracer().events("engine.decode.launch")
              if e.args and "state_rows" in e.args][-1]
    assert launch["state_rows"] == launch["lanes"] >= 1


def test_a_chunk_of_several_blocks_serves_the_same_logits(model,
                                                          monkeypatch):
    """A chunk of 8 in blocks of 3 (3 + 3 + 2 and a padding position): the
    chunk programs and the tick give the reference's logits as with one
    block a chunk."""
    monkeypatch.setattr(GatedDeltaNet, "BLOCK", 3)
    cfg, params = model
    eng = engine(model)
    prompts = prompts_of([13, 21])
    prefill(eng, prompts)
    got = [[np.asarray(eng.logits[s])] for s in range(2)]
    streams = [list(p) for p in prompts]
    for tokens, logits in decode(eng, [13, 21, 0], 2):
        for s in range(2):
            streams[s].append(int(tokens[s]))
            got[s].append(logits[s])
    for s, p in enumerate(prompts):
        want = reference_logits(params, np.asarray(streams[s])[None])[0]
        assert np.abs(np.stack(got[s]) - want[len(p) - 1:]).max() <= TOL


# ---- a cache of state layers and one K/V pool layer ----------------------


def test_the_cache_tree_differs_by_layer(model):
    cfg, params = model
    pool = init_paged_cache(cfg, params, 9, BLOCK, n_slots=3)
    for i in range(LAYERS):
        layer = pool[f"block{i}"]["attn"]
        if i in GDN_LAYERS:  # no pool at all
            assert sorted(layer) == ["conv", "state"]
            assert layer["state"].shape == (3 + 1, HV, D, D)
            assert layer["state"].dtype == jnp.float32
            assert layer["conv"].shape == (3 + 1, TAPS - 1, CONV)
        else:  # real keys and values, two narrow heads side by side
            assert sorted(layer) == ["key", "value"]
            assert layer["key"].shape == layer["value"].shape == (
                9, BLOCK, KV_HEADS * A)
    state = len(GDN_LAYERS) * HV * D * D * 4
    conv = len(GDN_LAYERS) * (TAPS - 1) * CONV * 4
    row = 2 * KV_HEADS * A * 4  # a token's key and value rows
    assert pool_block_bytes(cfg, params, BLOCK) == BLOCK * row
    assert pool_slot_bytes(cfg, params) == state + conv
    with pytest.raises(ValueError, match="n_slots"):
        init_paged_cache(cfg, params, 9, BLOCK)
    eng = engine(model, n_blocks=9)
    alloc = spans.tracer().events("pool.alloc")[-1].args
    assert alloc["slot_state_leaves"] == 2 * len(GDN_LAYERS)
    assert alloc["state_bytes"] == 4 * state  # three slots and the trash row
    assert alloc["tail_bytes"] == 4 * conv
    assert alloc["pool_layers"] == alloc["cache_layers"] == 1
    assert alloc["weight_layers"] == LAYERS
    assert alloc["latent_row_bytes"] == 0 and alloc["kv_row_bytes"] == row
    assert alloc["block_bytes"] == pool_block_bytes(cfg, params, BLOCK)
    assert eng.slot_state_bytes == state
    assert eng.chain_bytes(3) == (3 * pool_block_bytes(cfg, params, BLOCK)
                                  + pool_slot_bytes(cfg, params) + 128 * 4)


def test_the_rows_span_argument_is_a_pool_layers_own():
    """``kv_row_bytes`` is a token's key and value rows in ONE layer that
    owns such pools: 0 for latent attention's one row, the same for a
    stack of 2 or 4 layers of the plain block."""
    from test_ling_lm import ling_config, seeded

    from pytorch_distributed_tpu.models.transformer import (
        TransformerLM,
        tiny_config,
    )

    cfg = ling_config()
    PagedEngine(cfg, seeded(cfg), 3, n_blocks=9, block_len=BLOCK,
                prefill_chunk=CHUNK)
    assert spans.tracer().events("pool.alloc")[-1].args["kv_row_bytes"] == 0
    for layers in (2, 4):
        cfg = tiny_config(num_layers=layers, max_seq_len=64)
        params = jax.eval_shape(TransformerLM(cfg).init, jax.random.key(0),
                                jnp.zeros((1, 8), jnp.int32))["params"]
        PagedEngine(cfg, params, 3, n_blocks=9, block_len=BLOCK,
                    prefill_chunk=CHUNK)
        alloc = spans.tracer().events("pool.alloc")[-1].args
        assert alloc["kv_row_bytes"] == 2 * 32 * 4
        assert alloc["pool_layers"] == layers


def test_a_reused_slot_equals_a_fresh_engine(model):
    """A row that starts at position 0 reads a zero state and zero taps
    whatever the slot held: the second request of a slot is served as a
    fresh engine's."""
    first, second = prompts_of([13]), prompts_of([10], seed=9)
    used = engine(model)
    prefill(used, first)
    decode(used, [13, 0, 0], 3)
    assert np.abs(slot_state(used, 0)).max() > 0.01
    used.release(0)
    fresh = engine(model)
    got = []
    for eng in (used, fresh):
        prefill(eng, second)
        got.append([np.asarray(eng.logits[0])]
                   + [lg[0] for _, lg in decode(eng, [10, 0, 0], 3)])
    assert (np.stack(got[0]) == np.stack(got[1])).all()


def test_a_slot_in_mid_prefill_keeps_its_state_across_ticks(model):
    """Slot 1 has prefilled one chunk of two while ticks run for slot 0:
    the tick's lane 1 is not live, so its state and taps stay as they were
    (its K/V writes go to the trash block), and its second chunk then gives
    the reference's logits."""
    cfg, params = model
    eng = engine(model)
    short, long = prompts_of([6, 14])
    prefill(eng, [short], [0])
    assert eng.admit(1, len(long), 4)
    eng.run_chunks(chunk_jobs([long], 0, [1]))
    before = [slot_state(eng, s) for s in range(4)]
    decode(eng, [6, 0, 0], 3)
    after = [slot_state(eng, s) for s in range(4)]
    assert (after[1] == before[1]).all() and np.abs(before[1]).max() > 0
    assert (after[2] == before[2]).all()  # a lane with no request
    assert (after[0] != before[0]).any()  # the live lane's moved
    eng.run_chunks(chunk_jobs([long], CHUNK, [1]))
    want = reference_logits(params, long[None])[0, -1]
    assert np.abs(np.asarray(eng.logits[1]) - want).max() <= TOL


@pytest.mark.parametrize("length", [5, 8, 2])
def test_a_chunks_padding_touches_neither_state_nor_taps(model, length):
    """A prompt that ends inside its chunk leaves the state and the taps of
    its last token, not of the padding behind it; the padding JOB of the
    program (one job pads to two) writes the trash row alone."""
    eng_a, eng_b = engine(model), engine(model)
    prompt = prompts_of([length])[0]
    idle = [slot_state(eng_a, s) for s in (1, 2)]
    for eng, pad in ((eng_a, 0), (eng_b, 77)):
        assert eng.admit(0, length, 4)
        seg = np.full((CHUNK,), pad, np.int32)
        seg[:length] = prompt
        eng.run_chunks([ChunkJob(0, seg, 0, True, length - 1)])
    assert (slot_state(eng_a, 0) == slot_state(eng_b, 0)).all()
    assert [(slot_state(eng_a, s) == idle[i]).all()
            for i, s in enumerate((1, 2))] == [True, True]
    a = [lg[0] for _, lg in decode(eng_a, [length, 0, 0], 2)]
    b = [lg[0] for _, lg in decode(eng_b, [length, 0, 0], 2)]
    assert (np.stack(a) == np.stack(b)).all()


@pytest.mark.parametrize("how", ["swap", "handoff"])
def test_state_rows_and_pool_blocks_travel_together(model, how):
    """A slot that owns state rows in three layers AND blocks of the fourth
    layer's key and value pools: swap out and in, and export and import
    into another engine's pool and another slot; the stream goes on as the
    one that stayed."""
    prompt = prompts_of([13])
    stay = engine(model)
    prefill(stay, prompt)
    decode(stay, [13, 0, 0], 2)
    want = [lg[0] for _, lg in decode(stay, [15, 0, 0], 3)]

    src = engine(model, swap=True, handoff=True)
    prefill(src, prompt)
    decode(src, [13, 0, 0], 2)
    if how == "swap":
        store = HostBlockStore()
        chain = src.swap_out_finish(src.swap_out_begin(0), store, rid=7)
        assert src.allocator.in_use == 0
        assert chain.nbytes == src.chain_bytes(chain.n_blocks)
        # another request dirties the slot and the freed blocks meanwhile
        prefill(src, prompts_of([9], seed=1))
        src.release(0)
        assert src.swap_in_chain(0, chain)
        dst, slot = src, 0
    else:
        export = src.export_chain(0)
        dst, slot = engine(model, handoff=True), 2
        assert dst.import_chain(slot, export)
    positions = np.zeros((3,), np.int32)
    positions[slot] = 15
    got = [lg[slot] for _, lg in decode(dst, positions, 3)]
    assert (np.stack(got) == np.stack(want)).all()
    # the warm-ups of the four programs leave live state alone
    before = slot_state(dst, slot)
    for n in (1, 2):
        if how == "swap":
            dst.warm_swap_out(n), dst.warm_swap_in(n)
        else:
            dst.warm_export(n), dst.warm_import(n)
    assert (slot_state(dst, slot) == before).all()


@pytest.mark.parametrize("what,match", [
    (dict(prefix_cache=True), "prefix_cache.*snapshot"),
    (dict(kv_dtype="int8"), "quantized"),
    (dict(kv_dtype="fp8"), "quantized"),
])
def test_a_shared_prefix_and_a_quantized_pool_are_refused(model, what, match):
    with pytest.raises(ValueError, match=match):
        engine(model, **what)


def test_the_programs_count_live_lanes_only(model):
    """The tick and the chunk programs hand back [expert layers, experts
    held] counts: the chunk's leave out its padding and its padding job,
    the tick's its inactive lanes."""
    eng = engine(model)
    prompts = prompts_of([5, 11, 9])
    prefill(eng, prompts)  # three jobs pad to four; then two jobs
    counts = np.asarray(eng.chunk_expert_counts)
    assert counts.shape == (LAYERS, HELD[1])
    # the second chunk: 11 - 8 and 9 - 8 real rows, of 4 pairs each
    assert (counts.sum(1) <= (3 + 1) * TOP_K).all() and counts.sum() > 0
    assert eng.tick_expert_counts is None
    decode(eng, [5, 11, 0], 1)  # slot 2 holds a prompt but is not armed
    counts = eng.tick_expert_counts
    assert counts.shape == (LAYERS, HELD[1])
    assert (counts.sum(1) <= 2 * TOP_K).all() and counts.sum() > 0


# ---- the paged read ------------------------------------------------------


def test_the_rule_gives_the_tick_the_kernel_and_a_chunk_the_dense_gather(
        monkeypatch):
    """Sixteen query heads over two K/V heads bring 8 rows a narrow head to
    a tick, which is ``KERNEL_MAX_ROWS``: the kernel on a TPU, its folded
    body (2 heads x 8 rows are 16 of the lane tile's 128 columns); a chunk
    of 128 positions brings 1,024 rows: the dense gather."""
    rule = attention_ops.default_gather_impl
    assert rule(rows=8) == rule(rows=8 * 128) == "dense"  # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert rule(rows=16 // 2) == "pallas"
    assert rule(rows=128 * 16 // 2) == "dense"
    assert heads_folded(2, 8) == 2 and heads_folded(1, 16) == 1


@pytest.mark.parametrize("d,positions", [
    pytest.param(256, (37, 5, 64), id="the-published-head"),
    pytest.param(64, (1, 63, 20), id="a-narrower-head")])
def test_the_kernel_at_two_heads_of_eight_rows_equals_the_dense_gather(
        d, positions):
    """The fused kernel in the interpreter at the full layer's shape (2 K/V
    heads x 8 query rows a head, a K and a V tile of 2 x ``d`` lanes)
    against the dense gather, over chains that end inside a block, in the
    first block and on the table's last position."""
    rng = np.random.default_rng(d)
    b, h_kv, rows, bl, w = 3, 2, 8, 16, 4
    k_pool, v_pool, tables, _ = random_pool(rng, b, h_kv, d, bl, w)
    q = jnp.asarray(rng.normal(size=(b, 1, h_kv * rows, d)), jnp.float32)
    at = jnp.asarray(positions, jnp.int32)[:, None] - 1
    dense, fused = (np.asarray(paged_attention(
        q, k_pool, v_pool, tables, at, gather_impl=impl))
        for impl in ("dense", "pallas"))
    assert dense.shape == (b, 1, h_kv * rows, d)
    assert np.abs(dense).max() > 0.1
    assert np.abs(fused - dense).max() <= 1e-5


# ---- the configurations the benchmark had --------------------------------

#: sha256[:12] of the lowered text of the nearest configuration's programs
#: (``ling-3.0-flash``: ``KDAttention`` now shares its state handling and its
#: block solve with ``GatedDeltaNet``, the expert layer gained a router),
#: taken on this PR's parent as ``tests/test_ling_serving.py::
#: PARENT_DIGESTS`` were on theirs.
LING_DIGESTS = {
    "decode_tick": "51808d7f7b71",
    "chunk_prefill[k=2,w=2]": "d610194eb4ad",
}


@pytest.mark.parametrize("program", sorted(LING_DIGESTS))
def test_the_ling_programs_did_not_move(program):
    assert lowered_digest("ling-3.0-flash", program) == LING_DIGESTS[program]
