"""The ``nemotron-h`` stack through ``PagedEngine`` and ``Scheduler`` at a toy
size on the CPU: chunked prefill then decode against the reference's full
forward (``tests/test_nemotron_h_lm.py`` has the model, the reference and the
tolerance); a cache in which four blocks of nine own per-slot leaves, one a
``key`` and a ``value`` pool leaf and four NOTHING (reuse, mid-prefill ticks,
export / import and swap); the paged kernel at two narrow heads of sixteen
query rows; what the engine refuses; and the digests of the programs of the
nearest configurations the benchmark already had."""

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
from test_nemotron_h_lm import (  # noqa: F401  (model, highest: fixtures)
    CONV,
    E_LAYERS,
    FULL,
    HELD,
    KV_HEADS,
    LAYERS,
    M_LAYERS,
    TAPS,
    TOL,
    TOP_K,
    A,
    H,
    N,
    P,
    full_logits,
    highest,
    model,
    prompts_of,
    reference_logits,
)
from test_paged_kernel import random_pool

from perfbench.harness.weights import CASTS
from pytorch_distributed_tpu.models.transformer import Mamba2Mixer
from pytorch_distributed_tpu.ops.attention import paged_attention
from pytorch_distributed_tpu.ops.paged_flash import heads_folded
from pytorch_distributed_tpu.serving import Scheduler
from pytorch_distributed_tpu.serving.engine import ChunkJob
from pytorch_distributed_tpu.serving.kv_pool import (
    HostBlockStore,
    init_paged_cache,
    pool_block_bytes,
    pool_slot_bytes,
)
from pytorch_distributed_tpu.telemetry import spans


def slot_state(eng, slot):
    """What ``slot`` holds beside its blocks: every Mamba-2 block's state
    and convolution inputs, flattened."""
    return np.concatenate([
        np.asarray(eng.cache[f"block{i}"]["attn"][name][slot],
                   np.float32).ravel()
        for i in M_LAYERS for name in ("state", "conv")])


@pytest.mark.parametrize("lengths,gather_impl", [
    ((5, 11), "dense"), ((8, 3), "dense"), ((9, 17), "dense"),
    ((26, 10), "dense"), ((13, 21), "pallas")])
def test_chunked_prefill_then_decode_matches_the_full_forward(
        model, steer_paged_read, lengths, gather_impl):
    """Chunks of 8 against a convolution of 4 taps: prompts that end inside
    a chunk, on its edge, one and two past it (a window split over two
    chunks at every offset), up to four chunks; every chunk crosses the
    four state blocks, the four expert blocks and the pool block. At the
    prompt's last position and at every decoded one the LOGITS are the
    reference's full forward's, through the dense gather and through the
    kernel's folded body (two narrow heads of two query rows)."""
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
    monkeypatch.setattr(Mamba2Mixer, "BLOCK", 3)
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


# ---- a cache in which four blocks of nine hold nothing -------------------


def test_the_cache_tree_differs_by_layer_and_four_layers_hold_nothing(model):
    cfg, params = model
    pool = init_paged_cache(cfg, params, 9, BLOCK, n_slots=3)
    assert sorted(pool) == sorted(f"block{i}" for i in M_LAYERS + [FULL])
    assert not any(f"block{i}" in pool for i in E_LAYERS)
    for i in M_LAYERS:  # no pool at all
        layer = pool[f"block{i}"]["attn"]
        assert sorted(layer) == ["conv", "state"]
        assert layer["state"].shape == (3 + 1, H, P, N)  # not square
        assert layer["state"].dtype == jnp.float32
        assert layer["conv"].shape == (3 + 1, TAPS - 1, CONV)
    layer = pool[f"block{FULL}"]["attn"]  # real keys and values
    assert sorted(layer) == ["key", "value"]
    assert layer["key"].shape == layer["value"].shape == (
        9, BLOCK, KV_HEADS * A)
    state = len(M_LAYERS) * H * P * N * 4
    conv = len(M_LAYERS) * (TAPS - 1) * CONV * 4
    row = 2 * KV_HEADS * A * 4  # a token's key and value rows
    assert pool_block_bytes(cfg, params, BLOCK) == BLOCK * row
    assert pool_slot_bytes(cfg, params) == state + conv
    with pytest.raises(ValueError, match="n_slots"):
        init_paged_cache(cfg, params, 9, BLOCK)
    eng = engine(model, n_blocks=9)
    alloc = spans.tracer().events("pool.alloc")[-1].args
    assert alloc["slot_state_leaves"] == 2 * len(M_LAYERS)
    assert alloc["state_bytes"] == 4 * state  # three slots and the trash row
    assert alloc["tail_bytes"] == 4 * conv
    assert alloc["pool_layers"] == alloc["cache_layers"] == 1
    assert alloc["weight_layers"] == LAYERS
    assert alloc["latent_row_bytes"] == 0 and alloc["kv_row_bytes"] == row
    assert alloc["block_bytes"] == pool_block_bytes(cfg, params, BLOCK)
    assert alloc["read"] == "dense" and alloc["heads_folded"] == 1  # the CPU
    assert eng.slot_state_bytes == state
    assert eng.chain_bytes(3) == (3 * pool_block_bytes(cfg, params, BLOCK)
                                  + pool_slot_bytes(cfg, params) + 128 * 4)


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
    """A slot that owns state rows in four blocks AND chain blocks of the
    attention block's key and value pools, in a tree where four blocks own
    nothing: swap out and in, and export and import into another engine's
    pool and another slot; the stream goes on as the one that stayed."""
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
    """The tick and the chunk programs hand back [expert blocks, experts
    held] counts (four of the nine blocks sow them): the chunk's leave out
    its padding and its padding job, the tick's its inactive lanes."""
    eng = engine(model)
    prompts = prompts_of([5, 11, 9])
    prefill(eng, prompts)  # three jobs pad to four; then two jobs
    counts = np.asarray(eng.chunk_expert_counts)
    assert counts.shape == (len(E_LAYERS), HELD[1])
    # the second chunk: 11 - 8 and 9 - 8 real rows, of 3 pairs each
    assert (counts.sum(1) <= (3 + 1) * TOP_K).all() and counts.sum() > 0
    assert eng.tick_expert_counts is None
    decode(eng, [5, 11, 0], 1)  # slot 2 holds a prompt but is not armed
    counts = eng.tick_expert_counts
    assert counts.shape == (len(E_LAYERS), HELD[1])
    assert (counts.sum(1) <= 2 * TOP_K).all() and counts.sum() > 0


# ---- the paged read ------------------------------------------------------


def test_sixteen_rows_a_head_fold_into_the_lane_tile():
    """32 query heads over 2 K/V heads bring 16 rows a narrow head to a
    tick: 2 x 16 are 32 of the lane tile's 128 columns, so the kernel's
    folded body can take them (what the rule answers for 16 rows is
    ``tests/test_tpu_compile.py``'s, read through a program)."""
    assert heads_folded(2, 16) == 2 and heads_folded(2, 64) == 2
    assert heads_folded(2, 65) == 1 and heads_folded(1, 16) == 1


@pytest.mark.parametrize("positions", [
    pytest.param((37, 5, 64), id="inside-a-block"),
    pytest.param((1, 63, 20), id="the-first-position")])
def test_the_kernel_at_two_heads_of_sixteen_rows_equals_the_dense_gather(
        positions):
    """The fused kernel in the interpreter at the attention block's shape (2
    K/V heads x 16 query rows a head, a K and a V tile of 2 x 128 lanes)
    against the dense gather, over chains that end inside a block, in the
    first block and on the table's last position."""
    rng = np.random.default_rng(16)
    b, h_kv, rows, d, bl, w = 3, 2, 16, 128, 16, 4
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

#: sha256[:12] of the lowered text of the nearest configurations' programs
#: (``qwen3-next-80b-a3b``, ``ling-3.0-flash``, ``zaya1-8b``: ``Block`` now
#: runs its sublayers through three local functions, ``_SlotStateAttention``
#: takes the state's shape from its subclass, the convolution may carry a
#: bias and the dropless experts a second form), taken on this PR's parent
#: as ``tests/test_qwen3_next_serving.py::LING_DIGESTS`` were on theirs.
PARENT_DIGESTS = {
    ("qwen3-next-80b-a3b", "decode_tick"): "c84da6979b4a",
    ("qwen3-next-80b-a3b", "chunk_prefill[k=2,w=2]"): "206ed4318e1f",
    ("ling-3.0-flash", "decode_tick"): "51808d7f7b71",
    ("ling-3.0-flash", "chunk_prefill[k=2,w=2]"): "d610194eb4ad",
    ("zaya1-8b", "decode_tick"): "b6f409948883",
    ("zaya1-8b", "chunk_prefill[k=2,w=2]"): "c9b84b95eb62",
}


@pytest.mark.parametrize("name,program", sorted(PARENT_DIGESTS))
def test_the_older_programs_lower_to_the_parents_text(name, program):
    assert lowered_digest(name, program) == PARENT_DIGESTS[name, program]
