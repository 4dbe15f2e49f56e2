"""The ``ling`` stack through ``PagedEngine`` and ``Scheduler`` at a toy size
on the CPU: chunked prefill then decode against the reference's full forward
(``tests/test_ling_lm.py`` has the model, the reference and the tolerance),
the state that is a request's (reuse, mid-prefill ticks, padding, export /
import and swap), the expert counts out of the programs, and the digests of
the programs of the configurations the benchmark already had."""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_ling_lm import (  # noqa: F401  (model, highest: fixtures)
    DENSE,
    HEADS,
    HELD,
    KDA_LAYERS,
    LAYERS,
    ROOT,
    ROW,
    TAPS,
    TOL,
    TOP_K,
    D,
    full_logits,
    highest,
    ling_config,
    model,
    prompts_of,
    reference_logits,
    seeded,
)

from perfbench.harness.weights import CASTS
from pytorch_distributed_tpu.models.transformer import (
    KDAttention,
    TransformerConfig,
    TransformerLM,
)
from pytorch_distributed_tpu.serving import Scheduler
from pytorch_distributed_tpu.serving.engine import ChunkJob, PagedEngine
from pytorch_distributed_tpu.serving.kv_pool import (
    HostBlockStore,
    init_paged_cache,
    pool_block_bytes,
    pool_slot_bytes,
)
from pytorch_distributed_tpu.telemetry import spans

CHUNK, BLOCK = 8, 8


def engine(model, n_slots=3, **kw):
    cfg, params = model
    return PagedEngine(cfg, params, n_slots, n_blocks=kw.pop("n_blocks", 25),
                       block_len=BLOCK, prefill_chunk=CHUNK, **kw)


def chunk_jobs(prompts, start, slots=None):
    """One chunk job a prompt that still has positions at ``start``."""
    jobs = []
    for slot, p in zip(slots or range(len(prompts)), prompts):
        if start >= len(p):
            continue
        seg = np.zeros((CHUNK,), np.int32)
        seg[:len(p[start:start + CHUNK])] = p[start:start + CHUNK]
        last = start + CHUNK >= len(p)
        jobs.append(ChunkJob(slot, seg, start, last,
                             len(p) - 1 - start if last else 0))
    return jobs


def prefill(eng, prompts, slots=None, new=4):
    slots = list(slots or range(len(prompts)))
    for slot, p in zip(slots, prompts):
        assert eng.admit(slot, len(p), new)
    for start in range(0, max(map(len, prompts)), CHUNK):
        eng.run_chunks(chunk_jobs(prompts, start, slots))


def decode(eng, positions, steps):
    """``steps`` ticks over the lanes with a position; returns per tick
    (tokens, the logits buffer afterwards)."""
    positions = np.asarray(positions, np.int32)
    active = positions > 0
    out = []
    for _ in range(steps):
        tokens, positions = eng.decode(positions, active, jax.random.key(0))
        out.append((np.asarray(tokens), np.asarray(eng.logits)))
    return out


def slot_state(eng, slot):
    """What ``slot`` holds beside its blocks: every linear-attention
    layer's state and convolution inputs, flattened."""
    return np.concatenate([
        np.asarray(eng.cache[f"block{i}"]["attn"][name][slot],
                   np.float32).ravel()
        for i in KDA_LAYERS for name in ("state", "conv")])


@pytest.mark.parametrize("lengths,gather_impl", [
    ((5, 11), "dense"), ((8, 3), "dense"), ((9, 17), "dense"),
    ((26, 10), "dense"), ((13, 21), "pallas")])
def test_chunked_prefill_then_decode_matches_the_full_forward(
        model, steer_paged_read, lengths, gather_impl):
    """Chunks of 8 against convolutions of 4 taps: prompts that end inside
    a chunk, on its edge, one and two past it (a window split over two
    chunks at every offset), up to four chunks; every chunk crosses both
    latent layers. At the prompt's last position and at every decoded one
    the LOGITS are the reference's full forward's."""
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
    monkeypatch.setattr(KDAttention, "BLOCK", 3)
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


# ---- state that is a request's -------------------------------------------


def test_the_cache_tree_differs_by_layer(model):
    cfg, params = model
    pool = init_paged_cache(cfg, params, 9, BLOCK, n_slots=3)
    for i in range(LAYERS):
        layer = pool[f"block{i}"]["attn"]
        if i in KDA_LAYERS:  # no pool at all
            assert sorted(layer) == ["conv", "state"]
            assert layer["state"].shape == (3 + 1, HEADS, D, D)
            assert layer["state"].dtype == jnp.float32
            assert layer["conv"].shape == (3 + 1, TAPS - 1, 3 * HEADS * D)
        else:  # one row a token, its own key and value
            assert sorted(layer) == ["latent"]
            assert layer["latent"].shape == (9, BLOCK, ROW)
    state = len(KDA_LAYERS) * HEADS * D * D * 4
    conv = len(KDA_LAYERS) * (TAPS - 1) * 3 * HEADS * D * 4
    assert pool_block_bytes(cfg, params, BLOCK) == 2 * BLOCK * ROW * 4
    assert pool_slot_bytes(cfg, params) == state + conv
    with pytest.raises(ValueError, match="n_slots"):
        init_paged_cache(cfg, params, 9, BLOCK)
    eng = engine(model, n_blocks=9)
    alloc = spans.tracer().events("pool.alloc")[-1].args
    assert alloc["slot_state_leaves"] == 2 * len(KDA_LAYERS)
    assert alloc["state_bytes"] == 4 * state  # three slots and the trash row
    assert alloc["tail_bytes"] == 4 * conv
    assert alloc["pool_layers"] == alloc["cache_layers"] == 2
    assert alloc["weight_layers"] == LAYERS
    assert alloc["latent_row_bytes"] == ROW * 4
    assert alloc["block_bytes"] == pool_block_bytes(cfg, params, BLOCK)
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
    the tick's lane 1 is not live, so its state and taps stay as they were,
    and its second chunk then gives the reference's logits."""
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
def test_state_taps_and_latent_blocks_travel_together(model, how):
    """Swap out and in, and export and import into another engine's pool
    and another slot: the stream goes on as the one that stayed."""
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
        # another request dirties the slot meanwhile
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
])
def test_a_shared_prefix_and_a_quantized_pool_are_refused(model, what, match):
    with pytest.raises(ValueError, match=match):
        engine(model, **what)


def test_a_quantized_latent_pool_says_why():
    cfg = ling_config(attn_kind="mla", layer_group_size=0)
    assert cfg.attn_kinds == ("mla",) and not cfg.slot_state
    with pytest.raises(ValueError, match="its own key and\\s+value"):
        init_paged_cache(cfg, seeded(cfg), 9, BLOCK, kv_dtype="int8")


def test_the_programs_count_live_lanes_only(model):
    """The tick and the chunk programs hand back [expert layers, experts
    held] counts (the two dense layers contribute none): the chunk's leave
    out its padding and its padding job, the tick's its inactive lanes."""
    eng = engine(model)
    prompts = prompts_of([5, 11, 9])
    prefill(eng, prompts)  # three jobs pad to four; then two jobs
    counts = np.asarray(eng.chunk_expert_counts)
    assert counts.shape == (LAYERS - DENSE, HELD[1])
    # the second chunk: 11 - 8 and 9 - 8 real rows, of 4 pairs each
    assert (counts.sum(1) <= (3 + 1) * TOP_K).all() and counts.sum() > 0
    assert eng.tick_expert_counts is None
    decode(eng, [5, 11, 0], 1)  # slot 2 holds a prompt but is not armed
    counts = eng.tick_expert_counts
    assert counts.shape == (LAYERS - DENSE, HELD[1])
    assert (counts.sum(1) <= 2 * TOP_K).all() and counts.sum() > 0


# ---- the configurations the benchmark had --------------------------------

#: sha256[:12] of the lowered text of the decode tick and of one chunk
#: program of each configuration the benchmark had, at its file's ``tiny``
#: size on the CPU (the dense read): a default of a new config field, or a
#: rewrite of the expert layer, that moved one of their programs moves a
#: digest here. Taken on PR 36's parent, and again in PR 39, whose one
#: packed operand a launch changed every tick program's signature and so
#: its text (a PR that means to change them records new digests here, as
#: ``tests/test_tpu_compile.py::CHUNK_DIGESTS``). (The chunk programs at the
#: cells' own sizes are in ``tests/test_tpu_compile.py``.)
PARENT_DIGESTS = {
    ("gpt2-medium", "decode_tick"): "d060c128c39e",
    ("gpt2-medium", "chunk_prefill[k=2,w=2]"): "8793f9265d18",
    ("ouro-2.6b", "decode_tick"): "a1fd72f574b5",
    ("ouro-2.6b", "chunk_prefill[k=2,w=2]"): "296396567d02",
    ("zaya1-8b", "decode_tick"): "b6f409948883",
    ("zaya1-8b", "chunk_prefill[k=2,w=2]"): "c9b84b95eb62",
}


def lowered_digest(name, program):
    with open(os.path.join(ROOT, "perfbench", "configs", name + ".json")) as f:
        tiny = json.load(f)["tiny"]
    if "program" in tiny:
        cfg = TransformerConfig(**tiny["program"], dropout=0.0,
                                dtype=jnp.float32, attention="dense")
    else:
        cfg = TransformerConfig(
            vocab_size=tiny["vocab_size"], num_layers=tiny["n_layer"],
            num_heads=tiny["n_head"], embed_dim=tiny["n_embd"],
            max_seq_len=tiny["n_positions"], dropout=0.0, dtype=jnp.float32,
            attention="dense")
    params = jax.eval_shape(TransformerLM(cfg).init, jax.random.key(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    n = 4
    eng = PagedEngine(cfg, params, n, n_blocks=9, block_len=8,
                      prefill_chunk=8)
    if program == "decode_tick":
        fn = eng._decode()
        args = (params, eng.cache, eng.logits, eng._decode_operand(
            np.zeros((n,), np.int32), np.zeros((n,), bool)),
                jax.random.key(0))
    else:
        fn = eng._chunk_fn(2, 2)
        args = (params, eng.cache, eng.logits, eng._chunk_operand(2, 2))
    with jax.default_matmul_precision(None):  # the programs' own
        text = fn.lower(*args).as_text()
    return hashlib.sha256(text.encode()).hexdigest()[:12]


@pytest.mark.parametrize("name,program", sorted(PARENT_DIGESTS))
def test_the_programs_of_the_configurations_that_were_there_did_not_move(
        name, program):
    assert lowered_digest(name, program) == PARENT_DIGESTS[name, program]

