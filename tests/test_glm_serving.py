"""The ``glm`` stack through ``PagedEngine`` and ``Scheduler`` at a toy size
on the CPU: chunked prefill then decode through the latent pool against the
reference's one full pass (``tests/test_glm_lm.py`` has the model, the
reference and the tolerance). Every layer owns a latent leaf and NO layer a
per-slot one: the pool is the only cache, and the thing that bounds the
batch."""

import numpy as np
import pytest

from test_glm_lm import (  # noqa: F401  (model, highest: fixtures)
    LAYERS,
    ROW,
    TOL,
    TOP_K,
    full_logits,
    highest,
    model,
    prompts_of,
    reference_logits,
)

# the engine's drivers are the ling stack's tests' own: an engine of blocks
# and chunks of 8, a chunk job a prompt, ticks over the lanes with a position
from test_ling_serving import (
    BLOCK,
    CHUNK,
    decode,
    engine,
    prefill,
)

from perfbench.harness.weights import CASTS
from pytorch_distributed_tpu.serving import Scheduler
from pytorch_distributed_tpu.serving.kv_pool import (
    init_paged_cache,
    pool_block_bytes,
    pool_slot_bytes,
)
from pytorch_distributed_tpu.telemetry import spans


def served_logits(eng, prompts, steps=4):
    """Per prompt (its stream with the decoded tokens, the logits at its
    last position and at every decoded one)."""
    prefill(eng, prompts, new=steps)
    got = [[np.asarray(eng.logits[s])] for s in range(len(prompts))]
    streams = [list(p) for p in prompts]
    lanes = [len(p) for p in prompts] + [0] * (eng.n_slots - len(prompts))
    for tokens, logits in decode(eng, lanes, steps):
        for s in range(len(prompts)):
            streams[s].append(int(tokens[s]))
            got[s].append(logits[s])
    return streams, [np.stack(g) for g in got]


@pytest.mark.parametrize("lengths,gather_impl", [
    ((5, 11), "dense"), ((8, 3), "dense"), ((9, 17), "dense"),
    ((26, 10), "dense"), ((13, 21), "pallas"), ((16, 24), "pallas")])
def test_chunked_prefill_then_decode_matches_the_full_forward(
        model, steer_paged_read, lengths, gather_impl):
    """Chunks and blocks of 8: prompts that end inside a chunk, on its edge
    (8, 16, 24: the first decoded row opens a new block), one past it, up to
    four chunks; every chunk and every tick crosses all three latent layers,
    folded, through the dense gather and through the fused kernel (5 query
    rows a position on the one narrow head, padded to 8). At the prompt's
    last position and at every decoded one the LOGITS are the reference's
    one full pass's, which expands keys and values and holds no cache."""
    cfg, params = model
    steer_paged_read(gather_impl)
    prompts = prompts_of(lengths)
    streams, got = served_logits(engine(model), prompts)
    for s, p in enumerate(prompts):
        want = reference_logits(params, np.asarray(streams[s])[None])[0]
        assert np.abs(got[s] - want[len(p) - 1:]).max() <= TOL
    control = reference_logits(params, np.asarray(streams[0])[None],
                               CASTS["fp8"])[0]
    assert np.abs(control - reference_logits(
        params, np.asarray(streams[0])[None])[0]).max() > 100 * TOL


def test_a_row_read_from_the_wrong_block_breaks_the_tolerance(model):
    """What the cell's ``served_logit_gap`` has to catch: two requests whose
    tables are swapped after prefill read each other's latent rows, and the
    next tick's logits are far from the reference's."""
    cfg, params = model
    eng = engine(model)
    prompts = prompts_of([13, 13], seed=4)
    prefill(eng, prompts)
    eng.tables[[0, 1]] = eng.tables[[1, 0]]
    _, logits = decode(eng, [13, 13, 0], 1)[0]
    first = int(np.argmax(reference_logits(params, prompts[0][None])[0, -1]))
    want = reference_logits(
        params, np.asarray(list(prompts[0]) + [first])[None])[0, -1]
    assert np.abs(logits[0] - want).max() > 1000 * TOL


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
    # the tick's span says what its experts took: every expert is held, so
    # every pair a layer routed landed here
    process = [e.args for e in spans.tracer().events("sched.collect.process")
               if e.args and "pairs" in e.args][-1]
    assert process["pairs"] in (TOP_K, 2 * TOP_K, 3 * TOP_K)
    assert process["routed"] == process["pairs"]
    assert 1 <= process["experts_hit"] <= process["routed"]
    # and no tick moved a state that is a request's
    launch = [e.args for e in spans.tracer().events("engine.decode.launch")
              if e.args][-1]
    assert not launch.get("state_rows")


def test_the_pool_is_the_only_cache(model):
    cfg, params = model
    pool = init_paged_cache(cfg, params, 9, BLOCK, n_slots=3)
    assert sorted(pool) == [f"block{i}" for i in range(LAYERS)]
    for i in range(LAYERS):  # one row a token, its own key and value
        layer = pool[f"block{i}"]["attn"]
        assert sorted(layer) == ["latent"]
        assert layer["latent"].shape == (9, BLOCK, ROW)
    assert pool_block_bytes(cfg, params, BLOCK) == LAYERS * BLOCK * ROW * 4
    assert pool_slot_bytes(cfg, params) == 0
    eng = engine(model, n_blocks=9)
    alloc = spans.tracer().events("pool.alloc")[-1].args
    assert alloc["slot_state_leaves"] == 0
    assert alloc["state_bytes"] == alloc["tail_bytes"] == 0
    assert alloc["pool_layers"] == alloc["cache_layers"] == LAYERS
    assert alloc["weight_layers"] == LAYERS
    assert alloc["latent_row_bytes"] == ROW * 4 and not alloc["kv_row_bytes"]
    assert alloc["block_bytes"] == pool_block_bytes(cfg, params, BLOCK)
    assert eng.slot_state_bytes == 0 and eng.state_update == ""


def test_the_blocks_and_not_the_slots_bound_the_batch(model):
    """Eight blocks beside the trash block, three slots: a request of 20
    positions and 4 new tokens reserves three blocks, so two fit and a
    third is refused with a slot still free; releasing one admits it, and
    the slot's next request reads nothing of the last one's rows."""
    cfg, params = model
    eng = engine(model, n_blocks=9)
    a, b, c = prompts_of([20, 20, 20], seed=6)
    assert eng.admit(0, 20, 4) and eng.admit(1, 20, 4)
    assert eng.allocator.in_use == 6
    assert not eng.admit(2, 20, 4)  # a slot is free; the pool is not
    eng.release(1)
    assert eng.allocator.in_use == 3
    eng.release(0)
    streams, got = served_logits(eng, [c])
    want = reference_logits(params, np.asarray(streams[0])[None])[0]
    assert np.abs(got[0] - want[19:]).max() <= TOL


def test_a_lane_with_no_request_routes_no_pair(model):
    """One live lane of three: the tick's expert counts (fetched with its
    tokens) are that lane's ``top_k`` pairs in each expert layer."""
    eng = engine(model)
    prefill(eng, prompts_of([9]))
    decode(eng, [9, 0, 0], 1)
    counts = np.asarray(eng.tick_expert_counts)
    assert counts.shape == (LAYERS - 1, 16)
    assert (counts.sum(axis=1) == TOP_K).all()


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
def test_a_quantized_pool_is_refused(model, kv_dtype):
    cfg, params = model
    with pytest.raises(ValueError, match="latent"):
        init_paged_cache(cfg, params, 9, BLOCK, kv_dtype=kv_dtype, n_slots=3)


def test_prefix_sharing_serves_the_same_streams(model):
    """A stack with no state that is a request's is the first expert
    configuration ``prefix_cache=True`` does not refuse: a prefix's latent
    rows are its blocks', so requests that share 16 positions (two of them
    the block-aligned prefix itself, which forces a copy on write) are
    served the streams of a server that shares nothing."""
    cfg, params = model
    rng = np.random.default_rng(0)
    prefix = rng.integers(1, 128, size=16).astype(np.int32)
    prompts = [np.concatenate([prefix, rng.integers(
        1, 128, size=n).astype(np.int32)]) for n in (3, 9, 5)] + [
        prefix.copy(), prefix.copy()]
    streams, metrics = [], []
    for share in (False, True):
        sched = Scheduler(cfg, params, n_slots=3, n_blocks=25,
                          block_len=BLOCK, prefill_chunk=CHUNK,
                          prefix_cache=share)
        got, rids = {}, []
        for p in prompts:  # staggered: earlier blocks are indexed first
            rids.append(sched.submit(p, 5))
            for _ in range(3):
                for rid, token in sched.step():
                    got.setdefault(rid, []).append(int(token))
        for rid, tokens in sched.drain().items():
            got.setdefault(rid, []).extend(int(t) for t in tokens)
        streams.append([got[rid] for rid in rids])
        metrics.append(sched.metrics())
        sched.engine.release_all()
        assert sched.engine.allocator.in_use == 0
    assert streams[0] == streams[1]
    assert metrics[1]["prefix_hits"] >= 3
    assert metrics[1]["prefix_cow_copies"] >= 1
    assert (metrics[1]["admitted_prefill_tokens"]
            < metrics[0]["admitted_prefill_tokens"])
