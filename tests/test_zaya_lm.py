"""The ``zaya`` block (attention inside a compressed latent with a two-tap
convolution TAIL beside the K/V pool, a dropless top-1 expert layer behind
an MLP router that carries state from layer to layer, a tied head, a scaled
residual) against the plain reference ``perfbench/references/zaya.py`` at a
toy size on the CPU, through the full forward and through ``PagedEngine``.

Tolerances. Program and reference are both float32 here and differ only in
the order of their sums: logits of size 0.3-0.5 agree to 1e-6 or so and
``TOL`` = 1e-5 leaves room for another BLAS. The float8 control (every
matrix operand cast to scaled e4m3, ``harness/weights.py``) moves the same
logits by 1e-2: it must break ``TOL``.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from conftest import SERVED_TINY, seeded_params  # noqa: E402

from perfbench.harness.weights import CASTS  # noqa: E402
from perfbench.references import zaya  # noqa: E402
from pytorch_distributed_tpu.models.generate import generate  # noqa: E402
from pytorch_distributed_tpu.models.moe import DroplessMoE  # noqa: E402
from pytorch_distributed_tpu.models.transformer import (  # noqa: E402
    TransformerConfig,
    TransformerLM,
    tiny_config,
)
from pytorch_distributed_tpu.serving import Scheduler  # noqa: E402
from pytorch_distributed_tpu.serving.engine import ChunkJob, PagedEngine  # noqa: E402
from pytorch_distributed_tpu.serving.kv_pool import (  # noqa: E402
    HostBlockStore,
    init_paged_cache,
    pool_block_bytes,
    pool_slot_bytes,
)
from pytorch_distributed_tpu.telemetry import spans  # noqa: E402

TOL = 1e-5
CHUNK, BLOCK = 8, 8
#: the published block at toy widths: an inner width (4 x 8) that is not
#: the model's (48), 2 narrow heads, 4 experts of 24 behind a router of 8
ZAYA = SERVED_TINY["zaya"]
LAYERS, EXPERTS, ROUTER = (ZAYA[k] for k in (
    "num_layers", "n_experts", "router_dim"))
TAIL = 2 * (4 + 2) * 8 + 8  # u, c1 and the shifted value half


def zaya_config(**over) -> TransformerConfig:
    return tiny_config(**dict(ZAYA, **over))


def seeded(cfg, seed=5):
    return seeded_params(zaya, cfg, seed)


def reference_logits(params, tokens, cast=None):
    with jax.default_matmul_precision("highest"):
        return np.asarray(zaya.logits(params, jnp.asarray(tokens), cast))


@pytest.fixture(scope="module")
def model():
    cfg = zaya_config()
    zaya.configure(dict(rope_theta=5e6, rotary_share=0.5, norm_eps=1e-5))
    return cfg, seeded(cfg)


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def engine(model, n_slots=3, **kw):
    cfg, params = model
    return PagedEngine(cfg, params, n_slots, n_blocks=kw.pop("n_blocks", 17),
                       block_len=BLOCK, prefill_chunk=CHUNK, **kw)


def prompts_of(lengths, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 128, size=n).astype(np.int32) for n in lengths]


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


def tails(eng):
    """[layers, n_slots + 1, width]: every layer's tail leaf."""
    return np.stack([np.asarray(eng.cache[f"block{i}"]["attn"]["tail"])
                     for i in range(LAYERS)])


# ---- the model -----------------------------------------------------------


def test_the_tree_is_tied_and_every_layer_has_the_same_leaves(model):
    cfg, params = model
    assert sorted(params) == ["block0", "block1", "ln_f", "wte"]
    assert "lm_head" not in params  # the head is wte's transpose
    shapes = [jax.tree.map(lambda x: x.shape, params[f"block{i}"])
              for i in range(LAYERS)]
    assert shapes[0] == shapes[1]
    b = params["block0"]
    assert sorted(b) == ["attn", "ln1", "ln2", "moe", "rs1", "rs2"]
    assert b["attn"]["qkv"]["kernel"].shape == (48, (4 + 2) * 8 + 2 * 8)
    assert b["attn"]["conv2_kernel"].shape == (6, 2, 8, 8)
    assert b["attn"]["proj"]["kernel"].shape == (4, 8, 48)  # Eq -> E
    assert b["moe"]["w_gate_up"].shape == (EXPERTS, 48, 2 * 24)
    assert b["moe"]["router_w3"]["kernel"].shape == (ROUTER, EXPERTS)
    assert sorted(b["rs1"]) == ["f_bias", "f_scale", "x_bias", "x_scale"]
    assert cfg.cca_tail_width == TAIL


def test_full_forward_matches_the_reference(model):
    cfg, params = model
    tokens = jax.random.randint(jax.random.key(1), (2, 13), 1, 128)
    logits = TransformerLM(cfg).apply({"params": params}, tokens,
                                      train=False)
    want = reference_logits(params, tokens)
    assert np.abs(np.asarray(logits) - want).max() <= TOL
    assert np.abs(want).max() > 0.1
    control = reference_logits(params, tokens, CASTS["fp8"])
    assert np.abs(control - want).max() > 100 * TOL


@pytest.mark.parametrize("attention", ["blockwise", "flash"])
def test_the_other_full_sequence_paths_compute_the_same(model, attention):
    cfg, params = model
    tokens = jax.random.randint(jax.random.key(2), (1, 16), 1, 128)
    got = TransformerLM(dataclasses.replace(
        cfg, attention=attention, block_size=8)).apply(
        {"params": params}, tokens, train=False)
    assert np.abs(np.asarray(got) - reference_logits(params, tokens)
                  ).max() <= 20 * TOL


@pytest.mark.parametrize("length,gather_impl", [
    (5, "dense"), (8, "dense"), (9, "dense"), (17, "dense"), (9, "pallas")])
def test_chunked_prefill_then_decode_matches_the_full_forward(
        model, steer_paged_read, length, gather_impl):
    """Chunk edges on both sides of the taps (a prompt that ends inside a
    chunk, on its edge, one past it, two chunks and one): at the prompt's
    last position and at every decoded one the logits are the reference's
    full forward's. A second prompt rides in the same programs."""
    cfg, params = model
    steer_paged_read(gather_impl)
    eng = engine(model)
    prompts = prompts_of([length, 11])
    prefill(eng, prompts)
    got = [[np.asarray(eng.logits[s])] for s in range(2)]
    streams = [list(p) for p in prompts]
    for tokens, logits in decode(eng, [length, 11, 0], 4):
        for s in range(2):
            streams[s].append(int(tokens[s]))
            got[s].append(logits[s])
    for s, p in enumerate(prompts):
        want = reference_logits(params, np.asarray(streams[s])[None])[0]
        rows = want[len(p) - 1:]
        assert len(rows) == len(got[s]) == 5
        assert np.abs(np.stack(got[s]) - rows).max() <= TOL
    control = reference_logits(params, np.asarray(streams[0])[None],
                               CASTS["fp8"])[0]
    assert np.abs(control[length - 1:]
                  - reference_logits(params, np.asarray(streams[0])[None]
                                     )[0][length - 1:]).max() > 100 * TOL


def test_the_scheduler_serves_it_and_streams_equal_the_full_forward(model):
    cfg, params = model
    sched = Scheduler(cfg, params, n_slots=3, n_blocks=20, block_len=BLOCK,
                      prefill_chunk=CHUNK)
    prompts = prompts_of([5, 13, 9, 20], seed=0)
    rids = [sched.submit(p, 5) for p in prompts]
    out = sched.drain()
    full = jax.jit(lambda t: TransformerLM(cfg).apply(
        {"params": params}, t[None], train=False)[0])
    for rid, p in zip(rids, prompts):
        seq = list(p)
        for _ in range(5):
            padded = np.zeros((32,), np.int32)
            padded[:len(seq)] = seq
            seq.append(int(jnp.argmax(full(jnp.asarray(padded))[len(seq) - 1])))
        assert [int(t) for t in out[rid]] == seq[len(p):]
    assert sched.engine.allocator.in_use == 0
    # the tick's span says what its experts took
    process = [e.args for e in spans.tracer().events("sched.collect.process")
               if e.args and "routed" in e.args][-1]
    assert 1 <= process["routed"] <= 3
    assert 1.0 <= process["experts_hit"] <= min(EXPERTS, process["routed"])
    assert process["expert_tokens_peak"] <= process["routed"]


def test_generate_decodes_through_the_dense_cache(model):
    cfg, params = model
    prompt = jax.random.randint(jax.random.key(4), (2, 7), 1, 128)
    out = np.asarray(generate(cfg, params, prompt, jax.random.key(0),
                              max_new_tokens=4))
    lm = TransformerLM(cfg)
    seq = np.asarray(prompt)
    for _ in range(4):
        logits = lm.apply({"params": params}, jnp.asarray(seq), train=False)
        seq = np.concatenate(
            [seq, np.asarray(jnp.argmax(logits[:, -1], -1))[:, None]], 1)
    assert (out == seq).all()


# ---- the tail: state that belongs to a request ---------------------------


def test_the_pool_has_a_tail_leaf_a_layer(model):
    cfg, params = model
    pool = init_paged_cache(cfg, params, 9, BLOCK, n_slots=3)
    layer = pool["block1"]["attn"]
    assert sorted(layer) == ["key", "tail", "value"]
    assert layer["key"].shape == (9, BLOCK, 2 * 8)
    assert layer["tail"].shape == (3 + 1, TAIL)  # the last row is trash
    assert pool_block_bytes(cfg, params, BLOCK) == (
        LAYERS * 2 * BLOCK * 2 * 8 * 4)
    assert pool_slot_bytes(cfg, params) == LAYERS * TAIL * 4
    plain = tiny_config()  # block chains only: no per-slot state
    assert pool_slot_bytes(plain, jax.eval_shape(
        TransformerLM(plain).init, jax.random.key(0),
        jnp.zeros((1, 8), jnp.int32))["params"]) == 0
    with pytest.raises(ValueError, match="n_slots"):
        init_paged_cache(cfg, params, 9, BLOCK)
    eng = engine(model, n_blocks=9)
    alloc = spans.tracer().events("pool.alloc")[-1].args
    assert alloc["slot_state_leaves"] == LAYERS
    assert alloc["tail_bytes"] == LAYERS * 4 * TAIL * 4
    assert alloc["block_bytes"] == pool_block_bytes(cfg, params, BLOCK)
    assert eng.chain_bytes(3) == (3 * pool_block_bytes(cfg, params, BLOCK)
                                  + pool_slot_bytes(cfg, params) + 128 * 4)


def test_a_reused_slot_equals_a_fresh_engine(model):
    """A row that starts at position 0 reads a zero tail whatever the slot
    held: the second request of a slot is served as a fresh engine's."""
    first, second = prompts_of([13]), prompts_of([10], seed=9)
    used = engine(model)
    prefill(used, first)
    decode(used, [13, 0, 0], 3)
    assert np.abs(tails(used)[:, 0]).max() > 0.01
    used.release(0)
    fresh = engine(model)
    got = []
    for eng in (used, fresh):
        prefill(eng, second)
        got.append([np.asarray(eng.logits[0])]
                   + [lg[0] for _, lg in decode(eng, [10, 0, 0], 3)])
    assert (np.stack(got[0]) == np.stack(got[1])).all()


def test_a_slot_in_mid_prefill_keeps_its_tail_across_ticks(model):
    """Slot 1 has prefilled one chunk of two while ticks run for slot 0:
    the tick passes it the trash row, its tail is untouched, and its
    second chunk then gives the reference's logits."""
    cfg, params = model
    eng = engine(model)
    short, long = prompts_of([6, 14])
    prefill(eng, [short], [0])
    assert eng.admit(1, len(long), 4)
    eng.run_chunks(chunk_jobs([long], 0, [1]))
    before = tails(eng)
    decode(eng, [6, 0, 0], 3)
    after = tails(eng)
    assert (after[:, 1] == before[:, 1]).all()
    assert (after[:, 0] != before[:, 0]).any()  # the live lane's moved
    eng.run_chunks(chunk_jobs([long], CHUNK, [1]))
    want = reference_logits(params, long[None])[0, -1]
    assert np.abs(np.asarray(eng.logits[1]) - want).max() <= TOL


def test_a_chunk_writes_the_tail_of_its_last_real_position(model):
    """A prompt that ends inside its chunk leaves the tail of its last
    token, not of the padding behind it: what follows the padded prompt
    in the chunk does not matter."""
    eng_a, eng_b = engine(model), engine(model)
    prompt = prompts_of([5])[0]
    for eng, pad in ((eng_a, 0), (eng_b, 77)):
        assert eng.admit(0, 5, 4)
        seg = np.full((CHUNK,), pad, np.int32)
        seg[:5] = prompt
        eng.run_chunks([ChunkJob(0, seg, 0, True, 4)])
    assert (tails(eng_a)[:, 0] == tails(eng_b)[:, 0]).all()
    a = [lg[0] for _, lg in decode(eng_a, [5, 0, 0], 2)]
    b = [lg[0] for _, lg in decode(eng_b, [5, 0, 0], 2)]
    assert (np.stack(a) == np.stack(b)).all()


@pytest.mark.parametrize("how", ["swap", "handoff"])
def test_the_tail_travels_with_the_chain(model, how):
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
    before = tails(dst)[:, slot]
    for n in (1, 2):
        if how == "swap":
            dst.warm_swap_out(n), dst.warm_swap_in(n)
        else:
            dst.warm_export(n), dst.warm_import(n)
    assert (tails(dst)[:, slot] == before).all()


def test_a_shared_prefix_is_refused(model):
    with pytest.raises(ValueError, match="prefix_cache"):
        engine(model, prefix_cache=True)
    cfg, params = model
    with pytest.raises(ValueError, match="prefix_cache"):
        Scheduler(cfg, params, n_slots=2, n_blocks=9, block_len=BLOCK,
                  prefill_chunk=CHUNK, prefix_cache=True)


# ---- the expert layer ----------------------------------------------------


def expert_layer(**kw):
    return DroplessMoE(n_experts=EXPERTS, moe_dim=24, router_dim=ROUTER,
                       norm_eps=1e-5, **kw)


@pytest.fixture(scope="module")
def layer_params(model):
    return model[1]["block1"]["moe"]


def test_one_expert_takes_every_token_and_drops_none(layer_params):
    """Every token forced onto expert 2 (its bias far above the others'):
    the layer is that expert's dense SwiGLU times its probability, for
    every token, however many there are."""
    p = dict(layer_params,
             router_bias=jnp.zeros((EXPERTS,)).at[2].set(100.0))
    x = jax.random.normal(jax.random.key(3), (2, 19, 48))
    (out, _), stats = expert_layer().apply({"params": p}, x,
                                           mutable=["moe_stats"])
    probs, choice, _ = zaya.route(x, None, p, None)
    assert (np.asarray(choice) == 2).all()
    gu = jnp.einsum("ble,ef->blf", x, p["w_gate_up"][2])
    dense = jnp.einsum("blf,fe->ble", jax.nn.silu(gu[..., :24]) * gu[..., 24:],
                       p["w_down"][2]) * probs[..., 2:3]
    assert np.abs(np.asarray(out) - np.asarray(dense)).max() <= TOL
    assert np.abs(np.asarray(out)).min(-1).max() > 0  # no row left out
    assert list(stats["moe_stats"]["expert_tokens"][0]) == [0, 0, 38, 0]


def test_the_counts_are_a_bincount_of_the_live_rows(layer_params):
    x = jax.random.normal(jax.random.key(4), (3, 8, 48))
    lengths = np.array([8, 0, 5])  # a full row, a padding job, a short one
    live = np.arange(8)[None] < lengths[:, None]
    (out, _), stats = expert_layer().apply(
        {"params": layer_params}, x, None, jnp.asarray(live),
        mutable=["moe_stats"])
    _, choice, _ = zaya.route(x, None, layer_params, None)
    want = np.bincount(np.asarray(choice)[live], minlength=EXPERTS)
    assert len(set(want)) > 1 and want.sum() == 13
    assert list(stats["moe_stats"]["expert_tokens"][0]) == list(want)
    assert (np.asarray(out)[~live] == 0).all()
    ref, _ = zaya.moe(x, None, layer_params, None)
    assert np.abs(np.asarray(out)[live] - np.asarray(ref)[live]).max() <= TOL


def test_the_programs_count_live_lanes_only(model):
    """The tick and the chunk programs hand back [layers, experts] counts:
    the chunk's leave out its padding and its padding job, the tick's its
    inactive lanes."""
    cfg, params = model
    eng = engine(model)
    prompts = prompts_of([5, 11, 9])
    prefill(eng, prompts)  # three jobs pad to four; then one job
    counts = np.asarray(eng.chunk_expert_counts)
    assert counts.shape == (LAYERS, EXPERTS)
    # the second chunk: 11 - 8 and 9 - 8 real rows of two chunks of 8
    assert (counts.sum(1) == 3 + 1).all()
    assert eng.tick_expert_counts is None
    decode(eng, [5, 11, 0], 1)  # slot 2 holds a prompt but is not armed
    counts = eng.tick_expert_counts
    assert counts.shape == (LAYERS, EXPERTS) and (counts.sum(1) == 2).all()


def test_the_router_stream_of_a_layer_enters_the_next(model):
    cfg, params = model
    h = jax.random.normal(jax.random.key(5), (1, 6, 48))
    p = params["block1"]["moe"]
    _, r0 = expert_layer().apply({"params": p}, h)
    assert r0.shape == (1, 6, ROUTER)
    _, r1 = expert_layer().apply({"params": p}, h, 2.0 * r0)
    mix = float(p["router_mix"][0])
    assert np.abs(np.asarray(r1) - (1 + 2 * mix) * np.asarray(r0)).max() < 1e-5
    # in the model: a change to layer 0's router projection alone moves
    # layer 1's routing state, and so the logits, though layer 0's own
    # choice is held (its bias decides)
    pinned = jax.tree.map(lambda x: x, params)
    for i in range(LAYERS):
        pinned[f"block{i}"]["moe"] = dict(
            pinned[f"block{i}"]["moe"],
            router_bias=jnp.zeros((EXPERTS,)).at[1].set(100.0))
    tokens = jax.random.randint(jax.random.key(6), (1, 9), 1, 128)
    base = TransformerLM(cfg).apply({"params": pinned}, tokens, train=False)
    moved = jax.tree.map(lambda x: x, pinned)
    moved["block0"]["moe"] = dict(
        moved["block0"]["moe"], router_down={
            "kernel": -8.0 * pinned["block0"]["moe"]["router_down"]["kernel"]})
    got = TransformerLM(cfg).apply({"params": moved}, tokens, train=False)
    assert np.abs(np.asarray(got) - np.asarray(base)).max() > 10 * TOL
    want = reference_logits(moved, tokens)
    assert np.abs(np.asarray(got) - want).max() <= TOL


# ---- what the config refuses ---------------------------------------------


@pytest.mark.parametrize("over,match", [
    (dict(moe_dim=None), "moe_dim"),
    (dict(router_dim=None), "router_dim"),
    (dict(moe_every=2), "every block"),
    (dict(num_kv_heads=None), "num_kv_heads"),
    (dict(pos_embedding="learned"), "rope"),
    (dict(attention="ring"), "one shard"),
    (dict(ut_steps=2), "one pass|one shard"),
    (dict(rotary_share=0.4), "rotary_share"),
    (dict(moe_top_k=2), "top-1"),
    (dict(n_experts=0), "top-1"),
    (dict(attn_kind="latent"), "attn_kind"),
    (dict(moe_kind="sorted"), "moe_kind"),
    (dict(moe_kind="capacity"), "moe_dim and router_dim"),
    # since PR 41 ``Attention`` takes an inner width of its own (4 x 8 in
    # a model of 48); its gate needs the separate q projection, and the
    # norm a head and the gate are no options of this attention
    (dict(attn_kind="mha", head_dim=8, attn_gate=True, num_kv_heads=None),
     "num_kv_heads"),
    (dict(qk_norm=True), "qk_norm and attn_gate"),
])
def test_the_config_refuses_what_it_cannot_run(over, match):
    with pytest.raises(ValueError, match=match):
        zaya_config(**over)


def test_a_quantized_pool_is_refused(model):
    with pytest.raises(ValueError, match="quantized"):
        engine(model, kv_dtype="int8")
