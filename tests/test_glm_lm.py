"""The ``glm`` stack (latent attention with a COMPRESSED query and values
wider than its unrotated keys in EVERY layer, no gate; one leading dense MLP,
then sigmoid-routed top-4 experts beside a shared one, every expert held)
against the plain reference ``perfbench/references/glm4_moe_lite.py`` at a toy
size on the CPU, through the full forward (``tests/test_glm_serving.py``:
through ``PagedEngine``, where the latent pool is the only cache).

Tolerances. Program and reference are both float32 here and differ only in
the order of their sums: logits of size 2-4 agree to 2e-6 or so and ``TOL`` =
2e-5 leaves room for another BLAS. A bfloat16 run of the same program moves
the same logits by 1e-2 and more, and the float8 control (every matrix
operand cast to scaled e4m3, ``harness/weights.py``) further: both must break
``TOL`` a hundred times over.
"""

import dataclasses
import hashlib
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
from perfbench.references import glm4_moe_lite as glm  # noqa: E402
from pytorch_distributed_tpu.models.generate import generate  # noqa: E402
from pytorch_distributed_tpu.models.moe import DroplessMoE  # noqa: E402
from pytorch_distributed_tpu.models.transformer import (  # noqa: E402
    MLAttention,
    TransformerConfig,
    TransformerLM,
    tiny_config,
)

TOL = 2e-5
#: the published stack at toy widths: 5 heads (no multiple of a tile's 8
#: rows, as the published 20 is none of 16) of 12 unrotated and 4 rotated
#: query dims and 16 value dims, a query rank of 24 and a latent of 16, one
#: leading dense MLP, 16 experts, 4 a token, all held
GLM = SERVED_TINY["glm"]
LAYERS, DENSE = GLM["num_layers"], GLM["first_k_dense_replace"]
HEADS, D, V = GLM["num_heads"], GLM["head_dim"], GLM["v_head_dim"]
RANK, LATENT, ROPE, ROW = (GLM["q_lora_rank"], GLM["kv_lora_rank"],
                           GLM["qk_rope_head_dim"], 128)
EXPERTS, TOP_K, SCALE = (GLM["n_experts"], GLM["moe_top_k"],
                         GLM["moe_routed_scale"])


def glm_config(**over) -> TransformerConfig:
    return tiny_config(**dict(GLM, **over))


def seeded(cfg, seed=5):
    return seeded_params(glm, cfg, seed)


PAD = 48  # one compiled reference pass and one full forward serve them all


def padded(tokens):
    tokens = np.asarray(tokens)
    out = np.zeros((tokens.shape[0], PAD), np.int32)
    out[:, :tokens.shape[1]] = tokens
    return jnp.asarray(out)


_reference = {cast: jax.jit(lambda p, t, cast=cast: glm.logits(p, t, cast))
              for cast in (None, CASTS["fp8"])}


def reference_logits(params, tokens, cast=None):
    """The reference's logits of ``tokens`` [B, L], through one compiled
    pass at ``PAD`` positions (a causal model does not see what follows)."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(_reference[cast](params, padded(tokens)))[
            :, :np.shape(tokens)[1]]


_forward = jax.jit(lambda cfg, p, t: TransformerLM(cfg).apply(
    {"params": p}, t, train=False), static_argnums=0)


def full_logits(cfg, params, tokens):
    """The program's full-sequence forward, compiled once a config."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(_forward(cfg, params, padded(tokens)))[
            :, :np.shape(tokens)[1]]


@pytest.fixture(scope="module")
def model():
    glm.configure(GLM)
    cfg = glm_config()
    return cfg, seeded(cfg)


@pytest.fixture(autouse=True)
def highest():
    glm.configure(GLM)
    with jax.default_matmul_precision("highest"):
        yield


def prompts_of(lengths, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 128, size=n).astype(np.int32) for n in lengths]


# ---- the model -----------------------------------------------------------


def test_every_layer_is_latent_and_the_tree_says_so(model):
    cfg, params = model
    assert sorted(params) == [f"block{i}" for i in range(LAYERS)] + [
        "lm_head", "ln_f", "wte"]
    assert [cfg.attn_kind_at(i) for i in range(LAYERS)] == ["mla"] * LAYERS
    assert [cfg.moe_at(i) for i in range(LAYERS)] == [False, True, True]
    # no state that is a request's: the pool of rows is the only cache
    assert cfg.attn_kinds == ("mla",) and not cfg.slot_state
    assert cfg.latent_row_width == ROW and cfg.experts_held is None
    assert (cfg.head_width, cfg.value_head_width) == (D, V) and V != D
    for i in range(LAYERS):
        mla = params[f"block{i}"]["attn"]
        assert sorted(mla) == ["kv_a", "kv_a_norm", "kv_b", "proj", "q_a",
                               "q_a_norm", "q_b"]  # no "q", no "gate"
        assert mla["q_a"]["kernel"].shape == (48, RANK)
        assert mla["q_a_norm"]["scale"].shape == (RANK,)
        assert mla["q_b"]["kernel"].shape == (RANK, HEADS, D + ROPE)
        assert mla["kv_a"]["kernel"].shape == (48, LATENT + ROPE)
        assert mla["kv_b"].shape == (LATENT, HEADS, D + V)
        assert mla["proj"]["kernel"].shape == (HEADS, V, 48)
    assert sorted(params["block0"]) == ["attn", "ln1", "ln2", "mlp_down",
                                        "mlp_gate", "mlp_up"]
    moe = params["block1"]["moe"]
    assert moe["router"]["kernel"].shape == (48, EXPERTS)
    assert moe["w_gate_up"].shape == (EXPERTS, 48, 2 * 24)  # holds them all
    assert moe["w_down"].shape == (EXPERTS, 24, 48)
    assert moe["shared_gate_up"]["kernel"].shape == (48, 2 * 24)


@pytest.mark.parametrize("seed,shape", [(1, (2, 13)), (2, (1, 40))])
def test_full_forward_matches_the_reference(model, seed, shape):
    cfg, params = model
    tokens = jax.random.randint(jax.random.key(seed), shape, 1, 128)
    logits = full_logits(cfg, params, tokens)
    want = reference_logits(params, tokens)
    assert np.abs(logits - want).max() <= TOL
    assert np.abs(want).max() > 1.0
    control = reference_logits(params, tokens, CASTS["fp8"])
    assert np.abs(control - want).max() > 100 * TOL


def test_a_bfloat16_run_of_the_program_breaks_the_tolerance(model):
    cfg, params = model
    tokens = jax.random.randint(jax.random.key(1), (2, 13), 1, 128)
    low = full_logits(dataclasses.replace(cfg, dtype=jnp.bfloat16), params,
                      tokens)
    assert np.abs(low - reference_logits(params, tokens)).max() > 100 * TOL


def test_generate_decodes_through_the_dense_cache(model):
    """``generate`` prefills EXPANDED and decodes FOLDED over its own dense
    cache of rows: its greedy stream is the full forward's."""
    cfg, params = model
    prompt = jax.random.randint(jax.random.key(4), (2, 7), 1, 128)
    out = np.asarray(generate(cfg, params, prompt, jax.random.key(0),
                              max_new_tokens=4))
    seq = np.asarray(prompt)
    for _ in range(4):
        logits = full_logits(cfg, params, seq)
        seq = np.concatenate([seq, np.argmax(logits[:, -1], -1)[:, None]], 1)
    assert (out == seq).all()


# ---- latent attention ----------------------------------------------------


def test_the_sublayer_is_the_references(model):
    cfg, params = model
    p = params["block1"]["attn"]
    x = jax.random.normal(jax.random.key(2), (2, 23, 48))
    got = np.asarray(MLAttention(cfg).apply({"params": p}, x, 0,
                                            jnp.arange(23)))
    want = np.asarray(glm.mla(x, p, None))
    assert np.abs(want).max() > 0.05
    assert np.abs(got - want).max() <= TOL


@pytest.mark.parametrize("length", [1, 7, 19])
def test_folded_and_expanded_latent_attention_agree(model, length):
    """The full-sequence forward expands keys (12 + 4 dims a head) and values
    (16 a head) for every position; the dense decode cache reads one row a
    token with ``W_UK`` (``kv_b``'s 12 key columns a head) folded into the
    compressed query's heads and ``W_UV`` (its 16 value columns) into the
    output. One function: every position's output is the same."""
    cfg, params = model
    p = params["block1"]["attn"]
    x = jax.random.normal(jax.random.key(length), (2, length, 48))
    pos = jnp.arange(length)
    expanded = MLAttention(cfg).apply({"params": p}, x, 0, pos)
    cache = None
    for t in range(length):
        variables = {"params": p} if cache is None else {
            "params": p, "cache": cache}
        folded, updated = MLAttention(cfg, decode=True).apply(
            variables, x[:, t:t + 1], t, pos[t:t + 1], mutable=["cache"])
        cache = updated["cache"]
        assert np.abs(np.asarray(folded[:, 0])
                      - np.asarray(expanded[:, t])).max() <= TOL
    row = np.asarray(cache["latent"])
    assert row.shape == (2, 64, 1, ROW)
    assert (row[..., LATENT + ROPE:] == 0).all()  # the padding lanes
    assert np.abs(row[:, :length, 0, :LATENT + ROPE]).min(-1).min() > 0


@pytest.mark.parametrize("gather_impl", ["dense", "pallas"])
def test_the_paged_read_of_the_pool_is_the_expanded_attention(
        model, steer_paged_read, gather_impl):
    """A chunk of 11 positions written into and read back from a block pool
    of rows through scattered tables (blocks of 4: the chunk crosses two
    block edges), 5 query rows a position on the row's one narrow head,
    through the dense gather and through the fused kernel (whose rows pad
    to 8): the expanded attention's output at every position."""
    cfg, params = model
    steer_paged_read(gather_impl)
    p = params["block2"]["attn"]
    x = jax.random.normal(jax.random.key(9), (2, 11, 48))
    pos = jnp.broadcast_to(jnp.arange(11), (2, 11))
    expanded = MLAttention(cfg).apply({"params": p}, x, 0, jnp.arange(11))
    pool = {"latent": jnp.zeros((9, 4, ROW), jnp.float32)}
    tables = jnp.asarray([[7, 2, 5, 8], [1, 6, 3, 8]], jnp.int32)
    paged, updated = MLAttention(cfg, prefill=True).apply(
        {"params": p, "cache": pool}, x, jnp.zeros((2,), jnp.int32), pos,
        tables, mutable=["cache"])
    assert np.abs(np.asarray(paged) - np.asarray(expanded)).max() <= TOL
    rows = np.asarray(updated["cache"]["latent"])
    assert np.abs(rows[7]).max() > 0 and (rows[[0, 4, 8]] == 0).all()


# ---- the fields default to what ran before them --------------------------


#: sha256 (12 hex digits) of the StableHLO text of ling's toy programs, taken
#: on PR 50's PARENT (under this file's ``highest`` matrix precision): the
#: full forward over [2, 48] ids, and one folded decode step of its latent
#: layer over a dense cache. The same text is the same logits, bit for bit.
LING_DIGESTS = {"forward": "09d6c0ebcf77", "decode": "ac8196d47cff"}


@pytest.mark.parametrize("program", sorted(LING_DIGESTS))
def test_lings_programs_are_the_parents_text(program):
    cfg = tiny_config(**SERVED_TINY["ling"])
    assert (cfg.q_lora_rank, cfg.v_head_dim, cfg.mla_head_gate) == (
        None, None, True)
    shapes = jax.eval_shape(TransformerLM(cfg).init, jax.random.key(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    if program == "forward":
        lowered = jax.jit(lambda p, t: TransformerLM(cfg).apply(
            {"params": p}, t, train=False)).lower(
            shapes, jax.ShapeDtypeStruct((2, 48), jnp.int32))
    else:
        def step(p, cache, x, t):
            return MLAttention(cfg, decode=True).apply(
                {"params": p, "cache": cache}, x, t, t[None],
                mutable=["cache"])

        lowered = jax.jit(step).lower(
            shapes["block2"]["attn"],
            {"latent": jax.ShapeDtypeStruct((2, 64, 1, 128), jnp.float32)},
            jax.ShapeDtypeStruct((2, 1, 48), jnp.float32),
            jax.ShapeDtypeStruct((), jnp.int32))
    assert hashlib.sha256(lowered.as_text().encode()).hexdigest()[
        :12] == LING_DIGESTS[program]


def test_a_value_width_equal_to_the_keys_is_no_value_width():
    """``v_head_dim`` = ``head_dim`` spelled out is the layer without the
    key: the same tree and the same logits, bit for bit."""
    from perfbench.references import ling

    ling.configure(SERVED_TINY["ling"])
    cfg = tiny_config(**SERVED_TINY["ling"])
    spelled = dataclasses.replace(cfg, v_head_dim=cfg.head_dim)
    params = seeded_params(ling, cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 13), 1, 128)
    assert (full_logits(cfg, params, tokens)
            == full_logits(spelled, params, tokens)).all()


# ---- the expert layer ----------------------------------------------------


def expert_layer(held=None, shared=24):
    return DroplessMoE(
        n_experts=EXPERTS, moe_dim=24, router="sigmoid", top_k=TOP_K,
        routed_scale=SCALE, shared_dim=shared, held=held, norm_eps=1e-5)


def shard_of(p, lo, hi):
    return dict(p, w_gate_up=p["w_gate_up"][lo:hi], w_down=p["w_down"][lo:hi])


def routed_input(model, seed=3, shape=(2, 19)):
    """Normed tokens as the first expert layer's router sees them (the
    router's draw stands on the stream's common component)."""
    cfg, params = model
    tokens = jax.random.randint(jax.random.key(seed), shape, 1, 128)
    x = params["wte"]["embedding"][tokens]
    return glm._rms(x, params["block1"]["ln2"]["scale"])


def test_every_expert_held_is_two_half_shares_and_the_shared_expert_once(
        model):
    """The layer with ``held=None`` (what the cell runs) against the guide's
    share test: experts [0, 8) and [8, 16) on two shards, each routing over
    all 16 and computing its own; the two routed parts plus the shared
    expert ONCE are the uncut layer, the program's and the reference's."""
    _, params = model
    layer = params["block1"]["moe"]
    x = routed_input(model)
    glm.HELD_FROM = 0
    want = np.asarray(glm.moe(x, layer, None))
    shared = np.asarray(want - glm.moe(x, layer, None, shared=False))
    assert np.abs(shared).max() > 1e-3
    total, pairs = shared, 0
    for lo in (0, 8):
        (out, state), stats = expert_layer(held=(lo, lo + 8)).apply(
            {"params": shard_of(layer, lo, lo + 8)}, x,
            mutable=["moe_stats"])
        assert state is None
        total = total + (np.asarray(out) - shared)
        counts = stats["moe_stats"]["expert_tokens"][0]
        assert counts.shape == (8,)
        pairs += int(counts.sum())
        # the reference, told the share, computes the same part
        glm.HELD_FROM = lo
        part = np.asarray(glm.moe(x, shard_of(layer, lo, lo + 8), None))
        glm.HELD_FROM = 0
        assert np.abs(np.asarray(out) - part).max() <= TOL
    assert pairs == 2 * 19 * TOP_K  # every pair landed on exactly one shard
    assert np.abs(total - want).max() <= TOL
    (out, _), stats = expert_layer().apply({"params": layer}, x,
                                           mutable=["moe_stats"])
    assert np.abs(np.asarray(out) - want).max() <= TOL
    counts = np.asarray(stats["moe_stats"]["expert_tokens"][0])
    assert counts.shape == (EXPERTS,) and counts.sum() == 2 * 19 * TOP_K


def test_the_bias_moves_the_choice_and_not_the_weights(model):
    _, params = model
    layer = params["block1"]["moe"]
    x = routed_input(model, seed=8, shape=(1, 33))
    ids, w = (np.asarray(a) for a in glm.route(x, layer, None))
    assert ids.shape == (1, 33, TOP_K)
    assert np.allclose(w.sum(-1), SCALE, atol=1e-5)  # norm_topk_prob, scaled
    # the draw's chosen scores fall off: the first carries most of the weight
    assert np.median(w.max(-1)) > 0.5 * SCALE
    tilted = dict(layer, router_bias=layer["router_bias"]
                  + jnp.where(jnp.arange(EXPERTS) == 5, 10.0, 0.0))
    ids2, w2 = (np.asarray(a) for a in glm.route(x, tilted, None))
    assert (ids2 == 5).any(-1).all()  # the bias decides the choice
    assert not (ids == 5).any(-1).all()
    scores = np.asarray(jax.nn.sigmoid(jnp.einsum(
        "ble,ex->blx", x, layer["router"]["kernel"],
        precision=jax.lax.Precision.HIGHEST)))
    got = np.where(ids2 == 5, w2, 0).sum(-1)[0]
    chosen = np.take_along_axis(scores, ids2, -1).sum(-1)[0]
    # and not the weight: expert 5 carries its SCORE's share
    assert np.allclose(got, SCALE * scores[0, :, 5] / chosen, atol=1e-5)
    # the program's layer takes the same choice and the same weights
    out, _ = expert_layer().apply({"params": tilted}, x)
    assert np.abs(np.asarray(out) - np.asarray(glm.moe(x, tilted, None))
                  ).max() <= TOL


# ---- what the config refuses ---------------------------------------------


@pytest.mark.parametrize("over,match", [
    (dict(head_dim=None), "head_dim"),
    (dict(num_kv_heads=5), "num_kv_heads"),
    (dict(pos_embedding="learned"), "rope"),
    (dict(attention="flash"), "one shard"),
    (dict(kv_lora_rank=None), "kv_lora_rank"),
    (dict(qk_rope_head_dim=3), "qk_rope_head_dim"),
    (dict(q_lora_rank=0), "q_lora_rank"),
    (dict(v_head_dim=0), "v_head_dim"),
    (dict(layer_group_size=2), "layer_group_size"),
    (dict(attn_kind="mha", head_dim=None, embed_dim=40,
          kv_lora_rank=None, qk_rope_head_dim=None, v_head_dim=None,
          mla_head_gate=True), "q_lora_rank"),
    (dict(attn_kind="mha", head_dim=None, embed_dim=40, q_lora_rank=None,
          kv_lora_rank=None, qk_rope_head_dim=None, v_head_dim=None),
     "mla_head_gate"),
    (dict(experts_held=(0, 17)), "experts_held"),
])
def test_the_config_refuses_what_it_cannot_run(over, match):
    with pytest.raises(ValueError, match=match):
        glm_config(**over)
