"""The ``ling`` stack (five delta-rule linear-attention layers whose float32
STATE is a request's to one latent-attention layer over a one-row-a-token
pool; two leading dense MLPs, then sigmoid-routed top-k experts of which the
shard holds a part, beside a shared one) against the plain reference
``perfbench/references/ling.py`` at a toy size on the CPU, through the full
forward (``tests/test_ling_serving.py``: through ``PagedEngine``).

Tolerances. Program and reference are both float32 here and differ only in
the order of their sums: logits of size 0.3-0.6 agree to 1e-6 or so and
``TOL`` = 1e-5 leaves room for another BLAS. A bfloat16 run of the same
program moves the same logits by 1e-2 and the float8 control (every matrix
operand cast to scaled e4m3, ``harness/weights.py``) by more: both must
break ``TOL``.
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
from perfbench.references import ling  # noqa: E402
from pytorch_distributed_tpu.models.generate import generate  # noqa: E402
from pytorch_distributed_tpu.models.moe import DroplessMoE  # noqa: E402
from pytorch_distributed_tpu.models.transformer import (  # noqa: E402
    KDAttention,
    MLAttention,
    TransformerConfig,
    TransformerLM,
    tiny_config,
)
from pytorch_distributed_tpu.ops import attention as attention_ops  # noqa: E402

TOL = 1e-5
#: the published stack at toy widths: an inner width (4 x 8) that is not the
#: model's (48), a period of 3, 16 experts in 4 groups of which 2 stay open,
#: 4 a token, the first 8 held here
LING = SERVED_TINY["ling"]
# 6, 3, 2: layers 2 and 5 are latent, 0 and 1 dense
LAYERS, GROUP, DENSE = (LING[k] for k in (
    "num_layers", "layer_group_size", "first_k_dense_replace"))
HEADS, D, TAPS = LING["num_heads"], LING["head_dim"], KDAttention.TAPS
EXPERTS, HELD, TOP_K = (LING[k] for k in (
    "n_experts", "experts_held", "moe_top_k"))
# a row of 16 + 4 values padded to a lane tile
LATENT, ROPE, ROW = LING["kv_lora_rank"], LING["qk_rope_head_dim"], 128
KDA_LAYERS = [0, 1, 3, 4]


def ling_config(**over) -> TransformerConfig:
    return tiny_config(**dict(LING, **over))


def seeded(cfg, seed=5):
    return seeded_params(ling, cfg, seed)


PAD = 48  # one compiled reference pass and one full forward serve them all


def padded(tokens):
    tokens = np.asarray(tokens)
    out = np.zeros((tokens.shape[0], PAD), np.int32)
    out[:, :tokens.shape[1]] = tokens
    return jnp.asarray(out)


_reference = {cast: jax.jit(lambda p, t, cast=cast: ling.logits(p, t, cast))
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
    cfg = ling_config()
    return cfg, seeded(cfg)


@pytest.fixture(autouse=True)
def highest():
    ling.configure(LING)
    with jax.default_matmul_precision("highest"):
        yield


def prompts_of(lengths, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 128, size=n).astype(np.int32) for n in lengths]


# ---- the model -----------------------------------------------------------


def test_the_tree_has_the_three_kinds_of_sublayer(model):
    cfg, params = model
    assert sorted(params) == [f"block{i}" for i in range(LAYERS)] + [
        "lm_head", "ln_f", "wte"]
    assert [cfg.attn_kind_at(i) for i in range(LAYERS)] == [
        "kda", "kda", "mla", "kda", "kda", "mla"]
    assert [cfg.moe_at(i) for i in range(LAYERS)] == [False] * 2 + [True] * 4
    assert cfg.attn_kinds == ("kda", "mla") and cfg.slot_state
    assert cfg.latent_row_width == ROW and cfg.experts_held == HELD
    kda, mla = params["block0"]["attn"], params["block2"]["attn"]
    inner = HEADS * D
    assert kda["qkv"]["kernel"].shape == (48, 3 * inner)
    assert kda["conv_kernel"].shape == (TAPS, 3 * inner)
    assert kda["gate_f"]["kernel"].shape == kda["gate_o"]["kernel"].shape == (
        48, inner)
    assert kda["beta"]["kernel"].shape == (48, HEADS)
    assert kda["A_log"].shape == (HEADS,) and kda["dt_bias"].shape == (inner,)
    assert kda["o_norm"]["scale"].shape == (D,)
    assert mla["q"]["kernel"].shape == (48, HEADS, D + ROPE)
    assert mla["kv_a"]["kernel"].shape == (48, LATENT + ROPE)
    assert mla["kv_b"].shape == (LATENT, HEADS, 2 * D)
    assert mla["gate"]["kernel"].shape == (48, HEADS)
    assert sorted(params["block0"]) == ["attn", "ln1", "ln2", "mlp_down",
                                        "mlp_gate", "mlp_up"]
    moe = params["block2"]["moe"]
    assert moe["router"]["kernel"].shape == (48, EXPERTS)  # scores them all
    assert moe["w_gate_up"].shape == (8, 48, 2 * 24)  # holds eight
    assert moe["shared_gate_up"]["kernel"].shape == (48, 2 * 24)


@pytest.mark.parametrize("seed,shape", [(1, (2, 13)), (2, (1, 40))])
def test_full_forward_matches_the_reference(model, seed, shape):
    cfg, params = model
    tokens = jax.random.randint(jax.random.key(seed), shape, 1, 128)
    logits = full_logits(cfg, params, tokens)
    want = reference_logits(params, tokens)
    assert np.abs(logits - want).max() <= TOL
    assert np.abs(want).max() > 0.1
    control = reference_logits(params, tokens, CASTS["fp8"])
    assert np.abs(control - want).max() > 100 * TOL


def test_a_bfloat16_run_of_the_program_breaks_the_tolerance(model):
    cfg, params = model
    tokens = jax.random.randint(jax.random.key(1), (2, 13), 1, 128)
    low = full_logits(dataclasses.replace(cfg, dtype=jnp.bfloat16), params,
                      tokens)
    assert np.abs(low - reference_logits(params, tokens)).max() > 100 * TOL


def test_generate_decodes_through_the_dense_cache(model):
    cfg, params = model
    prompt = jax.random.randint(jax.random.key(4), (2, 7), 1, 128)
    out = np.asarray(generate(cfg, params, prompt, jax.random.key(0),
                              max_new_tokens=4))
    seq = np.asarray(prompt)
    for _ in range(4):
        logits = full_logits(cfg, params, seq)
        seq = np.concatenate([seq, np.argmax(logits[:, -1], -1)[:, None]], 1)
    assert (out == seq).all()


# ---- the delta rule ------------------------------------------------------


@pytest.mark.parametrize("block", [3, 4, 16])
@pytest.mark.parametrize("shift", [0.0, 20.0])
def test_the_block_recurrence_is_the_references_token_at_a_time(
        model, monkeypatch, block, shift):
    """``delta_rule_blocks`` takes ``BLOCK`` positions a step; the
    reference runs the recurrence a token at a time. 23 positions in blocks
    of 3 (with two padding positions), 4 and 16, from a zero state; with
    ``dt_bias`` shifted by 20 every channel's gate sits at its bound (alpha
    = e^-5, a block's decays down to e^-75) and nothing overflows."""
    monkeypatch.setattr(KDAttention, "BLOCK", block)
    cfg, params = model
    p = dict(params["block1"]["attn"])
    p["dt_bias"] = p["dt_bias"] + shift
    x = jax.random.normal(jax.random.key(7), (2, 23, 48))
    got = np.asarray(KDAttention(cfg).apply({"params": p}, x, 0))
    want = np.asarray(ling.kda(x, p, None))
    assert np.isfinite(got).all() and np.abs(want).max() > 0.01
    assert np.abs(got - want).max() <= TOL


# ---- latent attention ----------------------------------------------------


@pytest.mark.parametrize("length", [1, 7, 19])
def test_folded_and_expanded_latent_attention_agree(model, length):
    """The full-sequence forward expands keys and values for every
    position; the dense decode cache reads one row a token with W_UK folded
    into the query and W_UV into the output. One function: the last
    position's output is the same."""
    cfg, params = model
    p = params["block2"]["attn"]
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


def test_the_rule_takes_the_kernel_where_a_dense_gather_is_too_large(
        monkeypatch):
    """32 query rows on one narrow head are four times ``KERNEL_MAX_ROWS``;
    the rule still answers the kernel on a TPU where the dense spelling's
    float32 copy of the tables would pass ``DENSE_GATHER_MAX_BYTES``."""
    rule = attention_ops.default_gather_impl
    tick = attention_ops.dense_gather_bytes(256, 3072, 640)
    chunk = attention_ops.dense_gather_bytes(4, 2048, 640)
    assert tick == 4 * 256 * 3072 * 640 > attention_ops.DENSE_GATHER_MAX_BYTES
    assert rule(32, tick) == rule(32 * 128, chunk) == "dense"  # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert rule(32, tick) == "pallas"
    assert rule(32 * 128, chunk) == rule(32) == "dense"
    assert rule(8) == rule() == "pallas"


# ---- the expert layer ----------------------------------------------------


def expert_layer(held=HELD, shared=24, **kw):
    return DroplessMoE(
        n_experts=EXPERTS, moe_dim=24, router="sigmoid", top_k=TOP_K,
        n_group=4, topk_group=2, routed_scale=2.5, shared_dim=shared,
        held=held, **kw)


@pytest.fixture(scope="module")
def uncut_layer():
    """The expert layer with all 16 experts held, seeded."""
    cfg = ling_config(experts_held=None)
    return seeded(cfg, seed=11)["block3"]["moe"]


def shard_of(p, lo, hi):
    return dict(p, w_gate_up=p["w_gate_up"][lo:hi], w_down=p["w_down"][lo:hi])


def test_the_four_shares_add_up(uncut_layer):
    """Experts [0, 4) ... [12, 16) on four shards: each routes over all 16
    and computes its own; the four routed parts plus the shared expert ONCE
    are the uncut layer of the reference."""
    x = jax.random.normal(jax.random.key(3), (2, 19, 48))
    ling.HELD_FROM = 0
    want = np.asarray(ling.moe(x, uncut_layer, None))
    shared = np.asarray(want - ling.moe(x, uncut_layer, None, shared=False))
    total, pairs = shared, 0
    for lo in range(0, EXPERTS, 4):
        (out, state), stats = expert_layer(held=(lo, lo + 4)).apply(
            {"params": shard_of(uncut_layer, lo, lo + 4)}, x,
            mutable=["moe_stats"])
        assert state is None
        total = total + (np.asarray(out) - shared)
        counts = stats["moe_stats"]["expert_tokens"][0]
        assert counts.shape == (4,)
        pairs += int(counts.sum())
    assert pairs == 2 * 19 * TOP_K  # every pair landed on exactly one shard
    assert np.abs(total - want).max() <= TOL
    # and the uncut program layer is the same function
    out, _ = expert_layer(held=None).apply({"params": uncut_layer}, x)
    assert np.abs(np.asarray(out) - want).max() <= TOL


def test_the_choice_is_group_limited_and_the_bias_is_not_in_the_weight(
        uncut_layer):
    x = jax.random.normal(jax.random.key(8), (1, 33, 48))
    ling.HELD_FROM = 0
    ids, w = (np.asarray(a) for a in ling.route(x, uncut_layer, None))
    assert ids.shape == (1, 33, TOP_K)
    assert all(len({i // 4 for i in row}) <= 2 for row in ids[0])
    assert np.allclose(w.sum(-1), 2.5, atol=1e-5)
    tilted = dict(uncut_layer, router_bias=uncut_layer["router_bias"]
                  + jnp.where(jnp.arange(EXPERTS) == 5, 10.0, 0.0))
    ids2, w2 = (np.asarray(a) for a in ling.route(x, tilted, None))
    assert (ids2 == 5).any(-1).all()  # the bias decides the choice
    scores = jax.nn.sigmoid(x @ uncut_layer["router"]["kernel"])
    at5 = np.asarray(scores)[0, :, 5]
    got = np.where(ids2 == 5, w2, 0).sum(-1)[0]
    chosen = np.take_along_axis(np.asarray(scores), ids2, -1).sum(-1)[0]
    assert np.allclose(got, 2.5 * at5 / chosen, atol=1e-5)  # and not the weight


def test_one_expert_takes_every_token_and_drops_none(uncut_layer):
    """Every token's first choice forced onto expert 2 (its bias far above
    the others'): 38 pairs in one group, no row left out, and the layer is
    still the reference's."""
    p = dict(uncut_layer,
             router_bias=jnp.zeros((EXPERTS,)).at[2].set(100.0))
    x = jax.random.normal(jax.random.key(3), (2, 19, 48))
    (out, _), stats = expert_layer(held=None).apply(
        {"params": p}, x, mutable=["moe_stats"])
    counts = np.asarray(stats["moe_stats"]["expert_tokens"][0])
    assert counts[2] == 38 and counts.sum() == 38 * TOP_K
    ling.HELD_FROM = 0
    assert np.abs(np.asarray(out) - np.asarray(ling.moe(x, p, None))
                  ).max() <= TOL
    # a shard that holds expert 2 alone computes every token through it
    (mine, _), stats = expert_layer(held=(2, 3), shared=None).apply(
        {"params": {k: v for k, v in shard_of(p, 2, 3).items()
                    if not k.startswith("shared")}}, x,
        mutable=["moe_stats"])
    assert list(stats["moe_stats"]["expert_tokens"][0]) == [38]
    assert np.abs(np.asarray(mine)).min(-1).max() > 0


def test_the_counts_are_a_bincount_of_live_pairs_on_held_experts(model):
    cfg, params = model
    p = params["block3"]["moe"]
    x = jax.random.normal(jax.random.key(4), (3, 8, 48))
    lengths = np.array([8, 0, 5])  # a full row, a padding job, a short one
    live = np.arange(8)[None] < lengths[:, None]
    (out, _), stats = expert_layer().apply(
        {"params": p}, x, None, jnp.asarray(live), mutable=["moe_stats"])
    ids, _ = ling.route(x, p, None)
    mine = np.asarray(ids)[live].ravel()
    want = np.bincount(mine[mine < HELD[1]], minlength=HELD[1])
    assert len(set(want)) > 1 and 0 < want.sum() < 13 * TOP_K
    assert list(stats["moe_stats"]["expert_tokens"][0]) == list(want)
    assert (np.asarray(out)[~live] == 0).all()
    ref = np.asarray(ling.moe(x, p, None))
    assert np.abs(np.asarray(out)[live] - ref[live]).max() <= TOL


# ---- what the config refuses ---------------------------------------------


@pytest.mark.parametrize("over,match", [
    (dict(head_dim=None), "head_dim"),
    (dict(num_kv_heads=2), "num_kv_heads"),
    (dict(pos_embedding="learned"), "rope"),
    (dict(attention="flash"), "one shard"),
    (dict(ut_steps=2), "one pass|one shard"),
    (dict(kv_lora_rank=None), "kv_lora_rank"),
    (dict(qk_rope_head_dim=3), "qk_rope_head_dim"),
    (dict(layer_group_size=0), "latent\\s+attention"),
    (dict(attn_kind="mha", head_dim=None, embed_dim=48), "layer_group_size"),
    (dict(moe_router="softmax"), "moe_router"),
    (dict(router_dim=8), "router_dim"),
    (dict(moe_dim=None), "moe_dim"),
    (dict(moe_every=2), "moe_every"),
    (dict(moe_n_group=3), "groups"),
    (dict(moe_topk_group=5), "groups"),
    (dict(moe_top_k=9), "fit inside"),
    (dict(moe_n_group=16, moe_topk_group=8), "two best"),
    (dict(experts_held=(8, 4)), "experts_held"),
    (dict(experts_held=(0, 17)), "experts_held"),
    (dict(moe_shared_dim=0), "moe_shared_dim"),
    (dict(first_k_dense_replace=7), "first_k_dense_replace"),
    (dict(moe_kind="capacity", moe_dim=None), "sigmoid"),
    (dict(moe_router="mlp", router_dim=8, moe_top_k=1),
     "one-matrix routers .*only"),
])
def test_the_config_refuses_what_it_cannot_run(over, match):
    with pytest.raises(ValueError, match=match):
        ling_config(**over)


def test_the_json_list_of_held_experts_hashes():
    cfg = ling_config(experts_held=[4, 12])
    assert cfg.experts_held == (4, 12) and hash(cfg) == hash(
        ling_config(experts_held=(4, 12)))
