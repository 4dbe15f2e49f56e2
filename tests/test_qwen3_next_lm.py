"""The ``qwen3-next`` stack (three gated delta-rule layers whose float32
STATE is a request's to one gated grouped-head softmax-attention layer over a
real K/V pool; a softmax top-k router over experts of which the shard holds a
part, beside a gated shared one) against the plain reference
``perfbench/references/qwen3_next.py`` at a toy size on the CPU, through the
full forward (``tests/test_qwen3_next_serving.py``: through ``PagedEngine``).

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
from perfbench.references import qwen3_next as ref  # noqa: E402
from pytorch_distributed_tpu.models.generate import generate  # noqa: E402
from pytorch_distributed_tpu.models.moe import DroplessMoE  # noqa: E402
from pytorch_distributed_tpu.models.transformer import (  # noqa: E402
    Attention,
    GatedDeltaNet,
    TransformerConfig,
    TransformerLM,
    tiny_config,
)

TOL = 1e-5
#: the published stack at toy widths: two state heads a key head, a doubled
#: q projection, a quarter of a head rotated, 16 experts of which the first
#: 8 are held, 4 a token
QWEN = SERVED_TINY["qwen3-next"]
# 4, 4: layers 0-2 the delta rule, layer 3 full attention
LAYERS, GROUP = QWEN["num_layers"], QWEN["layer_group_size"]
HV, HK, D, TAPS = (QWEN["linear_num_heads"], QWEN["linear_num_key_heads"],
                   QWEN["linear_head_dim"], GatedDeltaNet.TAPS)
# an inner width (4 x 16), not the model's
HEADS, KV_HEADS, A = (QWEN[k] for k in (
    "num_heads", "num_kv_heads", "head_dim"))
EXPERTS, HELD, TOP_K = (QWEN[k] for k in (
    "n_experts", "experts_held", "moe_top_k"))
GDN_LAYERS = [0, 1, 2]
CONV = 2 * HK * D + HV * D  # channels under the one convolution


def qwen_config(**over) -> TransformerConfig:
    return tiny_config(**dict(QWEN, **over))


def seeded(cfg, seed=5):
    return seeded_params(ref, cfg, seed)


PAD = 48  # one compiled reference pass and one full forward serve them all


def padded(tokens):
    tokens = np.asarray(tokens)
    out = np.zeros((tokens.shape[0], PAD), np.int32)
    out[:, :tokens.shape[1]] = tokens
    return jnp.asarray(out)


_reference = {cast: jax.jit(lambda p, t, cast=cast: ref.logits(p, t, cast))
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
    ref.configure(QWEN)
    cfg = qwen_config()
    return cfg, seeded(cfg)


@pytest.fixture(autouse=True)
def highest():
    ref.configure(QWEN)
    with jax.default_matmul_precision("highest"):
        yield


def prompts_of(lengths, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 128, size=n).astype(np.int32) for n in lengths]


# ---- the model -----------------------------------------------------------


def test_the_tree_has_the_two_kinds_of_attention(model):
    cfg, params = model
    assert sorted(params) == [f"block{i}" for i in range(LAYERS)] + [
        "lm_head", "ln_f", "wte"]
    assert [cfg.attn_kind_at(i) for i in range(LAYERS)] == [
        "gdn", "gdn", "gdn", "mha"]
    assert all(cfg.moe_at(i) for i in range(LAYERS))
    assert cfg.attn_kinds == ("gdn", "mha") and cfg.slot_state
    assert cfg.latent_row_width == 0 and cfg.experts_held == HELD
    gdn, full = params["block0"]["attn"], params["block3"]["attn"]
    assert gdn["qkvz"]["kernel"].shape == (48, CONV + HV * D)
    assert gdn["ba"]["kernel"].shape == (48, 2 * HV)
    assert gdn["conv_kernel"].shape == (TAPS, CONV)
    assert gdn["A_log"].shape == gdn["dt_bias"].shape == (HV,)
    assert gdn["o_norm"]["scale"].shape == (D,)
    assert gdn["proj"]["kernel"].shape == (HV * D, 48)
    assert full["q"]["kernel"].shape == (48, HEADS, 2 * A)  # query and gate
    assert full["kv"]["kernel"].shape == (48, 2, KV_HEADS, A)
    assert full["q_norm"]["scale"].shape == full["k_norm"]["scale"].shape == (
        A,)
    assert full["proj"]["kernel"].shape == (HEADS, A, 48)
    moe = params["block0"]["moe"]
    assert moe["router"]["kernel"].shape == (48, EXPERTS)  # scores them all
    assert moe["w_gate_up"].shape == (8, 48, 2 * 24)  # holds eight
    assert moe["shared_gate"]["kernel"].shape == (48, 1)
    assert "router_bias" not in moe


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


def test_the_routers_probe_clears_the_common_component(model):
    """``balance_routers`` leaves every router's matrix orthogonal to the
    mean normed token its probe saw: run again on the same seed it changes
    nothing more, and run on a matrix that leans along that mean it takes
    the lean out."""
    cfg, params = model
    again = ref.balance_routers(params, 5)
    tilted = dict(params, block0=dict(params["block0"], moe=dict(
        params["block0"]["moe"], router={"kernel": params["block0"]["moe"][
            "router"]["kernel"] + 1.0})))
    cleared = ref.balance_routers(tilted, 5)
    for i in range(LAYERS):
        w = np.asarray(params[f"block{i}"]["moe"]["router"]["kernel"])
        assert 2.0 < np.linalg.norm(w, axis=0).min()  # the stated spread,
        assert np.linalg.norm(w, axis=0).max() <= 3.0 + 1e-5  # less the lean
        assert np.abs(np.asarray(
            again[f"block{i}"]["moe"]["router"]["kernel"]) - w).max() < 1e-5
    was = np.asarray(tilted["block0"]["moe"]["router"]["kernel"])
    now = np.asarray(cleared["block0"]["moe"]["router"]["kernel"])
    lean = was - now  # rank one: m (m . W) / |m|^2
    assert np.linalg.matrix_rank(lean, tol=1e-4) == 1
    m = lean[:, 0] / np.linalg.norm(lean[:, 0])
    assert np.abs(m @ now).max() < 1e-4 < np.abs(m @ was).max()


# ---- the gated delta rule ------------------------------------------------


@pytest.mark.parametrize("block", [3, 4, 16])
@pytest.mark.parametrize("shift", [0.0, 8.0])
def test_the_block_recurrence_is_the_references_position_at_a_time(
        model, monkeypatch, block, shift):
    """``delta_rule_blocks`` takes ``BLOCK`` positions a step; the reference
    runs the recurrence a position at a time. 23 positions in blocks of 3
    (with a padding position), 4 and 16, from a zero state; with
    ``dt_bias`` shifted by 8 a head forgets nearly all of its state every
    token (alpha about e^-8: the decay has no bound below) and nothing
    overflows."""
    monkeypatch.setattr(GatedDeltaNet, "BLOCK", block)
    cfg, params = model
    p = dict(params["block1"]["attn"])
    p["dt_bias"] = p["dt_bias"] + shift
    x = jax.random.normal(jax.random.key(7), (2, 23, 48))
    got = np.asarray(GatedDeltaNet(cfg).apply({"params": p}, x, 0))
    want = np.asarray(ref.gdn(x, p, None))
    assert np.isfinite(got).all() and np.abs(want).max() > 0.01
    assert np.abs(got - want).max() <= TOL


@pytest.mark.parametrize("block", [3, 16])
def test_tick_form_sequence_form_and_the_recurrence_agree_from_a_state(
        model, monkeypatch, block):
    """From the NON-ZERO state and convolution inputs that 5 positions
    leave, rows of 7 and 4 real positions (``lengths``; the shorter one's
    chunk is padded): the sequence form in one call, the tick form a
    position at a time, and the reference's recurrence over the whole
    sequence give the same outputs, and the two forms leave the same state
    and inputs behind (the padding moved neither)."""
    monkeypatch.setattr(GatedDeltaNet, "BLOCK", block)
    cfg, params = model
    p = params["block0"]["attn"]
    x = jax.random.normal(jax.random.key(9), (2, 12, 48))
    lengths = np.array([7, 4])
    want = np.asarray(ref.gdn(x, p, None))
    _, start = GatedDeltaNet(cfg, prefill=True).apply(
        {"params": p}, x[:, :5], 0, mutable=["cache"])
    assert np.abs(np.asarray(start["cache"]["state"])).max() > 1e-3
    seq, after_seq = GatedDeltaNet(cfg, prefill=True).apply(
        {"params": p, "cache": start["cache"]}, x[:, 5:], 5,
        lengths=jnp.asarray(lengths), mutable=["cache"])
    cache, ticks = start["cache"], []
    for t in range(7):
        out, updated = GatedDeltaNet(cfg, decode=True).apply(
            {"params": p, "cache": cache}, x[:, 5 + t:6 + t], 5 + t,
            lengths=jnp.asarray((t < lengths).astype(np.int32)),
            mutable=["cache"])
        cache = updated["cache"]
        ticks.append(np.asarray(out[:, 0]))
    ticks = np.stack(ticks, 1)
    for row, n in enumerate(lengths):
        assert np.abs(np.asarray(seq)[row, :n] - want[row, 5:5 + n]
                      ).max() <= TOL
        assert np.abs(ticks[row, :n] - want[row, 5:5 + n]).max() <= TOL
    for name in ("state", "conv"):
        assert np.abs(np.asarray(after_seq["cache"][name])
                      - np.asarray(cache[name])).max() <= TOL
    # the row of 4 stopped where a run over 9 positions stops
    _, nine = GatedDeltaNet(cfg, prefill=True).apply(
        {"params": p}, x[1:, :9], 0, mutable=["cache"])
    assert np.abs(np.asarray(nine["cache"]["state"][0])
                  - np.asarray(cache["state"][1])).max() <= TOL


# ---- the full layer ------------------------------------------------------


@pytest.mark.parametrize("length", [1, 7, 19])
def test_the_dense_decode_cache_reads_the_narrow_heads(model, length):
    """The full-sequence forward widens K and V to the query heads; the
    dense decode cache keeps the two narrow heads and reads them grouped.
    One function: norm a head, a quarter of a head rotated, the gate."""
    cfg, params = model
    p = params["block3"]["attn"]
    x = jax.random.normal(jax.random.key(length), (2, length, 48))
    pos = jnp.arange(length)
    whole = Attention(cfg).apply({"params": p}, x, 0, pos)
    want = np.asarray(ref.full_attention(x, p, None))
    assert np.abs(np.asarray(whole) - want).max() <= TOL
    cache = None
    for t in range(length):
        variables = {"params": p} if cache is None else {
            "params": p, "cache": cache}
        out, updated = Attention(cfg, decode=True).apply(
            variables, x[:, t:t + 1], t, pos[t:t + 1], mutable=["cache"])
        cache = updated["cache"]
        assert np.abs(np.asarray(out[:, 0]) - want[:, t]).max() <= TOL
    assert cache["key"].shape == (2, 64, KV_HEADS, A)


def test_the_gate_and_the_norms_are_in_the_function(model):
    """Without the gate, or without the norm a head, the layer is another
    function of the same weights: neither option is decoration."""
    cfg, params = model
    p = params["block3"]["attn"]
    x = jax.random.normal(jax.random.key(2), (1, 9, 48))
    want = np.asarray(ref.full_attention(x, p, None))
    bare = {k: v for k, v in p.items() if not k.endswith("_norm")}
    no_norm = Attention(dataclasses.replace(cfg, qk_norm=False)).apply(
        {"params": bare}, x, 0, jnp.arange(9))
    assert np.abs(np.asarray(no_norm) - want).max() > 100 * TOL
    ungated = dict(p, q={"kernel": p["q"]["kernel"][..., :A]})
    no_gate = Attention(dataclasses.replace(cfg, attn_gate=False)).apply(
        {"params": ungated}, x, 0, jnp.arange(9))
    assert np.abs(np.asarray(no_gate) - want).max() > 100 * TOL


# ---- the expert layer ----------------------------------------------------


def expert_layer(held=HELD, **kw):
    return DroplessMoE(
        n_experts=EXPERTS, moe_dim=24, router="softmax", top_k=TOP_K,
        shared_dim=24, shared_gate=True, held=held, **kw)


@pytest.fixture(scope="module")
def uncut_layer():
    """The expert layer with all 16 experts held, seeded."""
    ref.configure(QWEN)
    cfg = qwen_config(experts_held=None)
    return seeded(cfg, seed=11)["block3"]["moe"]


def shard_of(p, lo, hi):
    return dict(p, w_gate_up=p["w_gate_up"][lo:hi], w_down=p["w_down"][lo:hi])


@pytest.mark.parametrize("cuts", [(0, 8, 16), (0, 4, 8, 12, 16)])
def test_the_shares_add_up(uncut_layer, cuts):
    """Experts [0, 8) and [8, 16) on two shards (the deployment's cut), or
    four of four: each routes over all 16 and computes its own; the routed
    parts plus the gated shared expert ONCE are the uncut layer of the
    reference."""
    x = jax.random.normal(jax.random.key(3), (2, 19, 48))
    ref.HELD_FROM = 0
    want = np.asarray(ref.moe(x, uncut_layer, None))
    shared = np.asarray(want - ref.moe(x, uncut_layer, None, shared=False))
    total, pairs = shared, 0
    for lo, hi in zip(cuts, cuts[1:]):
        (out, state), stats = expert_layer(held=(lo, hi)).apply(
            {"params": shard_of(uncut_layer, lo, hi)}, x,
            mutable=["moe_stats"])
        assert state is None
        total = total + (np.asarray(out) - shared)
        counts = stats["moe_stats"]["expert_tokens"][0]
        assert counts.shape == (hi - lo,)
        pairs += int(counts.sum())
    assert pairs == 2 * 19 * TOP_K  # every pair landed on exactly one shard
    assert np.abs(total - want).max() <= TOL
    # and the uncut program layer is the same function
    out, _ = expert_layer(held=None).apply({"params": uncut_layer}, x)
    assert np.abs(np.asarray(out) - want).max() <= TOL


def test_the_weights_are_the_renormalised_softmax(uncut_layer):
    x = jax.random.normal(jax.random.key(8), (1, 33, 48))
    ids, w = (np.asarray(a) for a in ref.route(x, uncut_layer, None))
    assert ids.shape == (1, 33, TOP_K)
    assert np.allclose(w.sum(-1), 1.0, atol=1e-6)
    probs = np.asarray(jax.nn.softmax(
        x @ uncut_layer["router"]["kernel"], -1))
    top = np.sort(probs, -1)[..., ::-1][..., :TOP_K]
    assert np.allclose(np.sort(w, -1)[..., ::-1],
                       top / top.sum(-1, keepdims=True), atol=1e-6)
    assert (np.take_along_axis(probs, ids, -1) >= top[..., -1:] - 1e-7).all()


def test_the_shared_expert_is_gated_a_token(uncut_layer):
    """With the gate's vector at zero every token's shared expert counts a
    half: the routed part is what the layer gives without it."""
    x = jax.random.normal(jax.random.key(5), (2, 11, 48))
    ref.HELD_FROM = 0
    routed = np.asarray(ref.moe(x, uncut_layer, None, shared=False))
    shared = np.asarray(ref._swiglu(x, uncut_layer["shared_gate_up"]["kernel"],
                                    uncut_layer["shared_down"]["kernel"],
                                    None))
    half = dict(uncut_layer,
                shared_gate={"kernel": jnp.zeros((48, 1), jnp.float32)})
    out, _ = expert_layer(held=None).apply({"params": half}, x)
    assert np.abs(np.asarray(out) - (routed + 0.5 * shared)).max() <= TOL
    out, _ = expert_layer(held=None).apply({"params": uncut_layer}, x)
    assert np.abs(np.asarray(out) - (routed + 0.5 * shared)).max() > 10 * TOL


def test_the_counts_are_a_bincount_of_live_pairs_on_held_experts(model):
    cfg, params = model
    p = params["block2"]["moe"]
    x = jax.random.normal(jax.random.key(4), (3, 8, 48))
    lengths = np.array([8, 0, 5])  # a full row, a padding job, a short one
    live = np.arange(8)[None] < lengths[:, None]
    (out, _), stats = expert_layer().apply(
        {"params": p}, x, None, jnp.asarray(live), mutable=["moe_stats"])
    ids, _ = ref.route(x, p, None)
    mine = np.asarray(ids)[live].ravel()
    want = np.bincount(mine[mine < HELD[1]], minlength=HELD[1])
    assert len(set(want)) > 1 and 0 < want.sum() < 13 * TOP_K
    assert list(stats["moe_stats"]["expert_tokens"][0]) == list(want)
    assert (np.asarray(out)[~live] == 0).all()
    want_out = np.asarray(ref.moe(x, p, None))
    assert np.abs(np.asarray(out)[live] - want_out[live]).max() <= TOL


# ---- what the config refuses ---------------------------------------------


@pytest.mark.parametrize("over,match", [
    (dict(head_dim=None), "head_dim"),
    (dict(pos_embedding="learned"), "rope"),
    (dict(attention="flash"), "one shard"),
    (dict(ut_steps=2), "one pass|one shard"),
    (dict(tp_size=2, model_axis="model"), "one shard"),
    (dict(full_attn_kind="cca"), "full_attn_kind"),
    (dict(layer_group_size=0), "full_attn_kind"),
    (dict(full_attn_kind="mla", num_kv_heads=None, qk_norm=False,
          attn_gate=False), "kv_lora_rank"),
    (dict(attn_kind="kda"), "linear_num_heads"),
    (dict(linear_num_key_heads=3), "whole groups"),
    (dict(linear_head_dim=0), "whole groups"),
    (dict(attn_gate=True, num_kv_heads=None), "num_kv_heads"),
    (dict(rotary_share=0.2), "rotary_share"),
    (dict(moe_n_group=2), "sigmoid' only"),
    (dict(moe_routed_scale=2.5), "sigmoid' only"),
    (dict(moe_router="top"), "moe_router"),
    (dict(router_dim=8), "router_dim"),
    (dict(moe_dim=None), "moe_dim"),
    (dict(moe_every=2), "moe_every"),
    (dict(moe_top_k=17), "fit inside"),
    (dict(experts_held=(8, 4)), "experts_held"),
    (dict(moe_shared_dim=None), "moe_shared_gate"),
    (dict(moe_kind="capacity", moe_dim=None), "softmax"),
])
def test_the_config_refuses_what_it_cannot_run(over, match):
    with pytest.raises(ValueError, match=match):
        qwen_config(**over)


@pytest.mark.parametrize("over,match", [
    (dict(qk_norm=True), "qk_norm and attn_gate"),
    (dict(attn_gate=True), "qk_norm and attn_gate"),
    (dict(full_attn_kind="mha"), "full_attn_kind"),
    (dict(linear_num_heads=4), "gdn' only"),
])
def test_the_new_keys_describe_their_own_layers_only(over, match):
    """On a stack of latent and "kda" layers, which has no "mha" layer and
    no "gdn" one."""
    from test_ling_lm import ling_config

    with pytest.raises(ValueError, match=match):
        ling_config(**dict(dict(layer_group_size=0, kv_lora_rank=None,
                                qk_rope_head_dim=None), **over))


def test_a_plain_stack_takes_the_three_options():
    """``Attention`` alone, no linear layer: grouped K/V heads, an inner
    width of its own, the norm a head and the gate, through the full
    forward and ``generate``'s dense cache."""
    cfg = tiny_config(num_layers=2, embed_dim=48, num_heads=4, num_kv_heads=2,
                      head_dim=16, qk_norm=True, attn_gate=True,
                      rotary_share=0.5, pos_embedding="rope", max_seq_len=32)
    params = TransformerLM(cfg).init(jax.random.key(0),
                                     jnp.zeros((1, 8), jnp.int32))["params"]
    assert params["block0"]["attn"]["q"]["kernel"].shape == (48, 4, 32)
    prompt = jax.random.randint(jax.random.key(4), (2, 7), 1, 128)
    out = np.asarray(generate(cfg, params, prompt, jax.random.key(0),
                              max_new_tokens=3))
    seq = np.asarray(prompt)
    for _ in range(3):
        logits = TransformerLM(cfg).apply({"params": params}, seq,
                                          train=False)
        seq = np.concatenate(
            [seq, np.argmax(np.asarray(logits)[:, -1], -1)[:, None]], 1)
    assert (out == seq).all()
