"""The ``nemotron-h`` stack (blocks of ONE sublayer in the order a string
gives: Mamba-2 mixers whose float32 STATE is a request's, expert layers behind
a sigmoid router whose experts are two matrices and a squared ReLU, of which
the shard holds a part, and position-free softmax attention over grouped K/V
heads) against the plain reference ``perfbench/references/nemotron_h.py`` at a
toy size on the CPU, through the full forward
(``tests/test_nemotron_h_serving.py``: through ``PagedEngine``).

Tolerances. Program and reference are both float32 here and differ only in
the order of their sums: logits of size 1-5 agree to a few 1e-6 and ``TOL`` =
2e-5 leaves room for another BLAS and for the blocked scan's products
(``exp(l_i - l_j)`` against a product of decays). A bfloat16 run of the same
program moves the same logits by 1e-2 or more and the float8 control (every
matrix operand cast to scaled e4m3, ``harness/weights.py``) further: both
must break ``TOL``.
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
from perfbench.references import nemotron_h as ref  # noqa: E402
from pytorch_distributed_tpu.models.generate import generate  # noqa: E402
from pytorch_distributed_tpu.models.moe import (  # noqa: E402
    DroplessMoE,
    grouped_width,
)
from pytorch_distributed_tpu.models.transformer import (  # noqa: E402
    Attention,
    Mamba2Mixer,
    TransformerConfig,
    TransformerLM,
    tiny_config,
)

TOL = 2e-5
#: the published stack at toy widths: two heads a B/C group, two query heads
#: a K/V head, 16 experts of which the first 8 are held, 3 a token
NEMO = SERVED_TINY["nemotron-h"]
PATTERN = NEMO["layer_pattern"]  # the published pattern's first nine letters
LAYERS = len(PATTERN)
M_LAYERS = [i for i, c in enumerate(PATTERN) if c == "M"]
E_LAYERS = [i for i, c in enumerate(PATTERN) if c == "E"]
FULL = PATTERN.index("*")
H, P, N, G, TAPS = (NEMO["mamba_num_heads"], NEMO["mamba_head_dim"],
                    NEMO["mamba_state_size"], NEMO["mamba_n_groups"],
                    Mamba2Mixer.TAPS)
INNER = H * P
CONV = INNER + 2 * G * N  # channels under the one convolution
# an inner width (4 x 16), not the model's
HEADS, KV_HEADS, A = (NEMO[k] for k in (
    "num_heads", "num_kv_heads", "head_dim"))
EXPERTS, HELD, TOP_K, F, SHARED = (NEMO[k] for k in (
    "n_experts", "experts_held", "moe_top_k", "moe_dim", "moe_shared_dim"))


def nemo_config(**over) -> TransformerConfig:
    return tiny_config(**dict(NEMO, **over))


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
    ref.configure(NEMO)
    cfg = nemo_config()
    return cfg, seeded(cfg)


@pytest.fixture(autouse=True)
def highest():
    ref.configure(NEMO)
    with jax.default_matmul_precision("highest"):
        yield


def prompts_of(lengths, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 128, size=n).astype(np.int32) for n in lengths]


# ---- the model -----------------------------------------------------------


def test_a_block_is_one_sublayer_in_the_strings_order(model):
    cfg, params = model
    assert sorted(params) == sorted(
        [f"block{i}" for i in range(LAYERS)] + ["lm_head", "ln_f", "wte"])
    assert [cfg.attn_kind_at(i) for i in range(LAYERS)] == [
        "mamba2", None, "mamba2", None, "mamba2", "mha", None, "mamba2",
        None]
    assert [i for i in range(LAYERS) if cfg.moe_at(i)] == E_LAYERS
    assert cfg.attn_kinds == ("mamba2", "mha") and cfg.slot_state
    assert cfg.latent_row_width == 0 and cfg.experts_held == HELD
    for i, letter in enumerate(PATTERN):  # ONE norm and ONE sublayer
        assert sorted(params[f"block{i}"]) == sorted(
            ["ln1", "moe" if letter == "E" else "attn"])
    assert "wpe" not in params  # no position table
    mixer, full = params["block0"]["attn"], params[f"block{FULL}"]["attn"]
    assert mixer["in_proj"]["kernel"].shape == (48, INNER + CONV + H)
    assert mixer["conv_kernel"].shape == (TAPS, CONV)
    assert mixer["conv_bias"].shape == (CONV,)
    assert mixer["A_log"].shape == mixer["dt_bias"].shape == (H,)
    assert mixer["D"].shape == (H,)
    assert mixer["o_norm"]["scale"].shape == (G, INNER // G)
    assert mixer["proj"]["kernel"].shape == (INNER, 48)
    assert sorted(full) == ["kv", "proj", "q"]  # no norm a head, no gate
    assert full["q"]["kernel"].shape == (48, HEADS, A)
    assert full["kv"]["kernel"].shape == (48, 2, KV_HEADS, A)
    moe = params["block1"]["moe"]
    assert moe["router"]["kernel"].shape == (48, EXPERTS)  # scores them all
    assert moe["router_bias"].shape == (EXPERTS,)
    assert moe["w_up"].shape == (8, 48, F)  # holds eight, two matrices each
    assert moe["w_down"].shape == (8, F, 48)
    assert moe["shared_up"]["kernel"].shape == (48, SHARED)
    assert "w_gate_up" not in moe and "shared_gate_up" not in moe


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


def test_a_dense_block_is_two_matrices_and_a_squared_relu():
    """"-" in a pattern (the family's other members): ``relu(x W_up)^2
    W_down`` behind the block's one norm, against the reference."""
    over = dict(num_layers=4, layer_pattern="M-*-", n_experts=0,
                moe_kind="capacity", moe_router="mlp", moe_top_k=1,
                moe_routed_scale=1.0, moe_dim=None, moe_shared_dim=None,
                experts_held=None, mlp_dim=40)
    ref.configure(dict(NEMO, **over))
    cfg = nemo_config(**over)
    params = seeded(cfg)
    assert sorted(params["block1"]) == ["ln1", "mlp_down", "mlp_up"]
    assert params["block1"]["mlp_up"]["kernel"].shape == (48, 40)
    tokens = jax.random.randint(jax.random.key(3), (2, 11), 1, 128)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(lambda t: TransformerLM(cfg).apply(
            {"params": params}, t, train=False))(tokens))
        want = np.asarray(jax.jit(lambda t: ref.logits(params, t))(tokens))
    assert np.abs(want).max() > 0.1
    assert np.abs(got - want).max() <= TOL


def test_the_routers_probe_makes_the_common_component_every_experts(model):
    """``balance_routers`` leaves every router's matrix with ONE component
    along the mean normed token its probe saw, the same for every expert
    (``-ROUTER_OFFSET`` a logit in the mean): run again on the same seed it
    changes nothing more, and run on a matrix that leans along that mean by
    expert it takes the lean out."""
    cfg, params = model
    first = E_LAYERS[0]
    again = ref.balance_routers(params, 5)
    rng = np.random.default_rng(0)
    tilted = dict(params, **{f"block{first}": dict(
        params[f"block{first}"], moe=dict(
            params[f"block{first}"]["moe"], router={"kernel": params[
                f"block{first}"]["moe"]["router"]["kernel"] + jnp.asarray(
                    rng.normal(size=(48, EXPERTS)), jnp.float32)}))})
    cleared = ref.balance_routers(tilted, 5)
    for i in E_LAYERS:
        w = np.asarray(params[f"block{i}"]["moe"]["router"]["kernel"])
        assert np.abs(np.asarray(
            again[f"block{i}"]["moe"]["router"]["kernel"]) - w).max() < 1e-5
    was = np.asarray(tilted[f"block{first}"]["moe"]["router"]["kernel"])
    now = np.asarray(cleared[f"block{first}"]["moe"]["router"]["kernel"])
    lean = was - now  # rank one: m (m . W + offset) / |m|^2
    assert np.linalg.matrix_rank(lean, tol=1e-4) == 1
    u, sv, _ = np.linalg.svd(lean)
    m = u[:, 0]  # the mean's direction, up to its sign
    along = m @ now  # -offset / |m| for every expert alike
    assert np.ptp(along) < 1e-4 < np.ptp(m @ was)
    assert np.abs(along).min() > 0.1
    # across the mean the columns keep the stated spread, less their lean
    w = np.asarray(params[f"block{first}"]["moe"]["router"]["kernel"])
    across = np.linalg.norm(w - np.outer(m, m @ w), axis=0)
    assert 0.6 * ref.ROUTER_SPREAD < across.min()
    assert across.max() <= ref.ROUTER_SPREAD + 1e-5
    # a token's six weights are spread, not equal: the scores lie under 1/2
    tokens = jax.random.randint(jax.random.key(2), (1, 40), 1, 128)
    h = ref._rms(params["wte"]["embedding"][tokens],
                 params["block0"]["ln1"]["scale"])
    _, weights = ref.route(h, params[f"block{first}"]["moe"], None)
    spread = np.sort(np.asarray(weights), -1)
    assert (spread[..., -1] > 2.0 * spread[..., 0]).mean() > 0.5


def test_the_draw_is_the_published_init(model):
    """``A_log`` in log([1, 16]), ``D`` one, ``dt_bias`` the inverse
    softplus of a step in [0.001, 0.1]: heads that forget over tens to
    thousands of tokens."""
    _, params = model
    for i in M_LAYERS:
        p = params[f"block{i}"]["attn"]
        a = np.exp(np.asarray(p["A_log"]))
        assert (a >= 1.0).all() and (a <= 16.0).all()
        assert (np.asarray(p["D"]) == 1.0).all()
        dt = np.asarray(jax.nn.softplus(p["dt_bias"]))
        assert (dt >= 1e-3 - 1e-6).all() and (dt <= 0.1 + 1e-6).all()


# ---- the Mamba-2 mixer ---------------------------------------------------


@pytest.mark.parametrize("block", [3, 5, 16, 64])
@pytest.mark.parametrize("shift", [0.0, 8.0])
def test_the_blocked_scan_is_the_references_position_at_a_time(
        model, monkeypatch, block, shift):
    """``ssm_blocks`` takes ``BLOCK`` positions a step; the reference runs
    the recurrence a position at a time. 23 positions in blocks of 3 and 5
    (neither divides 23: a padded last block), 16 and one block of 23, from
    a zero state; with ``dt_bias`` shifted by 8 the step is about 8 and a
    head forgets nearly all of its state every token (a = exp(-A 8)) and
    nothing overflows: every exponent is a later sum less an earlier."""
    monkeypatch.setattr(Mamba2Mixer, "BLOCK", block)
    cfg, params = model
    p = dict(params["block2"]["attn"])
    p["dt_bias"] = p["dt_bias"] + shift
    x = jax.random.normal(jax.random.key(7), (2, 23, 48))
    got = np.asarray(Mamba2Mixer(cfg).apply({"params": p}, x, 0))
    want = np.asarray(ref.mamba2(x, p, None))
    assert np.isfinite(got).all() and np.abs(want).max() > 0.01
    assert np.abs(got - want).max() <= TOL


@pytest.mark.parametrize("block", [3, 64])
def test_tick_form_sequence_form_and_the_recurrence_agree_from_a_state(
        model, monkeypatch, block):
    """From the NON-ZERO state and convolution inputs that 5 positions
    leave, rows of 7 and 4 real positions (``lengths``; the shorter one's
    chunk is padded): the sequence form in one call, the tick form a
    position at a time, and the reference's recurrence over the whole
    sequence give the same outputs, and the two forms leave the same state
    and inputs behind (the padding moved neither)."""
    monkeypatch.setattr(Mamba2Mixer, "BLOCK", block)
    cfg, params = model
    p = params["block0"]["attn"]
    x = jax.random.normal(jax.random.key(9), (2, 12, 48))
    lengths = np.array([7, 4])
    want = np.asarray(ref.mamba2(x, p, None))
    _, start = Mamba2Mixer(cfg, prefill=True).apply(
        {"params": p}, x[:, :5], 0, mutable=["cache"])
    assert start["cache"]["state"].shape == (2, H, P, N)  # not square
    assert start["cache"]["conv"].shape == (2, TAPS - 1, CONV)
    assert np.abs(np.asarray(start["cache"]["state"])).max() > 1e-3
    seq, after_seq = Mamba2Mixer(cfg, prefill=True).apply(
        {"params": p, "cache": start["cache"]}, x[:, 5:], 5,
        lengths=jnp.asarray(lengths), mutable=["cache"])
    tick = jax.jit(lambda cache, x_t, at, live: Mamba2Mixer(
        cfg, decode=True).apply({"params": p, "cache": cache}, x_t, at,
                                lengths=live, mutable=["cache"]))
    cache, ticks = start["cache"], []
    for t in range(7):
        out, updated = tick(cache, x[:, 5 + t:6 + t], 5 + t,
                            jnp.asarray((t < lengths).astype(np.int32)))
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
    _, nine = Mamba2Mixer(cfg, prefill=True).apply(
        {"params": p}, x[1:, :9], 0, mutable=["cache"])
    assert np.abs(np.asarray(nine["cache"]["state"][0])
                  - np.asarray(cache["state"][1])).max() <= TOL


@pytest.mark.parametrize("leaf", ["conv_bias", "D", "dt_bias"])
def test_the_bias_the_skip_and_the_step_are_in_the_function(model, leaf):
    """Each of the mixer's small leaves moves the output: none is
    decoration (a convolution without its bias, a state read without the
    skip ``D x``, a step without ``dt_bias`` are other functions)."""
    cfg, params = model
    p = params["block0"]["attn"]
    x = jax.random.normal(jax.random.key(2), (1, 9, 48))
    want = np.asarray(ref.mamba2(x, p, None))
    got = np.asarray(Mamba2Mixer(cfg).apply(
        {"params": dict(p, **{leaf: jnp.zeros_like(p[leaf])})}, x, 0))
    assert np.abs(got - want).max() > 100 * TOL


# ---- the attention layer -------------------------------------------------


@pytest.mark.parametrize("length", [1, 7, 19])
def test_the_dense_decode_cache_reads_the_narrow_heads(model, length):
    """The full-sequence forward widens K and V to the query heads; the
    dense decode cache keeps the two narrow heads and reads them grouped.
    One function: no norm, no rotation, no gate."""
    cfg, params = model
    p = params[f"block{FULL}"]["attn"]
    x = jax.random.normal(jax.random.key(length), (2, length, 48))
    whole = Attention(cfg).apply({"params": p}, x, 0)
    want = np.asarray(ref.attention(x, p, None))
    assert np.abs(np.asarray(whole) - want).max() <= TOL
    cache = None
    for t in range(length):
        variables = {"params": p} if cache is None else {
            "params": p, "cache": cache}
        out, updated = Attention(cfg, decode=True).apply(
            variables, x[:, t:t + 1], t, mutable=["cache"])
        cache = updated["cache"]
        assert np.abs(np.asarray(out[:, 0]) - want[:, t]).max() <= TOL
    assert cache["key"].shape == (2, 64, KV_HEADS, A)


def test_attention_carries_no_position(model):
    """The same rows at another offset give the same outputs: nothing
    rotates and no table is read (under "rope" they would differ)."""
    cfg, params = model
    p = params[f"block{FULL}"]["attn"]
    x = jax.random.normal(jax.random.key(6), (1, 9, 48))
    here = np.asarray(Attention(cfg).apply({"params": p}, x, 0,
                                           jnp.arange(9)))
    there = np.asarray(Attention(cfg).apply({"params": p}, x, 30,
                                            30 + jnp.arange(9)))
    assert (here == there).all()
    rope = dataclasses.replace(cfg, pos_embedding="rope")
    turned = np.asarray(Attention(rope).apply({"params": p}, x, 30,
                                              30 + jnp.arange(9)))
    assert np.abs(turned - here).max() > 100 * TOL


# ---- the expert layer ----------------------------------------------------


def expert_layer(held=HELD, **kw):
    return DroplessMoE(
        n_experts=EXPERTS, moe_dim=F, router="sigmoid", top_k=TOP_K,
        routed_scale=2.5, shared_dim=SHARED, held=held, relu2=True, **kw)


@pytest.fixture(scope="module")
def uncut_layer():
    """The expert layer with all 16 experts held, seeded."""
    ref.configure(NEMO)
    cfg = nemo_config(experts_held=None)
    return seeded(cfg, seed=11)[f"block{E_LAYERS[-1]}"]["moe"]


def shard_of(p, lo, hi):
    return dict(p, w_up=p["w_up"][lo:hi], w_down=p["w_down"][lo:hi])


@pytest.mark.parametrize("cuts", [(0, 8, 16), (0, 4, 8, 12, 16)])
def test_the_shares_add_up(uncut_layer, cuts):
    """Experts [0, 8) and [8, 16) on two shards (the deployment's cut), or
    four of four: each routes over all 16 and computes its own; the routed
    parts plus the shared expert ONCE are the uncut layer of the
    reference."""
    x = jax.random.normal(jax.random.key(3), (2, 19, 48))
    ref.HELD_FROM = 0
    want = np.asarray(ref.moe(x, uncut_layer, None))
    shared = np.asarray(want - ref.moe(x, uncut_layer, None, shared=False))
    assert np.abs(shared).max() > 0.01
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


def test_the_weights_are_the_scaled_renormalised_sigmoid(uncut_layer):
    x = jax.random.normal(jax.random.key(8), (1, 33, 48))
    ids, w = (np.asarray(a) for a in ref.route(x, uncut_layer, None))
    assert ids.shape == (1, 33, TOP_K)
    assert np.allclose(w.sum(-1), 2.5, atol=1e-5)
    scores = np.asarray(jax.nn.sigmoid(x @ uncut_layer["router"]["kernel"]))
    top = np.sort(scores, -1)[..., ::-1][..., :TOP_K]
    assert np.allclose(np.sort(w, -1)[..., ::-1],
                       2.5 * top / top.sum(-1, keepdims=True), atol=1e-5)
    # the bias enters the choice and not the weight
    lifted = dict(uncut_layer, router_bias=jnp.zeros((EXPERTS,)).at[3].set(
        10.0))
    ids, w = (np.asarray(a) for a in ref.route(x, lifted, None))
    assert (ids == 3).any(-1).all()
    mine = np.take_along_axis(scores, ids, -1)
    assert np.allclose(w, 2.5 * mine / mine.sum(-1, keepdims=True),
                       atol=1e-5)
    out, _ = expert_layer(held=None).apply({"params": lifted}, x)
    ref.HELD_FROM = 0
    assert np.abs(np.asarray(out) - np.asarray(ref.moe(x, lifted, None))
                  ).max() <= TOL


def test_an_expert_is_two_matrices_and_a_squared_relu(uncut_layer):
    """One token sent to one expert with weight 1: ``relu(x W_up)^2
    W_down``, by hand."""
    x = jax.random.normal(jax.random.key(5), (1, 1, 48))
    one = dict(uncut_layer, router_bias=jnp.zeros((EXPERTS,)).at[5].set(9.0))
    (out, _) = DroplessMoE(n_experts=EXPERTS, moe_dim=F, router="sigmoid",
                           top_k=1, relu2=True).apply(
        {"params": {k: v for k, v in one.items()
                    if not k.startswith("shared")}}, x)
    up = np.asarray(x[0, 0] @ uncut_layer["w_up"][5])
    want = np.square(np.maximum(up, 0.0)) @ np.asarray(
        uncut_layer["w_down"][5])
    assert np.abs(np.asarray(out)[0, 0] - want).max() <= TOL
    assert np.abs(want).max() > 0.01


def test_the_expert_stacks_are_held_in_whole_tiles_of_the_grouped_product():
    """At 520 features the two stacks are held 1,024 wide (XLA's grouped
    product takes 512-wide tiles of a width that is whole ones, 128-wide
    tiles of any other): zeros beyond the model's widths at init and in the
    reference's draw, and the layer is the function of the corner that the
    reference reads; a toy's widths stay as they are."""
    assert [grouped_width(w) for w in (24, 511, 512, 1856, 2688)] == [
        24, 511, 512, 2048, 3072]
    layer = DroplessMoE(n_experts=4, moe_dim=520, router="sigmoid", top_k=2,
                        relu2=True)
    x = jax.random.normal(jax.random.key(1), (1, 7, 520))
    params = layer.init(jax.random.key(2), x)["params"]
    for name in ("w_up", "w_down"):
        w = np.asarray(params[name])
        assert w.shape == (4, 1024, 1024)
        assert (w[:, 520:] == 0).all() and (w[:, :, 520:] == 0).all()
        assert np.abs(w[:, :520, :520]).min() > 0
        ref.configure(dict(NEMO, embed_dim=520, moe_dim=520))
        drawn = np.asarray(ref.param_rule(("block1", "moe", name), w.shape)(
            jnp.ones(w.shape)))
        assert (drawn[:, 520:] == 0).all() and (drawn[:, :, 520:] == 0).all()
        assert (drawn[:, :520, :520] > 0).all()
    ref.configure(dict(NEMO, embed_dim=520, moe_dim=520, moe_top_k=2,
                       moe_routed_scale=1.0))
    ref.HELD_FROM = 0
    want = np.asarray(ref.moe(x, params, None, shared=False))
    got, _ = layer.apply({"params": params}, x)
    assert np.abs(want).max() > 0.01
    assert np.abs(np.asarray(got) - want).max() <= TOL
    # what lies beyond the corner is the program's to keep at zero: the
    # reference does not read it
    dirty = dict(params, w_up=params["w_up"].at[:, :, 520:].set(1.0))
    assert (np.asarray(ref.moe(x, dirty, None, shared=False)) == want).all()


def test_the_counts_are_a_bincount_of_live_pairs_on_held_experts(model):
    cfg, params = model
    p = params[f"block{E_LAYERS[1]}"]["moe"]
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
    (dict(layer_pattern="MEMEM*EM"), "num_layers"),
    (dict(layer_pattern="MEMEM*EMX"), "layer_pattern"),
    (dict(layer_pattern="MEMEMAEME"), "layer_pattern"),
    (dict(attn_kind="gdn"), "attn_kind stays"),
    (dict(layer_group_size=3), "attn_kind stays"),
    (dict(first_k_dense_replace=1), "attn_kind stays"),
    (dict(mamba_num_heads=None), "whole groups"),
    (dict(mamba_state_size=0), "whole groups"),
    (dict(mamba_n_groups=3), "whole groups"),
    (dict(attention="flash"), "one shard"),
    (dict(ut_steps=2), "one pass|one shard"),
    (dict(tp_size=2, model_axis="model"), "one shard"),
    (dict(layer_pattern="M-M-M*-M-"), "'E' layers"),
    (dict(n_experts=0), "'E' layers|n_experts"),
    (dict(moe_kind="capacity", moe_dim=None), "'E' layers"),
    (dict(moe_router="mlp", moe_routed_scale=1.0, moe_shared_dim=None,
          experts_held=None), "'E' layers"),
    (dict(pos_embedding="sinusoid"), "pos_embedding"),
    (dict(mlp="relu"), "mlp"),
    (dict(moe_top_k=17), "fit inside"),
    (dict(experts_held=(8, 4)), "experts_held"),
    (dict(router_dim=8), "router_dim"),
    (dict(moe_dim=None), "moe_dim"),
])
def test_the_config_refuses_what_it_cannot_run(over, match):
    with pytest.raises(ValueError, match=match):
        nemo_config(**over)


@pytest.mark.parametrize("over,match", [
    (dict(mamba_num_heads=4), "'M' layers"),
    (dict(mamba_state_size=16), "'M' layers"),
    (dict(layer_pattern="**", mamba_n_groups=2), "'M' layers"),
])
def test_the_mamba_keys_describe_their_own_layers_only(over, match):
    """On the plain block, and on a pattern without an "M"."""
    with pytest.raises(ValueError, match=match):
        tiny_config(**over)


def test_a_plain_stack_takes_no_positions():
    """``pos_embedding="none"`` on the plain block (attention then MLP, no
    pattern): no table in the tree, and ``generate``'s dense cache decodes
    what the full forward gives."""
    cfg = tiny_config(num_layers=2, embed_dim=48, num_heads=4, num_kv_heads=2,
                      pos_embedding="none", mlp="relu2", max_seq_len=32)
    params = TransformerLM(cfg).init(jax.random.key(0),
                                     jnp.zeros((1, 8), jnp.int32))["params"]
    assert "wpe" not in params and "mlp_gate" not in params["block0"]
    prompt = jax.random.randint(jax.random.key(4), (2, 7), 1, 128)
    out = np.asarray(generate(cfg, params, prompt, jax.random.key(0),
                              max_new_tokens=2))
    forward = jax.jit(lambda t: TransformerLM(cfg).apply(
        {"params": params}, t, train=False))
    seq = np.zeros((2, 9), np.int32)
    seq[:, :7] = np.asarray(prompt)
    for at in (7, 8):  # a causal model does not see the zeros behind
        seq[:, at] = np.argmax(np.asarray(forward(seq))[:, at - 1], -1)
    assert (out == seq).all()
