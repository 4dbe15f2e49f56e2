"""GLM-4.7-Flash (zai-org; ``config.json`` of zai-org/GLM-4.7-Flash,
``model_type: "glm4_moe_lite"``), one pipeline stage whole on one chip, as
held without a network. Its two kinds of sublayer are published mechanisms:
multi-head latent attention WITH query compression (DeepSeek-V2,
arXiv:2405.04434; ``q_lora_rank: 768``) whose values are wider than its
unrotated keys (``v_head_dim`` 256, ``qk_nope_head_dim`` 192), in EVERY
layer, and the sigmoid-scored, bias-corrected top-k router with one shared
expert (DeepSeek-V3's ``noaux_tc`` form, ``n_group: 1``). E the model's
width, H heads of D unrotated and R rotated query/key dims and V value dims,
Q the query's rank, C the latent, X experts of F features, k a token:

    x_0 = wte[tokens]        logits = n(x_N) W_head               (untied)
    layer l:  x <- x + MLA_l(n(x));   x <- x + F_l(n(x))
    F_l is the dense SwiGLU for l < first_k_dense_replace, else the experts
    n(x) = x / rms(x) * w                    (a learned scale; eps 1e-5)

    MLA(x):  c_q = n(x W_qa)  [Q];   [q_nope_h; q_rope_h] = c_q W_qb,h  [D + R]
             [c~; k_r] = x W_kva  [C + R];   c = n(c~)
             [k_nope_h; v_h] = c W_kvb,h  [D + V a head]
             RoPE (pairs (2i, 2i+1), theta) on each q_rope_h and on the ONE
             k_r all heads share, by the token's position
             s_h = (q_nope_h . k_nope_h + q_rope_h . k_r) / sqrt(D + R)
             o_h = softmax(s_h + causal) v_h             (no gate, no bias)
             MLA = concat_h(o_h) W_o              [H V -> E]

    MoE(x):  s = sigmoid(x W_r)  [X], float32
             chosen: the k largest of s + b   (b enters the choice only)
             w_i = scale * s_i / (sum_chosen s_j + 1e-20)
             MoE = sum_{i chosen} w_i E_i(x) + E_sh(x)
             E(x) = (SiLU(x W_gate) * (x W_up)) W_down

Every expert is held here (a ``program`` block with ``experts_held`` names
the first held expert and the stacks then hold that share: the tests' share
sum). The multi-token-prediction module (``num_nextn_predict_layers`` 1)
follows the last published layer, which the last stage holds, and the
server does not draft: left out.

Assumed (wiring that no key of ``config.json`` settles; the configuration's
file lists each): RoPE's pairing of dims inside the R follows the program's
``_rope_rotate``, pairs ``(2i, 2i+1)`` (under seeded random weights the
other pairing is a permutation of W_qb's and W_kva's columns); no per-head
norm inside attention and no output gate; ``n_group`` 1 / ``topk_group`` 1
mean no group limit; the shared expert is added with weight 1.

Departures, the program's own, relabellings of weights that random weights
do not see: an expert's W_gate and W_up sit side by side (``w_gate_up``,
``shared_gate_up``); ``kv_b`` holds a head's D key columns, then its V value
columns; ``q_b`` a head's D unrotated, then its R rotated. The program pads
the cache row from C + R = 576 to 640 lanes with zeros, which no product
sees, and leaves the 1e-20 out of the weights' denominator.

This file holds no cache, expands keys and values for every position, runs
attention dense and causal (a block of query rows at a time), routes from its
own float32 arithmetic and runs every held expert over every token, keeping
each token's weight by a mask. ``cast`` is applied to both operands of every
matrix product (None: the reference proper; a lower precision: the control).
A weight becomes float32 where it is used.

**The draw** (``param_rule``). Every matrix at fan-in scale, N(0, 1 /
fan_in), and the output projections (W_o, W_down of the dense MLP, of the
experts and of the shared expert) scaled by ``1 / sqrt(2 L)`` more; the
embedding at N(0, 1) a row, every row distinct, plus SHARED_NORM / sqrt(E)
of ONE row that every token shares (0.22 of the pad id's, which is never
sent: the router's draw stands on it); norm scales at 1 + N(0, 0.02). With these a head's
scores have a standard deviation of about one: attention neither flat nor
one-hot, so a latent row read from the wrong block shows in the logits.
**The router's draw** (``router_draw`` in the configuration's file) is what
PR 41 and PR 44 learned, for an EVEN load and for weights that FALL OFF:
every expert's column of ``W_r`` is drawn at N(0, 1) and scaled to the
length ROUTER_SPREAD, and ``init_params`` runs the stack once over
``PROBE_TOKENS`` seeded ids, layer by layer, takes the mean ``m`` of the
normed tokens each router sees, and replaces its matrix ``W`` by ``(I - m
m^T / |m|^2) W - ROUTER_OFFSET m 1^T / |m|^2``: no expert is favoured by
what all tokens share, and that share takes ROUTER_OFFSET off every
expert's logit. The choice does not see a shift common to the experts; the
WEIGHTS do: about -18 at spread 6 the chosen logits lie at -4 to -8, the
scores fall off as ``e^z`` does (the first chosen expert carries 1.3 of a
token's 1.8, the fourth 0.035 in the median), and a bfloat16 rounding that
swaps the fourth chosen expert for the fifth moves a few hundredths of a
token's routed weight (about zero all four scores would lie near 1 and the
swap would move a quarter of it, as far as the float8 control moves a
logit). The offset stands on ``m``: a token's share of it wobbles by
ROUTER_OFFSET / |m| (1.6-1.9 with |m| = SHARED_NORM = 10, 0.22 of a row at
the published width).
``b`` is drawn at N(0, ROUTER_BIAS) with ROUTER_BIAS 1e-7, UNDER the
smallest chosen score (the fourth's is 2e-4 in the median and 1e-6 three
wobbles down): it moves a choice between two scores that all but tie and
no weight. **The first draw was ISSUE 50's and PR 44's (spread 5, offset
14, 0.15 of a row, ``b`` at N(0, 3e-4)) and failed on the chip** (my chip
runs, PR 50): a fourth chosen score of 2e-3 and less lies INSIDE that
bias's spread, so the third and fourth choices went to whichever experts
drew the largest ``b`` among dozens of scores that tie to within it, which
a bfloat16 rounding flips: three sound readings 0.074, 0.223 and 1.168
against the control's 1.660 (no limit between) and
``expert_load_peak_ratio`` 3.48 (the fullest of 64 experts took 42 pairs at
a mean of 12). A probe of the same stack at a reduced width on the CPU
(512 wide, 6 layers, bfloat16 program against this file, 1,400 tokens a
reading, 8 readings a draw; a host count, not a device number) read
0.30-1.19 against 1.08-2.18 at that draw, 0.02-0.23 against 0.31-0.61 with
``b`` at 1e-6 alone (the load even, 1.3), 0.04-0.13 against 0.39-0.98 at
spread 6 / offset 18 / |m| 10, and 0.19-0.62 against 0.68-1.77 at offset 20
(the fourth score falls under ``b`` again).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perfbench.harness.weights import seed_key, seeded_tree

HIGHEST = jax.lax.Precision.HIGHEST

#: what the parameter tree does not hold; the published values, which
#: ``configure`` replaces with the configuration's as it is run
ROPE_THETA = 1e6
RMS_EPS = 1e-5
TOP_K = 4
ROUTED_SCALE = 1.8
HELD_FROM = 0
LAYERS = 6
#: standard deviation of a normed token's router logits
ROUTER_SPREAD = 6.0
#: what the stream's common component takes off EVERY expert's logit
ROUTER_OFFSET = 18.0
#: standard deviation of the selection bias
ROUTER_BIAS = 1e-7
#: the length of what every token's embedding carries of ONE shared row (a
#: row's own length is sqrt(E): 0.22 of the row at the published 2,048, and
#: the same length at a toy's width, where 0.22 of a row would leave the
#: routers' common offset standing on next to nothing)
SHARED_NORM = 10.0
#: ids the router's probe runs over (``init_params``)
PROBE_TOKENS = 1024
#: query rows an attention layer scores at a time
QUERY_BLOCK = 1024


def configure(program: dict) -> None:
    """What a ``program`` block says beside the tree's shapes."""
    global ROPE_THETA, RMS_EPS, TOP_K, ROUTED_SCALE, HELD_FROM, LAYERS
    ROPE_THETA = float(program["rope_theta"])
    RMS_EPS = float(program["norm_eps"])
    TOP_K = int(program["moe_top_k"])
    ROUTED_SCALE = float(program["moe_routed_scale"])
    LAYERS = int(program["num_layers"])
    held = program.get("experts_held")
    HELD_FROM = int(held[0]) if held else 0


def param_rule(names, shape):
    leaf = names[-2] if names[-1] in ("kernel", "embedding") else names[-1]
    out_scale = 1.0 / math.sqrt(2 * LAYERS)
    if leaf == "scale":
        return lambda n: 1.0 + 0.02 * n
    if leaf == "wte":  # row 0 (the pad id, never sent) is every row's share
        return lambda n: n + SHARED_NORM * shape[1] ** -0.5 * n[:1]
    if leaf == "router_bias":
        return lambda n: ROUTER_BIAS * n
    if leaf == "router":  # [E, X]: an expert's column, all of one length
        return lambda n: ROUTER_SPREAD * n * jax.lax.rsqrt(
            jnp.sum(jnp.square(n), axis=0, keepdims=True))
    if leaf == "w_gate_up":  # [experts, E, 2 F]
        return lambda n: shape[1] ** -0.5 * n
    if leaf == "w_down":  # [experts, F, E]
        return lambda n: out_scale * shape[1] ** -0.5 * n
    if leaf == "proj":  # [H, V, E]: every axis but the last
        return lambda n: out_scale * math.prod(shape[:-1]) ** -0.5 * n
    if leaf in ("shared_down", "mlp_down"):
        return lambda n: out_scale * shape[0] ** -0.5 * n
    return lambda n: shape[0] ** -0.5 * n  # [fan-in, ...]


def init_params(seed: int, shapes, dtype=None):
    """The tree filled from the seed, a layer at a time (an expert layer's
    two stacks of matrices are drawn each by itself: 403M normals are 1.6 GB
    of float32 beside the weights they make), then every router's matrix
    cleared of the stream's common component (``balance_routers``). Layers
    of one kind share a compiled filler."""
    base = (int(seed) & 0xFFFFFFFFFFFF) * 4099

    def part(i, tree):
        return seeded_tree(base + i, tree, param_rule, dtype)

    blocks = sorted((k for k in shapes if k.startswith("block")),
                    key=lambda k: int(k[5:]))
    out = part(0, {k: v for k, v in shapes.items() if k not in blocks})
    for i, k in enumerate(blocks):
        block = dict(shapes[k])
        if "moe" not in block:
            out[k] = part(8 * i + 1, block)
            continue
        moe_shapes = dict(block.pop("moe"))
        stacks = {n: moe_shapes.pop(n) for n in ("w_gate_up", "w_down")}
        filled = part(8 * i + 1, block)
        filled["moe"] = part(8 * i + 2, moe_shapes)
        for j, (n, leaf) in enumerate(sorted(stacks.items())):
            filled["moe"][n] = part(8 * i + 3 + j, {n: leaf})[n]
        out[k] = filled
    return balance_routers(out, seed)


def balance_routers(params, seed: int):
    """``params`` with every router's matrix ``W`` [E, X] replaced by ``(I -
    m m^T / |m|^2) W - ROUTER_OFFSET m 1^T / |m|^2``, ``m`` the mean over
    ``PROBE_TOKENS`` seeded ids of the normed tokens that router sees, the
    layers below it already cleared: what all tokens share moves every
    expert's logit alike, by ``-ROUTER_OFFSET`` in the mean. One compiled
    step a kind of layer."""
    vocab = params["wte"]["embedding"].shape[0]
    tokens = jax.random.randint(
        jax.random.fold_in(seed_key(seed), 0x5EED), (1, PROBE_TOKENS), 1,
        vocab)

    def dense_step(x, p):
        x = x + mla(_rms(x, p["ln1"]["scale"]), p["attn"], None)
        return x + dense_mlp(_rms(x, p["ln2"]["scale"]), p, None), None

    def cleared(x, p):
        x = x + mla(_rms(x, p["ln1"]["scale"]), p["attn"], None)
        h = _rms(x, p["ln2"]["scale"])
        m = jnp.mean(h, axis=(0, 1))
        w = _f32(p["moe"]["router"]["kernel"])
        lean = jnp.dot(m, w, precision=HIGHEST)  # an expert's own, [X]
        w = (w - jnp.outer(m, lean + ROUTER_OFFSET) / jnp.dot(m, m)).astype(
            p["moe"]["router"]["kernel"].dtype)
        return x + moe(h, dict(p["moe"], router={"kernel": w}), None), w

    dense_step, cleared = jax.jit(dense_step), jax.jit(cleared)
    out = dict(params)
    with jax.default_matmul_precision("highest"):
        x = _f32(params["wte"]["embedding"][tokens])
        for i in range(_layers(params)):
            p = params[f"block{i}"]
            x, w = (cleared if "moe" in p else dense_step)(x, p)
            if w is not None:
                out[f"block{i}"] = dict(p, moe=dict(p["moe"],
                                                    router={"kernel": w}))
    return out


def _layers(params) -> int:
    return sum(1 for k in params if k.startswith("block"))


def _f32(x):
    return x.astype(jnp.float32)


def _mm(spec, a, b, cast):
    if cast is not None:
        a, b = cast(a), cast(b)
    return jnp.einsum(spec, _f32(a), _f32(b), precision=HIGHEST)


def _rms(x, scale):
    return (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                              + RMS_EPS) * _f32(scale))


def _rope(x):
    """[B, L, H, R] at positions 0..L-1: every pair (2i, 2i+1) turns."""
    l, r = x.shape[1], x.shape[-1]
    freq = ROPE_THETA ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(l, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     -1).reshape(x.shape)


def _swiglu(h, w_in, w_down, cast):
    f = w_down.shape[0]
    gu = _mm("ble,ef->blf", h, w_in, cast)
    return _mm("blf,fe->ble", jax.nn.silu(gu[..., :f]) * gu[..., f:], w_down,
               cast)


def mla(h, p, cast):
    """[B, L, E] normed state -> latent attention with a compressed query,
    keys and values expanded for every position, ``QUERY_BLOCK`` query rows
    at a time."""
    l = h.shape[1]
    _, v_dim, _ = p["proj"]["kernel"].shape
    latent = p["kv_a_norm"]["scale"].shape[0]
    rot = p["kv_a"]["kernel"].shape[1] - latent
    d = p["kv_b"].shape[-1] - v_dim
    c_q = _rms(_mm("ble,eq->blq", h, p["q_a"]["kernel"], cast),
               p["q_a_norm"]["scale"])
    q = _mm("blq,qhd->blhd", c_q, p["q_b"]["kernel"], cast)
    q_nope, q_rope = q[..., :d], _rope(q[..., d:])
    kva = _mm("ble,ef->blf", h, p["kv_a"]["kernel"], cast)
    c = _rms(kva[..., :latent], p["kv_a_norm"]["scale"])
    k_r = _rope(kva[..., latent:][:, :, None, :])[:, :, 0]
    kv = _mm("blc,chd->blhd", c, p["kv_b"], cast)
    k_nope, v = kv[..., :d], kv[..., d:]
    rows = []
    for at in range(0, l, QUERY_BLOCK):
        cut = slice(at, at + QUERY_BLOCK)
        s = (_mm("bqhd,bkhd->bhqk", q_nope[:, cut], k_nope, cast)
             + _mm("bqhr,bkr->bhqk", q_rope[:, cut], k_r, cast)
             ) / math.sqrt(d + rot)
        seen = (jnp.arange(l)[None, :]
                <= (at + jnp.arange(s.shape[2]))[:, None])
        s = jnp.where(seen, s, -jnp.inf)
        rows.append(_mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v, cast))
    return _mm("bqhd,hde->bqe", jnp.concatenate(rows, 1),
               p["proj"]["kernel"], cast)


def route(h, p, cast):
    """(expert ids [B, L, k], weights [B, L, k]) over ALL the experts."""
    scores = jax.nn.sigmoid(
        _mm("ble,ex->blx", h, p["router"]["kernel"], cast))
    ids = jax.lax.top_k(scores + _f32(p["router_bias"]), TOP_K)[1]
    w = jnp.take_along_axis(scores, ids, axis=-1)
    return ids, ROUTED_SCALE * w / (jnp.sum(w, -1, keepdims=True) + 1e-20)


def moe(h, p, cast, shared: bool = True):
    """The held experts' part of the routed sum (a dense loop, each token's
    weight kept by a mask) and, with ``shared``, the shared expert."""
    ids, w = route(h, p, cast)

    def one(acc, expert):
        i, w_in, w_down = expert
        mine = jnp.sum(jnp.where(ids == i, w, 0.0), -1)[..., None]
        return acc + mine * _swiglu(h, w_in, w_down, cast), None

    n = p["w_down"].shape[0]
    out, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (HELD_FROM + jnp.arange(n), p["w_gate_up"], p["w_down"]))
    if shared:
        out = out + _swiglu(h, p["shared_gate_up"]["kernel"],
                            p["shared_down"]["kernel"], cast)
    return out


def dense_mlp(h, p, cast):
    gate = jax.nn.silu(_mm("ble,ef->blf", h, p["mlp_gate"]["kernel"], cast))
    up = _mm("ble,ef->blf", h, p["mlp_up"]["kernel"], cast)
    return _mm("blf,fe->ble", gate * up, p["mlp_down"]["kernel"], cast)


def hidden(params, tokens, cast=None):
    """[B, L] token ids -> the final normed state [B, L, E]."""
    x = _f32(params["wte"]["embedding"][tokens])
    for i in range(_layers(params)):
        p = params[f"block{i}"]
        x = x + mla(_rms(x, p["ln1"]["scale"]), p["attn"], cast)
        h = _rms(x, p["ln2"]["scale"])
        x = x + (moe(h, p["moe"], cast) if "moe" in p
                 else dense_mlp(h, p, cast))
    return _rms(x, params["ln_f"]["scale"])


def logits(params, tokens, cast=None):
    return _mm("ble,ev->blv", hidden(params, tokens, cast),
               params["lm_head"]["kernel"], cast)
