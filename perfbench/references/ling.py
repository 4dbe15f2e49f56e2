"""Ling-3.0-flash (inclusionAI; ``config.json`` of inclusionAI/Ling-3.0-flash,
``model_type: "bailing_hybrid"``), one chip's share of one pipeline stage,
as held without a network. Its three kinds of sublayer are published
mechanisms: Kimi Delta Attention (arXiv:2510.26692) with the lower-bounded
gate the keys name (``kda_lower_bound``, ``kda_safe_gate``), multi-head
latent attention without query compression (DeepSeek-V2, arXiv:2405.04434;
``q_lora_rank: null``) and the sigmoid-scored, bias-corrected,
group-limited top-k router with one shared expert (DeepSeek-V3 / Ling 2.0:
``score_function: sigmoid``, ``topk_method: noaux_tc``). E the model's
width, H heads, D the head size (KDA's d_k = d_v, MLA's unrotated and value
dims), R MLA's rotated dims, C its latent, X experts of F features of which
this chip holds ``[lo, hi)``, k experts a token, G groups of which g are
kept:

    x_0 = wte[tokens]        logits = RMSNorm(x_N) W_head    (untied, a slice)
    layer l:  x <- x + Attn_l(RMSNorm(x));   x <- x + MLP_l(RMSNorm(x))
    Attn_l is MLA where (l + 1) % layer_group_size == 0, KDA otherwise
    MLP_l is dense SwiGLU for l < first_k_dense_replace, else the experts

    KDA(x):  q~, k~, v~ = x Wq, x Wk, x Wv                      [H D each]
             q_t = SiLU(sum_{j=0..T-1} w_j * q~_{t-(T-1)+j})    (depthwise, T
                   taps, zeros before position 0; k and v alike)
             q^ = q / |q| * D^-1/2,  k^ = k / |k|               (a head)
             g_t = L * sigmoid(exp(A_log_h) * (x_t Wf + dt_bias)),  L = -5
             alpha_t = exp(g_t) in (e^L, 1)^D      beta_t = sigmoid(x_t Wb)_h
             S_t = (I - beta_t k^_t k^_t^T) Diag(alpha_t) S_{t-1}
                   + beta_t k^_t v_t^T,   S_0 = 0   [D x D a head, float32]
             o_t = S_t^T q^_t
             KDA = [RMSNorm_head(o_t) * sigmoid(x_t Wg)] Wo

    MLA(x):  q = x Wq  [H (D + R)];   [c~; k_r] = x Wkva  [C + R]
             c = RMSNorm(c~);   [k_nope_h; v_h] = c Wkvb_h  [D + D a head]
             RoPE (pairs (2i, 2i+1), theta) on q's R rotated dims a head and
             on the one shared k_r
             s_h = (q_nope_h . k_nope_h + q_rope_h . k_r) / sqrt(D + R)
             o_h = softmax(s_h + causal) v_h * sigmoid(x Wgh)_h
             MLA = o Wo

    MoE(x):  s = sigmoid(x Wr)  [X], float32
             choice on s + b: X in G groups, a group's score the sum of its
             two largest, the g best groups kept, the k best experts inside
             w_i = scale * s_i / sum_sel s_j        (b not in the weight)
             MoE = sum_{i in sel, lo <= i < hi} w_i E_i(x) + E_sh(x)
             E(x) = (SiLU(x Wgate) * (x Wup)) Wdown

``MoE`` is THIS CHIP's part of the routed sum (the router scores all X; the
experts outside ``[lo, hi)`` are three other chips') plus the shared expert.
The ``*_swiglu_limit_list``s are 0 in every layer held, so nothing is
clamped. The multi-token-prediction module follows the last published
layer, which another stage holds: left out.

Assumed (wiring that no key of ``config.json`` settles; the configuration's
file lists each, and each is a place where the published model may differ):
``use_qk_norm`` is KDA's L2 norm of q and k, and MLA has no per-head norm
(a norm behind the up-projection would forbid the folded decode path; the
latent has ``kv_a``'s RMSNorm); ``linear_silu`` is the SiLU behind the
convolutions; ``group_norm_size: 1`` is KDA's output norm taken a head, with
one learned scale of D shared by the heads; ``gated_attention_proj_
granularity_type: head_wise`` is MLA's output gate, a scalar a head, and
KDA's output gate is Kimi Linear's elementwise one at full rank; the
convolutions carry no bias; ``partial_rotary_factor`` / ``rotary_dim``
restate ``qk_rope_head_dim``; a group's score is the sum of its two
largest; ``A_log`` is one scalar a head and ``dt_bias`` one a channel; the
L2 norms' epsilon 1e-6 under the root.

Departures, the program's own, relabellings of weights that random weights
do not see: Wq, Wk, Wv of KDA are columns of one matrix ``qkv`` and its
three convolutions one ``conv_kernel``; an expert's Wgate and Wup sit side
by side (``w_gate_up``, ``shared_gate_up``). The program pads MLA's cache
row from C + R = 576 to 640 lanes with zeros, which no product sees.

This file holds no cache, runs KDA a token at a time, expands MLA's keys
and values for every position, routes from its own float32 arithmetic and
runs every held expert over every token, keeping each token's weight by a
mask. ``cast`` is applied to both operands of every matrix product (None:
the reference proper; a lower precision: the control). A weight becomes
float32 where it is used.

**The draw.** Every matrix at N(0, 0.02), scales at 1 + N(0, 0.02). The
router's matrix at N(0, 0.02) too: over RMS-normed tokens of width 2,560
that is logits of standard deviation 1, sigmoid scores from 0.1 to 0.9, and
with ``router_bias`` at N(0, 0.02) no expert has a head start, so a tick of
256 tokens x 8 lands on every one of 512 experts a few times. ``dt_bias`` is
drawn at -4 + N(0, 1) and ``A_log`` at N(0, 0.02), so alpha lies between
0.75 and 0.995 a channel (a memory of 4 to 200 tokens: a state that forgot
at once would hide a state read from the wrong slot); the convolutions'
taps at 1/T + N(0, 0.1).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perfbench.harness.weights import seeded_tree

HIGHEST = jax.lax.Precision.HIGHEST

#: what the parameter tree does not hold; the published values, which
#: ``configure`` replaces with the configuration's as it is run
ROPE_THETA = 6e6
RMS_EPS = 1e-6
KDA_LOWER_BOUND = -5.0
LAYER_GROUP = 6
TOP_K, N_GROUP, TOPK_GROUP, ROUTED_SCALE = 8, 8, 4, 2.5
HELD_FROM = 0
L2_EPS = 1e-6


def configure(program: dict) -> None:
    """What a ``program`` block says beside the tree's shapes."""
    global ROPE_THETA, RMS_EPS, LAYER_GROUP, TOP_K
    global N_GROUP, TOPK_GROUP, ROUTED_SCALE, HELD_FROM
    ROPE_THETA = float(program["rope_theta"])
    RMS_EPS = float(program["norm_eps"])
    LAYER_GROUP = int(program["layer_group_size"])
    TOP_K = int(program["moe_top_k"])
    N_GROUP = int(program["moe_n_group"])
    TOPK_GROUP = int(program["moe_topk_group"])
    ROUTED_SCALE = float(program["moe_routed_scale"])
    held = program.get("experts_held")
    HELD_FROM = int(held[0]) if held else 0


def param_rule(names, shape):
    leaf = names[-2] if names[-1] in ("kernel", "embedding") else names[-1]
    if leaf in ("scale",):
        return lambda n: 1.0 + 0.02 * n
    if leaf == "dt_bias":
        return lambda n: -4.0 + n
    if leaf == "conv_kernel":
        return lambda n: 1.0 / shape[0] + 0.1 * n
    return lambda n: 0.02 * n


def init_params(seed: int, shapes, dtype=None):
    """The tree filled from the seed, a few leaves at a time: an expert
    layer's two stacks of matrices are drawn each by itself (503M normals
    are 2 GB of float32 beside the weights they make), the rest of a block
    together. Blocks of one kind share a compiled filler."""
    base = (int(seed) & 0xFFFFFFFFFFFF) * 4099

    def part(i, tree):
        return seeded_tree(base + i, tree, param_rule, dtype)

    blocks = sorted((k for k in shapes if k.startswith("block")),
                    key=lambda k: int(k[5:]))
    out = part(0, {k: v for k, v in shapes.items() if k not in blocks})
    for i, k in enumerate(blocks):
        block = dict(shapes[k])
        moe = dict(block.pop("moe", {}))
        stacks = {n: moe.pop(n) for n in ("w_gate_up", "w_down") if n in moe}
        filled = part(8 * i + 1, block)
        if moe:
            filled["moe"] = part(8 * i + 2, moe)
            for j, (n, leaf) in enumerate(sorted(stacks.items())):
                filled["moe"][n] = part(8 * i + 3 + j, {n: leaf})[n]
        out[k] = filled
    return out


def _f32(x):
    return x.astype(jnp.float32)


def _mm(spec, a, b, cast):
    if cast is not None:
        a, b = cast(a), cast(b)
    return jnp.einsum(spec, _f32(a), _f32(b), precision=HIGHEST)


def _rms(x, scale):
    return (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                              + RMS_EPS) * _f32(scale))


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + L2_EPS)


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


def kda(h, p, cast):
    """[B, L, E] normed state -> the delta-rule sublayer's output: the
    recurrence a token at a time from a zero state."""
    b, l, _ = h.shape
    heads = p["A_log"].shape[0]
    inner = p["gate_f"]["kernel"].shape[1]
    d = inner // heads
    w = _f32(p["conv_kernel"])  # [taps, 3 inner]
    taps = w.shape[0]
    pre = jnp.pad(_mm("ble,ef->blf", h, p["qkv"]["kernel"], cast),
                  ((0, 0), (taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(w[j] * pre[:, j:j + l] for j in range(taps)))
    q, k, v = (qkv[..., i * inner:(i + 1) * inner].reshape(b, l, heads, d)
               for i in range(3))
    q, k = _unit(q) * d ** -0.5, _unit(k)
    f = _mm("ble,ef->blf", h, p["gate_f"]["kernel"], cast) + _f32(
        p["dt_bias"])
    rate = jnp.exp(_f32(p["A_log"]))[:, None]
    alpha = jnp.exp(KDA_LOWER_BOUND * jax.nn.sigmoid(
        rate * f.reshape(b, l, heads, d)))
    beta = jax.nn.sigmoid(_mm("ble,eh->blh", h, p["beta"]["kernel"], cast))

    def step(s, t):
        q_t, k_t, v_t, a_t, b_t = t  # [B, H, D] and beta [B, H]
        s = a_t[..., None] * s
        seen = jnp.einsum("bhk,bhkv->bhv", k_t, s, precision=HIGHEST)
        s = s + jnp.einsum("bhk,bhv->bhkv", b_t[..., None] * k_t, v_t - seen,
                           precision=HIGHEST)
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision=HIGHEST)

    along = [jnp.moveaxis(x, 1, 0) for x in (q, k, v, alpha, beta)]
    _, o = jax.lax.scan(step, jnp.zeros((b, heads, d, d), jnp.float32),
                        tuple(along))
    o = _rms(jnp.moveaxis(o, 0, 1), p["o_norm"]["scale"])
    gate = jax.nn.sigmoid(_mm("ble,ef->blf", h, p["gate_o"]["kernel"], cast))
    return _mm("blf,fe->ble", o.reshape(b, l, inner) * gate,
               p["proj"]["kernel"], cast)


def mla(h, p, cast):
    """[B, L, E] normed state -> latent attention, keys and values expanded
    for every position."""
    b, l, _ = h.shape
    heads, d, _ = p["proj"]["kernel"].shape
    latent = p["kv_a_norm"]["scale"].shape[0]
    rot = p["kv_a"]["kernel"].shape[1] - latent
    q = _mm("ble,ehd->blhd", h, p["q"]["kernel"], cast)
    q_nope, q_rope = q[..., :d], _rope(q[..., d:])
    kva = _mm("ble,ef->blf", h, p["kv_a"]["kernel"], cast)
    c = _rms(kva[..., :latent], p["kv_a_norm"]["scale"])
    k_r = _rope(kva[..., latent:][:, :, None, :])[:, :, 0]
    kv = _mm("blc,chd->blhd", c, p["kv_b"], cast)
    k_nope, v = kv[..., :d], kv[..., d:]
    s = (_mm("bqhd,bkhd->bhqk", q_nope, k_nope, cast)
         + _mm("bqhr,bkr->bhqk", q_rope, k_r, cast)) / math.sqrt(d + rot)
    s = jnp.where(jnp.tril(jnp.ones((l, l), bool)), s, -jnp.inf)
    o = _mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v, cast)
    gate = jax.nn.sigmoid(_mm("ble,eh->blh", h, p["gate"]["kernel"], cast))
    return _mm("bqhd,hde->bqe", o * gate[..., None], p["proj"]["kernel"],
               cast)


def route(h, p, cast):
    """(expert ids [B, L, k], weights [B, L, k]) over ALL the experts."""
    s = jax.nn.sigmoid(_mm("ble,ex->blx", h, p["router"]["kernel"], cast))
    sel = s + _f32(p["router_bias"])
    x = sel.shape[-1]
    groups = sel.reshape(sel.shape[:-1] + (N_GROUP, x // N_GROUP))
    score = jnp.sum(jax.lax.top_k(groups, 2)[0], -1)  # [B, L, G]
    kept = jax.lax.top_k(score, TOPK_GROUP)[1]
    open_ = jnp.any(kept[..., None] == jnp.arange(N_GROUP), -2)  # [B, L, G]
    sel = jnp.where(jnp.repeat(open_, x // N_GROUP, -1), sel, -jnp.inf)
    ids = jax.lax.top_k(sel, TOP_K)[1]
    w = jnp.take_along_axis(s, ids, -1)
    return ids, ROUTED_SCALE * w / jnp.sum(w, -1, keepdims=True)


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
    for i in range(sum(1 for k in params if k.startswith("block"))):
        p = params[f"block{i}"]
        attn = mla if (i + 1) % LAYER_GROUP == 0 else kda
        x = x + attn(_rms(x, p["ln1"]["scale"]), p["attn"], cast)
        h = _rms(x, p["ln2"]["scale"])
        x = x + (moe(h, p["moe"], cast) if "moe" in p
                 else dense_mlp(h, p, cast))
    return _rms(x, params["ln_f"]["scale"])


def logits(params, tokens, cast=None):
    return _mm("ble,ev->blv", hidden(params, tokens, cast),
               params["lm_head"]["kernel"], cast)
