"""Qwen3-Next-80B-A3B-Instruct (Qwen; ``config.json`` of
Qwen/Qwen3-Next-80B-A3B-Instruct, ``model_type: "qwen3_next"``), one chip's
share of one pipeline stage, as held without a network. Its three kinds of
sublayer are published mechanisms: Gated DeltaNet (arXiv:2412.06464) behind
one short convolution, softmax attention over grouped K/V heads with a norm a
head on q and k, partial RoPE and an elementwise output gate, and a plain
softmax top-k router with one gated shared expert. E the model's width;
H_v state heads served by H_k q/k heads of D (the linear layers); H query
heads over H_kv K/V heads of A (the full layers), the first R of A rotated;
X experts of F features of which this chip holds ``[lo, hi)``, k a token:

    x_0 = wte[tokens]        logits = n(x_N) W_head        (untied, a slice)
    layer l:  x <- x + Attn_l(n(x));   x <- x + MoE_l(n(x))
    n(x) = x / rms(x) * (1 + w)        (zero-centred weights; eps 1e-6)
    Attn_l is Full where (l + 1) % full_attention_interval == 0, GDN otherwise

    GDN(h):  [q~ | k~ | v~ | z] = h W_qkvz     [H_k D, H_k D, H_v D, H_v D]
             [b | a] = h W_ba                  [H_v, H_v]
             [q | k | v]_t = SiLU(sum_{j=0..T-1} w_j * [q~|k~|v~]_{t-(T-1)+j})
                   (ONE depthwise convolution over the concatenated
                   channels, T taps, zeros before position 0, no bias)
             q^ = q / |q| * D^-1/2,  k^ = k / |k|     (a head; key head j
                   serves state heads j H_v/H_k ... (j + 1) H_v/H_k - 1)
             beta_t = sigmoid(b_t)
             alpha_t = exp(-exp(A_log_h) * softplus(a_t + dt_bias_h))
             S_t = alpha_t S_{t-1}
             S_t <- S_t + beta_t k^_t (v_t - S_t^T k^_t)^T,   S_0 = 0
                   [D x D a state head, float32]
             o_t = S_t^T q^_t
             GDN = [RMSNorm_head(o_t) * SiLU(z_t)] W_o

    Full(h): [q | g]_head = h W_q   [H x 2A: a head's columns are its query
                   and its gate];   k, v = h W_k, h W_v   [H_kv A each]
             q, k RMS-normed a head (1 + w, one scale of A for every head);
             RoPE (pairs (2i, 2i+1), theta) on the first R dims of q and k
             s = q . k / sqrt(A), query heads jG ... (j + 1)G - 1 on K/V
                   head j (G = H / H_kv), causal
             Full = [softmax(s) v * sigmoid(g)] W_o

    MoE(h):  p = softmax(h W_r)  [X], float32;  the k largest
             w_i = p_i / sum_sel p_j
             MoE = sum_{i in sel, lo <= i < hi} w_i E_i(h)
                   + sigmoid(h w_s) E_sh(h)
             E(h) = (SiLU(h W_gate) * (h W_up)) W_down

``MoE`` is THIS CHIP's part of the routed sum (the router scores all X; the
experts outside ``[lo, hi)`` are the other chip's) plus the gated shared
expert. The multi-token-prediction module follows the last published layer,
which another stage holds: left out.

Assumed (wiring that no key of ``config.json`` settles; the configuration's
file lists each): the zero-centred norm weights (the tree's ``scale`` leaves
hold ``1 + w``; the norm a head behind the delta rule has a plain weight,
which the same leaf holds); the order ``[q|k|v|z]`` and ``[b|a]`` inside the
fused projections; which state heads a key head serves; no bias on the
convolution or anywhere else; the gate as the second half of ``W_q`` a head;
``A_log`` and ``dt_bias`` one scalar a state head; the L2 norms' epsilon
1e-6 under the root.

Departures, the program's own, relabellings of weights that random weights
do not see: K and V of a full layer are one matrix ``kv``; an expert's
W_gate and W_up sit side by side (``w_gate_up``, ``shared_gate_up``); RoPE
turns the pairs (2i, 2i+1) of a head's first R dims where the published
code turns (i, i + R/2): a permutation of q's and k's columns and of the
two norms' scales.

This file holds no cache, runs the delta rule a POSITION at a time, runs
the full layer dense and causal (a block of query rows at a time, so that
4,864 positions fit beside the weights), routes from its own float32
arithmetic and runs every held expert over every token, keeping each
token's weight by a mask. ``cast`` is applied to both operands of every
matrix product (None: the reference proper; a lower precision: the
control). A weight becomes float32 where it is used.

**The draw.** Every matrix at N(0, 0.02), scales at 1 + N(0, 0.02),
``dt_bias`` at -4 + N(0, 1) and ``A_log`` at N(0, 0.02) (alpha between 0.93
and 0.995 a head: a memory of 14 to 200 tokens; a state that forgot at once
would hide a state read from the wrong slot), the convolution's taps at 1/T
+ N(0, 0.1). **The router's draw** (``router_draw`` in the configuration's
file) is made for an EVEN load and a decisive choice, because a trained
router is balanced by its loss and a random one is not: every expert's
column of its matrix is drawn at N(0, 1) and scaled to the length
ROUTER_SPREAD, so that a normed token's logits have standard deviation
ROUTER_SPREAD for every expert alike (3: a token's first expert then carries
two fifths of its weight and its tenth a fortieth, and a rounding that swaps
the tenth for the eleventh moves little; a softmax does not see a shift
common to the experts, so nothing is centred across them); and the stream's
COMMON component is taken out of it. The stream has one: SiLU behind the
convolution gives v a mean, the state and the full layer's average carry it
to every token alike, and a router column that leans along it is chosen by
every token of a tick or by none (what left 20-27 of ling's 128 held experts
without a pair). ``init_params`` therefore runs the stack once over
``PROBE_TOKENS`` seeded ids, layer by layer, takes the mean ``m`` of the
normed tokens that layer's router sees, and replaces its matrix ``W`` by
``(I - m m^T / |m|^2) W``: no expert is favoured by what all tokens share.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.harness.weights import seed_key, seeded_tree

HIGHEST = jax.lax.Precision.HIGHEST

#: what the parameter tree does not hold; the published values, which
#: ``configure`` replaces with the configuration's as it is run
ROPE_THETA = 1e7
ROTARY_SHARE = 0.25
RMS_EPS = 1e-6
LAYER_GROUP = 4
TOP_K = 10
HELD_FROM = 0
L2_EPS = 1e-6
#: standard deviation of a normed token's router logits
ROUTER_SPREAD = 3.0
#: ids the router's probe runs over (``init_params``)
PROBE_TOKENS = 1024
#: query rows the full layer scores at a time
QUERY_BLOCK = 1024


def configure(program: dict) -> None:
    """What a ``program`` block says beside the tree's shapes."""
    global ROPE_THETA, ROTARY_SHARE, RMS_EPS, LAYER_GROUP, TOP_K, HELD_FROM
    ROPE_THETA = float(program["rope_theta"])
    ROTARY_SHARE = float(program["rotary_share"])
    RMS_EPS = float(program["norm_eps"])
    LAYER_GROUP = int(program["layer_group_size"])
    TOP_K = int(program["moe_top_k"])
    held = program.get("experts_held")
    HELD_FROM = int(held[0]) if held else 0


def param_rule(names, shape):
    leaf = names[-2] if names[-1] in ("kernel", "embedding") else names[-1]
    if leaf == "scale":
        return lambda n: 1.0 + 0.02 * n
    if leaf == "dt_bias":
        return lambda n: -4.0 + n
    if leaf == "conv_kernel":
        return lambda n: 1.0 / shape[0] + 0.1 * n
    if leaf == "router":  # [E, X]: an expert's column, all of one length
        return lambda n: ROUTER_SPREAD * n * jax.lax.rsqrt(
            jnp.sum(jnp.square(n), axis=0, keepdims=True))
    return lambda n: 0.02 * n


def init_params(seed: int, shapes, dtype=None):
    """The tree filled from the seed, a few leaves at a time (an expert
    layer's two stacks of matrices are drawn each by itself: 805M normals
    are 3.2 GB of float32 beside the weights they make), then every
    router's matrix cleared of the stream's common component
    (``balance_routers``). Blocks of one kind share a compiled filler."""
    base = (int(seed) & 0xFFFFFFFFFFFF) * 4099

    def part(i, tree):
        return seeded_tree(base + i, tree, param_rule, dtype)

    blocks = sorted((k for k in shapes if k.startswith("block")),
                    key=lambda k: int(k[5:]))
    out = part(0, {k: v for k, v in shapes.items() if k not in blocks})
    for i, k in enumerate(blocks):
        block = dict(shapes[k])
        moe = dict(block.pop("moe"))
        stacks = {n: moe.pop(n) for n in ("w_gate_up", "w_down")}
        filled = part(8 * i + 1, block)
        filled["moe"] = part(8 * i + 2, moe)
        for j, (n, leaf) in enumerate(sorted(stacks.items())):
            filled["moe"][n] = part(8 * i + 3 + j, {n: leaf})[n]
        out[k] = filled
    return balance_routers(out, seed)


def balance_routers(params, seed: int):
    """``params`` with every router's matrix ``W`` [E, X] replaced by ``(I -
    m m^T / |m|^2) W``, ``m`` the mean over ``PROBE_TOKENS`` seeded ids of
    the normed tokens that router sees, the layers below it already
    cleared. One compiled step a kind of layer."""
    vocab = params["wte"]["embedding"].shape[0]
    tokens = jax.random.randint(
        jax.random.fold_in(seed_key(seed), 0x5EED), (1, PROBE_TOKENS), 1,
        vocab)

    def step(kind):
        def run(x, p):
            x = x + kind(_rms(x, p["ln1"]["scale"]), p["attn"], None)
            h = _rms(x, p["ln2"]["scale"])
            m = jnp.mean(h, axis=(0, 1))
            w = _f32(p["moe"]["router"]["kernel"])
            w = (w - jnp.outer(m, jnp.dot(m, w, precision=HIGHEST))
                 / jnp.dot(m, m)).astype(p["moe"]["router"]["kernel"].dtype)
            moe_p = dict(p["moe"], router={"kernel": w})
            return x + moe(h, moe_p, None), w

        return jax.jit(run)

    steps = {full_attention: step(full_attention), gdn: step(gdn)}
    out = dict(params)
    with jax.default_matmul_precision("highest"):
        x = _f32(params["wte"]["embedding"][tokens])
        for i in range(_layers(params)):
            p = params[f"block{i}"]
            x, w = steps[_kind(i)](x, p)
            out[f"block{i}"] = dict(p, moe=dict(p["moe"],
                                                router={"kernel": w}))
    return out


def _layers(params) -> int:
    return sum(1 for k in params if k.startswith("block"))


def _kind(layer: int):
    return full_attention if (layer + 1) % LAYER_GROUP == 0 else gdn


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
    """[B, L, H, A] at positions 0..L-1: every pair (2i, 2i+1) of the first
    ``ROTARY_SHARE`` of a head turns, the rest pass."""
    l, r = x.shape[1], int(ROTARY_SHARE * x.shape[-1])
    freq = ROPE_THETA ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(l, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0:r:2], x[..., 1:r:2]
    turned = jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                       -1).reshape(x.shape[:-1] + (r,))
    return jnp.concatenate([turned, x[..., r:]], -1)


def _swiglu(h, w_in, w_down, cast):
    f = w_down.shape[0]
    gu = _mm("ble,ef->blf", h, w_in, cast)
    return _mm("blf,fe->ble", jax.nn.silu(gu[..., :f]) * gu[..., f:], w_down,
               cast)


def gdn(h, p, cast):
    """[B, L, E] normed state -> the gated delta rule's output: the
    recurrence a position at a time from a zero state."""
    b, l, _ = h.shape
    hv = p["A_log"].shape[0]
    d = p["o_norm"]["scale"].shape[0]
    values = hv * d
    w = _f32(p["conv_kernel"])  # [taps, 2 keys + values]
    taps, keys = w.shape[0], (w.shape[1] - values) // 2
    hk = keys // d
    qkvz = _mm("ble,ef->blf", h, p["qkvz"]["kernel"], cast)
    pre = jnp.pad(qkvz[..., :2 * keys + values],
                  ((0, 0), (taps - 1, 0), (0, 0)))
    z = qkvz[..., 2 * keys + values:]
    qkv = jax.nn.silu(sum(w[j] * pre[:, j:j + l] for j in range(taps)))
    q = qkv[..., :keys].reshape(b, l, hk, d)
    k = qkv[..., keys:2 * keys].reshape(b, l, hk, d)
    v = qkv[..., 2 * keys:].reshape(b, l, hv, d)
    q = jnp.repeat(_unit(q) * d ** -0.5, hv // hk, axis=2)
    k = jnp.repeat(_unit(k), hv // hk, axis=2)
    ba = _mm("ble,eh->blh", h, p["ba"]["kernel"], cast)
    beta = jax.nn.sigmoid(ba[..., :hv])
    alpha = jnp.exp(-jnp.exp(_f32(p["A_log"])) * jax.nn.softplus(
        ba[..., hv:] + _f32(p["dt_bias"])))

    def step(s, t):
        q_t, k_t, v_t, a_t, b_t = t  # [B, H, D], alpha and beta [B, H]
        s = a_t[..., None, None] * s
        seen = jnp.einsum("bhk,bhkv->bhv", k_t, s, precision=HIGHEST)
        s = s + jnp.einsum("bhk,bhv->bhkv", b_t[..., None] * k_t, v_t - seen,
                           precision=HIGHEST)
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision=HIGHEST)

    along = [jnp.moveaxis(x, 1, 0) for x in (q, k, v, alpha, beta)]
    _, o = jax.lax.scan(step, jnp.zeros((b, hv, d, d), jnp.float32),
                        tuple(along))
    o = _rms(jnp.moveaxis(o, 0, 1), p["o_norm"]["scale"])
    return _mm("blf,fe->ble", o.reshape(b, l, values) * jax.nn.silu(z),
               p["proj"]["kernel"], cast)


def full_attention(h, p, cast):
    """[B, L, E] normed state -> gated softmax attention over grouped K/V
    heads, dense and causal, ``QUERY_BLOCK`` query rows at a time."""
    b, l, _ = h.shape
    heads, a, _ = p["proj"]["kernel"].shape
    kv_heads = p["kv"]["kernel"].shape[2]
    group = heads // kv_heads
    qg = _mm("ble,ehd->blhd", h, p["q"]["kernel"], cast)
    q, gate = qg[..., :a], qg[..., a:]
    kv = _mm("ble,ethd->blthd", h, p["kv"]["kernel"], cast)
    q = _rope(_rms(q, p["q_norm"]["scale"]))
    k = _rope(_rms(kv[:, :, 0], p["k_norm"]["scale"]))
    v = kv[:, :, 1]
    q = q.reshape(b, l, kv_heads, group, a)
    rows = []
    for at in range(0, l, QUERY_BLOCK):
        q_rows = q[:, at:at + QUERY_BLOCK]
        s = _mm("bqhgd,bkhd->bhgqk", q_rows, k, cast) * a ** -0.5
        seen = (jnp.arange(l)[None, :]
                <= (at + jnp.arange(q_rows.shape[1]))[:, None])
        s = jnp.where(seen, s, -jnp.inf)
        rows.append(_mm("bhgqk,bkhd->bqhgd", jax.nn.softmax(s, -1), v, cast))
    o = jnp.concatenate(rows, 1).reshape(b, l, heads, a)
    return _mm("bqhd,hde->bqe", o * jax.nn.sigmoid(gate),
               p["proj"]["kernel"], cast)


def route(h, p, cast):
    """(expert ids [B, L, k], weights [B, L, k]) over ALL the experts."""
    probs = jax.nn.softmax(
        _mm("ble,ex->blx", h, p["router"]["kernel"], cast), -1)
    w, ids = jax.lax.top_k(probs, TOP_K)
    return ids, w / jnp.sum(w, -1, keepdims=True)


def moe(h, p, cast, shared: bool = True):
    """The held experts' part of the routed sum (a dense loop, each token's
    weight kept by a mask) and, with ``shared``, the gated shared expert."""
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
        gate = jax.nn.sigmoid(_mm("ble,eo->blo", h,
                                  p["shared_gate"]["kernel"], cast))
        out = out + gate * _swiglu(h, p["shared_gate_up"]["kernel"],
                                   p["shared_down"]["kernel"], cast)
    return out


def hidden(params, tokens, cast=None):
    """[B, L] token ids -> the final normed state [B, L, E]."""
    x = _f32(params["wte"]["embedding"][tokens])
    for i in range(_layers(params)):
        p = params[f"block{i}"]
        x = x + _kind(i)(_rms(x, p["ln1"]["scale"]), p["attn"], cast)
        x = x + moe(_rms(x, p["ln2"]["scale"]), p["moe"], cast)
    return _rms(x, params["ln_f"]["scale"])


def logits(params, tokens, cast=None):
    return _mm("ble,ev->blv", hidden(params, tokens, cast),
               params["lm_head"]["kernel"], cast)
