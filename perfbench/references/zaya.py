"""ZAYA1 (Zyphra; `config.json` of Zyphra/ZAYA1-8B, ``model_type: "zaya"``;
attention: Compressed Convolutional Attention, arXiv:2510.04476; router and
residual scaling: the ZAYA1 report, arXiv:2511.17127), as held without a
network. E the model's width, H query heads, H_kv narrow heads, G = H / H_kv,
D the head size, R the router's width, X experts of F features:

    x_0 = wte[tokens]                  logits = RMSNorm(x_N) wte^T   (tied)
    layer l:  x <- RS1(x, CCA(RMSNorm(x)))
              m, r_l = MoE(RMSNorm(x), r_{l-1});  x <- RS2(x, m)     r_{-1} = 0
    RS(x, f) = (s_x * x + b_x) + (s_f * f + b_f)

    CCA(h):  u_t = h_t Wqk  [(H + H_kv) D]     vv_t = h_t Wv  [H_kv D]
             c1_t = a0 * u_{t-1} + a1 * u_t + beta1            (depthwise)
             c2_t = c1_{t-1} B0 + c1_t B1 + beta2   (one D x D block a head)
             qt = u[:H D], kt = u[H D:] as heads
             mq[h] = (qt[h] + kt[h // G]) / 2
             mk[g] = (mean_{h in g} qt[h] + kt[g]) / 2
             q = c2[:H D] + mq      k = c2[H D:] + mk
             q <- sqrt(D) q / |q|   k <- tau_g sqrt(D) k / |k|     (a head)
             RoPE on the first ``rotary_share`` of every head's dims
             v_t = [vv_t[:H_kv D / 2] ; vv_{t-1}[H_kv D / 2:]] as H_kv heads
             o = softmax(q k^T / sqrt(D) + causal) v, head h reads h // G
             CCA = o Wo;   everything before position 0 is zero

    MoE(h, r_prev):  r = h Wd + gamma * r_prev
                     z = gelu(gelu(RMSNorm(r) W1) W2) W3   [R -> R -> R -> X]
                     p = softmax(z);  e = argmax(p + b)
                     m = p_e * (silu(h Wgate_e) * (h Wup_e)) Wdown_e

Assumed (not pinned by a key of ``config.json``; the configuration's file
lists them): RS's four vectors, which value half is shifted and that the
halves fill the narrow heads in order, the convolutions' biases, gamma as
one learned scalar a layer, the router MLP's depth, its tanh-form GELU and
where its norm sits, the bias b entering the choice only, no skip choice
among the router's outputs (the "MoD" of the family's description has no
key), RMSNorm's epsilon inside the router.

Departures, the program's own, relabellings of weights that random weights
do not see: Wqk and Wv are columns of one matrix ``qkv``; Wgate and Wup sit
side by side in ``w_gate_up``; RoPE rotates interleaved pairs (2i, 2i+1)
where the published ``rotate_half`` pairs (i, i + D/2).

This file routes from its own float32 arithmetic, runs EVERY expert over
every token and keeps the chosen one's output by a mask, holds no cache and
groups nothing. ``cast`` is applied to both operands of every matrix
product (None: the reference proper; a lower precision: the control), or,
with ``scope="experts"``, of the expert layer's products alone (the router's
and the experts'; ``perfbench/controls/zaya_lean.py`` reads that control).
A weight becomes float32 where it is used, and the head runs in blocks of the
vocabulary written into one buffer, so float32 copies of the 537M-parameter
embedding or of a layer's experts never exist whole.

**The router's draw.** With every leaf at N(0, 0.02) the X expert logits
are flat (their spread is a few thousandths) and bfloat16 picks other
experts than float32 for most tokens, which no limit can tell from an
error. ``param_rule`` draws the router's matrices at fan-in scale (``Wd``
at 1 / sqrt(E); ``W1``, ``W2`` at ``ROUTER_GAIN`` / sqrt(R): the tanh GELU
of a unit normal has an r.m.s. of 0.65, the gain gives it back; ``W3`` at
``W3_STD``), with every column of ``W1``..``W3`` centred to sum to zero, so
that the GELU's mean, the same for every token, gives no expert a head
start (uncentred, one expert takes a quarter to a half of the tokens). Over
random tokens at the published router widths (2048 -> 256 -> 16; a CPU
count) the expert logits then have a standard deviation of 2.0 about their
mean, the chosen expert's probability averages 0.45, a tick of 128 tokens
hits all 16 experts with the fullest at 1.8 times an even share, and the
two best logits lie within 0.05 of each other for 4% of the tokens of a
layer and within 0.02 for 1.7%: those are the tokens whose expert a rounding
of the stream can flip. The configuration's file repeats this
(``router_draw``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perfbench.harness.weights import seeded_tree

HIGHEST = jax.lax.Precision.HIGHEST
ROUTER_GAIN = 1.6
W3_STD = 0.143
EXPERT_SCALE = 0.25

#: what the parameter tree does not hold; the published values, which
#: ``configure`` replaces with the configuration's as it is run
ROPE_THETA = 5e6
ROTARY_SHARE = 0.5
RMS_EPS = 1e-5


def configure(program: dict) -> None:
    """RoPE's base and share and the norms' epsilon, from a configuration's
    ``program`` block."""
    global ROPE_THETA, ROTARY_SHARE, RMS_EPS
    ROPE_THETA = float(program["rope_theta"])
    ROTARY_SHARE = float(program["rotary_share"])
    RMS_EPS = float(program["norm_eps"])


def param_rule(names, shape):
    leaf = names[-2] if names[-1] in ("kernel", "embedding") else names[-1]
    if leaf == "router_down":
        return lambda n: n / math.sqrt(shape[0])
    if leaf in ("router_w1", "router_w2", "router_w3"):
        # columns that sum to zero: the GELU's common mean, the same for
        # every token, then gives no expert a head start (a trained
        # router is balanced by its loss; a raw draw sends a third of the
        # tokens to one expert)
        std = W3_STD if leaf == "router_w3" else (
            ROUTER_GAIN / math.sqrt(shape[0]))
        return lambda n: std * (n - jnp.mean(n, 0, keepdims=True))
    if leaf == "f_scale" and names[-2] == "rs2":
        return lambda n: EXPERT_SCALE + 0.02 * n
    if leaf in ("scale", "x_scale", "f_scale", "k_temp", "router_mix"):
        return lambda n: 1.0 + 0.02 * n
    if leaf == "conv1_kernel":  # taps that pass the latent on: a0, a1 near 1/2
        return lambda n: 0.5 + 0.02 * n
    if leaf == "conv2_kernel":  # fan-in 2 D: a block keeps its input's size
        return lambda n: n / math.sqrt(2 * shape[-1])
    return lambda n: 0.02 * n


def init_params(seed: int, shapes, dtype=None):
    """The tree filled from the seed, a layer at a time (a layer's 207.6M
    normals are 0.83 GB of float32 beside the weights they make; all
    4.7e9 at once would not fit). Every layer has the same shapes, so one
    compiled filler serves them all."""
    base = (int(seed) & 0xFFFFFFFFFFFF) * 4099

    def part(i, tree):
        return seeded_tree(base + i, tree, param_rule, dtype)

    blocks = sorted((k for k in shapes if k.startswith("block")),
                    key=lambda k: int(k[5:]))
    out = part(0, {k: v for k, v in shapes.items() if k not in blocks})
    out.update({k: part(1 + i, shapes[k]) for i, k in enumerate(blocks)})
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


def _rope(x):
    """[B, L, H, D] at positions 0..L-1: pairs (2i, 2i+1) of the first
    ``ROTARY_SHARE`` of the dims turn, the rest pass."""
    l, d = x.shape[1], x.shape[-1]
    rot = int(ROTARY_SHARE * d)
    freq = ROPE_THETA ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(l, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0:rot:2], x[..., 1:rot:2]
    turned = jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                       -1).reshape(x.shape[:-1] + (rot,))
    return jnp.concatenate([turned, x[..., rot:]], -1)


def _before(x):
    """x_{t-1} along the sequence axis, zero before position 0."""
    return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + 1e-12)


def _rs(x, f, p):
    return ((_f32(p["x_scale"]) * x + _f32(p["x_bias"]))
            + (_f32(p["f_scale"]) * f + _f32(p["f_bias"])))


def cca(h, p, cast):
    """[B, L, E] normed state -> the attention sublayer's output."""
    b, l, _ = h.shape
    heads, d, _ = p["proj"]["kernel"].shape
    h_kv = p["k_temp"].shape[0]
    g, latent, half = heads // h_kv, (heads + h_kv) * d, h_kv * d // 2
    proj = _mm("ble,ef->blf", h, p["qkv"]["kernel"], cast)
    u, vv = proj[..., :latent], proj[..., latent:]
    a, conv2 = _f32(p["conv1_kernel"]), p["conv2_kernel"]
    c1 = a[0] * _before(u) + a[1] * u + _f32(p["conv1_bias"])
    as_heads = (b, l, heads + h_kv, d)
    c2 = (_mm("blgk,gkd->blgd", _before(c1).reshape(as_heads), conv2[:, 0],
              cast)
          + _mm("blgk,gkd->blgd", c1.reshape(as_heads), conv2[:, 1], cast)
          + _f32(p["conv2_bias"]).reshape(heads + h_kv, d))
    qt = u[..., :heads * d].reshape(b, l, h_kv, g, d)
    kt = u[..., heads * d:].reshape(b, l, h_kv, d)
    mq = ((qt + kt[:, :, :, None]) / 2).reshape(b, l, heads, d)
    mk = (jnp.mean(qt, 3) + kt) / 2
    q = _rope(_unit(c2[:, :, :heads] + mq) * math.sqrt(d))
    k = _rope(_unit(c2[:, :, heads:] + mk) * math.sqrt(d)
              * _f32(p["k_temp"])[:, None])
    v = jnp.concatenate([vv[..., :half], _before(vv[..., half:])],
                        -1).reshape(b, l, h_kv, d)
    s = _mm("bqhgd,bkhd->bhgqk", q.reshape(b, l, h_kv, g, d), k,
            cast) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((l, l), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = _mm("bhgqk,bkhd->bqhgd", jax.nn.softmax(s, -1), v, cast)
    return _mm("bqhd,hde->bqe", o.reshape(b, l, heads, d),
               p["proj"]["kernel"], cast)


def route(h, r_prev, p, cast):
    """(probabilities [B, L, X], chosen expert [B, L], router state)."""
    r = _mm("ble,er->blr", h, p["router_down"]["kernel"], cast)
    if r_prev is not None:
        r = r + _f32(p["router_mix"])[0] * r_prev
    z = _rms(r, p["router_norm"]["scale"])
    for name in ("router_w1", "router_w2"):
        z = jax.nn.gelu(_mm("blr,rs->bls", z, p[name]["kernel"], cast),
                        approximate=True)
    z = _mm("blr,rx->blx", z, p["router_w3"]["kernel"], cast)
    probs = jax.nn.softmax(z, -1)
    return probs, jnp.argmax(probs + _f32(p["router_bias"]), -1), r


def experts(h, probs, choice, p, cast):
    """Every expert over every token; the chosen one's output, weighted by
    its probability, kept by a mask."""
    f = p["w_down"].shape[1]

    def one(acc, expert):
        i, w_in, w_down = expert
        gu = _mm("ble,ef->blf", h, w_in, cast)
        y = _mm("blf,fe->ble", jax.nn.silu(gu[..., :f]) * gu[..., f:],
                w_down, cast)
        keep = (choice == i)[..., None] * probs[..., i][..., None]
        return acc + keep * y, None

    n = p["w_down"].shape[0]
    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (jnp.arange(n), p["w_gate_up"], p["w_down"]))
    return out


def moe(h, r_prev, p, cast):
    probs, choice, r = route(h, r_prev, p, cast)
    return experts(h, probs, choice, p, cast), r


def _elsewhere(cast, scope):
    """The cast of the products outside the expert layer."""
    if scope not in ("all", "experts"):
        raise ValueError(f"scope {scope!r} must be 'all' or 'experts'")
    return cast if scope == "all" else None


def walk(params, tokens, cast=None, scope="all"):
    """[B, L] token ids -> (the final normed state [B, L, E]; the routers'
    MARGINS [layers, B, L]: by how much the chosen expert's score
    ``p + b`` leads the next one's. A token whose margin is a few
    hundredths in some layer is one whose expert a lower precision's
    rounding can flip)."""
    attn_cast = _elsewhere(cast, scope)
    x = _f32(params["wte"]["embedding"][tokens])
    r, margins = None, []
    for i in range(sum(1 for k in params if k.startswith("block"))):
        p = params[f"block{i}"]
        x = _rs(x, cca(_rms(x, p["ln1"]["scale"]), p["attn"], attn_cast),
                p["rs1"])
        h = _rms(x, p["ln2"]["scale"])
        probs, choice, r = route(h, r, p["moe"], cast)
        best = jax.lax.top_k(probs + _f32(p["moe"]["router_bias"]), 2)[0]
        margins.append(best[..., 0] - best[..., 1])
        x = _rs(x, experts(h, probs, choice, p["moe"], cast), p["rs2"])
    return _rms(x, params["ln_f"]["scale"]), jnp.stack(margins)


def hidden(params, tokens, cast=None, scope="all"):
    """[B, L] token ids -> the final normed state [B, L, E]."""
    return walk(params, tokens, cast, scope)[0]


def _vocab_block(v: int, most: int = 16384) -> int:
    """The largest divisor of ``v`` that is at most ``most``."""
    return max(c for c in range(1, min(v, most) + 1) if v % c == 0)


def logits(params, tokens, cast=None, scope="all"):
    z = hidden(params, tokens, cast, scope)
    cast = _elsewhere(cast, scope)
    wte = params["wte"]["embedding"]
    v, e = wte.shape
    c = _vocab_block(v)

    def one(i, out):
        rows = jax.lax.dynamic_slice(wte, (i * c, 0), (c, e))
        return jax.lax.dynamic_update_slice(
            out, _mm("ble,ve->blv", z, rows, cast), (0, 0, i * c))

    return jax.lax.fori_loop(
        0, v // c, one, jnp.zeros(z.shape[:2] + (v,), jnp.float32))
