"""NVIDIA-Nemotron-3-Nano-30B-A3B (NVIDIA; ``config.json`` of
nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, ``model_type: "nemotron_h"``), one
chip's share of one pipeline stage, as held without a network. Every block is
ONE sublayer behind ONE norm, its kind a letter of ``hybrid_override_pattern``:
a Mamba-2 mixer (arXiv:2405.21060; the hybrid of arXiv:2504.03624), an expert
layer behind a DeepSeek-V3-form sigmoid router, or softmax attention over
grouped K/V heads with NO positional signal. E the model's width; H heads of P
with a state of N a head, in G groups that share B and C (the "M" layers); Q
query heads over K K/V heads of A (the "*" layers); X experts of F features of
which this chip holds ``[lo, hi)``, k a token, a shared expert of F_s:

    x_0 = wte[tokens]        logits = n(x_L) W_head        (untied, a slice)
    block l:  x <- x + f_l(n(x)),   f_l by the pattern's letter
    n(x) = x / rms(x) * w              (a plain weight; eps 1e-5)

    M(u):  [z | xBC | dt] = u W_in            [H P, H P + 2 G N, H]
           xBC'_t = SiLU(b_c + sum_{j=0..T-1} w_j * xBC_{t-(T-1)+j})
                 (depthwise, causal, T taps, zeros before position 0)
           [x | B | C] = xBC'                 x [H, P];  B, C [G, N]
           Delta_t = softplus(dt_t + dt_bias_h)               (a head)
           a_t = exp(-exp(A_log_h) Delta_t)
           S_t = a_t S_{t-1} + Delta_t x_t B_t^T,  S_0 = 0
                 [P x N a head, float32; head h reads group h // (H / G)]
           y_t = S_t C_t + D_h x_t
           M = [w_n * groupRMS(y * SiLU(z))] W_out
                 (the product first, then an RMS norm over each group's
                 H P / G channels, eps 1e-5, one weight a channel)

    *(u):  q = u W_q [Q A];  k, v = u W_k, u W_v [K A each]
           s = q . k / sqrt(A), query head i on K/V head i // (Q / K), causal
           * = [softmax(s) v] W_o      (no rotation, no table, no norm a
                 head, no gate, no window)

    E(u):  s = sigmoid(u W_r)  [X], float32;  chosen: the k largest of s + b_r
           w_i = scale * s_i / (sum_chosen s_j + 1e-20)
           E = sum_{i chosen, lo <= i < hi} w_i F_i(u) + F_sh(u)
           F(u) = relu(u W_up)^2 W_down            (two matrices, no gate)

``E`` is THIS CHIP's part of the routed sum (the router scores all X; the
experts outside ``[lo, hi)`` are the other chip's) plus the shared expert.
"-" in a pattern is a dense ``F`` (the family's other members; not this one).

Assumed (wiring that no key of ``config.json`` settles; the configuration's
file lists each): the inner width is H P (4,096), not ``expand`` times the
hidden size; the gate ``SiLU(z)`` multiplies BEFORE the group norm; the
attention layers rotate nothing although the row carries ``rope_theta``;
``n_group`` 1 / ``topk_group`` 1 mean no group limit; ``chunk_size`` is the
published kernel's blocking and changes no result; the order ``[z | x | B | C
| dt]`` inside ``W_in``; ``time_step_*`` are the init of ``dt_bias`` only.

Departures, the program's own, relabellings of weights that random weights do
not see: K and V of an attention layer are one matrix ``kv``; the group
norm's weight is held ``[G, H P / G]``; the expert stacks ``w_up`` and
``w_down`` are HELD in whole 512-wide tiles of the grouped product
(``models/moe.py::grouped_width``: [X, 3072, 2048] and [X, 2048, 3072] at the
published widths, zeros beyond E and F), of which this file reads the
corner ``[:E, :F]`` alone and ``init_params`` fills no more. The program
leaves the 1e-20 out of the weights' denominator (a sum of k sigmoids is not
within float32's reach of it).

This file holds no cache, runs the recurrence a POSITION at a time in a
``lax.scan``, runs attention dense and causal (a block of query rows at a
time), routes from its own float32 arithmetic and runs every held expert over
every token, keeping each token's weight by a mask. ``cast`` is applied to
both operands of every matrix product (None: the reference proper; a lower
precision: the control). A weight becomes float32 where it is used.

**The draw** (``param_rule``). Every matrix at fan-in scale, N(0, 1 /
fan_in), and the output projections (``W_out``, ``W_o``, ``W_down``, the
shared expert's) scaled by ``1 / sqrt(2 L)`` more (the published
``rescale_prenorm_residual``); the embedding at N(0, 1) a row plus
SHARED_ROW of ONE row that every token shares (the pad id's, which is never
sent: the router's draw below stands on it); norm weights at 1 + N(0, 0.02); ``A_log = log(U[1, 16])``, ``D`` = 1, ``dt_bias`` the inverse
softplus of a log-uniform draw in [0.001, 0.1] floored at 1e-4 (the published
init: heads that forget over tens to thousands of tokens, so a state read
from the wrong slot shows in the logits); the convolution's taps at 1/T +
N(0, 0.1) and its bias at N(0, 0.1). **The router's draw** (``router_draw``
in the configuration's file) is made for an EVEN load and for weights that
FALL OFF, because a trained router is balanced by its bias updates and
scores most experts well under one half, and a random one does neither:
every expert's column of ``W_r`` is drawn at N(0, 1) and scaled to the length
ROUTER_SPREAD, ``b_r`` = 0, and the stream's COMMON component is made every
expert's alike: ``init_params`` runs the stack once over ``PROBE_TOKENS``
seeded ids, block by block, takes the mean ``m`` of the normed tokens each
router sees, and replaces its matrix ``W`` by ``(I - m m^T / |m|^2) W -
ROUTER_OFFSET m 1^T / |m|^2``: no expert is favoured by what all tokens
share (``relu(.)^2`` and ``SiLU`` give every sublayer's output a mean, which
every token carries alike), and that share takes ROUTER_OFFSET off every
expert's logit. The choice does not see a shift common to the experts; the
WEIGHTS do. With logits of standard deviation 3 about zero the six chosen
scores all lie at 0.99, each chosen expert carries 2.5 / 6 of a token's
routed weight, and a bfloat16 rounding that swaps the sixth for the seventh
moves a logit as far as the float8 control does (20 sound readings on the
chip 0.28-0.85, the control's four 0.84-0.94, my chip runs, PR 44: no limit
lies between). At spread 5 about -14 the chosen logits lie at -1 to -5, the
scores fall off as ``e^z`` does, the first chosen expert carries two thirds
of a token's weight and the sixth a hundredth (sound 0.02-0.12, control
0.39-0.82). The offset stands on ``m``, which has to be well defined in every
block: a token's share of it wobbles by ROUTER_OFFSET / |m|, and after the
first Mamba-2 block alone |m| is 3 of a token's 52 (a reduced probe on the
CPU), so SHARED_ROW of one embedding row is every token's (|m| 8-12, the
wobble 1-2). The whole row (SHARED_ROW 1) was read first: the head then
gives every position the same few favourites, greedy streams collapse onto
them, every lane of a tick routes alike (``experts_hit_share`` 36.7%,
``expert_load_peak_ratio`` 36, the rate up to 8% off by seed); at 0.15 the
load is even (50.0%, 3.5).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perfbench.harness.weights import seed_key, seeded_tree

HIGHEST = jax.lax.Precision.HIGHEST

#: what the parameter tree does not hold; the published values, which
#: ``configure`` replaces with the configuration's as it is run
PATTERN = "MEMEM*EME"
RMS_EPS = 1e-5
TOP_K = 6
ROUTED_SCALE = 2.5
HELD_FROM = 0
EMBED = 2688
MOE_DIM = 1856
#: standard deviation of a normed token's router logits
ROUTER_SPREAD = 5.0
#: what the stream's common component takes off EVERY expert's logit
ROUTER_OFFSET = 14.0
#: how much of one shared row every token's embedding carries
SHARED_ROW = 0.15
#: ids the router's probe runs over (``init_params``)
PROBE_TOKENS = 1024
#: query rows an attention layer scores at a time
QUERY_BLOCK = 1024


def configure(program: dict) -> None:
    """What a ``program`` block says beside the tree's shapes."""
    global PATTERN, RMS_EPS, TOP_K, ROUTED_SCALE, HELD_FROM, EMBED, MOE_DIM
    EMBED, MOE_DIM = int(program["embed_dim"]), int(program["moe_dim"] or 0)
    PATTERN = str(program["layer_pattern"])
    RMS_EPS = float(program["norm_eps"])
    TOP_K = int(program["moe_top_k"])
    ROUTED_SCALE = float(program["moe_routed_scale"])
    held = program.get("experts_held")
    HELD_FROM = int(held[0]) if held else 0


def _uniform(n):
    """A standard normal draw's place in (0, 1)."""
    return jax.scipy.stats.norm.cdf(n)


def param_rule(names, shape):
    leaf = names[-2] if names[-1] in ("kernel", "embedding") else names[-1]
    out_scale = 1.0 / math.sqrt(2 * len(PATTERN))
    if leaf == "scale":
        return lambda n: 1.0 + 0.02 * n
    if leaf == "wte":  # row 0 (the pad id, never sent) is every row's share
        return lambda n: n + SHARED_ROW * n[:1]
    if leaf == "A_log":
        return lambda n: jnp.log(1.0 + 15.0 * _uniform(n))
    if leaf == "D":
        return lambda n: jnp.ones_like(n)
    if leaf == "dt_bias":
        def dt_bias(n):
            dt = jnp.maximum(jnp.exp(
                math.log(1e-3) + _uniform(n) * math.log(1e-1 / 1e-3)), 1e-4)
            return dt + jnp.log(-jnp.expm1(-dt))  # softplus(.) = dt
        return dt_bias
    if leaf == "conv_kernel":
        return lambda n: 1.0 / shape[0] + 0.1 * n
    if leaf == "conv_bias":
        return lambda n: 0.1 * n
    if leaf == "router_bias":
        return jnp.zeros_like
    if leaf == "router":  # [E, X]: an expert's column, all of one length
        return lambda n: ROUTER_SPREAD * n * jax.lax.rsqrt(
            jnp.sum(jnp.square(n), axis=0, keepdims=True))
    if leaf in ("w_up", "w_down"):
        # [experts, in, out], HELD in whole tiles of the grouped product:
        # the model's own widths in the corner, zeros beyond them
        fan_in, fan_out = ((EMBED, MOE_DIM) if leaf == "w_up"
                           else (MOE_DIM, EMBED))
        std = fan_in ** -0.5 * (out_scale if leaf == "w_down" else 1.0)
        return lambda n: jnp.where(
            (jnp.arange(shape[1]) < fan_in)[:, None]
            & (jnp.arange(shape[2]) < fan_out)[None, :], std * n, 0.0)
    if leaf == "proj":  # [H P, E] or [Q, A, E]: every axis but the last
        return lambda n: out_scale * math.prod(shape[:-1]) ** -0.5 * n
    if leaf in ("shared_down", "mlp_down"):
        return lambda n: out_scale * shape[0] ** -0.5 * n
    return lambda n: shape[0] ** -0.5 * n  # [E, ...]: fan-in E


def init_params(seed: int, shapes, dtype=None):
    """The tree filled from the seed, a block at a time (an expert layer's
    two stacks of matrices are drawn each by itself: 319M normals are 1.3 GB
    of float32 beside the weights they make), then every router's matrix
    cleared of the stream's common component (``balance_routers``). Blocks
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
        stacks = {n: moe_shapes.pop(n) for n in ("w_up", "w_down")}
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
    blocks below it already cleared: what all tokens share moves every
    expert's logit alike, by ``-ROUTER_OFFSET`` in the mean. One compiled
    step a kind of block."""
    vocab = params["wte"]["embedding"].shape[0]
    tokens = jax.random.randint(
        jax.random.fold_in(seed_key(seed), 0x5EED), (1, PROBE_TOKENS), 1,
        vocab)

    def cleared(x, p):
        h = _rms(x, p["ln1"]["scale"])
        m = jnp.mean(h, axis=(0, 1))
        w = _f32(p["moe"]["router"]["kernel"])
        lean = jnp.dot(m, w, precision=HIGHEST)  # an expert's own, [X]
        w = (w - jnp.outer(m, lean + ROUTER_OFFSET) / jnp.dot(m, m)).astype(
            p["moe"]["router"]["kernel"].dtype)
        return x + moe(h, dict(p["moe"], router={"kernel": w}), None), w

    steps = {letter: jax.jit(lambda x, p, f=f: (
        x + f(_rms(x, p["ln1"]["scale"]), p, None), None))
        for letter, f in SUBLAYERS.items()}
    steps["E"] = jax.jit(cleared)
    out = dict(params)
    with jax.default_matmul_precision("highest"):
        x = _f32(params["wte"]["embedding"][tokens])
        for i, letter in enumerate(PATTERN[:_layers(params)]):
            p = params[f"block{i}"]
            x, w = steps[letter](x, p)
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


def _relu2(h, w_up, w_down, cast):
    up = _mm("ble,ef->blf", h, w_up, cast)
    return _mm("blf,fe->ble", jnp.square(jax.nn.relu(up)), w_down, cast)


def mamba2(h, p, cast):
    """[B, L, E] normed state -> the Mamba-2 mixer's output: the recurrence
    a position at a time from a zero state."""
    b, l, _ = h.shape
    heads = p["A_log"].shape[0]
    groups, per_group = p["o_norm"]["scale"].shape
    inner = groups * per_group
    width = inner // heads
    w = _f32(p["conv_kernel"])  # [taps, inner + 2 G N]
    taps, n = w.shape[0], (w.shape[1] - inner) // (2 * groups)
    zxbcdt = _mm("ble,ef->blf", h, p["in_proj"]["kernel"], cast)
    z = zxbcdt[..., :inner]
    pre = jnp.pad(zxbcdt[..., inner:inner + w.shape[1]],
                  ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(_f32(p["conv_bias"])
                      + sum(w[j] * pre[:, j:j + l] for j in range(taps)))
    x = xbc[..., :inner].reshape(b, l, heads, width)
    share = heads // groups  # head h reads group h // share
    bm = jnp.repeat(xbc[..., inner:inner + groups * n].reshape(
        b, l, groups, n), share, axis=2)
    cm = jnp.repeat(xbc[..., inner + groups * n:].reshape(
        b, l, groups, n), share, axis=2)
    delta = jax.nn.softplus(zxbcdt[..., inner + w.shape[1]:]
                            + _f32(p["dt_bias"]))  # [B, L, H]
    decay = jnp.exp(-jnp.exp(_f32(p["A_log"])) * delta)

    def step(s, t):
        x_t, b_t, c_t, a_t, d_t = t  # [B, H, P], [B, H, N] x 2, [B, H] x 2
        s = a_t[..., None, None] * s + (
            (d_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        return s, jnp.einsum("bhpn,bhn->bhp", s, c_t, precision=HIGHEST)

    along = [jnp.moveaxis(t, 1, 0) for t in (x, bm, cm, decay, delta)]
    _, y = jax.lax.scan(step, jnp.zeros((b, heads, width, n), jnp.float32),
                        tuple(along))
    y = jnp.moveaxis(y, 0, 1) + _f32(p["D"])[:, None] * x
    y = (y.reshape(b, l, inner) * jax.nn.silu(z)).reshape(
        b, l, groups, per_group)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                          + RMS_EPS) * _f32(p["o_norm"]["scale"])
    return _mm("blf,fe->ble", y.reshape(b, l, inner), p["proj"]["kernel"],
               cast)


def attention(h, p, cast):
    """[B, L, E] normed state -> softmax attention over grouped K/V heads,
    dense and causal, position-free, ``QUERY_BLOCK`` query rows at a time."""
    b, l, _ = h.shape
    heads, a, _ = p["proj"]["kernel"].shape
    kv_heads = p["kv"]["kernel"].shape[2]
    group = heads // kv_heads
    q = _mm("ble,ehd->blhd", h, p["q"]["kernel"], cast)
    kv = _mm("ble,ethd->blthd", h, p["kv"]["kernel"], cast)
    k, v = kv[:, :, 0], kv[:, :, 1]
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
    return _mm("bqhd,hde->bqe", o, p["proj"]["kernel"], cast)


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

    e, f = h.shape[-1], MOE_DIM  # the model's own widths, whatever is held

    def one(acc, expert):
        i, w_up, w_down = expert
        mine = jnp.sum(jnp.where(ids == i, w, 0.0), -1)[..., None]
        return acc + mine * _relu2(h, w_up[:e, :f], w_down[:f, :e],
                                   cast), None

    n = p["w_down"].shape[0]
    out, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (HELD_FROM + jnp.arange(n), p["w_up"], p["w_down"]))
    if shared:
        out = out + _relu2(h, p["shared_up"]["kernel"],
                           p["shared_down"]["kernel"], cast)
    return out


#: a block's one sublayer by the pattern's letter: (normed state, the
#: block's parameters, cast) -> what joins the stream
SUBLAYERS = {
    "M": lambda h, p, cast: mamba2(h, p["attn"], cast),
    "*": lambda h, p, cast: attention(h, p["attn"], cast),
    "E": lambda h, p, cast: moe(h, p["moe"], cast),
    "-": lambda h, p, cast: _relu2(h, p["mlp_up"]["kernel"],
                                   p["mlp_down"]["kernel"], cast),
}


def hidden(params, tokens, cast=None):
    """[B, L] token ids -> the final normed state [B, L, E]."""
    x = _f32(params["wte"]["embedding"][tokens])
    for i, letter in enumerate(PATTERN[:_layers(params)]):
        p = params[f"block{i}"]
        x = x + SUBLAYERS[letter](_rms(x, p["ln1"]["scale"]), p, cast)
    return _rms(x, params["ln_f"]["scale"])


def logits(params, tokens, cast=None):
    return _mm("ble,ev->blv", hidden(params, tokens, cast),
               params["lm_head"]["kernel"], cast)
