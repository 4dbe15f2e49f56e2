"""Ouro (ByteDance, arXiv:2510.25741; `config.json` of ByteDance/Ouro-2.6B,
``model_type: "ouro"``): a looped decoder. N layers run U times a token
over the SAME parameters; pass t attends only to the keys and values that
pass t wrote.

    h = E[x]
    for t in 1..U:
      for l in 1..N:
        a = RMSNorm(h; g1_l)
        q, k, v = a Wq_l, a Wk_l, a Wv_l            (no bias)
        q, k = RoPE(q, k; absolute position, theta)
        o = softmax(q k^T / sqrt(D) + causal) v
        h = h + RMSNorm(o Wo_l; g2_l)               (sandwich norm)
        m = RMSNorm(h; g3_l)
        h = h + RMSNorm((SiLU(m Wg_l) * (m Wu_l)) Wd_l; g4_l)
      h = RMSNorm(h; g_f);  z_t = h                 (the normed state goes on)
      lambda_t = sigmoid(w_e . z_t + b_e)           (exit gate)
    logits = z_U W_head                             (every token runs U passes)

Assumed, because `config.json` does not state it and no network was at
hand (the model's `modeling_ouro.py` and paper as remembered; listed in
the configuration's file too): the sandwich norms g2 and g4, that the one
final norm closes every pass, the gate's form, bias-free projections.

Departures, all the program's own and all a relabelling of weights that
random weights do not see: RoPE rotates interleaved pairs (2i, 2i+1), a
fixed permutation of Wq's and Wk's columns away from the published
``rotate_half`` pairs (i, i + D/2); q, k and v come from one fused matrix.

Parameters come in the program's tree layout (the benchmark fills it from
the seed): ``qkv.kernel [E, 3, H, D]``, ``proj.kernel [H, D, E]``,
``mlp_gate``/``mlp_up`` ``[E, F]``, ``mlp_down [F, E]``, ``exit_gate.kernel
[E, 1]``. ``cast`` is applied to both operands of every matrix
multiplication: None for the reference proper, a lower precision for the
control. Layers are walked one at a time and a weight becomes float32
where it is used, so float32 copies of all the weights never exist at
once. The parameter tree does not hold U and theta: ``configure`` takes
them from the configuration as it is run.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.harness.weights import seeded_tree

RMS_EPS = 1e-6
HIGHEST = jax.lax.Precision.HIGHEST

#: what the parameter tree does not hold; the published values, which
#: ``configure`` replaces with the configuration's as it is run (the CPU
#: rehearsal runs fewer passes)
UT_STEPS = 4
ROPE_THETA = 1e6


def configure(program: dict) -> None:
    """Take passes and RoPE base from a configuration's ``program`` block."""
    global UT_STEPS, ROPE_THETA
    UT_STEPS = int(program["ut_steps"])
    ROPE_THETA = float(program["rope_theta"])


def param_rule(names, shape):
    if names[-1] == "scale":
        return lambda n: 1.0 + 0.02 * n
    return lambda n: 0.02 * n


def init_params(seed: int, shapes, dtype=None):
    """The tree filled from the seed, a layer at a time: one draw of all
    2.7e9 normals would be 10.7 GB of float32 beside the weights it makes
    (and more elements than 32 bits count). Every layer has the same
    shapes, so one compiled filler serves them all."""
    base = (int(seed) & 0xFFFFFFFFFFFF) * 4099

    def part(i, tree):  # a stream of its own for each part of the tree
        return seeded_tree(base + i, tree, param_rule, dtype)

    blocks = sorted((k for k in shapes if k.startswith("block")),
                    key=lambda k: int(k[5:]))
    out = part(0, {k: v for k, v in shapes.items() if k not in blocks})
    out.update({k: part(1 + i, shapes[k]) for i, k in enumerate(blocks)})
    return out


def _mm(spec, a, b, cast):
    if cast is not None:
        a, b = cast(a), cast(b)
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def _rms(x, p):
    return (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                              + RMS_EPS) * p["scale"].astype(jnp.float32))


def _rope(x, theta):
    """[B, L, H, D] rotated at positions 0..L-1, pairs (2i, 2i+1)."""
    l, d = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(l, dtype=jnp.float32)[:, None] * freq  # [L, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     -1).reshape(x.shape)


def _layer(h, p, causal, theta, cast):
    f32 = jnp.float32
    a = _rms(h, p["ln1"])
    qkv = _mm("ble,ekhd->blkhd", a, p["attn"]["qkv"]["kernel"], cast)
    q, k, v = _rope(qkv[:, :, 0], theta), _rope(qkv[:, :, 1], theta), \
        qkv[:, :, 2]
    s = _mm("bqhd,bkhd->bhqk", q, k, cast) / jnp.sqrt(f32(q.shape[-1]))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    o = _mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v, cast)
    h = h + _rms(_mm("bqhd,hde->bqe", o, p["attn"]["proj"]["kernel"], cast),
                 p["ln1_post"])
    m = _rms(h, p["ln2"])
    g = jax.nn.silu(_mm("ble,ef->blf", m, p["mlp_gate"]["kernel"], cast))
    u = _mm("ble,ef->blf", m, p["mlp_up"]["kernel"], cast)
    return h + _rms(_mm("blf,fe->ble", g * u, p["mlp_down"]["kernel"], cast),
                    p["ln2_post"])


def passes(params, tokens, cast=None):
    """[B, L] token ids -> (z [U, B, L, E], the normed state after each
    pass; lambda [U, B, L], each pass's exit gate)."""
    l = tokens.shape[1]
    h = params["wte"]["embedding"][tokens].astype(jnp.float32)
    n_layer = sum(1 for k in params if k.startswith("block"))
    causal = jnp.tril(jnp.ones((l, l), bool))
    gate = params["exit_gate"]

    def one_pass(h, _):
        for i in range(n_layer):
            h = _layer(h, params[f"block{i}"], causal, ROPE_THETA, cast)
        h = _rms(h, params["ln_f"])
        lam = jax.nn.sigmoid(
            _mm("ble,eo->blo", h, gate["kernel"], cast)[..., 0]
            + gate["bias"].astype(jnp.float32)[0])
        return h, (h, lam)

    # the same pass U times: a loop, so that a compiled reference holds
    # the N layers once (it compiles in a quarter of the time)
    _, (zs, lams) = jax.lax.scan(one_pass, h, None, length=UT_STEPS)
    return zs, lams


def hidden(params, tokens, cast=None):
    """[B, L] token ids -> the last pass's normed state [B, L, E]."""
    return passes(params, tokens, cast)[0][-1]


def logits(params, tokens, cast=None):
    return _mm("ble,ev->blv", hidden(params, tokens, cast),
               params["lm_head"]["kernel"], cast)
