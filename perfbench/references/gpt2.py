"""GPT-2 (Radford et al. 2019; `config.json` of openai-community/gpt2-*):
pre-LN decoder blocks, learned positions, GELU (tanh form), LayerNorm.

Departures from the published description, all the program's own and
listed in the configuration file: the output head is a matrix of its own
(not tied to the token embedding), the attention output projection and
the MLP's down projection carry no bias, and LayerNorm's epsilon is 1e-6.

Parameters come in the program's tree layout (the benchmark fills it from
the seed): ``qkv.kernel [E, 3, H, D]``, ``proj.kernel [H, D, E]``.
``cast`` is applied to both operands of every matrix multiplication: None
for the reference proper, a lower precision for the control.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.harness.weights import seeded_tree

LN_EPS = 1e-6
HIGHEST = jax.lax.Precision.HIGHEST


def param_rule(names, shape):
    if names[-1] == "scale":
        return lambda n: 1.0 + 0.02 * n
    return lambda n: 0.02 * n


def init_params(seed: int, shapes, dtype=None):
    return seeded_tree(seed, shapes, param_rule, dtype)


def _mm(spec, a, b, cast):
    if cast is not None:
        a, b = cast(a), cast(b)
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def _ln(x, p):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + LN_EPS)
            * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32))


def hidden(params, tokens, cast=None):
    """[B, L] token ids -> final-LayerNorm hidden states [B, L, E]."""
    f32 = jnp.float32
    _, l = tokens.shape
    x = (params["wte"]["embedding"].astype(f32)[tokens]
         + params["wpe"]["embedding"].astype(f32)[:l][None])
    n_layer = sum(1 for k in params if k.startswith("block"))
    causal = jnp.tril(jnp.ones((l, l), bool))
    for i in range(n_layer):
        p = params[f"block{i}"]
        h = _ln(x, p["ln1"])
        qkv = (_mm("ble,ekhd->blkhd", h, p["attn"]["qkv"]["kernel"], cast)
               + p["attn"]["qkv"]["bias"].astype(f32))
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        d = q.shape[-1]
        s = _mm("bqhd,bkhd->bhqk", q, k, cast) / jnp.sqrt(f32(d))
        s = jnp.where(causal[None, None], s, -jnp.inf)
        a = _mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v, cast)
        x = x + _mm("bqhd,hde->bqe", a, p["attn"]["proj"]["kernel"], cast)
        h = _ln(x, p["ln2"])
        h = (_mm("ble,ef->blf", h, p["mlp_up"]["kernel"], cast)
             + p["mlp_up"]["bias"].astype(f32))
        h = jax.nn.gelu(h, approximate=True)
        x = x + _mm("blf,fe->ble", h, p["mlp_down"]["kernel"], cast)
    return _ln(x, params["ln_f"])


def logits(params, tokens, cast=None):
    return _mm("ble,ev->blv", hidden(params, tokens, cast),
               params["lm_head"]["kernel"], cast)


def loss_sum(params, aux, batch, cast=None):
    """Summed next-token cross-entropy over the weighted positions of one
    block of rows, the number of positions, and the (unchanged) aux."""
    lg = logits(params, batch["tokens"], cast)
    logp = jax.nn.log_softmax(lg, -1)
    nll = -jnp.take_along_axis(logp, batch["labels"][..., None], -1)[..., 0]
    w = batch["weights"].astype(jnp.float32)
    return jnp.sum(nll * w), jnp.sum(w), aux
