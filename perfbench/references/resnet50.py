"""ResNet-50 (He et al. 2015) as `torchvision.models.resnet50()` builds it
(v1.5: the stride sits on the 3x3 convolution), NHWC, training mode:
BatchNorm normalises with the statistics of the rows it is given, which is
one replica's share of the batch under DistributedDataParallel.

Parameters come in the program's tree layout: ``conv_init``, ``bn_init``,
``stage{i}_block{j}/{Conv_0,BatchNorm_0,...,downsample_conv,
downsample_bn}``, ``fc``. ``cast`` is applied to both operands of every
convolution and of the classifier's matrix multiplication.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perfbench.harness.weights import seeded_tree

BN_EPS = 1e-5
BN_MOMENTUM = 0.9  # flax convention: new = 0.9 * old + 0.1 * batch
HIGHEST = jax.lax.Precision.HIGHEST
MEAN = (0.485, 0.456, 0.406)
STD = (0.229, 0.224, 0.225)


def param_rule(names, shape):
    if names[-1] == "scale":
        return lambda n: 1.0 + 0.1 * n
    if names[-1] == "bias":
        return lambda n: 0.1 * n
    if len(shape) == 4:  # HWIO convolution: He, fan-out
        std = math.sqrt(2.0 / (shape[0] * shape[1] * shape[3]))
        return lambda n: std * n
    return lambda n: 0.01 * n


def init_params(seed: int, shapes, dtype=None):
    return seeded_tree(seed, shapes, param_rule, dtype)


def _conv(x, w, stride, pad, cast):
    if cast is not None:
        x, w = cast(x), cast(w)
    return jax.lax.conv_general_dilated(
        x.astype(jnp.float32), w.astype(jnp.float32), (stride, stride),
        [(pad, pad), (pad, pad)], dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=HIGHEST)


def _bn(x, p, stats, new_stats, name):
    mean = jnp.mean(x, (0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), (0, 1, 2))
    old = stats[name]
    new_stats[name] = {
        "mean": BN_MOMENTUM * old["mean"] + (1 - BN_MOMENTUM) * mean,
        "var": BN_MOMENTUM * old["var"] + (1 - BN_MOMENTUM) * var,
    }
    return ((x - mean) * jax.lax.rsqrt(var + BN_EPS)
            * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32))


def _bottleneck(x, p, stats, stride, cast):
    new = {}
    y = _conv(x, p["Conv_0"]["kernel"], 1, 0, cast)
    y = jax.nn.relu(_bn(y, p["BatchNorm_0"], stats, new, "BatchNorm_0"))
    y = _conv(y, p["Conv_1"]["kernel"], stride, 1, cast)
    y = jax.nn.relu(_bn(y, p["BatchNorm_1"], stats, new, "BatchNorm_1"))
    y = _conv(y, p["Conv_2"]["kernel"], 1, 0, cast)
    y = _bn(y, p["BatchNorm_2"], stats, new, "BatchNorm_2")
    if "downsample_conv" in p:
        x = _conv(x, p["downsample_conv"]["kernel"], stride, 0, cast)
        x = _bn(x, p["downsample_bn"], stats, new, "downsample_bn")
    return jax.nn.relu(x + y), new


def forward(params, stats, image, cast=None):
    """uint8 or float [N, H, W, 3] -> (logits [N, classes], new stats)."""
    x = image
    if x.dtype == jnp.uint8:
        x = (x.astype(jnp.float32) / 255.0 - jnp.array(MEAN)) / jnp.array(STD)
    new = {}
    x = _conv(x, params["conv_init"]["kernel"], 2, 3, cast)
    x = jax.nn.relu(_bn(x, params["bn_init"], stats, new, "bn_init"))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), [(0, 0), (1, 1), (1, 1), (0, 0)])
    stage = 1
    while f"stage{stage}_block1" in params:
        block = 1
        while f"stage{stage}_block{block}" in params:
            name = f"stage{stage}_block{block}"
            stride = 2 if (stage > 1 and block == 1) else 1
            # recompute inside a block in the backward pass: float32
            # activations of 128 rows would not fit beside the weights
            x, new[name] = jax.checkpoint(
                lambda x, p, s, stride=stride: _bottleneck(x, p, s, stride,
                                                           cast)
            )(x, params[name], stats[name])
            block += 1
        stage += 1
    x = jnp.mean(x, (1, 2))
    w, b = params["fc"]["kernel"], params["fc"]["bias"]
    if cast is not None:
        x, w = cast(x), cast(w)
    out = jnp.matmul(x, w.astype(jnp.float32), precision=HIGHEST)
    return out + b.astype(jnp.float32), new


def loss_sum(params, aux, batch, cast=None):
    """Summed cross-entropy of one replica's rows, the row count, and the
    running statistics after them."""
    lg, new_stats = forward(params, aux, batch["image"], cast)
    logp = jax.nn.log_softmax(lg, -1)
    nll = -jnp.take_along_axis(logp, batch["label"][:, None], -1)[:, 0]
    return jnp.sum(nll), jnp.float32(nll.shape[0]), new_stats
