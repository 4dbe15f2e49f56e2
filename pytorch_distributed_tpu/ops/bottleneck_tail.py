"""Pallas kernels for the fused bottleneck expand tail.

The ResNet bottleneck's expand tail — ``relu(bn(conv1x1(z, w)) + r)`` with
moment-derived batch stats (models/resnet.py `_fused_expand_tail`) — is
HBM-bandwidth-bound, and the v5e profiler trace shows XLA running its
reductions as separate ``convert_reduce_fusion`` kernels that each re-read
a wide tensor (17 ms/step across the ResNet-50 train step). These kernels
accumulate every reduction in VMEM **in the same pass** as the matmul or
elementwise work that already touches the tensor:

- ``moments(z)``: one read of z produces Σz AND zᵀz (XLA: a dot plus a
  separate reduce — two reads).
- ``tail_bwd_reduce(z, g, out)``: one read of (z, g, out) produces the
  masked gradient ``gp`` (written once — it IS the residual branch's
  gradient), the weight-gradient/BN-reduction carrier ``P = zᵀ gp``, and
  ``Σgp`` (XLA: materialize gp, then two more full reads).
- ``tail_bwd_dz(gp, z, wa, c, dmn)``: ``dz = gp·wa + z·c + dmn`` — two MXU
  matmuls and the broadcast merged into one output write (XLA: two conv
  kernels each materializing a [*, F] temporary, then an add fusion).

All kernels grid over the batch dim with full-spatial blocks (ResNet-50's
largest row is ~1.6 MB — VMEM-comfortable), accumulate in fp32, and run
in interpret mode off-TPU so CPU tests execute the same code path.
"""

# jaxlint: disable-file=precision-cast -- Pallas reduction scratch accumulates in fp32 regardless of io dtype; the casts feed those accumulators (burned down from the lint baseline, PR 9)

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _interpret() -> bool:
    # Mosaic needs the interpreter on ANY non-TPU backend, not just CPU
    return jax.default_backend() != "tpu"


def _row(ref):
    """Load a [1, h, w, C] block as [h*w, C]."""
    v = ref[0]
    return v.reshape(v.shape[0] * v.shape[1], v.shape[2])


def _moments_kernel(z_ref, s_ref, m2_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        s_ref[:] = jnp.zeros_like(s_ref)
        m2_ref[:] = jnp.zeros_like(m2_ref)

    z = _row(z_ref)
    s_ref[:] = s_ref[:] + jnp.sum(z.astype(jnp.float32), axis=0,
                                  keepdims=True)
    m2_ref[:] = m2_ref[:] + jax.lax.dot_general(
        z, z, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


@jax.jit
def moments(z: jax.Array):
    """``(Σz, zᵀz)`` over (B,H,W) of NHWC ``z``, one pass, fp32."""
    b, h, w, f = z.shape
    s, m2 = pl.pallas_call(
        _moments_kernel,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, h, w, f), lambda i: (i, 0, 0, 0))],
        out_specs=[
            pl.BlockSpec((1, f), lambda i: (0, 0)),
            pl.BlockSpec((f, f), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, f), jnp.float32),
            jax.ShapeDtypeStruct((f, f), jnp.float32),
        ],
        interpret=_interpret(),
        name="bottleneck_moments",
    )(z)
    return s[0], m2


def _bwd_reduce_kernel(z_ref, g_ref, out_ref, gp_ref, p_ref, sb_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        p_ref[:] = jnp.zeros_like(p_ref)
        sb_ref[:] = jnp.zeros_like(sb_ref)

    g = g_ref[0]
    # compare in fp32: Mosaic (v5e) rejects bf16 vector comparisons
    gp = jnp.where(out_ref[0].astype(jnp.float32) > 0, g, jnp.zeros_like(g))
    gp_ref[0] = gp
    gpf = gp.reshape(gp.shape[0] * gp.shape[1], gp.shape[2])
    p_ref[:] = p_ref[:] + jax.lax.dot_general(
        _row(z_ref), gpf, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    sb_ref[:] = sb_ref[:] + jnp.sum(gpf.astype(jnp.float32), axis=0,
                                    keepdims=True)


@jax.jit
def tail_bwd_reduce(z: jax.Array, g: jax.Array, out: jax.Array):
    """One pass over (z, g, out): returns ``(gp, P, Σgp)`` where
    ``gp = g·[out>0]`` (the relu-masked gradient, = the residual grad),
    ``P = zᵀgp`` [F,E] fp32, ``Σgp`` [E] fp32."""
    b, h, w, f = z.shape
    e = g.shape[-1]
    gp, p, sb = pl.pallas_call(
        _bwd_reduce_kernel,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, w, f), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, h, w, e), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, h, w, e), lambda i: (i, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, h, w, e), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((f, e), lambda i: (0, 0)),
            pl.BlockSpec((1, e), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(g.shape, g.dtype),
            jax.ShapeDtypeStruct((f, e), jnp.float32),
            jax.ShapeDtypeStruct((1, e), jnp.float32),
        ],
        interpret=_interpret(),
        name="bottleneck_bwd_reduce",
    )(z, g, out)
    return gp, p, sb[0]


def _bwd_dz_kernel(gp_ref, z_ref, wa_ref, c_ref, dmn_ref, dz_ref):
    acc = jax.lax.dot_general(
        _row(gp_ref), wa_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc = acc + jax.lax.dot_general(
        _row(z_ref), c_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc = acc + dmn_ref[:]
    sh = dz_ref.shape
    dz_ref[0] = acc.astype(dz_ref.dtype).reshape(sh[1], sh[2], sh[3])


@jax.jit
def tail_bwd_dz(gp: jax.Array, z: jax.Array, wa: jax.Array, c: jax.Array,
                dmn: jax.Array):
    """``dz = gp @ wa + z @ c + dmn`` in one output write.

    ``wa = diag(a)·wᵀ`` [E,F] carries the conv backward, ``c = 2·dM`` [F,F]
    the moment path, ``dmn = dm/n`` [1,F] the mean path."""
    b, h, w, f = z.shape
    e = gp.shape[-1]
    return pl.pallas_call(
        _bwd_dz_kernel,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, w, e), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, h, w, f), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((e, f), lambda i: (0, 0)),
            pl.BlockSpec((f, f), lambda i: (0, 0)),
            pl.BlockSpec((1, f), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, w, f), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(z.shape, z.dtype),
        interpret=_interpret(),
        name="bottleneck_bwd_dz",
    )(gp, z, wa, c, dmn)
