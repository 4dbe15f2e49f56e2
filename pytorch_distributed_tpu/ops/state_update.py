"""A decode tick's update of the delta rule's state, a Pallas TPU kernel.

The delta-rule layers (``models/transformer.py``: ``KDAttention``,
``GatedDeltaNet``) keep one float32 ``[D, D]`` matrix a head a slot in a
cache leaf ``[n_slots + 1, H, D, D]``, and a paged decode tick moves every
live slot's matrices one token on. Spelled in ``jax.numpy``
(``delta_rule_update``: the specification, and what every other backend and
program runs) XLA compiles three passes over the leaf: a fusion reads the
old state for the two reductions, a second reads it again and writes the new
one. Here a grid step (lane, head block) holds a few heads' matrices in VMEM
(``head_block``), reads them from HBM once and writes them once WHERE THEY
LIE (``input_output_aliases``: the leaf is updated in place and nothing
copies it), and the token's output row leaves lane-dense.

The sums are ``delta_rule_update``'s with the decay taken into the state
first, float32 multiplies and sums on the vector unit::

    T = Diag(a) S;  seen = k^T T;  read = q^T T
    S' = T + k (beta (v - seen))^T
    o  = read + (v - seen) (beta k . q)

Both reductions run over the ROWS of the old decayed state. What multiplies
a row of a head's matrix (``k``, ``q``, a decay a channel) is a COLUMN
vector of it: these arrive lane-dense (``[heads, D]`` blocks) and are turned
inside the kernel (one transpose of a ``[heads, D]`` tile a grid step),
never as ``[..., D, 1]`` blocks whose DMA would pad one value to 128 lanes.
The decay is a channel's (``[B, H, D]``: ``KDAttention``) or a head's (``[B,
H, 1]``: ``GatedDeltaNet``), observed from the operand's shape; a decay a
head is the same in every lane of its row and scales from a row.

What ``_SlotStateAttention._held`` does around the ``jax.numpy`` update is
the kernel's too, by two flags a lane (scalar prefetch): a ``fresh`` lane
(position 0) starts from zeros by a SELECT, whatever the row held (never a
multiply by zero: the row may hold anything), and a lane that is not
``live`` keeps the bits it held (its blocks are copied through) and reads
zeros. Rows of the leaf behind the lanes (the trash row of padding jobs) are
outside the grid and keep theirs.

``head_block`` is the ONE place the heads of a grid step are decided, from
the static shapes alone; no config field, constructor argument, flag or
environment variable names it (``heads=`` is for kernel-level callers: tests
and the chip sweep). Mamba-2's update (``ssm_update``) has no kernel here:
its reductions run over the state's LANES, and a body of this shape read 21%
slower on the chip than XLA's fusions (PERF.md section 6, PR 46 / PR 47).
Forward only, as ``ops/grouped_matmul.py``: nothing differentiates a decode
tick, and a ``pallas_call`` has no transpose rule.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: least bytes of state a grid step moves each way. On the chip at ``[257,
#: 32, 128, 128]`` (PERF.md section 6, PR 47) 16 and 32 heads a step read
#: what a body that only copies its blocks through reads (1.70 ms a layer,
#: 630 GB/s: the pipeline's DMAs bound it, not the vector unit), 8 heads
#: 5-10% more; 16 takes half the VMEM of 32
STEP_BYTES = 1 << 20
#: a block of fewer heads than all of them is whole sublane tiles of the
#: float32 operand rows
HEAD_ALIGN = 8


def head_block(heads: int, head_bytes: int) -> int:
    """Heads of one grid step for a layer of ``heads`` heads whose state is
    ``head_bytes`` a head: as many as move ``STEP_BYTES`` (in whole
    ``HEAD_ALIGN``s), or all of them where there are no more."""
    block = -(-STEP_BYTES // head_bytes)
    block = -(-block // HEAD_ALIGN) * HEAD_ALIGN
    return heads if heads <= block else block


def _body(fresh, live, q_ref, k_ref, v_ref, a_ref, b_ref, s_ref, s_out,
          o_out, *, channel: bool):
    """One (lane, head block) step: ``q``, ``k``, ``v``, the decay and beta
    ``[heads, D]`` (a decay a head and beta the same in every lane), the
    state ``[heads, D, D]`` in and out, the output ``[heads, D]``."""
    lane = pl.program_id(0)
    alive, start = live[lane] != 0, fresh[lane] != 0

    @pl.when(jnp.logical_not(alive))
    def _():
        s_out[...] = s_ref[...]
        o_out[...] = jnp.zeros_like(o_out)

    @pl.when(alive)
    def _():
        q, k, a, beta = q_ref[...], k_ref[...], a_ref[...], b_ref[...]
        # the rows' vectors, turned to columns: [D, heads]
        kt, qt = k.T, q.T
        at = a.T if channel else None
        wq = jnp.sum(beta * k * q, axis=-1, keepdims=True)  # [heads, 1]
        for i in range(s_ref.shape[0]):
            kc = kt[:, i:i + 1]
            # the decayed state, from zeros where the lane is fresh
            s = (at[:, i:i + 1] if channel else a[i:i + 1]) * jnp.where(
                start, 0.0, s_ref[i])  # [D, D]
            seen = jnp.sum(kc * s, axis=0, keepdims=True)
            read = jnp.sum(qt[:, i:i + 1] * s, axis=0, keepdims=True)
            new = v_ref[i:i + 1, :] - seen  # [1, D]
            s_out[i] = s + kc * (beta[i:i + 1] * new)
            o_out[i:i + 1, :] = read + new * wq[i:i + 1]


def delta_rule_tick(state, q, k, v, a, beta, *, fresh, live,
                    heads: Optional[int] = None):
    """``models.transformer.delta_rule_update`` for a paged decode tick, on
    the cache leaf: ``state`` ``[S, H, D, D]`` float32 (lane ``i`` is row
    ``i``; rows behind the ``B <= S`` lanes are not touched), ``q``, ``k``,
    ``v`` ``[B, H, D]``, the decay ``a`` ``[B, H, D]`` a channel or ``[B, H,
    1]`` a head (observed), ``beta`` ``[B, H]``, ``fresh`` and ``live``
    ``[B]`` bool. Returns (the leaf updated in place, ``o`` ``[B, H, D]``; a
    lane that is not live reads zeros)."""
    b, h, d = q.shape
    if state.ndim != 4 or state.shape[0] < b or state.shape[1:] != (h, d, d):
        raise ValueError(
            f"the state leaf must be [rows >= {b}, {h}, {d}, {d}], got "
            f"{state.shape}")
    if a.shape not in ((b, h, d), (b, h, 1)):
        raise ValueError(
            f"the decay must be a channel's [{b}, {h}, {d}] or a head's "
            f"[{b}, {h}, 1], got {a.shape}")
    if any(t.dtype != jnp.float32 for t in (state, q, k, v, a, beta)):
        raise ValueError("the state and its operands are float32")
    hb = heads or head_block(h, d * d * 4)
    interpret = jax.default_backend() != "tpu"

    def by_heads(*trailing):
        return lambda lane, block, fresh, live: (lane, block) + trailing

    row_spec = pl.BlockSpec((None, hb, d), by_heads(0))
    state_spec = pl.BlockSpec((None, hb, d, d), by_heads(0, 0))
    kwargs = {}
    if not interpret:
        # two buffers of the state each way, and room for the operands'
        # and the compiler's own
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=4 * hb * d * d * 4 + (16 << 20))
    return pl.pallas_call(
        functools.partial(_body, channel=a.shape[-1] != 1),
        out_shape=(jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((b, h, d), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, -(-h // hb)),
            in_specs=[row_spec] * 5 + [state_spec],
            out_specs=(state_spec, row_spec),
        ),
        # the leaf is the last operand, behind the two flags and five rows
        input_output_aliases={7: 0},
        cost_estimate=pl.CostEstimate(
            flops=8 * b * h * d * d, transcendentals=0,
            bytes_accessed=2 * b * h * d * d * 4),
        interpret=interpret,
        name="slot_state_update",
        **kwargs,
    )(fresh.astype(jnp.int32), live.astype(jnp.int32), q, k, v,
      jnp.broadcast_to(a, q.shape),
      jnp.broadcast_to(beta[..., None], q.shape), state)
