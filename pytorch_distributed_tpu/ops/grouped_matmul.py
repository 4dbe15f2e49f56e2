"""Grouped matrix product for the dropless experts, a Pallas TPU kernel.

``grouped_matmul(lhs [M, K], rhs [G, K, N], sizes [G]) -> [M, N]`` is the
contract ``jax.lax.ragged_dot`` has in ``models/moe.py::DroplessMoE``: the
rows of ``lhs`` are sorted by group, group ``g`` owns the ``sizes[g]`` rows
behind those of the groups before it and is multiplied by ``rhs[g]``; the
rows behind the last group belong to none.

Why a kernel of the repo's own: on a TPU XLA lowers ``ragged_dot`` to a
kernel of 512 x 512 x 512 tiles that visits a row tile once for every group
that touches it and multiplies the WHOLE 512-row tile each time. A decode
tick's groups hold 5 to 12 rows, so 97% of the matrix unit's work fell on
rows the store mask threw away and the product ran at a third of the rate
its bytes allow (PERF.md section 6, PR 45). Here the row tile fits a group
(``row_tile``) and a grid step fetches the group's whole matrix, so the
product is what it should be on this chip: the stream of the hit experts'
matrices.

The design is ``jax.experimental.pallas.ops.tpu.megablox.gmm``'s, cut to
what the experts need (no sharded groups, no transposed or accumulated
output, no split of the contracted or the output width: on the chip a
group's whole matrix a step read fastest or within 1.5% of it at every
shape the serving cells have, PERF.md section 6, PR 45) with its own visit
metadata:

- the grid is the VISITS: a visit is one (group, row tile) pair that share
  a row; ``visit_metadata`` lists them in row order from ``sizes`` and the
  kernel reads the list through scalar prefetch: a visit's row tile places
  the ``lhs`` and output blocks, its group the ``[K, N]`` block of ``rhs``.
  The grid is as long as the visits there ARE (a dynamic bound): a group
  without a row is not visited, so its matrix is not read, and a row tile
  behind the last group is not visited either. A group that runs on into
  the next row tile keeps its matrix in VMEM (the same block twice).
- a visit's product is one float32 ``[tm, N]``, cast once; it stores only
  its group's rows (a select against what the output block holds:
  consecutive visits of one row tile keep the block in VMEM).
- rows of no group are never written: they hold whatever the memory held,
  not zeros and not promised to be numbers. A caller selects them away.

``row_tile(m)`` is the ONE place the tile is decided, from the static shape
alone; no config field, constructor argument, flag or environment variable
names it (``tm=`` is for kernel-level callers: tests and the chip sweep).
Forward only: nothing differentiates ``DroplessMoE``, and a ``pallas_call``
has no transpose rule, so a gradient through this function raises;
``jax.lax.ragged_dot`` is the differentiable spelling.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows a grid step multiplies: the matrix unit's own height, and about the
#: rows a busy expert takes in a chunk program (a tick's groups hold 5 to 12)
ROW_TILE = 128
#: a row tile of fewer rows is whole sublane tiles of the narrowest operand
#: dtype the experts use (bfloat16 packs 16 rows a register)
ROW_ALIGN = 16
#: most bytes of one group's ``[K, N]`` matrix: two of them are in VMEM at a
#: time, the one the step multiplies and the next one's DMA, of the 128 MiB
#: a v5e core has. Every expert matrix of the serving cells fits (1 to 16
#: MiB); a larger one is refused until a configuration brings it, and the
#: split of its widths with it
RHS_BLOCK_BYTES = 16 << 20


def row_tile(m: int) -> int:
    """Rows of one grid step for a product of ``m`` rows: ``ROW_TILE``, or
    all the rows (in whole ``ROW_ALIGN``s) where there are fewer."""
    return min(ROW_TILE, -(-m // ROW_ALIGN) * ROW_ALIGN)


def visit_metadata(sizes: jax.Array, m: int, tm: int):
    """What the kernel's index maps read, from ``sizes`` ``[G]`` int32 for
    ``m`` rows (whole tiles of ``tm``): ``(offsets [G + 1], group_ids [V],
    tile_ids [V], visits)``. Group ``g`` owns rows ``[offsets[g], offsets[g
    + 1])``; visit ``v < visits`` multiplies row tile ``tile_ids[v]`` by
    group ``group_ids[v]``; ``V = m // tm + G - 1`` bounds ``visits`` (a
    group adds at most one visit to the tiles' own). Visits run in row
    order, so the visits of one row tile are consecutive."""
    g = sizes.shape[0]
    ends = jnp.minimum(jnp.cumsum(sizes.astype(jnp.int32)), m)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends[:-1]])
    first = starts // tm  # the row tile a group starts in
    spans = jnp.where(ends > starts, (ends - 1) // tm - first + 1, 0)
    visit_ends = jnp.cumsum(spans)
    v = jnp.arange(m // tm + g - 1, dtype=jnp.int32)
    # a visit's group: the groups whose visits all lie before it
    group_ids = jnp.minimum(
        jnp.sum(visit_ends[None, :] <= v[:, None], axis=1, dtype=jnp.int32),
        g - 1)
    # its row tile: the group's first, and one more a visit since the
    # group's first visit
    tile_ids = jnp.clip((first - (visit_ends - spans))[group_ids] + v,
                        0, m // tm - 1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, group_ids, tile_ids, visit_ends[-1]


def _kernel(offsets, group_ids, tile_ids, lhs_ref, rhs_ref, out_ref, *,
            tm: int):
    v = pl.program_id(0)
    prod = jax.lax.dot_general(
        lhs_ref[...], rhs_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    group = group_ids[v]
    rows = tile_ids[v] * tm + jax.lax.broadcasted_iota(
        jnp.int32, prod.shape, 0)
    mine = (rows >= offsets[group]) & (rows < offsets[group + 1])
    kept = out_ref[...].astype(jnp.float32)  # jaxlint: disable=precision-cast -- the select runs in the sums' float32 (a v5e's vector unit has no bfloat16 select); the other groups' rows round-trip exactly
    out_ref[...] = jnp.where(mine, prod, kept).astype(out_ref.dtype)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, sizes: jax.Array, *,
                   tm: Optional[int] = None) -> jax.Array:
    """``out[offsets[g]:offsets[g + 1]] = lhs[offsets[g]:offsets[g + 1]] @
    rhs[g]`` for every group ``g`` with a row (module docstring). On a TPU
    the compiled kernel; on any other backend the Pallas interpreter.

    Args:
      lhs: ``[M, K]``, rows sorted by group; the rows behind
        ``sum(sizes)`` are read by no sum that is stored.
      rhs: ``[G, K, N]`` of ``lhs``'s dtype (bfloat16 in the serving
        cells; float32 for the tests' toys), a matrix of at most
        ``RHS_BLOCK_BYTES``.
      sizes: ``[G]`` int32, ``sum(sizes) <= M``.
      tm: the row tile, a multiple of ``ROW_ALIGN``; None asks
        ``row_tile``.

    Returns ``[M, N]`` of ``lhs``'s dtype, float32 sums cast once; rows of
    no group are uninitialised.
    """
    if lhs.ndim != 2 or rhs.ndim != 3 or lhs.shape[1] != rhs.shape[1]:
        raise ValueError(
            f"grouped_matmul takes lhs [M, K] and rhs [G, K, N], got "
            f"{lhs.shape} and {rhs.shape}")
    if lhs.dtype != rhs.dtype:
        raise ValueError(
            f"lhs and rhs must share a dtype, got {lhs.dtype} and "
            f"{rhs.dtype}")
    if sizes.shape != rhs.shape[:1]:
        raise ValueError(
            f"sizes must be [{rhs.shape[0]}], a size a group, got "
            f"{sizes.shape}")
    m, k = lhs.shape
    n = rhs.shape[2]
    if k * n * rhs.dtype.itemsize > RHS_BLOCK_BYTES:
        raise ValueError(
            f"a group's matrix of {k} x {n} {rhs.dtype} is over the "
            f"{RHS_BLOCK_BYTES >> 20} MiB a grid step holds whole; "
            "grouped_matmul splits neither width")
    tm = tm or row_tile(m)
    rows = -(-m // tm) * tm
    if rows != m:
        # rows behind the last group, which no visit stores
        lhs = jnp.pad(lhs, ((0, rows - m), (0, 0)))
    out = _grouped_matmul(lhs, rhs, sizes, tm=tm,
                          interpret=jax.default_backend() != "tpu")
    return out[:m] if rows != m else out


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def _grouped_matmul(lhs, rhs, sizes, *, tm: int, interpret: bool):
    m, k = lhs.shape
    g, _, n = rhs.shape
    offsets, group_ids, tile_ids, visits = visit_metadata(sizes, m, tm)
    item = lhs.dtype.itemsize
    kwargs = {}
    if not interpret:
        # two buffers an operand and the output, the float32 product and
        # the select's copy of it, and room for the compiler's own
        need = 2 * (tm * k + k * n + tm * n) * item + 2 * tm * n * 4
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=need + (8 << 20))
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(visits,),
            in_specs=[
                pl.BlockSpec((tm, k), lambda v, offsets, group_ids,
                             tile_ids: (tile_ids[v], 0)),
                pl.BlockSpec((None, k, n), lambda v, offsets, group_ids,
                             tile_ids: (group_ids[v], 0, 0)),
            ],
            out_specs=pl.BlockSpec(
                (tm, n), lambda v, offsets, group_ids, tile_ids:
                (tile_ids[v], 0)),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * k + g * k * n + m * n) * item),
        interpret=interpret,
        name="grouped_matmul",
        **kwargs,
    )(offsets, group_ids, tile_ids, lhs, rhs)
