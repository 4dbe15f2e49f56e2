"""``ops/grouped_matmul.py`` in the Pallas interpreter (PR 45): the kernel
beside ``jax.lax.ragged_dot`` and beside a float32 loop over the groups, at
small twins of the serving cells' shapes, and ``DroplessMoE`` on the kernel
beside ``DroplessMoE`` on ``ragged_dot`` through its three routers and
``relu2``. What Mosaic makes of the real shapes is
``tests/test_tpu_compile.py``'s; what the chip makes of them PERF.md's."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from pytorch_distributed_tpu.models import moe  # noqa: E402
from pytorch_distributed_tpu.ops import grouped_matmul as gm  # noqa: E402


def loop_over_groups(lhs, rhs, sizes):
    """The float32 reference: a plain product a group; rows of no group
    NaN, so that a comparison that reads one fails."""
    lhs, rhs = np.asarray(lhs, np.float32), np.asarray(rhs, np.float32)
    out = np.full((lhs.shape[0], rhs.shape[2]), np.nan, np.float32)
    row = 0
    for g, size in enumerate(np.asarray(sizes)):
        out[row:row + size] = lhs[row:row + size] @ rhs[g]
        row += size
    return out, row


#: (rows, K, N, sizes, row tile or None for ``row_tile``): what each case
#: holds
CASES = {
    "an empty group in the middle": (64, 32, 48, [5, 0, 20, 7], 16),
    "a group that straddles two row tiles": (64, 32, 48, [10, 30, 4], 16),
    "a group that owns three row tiles and ends in a fourth": (
        128, 128, 128, [3, 100, 9], 32),
    "rows behind the last group": (96, 32, 48, [5, 20], 16),
    "sizes all zero": (64, 32, 48, [0, 0, 0, 0], 16),
    "one group owns every row": (64, 32, 48, [64], 16),
    "every group empty but the last": (64, 32, 48, [0, 0, 0, 9], 16),
    "K and N that are not whole lane tiles": (
        64, 200, 130, [5, 0, 20, 7], 16),
    "a contracted width of three lane tiles": (64, 384, 128, [33, 31], 32),
    "M under one row tile": (10, 32, 48, [2, 3, 4], None),
    "M that is not whole row tiles": (300, 128, 256, [100, 0, 150, 3], None),
    "as many groups as a tick's": (256, 64, 128, [2] * 100 + [0] * 28, None),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_ragged_dot_on_the_rows_of_a_group(case, dtype):
    m, k, n, sizes, tm = CASES[case]
    rng = np.random.default_rng(len(case))
    lhs = jnp.asarray(rng.normal(size=(m, k)), dtype)
    rhs = jnp.asarray(rng.normal(size=(len(sizes), k, n)), dtype)
    sizes = jnp.asarray(sizes, jnp.int32)
    want, live = loop_over_groups(lhs, rhs, sizes)
    xla = np.asarray(jax.lax.ragged_dot(lhs, rhs, sizes), np.float32)
    # the rows behind the last group are read by no sum that is stored
    got = gm.grouped_matmul(lhs.at[live:].set(jnp.nan), rhs, sizes, tm=tm)
    assert got.shape == (m, n) and got.dtype == dtype
    got = np.asarray(got, np.float32)[:live]
    assert np.isfinite(got).all()
    # float32 sums cast once: to the reference within an ulp of the dtype
    # at the sums' size, to XLA's product within the order of the sums
    ulp = 2.0 ** (-7 if dtype == jnp.bfloat16 else -19)
    scale = max(np.abs(want[:live]).max(initial=0.0), 1.0)
    assert np.abs(got - want[:live]).max(initial=0.0) <= ulp * scale
    assert np.abs(got - xla[:live]).max(initial=0.0) <= ulp * scale


def test_the_visits_are_the_group_and_row_tile_pairs_that_share_a_row():
    """``visit_metadata`` by hand: tiles of 16 rows; group 0 rows 0-9,
    group 1 empty, group 2 rows 10-39 (tiles 0, 1, 2), group 3 rows 40-43
    (tile 2); tile 3 and the rows behind 44 are nobody's."""
    sizes = jnp.asarray([10, 0, 30, 4], jnp.int32)
    offsets, groups, tiles, visits = gm.visit_metadata(sizes, 64, 16)
    assert offsets.tolist() == [0, 10, 10, 40, 44]
    assert int(visits) == 5
    assert groups.shape == tiles.shape == (64 // 16 + 4 - 1,)
    assert groups[:5].tolist() == [0, 2, 2, 2, 3]
    assert tiles[:5].tolist() == [0, 0, 1, 2, 2]
    # what lies behind the visits is never run, and names real blocks
    assert 0 <= int(tiles.min()) and int(tiles.max()) < 4
    assert int(groups.max()) < 4
    # no row: no visit
    assert int(gm.visit_metadata(jnp.zeros((4,), jnp.int32), 64, 16)[3]) == 0
    # sizes that claim more rows than there are stop at the last row
    over = gm.visit_metadata(jnp.asarray([40, 40], jnp.int32), 64, 16)
    assert over[0].tolist() == [0, 40, 64] and int(over[3]) == 5


@pytest.mark.parametrize("m,k,n,want", [
    # the serving cells' products (PERF.md section 6, PR 45): a row tile of
    # 128, and a visit multiplies by its group's whole matrix
    (1536, 3072, 2048, 128),  # nemotron's tick, w_up
    (6144, 2048, 3072, 128),  # its chunk program, w_down
    (2560, 2048, 1024, 128),  # qwen3-next's tick, w_gate_up
    (20480, 512, 2048, 128),  # its chunk program, w_down
    (2048, 2560, 1536, 128),  # ling's tick, w_gate_up
    (8192, 768, 2560, 128),  # its chunk program, w_down
    (128, 2048, 4096, 128),  # zaya's tick, w_gate_up: the largest, 16 MiB
    (256, 2048, 2048, 128),  # its chunk program, w_down
    (512, 2048, 4096, 128),  # its chunk program of four jobs
    # the toys: all the rows in whole sixteens
    (9, 48, 64, 16),
    (100, 200, 130, 112),
])
def test_the_row_tile_follows_from_the_rows(m, k, n, want):
    tm = gm.row_tile(m)
    assert tm == want <= gm.ROW_TILE and tm % gm.ROW_ALIGN == 0
    # a visit holds the group's whole matrix
    assert k * n * 2 <= gm.RHS_BLOCK_BYTES


def test_a_matrix_over_the_block_bytes_is_refused():
    """No width is split: a group's matrix that does not fit a grid step
    whole (none of the serving cells': at most 16 MiB) is refused by name,
    before anything is traced."""
    sizes = jnp.zeros((2,), jnp.int32)
    lhs = jax.ShapeDtypeStruct((128, 4096), jnp.bfloat16)
    for n, fits in ((2048, True), (2176, False)):
        rhs = jax.ShapeDtypeStruct((2, 4096, n), jnp.bfloat16)
        if fits:
            assert jax.eval_shape(gm.grouped_matmul, lhs, rhs,
                                  sizes).shape == (128, n)
            continue
        with pytest.raises(ValueError, match="over the 16 MiB"):
            jax.eval_shape(gm.grouped_matmul, lhs, rhs, sizes)


def test_mismatched_operands_are_refused():
    lhs, rhs = jnp.zeros((16, 8)), jnp.zeros((2, 8, 4))
    sizes = jnp.zeros((2,), jnp.int32)
    with pytest.raises(ValueError, match="lhs \\[M, K\\]"):
        gm.grouped_matmul(lhs, jnp.zeros((2, 9, 4)), sizes)
    with pytest.raises(ValueError, match="share a dtype"):
        gm.grouped_matmul(lhs, rhs.astype(jnp.bfloat16), sizes)
    with pytest.raises(ValueError, match="a size a group"):
        gm.grouped_matmul(lhs, rhs, jnp.zeros((3,), jnp.int32))


# ---- DroplessMoE on the kernel ---------------------------------------------

EXPERTS, D, T = 8, 32, 24

LAYERS = {
    "mlp": dict(router="mlp", router_dim=16),
    "sigmoid": dict(router="sigmoid", top_k=3, n_group=4, topk_group=2,
                    routed_scale=2.5, shared_dim=24, held=(2, 6)),
    "softmax": dict(router="softmax", top_k=3, shared_dim=24,
                    shared_gate=True, held=(0, 4)),
    "relu2": dict(router="sigmoid", top_k=2, routed_scale=2.5,
                  shared_dim=40, held=(0, 4), relu2=True),
}


def poisoned(lhs, rhs, sizes, kernel=gm.grouped_matmul):
    """The kernel (bound here: the test puts this function in its name's
    place), with what the chip may leave in a row of no group."""
    out = kernel(lhs, rhs, sizes)
    rows = jnp.arange(out.shape[0])[:, None]
    return jnp.where(rows < jnp.sum(sizes), out, jnp.nan)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("masked", [False, True], ids=["all-live", "masked"])
@pytest.mark.parametrize("kind", sorted(LAYERS))
def test_the_expert_layer_on_the_kernel_is_the_layer_on_ragged_dot(
        monkeypatch, kind, masked, dtype):
    """``DroplessMoE`` asks ``grouped_rows`` which product it compiles; on
    the kernel (interpreted, its rows of no group NaN) every router's path
    gives what it gives on ``ragged_dot``, to the dtype's rounding: the
    pairs of an expert that is not held, of a row that is not live, and the
    NaNs behind them are selected away, not multiplied by zero."""
    layer = moe.DroplessMoE(n_experts=EXPERTS, moe_dim=24, dtype=dtype,
                            **LAYERS[kind])
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, T // 2, D)), dtype)
    live = jnp.asarray(rng.random((2, T // 2)) < 0.7) if masked else None
    state = (jnp.asarray(rng.normal(size=(2, T // 2, 16)), jnp.float32)
             if kind == "mlp" else None)
    params = layer.init(jax.random.key(1), x, state, live)["params"]
    assert moe.grouped_rows(T, layer.top_k) == 0  # the CPU keeps XLA's
    want, want_state = layer.apply({"params": params}, x, state, live)

    calls = []
    monkeypatch.setattr(moe, "grouped_rows",
                        lambda tokens, top_k: calls.append((tokens, top_k))
                        or gm.row_tile(tokens * top_k))
    monkeypatch.setattr(gm, "grouped_matmul", poisoned)
    got, got_state = layer.apply({"params": params}, x, state, live)
    assert calls == [(T, layer.top_k)]
    assert got.dtype == want.dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    ulp = 2.0 ** (-6 if dtype == jnp.bfloat16 else -20)
    assert np.abs(got - want).max() <= ulp * max(np.abs(want).max(), 1.0)
    if live is not None:
        assert not got[~np.asarray(live)].any()
    if kind == "mlp":
        np.testing.assert_array_equal(np.asarray(got_state),
                                      np.asarray(want_state))
