"""Attention kernel math: blockwise == dense, gradients included."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow

from pytorch_distributed_tpu.ops.attention import (
    blockwise_attention,
    dense_attention,
)


def qkv(b=2, l=32, h=3, d=8, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(b, l, h, d)), dtype)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block_size", [8, 16, 32])
def test_blockwise_matches_dense(causal, block_size):
    q, k, v = qkv()
    ref = dense_attention(q, k, v, causal=causal)
    out = blockwise_attention(q, k, v, causal=causal, block_size=block_size)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_blockwise_grads_match_dense():
    q, k, v = qkv()

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=True) ** 2)

    def loss_block(q, k, v):
        return jnp.sum(blockwise_attention(q, k, v, causal=True, block_size=8) ** 2)

    g_ref = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    g_blk = jax.grad(loss_block, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_blk):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_causal_first_token_attends_self_only():
    q, k, v = qkv(b=1, l=4, h=1, d=4)
    out = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out[0, 0, 0]), np.asarray(v[0, 0, 0]), rtol=1e-5, atol=1e-6
    )


def test_offsets_reproduce_causal_tiling():
    """Computing causal attention row-block by row-block with explicit
    offsets equals the full causal result — the property ring attention
    relies on."""
    q, k, v = qkv(b=1, l=16, h=2, d=8)
    ref = dense_attention(q, k, v, causal=True)
    half = 8
    top = blockwise_attention(
        q[:, :half], k, v, causal=True, block_size=8, q_offset=0, k_offset=0
    )
    bot = blockwise_attention(
        q[:, half:], k, v, causal=True, block_size=8, q_offset=half, k_offset=0
    )
    out = jnp.concatenate([top, bot], axis=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_bf16_inputs_fp32_softmax():
    q, k, v = qkv(dtype=jnp.bfloat16)
    ref = dense_attention(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        causal=True,
    )
    out = blockwise_attention(q, k, v, causal=True, block_size=8)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), rtol=0.05, atol=0.05
    )


def test_fully_masked_rows_are_zero():
    """A query block whose keys are all in the future must produce zeros
    (the documented finalize() contract), not uniform mean(V)."""
    q, k, v = qkv(b=1, l=8, h=1, d=4)
    out_blk = blockwise_attention(q, k, v, causal=True, block_size=8,
                                  q_offset=0, k_offset=100)
    out_dense = dense_attention(q, k, v, causal=True, q_offset=0, k_offset=100)
    np.testing.assert_array_equal(np.asarray(out_blk), 0.0)
    np.testing.assert_array_equal(np.asarray(out_dense), 0.0)


def test_indivisible_block_raises():
    q, k, v = qkv(l=30)
    with pytest.raises(ValueError):
        blockwise_attention(q, k, v, block_size=16)


# ---- Pallas flash attention (interpret mode: same kernel, CPU executed) ----


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_dense(causal):
    from pytorch_distributed_tpu.ops.flash_attention import flash_attention

    q, k, v = qkv(l=64, d=16)
    out = flash_attention(
        q, k, v, causal=causal, block_q=16, block_k=16, interpret=True
    )
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_flash_grads_match_dense():
    from pytorch_distributed_tpu.ops.flash_attention import flash_attention

    q, k, v = qkv(l=32, d=16)

    def loss_flash(q, k, v):
        out = flash_attention(
            q, k, v, causal=True, block_q=16, block_k=16, interpret=True
        )
        return jnp.sum(out**2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=True) ** 2)

    g_f = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_d = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_f, g_d):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_arbitrary_lengths_match_dense(causal):
    """r2: lengths that are NOT block multiples work via zero padding +
    in-kernel key masking (round 1 raised), values AND gradients."""
    from pytorch_distributed_tpu.ops.flash_attention import flash_attention

    q, k, v = qkv(l=30, d=16)
    out = flash_attention(
        q, k, v, causal=causal, block_q=16, block_k=16, interpret=True
    )
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)

    g_f = jax.grad(
        lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, causal=causal, block_q=16, block_k=16,
                            interpret=True) ** 2
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    g_d = jax.grad(
        lambda q, k, v: jnp.sum(dense_attention(q, k, v, causal=causal) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g_f, g_d):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5)


def test_flash_cross_attention_lengths():
    """Lq != Lk (cross/prefix shapes), non-causal, with key padding."""
    from pytorch_distributed_tpu.ops.flash_attention import flash_attention

    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(2, 24, 2, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 50, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 50, 2, 16)), jnp.float32)
    out = flash_attention(q, k, v, block_q=16, block_k=16, interpret=True)
    ref = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)


def test_flash_lm_forward_matches_dense():
    from pytorch_distributed_tpu.models.transformer import TransformerLM, tiny_config

    # interpret-mode flash inside the full model on CPU
    import importlib

    fa = importlib.import_module("pytorch_distributed_tpu.ops.flash_attention")

    cfg_d = tiny_config(attention="dense")
    cfg_f = tiny_config(attention="flash")
    tokens = jnp.asarray(np.random.default_rng(0).integers(1, 128, (2, 32)), jnp.int32)
    model_d = TransformerLM(cfg_d)
    variables = model_d.init(jax.random.key(0), tokens)
    out_d = model_d.apply(variables, tokens)
    orig = fa.flash_attention
    try:
        fa.flash_attention = lambda *a, **kw: orig(*a, **{**kw, "interpret": True})
        out_f = TransformerLM(cfg_f).apply(variables, tokens)
    finally:
        fa.flash_attention = orig
    np.testing.assert_allclose(
        np.asarray(out_f), np.asarray(out_d), rtol=2e-4, atol=2e-5
    )

    # and backward: every parameter's gradient (the training path reads
    # q, k and v out of the fused product: flash_attention_qkv)
    def grads(cfg):
        return jax.grad(lambda p: jnp.sum(
            TransformerLM(cfg).apply({"params": p}, tokens) ** 2
        ))(variables["params"])

    for (path, g_f), g_d in zip(
        jax.tree_util.tree_leaves_with_path(grads(cfg_f)),
        jax.tree.leaves(grads(cfg_d)),
    ):
        scale = float(jnp.abs(g_d).max()) + 1e-6
        np.testing.assert_allclose(
            np.asarray(g_f) / scale, np.asarray(g_d) / scale, atol=2e-4,
            err_msg=jax.tree_util.keystr(path),
        )


def test_flash_fused_backward_matches_split():
    """The single-pass backward (bwd_impl='fused') must produce the same
    gradients as the two-kernel split backward — including the causal
    skip-block zeroing of dQ partials and padded lengths."""
    import numpy as np

    from pytorch_distributed_tpu.ops.flash_attention import flash_attention

    r = np.random.RandomState(0)
    for (b, l, h, d) in [(2, 256, 2, 32), (1, 200, 2, 32)]:
        q = jnp.asarray(r.randn(b, l, h, d), jnp.float32)
        k = jnp.asarray(r.randn(b, l, h, d), jnp.float32)
        v = jnp.asarray(r.randn(b, l, h, d), jnp.float32)

        def loss(impl):
            return lambda q_, k_, v_: jnp.sum(
                flash_attention(q_, k_, v_, causal=True, block_q=64,
                                block_k=64, bwd_impl=impl) ** 2
            )

        g_split = jax.grad(loss("split"), argnums=(0, 1, 2))(q, k, v)
        g_fused = jax.grad(loss("fused"), argnums=(0, 1, 2))(q, k, v)
        for a, bb in zip(g_fused, g_split):
            np.testing.assert_allclose(a, bb, rtol=2e-4, atol=2e-5)


def test_flash_partials_f32_knob():
    """ADVICE r5 #2: ``partials_f32=True`` keeps the fused backward's dQ
    partials in fp32. For fp32 inputs the partials already ARE fp32, so
    the knob must be exactly inert; for bf16 inputs it removes the
    per-partial bf16 rounding, so the fused dQ must land at least as
    close to the split backward's pure-fp32 dQ accumulation as the
    default does."""
    import numpy as np

    from pytorch_distributed_tpu.ops.flash_attention import flash_attention

    r = np.random.RandomState(1)
    raw = [r.randn(2, 256, 2, 32) for _ in range(3)]

    def grads(dtype, impl, pf32):
        q, k, v = (jnp.asarray(x, dtype) for x in raw)
        loss = lambda q_, k_, v_: jnp.sum(
            flash_attention(q_, k_, v_, causal=True, block_q=64,
                            block_k=64, bwd_impl=impl,
                            partials_f32=pf32).astype(jnp.float32) ** 2
        )
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    # fp32: bit-inert (partials were fp32 either way)
    for a, b in zip(grads(jnp.float32, "fused", True),
                    grads(jnp.float32, "fused", False)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # bf16: fp32 partials must not be FARTHER from the split (pure-fp32
    # dQ accumulation) reference than the default bf16 partials
    dq_split = np.asarray(grads(jnp.bfloat16, "split", False)[0],
                          np.float32)
    dq_bf16 = np.asarray(grads(jnp.bfloat16, "fused", False)[0], np.float32)
    dq_f32 = np.asarray(grads(jnp.bfloat16, "fused", True)[0], np.float32)
    err = lambda x: np.abs(x - dq_split).max()
    assert err(dq_f32) <= err(dq_bf16) + 1e-6
    np.testing.assert_allclose(dq_f32, dq_split, rtol=2e-2, atol=2e-2)


# ---- the kernels on [B, L, heads·D] rows, g = 128 // D heads a block --------


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("d", [32, 64, 128])  # g = 4, 2, 1 heads a block
def test_flash_qkv_matches_dense(d, causal):
    """``flash_attention_qkv`` reads q, k and v at their columns of ONE
    ``[B, L, 3·H·D]`` array and returns ``[B, L, H·D]``: output AND the
    packed operand's gradient against ``dense_attention``, at a length
    that is no multiple of the block (zero rows padded, keys masked)."""
    from pytorch_distributed_tpu.ops.flash_attention import (
        _heads_a_block,
        flash_attention_qkv,
    )

    b, l, h = 2, 40, 256 // d
    assert _heads_a_block(h, d) == max(128 // d, 1)
    rows = jnp.asarray(
        np.random.default_rng(d).normal(size=(b, l, 3 * h * d)), jnp.float32)

    def heads(x):  # the three [B, L, H, D] views of the packed rows
        return [x[..., i * h * d:(i + 1) * h * d].reshape(b, l, h, d)
                for i in range(3)]

    def flash(x):
        return flash_attention_qkv(x, h, causal=causal, block_q=16,
                                   block_k=16, interpret=True)

    def dense(x):
        return dense_attention(*heads(x), causal=causal).reshape(b, l, h * d)

    np.testing.assert_allclose(np.asarray(flash(rows)),
                               np.asarray(dense(rows)), rtol=1e-5, atol=1e-5)
    g_f = jax.grad(lambda x: jnp.sum(flash(x) ** 2))(rows)
    g_d = jax.grad(lambda x: jnp.sum(dense(x) ** 2))(rows)
    np.testing.assert_allclose(np.asarray(g_f), np.asarray(g_d), rtol=2e-4,
                               atol=5e-5)


@pytest.mark.parametrize("h,d,g", [
    (16, 64, 2), (12, 64, 2), (8, 32, 4), (2, 128, 1), (1, 64, 1),
    (2, 16, 2), (3, 64, 0), (16, 80, 0), (1, 80, 1),
])
def test_heads_a_block_follows_from_the_shapes(h, d, g):
    """128 // D heads where they fill a 128-lane block and the row cuts
    into such blocks, one head of whole lane tiles, the whole of a row no
    wider than 128 lanes, and 0 (heads to the batch axis) elsewhere."""
    from pytorch_distributed_tpu.ops.flash_attention import _heads_a_block

    assert _heads_a_block(h, d) == g


def test_flash_rows_that_do_not_cut_into_lane_blocks():
    """H·D = 192 is no multiple of 128 and wider than a block: the public
    entry moves the heads to the batch axis and still matches dense."""
    from pytorch_distributed_tpu.ops.flash_attention import flash_attention

    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 24, 3, 64)), jnp.float32)
               for _ in range(3))
    out = flash_attention(q, k, v, causal=True, block_q=8, block_k=8,
                          interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(dense_attention(q, k, v, causal=True)),
        rtol=1e-5, atol=1e-5)


def test_training_attention_holds_no_relayout():
    """Between the qkv product and the output projection the training
    path (``attention="flash"``, fused qkv) moves no activation: the
    lowered forward-and-backward of ``Attention`` transposes only weight
    gradients (rank 2), where the parent's text held twelve rank-4
    ``[B, L, H, D] <-> [B, H, L, D]`` transposes."""
    import re

    from pytorch_distributed_tpu.models.transformer import (
        Attention,
        tiny_config,
    )

    cfg = tiny_config(attention="flash")
    att = Attention(cfg)
    x = jnp.ones((2, 32, cfg.embed_dim), jnp.float32)
    params = att.init(jax.random.key(0), x, 0)
    text = jax.jit(jax.grad(
        lambda p, x: att.apply(p, x, 0).sum(), argnums=(0, 1)
    )).lower(params, x).as_text()
    moved = re.findall(r"stablehlo\.transpose .*: \(tensor<([0-9x]+)x\w+>\)",
                       text)
    assert moved, "the weight gradients' transposes should be in the text"
    assert all(len(shape.split("x")) == 2 for shape in moved), moved


@pytest.mark.parametrize("attention", ["dense", "flash"])
def test_lm_parameter_tree_is_the_checkpoints(attention):
    """The packed path takes its products on flat rows (``RowsDense``)
    under ``nn.DenseGeneral``'s names, shapes AND initial values: the
    tree a ``TransformerLM`` initialises is the one every checkpoint
    holds (``qkv/kernel`` [E, 3, H, D], ``qkv/bias`` [3, H, D],
    ``proj/kernel`` [H, D, E]), whichever attention initialises it."""
    from pytorch_distributed_tpu.models.transformer import (
        TransformerLM,
        tiny_config,
    )

    tokens = jnp.ones((1, 16), jnp.int32)
    cfg = tiny_config(attention=attention)
    params = TransformerLM(cfg).init(jax.random.key(0), tokens)["params"]
    e, h, d = cfg.embed_dim, cfg.num_heads, cfg.head_width
    attn = params["block0"]["attn"]
    assert {k: {n: p.shape for n, p in v.items()} for k, v in attn.items()} \
        == {"qkv": {"kernel": (e, 3, h, d), "bias": (3, h, d)},
            "proj": {"kernel": (h, d, e)}}
    # value for value what the head-by-head products initialise
    ref = TransformerLM(tiny_config(attention="dense")).init(
        jax.random.key(0), tokens)["params"]
    assert jax.tree.structure(params) == jax.tree.structure(ref)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(ref)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
