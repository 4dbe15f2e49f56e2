"""The main path's kernels compile for the chip — checked without one.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (``/opt/skills/guides/on-chip-measurement`` §2.3).
The Pallas interpreter checks neither Mosaic's tiling rule nor VMEM, so
the interpret-mode parity tests cannot see a kernel the chip refuses: the
paged kernels passed 60+ of them while refusing every shape here (PR 21).
Each case lowers with ``interpret=False`` at GPT-2-small serving/training
widths (H=12, D=64) and asserts the compiled program holds the kernel.

Nothing runs: a compile that passes says nothing about results or time.
"""

import functools
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import topologies
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import (
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

from pytorch_distributed_tpu.ops.flash_attention import flash_attention
from pytorch_distributed_tpu.ops.paged_flash import (
    paged_flash_attention,
    paged_quantize_scatter,
)
from pytorch_distributed_tpu.ops.ring_flash import ring_flash_attention
from pytorch_distributed_tpu.parallel.mesh import SEQ_AXIS, shard_map
from pytorch_distributed_tpu.serving.kv_pool import (
    kv_pool_dtype,
    pool_scale_dtype,
)

H, D, BLOCK_LEN, SEQ = 12, 64, 16, 2048
N_BLOCKS = 32 * (SEQ // BLOCK_LEN) + 1  # 32 slots at capacity + trash


@pytest.fixture(scope="module")
def v5e():
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu here: nothing to compile with
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")
    # A compile for a described chip is written to jax's persistent cache
    # but cannot be read back without the chip; keep it out of the cache.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _loss_grad(attend):
    return jax.grad(lambda q, k, v: attend(q, k, v).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2))


def _flash(topo, grad):
    x = jax.ShapeDtypeStruct((4, SEQ, H, D), jnp.bfloat16,
                             sharding=SingleDeviceSharding(topo.devices[0]))
    fn = functools.partial(flash_attention, causal=True, interpret=False)
    return jax.jit(_loss_grad(fn) if grad else fn).lower(x, x, x)


def _ring_flash(topo):
    mesh = Mesh(topo.devices, (SEQ_AXIS,))
    spec = P(None, SEQ_AXIS)
    x = jax.ShapeDtypeStruct((2, 8192, H, D), jnp.bfloat16,
                             sharding=NamedSharding(mesh, spec))
    ring = shard_map(
        functools.partial(ring_flash_attention, causal=True,
                          interpret=False),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False,
    )
    return jax.jit(_loss_grad(ring)).lower(x, x, x)


def _pool_avals(topo, kv_dtype):
    sds = functools.partial(jax.ShapeDtypeStruct,
                            sharding=SingleDeviceSharding(topo.devices[0]))
    pool_dt = jnp.bfloat16 if kv_dtype is None else kv_pool_dtype(kv_dtype)
    pool = sds((N_BLOCKS, BLOCK_LEN, H, D), pool_dt)
    scale = (None if kv_dtype is None else
             sds((N_BLOCKS, BLOCK_LEN, H), pool_scale_dtype(pool_dt)))
    return sds, pool, scale


def _paged(topo, b, c, kv_dtype=None, split_s=1):
    sds, pool, scale = _pool_avals(topo, kv_dtype)

    def fn(q, k, v, tables, pos, *scales):
        ks, vs = scales or (None, None)
        return paged_flash_attention(q, k, v, tables, pos, k_scale=ks,
                                     v_scale=vs, split_s=split_s,
                                     interpret=False)

    args = [sds((b, c, H, D), jnp.bfloat16), pool, pool,
            sds((b, SEQ // BLOCK_LEN), jnp.int32), sds((b, c), jnp.int32)]
    if scale is not None:
        args += [scale, scale]
    return jax.jit(fn).lower(*args)


def _scatter(topo, kv_dtype):
    sds, pool, scale = _pool_avals(topo, kv_dtype)
    b, l = 4, 32  # recipes/serve_lm.py's default prefill chunk
    rows = sds((b, l, H, D), jnp.bfloat16)
    idx = sds((b, l), jnp.int32)
    fn = functools.partial(paged_quantize_scatter, interpret=False)
    return jax.jit(fn, donate_argnums=(4, 5, 6, 7)).lower(
        rows, rows, idx, idx, pool, pool, scale, scale
    )


CASES = {
    "flash_fwd": lambda t: _flash(t, grad=False),
    "flash_fwd_bwd": lambda t: _flash(t, grad=True),
    "ring_flash_fwd_bwd_seq4": _ring_flash,
    "paged_decode_bf16": lambda t: _paged(t, 32, 1),
    "paged_chunk_bf16": lambda t: _paged(t, 4, 128),
    "paged_decode_int8": lambda t: _paged(t, 32, 1, "int8"),
    "paged_decode_fp8": lambda t: _paged(t, 32, 1, "fp8"),
    "paged_decode_split2": lambda t: _paged(t, 32, 1, split_s=2),
    "quantize_scatter_int8": lambda t: _scatter(t, "int8"),
    "quantize_scatter_fp8": lambda t: _scatter(t, "fp8"),
}


#: the kernels' ``name=``, as each case's compiled program must show them
#: (autodiff wraps a name: ``jvp_flash_fwd_``, ``transpose_jvp_flash_bwd_
#: fused__``), so that a device trace says which kernel an operation is
KERNELS = {
    "flash_fwd": ("flash_fwd",),
    "flash_fwd_bwd": ("flash_fwd", "flash_bwd_fused"),
    "ring_flash_fwd_bwd_seq4": ("flash_fwd", "flash_bwd_fused"),
    "paged_decode_bf16": ("paged_decode_attn",),
    "paged_chunk_bf16": ("paged_decode_attn",),
    "paged_decode_int8": ("paged_decode_attn",),
    "paged_decode_fp8": ("paged_decode_attn",),
    "paged_decode_split2": ("paged_decode_attn",),
    "quantize_scatter_int8": ("paged_kv_write",),
    "quantize_scatter_fp8": ("paged_kv_write",),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_compiles_for_v5e(v5e, case):
    text = CASES[case](v5e).compile().as_text()
    assert "tpu_custom_call" in text, f"{case}: no Mosaic kernel in program"
    calls = [line.split(" = ")[0] for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for kernel in KERNELS[case]:
        assert any(kernel in c for c in calls), (case, kernel, calls)
    if case.startswith("ring_flash"):
        assert "collective-permute" in text


def test_split_backward_kernels_are_named(v5e):
    """The two-kernel backward (``bwd_impl="split"``) names both."""
    x = jax.ShapeDtypeStruct((4, SEQ, H, D), jnp.bfloat16,
                             sharding=SingleDeviceSharding(v5e.devices[0]))
    fn = functools.partial(flash_attention, causal=True, interpret=False,
                           bwd_impl="split")
    text = jax.jit(_loss_grad(fn)).lower(x, x, x).compile().as_text()
    assert "flash_bwd_dq" in text and "flash_bwd_dkv" in text


def test_decode_tick_module_is_named(v5e):
    """The profiler calls a program ``jit_<function name>``: the paged
    engine's decode tick compiles for the chip as ``jit_decode_tick``."""
    from pytorch_distributed_tpu.models.transformer import (
        TransformerLM,
        tiny_config,
    )
    from pytorch_distributed_tpu.serving.engine import PagedEngine

    cfg = tiny_config(attention="dense", max_seq_len=64)
    params = TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    eng = PagedEngine(cfg, params, 4, block_len=8, prefill_chunk=8)
    one = SingleDeviceSharding(v5e.devices[0])

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)

    args = jax.tree.map(on_chip, (
        eng.params, eng.cache, eng.logits, jnp.zeros((4,), jnp.int32),
        jnp.zeros((4,), bool), jnp.zeros((4, eng.table_width), jnp.int32),
        jax.random.key(0)))
    lowered = eng._decode().lower(*args)
    assert lowered.as_text().startswith("module @jit_decode_tick ")
    assert lowered.compile().as_text().startswith("HloModule jit_decode_tick,")
