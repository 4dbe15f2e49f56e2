"""The main path's kernels compile for the chip — checked without one.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (``/opt/skills/guides/on-chip-measurement`` §2.3).
The Pallas interpreter checks neither Mosaic's tiling rule nor VMEM, so
the interpret-mode parity tests cannot see a kernel the chip refuses: the
paged kernels passed 60+ of them while refusing every shape here (PR 21).
Each case lowers with ``interpret=False`` at GPT-2-small serving/training
widths (H=12, D=64) and asserts the compiled program holds the kernel.

The K/V pool's layout is guarded the same way: the paged engine's decode
tick and one chunk program compile at the benchmark's serving shapes and
must keep every pool leaf row-major, with no copy of a whole leaf.

Nothing runs: a compile that passes says nothing about results or time.
"""

import functools
import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import topologies
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import (
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

from pytorch_distributed_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_qkv,
)
from pytorch_distributed_tpu.ops.paged_flash import (
    paged_flash_attention,
    paged_quantize_scatter,
)
from pytorch_distributed_tpu.ops.ring_flash import ring_flash_attention
from pytorch_distributed_tpu.parallel.mesh import SEQ_AXIS, shard_map
from pytorch_distributed_tpu.serving.kv_pool import (
    kv_pool_dtype,
    pool_leaf_shape,
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


def _flash_qkv(topo):
    """The pretrain cell's attention (16 heads of 64 at L = 1,024) off the
    packed qkv rows, forward and backward: two heads a 128-lane block."""
    x = jax.ShapeDtypeStruct((4, 1024, 3 * 16 * 64), jnp.bfloat16,
                             sharding=SingleDeviceSharding(topo.devices[0]))
    fn = functools.partial(flash_attention_qkv, heads=16, causal=True,
                           interpret=False)
    return jax.jit(jax.grad(
        lambda x: fn(x).astype(jnp.float32).sum())).lower(x)


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
    pool = sds(pool_leaf_shape(N_BLOCKS, BLOCK_LEN, H, D), pool_dt)
    scale = (None if kv_dtype is None else
             sds(pool_leaf_shape(N_BLOCKS, BLOCK_LEN, H, D, scale=True),
                 pool_scale_dtype(pool_dt)))
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
    "flash_qkv_fwd_bwd": _flash_qkv,
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
    "flash_qkv_fwd_bwd": ("flash_fwd", "flash_bwd_fused"),
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


def test_training_attention_compiles_without_a_relayout(v5e, monkeypatch):
    """``Attention`` as the pretrain cell trains it (``attention="flash"``,
    fused qkv, gpt2-medium's widths in bfloat16), forward and backward,
    compiled for the chip: the kernels take the qkv product's rows and
    give the projection its rows, and the compiler puts NO copy or
    transpose of a ``[B, L, ...]`` activation between them. (It laid the
    head-by-head product ``[B, L, 3, H, 64]`` out with the sequence minor
    and copied it for any consumer that wanted rows: ``RowsDense``.)"""
    from pytorch_distributed_tpu.models.transformer import (
        Attention,
        TransformerConfig,
    )

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    b, l, e = 4, 1024, 1024
    cfg = TransformerConfig(vocab_size=512, num_layers=1, num_heads=16,
                            embed_dim=e, max_seq_len=l, dropout=0.0,
                            dtype=jnp.bfloat16, attention="flash")
    att = Attention(cfg)
    one = SingleDeviceSharding(v5e.devices[0])
    x = jax.ShapeDtypeStruct((b, l, e), jnp.bfloat16, sharding=one)
    params = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=one),
        jax.eval_shape(att.init, jax.random.key(0),
                       jnp.zeros((b, l, e), jnp.bfloat16), 0))
    text = jax.jit(jax.grad(
        lambda p, x: att.apply(p, x, 0).astype(jnp.float32).sum(),
        argnums=(0, 1),
    )).lower(params, x).compile().as_text()
    assert "flash_fwd" in text and "flash_bwd_fused" in text
    moved = [line.split(" = ")[0].strip() for line in text.splitlines()
             if re.search(rf" = \w+\[{b},{l},[0-9,]+\]\S* (copy|transpose)\(",
                          line)]
    assert not moved, moved


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

    fn, operands = _tick_operands(eng)
    lowered = fn.lower(*jax.tree.map(
        on_chip, (eng.params, eng.cache, eng.logits) + operands))
    assert lowered.as_text().startswith("module @jit_decode_tick ")
    assert lowered.compile().as_text().startswith("HloModule jit_decode_tick,")


# ---- the K/V pool's layout on the chip (PR 25) -----------------------------

#: gpt2-medium.chat-backlog's attention and pool (perfbench/cells), on 2
#: layers and a small vocabulary to keep the compile short
POOL_CELL = dict(heads=16, head_dim=64, slots=64, blocks=2561, block_len=16,
                 chunk=32, max_seq_len=1024)


def _tick_operands(eng):
    """The decode tick and what its call takes behind the logits buffer:
    the engine's own packed operand (no lane active) and a key."""
    n = eng.n_slots
    return eng._decode(), (eng._decode_operand(
        np.zeros((n,), np.int32), np.zeros((n,), bool)), jax.random.key(0))


def _chunk_operands(eng, k, w):
    """The (k, w) chunk program and its one packed operand (every job a
    padding job), as ``warm_chunk`` builds it."""
    return eng._chunk_fn(k, w), (eng._chunk_operand(k, w),)


def _engine_program(v5e, program, cell=None, kv_dtype=None, bucket=(4, 8),
                    **block):
    """``PagedEngine``'s decode tick or a chunk program (the ``(4, 8)``
    bucket unless told), lowered for the described chip from shapes
    alone: the engine is built on a two-block pool and the program takes
    the cell's whole pool as an aval (a program does not hold the pool's
    size). ``block`` describes another block kind than GPT-2's,
    ``kv_dtype`` a quantized pool."""
    from pytorch_distributed_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )
    from pytorch_distributed_tpu.serving.engine import PagedEngine
    from pytorch_distributed_tpu.serving.kv_pool import init_paged_cache

    c = cell or POOL_CELL
    cfg = TransformerConfig(
        vocab_size=512, num_layers=2, num_heads=c["heads"],
        embed_dim=c["heads"] * c["head_dim"], max_seq_len=c["max_seq_len"],
        dropout=0.0, dtype=jnp.bfloat16, attention="dense", **block,
    )
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16),
        jax.eval_shape(TransformerLM(cfg).init, jax.random.key(0),
                       jnp.zeros((1, 8), jnp.int32))["params"],
    )
    eng = PagedEngine(cfg, params, c["slots"], n_blocks=2,
                      block_len=c["block_len"], prefill_chunk=c["chunk"],
                      kv_dtype=kv_dtype)
    pool = jax.eval_shape(
        lambda p: init_paged_cache(cfg, p, c["blocks"], c["block_len"],
                                   kv_dtype=kv_dtype),
        params,
    )
    one = SingleDeviceSharding(v5e.devices[0])

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)

    if program == "decode_tick":
        fn, operands = _tick_operands(eng)
    else:
        fn, operands = _chunk_operands(eng, *bucket)
        assert eng.chunk_program_name(*bucket) == program
    args = (params, pool, eng.logits) + operands
    return fn.lower(*jax.tree.map(on_chip, args)), jax.tree.leaves(pool)


def _kernel_reads(text):
    """The compiled program's ``paged_decode_attn`` calls, a line each."""
    return [line for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            and "paged_decode_attn" in line.split(" = ")[0]]


def _grouped_products(text, pairs, experts):
    """``[(K, N)]`` of the compiled program's ``grouped_matmul`` calls
    (``ops/grouped_matmul.py``, the experts' products on a TPU since PR 45),
    in program order, having checked of each that it multiplies the
    program's ``pairs`` rows by the ``experts`` held matrices where they lie
    (row-major, no operand a copy's result) and that the visit lists it
    prefetches are as long as ``row_tile`` makes them (``pairs // tm
    + experts - 1``: the row tile is not in the text, the lists are)."""
    from pytorch_distributed_tpu.ops.grouped_matmul import row_tile

    products = []
    for line in text.splitlines():
        if ('custom_call_target="tpu_custom_call"' not in line
                or "grouped_matmul" not in line.split(" = ")[0]):
            continue
        lists = re.search(r"operand_layout_constraints=\{s32\[\], "
                          r"s32\[(\d+)\]\{0\}, s32\[(\d+)\]\{0\}, "
                          r"s32\[(\d+)\]\{0\}, bf16\[(\d+),(\d+)\]\{1,0\}, "
                          r"bf16\[(\d+),(\d+),(\d+)\]\{2,1,0\}\}", line)
        assert lists, line
        offsets, groups, rows, m, k, g, k2, n = map(int, lists.groups())
        assert (m, g, k2, offsets) == (pairs, experts, k, experts + 1), line
        tm = row_tile(m)
        assert tm == min(128, pairs) and m % tm == 0
        assert groups == rows == m // tm + g - 1, line
        operands = line.split(" custom-call(")[1].split(")")[0]
        assert "copy" not in operands and "transpose" not in operands, line
        products.append((k, n))
    assert "ragged-dot" not in text
    return products


def _state_updates(text, state, lanes):
    """How many ``slot_state_update`` calls the compiled program holds
    (``ops/state_update.py``, a paged decode tick's update of the delta
    rule's state on a TPU since PR 47), having checked of each that it takes
    the ``state`` leaf where it lies (row-major float32, no operand a copy's
    result) behind a flag a lane, and returns it."""
    leaf = "f32[%s]{3,2,1,0" % ",".join(map(str, state.shape))
    updates = 0
    for line in text.splitlines():
        if ('custom_call_target="tpu_custom_call"' not in line
                or "slot_state_update" not in line.split(" = ")[0]):
            continue
        updates += 1
        layouts = line.split("operand_layout_constraints={")[1].split(
            "}}")[0].split(", ")
        assert layouts[:2] == [f"s32[{lanes}]{{0}}"] * 2, line
        assert layouts[-1] == leaf, line
        assert line.split(" = (")[1].startswith(leaf), line
        # the leaf is the last operand and IS the first result
        assert "output_to_operand_aliasing={{0}: (%d, {})}" % (
            len(layouts) - 1) in line, line
        came = line.split(" custom-call(")[1].split(")")[0].split(", ")[-1]
        assert "copy" not in came and "transpose" not in came, line
    return updates


#: sha256 (12 hex digits) of the StableHLO text of the state cells' programs
#: that PR 47 must NOT move, as they lower for a described v5e where the
#: backend answers ``tpu`` (the tests below: one period of layers, 512
#: tokens): every chunk program (the kernel is the tick's), and nemotron's
#: tick (``Mamba2Mixer`` keeps ``ssm_update``: ``slot_state_update``). Taken
#: on PR 47's parent, with each kernel's serialized body blanked (a
#: ``tpu_custom_call``'s ``backend_config`` carries its source's path and
#: line numbers; the kernels have their own tests). A PR that means to
#: change one records a new digest.
STATE_DIGESTS = {
    ("ling", "chunk_prefill[k=4,w=128]"): "08f5d25a3dce",
    ("qwen3-next", "chunk_prefill[k=16,w=256]"): "5d13c20be4c5",
    ("nemotron-h", "chunk_prefill[k=8,w=128]"): "a88f1d2ffbbc",
    ("nemotron-h", "decode_tick"): "504f1ae852fc",
}


def _lowers_to_the_parents_text(lowered, stack, program):
    """Whether ``STATE_DIGESTS`` holds the program and its text is that."""
    import hashlib

    want = STATE_DIGESTS.get((stack, program))
    text = re.sub(r'backend_config = "[^"]*"', 'backend_config = ""',
                  lowered.as_text())
    got = hashlib.sha256(text.encode()).hexdigest()[:12]
    assert want is None or got == want, (stack, program, got)
    return want is not None


@pytest.mark.parametrize("program", ["decode_tick", "chunk_prefill[k=4,w=8]"])
def test_pool_leaves_stay_row_major_and_uncopied(v5e, program):
    """The layout's guard without a chip. A ``[n_blocks, block_len,
    H_kv, D]`` leaf enters these programs ``n_blocks``-minor
    (``{0,3,2,1}``: the compiler avoids padding D=64 to 128 lanes) and
    every scatter and gather is wrapped in copies of the whole leaf: 8
    ``copy`` of 84 MB in each of these two-layer programs (PR 24's
    tree), 40% of the serving cell's device time. ``kv_pool.
    pool_leaf_shape`` flattens the heads into the row: the leaf enters
    row-major and nothing the size of a leaf is copied or transposed."""
    lowered, leaves = _engine_program(v5e, program)
    text = lowered.compile().as_text()
    leaf = leaves[0]
    assert all(x.shape == leaf.shape for x in leaves)
    dims = ",".join(map(str, leaf.shape))
    entry = re.search(r"entry_computation_layout=\{\((.*?)\)->", text)
    layouts = re.findall(r"bf16\[%s\]\{([\d,]+)" % dims, entry.group(1))
    assert len(layouts) == len(leaves) and set(layouts) == {"2,1,0"}, layouts
    moved = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?(\S+) = \w+\[([\d,]+)\]\S* "
                     r"(copy|transpose)\(", line)
        if m and math.prod(map(int, m.group(2).split(","))) == leaf.size:
            moved.append(m.group(1))
    assert not moved, f"{program}: pool-sized copies {moved}"


# ---- a looped stack's pool and loop on the chip (PR 27) -------------------

#: ouro-2.6b.reason-backlog's attention, pool and block (perfbench/cells,
#: perfbench/configs), on 2 layers and a small vocabulary
LOOPED_CELL = dict(heads=16, head_dim=128, slots=16, blocks=289,
                   block_len=16, chunk=32, max_seq_len=640)
LOOPED_BLOCK = dict(norm="rmsnorm", mlp="swiglu", mlp_dim=5632,
                    post_norm=True, use_bias=False, pos_embedding="rope",
                    rope_theta=1e6)
PASSES = 4


@pytest.mark.parametrize("program", ["decode_tick", "chunk_prefill[k=4,w=8]"])
def test_looped_programs_index_the_pool_in_place_under_one_loop(v5e, program):
    """A looped stack's programs hold the layers ONCE, under a loop of
    ``ut_steps`` trips, and each pass scatters into and gathers from the
    carried ``[n_blocks, passes, block_len, H_kv*D]`` leaf in place: the
    leaf enters row-major, nothing the size of a leaf or of one pass's
    share of it is copied, transposed or sliced out, and the program has
    fewer than twice the one-pass program's instructions (unrolled it
    would have four times)."""
    def compiled(passes):
        lowered, leaves = _engine_program(v5e, program, LOOPED_CELL,
                                          ut_steps=passes, **LOOPED_BLOCK)
        return lowered.compile().as_text(), leaves

    def instructions(text):
        return sum(" = " in line for line in text.splitlines())

    text, leaves = compiled(PASSES)
    leaf = leaves[0]
    c = LOOPED_CELL
    assert all(x.shape == leaf.shape for x in leaves) and leaf.shape == (
        c["blocks"], PASSES, c["block_len"], c["heads"] * c["head_dim"])
    dims = ",".join(map(str, leaf.shape))
    entry = re.search(r"entry_computation_layout=\{\((.*?)\)->", text)
    layouts = re.findall(r"bf16\[%s\]\{([\d,]+)" % dims, entry.group(1))
    assert len(layouts) == len(leaves) and set(layouts) == {"3,2,1,0"}, layouts
    moved = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?(\S+) = \w+\[([\d,]+)\]\S* "
                     r"(copy|transpose|dynamic-slice|dynamic-update-slice)\(",
                     line)
        if m and math.prod(map(int, m.group(2).split(","))) in (
                leaf.size, leaf.size // PASSES):
            moved.append(m.group(1))
    assert not moved, f"{program}: pool-sized moves {moved}"
    assert len(re.findall(r" while\(", text)) == 1
    one_pass, _ = compiled(1)
    assert " while(" not in one_pass
    assert instructions(text) < 2 * instructions(one_pass)


# ---- the read the served programs compile by default (PR 28) ---------------

CELLS = {"chat-backlog": (POOL_CELL, {}),
         "reason-backlog": (LOOPED_CELL, dict(ut_steps=PASSES,
                                              **LOOPED_BLOCK))}


@pytest.mark.parametrize("backend,cell,program", [
    ("tpu", "chat-backlog", "decode_tick"),
    ("tpu", "chat-backlog", "chunk_prefill[k=4,w=8]"),
    ("tpu", "reason-backlog", "decode_tick"),
    ("tpu", "reason-backlog", "chunk_prefill[k=4,w=8]"),
    ("cpu", "chat-backlog", "decode_tick"),
    ("cpu", "reason-backlog", "decode_tick"),
])
def test_the_served_tick_reads_through_the_kernel_where_the_backend_is_a_tpu(
        v5e, monkeypatch, backend, cell, program):
    """No constructor takes a read: ``ops.attention.default_gather_impl``
    chooses. Where the program asks
    ``jax.default_backend()`` and hears ``tpu`` (steered here, in the
    test: the compile is for a described chip, the process's backend is
    the CPU), the decode tick holds the fused kernel, once a layer,
    inside the loop where the stack is looped, traced and lowered ONCE a
    program (the layers call one function), and no array the shape of a
    slot's gathered table. A chunk program's rows are a chunk's, so it
    gathers dense, as every program does on another backend: no kernel,
    and the gathered tables are there."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    c, block = CELLS[cell]
    lowered, _ = _engine_program(v5e, program, c, **block)
    text = lowered.compile().as_text()
    calls = [line.split(" = ")[0].strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    b, w = ((c["slots"], -(-c["max_seq_len"] // c["block_len"]))
            if program == "decode_tick" else (4, 8))
    bl, h, d = c["block_len"], c["heads"], c["head_dim"]
    gathered = {(b, w, bl, h * d), (b * w, bl, h * d), (b, w * bl, h, d)}
    found = {m.group(1) + m.group(2) for m in re.finditer(
        r" = (\w+)\[([\d,]+)\]", text)
        if tuple(map(int, m.group(2).split(","))) in gathered}
    if backend != "tpu" or program != "decode_tick":
        assert not calls and found, (calls, found)
        return
    assert len(calls) == 2 and all("paged_decode_attn" in x for x in calls)
    assert lowered.as_text().count("tpu_custom_call") == 1
    assert not found, f"{cell} {program}: gathered tables {sorted(found)}"
    if block:  # the kernel runs inside the passes' one loop
        (name,) = re.findall(r" while\(.*?body=(%[\w.\-]+)", text)
        start = text.index(f"\n{name} (")
        body = text[start:text.index("\n}\n", start)]
        assert body.count('custom_call_target="tpu_custom_call"') == 2


#: a tiny grouped-query engine at gpt2-medium's head width: 16 query
#: heads over 2 narrow heads is a group of 8 (128 lanes a pool row, a
#: block Mosaic accepts), 32 over 2 a group of 16, 64 over 2 of 32
GROUPED_CELL = dict(heads=16, head_dim=64, slots=8, blocks=65, block_len=16,
                    chunk=32, max_seq_len=128)


@pytest.mark.parametrize("heads,kv_heads,program,kernel", [
    pytest.param(16, 2, "decode_tick", True, id="group8-tick"),
    pytest.param(32, 2, "decode_tick", True, id="group16-tick"),
    pytest.param(64, 2, "decode_tick", False, id="group32-tick"),
    pytest.param(16, 2, "chunk_prefill[k=4,w=8]", False, id="group8-chunk"),
])
def test_the_rule_counts_the_rows_a_grouped_head_brings(
        v5e, monkeypatch, heads, kv_heads, program, kernel):
    """The rule's boundary, read through a program: a narrow head's
    query group folds into the kernel's rows, so a tick of 8 or of 16 query
    heads a narrow head (16 rows, ``KERNEL_MAX_ROWS`` since PR 44's
    reading) compiles the fused kernel, a tick of 32 a narrow head does
    not, and a chunk's 8 x 32 rows do not."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lowered, _ = _engine_program(v5e, program, dict(GROUPED_CELL, heads=heads),
                                 num_kv_heads=kv_heads)
    text = lowered.compile().as_text()
    assert ("paged_decode_attn" in text) == kernel
    assert ('custom_call_target="tpu_custom_call"' in text) == kernel


# ---- the tile a grid step of the tick's kernel stages (PR 30) --------------


@pytest.mark.parametrize("cell,kv_dtype", [
    ("chat-backlog", None), ("chat-backlog", "int8"),
    ("reason-backlog", None), ("reason-backlog", "int8"),
])
def test_the_tick_compiles_with_a_tile_of_blocks_a_grid_step(
        v5e, monkeypatch, cell, kv_dtype):
    """Both serving cells' decode ticks with the kernel as the rule sizes
    it for their tables: eight blocks of 16 a grid step. Mosaic takes the
    kernel's DMAs of whole pool blocks at rows of 1,024 and of 2,048
    lanes, bfloat16 and int8 (whose scale siblings, 16 lanes wide, ride
    the pipeline: a DMA of a block that narrow is refused), the program is
    still ``jit_decode_tick`` and the kernel in it ``paged_decode_attn``,
    one a layer, its 16 heads folded into one product a tile."""
    from pytorch_distributed_tpu.ops.paged_flash import (
        heads_folded,
        staged_row_bytes,
        tile_blocks,
    )

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    c, block = CELLS[cell]
    lowered, leaves = _engine_program(v5e, "decode_tick", c,
                                      kv_dtype=kv_dtype, **block)
    layers = len(leaves) // (4 if kv_dtype else 2)  # the program's two
    row_bytes = staged_row_bytes(*leaves) // layers
    w = -(-c["max_seq_len"] // c["block_len"])
    assert tile_blocks(w, c["block_len"], row_bytes) == 8
    assert lowered.as_text().startswith("module @jit_decode_tick ")
    text = lowered.compile().as_text()
    assert text.startswith("HloModule jit_decode_tick,")
    reads = _kernel_reads(text)
    assert len(reads) == 2, text  # one a layer
    # every narrow head of a tile in ONE product (``heads_folded``): the
    # kernel's query operand is the lane's block-diagonal one, 16 (row,
    # head) columns of all heads' lanes, and its output lane-dense
    assert heads_folded(c["heads"], 1) == c["heads"] == 16
    slots, lanes = c["slots"], c["heads"] * c["head_dim"]
    assert all(f"bf16[{slots},16,{lanes}]" in x
               and f"bf16[{slots},8,{lanes}]" in x.split(" custom-call(")[0]
               for x in reads), reads


#: sha256 (12 hex digits) of the StableHLO text of chunk programs as they
#: lower for a described v5e where the backend answers ``tpu``
#: (``_engine_program``: two layers, 512 tokens). Taken again in PR 39,
#: which gave every tick program ONE packed int32 operand where it took
#: six (the model's part of the text did not move; PR 30's parent read
#: 37ed79f1586a, b63225f480f7 and 738ae8911195). The chunk programs
#: gather dense, so nothing in ``ops/paged_flash.py`` may move them: the
#: serving cells' 30 + 2 compile-cache entries stay valid and
#: ``prefill_chunk_device_ms`` is the control that does not move. A PR
#: that means to change a chunk program records new digests here.
CHUNK_DIGESTS = {
    ("chat-backlog", (4, 8)): "99c83f9196da",
    ("chat-backlog", (1, 2)): "19fad1292ee4",
    ("reason-backlog", (2, 16)): "17063e500523",
}


@pytest.mark.parametrize("cell,bucket", sorted(CHUNK_DIGESTS))
def test_the_chunk_programs_lower_to_the_parents_text(v5e, monkeypatch, cell,
                                                      bucket):
    import hashlib

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    c, block = CELLS[cell]
    k, w = bucket
    lowered, _ = _engine_program(v5e, f"chunk_prefill[k={k},w={w}]", c,
                                 bucket=bucket, **block)
    text = lowered.as_text()
    assert "tpu_custom_call" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:12] == CHUNK_DIGESTS[
        cell, bucket]


# ---- the zaya block's served programs (PR 31) ------------------------------

#: zaya1-8b.reason-long-backlog's attention, experts, pool and slots
#: (perfbench/configs, perfbench/cells), on 2 layers and a small vocabulary
ZAYA_CELL = dict(slots=128, blocks=9217, block_len=16, chunk=128,
                 max_seq_len=2560)
ZAYA_BLOCK = dict(
    embed_dim=2048, num_heads=8, num_kv_heads=2, head_dim=128,
    attn_kind="cca", pos_embedding="rope", rope_theta=5e6, rotary_share=0.5,
    norm="rmsnorm", norm_eps=1e-5, use_bias=False, tie_embeddings=True,
    residual_scaling=True, n_experts=16, moe_every=1, moe_kind="dropless",
    moe_dim=2048, router_dim=256)


@pytest.mark.parametrize("program", ["decode_tick", "chunk_prefill[k=4,w=32]"])
def test_the_zaya_programs_compile_for_the_chip(v5e, monkeypatch, program):
    """The tick reads K/V of 2 narrow heads of 128 through the fused
    kernel's grouped fold (4 query rows a narrow head, rows of 256 lanes:
    Mosaic takes the DMAs of its 512-byte pool rows), once a layer, and
    the chunk program gathers dense; both run the experts as the repo's
    grouped products (``grouped_matmul``, two a layer: gate and up side by
    side, and down; the tick's 128 pair rows ONE row tile, the chunk
    program's 512 four) and neither moves a pool-sized array or an
    expert stack."""
    from pytorch_distributed_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )
    from pytorch_distributed_tpu.serving.engine import PagedEngine
    from pytorch_distributed_tpu.serving.kv_pool import init_paged_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    c = ZAYA_CELL
    cfg = TransformerConfig(
        vocab_size=512, num_layers=2, max_seq_len=c["max_seq_len"],
        dropout=0.0, dtype=jnp.bfloat16, attention="dense", **ZAYA_BLOCK)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16),
        jax.eval_shape(TransformerLM(cfg).init, jax.random.key(0),
                       jnp.zeros((1, 8), jnp.int32))["params"])
    n = c["slots"]
    eng = PagedEngine(cfg, params, n, n_blocks=2, block_len=c["block_len"],
                      prefill_chunk=c["chunk"], chunk_bucket_floor=(2, 32),
                      max_chunk_jobs=4)
    assert eng.gather_impl == "pallas" and eng.tile_blocks == 8
    pool = jax.eval_shape(
        lambda p: init_paged_cache(cfg, p, c["blocks"], c["block_len"],
                                   n_slots=n), params)
    one = SingleDeviceSharding(v5e.devices[0])
    if program == "decode_tick":
        fn, operands = _tick_operands(eng)
    else:
        fn, operands = _chunk_operands(eng, 4, 32)
        assert eng.chunk_program_name(4, 32) == program
    args = (params, pool, eng.logits) + operands
    compiled = fn.lower(*jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
        args)).compile()
    text = compiled.as_text()
    calls = [line.split(" = ")[0].strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    reads = [x for x in calls if "paged_decode_attn" in x]
    assert len(reads) == (2 if program == "decode_tick" else 0), calls
    # gate and up side by side, and down, in each of the two layers
    assert _grouped_products(
        text, n if program == "decode_tick" else 4 * c["chunk"], 16) == [
            (2048, 4096), (2048, 2048)] * 2, calls
    # the tick's two narrow heads fold into one product a tile: the
    # kernel's query is block-diagonal, 2 x 4 (row, head) columns (padded
    # to 16) of both heads' 256 lanes
    assert all("bf16[128,16,256]" in x for x in _kernel_reads(text))
    # the counts come back beside what the programs returned before
    shapes = [tuple(s.shape) for s in jax.tree.leaves(
        jax.eval_shape(fn, *args))]
    assert shapes[-1] == (2, 16)
    leaf = jax.tree.leaves(pool)[0]
    stacks = params["block0"]["moe"]
    moved = [m.group(1) for m in re.finditer(
        r"(\S+) = \w+\[([\d,]+)\]\S* (?:copy|transpose)\(", text)
        if math.prod(map(int, m.group(2).split(","))) in (
            leaf.size, stacks["w_gate_up"].size, stacks["w_down"].size)]
    assert not moved, moved


# ---- the ling stack's served programs (PR 36) ------------------------------

#: ling-3.0-flash.doc-reason-backlog's attention, experts, state, pool and
#: slots (perfbench/configs, perfbench/cells), on a period of 2 layers (one
#: linear-attention layer with a dense MLP, one latent layer with experts)
#: and a small vocabulary
LING_CELL = dict(slots=256, blocks=65537, block_len=16, chunk=128,
                 max_seq_len=3072)
LING_BLOCK = dict(
    embed_dim=2560, num_heads=32, head_dim=128, attn_kind="kda",
    layer_group_size=2, kv_lora_rank=512, qk_rope_head_dim=64,
    pos_embedding="rope", rope_theta=6e6,
    norm="rmsnorm", norm_eps=1e-6, use_bias=False, mlp="swiglu", mlp_dim=6144,
    n_experts=512, moe_every=1, moe_kind="dropless", moe_router="sigmoid",
    moe_top_k=8, moe_n_group=8, moe_topk_group=4, moe_routed_scale=2.5,
    moe_dim=768, moe_shared_dim=768, experts_held=(0, 128),
    first_k_dense_replace=1)


@pytest.mark.parametrize("program", ["decode_tick",
                                     "chunk_prefill[k=4,w=128]"])
def test_the_ling_programs_compile_for_the_chip(v5e, monkeypatch, program):
    """The tick reads the latent pool through the fused kernel, the one
    640-lane leaf as keys and as values with 32 query rows on its one
    narrow head (the rule answers the kernel because the dense gather would
    write 2 GB), and no float32 array of [lanes, table positions, ...]
    exists; the chunk program gathers dense over its own table slice. Both
    run the held experts as two ``grouped_matmul`` calls (row tiles of 128
    of the 2,048 or 4,096 pair rows), update the float32 state where it lies
    (no copy of a state leaf) and move no pool-sized array and no expert
    stack."""
    from pytorch_distributed_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )
    from pytorch_distributed_tpu.serving.engine import PagedEngine
    from pytorch_distributed_tpu.serving.kv_pool import init_paged_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    c = LING_CELL
    cfg = TransformerConfig(
        vocab_size=512, num_layers=2, max_seq_len=c["max_seq_len"],
        dropout=0.0, dtype=jnp.bfloat16, attention="dense", **LING_BLOCK)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16),
        jax.eval_shape(TransformerLM(cfg).init, jax.random.key(0),
                       jnp.zeros((1, 8), jnp.int32))["params"])
    n = c["slots"]
    eng = PagedEngine(cfg, params, n, n_blocks=2, block_len=c["block_len"],
                      prefill_chunk=c["chunk"], chunk_bucket_floor=(2, 128),
                      max_chunk_jobs=4)
    assert eng.gather_impl == "pallas" and eng.tile_blocks == 8
    pool = jax.eval_shape(
        lambda p: init_paged_cache(cfg, p, c["blocks"], c["block_len"],
                                   n_slots=n), params)
    state = pool["block0"]["attn"]["state"]
    latent = pool["block1"]["attn"]["latent"]
    assert state.shape == (n + 1, 32, 128, 128) and state.dtype == jnp.float32
    assert latent.shape == (c["blocks"], c["block_len"], 640)
    one = SingleDeviceSharding(v5e.devices[0])
    if program == "decode_tick":
        fn, operands = _tick_operands(eng)
    else:
        fn, operands = _chunk_operands(eng, 4, 128)
        assert eng.chunk_program_name(4, 128) == program
    args = (params, pool, eng.logits) + operands
    lowered = fn.lower(*jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
        args))
    compiled = lowered.compile()
    text = compiled.as_text()
    calls = [line.split(" = ")[0].strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    reads = [x for x in calls if "paged_decode_attn" in x]
    assert len(reads) == (1 if program == "decode_tick" else 0), calls
    # the tick's state layer updates its leaf through the kernel, ONE call
    # whose result aliases the leaf; the chunk program, which runs ``BLOCK``
    # positions a step, lowers to the parent's text
    assert _lowers_to_the_parents_text(lowered, "ling", program) == (
        program != "decode_tick")
    assert eng.state_update == "pallas"
    assert _state_updates(text, state, n) == (program == "decode_tick"), calls
    # gate and up side by side, and down; eight pairs a token
    assert _grouped_products(
        text, 8 * (n if program == "decode_tick" else 4 * c["chunk"]),
        128) == [(2560, 1536), (768, 2560)], calls
    # the latent row's ONE narrow head keeps the loop's body
    # (``heads_folded``): its query goes in a slab a head, 32 rows of 640
    assert all("bf16[256,1,32,640]" in x for x in _kernel_reads(text))
    # the counts come back beside what the programs returned before: one
    # expert layer, the experts held
    shapes = [tuple(s.shape) for s in jax.tree.leaves(
        jax.eval_shape(fn, *args))]
    assert shapes[-1] == (1, 128)
    # neither a state leaf, the latent pool nor an expert stack is copied
    # or transposed
    stacks = params["block1"]["moe"]
    moved = [m.group(1) for m in re.finditer(
        r"(\S+) = \w+\[([\d,]+)\]\S* (?:copy|transpose)\(", text)
        if math.prod(map(int, m.group(2).split(","))) in (
            state.size, latent.size, stacks["w_gate_up"].size,
            stacks["w_down"].size)]
    assert not moved, moved
    # the tick gathers no lane's table: nothing of [lanes, positions, ...]
    rows = c["max_seq_len"]
    assert not re.search(rf"f32\[{n},(?:{rows}|{rows // 16},16),", text)
    # and what it holds beside its arguments is small: the state is
    # donated and updated in place
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= state.size * 4 + latent.size * 2
    assert memory.temp_size_in_bytes < 1 << 30


# ---- the qwen3-next stack's served programs (PR 41) ------------------------

#: qwen3-next-80b-a3b.doc-chat-backlog's attention, experts, state, pool and
#: slots (perfbench/configs, perfbench/cells), on a period of 2 layers (one
#: gated delta-rule layer, one full layer) and a small vocabulary
QWEN_CELL = dict(slots=256, blocks=65537, block_len=16, chunk=128,
                 max_seq_len=4864)
QWEN_BLOCK = dict(
    embed_dim=2048, num_heads=16, num_kv_heads=2, head_dim=256,
    attn_kind="gdn", layer_group_size=2, full_attn_kind="mha",
    linear_num_heads=32, linear_num_key_heads=16, linear_head_dim=128,
    qk_norm=True, attn_gate=True, rotary_share=0.25, pos_embedding="rope",
    rope_theta=1e7, norm="rmsnorm", norm_eps=1e-6, use_bias=False,
    mlp="swiglu", n_experts=512, moe_every=1, moe_kind="dropless",
    moe_router="softmax", moe_top_k=10, moe_dim=512, moe_shared_dim=512,
    moe_shared_gate=True, experts_held=(0, 256))


@pytest.mark.parametrize("program", ["decode_tick",
                                     "chunk_prefill[k=16,w=256]"])
def test_the_qwen3_next_programs_compile_for_the_chip(v5e, monkeypatch,
                                                      program):
    """The tick reads the full layer's REAL keys and values through the
    fused kernel's folded body (2 narrow heads x 8 query rows: 16 (row,
    head) columns over K and V tiles of 512 lanes); the chunk program
    gathers dense over its own table slice. Both run the held experts as
    two ``grouped_matmul`` calls a layer (row tiles of 128 of the 2,560 or
    20,480 pair rows), update the float32 state where it lies (no copy of a
    state leaf) and move no pool-sized array and no expert stack."""
    from pytorch_distributed_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )
    from pytorch_distributed_tpu.serving.engine import PagedEngine
    from pytorch_distributed_tpu.serving.kv_pool import init_paged_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    c = QWEN_CELL
    cfg = TransformerConfig(
        vocab_size=512, num_layers=2, max_seq_len=c["max_seq_len"],
        dropout=0.0, dtype=jnp.bfloat16, attention="dense", **QWEN_BLOCK)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16),
        jax.eval_shape(TransformerLM(cfg).init, jax.random.key(0),
                       jnp.zeros((1, 8), jnp.int32))["params"])
    n = c["slots"]
    eng = PagedEngine(cfg, params, n, n_blocks=2, block_len=c["block_len"],
                      prefill_chunk=c["chunk"], chunk_bucket_floor=(16, 256),
                      max_chunk_jobs=16)
    assert eng.gather_impl == "pallas" and eng.tile_blocks == 8
    assert eng.heads_folded == 2
    # ONE chunk program for prompts up to 4,096 positions (a resumed
    # request past them would take the table's whole width)
    assert eng.chunk_buckets() == [(16, 256), (16, 304)]
    pool = jax.eval_shape(
        lambda p: init_paged_cache(cfg, p, c["blocks"], c["block_len"],
                                   n_slots=n), params)
    state = pool["block0"]["attn"]["state"]
    keys = pool["block1"]["attn"]["key"]
    assert state.shape == (n + 1, 32, 128, 128) and state.dtype == jnp.float32
    assert pool["block0"]["attn"]["conv"].shape == (n + 1, 3, 8192)
    assert keys.shape == (c["blocks"], c["block_len"], 512)
    one = SingleDeviceSharding(v5e.devices[0])
    if program == "decode_tick":
        fn, operands = _tick_operands(eng)
    else:
        fn, operands = _chunk_operands(eng, 16, 256)
        assert eng.chunk_program_name(16, 256) == program
    args = (params, pool, eng.logits) + operands
    lowered = fn.lower(*jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
        args))
    compiled = lowered.compile()
    text = compiled.as_text()
    calls = [line.split(" = ")[0].strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    reads = [x for x in calls if "paged_decode_attn" in x]
    assert len(reads) == (1 if program == "decode_tick" else 0), calls
    # the tick's state layer updates its leaf through the kernel, ONE call
    # whose result aliases the leaf; the chunk program, which runs ``BLOCK``
    # positions a step, lowers to the parent's text
    assert _lowers_to_the_parents_text(lowered, "qwen3-next", program) == (
        program != "decode_tick")
    assert eng.state_update == "pallas"
    assert _state_updates(text, state, n) == (program == "decode_tick"), calls
    # gate and up side by side, and down, in each of the two layers; ten
    # pairs a token
    assert _grouped_products(
        text, 10 * (n if program == "decode_tick" else 16 * c["chunk"]),
        256) == [(2048, 1024), (512, 2048)] * 2, calls
    # the folded body: the query is block-diagonal, 2 x 8 (row, head)
    # columns of both heads' 512 lanes, and the output leaves lane-dense
    assert all("bf16[256,16,512]" in x and "bf16[256,8,512]" in x
               for x in _kernel_reads(text))
    # the counts come back beside what the programs returned before: two
    # expert layers, the experts held
    shapes = [tuple(s.shape) for s in jax.tree.leaves(
        jax.eval_shape(fn, *args))]
    assert shapes[-1] == (2, 256)
    # neither a state leaf, a pool nor an expert stack is copied or
    # transposed
    stacks = params["block0"]["moe"]
    moved = [m.group(1) for m in re.finditer(
        r"(\S+) = \w+\[([\d,]+)\]\S* (?:copy|transpose)\(", text)
        if math.prod(map(int, m.group(2).split(","))) in (
            state.size, keys.size, stacks["w_gate_up"].size,
            stacks["w_down"].size)]
    assert not moved, moved
    # the tick gathers no lane's table: nothing of [lanes, positions, ...]
    rows = c["max_seq_len"]
    assert not re.search(rf"f32\[{n},(?:{rows}|{rows // 16},16),", text)
    # and what it holds beside its arguments is small: state and pools are
    # donated and updated in place
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= state.size * 4 + 2 * keys.size * 2
    assert memory.temp_size_in_bytes < (
        1 << 27 if program == "decode_tick" else 3 << 29)


# ---- the nemotron-h stack's served programs (PR 44) ------------------------

#: nemotron-3-nano-30b-a3b.assistant-backlog's Mamba-2 mixer, experts,
#: attention, state, pool and slots (perfbench/configs, perfbench/cells), on
#: one block of each kind and a small vocabulary
NEMO_CELL = dict(slots=256, blocks=40961, block_len=16, chunk=128,
                 max_seq_len=2560)
NEMO_BLOCK = dict(
    layer_pattern="ME*", embed_dim=2688, num_heads=32, num_kv_heads=2,
    head_dim=128, pos_embedding="none", norm="rmsnorm", norm_eps=1e-5,
    use_bias=False, mlp="relu2", mamba_num_heads=64, mamba_head_dim=64,
    mamba_state_size=128, mamba_n_groups=8, n_experts=128,
    moe_kind="dropless", moe_router="sigmoid", moe_top_k=6,
    moe_routed_scale=2.5, moe_dim=1856, moe_shared_dim=3712,
    experts_held=(0, 64))


@pytest.mark.parametrize("program", ["decode_tick",
                                     "chunk_prefill[k=8,w=128]"])
def test_the_nemotron_h_programs_compile_for_the_chip(v5e, monkeypatch,
                                                      program):
    """The tick reads the attention block's REAL keys and values through the
    fused kernel's folded body (2 narrow heads x 16 query rows: 32 (row,
    head) columns over K and V tiles of 256 lanes); the chunk program
    gathers dense over its own table slice. Both run the held experts as
    TWO ``grouped_matmul`` calls a block (row tiles of 128 of the 1,536 or
    6,144 pair rows; the stacks are still held 3,072 x 2,048, the widths
    XLA's product wanted: ``grouped_width``), update the float32 state where
    it lies (no copy of a state leaf) and move no pool-sized array and no
    expert stack; the expert block owns no cache leaf."""
    from pytorch_distributed_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )
    from pytorch_distributed_tpu.serving.engine import PagedEngine
    from pytorch_distributed_tpu.serving.kv_pool import init_paged_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    c = NEMO_CELL
    cfg = TransformerConfig(
        vocab_size=512, num_layers=3, max_seq_len=c["max_seq_len"],
        dropout=0.0, dtype=jnp.bfloat16, attention="dense", **NEMO_BLOCK)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16),
        jax.eval_shape(TransformerLM(cfg).init, jax.random.key(0),
                       jnp.zeros((1, 8), jnp.int32))["params"])
    assert params["block1"]["moe"]["w_up"].shape == (64, 3072, 2048)
    assert params["block1"]["moe"]["w_down"].shape == (64, 2048, 3072)
    n = c["slots"]
    eng = PagedEngine(cfg, params, n, n_blocks=2, block_len=c["block_len"],
                      prefill_chunk=c["chunk"], chunk_bucket_floor=(8, 128),
                      max_chunk_jobs=8)
    assert eng.gather_impl == "pallas" and eng.tile_blocks == 8
    assert eng.heads_folded == 2
    # ONE chunk program for prompts up to 2,048 positions (a resumed
    # request past them would take the table's whole width)
    assert eng.chunk_buckets() == [(8, 128), (8, 160)]
    pool = jax.eval_shape(
        lambda p: init_paged_cache(cfg, p, c["blocks"], c["block_len"],
                                   n_slots=n), params)
    assert sorted(pool) == ["block0", "block2"]  # the experts hold nothing
    state = pool["block0"]["attn"]["state"]
    keys = pool["block2"]["attn"]["key"]
    assert state.shape == (n + 1, 64, 64, 128) and state.dtype == jnp.float32
    assert pool["block0"]["attn"]["conv"].shape == (n + 1, 3, 6144)
    assert keys.shape == (c["blocks"], c["block_len"], 256)
    one = SingleDeviceSharding(v5e.devices[0])
    if program == "decode_tick":
        fn, operands = _tick_operands(eng)
    else:
        fn, operands = _chunk_operands(eng, 8, 128)
        assert eng.chunk_program_name(8, 128) == program
    args = (params, pool, eng.logits) + operands
    lowered = fn.lower(*jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
        args))
    compiled = lowered.compile()
    text = compiled.as_text()
    calls = [line.split(" = ")[0].strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    reads = [x for x in calls if "paged_decode_attn" in x]
    assert len(reads) == (1 if program == "decode_tick" else 0), calls
    # ``Mamba2Mixer`` keeps ``ssm_update``: both programs lower to the
    # parent's text and hold no kernel of the state's
    assert _lowers_to_the_parents_text(lowered, "nemotron-h", program)
    assert eng.state_update == "xla"
    assert _state_updates(text, state, n) == 0, calls
    # up, and down: no gate matrix; six pairs a token
    assert _grouped_products(
        text, 6 * (n if program == "decode_tick" else 8 * c["chunk"]),
        64) == [(3072, 2048), (2048, 3072)], calls
    # the folded body: the query is block-diagonal, 2 x 16 (row, head)
    # columns of both heads' 256 lanes, and the output leaves lane-dense
    assert all("bf16[256,32,256]" in x and "bf16[256,16,256]" in x
               for x in _kernel_reads(text))
    # the counts come back beside what the programs returned before: one
    # expert block, the experts held
    shapes = [tuple(s.shape) for s in jax.tree.leaves(
        jax.eval_shape(fn, *args))]
    assert shapes[-1] == (1, 64)
    # neither a state leaf, a pool nor an expert stack is copied or
    # transposed
    stack = params["block1"]["moe"]["w_up"]
    moved = [m.group(1) for m in re.finditer(
        r"(\S+) = \w+\[([\d,]+)\]\S* (?:copy|transpose)\(", text)
        if math.prod(map(int, m.group(2).split(","))) in (
            state.size, keys.size, stack.size)]
    assert not moved, moved
    # the tick gathers no lane's table: nothing of [lanes, positions, ...]
    rows = c["max_seq_len"]
    if program == "decode_tick":
        assert not re.search(rf"f32\[{n},(?:{rows}|{rows // 16},16),", text)
    # and what it holds beside its arguments is small: state and pools are
    # donated and updated in place
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= state.size * 4 + 2 * keys.size * 2
    assert memory.temp_size_in_bytes < (
        1 << 28 if program == "decode_tick" else 3 << 29)


# ---- the glm stack's served programs (PR 50) -------------------------------

#: glm-4.7-flash.doc-chat-backlog's attention, experts, pool and slots
#: (perfbench/configs, perfbench/cells), on 2 layers (the leading dense one and
#: one expert layer, every expert held) and a small vocabulary
GLM_CELL = dict(slots=192, blocks=32769, block_len=16, chunk=128,
                max_seq_len=4864)
GLM_BLOCK = dict(
    embed_dim=2048, num_heads=20, head_dim=192, attn_kind="mla",
    q_lora_rank=768, kv_lora_rank=512, qk_rope_head_dim=64, v_head_dim=256,
    mla_head_gate=False, pos_embedding="rope", rope_theta=1e6,
    norm="rmsnorm", norm_eps=1e-5, use_bias=False, mlp="swiglu",
    mlp_dim=10240, n_experts=64, moe_every=1, moe_kind="dropless",
    moe_router="sigmoid", moe_top_k=4, moe_routed_scale=1.8, moe_dim=1536,
    moe_shared_dim=1536, first_k_dense_replace=1)
#: sha256 (12 hex digits) of the StableHLO text of its two programs, as
#: ``_lowers_to_the_parents_text`` takes it (each kernel's serialized body
#: blanked), recorded by the PR that brought the configuration: a PR that
#: means to change one records a new digest.
GLM_DIGESTS = {
    "decode_tick": "cb10e5e875c7",
    "chunk_prefill[k=16,w=256]": "77d5d83d1842",
}


@pytest.mark.parametrize("program", sorted(GLM_DIGESTS))
def test_the_glm_programs_compile_for_the_chip(v5e, monkeypatch, program):
    """Both layers are latent: the tick reads each layer's 640-lane leaf
    through the fused kernel, as keys and as values, with 20 query rows on
    its one narrow head, which the kernel pads to 24 (whole 8-row sublane
    tiles; Mosaic takes the 24-row slab though a bfloat16 tile packs 16), and
    no float32 array of [lanes, table positions, ...] exists; the 16-job
    chunk program gathers dense over its own table slice. Both run all 64
    experts as two ``grouped_matmul`` calls (gate and up side by side, and
    down; row tiles of 128 of the 768 or 8,192 pair rows), hold no per-slot
    leaf and move no pool-sized array and no expert stack."""
    import hashlib

    from pytorch_distributed_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )
    from pytorch_distributed_tpu.serving.engine import PagedEngine
    from pytorch_distributed_tpu.serving.kv_pool import init_paged_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    c = GLM_CELL
    cfg = TransformerConfig(
        vocab_size=512, num_layers=2, max_seq_len=c["max_seq_len"],
        dropout=0.0, dtype=jnp.bfloat16, attention="dense", **GLM_BLOCK)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16),
        jax.eval_shape(TransformerLM(cfg).init, jax.random.key(0),
                       jnp.zeros((1, 8), jnp.int32))["params"])
    n = c["slots"]
    eng = PagedEngine(cfg, params, n, n_blocks=2, block_len=c["block_len"],
                      prefill_chunk=c["chunk"], chunk_bucket_floor=(16, 256),
                      max_chunk_jobs=16)
    assert eng.gather_impl == "pallas" and eng.tile_blocks == 8
    assert eng.heads_folded == 1 and eng.state_update == ""
    assert eng.grouped_rows == 128
    pool = jax.eval_shape(
        lambda p: init_paged_cache(cfg, p, c["blocks"], c["block_len"],
                                   n_slots=n), params)
    leaves = jax.tree.leaves(pool)
    assert [x.shape for x in leaves] == [
        (c["blocks"], c["block_len"], 640)] * 2  # and no per-slot leaf
    one = SingleDeviceSharding(v5e.devices[0])
    if program == "decode_tick":
        fn, operands = _tick_operands(eng)
    else:
        fn, operands = _chunk_operands(eng, 16, 256)
        assert eng.chunk_program_name(16, 256) == program
    args = (params, pool, eng.logits) + operands
    lowered = fn.lower(*jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
        args))
    digest = hashlib.sha256(re.sub(
        r'backend_config = "[^"]*"', 'backend_config = ""',
        lowered.as_text()).encode()).hexdigest()[:12]
    assert digest == GLM_DIGESTS[program], (program, digest)
    compiled = lowered.compile()
    text = compiled.as_text()
    reads = _kernel_reads(text)
    assert len(reads) == (2 if program == "decode_tick" else 0), reads
    # the row's ONE narrow head keeps the loop's body (``heads_folded``):
    # its query goes in a slab a head, 20 rows padded to 24, of 640 lanes
    assert all("bf16[192,1,24,640]" in x for x in reads)
    # gate and up side by side, and down; four pairs a token, 64 experts
    assert _grouped_products(
        text, 4 * (n if program == "decode_tick" else 16 * c["chunk"]),
        64) == [(2048, 3072), (1536, 2048)]
    # the counts come back beside what the programs returned before: one
    # expert layer, every expert
    shapes = [tuple(s.shape) for s in jax.tree.leaves(
        jax.eval_shape(fn, *args))]
    assert shapes[-1] == (1, 64)
    stacks = params["block1"]["moe"]
    moved = [m.group(1) for m in re.finditer(
        r"(\S+) = \w+\[([\d,]+)\]\S* (?:copy|transpose)\(", text)
        if math.prod(map(int, m.group(2).split(","))) in (
            leaves[0].size, stacks["w_gate_up"].size, stacks["w_down"].size)]
    assert not moved, moved
    # the tick gathers no lane's table: nothing of [lanes, positions, ...]
    rows = c["max_seq_len"]
    if program == "decode_tick":
        assert not re.search(rf"f32\[{n},(?:{rows}|{rows // 16},16),", text)
    # and what it holds beside its arguments is small: the pools are donated
    # and updated in place
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 2 * leaves[0].size * 2
    assert memory.temp_size_in_bytes < (
        1 << 28 if program == "decode_tick" else 1 << 30)
