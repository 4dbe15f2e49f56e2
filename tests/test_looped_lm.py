"""A looped decoder (``ut_steps`` > 1: the layer stack run several times
over the same parameters, a K/V entry per (pass, layer)) and the block
description it needs (RMSNorm, SwiGLU, sandwich norms, bias-free
projections), against the plain reference ``perfbench/references/ouro.py``
at a toy size on the CPU.

Tolerances. Program and reference are both float32 here and differ only in
the order of their sums: logits of size 0.3-0.5 agree to 1e-6 or so, and
``TOL`` = 2e-5 leaves room for another BLAS. The float8 control (every
matrix operand cast to scaled e4m3, ``harness/weights.py``) moves the same
logits by 1e-2: it must break ``TOL``, or the comparison would not notice
a precision lost.
"""

import dataclasses
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from conftest import SERVED_TINY, seeded_params  # noqa: E402

from perfbench.harness.weights import CASTS  # noqa: E402
from perfbench.references import ouro  # noqa: E402
from pytorch_distributed_tpu.models.generate import generate, init_cache  # noqa: E402
from pytorch_distributed_tpu.models.transformer import (  # noqa: E402
    Attention,
    TransformerConfig,
    TransformerLM,
    tiny_config,
)
from pytorch_distributed_tpu.serving import Scheduler  # noqa: E402
from pytorch_distributed_tpu.serving.engine import ChunkJob, PagedEngine  # noqa: E402
from pytorch_distributed_tpu.serving.kv_pool import (  # noqa: E402
    init_paged_cache,
    pool_block_bytes,
    pool_leaf_shape,
)
from pytorch_distributed_tpu.telemetry import spans  # noqa: E402

TOL = 2e-5
LOOPED = SERVED_TINY["ouro"]
U, THETA = LOOPED["ut_steps"], LOOPED["rope_theta"]


def looped_config(**over) -> TransformerConfig:
    return tiny_config(**dict(LOOPED, **over))


def seeded(cfg, seed=5):
    params = seeded_params(ouro, cfg, seed)
    # a gate that is not 1/2 everywhere
    params["exit_gate"]["bias"] = params["exit_gate"]["bias"] + 0.3
    return params


def reference_logits(params, tokens, cast=None):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ouro.logits(params, jnp.asarray(tokens), cast))


@pytest.fixture(scope="module")
def model():
    cfg = looped_config()
    # the reference learns passes and RoPE base as the benchmark tells it
    ouro.configure(dict(ut_steps=U, rope_theta=THETA))
    return cfg, seeded(cfg)


def test_the_tree_has_one_stack_and_a_gate(model):
    cfg, params = model
    assert sorted(params) == ["block0", "block1", "exit_gate", "lm_head",
                              "ln_f", "wte"]
    assert sorted(params["block0"]) == ["attn", "ln1", "ln1_post", "ln2",
                                        "ln2_post", "mlp_down", "mlp_gate",
                                        "mlp_up"]
    assert list(params["block0"]["ln1"]) == ["scale"]  # RMSNorm: no bias
    assert list(params["block0"]["attn"]["qkv"]) == ["kernel"]
    assert params["block0"]["mlp_gate"]["kernel"].shape == (32, 48)
    assert params["exit_gate"]["kernel"].shape == (32, 1)


def test_full_forward_and_gates_match_the_reference(model):
    cfg, params = model
    tokens = jax.random.randint(jax.random.key(1), (2, 12), 1, 128)
    with jax.default_matmul_precision("highest"):
        logits, gates = TransformerLM(cfg).apply(
            {"params": params}, tokens, train=False, return_gates=True)
        _, lams = ouro.passes(params, tokens)
    want = reference_logits(params, tokens)
    assert gates.shape == (U, 2, 12)
    assert np.abs(np.asarray(logits) - want).max() <= TOL
    assert np.abs(np.asarray(gates) - np.asarray(lams)).max() <= TOL
    assert np.ptp(np.asarray(lams)) > 1e-3  # the gates say something
    # the lower-precision control is outside the tolerance
    control = reference_logits(params, tokens, CASTS["fp8"])
    assert np.abs(control - want).max() > 100 * TOL


def test_every_pass_changes_the_result(model):
    """Fewer passes is another function: the loop is not an identity."""
    cfg, params = model
    tokens = jax.random.randint(jax.random.key(2), (1, 9), 1, 128)
    full = TransformerLM(cfg).apply({"params": params}, tokens, train=False)
    fewer = TransformerLM(dataclasses.replace(cfg, ut_steps=U - 1)).apply(
        {"params": params}, tokens, train=False)
    assert np.abs(np.asarray(full) - np.asarray(fewer)).max() > 1e-3


@pytest.mark.parametrize("gather_impl", ["dense", "pallas"])
def test_chunked_prefill_then_decode_matches_the_full_forward(
        model, steer_paged_read, gather_impl):
    """Two chunks of prefill and three decode ticks through the paged pool
    (chunk and tick programs, the loop inside each) leave, at every step,
    the logits the reference's one full forward gives at that position."""
    cfg, params = model
    chunk, new = 8, 3
    steer_paged_read(gather_impl)
    eng = PagedEngine(cfg, params, 3, n_blocks=13, block_len=8,
                      prefill_chunk=chunk)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 128, size=n).astype(np.int32)
               for n in (13, 16)]
    for slot, p in enumerate(prompts):
        assert eng.admit(slot, len(p), new)
    got = [[] for _ in prompts]
    for start in (0, chunk):
        jobs = []
        for slot, p in enumerate(prompts):
            seg = np.zeros((chunk,), np.int32)
            seg[:len(p[start:start + chunk])] = p[start:start + chunk]
            last = start + chunk >= len(p)
            jobs.append(ChunkJob(slot, seg, start, last,
                                 len(p) - 1 - start if last else 0))
        eng.run_chunks(jobs)
    positions = np.array([len(p) for p in prompts] + [0], np.int32)
    active = positions > 0
    streams = [list(p) for p in prompts]
    for slot in range(len(prompts)):
        got[slot].append(np.asarray(eng.logits[slot]))
    for _ in range(new):
        tokens, positions = eng.decode(positions, active, jax.random.key(0))
        for slot in range(len(prompts)):
            streams[slot].append(int(tokens[slot]))
            got[slot].append(np.asarray(eng.logits[slot]))
    for slot, p in enumerate(prompts):
        want = reference_logits(params, np.asarray(streams[slot])[None])[0]
        control = reference_logits(params, np.asarray(streams[slot])[None],
                                   CASTS["fp8"])[0]
        rows = want[len(p) - 1:]
        assert len(rows) == len(got[slot]) == new + 1
        assert np.abs(np.stack(got[slot]) - rows).max() <= TOL
        assert np.abs(control[len(p) - 1:] - rows).max() > 100 * TOL


def test_the_scheduler_serves_it_and_streams_equal_the_full_forward(model):
    cfg, params = model
    sched = Scheduler(cfg, params, n_slots=3, n_blocks=20, block_len=8,
                      prefill_chunk=8)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 128, size=n).astype(np.int32)
               for n in (5, 13, 9, 20)]
    rids = [sched.submit(p, 5) for p in prompts]
    out = sched.drain()
    # greedy by the full forward: one program, the sequence padded at its
    # end (which a causal model does not see)
    full = jax.jit(lambda t: TransformerLM(cfg).apply(
        {"params": params}, t[None], train=False)[0])
    for rid, p in zip(rids, prompts):
        seq = list(p)
        for _ in range(5):
            padded = np.zeros((32,), np.int32)
            padded[:len(seq)] = seq
            seq.append(int(jnp.argmax(full(jnp.asarray(padded))[len(seq) - 1])))
        assert [int(t) for t in out[rid]] == seq[len(p):]
    assert sched.engine.allocator.in_use == 0


def test_generate_decodes_through_the_dense_cache(model):
    """``generate``'s dense cache carries a leading pass axis."""
    cfg, params = model
    cache = init_cache(cfg, params, 2)
    assert cache["block0"]["attn"]["key"].shape == (U, 2, 64, 2, 16)
    prompt = jax.random.randint(jax.random.key(4), (2, 7), 1, 128)
    out = np.asarray(generate(cfg, params, prompt, jax.random.key(0),
                              max_new_tokens=4))
    lm = TransformerLM(cfg)
    seq = np.asarray(prompt)
    for _ in range(4):
        logits = lm.apply({"params": params}, jnp.asarray(seq), train=False)
        seq = np.concatenate(
            [seq, np.asarray(jnp.argmax(logits[:, -1], -1))[:, None]], 1)
    assert (out == seq).all()


def test_tensor_parallel_decoding_matches_replicated(model, devices8):
    """The gated MLP's second column-parallel projection (``mlp_gate``) and
    the pass axis of the dense cache under the TP placement rules: two
    shards emit exactly the replicated path's tokens."""
    from pytorch_distributed_tpu.models.generate import generate_tp
    from pytorch_distributed_tpu.parallel import make_mesh

    cfg, params = model
    prompt = jax.random.randint(jax.random.key(6), (2, 7), 1, 128)
    tp_cfg = dataclasses.replace(cfg, model_axis="model", tp_size=2)
    mesh = make_mesh(devices8, data_parallel=4, model_parallel=2)
    want = generate(cfg, params, prompt, jax.random.key(5), max_new_tokens=5)
    got = generate_tp(mesh, tp_cfg, params, prompt, jax.random.key(5),
                      max_new_tokens=5)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_a_pass_reads_and_writes_only_its_own_entries(model):
    """Attention of pass t on a pool whose other passes' entries are
    overwritten with garbage gives the same output, bit for bit, and
    writes nothing outside its own share of each block."""
    cfg, _ = model
    attn = Attention(cfg, decode=True)
    b, nb, bl, hd = 2, 6, 8, 32
    x = jax.random.normal(jax.random.key(0), (b, 1, 32))
    params = attn.init(
        jax.random.key(1), x, jnp.zeros((b,), jnp.int32),
        jnp.zeros((b, 1), jnp.int32), None, 0)["params"]
    keys = jax.random.split(jax.random.key(2), 2)
    pool = {name: jax.random.normal(k, pool_leaf_shape(nb, bl, 2, 16,
                                                       passes=U))
            for name, k in zip(("key", "value"), keys)}
    assert pool["key"].shape == (nb, U, bl, hd)
    tables = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    at = jnp.asarray([9, 12], jnp.int32)  # second block of each chain

    def run(cache, t):
        out, new = attn.apply(
            {"params": params, "cache": cache}, x, at, at[:, None], tables,
            t, mutable=["cache"])
        return np.asarray(out), jax.tree.map(np.asarray, new["cache"])

    t = 1
    out, new = run(pool, t)
    others = [s for s in range(U) if s != t]
    garbage = jax.tree.map(lambda p: p.at[:, others].set(1e3), pool)
    out_g, _ = run(garbage, t)
    assert (out == out_g).all()
    for name in ("key", "value"):
        old = np.asarray(pool[name])
        assert (new[name][:, others] == old[:, others]).all()
        changed = np.argwhere((new[name] != old).any(-1))
        # one row a request, in its own block, at its own offset, pass t
        assert sorted(map(tuple, changed)) == [(2, t, 1), (4, t, 4)]
    # and its own entries do matter
    own = jax.tree.map(lambda p: p.at[:, t].add(1.0), pool)
    assert np.abs(run(own, t)[0] - out).max() > 1e-3


def test_a_block_counts_every_pass_and_layer(model):
    cfg, params = model
    block_len, heads, head_dim, layers = 8, 2, 16, 2
    assert pool_block_bytes(cfg, params, block_len) == (
        U * layers * 2 * block_len * heads * head_dim * 4)
    pool = init_paged_cache(cfg, params, 9, block_len)
    assert {x.shape for x in jax.tree.leaves(pool)} == {
        (9, U, block_len, heads * head_dim)}
    quantized = init_paged_cache(cfg, params, 9, block_len, kv_dtype="int8")
    assert quantized["block0"]["attn"]["key_scale"].shape == (
        9, U, block_len, heads)
    eng = PagedEngine(cfg, params, 2, n_blocks=9, block_len=block_len,
                      prefill_chunk=8)
    # what a swap moves: the blocks' bytes in every cache layer, and a
    # logits row
    assert eng.chain_bytes(3) == 3 * pool_block_bytes(
        cfg, params, block_len) + 128 * 4
    alloc = spans.tracer().events("pool.alloc")[-1].args
    assert alloc["cache_layers"] == U * layers
    assert alloc["weight_layers"] == layers
    assert alloc["block_bytes"] == pool_block_bytes(cfg, params, block_len)


def test_an_int8_pool_serves_the_looped_stack(model):
    """The quantized scatter and gather go through the same per-pass view."""
    cfg, params = model
    prompts = [np.arange(1, 12, dtype=np.int32)]
    streams = []
    for kv_dtype in (None, "int8"):
        sched = Scheduler(cfg, params, n_slots=2, n_blocks=12, block_len=8,
                          prefill_chunk=8, kv_dtype=kv_dtype)
        rid = sched.submit(prompts[0], 4)
        streams.append([int(t) for t in sched.drain()[rid]])
    assert len(streams[1]) == 4 and streams[0][0] == streams[1][0]


def test_the_programs_hold_the_stack_once_under_a_loop(model):
    """The decode tick's jaxpr has ONE scan of ``ut_steps`` trips whose
    body holds the layers once: as many matrix products as one pass."""
    cfg, params = model

    def dots(config, params):
        eng = PagedEngine(config, params, 2, n_blocks=5, block_len=8,
                          prefill_chunk=8)
        args = (eng.params, eng.cache, eng.logits, eng._decode_operand(
            np.zeros((2,), np.int32), np.zeros((2,), bool)),
                jax.random.key(0))
        scans, count = [], 0

        def walk(jaxpr):
            nonlocal count
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "scan":
                    scans.append(eqn.params["length"])
                count += eqn.primitive.name == "dot_general"
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

        walk(jax.make_jaxpr(eng._decode())(*args).jaxpr)
        return scans, count

    scans, looped = dots(cfg, params)
    assert scans == [U]
    scans_one, one = dots(
        dataclasses.replace(cfg, ut_steps=1),
        {k: v for k, v in params.items() if k != "exit_gate"})
    assert scans_one == [] and looped == one


def test_admission_says_what_it_found(model):
    """``sched.admit`` carries the free blocks after the tick's admissions
    and whether the queue's head waited for blocks."""
    cfg, params = model
    sched = Scheduler(cfg, params, n_slots=3, n_blocks=7, block_len=8,
                      prefill_chunk=8)
    t0 = spans.time.perf_counter()
    for n in (20, 20, 20):  # 4 blocks each (with 8 new): only one fits 6
        sched.submit(np.arange(1, n + 1, dtype=np.int32), 8)
    sched.step()
    first = [e for e in spans.tracer().events("sched.admit") if e.t0 >= t0][0]
    assert first.args["waited"] is True
    assert first.args["free_blocks"] == 6 - 4
    sched.drain()
    # the last admission found the queue empty and one request resident
    last = spans.tracer().events("sched.admit")[-1].args
    assert last["waited"] is False and last["free_blocks"] == 6 - 4
    assert sched.engine.allocator.available == 6


def test_adaptive_exit_is_refused():
    with pytest.raises(ValueError, match="adaptive exit is not implemented"):
        looped_config(early_exit_threshold=0.9)
    with pytest.raises(ValueError, match="ut_steps"):
        looped_config(ut_steps=0)
    with pytest.raises(ValueError, match="return_gates"):
        cfg = tiny_config()
        TransformerLM(cfg).init(jax.random.key(0),
                                jnp.zeros((1, 4), jnp.int32),
                                return_gates=True)


@pytest.mark.parametrize("field,value", [
    ("norm", "batchnorm"), ("mlp", "relu"), ("mlp_dim", 0)])
def test_a_block_description_is_validated(field, value):
    with pytest.raises(ValueError, match=field):
        tiny_config(**{field: value})


def test_moe_keeps_its_own_mlp():
    with pytest.raises(ValueError, match="MoE"):
        tiny_config(n_experts=4, mlp="swiglu")


# ---- the default block is the block this module always ran ----------------


class _Gpt2Block(nn.Module):
    """The block as it was written before it had a description."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, x, pos):
        cfg = self.config
        h = nn.LayerNorm(dtype=jnp.float32, name="ln1")(x)
        x = x + Attention(cfg, name="attn")(h, 0, pos)
        h = nn.LayerNorm(dtype=jnp.float32, name="ln2")(x)
        h = nn.Dense(cfg.embed_dim * cfg.mlp_ratio, dtype=cfg.dtype,
                     name="mlp_up")(h)
        h = nn.gelu(h)
        return x + nn.Dense(cfg.embed_dim, use_bias=False, dtype=cfg.dtype,
                            name="mlp_down")(h)


class _Gpt2LM(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.config
        pos = jnp.arange(tokens.shape[1])
        x = nn.Embed(cfg.vocab_size, cfg.embed_dim, dtype=cfg.dtype,
                     name="wte")(tokens)
        x = x + nn.Embed(cfg.max_seq_len, cfg.embed_dim, dtype=cfg.dtype,
                         name="wpe")(pos)
        for i in range(cfg.num_layers):
            x = _Gpt2Block(cfg, name=f"block{i}")(x, pos)
        x = nn.LayerNorm(dtype=jnp.float32, name="ln_f")(x)
        return nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                        name="lm_head")(x).astype(jnp.float32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_default_block_is_gpt2s_tree_and_logits_bit_for_bit(dtype):
    cfg = tiny_config(dtype=dtype)
    tokens = jax.random.randint(jax.random.key(1), (2, 10), 1, 128)
    new = TransformerLM(cfg).init(jax.random.key(0), tokens)["params"]
    old = _Gpt2LM(cfg).init(jax.random.key(0), tokens)["params"]
    assert jax.tree.structure(new) == jax.tree.structure(old)
    for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old)):
        assert a.shape == b.shape and (np.asarray(a) == np.asarray(b)).all()
    got = TransformerLM(cfg).apply({"params": new}, tokens, train=False)
    want = _Gpt2LM(cfg).apply({"params": new}, tokens)
    assert (np.asarray(got) == np.asarray(want)).all()
    # no pass axis anywhere
    pool = init_paged_cache(cfg, new, 5, 8)
    assert {x.ndim for x in jax.tree.leaves(pool)} == {3}
