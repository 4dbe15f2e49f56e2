"""``ops/state_update.py`` in the Pallas interpreter (PR 47): the kernel
beside ``delta_rule_update`` (a decay a channel and a decay a head) at the
serving cells' head shapes and a few slots, its two flags (a fresh lane over
a row of NaNs, a lane that is not live, the trash row behind the lanes),
head counts the head block does not divide, the rule that says which layers
compile it, and a paged decode tick of each delta-rule configuration on the
kernel beside the same tick on the ``jax.numpy`` spelling. What Mosaic makes
of the real leaves is ``tests/test_tpu_compile.py``'s; what the chip makes
of them PERF.md's."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402
from test_ling_lm import ling_config  # noqa: E402
from test_qwen3_next_lm import qwen_config  # noqa: E402

from pytorch_distributed_tpu.models import transformer  # noqa: E402
from pytorch_distributed_tpu.models.transformer import (  # noqa: E402
    TransformerLM,
    delta_rule_update,
)
from pytorch_distributed_tpu.ops import state_update as su  # noqa: E402
from pytorch_distributed_tpu.serving.engine import (  # noqa: E402
    ChunkJob,
    PagedEngine,
)
from pytorch_distributed_tpu.serving.kv_pool import (  # noqa: E402
    TRASH_BLOCK,
    is_slot_leaf,
)

#: the two cells' layers (ling's ``KDAttention``: a decay a channel;
#: qwen3-next's ``GatedDeltaNet``: a decay a head): heads, and a head's width
KINDS = ("kda", "gdn")
HEADS, WIDTH = 32, 128
SLOTS = 3


def token(kind, lanes, heads, width, seed=0):
    """One token's operands, as the layer hands them to its update."""
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    def unit(*shape):  # in (0, 1), as a decay and a beta are
        return jnp.asarray(rng.uniform(0.05, 0.95, size=shape), jnp.float32)

    decay = unit(lanes, heads, width if kind == "kda" else 1)
    return (normal(lanes, heads, width) * width ** -0.5,
            normal(lanes, heads, width) * width ** -0.5,
            normal(lanes, heads, width), decay, unit(lanes, heads))


def leaf_of(lanes, heads, width, seed=1):
    """A cache leaf: a row a lane and the trash row behind them."""
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(lanes + 1, heads, width, width)),
                       jnp.float32)


def kernel(leaf, operands, fresh, live, heads=None):
    return su.delta_rule_tick(leaf, *operands, fresh=jnp.asarray(fresh),
                              live=jnp.asarray(live), heads=heads)


def held(leaf, operands, fresh, live):
    """``_SlotStateAttention._held``'s spelling around the ``jax.numpy``
    update: a fresh row from zeros, a row that is not live as it was."""
    lanes = len(fresh)
    fresh, live = (jnp.asarray(t)[:, None, None, None] for t in (fresh, live))
    rows = leaf[:lanes]
    s1, out = delta_rule_update(jnp.where(fresh, 0.0, rows), *operands)
    return leaf.at[:lanes].set(jnp.where(live, s1, rows)), out


def close(got, want, rel=1e-5):
    got, want = np.asarray(got), np.asarray(want)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= rel * np.abs(want).max()


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("block", [None, 8, 16, 32])
def test_the_kernel_is_the_jnp_update_at_the_cells_head_shapes(kind, block):
    """To 1e-5 at ``[*, 32, 128, 128]``, at the rule's head block and at
    each block the chip's sweep read."""
    operands = token(kind, SLOTS, HEADS, WIDTH)
    leaf = leaf_of(SLOTS, HEADS, WIDTH)
    flags = ([False] * SLOTS, [True] * SLOTS)
    want_leaf, want_out = held(leaf, operands, *flags)
    got_leaf, got_out = kernel(leaf, operands, *flags, heads=block)
    assert got_leaf.shape == leaf.shape and got_leaf.dtype == jnp.float32
    assert got_out.shape == want_out.shape and got_out.dtype == jnp.float32
    close(got_leaf, want_leaf)
    close(got_out, want_out)


@pytest.mark.parametrize("kind", KINDS)
def test_a_fresh_lane_over_a_row_of_nans_gives_the_zero_states_answer(kind):
    """Position 0 starts from zeros by a select: what the row held (here
    NaNs, which a multiply by zero would keep) reaches nothing."""
    heads = HEADS // 4
    operands = token(kind, SLOTS, heads, WIDTH)
    leaf = leaf_of(SLOTS, heads, WIDTH).at[1].set(jnp.nan)
    fresh, live = [False, True, False], [True] * SLOTS
    zeros = leaf.at[1].set(0.0)
    want_leaf, want_out = held(zeros, operands, [False] * SLOTS, live)
    got_leaf, got_out = kernel(leaf, operands, fresh, live)
    close(got_leaf, want_leaf)
    close(got_out, want_out)


@pytest.mark.parametrize("kind", KINDS)
def test_a_lane_that_is_not_live_and_the_trash_row_keep_their_bits(kind):
    """Bit for bit, NaN payloads and all: a dead lane's row (fresh or not)
    and the row behind the lanes are not the update's to touch, and a dead
    lane reads zeros."""
    heads = HEADS // 4
    operands = token(kind, SLOTS, heads, WIDTH)
    leaf = leaf_of(SLOTS, heads, WIDTH)
    leaf = leaf.at[0, 0, 0, :4].set(jnp.asarray(
        [jnp.nan, -jnp.nan, jnp.inf, -0.0]))
    leaf = leaf.at[SLOTS, 0, 0, :2].set(jnp.asarray([jnp.nan, -0.0]))
    fresh, live = [True, False, False], [False, True, False]
    got_leaf, got_out = kernel(leaf, operands, fresh, live)
    bits = np.asarray(leaf).view(np.uint32)
    got_bits = np.asarray(got_leaf).view(np.uint32)
    for row in (0, 2, SLOTS):
        assert (got_bits[row] == bits[row]).all()
    assert (got_bits[1] != bits[1]).any()  # the live lane's moved
    want_leaf, want_out = held(leaf, operands, fresh, live)
    close(got_leaf[1], want_leaf[1])
    close(got_out[1], want_out[1])
    assert not np.asarray(got_out)[[0, 2]].any()


@pytest.mark.parametrize("kind,heads,block", [
    # a last block of four heads behind one of eight
    ("kda", 12, 8), ("gdn", 12, 8),
    # an odd count in one block
    ("kda", 5, None), ("gdn", 3, None),
    # three blocks, the last of one head
    ("kda", 17, 8),
])
def test_head_counts_the_head_block_does_not_divide(kind, heads, block):
    operands = token(kind, SLOTS, heads, 16, seed=heads)
    leaf = leaf_of(SLOTS, heads, 16)
    fresh, live = [False, True, False], [True, True, False]
    want_leaf, want_out = held(leaf, operands, fresh, live)
    got_leaf, got_out = kernel(leaf, operands, fresh, live, block)
    close(got_leaf, want_leaf)
    close(got_out[:2], want_out[:2])


@pytest.mark.parametrize("heads,head_bytes,want", [
    (32, 128 * 128 * 4, 16),  # ling's and qwen3-next's: 64 KB a head
    (8, 128 * 128 * 4, 8),  # no more heads than a block: all of them
    (4, 16 * 16 * 4, 4),  # the toys
    (64, 64 * 64 * 4, 64),  # 16 KB a head: 64 of them make a step
    (12, 256 * 256 * 4, 8),  # a head of 256 KB: still whole sublane tiles
])
def test_the_head_block_follows_from_the_shapes(heads, head_bytes, want):
    block = su.head_block(heads, head_bytes)
    assert block == want
    # a step moves at least STEP_BYTES each way, or the lane's whole state
    assert block * head_bytes >= min(su.STEP_BYTES, heads * head_bytes)
    assert block == heads or block % su.HEAD_ALIGN == 0


@pytest.mark.parametrize("what,match", [
    ("bfloat16 state", "are float32"),
    ("bfloat16 query", "are float32"),
    ("fewer rows than lanes", "the state leaf must be"),
    ("another head count", "the state leaf must be"),
    ("a state that is not square", "the state leaf must be"),
    ("a decay of another width", "the decay must be"),
])
def test_operands_the_kernel_cannot_take_are_refused_by_name(what, match):
    q, k, v, a, beta = token("kda", 2, 4, 16)
    leaf = leaf_of(2, 4, 16)
    if what == "bfloat16 state":
        leaf = leaf.astype(jnp.bfloat16)
    elif what == "bfloat16 query":
        q = q.astype(jnp.bfloat16)
    elif what == "fewer rows than lanes":
        leaf = leaf[:1]
    elif what == "another head count":
        leaf = leaf[:, :3]
    elif what == "a state that is not square":
        leaf = leaf[:, :, :8]
    else:
        a = a[..., :2]
    with pytest.raises(ValueError, match=match):
        su.delta_rule_tick(leaf, q, k, v, a, beta,
                           fresh=jnp.zeros((2,), bool),
                           live=jnp.ones((2,), bool))


@pytest.mark.parametrize("kinds,backend,want", [
    (("kda",), "tpu", "pallas"), (("gdn",), "tpu", "pallas"),
    (("gdn", "mha"), "tpu", "pallas"), (("kda", "mla"), "tpu", "pallas"),
    (("mamba2",), "tpu", "xla"), (("mamba2", "mha"), "tpu", "xla"),
    (("kda",), "cpu", "xla"), (("gdn", "mha"), "gpu", "xla"),
    (("mamba2",), "cpu", "xla"),
    (("mha",), "tpu", ""), (("cca",), "tpu", ""), (("mla",), "cpu", ""),
    ((), "tpu", ""),
])
def test_the_rule_answers_by_backend_and_layer_kind(monkeypatch, kinds,
                                                    backend, want):
    """``slot_state_update``: the kernel for the delta rule on a TPU, the
    ``jax.numpy`` update for Mamba-2 everywhere (the chip read a kernel of
    this shape slower there) and for every layer on another backend,
    nothing where no layer keeps a state."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert transformer.slot_state_update(kinds) == want


def test_each_state_layer_names_its_own_kind():
    layers = transformer.SLOT_STATE_LAYERS
    assert layers == {"kda": transformer.KDAttention,
                      "gdn": transformer.GatedDeltaNet,
                      "mamba2": transformer.Mamba2Mixer}
    assert all(c.KIND == kind for kind, c in layers.items())
    assert transformer.DELTA_RULE_KINDS == set(layers) - {"mamba2"}


# ---- a paged decode tick on the kernel --------------------------------------

CONFIGS = {"ling": ling_config, "qwen3-next": qwen_config}
CHUNK = 8


def ticks_of(cfg, params):
    """Slots 0 and 1 prefilled (one chunk and two), slot 2 admitted and ONE
    chunk of two in (its lane is not live while the ticks run), then four
    ticks: (the logits buffer after each, the cache)."""
    eng = PagedEngine(cfg, params, 3, n_blocks=25, block_len=8,
                      prefill_chunk=CHUNK)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 128, size=n).astype(np.int32)
               for n in (6, 13, 14)]
    for slot, p in enumerate(prompts):
        assert eng.admit(slot, len(p), 4)
    for start in (0, CHUNK):
        jobs = []
        for slot, p in enumerate(prompts):
            if start >= len(p) or (slot == 2 and start):
                continue
            seg = np.zeros((CHUNK,), np.int32)
            seg[:len(p[start:start + CHUNK])] = p[start:start + CHUNK]
            last = start + CHUNK >= len(p)
            jobs.append(ChunkJob(slot, seg, start, last,
                                 len(p) - 1 - start if last else 0))
        eng.run_chunks(jobs)
    positions = np.asarray([6, 13, 0], np.int32)
    logits = []
    for _ in range(4):
        _, positions = eng.decode(positions, positions > 0,
                                  jax.random.key(0))
        logits.append(np.asarray(eng.logits))
    return eng.state_update, logits, jax.tree.map(np.asarray, eng.cache)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_a_paged_tick_on_the_kernel_is_the_tick_on_the_jnp_update(
        monkeypatch, name):
    """``_SlotStateAttention`` asks ``slot_state_update`` which update its
    paged tick compiles; on the kernel (interpreted) four ticks give the
    ``jax.numpy`` tick's logits in the live lanes and its cache in every
    leaf, the lane in mid-prefill and the trash row included (but for a
    pool's trash block, where the lanes that are not live write what they
    computed: zeros behind the kernel, numbers of no request behind the
    ``jax.numpy`` update)."""
    cfg = CONFIGS[name]()
    params = TransformerLM(cfg).init(
        jax.random.key(1), jnp.zeros((1, 8), jnp.int32))["params"]
    said, want_logits, want_cache = ticks_of(cfg, params)
    assert said == "xla"  # the CPU keeps XLA's
    asked = []
    monkeypatch.setattr(transformer, "slot_state_update",
                        lambda kinds: asked.append(tuple(kinds)) or "pallas")
    said, got_logits, got_cache = ticks_of(cfg, params)
    # the engine asked with the stack's kinds, a layer with its own
    assert said == "pallas" and cfg.attn_kinds in asked
    assert (cfg.attn_kind,) in asked
    for got, want in zip(got_logits, want_logits):
        close(got[:2], want[:2], rel=2e-5)
    states = 0
    flat = dict(jax.tree_util.tree_leaves_with_path(want_cache))
    for path, got in jax.tree_util.tree_leaves_with_path(got_cache):
        want = flat[path]
        if getattr(path[-1], "key", None) == "state":
            states += 1
            # the lane in mid-prefill and the trash row: bit for bit
            assert (got[2:] == want[2:]).all() and np.abs(want[2]).max() > 0
        elif not is_slot_leaf(path):
            got, want = got[TRASH_BLOCK + 1:], want[TRASH_BLOCK + 1:]
        close(got, want, rel=2e-5)
    assert states >= 3
