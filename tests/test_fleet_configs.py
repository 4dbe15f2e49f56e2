"""Every served configuration through ``FleetRouter``, the loop the chip
measures: a tick in flight while rows are armed, admitted and retired. The
seven toy configurations of ``conftest.SERVED_TINY`` (gpt2, the looped
decoder, zaya's latent attention with a convolution tail a request, ling's
and qwen3-next's delta-rule state, nemotron-h's Mamba-2 state, glm's latent
pool that is the only cache) serve the same
greedy streams behind the router, lagged, as out of a lone ``Scheduler``
whose ``step()`` launches and collects in one call; and a slot whose request
was cancelled under a tick in flight hands its successor nothing of the
state, the taps or the blocks it held.
"""

import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from conftest import SERVED_TINY, seeded_params  # noqa: E402

from pytorch_distributed_tpu.fleet import FleetRouter, SLOConfig  # noqa: E402
from pytorch_distributed_tpu.models.transformer import (  # noqa: E402
    TransformerLM,
    tiny_config,
)
from pytorch_distributed_tpu.serving import Scheduler  # noqa: E402

#: the module of ``perfbench/references`` that seeds each configuration
REFERENCES = {"ouro": "ouro", "zaya": "zaya", "ling": "ling",
              "qwen3-next": "qwen3_next", "nemotron-h": "nemotron_h",
              "glm": "glm4_moe_lite"}
#: one chunk program an engine: every bucket floored to the widest
SCHED_KW = dict(n_blocks=25, block_len=8, prefill_chunk=8,
                chunk_bucket_floor=(4, 8))


def prompts_of(lengths, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 128, size=n).astype(np.int32) for n in lengths]


@pytest.fixture(scope="module", params=list(SERVED_TINY))
def served(request):
    """``(cfg, params, reference)`` of one configuration; ``reference(
    prompts, max_new)`` is the streams of a lone ``Scheduler``, a list in
    the prompts' order."""
    name = request.param
    cfg = tiny_config(**SERVED_TINY[name])
    if name in REFERENCES:
        ref = importlib.import_module(
            "perfbench.references." + REFERENCES[name])
        ref.configure(SERVED_TINY[name])
        params = seeded_params(ref, cfg)
    else:
        params = TransformerLM(cfg).init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    lone = Scheduler(cfg, params, n_slots=3, **SCHED_KW)

    def reference(prompts, max_new):
        rids = [lone.submit(p, max_new) for p in prompts]
        out = lone.drain()
        assert lone.engine.allocator.in_use == 0
        return [[int(t) for t in out[rid]] for rid in rids]

    return cfg, params, reference


def router_of(cfg, params, n_replicas, n_slots=3):
    return FleetRouter(
        cfg, params, n_replicas=n_replicas, n_slots=n_slots,
        slo=SLOConfig(spill_queue_depth=2, shed_queue_depth=10**6),
        **SCHED_KW)


def assert_settled(router):
    """Every block and slot free, nothing in flight or undelivered."""
    for s in router.replicas:
        assert s.engine.allocator.in_use == 0
        assert not s.resident and not s.queue
        assert len(s._free_slots()) == s.n_slots
        assert not s.has_uncollected and not s.tick_in_flight


@pytest.mark.parametrize("n_replicas", [1, 2])
def test_the_router_serves_what_a_lone_scheduler_serves(served, n_replicas):
    cfg, params, reference = served
    prompts = prompts_of([5, 13, 9, 20, 7, 11], seed=0)
    want = reference(prompts, 5)
    router = router_of(cfg, params, n_replicas)
    rids = [router.submit(p, 5, session=i) for i, p in enumerate(prompts)]
    assert router.step() == []  # the first step launches, collects nothing
    assert any(s._pending_tick is not None for s in router.replicas)
    got = router.drain()
    assert not router.rejected
    assert {router.placement[rid] for rid in rids} == set(range(n_replicas))
    for rid, stream in zip(rids, want):
        assert [int(t) for t in got[rid]] == stream, f"stream {rid}"
    assert_settled(router)


def test_a_slot_cancelled_under_a_tick_in_flight_serves_its_successor_clean(
        served):
    """Two residents decode in both slots and a third request waits. One
    resident is cancelled while the tick that decodes it is in flight: the
    collect comes first, then the release, and the waiting request takes
    the slot. What the lagged collect could leave behind there (a
    recurrent state, a convolution tail, a block) would move its stream."""
    cfg, params, reference = served
    residents = prompts_of([12, 7], seed=1)
    newcomer = prompts_of([10], seed=2)
    want = reference(residents, 14), reference(newcomer, 6)[0]
    router = router_of(cfg, params, 1, n_slots=2)
    sched = router.replicas[0]
    victim, keeper = (router.submit(p, 14) for p in residents)
    for _ in range(40):
        router.step()
        if (sched.tick_in_flight and len(router.results.get(victim, [])) >= 3
                and len(router.results.get(keeper, [])) >= 1):
            break
    assert sched.tick_in_flight and sched.remaining.all()
    slot = next(s for s, r in sched.resident.items() if r.rid == victim)
    late = router.submit(newcomer[0], 6)
    assert [r.rid for r in sched.queue] == [late]
    assert router.cancel(victim)
    assert not sched.tick_in_flight  # the cancel collected the tick first
    router.step()
    assert sched.resident[slot].rid == late
    got = router.drain()
    assert [int(t) for t in got[late]] == want[1]
    assert [int(t) for t in got[keeper]] == want[0][1]
    cut = [int(t) for t in got[victim]]
    assert 3 <= len(cut) < 14 and cut == want[0][0][:len(cut)]
    assert_settled(router)
