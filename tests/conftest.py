"""Test harness: 8 virtual CPU devices.

Multi-device behavior (pjit sharding, psum reductions, sampler shard logic)
is exercised without TPUs via XLA's host-platform device-count override —
the strategy SURVEY.md §4 prescribes. Must run before jax initializes a
backend, hence module-level in conftest.

Tiers (the full suite takes >10 min on one contended core):
  fast   pytest -m "not slow and not multihost"   (~5 min, 124 tests)
  full   pytest -m "not multihost"                 (everything local)
  all    pytest                                    (+ real 2-process runs)
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices8():
    import jax

    devices = jax.devices()
    assert len(devices) >= 8, f"expected 8 virtual devices, got {len(devices)}"
    return devices[:8]


@pytest.fixture
def steer_paged_read(monkeypatch):
    """Steer the one rule that chooses the paged read,
    ``ops.attention.default_gather_impl``, for the rest of the test:
    ``steer_paged_read("pallas")`` answers that spelling for any rows, a
    ``rows -> spelling`` function answers what it says; a later call
    steers again. Programs ask the rule as they trace, so steer before an
    engine's first call and drive it before steering elsewhere. The
    backend is left alone: off-TPU the kernel runs in the interpreter."""
    from pytorch_distributed_tpu.ops import attention

    def steer(rule):
        monkeypatch.setattr(
            attention, "default_gather_impl",
            (lambda rows=1, dense_bytes=0: rule) if isinstance(rule, str)
            else rule)

    return steer


def assert_trees_equal(a, b, rtol=0, atol=0):
    """Leaf-wise comparison of two pytrees by path (shared test helper)."""
    import numpy as np

    flat_b = {str(p): v for p, v in jax.tree_util.tree_leaves_with_path(b)}
    for path, leaf in jax.tree_util.tree_leaves_with_path(a):
        np.testing.assert_allclose(
            np.asarray(jax.device_get(leaf)),
            np.asarray(jax.device_get(flat_b[str(path)])),
            rtol=rtol, atol=atol, err_msg=str(path),
        )


#: the served configurations at toy widths, as overrides of ``tiny_config``
#: (gpt2 is the tiny config itself). Each model's own test files read their
#: entry (``tests/test_ling_lm.py::LING`` and so on) and say what the widths
#: stand for; ``tests/test_fleet_configs.py`` drives all seven through
#: ``FleetRouter``.
SERVED_TINY = {
    "gpt2": dict(attention="dense", max_seq_len=64),
    # a looped decoder: three passes over the stack, a gate a pass
    "ouro": dict(
        norm="rmsnorm", mlp="swiglu", mlp_dim=48, post_norm=True,
        use_bias=False, pos_embedding="rope", rope_theta=1e6, ut_steps=3,
        max_seq_len=64),
    # attention in a compressed latent with a convolution tail a request,
    # dropless top-1 experts behind a router with state across layers
    "zaya": dict(
        num_layers=2, embed_dim=48, num_heads=4, num_kv_heads=2, head_dim=8,
        attn_kind="cca", pos_embedding="rope", rope_theta=5e6,
        rotary_share=0.5, norm="rmsnorm", norm_eps=1e-5, use_bias=False,
        tie_embeddings=True, residual_scaling=True, n_experts=4, moe_every=1,
        moe_kind="dropless", moe_dim=24, router_dim=8, max_seq_len=64),
    # delta-rule layers whose float32 state is a slot's, a latent-attention
    # layer every third over a one-row-a-token pool
    "ling": dict(
        num_layers=6, embed_dim=48, num_heads=4, head_dim=8,
        attn_kind="kda", layer_group_size=3, kv_lora_rank=16,
        qk_rope_head_dim=4, pos_embedding="rope", rope_theta=6e6,
        norm="rmsnorm", norm_eps=1e-6, use_bias=False, mlp="swiglu",
        mlp_dim=64, n_experts=16, moe_every=1, moe_kind="dropless",
        moe_router="sigmoid", moe_top_k=4, moe_n_group=4, moe_topk_group=2,
        moe_routed_scale=2.5, moe_dim=24, moe_shared_dim=24,
        experts_held=(0, 8), first_k_dense_replace=2, max_seq_len=64),
    # gated delta-rule layers, then a gated grouped-head layer over a real
    # K/V pool
    "qwen3-next": dict(
        num_layers=4, embed_dim=48, num_heads=4, num_kv_heads=2, head_dim=16,
        attn_kind="gdn", layer_group_size=4, full_attn_kind="mha",
        linear_num_heads=4, linear_num_key_heads=2, linear_head_dim=8,
        qk_norm=True, attn_gate=True, rotary_share=0.25,
        pos_embedding="rope", rope_theta=1e7, norm="rmsnorm", norm_eps=1e-6,
        use_bias=False, mlp="swiglu", n_experts=16, moe_every=1,
        moe_kind="dropless", moe_router="softmax", moe_top_k=4, moe_dim=24,
        moe_shared_dim=24, moe_shared_gate=True, experts_held=(0, 8),
        max_seq_len=64),
    # blocks of one sublayer by a pattern string: Mamba-2, experts, one
    # position-free attention
    "nemotron-h": dict(
        num_layers=9, layer_pattern="MEMEM*EME", embed_dim=48, num_heads=4,
        num_kv_heads=2, head_dim=16, pos_embedding="none", norm="rmsnorm",
        norm_eps=1e-5, use_bias=False, mlp="relu2", mamba_num_heads=4,
        mamba_head_dim=8, mamba_state_size=16, mamba_n_groups=2,
        n_experts=16, moe_kind="dropless", moe_router="sigmoid", moe_top_k=3,
        moe_routed_scale=2.5, moe_dim=24, moe_shared_dim=40,
        experts_held=(0, 8), max_seq_len=64),
    # latent attention in EVERY layer (a compressed query, values wider than
    # the unrotated keys, no gate) over a pool that is the only cache; one
    # leading dense MLP, then sigmoid top-4 experts, every one held
    "glm": dict(
        num_layers=3, embed_dim=48, num_heads=5, head_dim=12, attn_kind="mla",
        q_lora_rank=24, kv_lora_rank=16, qk_rope_head_dim=4, v_head_dim=16,
        mla_head_gate=False, pos_embedding="rope", rope_theta=1e6,
        norm="rmsnorm", norm_eps=1e-5, use_bias=False, mlp="swiglu",
        mlp_dim=64, n_experts=16, moe_every=1, moe_kind="dropless",
        moe_router="sigmoid", moe_top_k=4, moe_routed_scale=1.8, moe_dim=24,
        moe_shared_dim=24, first_k_dense_replace=1, max_seq_len=64),
}


def seeded_params(reference, cfg, seed=5):
    """``cfg``'s parameter tree filled by ``reference.init_params`` (a
    module of ``perfbench/references``) from the seed."""
    import jax.numpy as jnp

    from pytorch_distributed_tpu.models.transformer import TransformerLM

    shapes = jax.eval_shape(TransformerLM(cfg).init, jax.random.key(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    return reference.init_params(seed, shapes)


from pytorch_distributed_tpu.utils.suspend import SuspendWatcher  # noqa: E402


class FireAtStep(SuspendWatcher):
    """Deterministic suspend injection shared by the trainer tests:
    fires once the poll count reaches n."""

    def __init__(self, n):
        super().__init__(install_handlers=False)
        self.n = n
        self.calls = 0

    def receive_suspend_command(self) -> bool:
        self.calls += 1
        return self.calls >= self.n or self._event.is_set()
