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
