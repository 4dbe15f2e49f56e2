"""Device mesh construction and sharding specs — the communication layer.

TPU-native replacement for the reference's NCCL process-group + DDP wrapper
stack (D6/D7/D13: ``dist.init_process_group('nccl', ...)``,
``restnet_ddp.py:94``; ``DistributedDataParallel(model.cuda())``,
``restnet_ddp.py:99``). There is no wrapper object here: parallelism is a
``jax.sharding.Mesh`` plus sharding specs on one SPMD step function. XLA
compiles the gradient all-reduce into the step program and routes it over
ICI (intra-pod) / DCN (cross-pod) automatically.

The mesh always carries three axes — ``data`` (the only one the reference's
capability surface uses: all three DP flavors map onto it), ``seq``
(sequence/context parallelism, ``parallel.sequence``), and ``model``
(tensor parallelism) — so adding a parallelism dimension is a sharding-spec
change, not a redesign (SURVEY.md §2c).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax import shard_map  # noqa: F401  (re-exported: call sites import it from here)
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
SEQ_AXIS = "seq"
MODEL_AXIS = "model"

# The canonical axis order, in one place: jaxlint's collective-axis rule
# treats these constants as the declared axis set, so a collective naming
# anything else is a build error (ANALYSIS.md).
MESH_AXES = (DATA_AXIS, SEQ_AXIS, MODEL_AXIS)


def make_mesh(
    devices: Optional[Sequence[jax.Device]] = None,
    data_parallel: Optional[int] = None,
    model_parallel: int = 1,
    seq_parallel: int = 1,
    axis_names: Sequence[str] = MESH_AXES,
) -> Mesh:
    """Build a (data, seq, model) mesh over the given (default: all) devices.

    With ``model_parallel=seq_parallel=1`` (the reference's entire capability
    surface) this is a pure data-parallel mesh: one replica per chip, the
    exact topology ``DistributedDataParallel`` builds with one process per
    GPU (``restnet_ddp.py:154-155``) — minus the processes: a single program
    spans every chip on every host.

    The ``seq`` axis carries sequence/context parallelism (ring attention,
    ``parallel.sequence``) and the ``model`` axis tensor parallelism — both
    absent from the reference (SURVEY.md §2c) but first-class here. Axis
    order is (data, seq, model) so the innermost (fastest-varying, i.e.
    physically closest over ICI) devices carry the most latency-sensitive
    collectives.
    """
    if len(axis_names) != 3:
        raise ValueError(
            f"make_mesh builds a 3-axis (data, seq, model) grid; got "
            f"axis_names={tuple(axis_names)}"
        )
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    n = len(devices)
    inner = model_parallel * seq_parallel
    if data_parallel is None:
        if n % inner:
            raise ValueError(
                f"{n} devices not divisible by seq_parallel*model_parallel={inner}"
            )
        data_parallel = n // inner
    if data_parallel * inner != n:
        raise ValueError(
            f"mesh {data_parallel}x{seq_parallel}x{model_parallel} != {n} devices"
        )
    grid = np.asarray(devices).reshape(data_parallel, seq_parallel, model_parallel)
    return Mesh(grid, axis_names=tuple(axis_names))


def single_device_mesh(device: Optional[jax.Device] = None) -> Mesh:
    """1-chip mesh: the ``resnet_single_gpu.py`` topology. The same SPMD
    step function runs unchanged; collectives over a size-1 axis are no-ops."""
    if device is None:
        device = jax.devices()[0]
    return make_mesh([device])


def local_mesh() -> Mesh:
    """All chips addressable by this process (the ``nn.DataParallel``
    topology, ``resnet_dp.py:82`` — 8 local devices, one process)."""
    return make_mesh(jax.local_devices())


def batch_sharding(mesh: Mesh, axis: str = DATA_AXIS) -> NamedSharding:
    """Leading (batch) dimension split across the data axis — how every
    input batch is laid out. Per-replica shard size ≙ the reference's
    per-process batch of 400 (``restnet_ddp.py:78``)."""
    return NamedSharding(mesh, P(axis))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Fully replicated — parameters and optimizer state in pure DP.

    ≙ DDP's broadcast-from-rank-0 at construction (``restnet_ddp.py:99``):
    placing the initial pytree with this sharding performs the broadcast.
    """
    return NamedSharding(mesh, P())


def specs_to_shardings(mesh: Mesh, specs):
    """PartitionSpec pytree → NamedSharding pytree over ``mesh``.

    The one place the spec→sharding mapping lives: initial placement
    (``fsdp.shard_fsdp_state``, ``lm.shard_lm_state``) and checkpoint
    restore (``Trainer.try_resume``) must place identically or resumed runs
    get a different layout than fresh ones.
    """
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def shard_batch(mesh: Mesh, batch, axis: str = DATA_AXIS):
    """Place a host-local numpy batch onto the mesh as a global array.

    Each process passes its local shard (what its DataLoader produced for
    its ranks); together they form the global batch. Replaces the per-step
    H2D copy ``x.cuda(non_blocking=True)`` (``restnet_ddp.py:25``) — the
    transfer is async and the result is already laid out for the compiled
    step, so no scatter happens at step time (unlike ``nn.DataParallel``'s
    per-step scatter, D5).
    """
    sharding = batch_sharding(mesh, axis)
    return jax.tree.map(
        lambda x: jax.make_array_from_process_local_data(sharding, np.asarray(x)),
        batch,
    )


def global_batch_size(mesh: Mesh, per_replica_batch: int, axis: str = DATA_AXIS) -> int:
    """per-replica bs × data-axis size (ref: 400 × world_size)."""
    return per_replica_batch * mesh.shape[axis]


def local_replica_count(mesh: Mesh, axis: str = DATA_AXIS) -> int:
    """How many data-axis replicas this process feeds (= local chips / model
    axis span). The loader produces ``local_replica_count × per_replica_bs``
    samples per step."""
    local = set(jax.local_devices())
    axis_index = mesh.axis_names.index(axis)
    coords = set()
    for idx in np.ndindex(mesh.devices.shape):
        if mesh.devices[idx] in local:
            coords.add(idx[axis_index])
    return max(len(coords), 1)
