"""Checkpoint save/restore wall-clock at the 135M-param LM size.

Measures the sharded checkpoint path (utils.checkpoint.save_sharded /
load_sharded) on a full AdamW TrainState: params + 2 moments, fp32 —
~1.6 GB. Runs on the CPU backend on purpose: what it times is host
serialization and disk, which dominate once the device→host hop rides
PCIe at GB/s (on the 2026-07 runtime that hop was ~24 MB/s, PERF_NOTES.md
§1, and an on-chip run timed the link instead). bench.py spawns it as a
child while holding the chip, which is the other reason it stays off the
device. Emits one JSON line:

  {"ckpt_params_m": ..., "ckpt_bytes_mb": ..., "ckpt_save_s": ...,
   "ckpt_restore_s": ..., "ckpt_mb_per_s": ...}

Restore rows are LABELED cold vs warm (ISSUE 8, reconciling ADVICE §4's
r4 0.59 s vs r5 11.99 s): ``ckpt_restore_warm_s`` is the median of N
page-cache-warm restores (the bytes were just written — a memcpy, not a
disk read), ``ckpt_restore_cold_s`` restores after evicting the
checkpoint's pages (``posix_fadvise DONTNEED``, no root needed) so it
pays the real disk read, and both are co-quoted with same-minute disk
probes (``ckpt_disk_mb_s`` write, ``ckpt_disk_read_mb_s`` cold read)
plus the read-bound floor ``ckpt_restore_disk_bound_s`` — so a restore
number is interpretable as efficiency-vs-disk instead of a
page-cache-state lottery. ``ckpt_restore_s`` keeps its historical
meaning (first restore right after save ≈ warm) for series continuity;
see PERF_NOTES §10.

``--reshard`` appends the elastic-restore section (reshard/, ROADMAP
item 4): the same dp4xtp2+FSDP checkpoint restored onto its own mesh
(exact-block fast path) vs onto (2,1,2) and (8,1,1) (cross-topology
block assembly), plus the offline repartition cost and the exact-path
restore it buys — keys ``ckpt_reshard_*``. Runs on 8 virtual CPU
devices (forced before jax import), so pass it on a dedicated
invocation if you want the headline sections on default devices.

Usage: python scripts/bench_checkpoint.py [--small] [--reshard]
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "--reshard" in sys.argv:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def _evict_page_cache(path: str) -> bool:
    """Best-effort eviction of ``path``'s files from the page cache:
    fsync any dirty pages, then ``posix_fadvise(DONTNEED)`` — works on
    our own files without root (DONTNEED drops only clean pages, hence
    the fsync first). Returns False when the platform has no fadvise, so
    the cold row can be labeled honestly instead of silently warm."""
    if not hasattr(os, "posix_fadvise"):
        return False
    paths = []
    if os.path.isdir(path):
        for root, _dirs, files in os.walk(path):
            paths += [os.path.join(root, f) for f in files]
    else:
        paths = [path]
    for p in paths:
        try:
            fd = os.open(p, os.O_RDONLY)
            try:
                os.fsync(fd)
                os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
            finally:
                os.close(fd)
        except OSError:
            return False
    return True


def main() -> None:
    from pytorch_distributed_tpu.models.transformer import TransformerConfig
    from pytorch_distributed_tpu.ops.optim import build_optimizer
    from pytorch_distributed_tpu.train.lm import create_lm_state
    from pytorch_distributed_tpu.utils.checkpoint import (
        load_sharded,
        save_sharded,
    )

    small = "--small" in sys.argv
    cfg = TransformerConfig(
        vocab_size=32000 if not small else 1024,
        num_layers=12 if not small else 2,
        num_heads=12 if not small else 2,
        embed_dim=768 if not small else 64,
        max_seq_len=1024 if not small else 64,
        dtype=jnp.float32,
    )
    tx = build_optimizer("adamw", 1e-4)
    state = create_lm_state(cfg, tx, jax.random.key(0), init_len=64)
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    payload = {"state": state, "epoch": 1, "step": 100, "best_ppl": 12.5}
    total_bytes = sum(
        x.size * x.dtype.itemsize
        for x in jax.tree.leaves(payload)
        if hasattr(x, "dtype")
    )

    from pytorch_distributed_tpu.utils.checkpoint import Checkpointer

    d = tempfile.mkdtemp(prefix="ckpt_bench_")
    try:
        # Concurrent raw-disk ceiling: the sync save is DISK-BOUND (the
        # round-5 analysis — raw write+fsync of the same byte count
        # measured 13.3 s = 116 MB/s on the shared disk the day the
        # "regression" was chased; r3's 9.7 s was a faster-disk day).
        # Measure it HERE, same minute, so ckpt_save_s is interpretable
        # as efficiency-vs-disk instead of a disk-weather lottery.
        probe_mb = 512 if not small else 8
        probe = np.ones(probe_mb * 2**20, np.uint8)
        pp = os.path.join(d, "disk_probe.bin")
        t0 = time.perf_counter()
        with open(pp, "wb") as f:
            f.write(memoryview(probe))
            f.flush()
            os.fsync(f.fileno())
        disk_mb_s = probe_mb / (time.perf_counter() - t0)
        os.remove(pp)
        del probe

        t0 = time.perf_counter()
        save_sharded(os.path.join(d, "latest.ckpt"), payload)
        save_s = time.perf_counter() - t0

        ckpt_path = os.path.join(d, "latest.ckpt")

        def timed_restore():
            t0 = time.perf_counter()
            back = load_sharded(ckpt_path, payload)
            # touch a leaf so lazy work can't hide
            float(np.asarray(
                jax.tree.leaves(back["state"].params)[0]
            ).ravel()[0])
            return time.perf_counter() - t0

        # historical row (r1-r5 series continuity): the first restore
        # right after save — page-cache WARM unless the box evicted the
        # bytes between save and restore, which is exactly the r4 0.59 s
        # vs r5 11.99 s ambiguity the labeled rows below resolve
        restore_s = timed_restore()

        # labeled WARM: median-of-3 cache-hot restores (a memcpy rate)
        warm_restores = [timed_restore() for _ in range(3)]

        # same-minute cold disk READ probe: evict the probe's own pages,
        # read it back — the r/w twin of the write probe above
        probe2 = np.ones(probe_mb * 2**20, np.uint8)
        pp = os.path.join(d, "disk_probe_read.bin")
        with open(pp, "wb") as f:
            f.write(memoryview(probe2))
            f.flush()
            os.fsync(f.fileno())
        del probe2
        disk_read_mb_s = None
        if _evict_page_cache(pp):
            t0 = time.perf_counter()
            with open(pp, "rb") as f:
                while f.read(32 * 2**20):
                    pass
            disk_read_mb_s = probe_mb / (time.perf_counter() - t0)
        os.remove(pp)

        # labeled COLD: evict the checkpoint's pages, restore once —
        # the relaunch-after-preemption number, disk-read bound
        cold_restore_s = (
            timed_restore() if _evict_page_cache(ckpt_path) else None
        )

        # the non-stalling trainer path: the step loop pays ONLY the
        # device→host snapshot; write rides a thread, commit lands at the
        # next epoch-boundary wait()
        ck = Checkpointer(d)
        # trainers call warm_for at init so the arena fault-in (the
        # dominant first-save cost on this kernel) overlaps the first XLA
        # compile; measure it as the background cost it is
        t0 = time.perf_counter()
        ck.warm_for(payload)
        ck._warm_thread.join()
        warm_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        ck.save_best_sharded(payload, block=False)
        stall_first_s = time.perf_counter() - t0  # arena pre-faulted
        # Steady state over FIVE saves, quoted as median + spread: the
        # r4 driver captured a single second-save sample of 1.84 s that
        # no instrumented rerun could reproduce (17 in-situ saves all
        # 0.32-0.69 s; /proc counters showed no reclaim/THP/steal — a
        # transient of the shared 1-core box). A single sample measures
        # the box's weather; the median measures the checkpointer.
        stalls = []
        for _ in range(5):
            ck.wait()  # commit previous (joins its write thread)
            t0 = time.perf_counter()
            ck.save_best_sharded(payload, block=False)
            stalls.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        ck.wait()
        commit_s = time.perf_counter() - t0

        reshard_keys = {}
        if "--reshard" in sys.argv:
            reshard_keys = _bench_reshard(d, cfg, tx, small)
    finally:
        shutil.rmtree(d, ignore_errors=True)

    print(json.dumps({
        "ckpt_params_m": round(n_params / 1e6, 1),
        "ckpt_bytes_mb": round(total_bytes / 2**20, 1),
        "ckpt_disk_mb_s": round(disk_mb_s, 1),
        "ckpt_save_s": round(save_s, 2),
        "ckpt_save_disk_bound_s": round(total_bytes / 2**20 / disk_mb_s, 2),
        "ckpt_restore_s": round(restore_s, 2),
        "ckpt_restore_warm_s": round(float(np.median(warm_restores)), 2),
        "ckpt_restore_warm_min_s": round(min(warm_restores), 2),
        "ckpt_restore_warm_max_s": round(max(warm_restores), 2),
        **({"ckpt_restore_cold_s": round(cold_restore_s, 2)}
           if cold_restore_s is not None else {}),
        **({"ckpt_disk_read_mb_s": round(disk_read_mb_s, 1),
            "ckpt_restore_disk_bound_s": round(
                total_bytes / 2**20 / disk_read_mb_s, 2)}
           if disk_read_mb_s else {}),
        "ckpt_arena_warm_bg_s": round(warm_s, 2),
        "ckpt_stall_first_s": round(stall_first_s, 2),
        "ckpt_stall_s": round(float(np.median(stalls)), 2),
        "ckpt_stall_min_s": round(min(stalls), 2),
        "ckpt_stall_max_s": round(max(stalls), 2),
        "ckpt_commit_after_overlap_s": round(commit_s, 2),
        "ckpt_mb_per_s": round(total_bytes / 2**20 / max(save_s, 1e-9), 1),
        **reshard_keys,
    }))


def _bench_reshard(d: str, cfg, tx, small: bool) -> dict:
    """Elastic-restore timings: one dp4xtp2+FSDP checkpoint restored
    onto three topologies, plus the offline repartition path."""
    import dataclasses

    from pytorch_distributed_tpu import reshard
    from pytorch_distributed_tpu.parallel import make_mesh
    from pytorch_distributed_tpu.train.lm import (
        create_lm_state,
        shard_lm_state,
    )
    from pytorch_distributed_tpu.utils.checkpoint import save_sharded

    tp_cfg = dataclasses.replace(cfg, model_axis="model", tp_size=2)
    state = create_lm_state(tp_cfg, tx, jax.random.key(1), init_len=64)
    devs = jax.devices()

    def mesh_of(dp, sp, mp):
        return make_mesh(devs[: dp * sp * mp], data_parallel=dp,
                         seq_parallel=sp, model_parallel=mp)

    mesh_a = mesh_of(4, 1, 2)
    placed, _ = shard_lm_state(mesh_a, state, tp_cfg, fsdp=True)
    src = os.path.join(d, "reshard_src.ckpt")
    save_sharded(src, {"state": placed, "epoch": 1, "step": 7,
                       "best_ppl": 5.0})

    def timed_restore(path, dp, sp, mp, target_cfg, fsdp):
        mesh = mesh_of(dp, sp, mp)
        specs = reshard.resolve_lm_state_specs(state, mesh, target_cfg,
                                               fsdp=fsdp)
        template = {"state": state, "epoch": 0, "step": 0, "best_ppl": 0.0}
        shardings = reshard.payload_shardings(mesh, template, specs)
        t0 = time.perf_counter()
        back, info = reshard.load_elastic(path, template, shardings,
                                          mesh=mesh)
        jax.block_until_ready(jax.tree.leaves(back["state"].params))
        return time.perf_counter() - t0, info

    cfg1 = dataclasses.replace(cfg, model_axis=None, tp_size=1)
    same_s, same_info = timed_restore(src, 4, 1, 2, tp_cfg, True)
    to22_s, to22_info = timed_restore(src, 2, 1, 2, tp_cfg, True)
    to81_s, _ = timed_restore(src, 8, 1, 1, cfg1, True)

    dst = os.path.join(d, "reshard_22.ckpt")
    t0 = time.perf_counter()
    reshard.repartition(src, dst, {"data": 2, "seq": 1, "model": 2},
                        config=tp_cfg, fsdp=True)
    offline_s = time.perf_counter() - t0
    pre_s, pre_info = timed_restore(dst, 2, 1, 2, tp_cfg, True)

    return {
        # same-mesh restore: every region exact-block (the r5 baseline)
        "ckpt_reshard_same_mesh_s": round(same_s, 2),
        "ckpt_reshard_same_assembled": same_info.assembled_regions,
        # cross-topology elastic restores: block assembly on the fly
        "ckpt_reshard_to_2x2_s": round(to22_s, 2),
        "ckpt_reshard_to_2x2_assembled": to22_info.assembled_regions,
        "ckpt_reshard_to_8x1_s": round(to81_s, 2),
        # offline repartition + the exact-path restore it buys
        "ckpt_reshard_offline_s": round(offline_s, 2),
        "ckpt_reshard_prepartitioned_s": round(pre_s, 2),
        "ckpt_reshard_prepartitioned_assembled":
            pre_info.assembled_regions,
    }


if __name__ == "__main__":
    main()
