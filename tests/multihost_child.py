"""Child process for the 2-process localhost rendezvous tests.

Launched by tests/test_multihost.py with the reference's env contract
(MASTER_IP/MASTER_PORT/WORLD_SIZE/RANK, ``restnet_ddp.py:87-94``) on the CPU
backend with 4 virtual local devices per process → 8 global. Runs the real
DDP code path: ``init_process_group`` → global ``make_mesh`` → ``Trainer``
on synthetic data.

Modes (argv[1]):
  train    fit() a tiny run to completion, print a JSON result line with a
           parameter digest so the parent can assert cross-host agreement.
  suspend  train with many epochs and suspend_sync_every=1; the parent
           SIGTERMs ONE rank mid-epoch and both processes must checkpoint
           (rank 0) and yield together. Touches <save_dir>/started.<rank>
           once training has begun so the parent knows when to fire.
  lm       LMTrainer over a dp2×sp2×tp2 GLOBAL mesh: ring attention and
           tensor parallelism span the two processes, so the checkpoint
           payload's gather_global really runs its cross-process
           process_allgather collective (TP-sharded leaves are not locally
           addressable). Prints the same JSON result line as ``train``.
"""

import json
import os
import sys

# Backend setup must precede the jax import (see tests/conftest.py).
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def run_lm(save_dir: str) -> None:
    from pytorch_distributed_tpu.data import SyntheticTokens
    from pytorch_distributed_tpu.models.transformer import tiny_config
    from pytorch_distributed_tpu.parallel import make_mesh
    from pytorch_distributed_tpu.parallel.distributed import get_rank, get_world_size
    from pytorch_distributed_tpu.train import LMTrainer, LMTrainerConfig

    mesh = make_mesh(data_parallel=2, seq_parallel=2, model_parallel=2)
    model_cfg = tiny_config(
        attention="ring", model_axis="model", tp_size=2, dropout=0.1
    )
    cfg = LMTrainerConfig(epochs=1, batch_size=2, lr=1e-2, save_dir=save_dir,
                          num_workers=0, log_every=2)
    train = SyntheticTokens(size=16, seq_len=32, vocab_size=128)
    val = SyntheticTokens(size=8, seq_len=32, vocab_size=128, seed=9)
    trainer = LMTrainer(model_cfg, train, val, cfg, mesh=mesh)
    summary = trainer.fit()
    # sanity: the TP qkv kernels really span processes (gather_global had
    # to run its cross-process collective to checkpoint them)
    qkv = trainer.state.params["block0"]["attn"]["qkv"]["kernel"]
    assert not qkv.is_fully_addressable
    from pytorch_distributed_tpu.utils.checkpoint import gather_global

    param_l1 = float(
        sum(np.abs(np.asarray(leaf)).sum()
            for leaf in jax.tree.leaves(gather_global(trainer.state.params)))
    )
    # ---- sharded checkpoint: save + resume WITHOUT any full-state gather
    # anywhere. gather_global (the one full-materialization entry point) is
    # patched to raise so a regression to gather-based checkpointing fails
    # loudly on both ranks. (process_allgather itself can't be patched:
    # the save's own sync_global_devices barrier uses it for a tiny
    # name-agreement value — not state.)
    from pytorch_distributed_tpu.utils import checkpoint as ckpt_mod

    def _forbidden(*a, **k):
        raise AssertionError(
            "gather_global called during sharded checkpoint save/resume"
        )

    orig_allgather = ckpt_mod.gather_global
    ckpt_mod.gather_global = _forbidden
    try:
        trainer.ckpt.save_latest_sharded(trainer._payload_live(1, 5))
        import glob as _glob

        my_files = _glob.glob(os.path.join(
            save_dir, "latest.ckpt", f"shard-*-{get_rank():05d}.npz"
        ))
        assert my_files, f"no shard file for rank {get_rank()}"
        # the TP-sharded qkv stack's blocks span BOTH processes' files
        with open(os.path.join(save_dir, "latest.ckpt",
                               "manifest.json")) as f:
            manifest = json.load(f)
        qkv_meta = next(
            v for k, v in manifest["leaves"].items()
            if k.startswith("state/params") and "qkv/kernel" in k
        )
        qkv_files = {b["file"] for b in qkv_meta["blocks"]}
        assert len(qkv_files) == 2, qkv_files

        fresh = LMTrainer(model_cfg, train, val, cfg, mesh=mesh)
        assert fresh.try_resume()
        assert fresh.start_epoch == 1 and fresh.start_step == 5

        def _local_equal(a, b):
            # compare only this process's shards (the whole point is that
            # no process can see the global value of a sharded leaf)
            sa = {s.device.id: np.asarray(s.data)
                  for s in a.addressable_shards}
            sb = {s.device.id: np.asarray(s.data)
                  for s in b.addressable_shards}
            return sa.keys() == sb.keys() and all(
                np.array_equal(sa[k], sb[k]) for k in sa
            )

        same = jax.tree.map(_local_equal, trainer.state.params,
                            fresh.state.params)
        sharded_ckpt_ok = all(jax.tree.leaves(same))
    finally:
        ckpt_mod.gather_global = orig_allgather

    print(json.dumps({
        "rank": get_rank(),
        "world": get_world_size(),
        "val_loss": round(summary["loss"], 6),
        "ppl": round(summary["ppl"], 4),
        "best_acc": 0.0,
        "param_l1": param_l1,
        "final_step": int(jax.device_get(trainer.state.step)),
        "sharded_ckpt_ok": bool(sharded_ckpt_ok),
    }))


def _tiny_lm_trainer(save_dir: str):
    from pytorch_distributed_tpu.data import SyntheticTokens
    from pytorch_distributed_tpu.models.transformer import tiny_config
    from pytorch_distributed_tpu.parallel import make_mesh
    from pytorch_distributed_tpu.train import LMTrainer, LMTrainerConfig

    mesh = make_mesh(data_parallel=2, seq_parallel=2, model_parallel=2)
    model_cfg = tiny_config(
        attention="ring", model_axis="model", tp_size=2, dropout=0.0
    )
    cfg = LMTrainerConfig(epochs=1, batch_size=2, lr=1e-2, save_dir=save_dir,
                          num_workers=0, log_every=2)
    train = SyntheticTokens(size=16, seq_len=32, vocab_size=128)
    val = SyntheticTokens(size=8, seq_len=32, vocab_size=128, seed=9)
    return LMTrainer(model_cfg, train, val, cfg, mesh=mesh)


def run_lm_crash_save(save_dir: str) -> None:
    """Complete save (epoch 1, step 5), then a save that 'crashes' after
    its data files land but BEFORE the manifest commit (epoch 2, step 9).
    The parent relaunches with lm_crash_resume and asserts the survivor is
    the COMPLETE save — the durability property of token-named files."""
    from pytorch_distributed_tpu.parallel.distributed import get_rank
    from pytorch_distributed_tpu.utils.checkpoint import _ShardedSave

    trainer = _tiny_lm_trainer(save_dir)
    trainer.ckpt.save_latest_sharded(trainer._payload_live(1, 5))
    crash = _ShardedSave(trainer.ckpt.latest_path,
                         trainer._payload_live(2, 9))
    crash.write()  # both ranks' data files land...
    # ...and the job dies before finalize(): no barrier, no manifest
    print(json.dumps({"rank": get_rank(), "crash_save_done": True}))


def run_lm_crash_resume(save_dir: str) -> None:
    from pytorch_distributed_tpu.parallel.distributed import get_rank

    trainer = _tiny_lm_trainer(save_dir)
    resumed = trainer.try_resume()
    print(json.dumps({
        "rank": get_rank(),
        "resumed": bool(resumed),
        "epoch": int(trainer.start_epoch),
        "step": int(trainer.start_step),
    }))


def main() -> None:
    mode = sys.argv[1]
    save_dir = sys.argv[2]

    from pytorch_distributed_tpu.data.synthetic import SyntheticImageClassification
    from pytorch_distributed_tpu.models.resnet import BasicBlock, ResNet
    from pytorch_distributed_tpu.parallel import make_mesh
    from pytorch_distributed_tpu.parallel.distributed import (
        get_rank,
        get_world_size,
        init_process_group,
        is_primary,
    )
    from pytorch_distributed_tpu.train import Trainer, TrainerConfig
    from pytorch_distributed_tpu.utils.suspend import SuspendWatcher

    init_process_group()
    assert get_world_size() == 2, get_world_size()
    assert jax.device_count() == 8, jax.device_count()
    assert is_primary() == (get_rank() == 0)

    if mode == "lm":
        run_lm(save_dir)
        return
    if mode == "lm_crash_save":
        run_lm_crash_save(save_dir)
        return
    if mode == "lm_crash_resume":
        run_lm_crash_resume(save_dir)
        return

    model = ResNet(
        stage_sizes=(1, 1), block_cls=BasicBlock, num_classes=10, num_filters=8
    )
    epochs = 2 if mode == "train" else 50
    cfg = TrainerConfig(
        epochs=epochs,
        batch_size=4,
        lr=0.05,
        save_dir=save_dir,
        num_workers=0,
        log_every=1,
        suspend_sync_every=int(os.environ.get("SUSPEND_SYNC", "1")),
    )
    train_ds = SyntheticImageClassification(size=64, image_size=16, num_classes=10)
    val_ds = SyntheticImageClassification(size=16, image_size=16, num_classes=10, seed=1)

    watcher = SuspendWatcher(install_handlers=(mode == "suspend"))
    trainer = Trainer(
        model,
        train_ds,
        val_ds,
        cfg,
        mesh=make_mesh(),
        suspend_watcher=watcher,
        input_shape=(1, 16, 16, 3),
    )

    if mode == "suspend":
        # Signal readiness AFTER the first optimizer step has executed so the
        # parent's SIGTERM lands mid-training, not mid-compile.
        orig_epoch = trainer.train_epoch

        def epoch_with_sentinel(epoch, start_step=0):
            if epoch == trainer.start_epoch:
                first = [True]

                orig_suspend = trainer._maybe_suspend

                def hooked(ep, st):
                    if first[0]:
                        first[0] = False
                        with open(
                            os.path.join(save_dir, f"started.{get_rank()}"), "w"
                        ) as f:
                            f.write("1")
                    orig_suspend(ep, st)

                trainer._maybe_suspend = hooked
            return orig_epoch(epoch, start_step)

        trainer.train_epoch = epoch_with_sentinel

    summary = trainer.fit()
    param_l1 = float(
        sum(np.abs(np.asarray(jax.device_get(p))).sum()
            for p in jax.tree.leaves(trainer.state.params))
    )
    print(json.dumps({
        "rank": get_rank(),
        "world": get_world_size(),
        "resumed_from_step": trainer.start_epoch,
        "val_loss": round(summary["loss"], 6),
        "acc1": round(summary["acc1"], 4),
        "best_acc": round(summary["best_acc"], 4),
        "param_l1": param_l1,
        "final_step": int(jax.device_get(trainer.state.step)),
    }))


if __name__ == "__main__":
    main()
