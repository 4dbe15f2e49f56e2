"""Kill-matrix child: a tiny real training run for crash-recovery tests.

Launched as a subprocess by tests/test_resilience.py (and by
``scripts/ci_check.sh --resilience-smoke``). Run 1 carries a
``PDT_FAULT_PLAN`` that SIGKILLs the process at an injected checkpoint
hazard site; run 2 relaunches with no plan and must resume from a
complete checkpoint. The child logs every step to ``progress.jsonl`` and
writes ``result.json`` on a clean finish, so the parent can assert
resume-point and step-monotonicity without parsing stdout.

Not a pytest module (no ``test_`` prefix) — invoke as
``python tests/crash_child.py --save-dir DIR``.
"""

import argparse
import json
import os
import sys

# 8 virtual CPU devices, pinned BEFORE jax import (same as conftest.py)
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

import numpy as np  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--save-dir", required=True)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--train-size", type=int, default=32)  # 2 steps/epoch
    # elastic resume (round 9): mesh shape "data,seq,model" and a
    # per-replica batch size, so a relaunch can resume the SAME save dir
    # on a DIFFERENT topology at a fixed global batch (reshard/)
    ap.add_argument("--mesh", default="8,1,1",
                    help="data,seq,model axis sizes (devices used = "
                    "their product)")
    ap.add_argument("--batch-size", type=int, default=2,
                    help="per-data-replica batch (global = bs x data)")
    args = ap.parse_args()
    dp, sp, mp = (int(x) for x in args.mesh.split(","))

    from pytorch_distributed_tpu.data import SyntheticImageClassification
    from pytorch_distributed_tpu.models.resnet import BasicBlock, ResNet
    from pytorch_distributed_tpu.parallel import make_mesh
    from pytorch_distributed_tpu.train import Trainer, TrainerConfig

    progress_path = os.path.join(args.save_dir, "progress.jsonl")

    class LoggingTrainer(Trainer):
        """Appends (run pid, global step, loss) after every train step so
        the parent can assert monotonic step progress across the crash."""

        def _post_step(self, metrics):
            super()._post_step(metrics)
            with open(progress_path, "a") as f:
                f.write(json.dumps({
                    "pid": os.getpid(),
                    "gstep": int(np.asarray(jax.device_get(self.state.step))),
                    "loss": float(metrics["loss"]),
                }) + "\n")

    cfg = TrainerConfig(
        epochs=args.epochs,
        batch_size=args.batch_size,  # default ×8 replicas = global 16
        lr=0.05,
        save_dir=args.save_dir,
        log_every=0,
        num_workers=0,
        prefetch=1,
        save_every_n_steps=1,  # every step is a durability point
        keep_last_ckpts=3,
    )
    model = ResNet(stage_sizes=(1, 1), block_cls=BasicBlock,
                   num_classes=10, num_filters=8)
    trainer = LoggingTrainer(
        model,
        SyntheticImageClassification(size=args.train_size, image_size=16,
                                     num_classes=10),
        SyntheticImageClassification(size=16, image_size=16, num_classes=10,
                                     seed=1),
        cfg,
        mesh=make_mesh(jax.devices()[: dp * sp * mp], data_parallel=dp,
                       seq_parallel=sp, model_parallel=mp),
        input_shape=(1, 16, 16, 3),
    )
    resumed = trainer.try_resume()  # fit() re-runs this; it's idempotent
    start_epoch, start_step = trainer.start_epoch, trainer.start_step
    summary = trainer.fit()
    with open(os.path.join(args.save_dir, "result.json"), "w") as f:
        json.dump({
            "resumed": bool(resumed),
            "start_epoch": start_epoch,
            "start_step": start_step,
            "final_step": int(np.asarray(jax.device_get(trainer.state.step))),
            "val_loss": float(summary["loss"]),
        }, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
