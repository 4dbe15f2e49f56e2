"""Cross-topology kill-matrix child: a tiny real LM training run whose
mesh shape is a command-line parameter.

The elastic-resume proof (tests/test_reshard.py, ROADMAP item 4) runs
this child three ways against ONE save directory: killed by an injected
SIGKILL on mesh (4,1,2), then relaunched on (2,1,2) and (8,1,1) — the
relaunch must reshard the checkpoint onto its own topology and finish
the run. The GLOBAL batch is fixed by ``--global-batch`` (the per-replica
batch is derived from the mesh's data-axis size), and the LM carries no
batch-norm and no dropout, so the training FUNCTION is identical across
topologies — the logged loss series of a resumed run matches an
unpreempted control up to cross-topology reduction order (bit-equal when
the topology is unchanged; see ANALYSIS.md "Elastic topology & reshard"
for the bit-stability boundary).

Every step appends (pid, gstep, loss) to ``progress.jsonl``;
``result.json`` lands on a clean finish. Not a pytest module — invoke as
``python tests/reshard_child.py --save-dir DIR --mesh 4,1,2``.
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
    ap.add_argument("--mesh", default="4,1,2",
                    help="data,seq,model axis sizes; model>1 runs TP")
    ap.add_argument("--global-batch", type=int, default=8,
                    help="fixed across topologies (per-replica bs is "
                    "global/data)")
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--steps-per-epoch", type=int, default=3)
    ap.add_argument("--fsdp", action="store_true",
                    help="ZeRO-shard the replicated leaves over data")
    args = ap.parse_args()
    dp, sp, mp = (int(x) for x in args.mesh.split(","))
    if args.global_batch % dp:
        raise SystemExit(
            f"--global-batch {args.global_batch} not divisible by "
            f"data={dp}"
        )

    from pytorch_distributed_tpu.data.tokens import SyntheticTokens
    from pytorch_distributed_tpu.models.transformer import tiny_config
    from pytorch_distributed_tpu.parallel import make_mesh
    from pytorch_distributed_tpu.train import LMTrainer, LMTrainerConfig

    progress_path = os.path.join(args.save_dir, "progress.jsonl")

    class LoggingTrainer(LMTrainer):
        """Appends (run pid, global step, loss) after every train step so
        the parent can compare series across crash + topology change."""

        def _post_step(self, metrics):
            super()._post_step(metrics)
            with open(progress_path, "a") as f:
                f.write(json.dumps({
                    "pid": os.getpid(),
                    "gstep": int(np.asarray(jax.device_get(self.state.step))),
                    "loss": float(jax.device_get(metrics["loss"])),
                }) + "\n")

    mesh = make_mesh(jax.devices()[: dp * sp * mp], data_parallel=dp,
                     seq_parallel=sp, model_parallel=mp)
    model_cfg = tiny_config(
        attention="dense",
        model_axis="model" if mp > 1 else None,
        tp_size=mp,
        dropout=0.0,  # no rng in the step: the function is topology-pure
    )
    cfg = LMTrainerConfig(
        epochs=args.epochs,
        batch_size=args.global_batch // dp,
        lr=1e-2,
        save_dir=args.save_dir,
        log_every=0,
        num_workers=0,
        prefetch=1,
        seed=0,
        save_every_n_steps=1,  # every step is a durability point
        keep_last_ckpts=4,
        fsdp=args.fsdp,
    )
    train = SyntheticTokens(
        size=args.global_batch * args.steps_per_epoch, seq_len=32,
        vocab_size=128,
    )
    val = SyntheticTokens(size=args.global_batch, seq_len=32,
                          vocab_size=128, seed=9)
    trainer = LoggingTrainer(model_cfg, train, val, cfg, mesh=mesh)
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
            "mesh": [dp, sp, mp],
        }, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
