"""Shared recipe scaffolding.

The reference implements its epoch/val/suspend loop four times (SURVEY.md
§2a R1-R4); here each recipe is a Mesh + a TrainerConfig over the one SPMD
trainer. This module holds the pieces every recipe shares: the hardcoded
reference hyperparameters (``restnet_ddp.py:77-83``), dataset construction
(real TPRC ImageNet or the synthetic stand-in), and the run function.

Recipes keep the reference's zero-required-args ergonomics (`python
recipes/resnet_ddp.py`); ``--synthetic`` / ``--tiny`` exist so every recipe
also runs as a smoke test on a laptop CPU (SURVEY.md §4 — the reference can
only validate on its real cluster; we refuse to inherit that).
"""

from __future__ import annotations

import argparse
import os
import sys

# `python recipes/<recipe>.py` puts recipes/ (not the repo root) on sys.path;
# make the package importable without an install.
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from pytorch_distributed_tpu.utils.env import compile_cache_dir, set_env

set_env("202607")

import jax
import jax.numpy as jnp

from pytorch_distributed_tpu.models import resnet50
from pytorch_distributed_tpu.models.resnet import BasicBlock, ResNet
from pytorch_distributed_tpu.parallel import global_batch_size
from pytorch_distributed_tpu.parallel.mesh import DATA_AXIS
from pytorch_distributed_tpu.train import Trainer, TrainerConfig
from pytorch_distributed_tpu.utils.logging import rank0_print
from pytorch_distributed_tpu.utils.suspend import SuspendWatcher


def _base_parser(description: str, save_dir: str,
                 batch_help: str) -> argparse.ArgumentParser:
    """Flags every recipe shares — one definition, no drift."""
    p = argparse.ArgumentParser(description=description)
    p.add_argument("--synthetic", action="store_true",
                   help="synthetic data instead of on-disk records")
    p.add_argument("--synthetic-size", type=int, default=None,
                   help="training examples the synthetic dataset holds "
                        "(validation gets an eighth; default 8192 images "
                        "/ 4096 sequences)")
    p.add_argument("--tiny", action="store_true",
                   help="tiny model/epochs for smoke-testing on CPU")
    p.add_argument("--save-dir", default=save_dir, help="checkpoint directory")
    p.add_argument("--epochs", type=int, default=None)
    p.add_argument("--batch-size", type=int, default=None, help=batch_help)
    p.add_argument("--log-every", type=int, default=100,
                   help="steps between train log records (ref "
                        "resnet_single_gpu.py:23)")
    p.add_argument("--save-every-n-steps", type=int, default=0,
                   help="step-interval durability: non-blocking sharded "
                        "step-<N>.ckpt saves every N steps (0 = off, the "
                        "reference's suspend/best-only policy)")
    p.add_argument("--keep-last-ckpts", type=int, default=3,
                   help="retention for --save-every-n-steps (completed "
                        "checkpoints kept; resume picks the newest)")
    # Resilience guards (resilience/; ANALYSIS.md "Failure model &
    # recovery guarantees"). Example — survive NaN spikes and hangs on a
    # long run:
    #   python recipes/lm_pretrain.py --tiny --nan-guard --max-bad-steps 5 \
    #       --watchdog-timeout 600 --save-every-n-steps 500
    p.add_argument("--nan-guard", action="store_true",
                   help="compile a finite gate into the train step: a "
                        "non-finite loss/grad step keeps the pre-step "
                        "params on device (no host sync) instead of "
                        "poisoning the run")
    p.add_argument("--max-bad-steps", type=int, default=0,
                   help="with --nan-guard: after this many CONSECUTIVE "
                        "skipped steps, roll back to the last good "
                        "checkpoint (0 = skip-only, never roll back)")
    p.add_argument("--watchdog-timeout", type=float, default=0.0,
                   help="seconds without a completed step before the "
                        "watchdog dumps all-thread stacks and latches "
                        "the suspend (checkpoint-and-yield) path "
                        "(0 = off)")
    # Telemetry (telemetry/; ANALYSIS.md "Observability & goodput").
    # Example — sync-free metrics + spans + a goodput report:
    #   python recipes/lm_pretrain.py --tiny --flush-every 8 \
    #       --metrics-out run.jsonl --trace-dir traces/
    #   python scripts/telemetry_report.py run.jsonl
    p.add_argument("--metrics-out", default=None,
                   help="JSONL metrics stream path (default "
                        "<save-dir>/metrics.jsonl; rank-0 only). Render "
                        "with scripts/telemetry_report.py — train series, "
                        "epoch timing, and the run's goodput breakdown")
    p.add_argument("--trace-dir", default=None,
                   help="where the span stream (always recorded; "
                        "trainer.build/program.load/train.data_wait/"
                        "train.step_dispatch/ckpt.*) is written at fit "
                        "end: <dir>/spans.trace.json. Under a profiler "
                        "session (PDT_TRACE_DIR) the same spans are "
                        "pdt:<name> annotations in the xprof trace")
    p.add_argument("--flush-every", type=int, default=32,
                   help="device metrics ring window: log-interval metric "
                        "scalars accumulate on device and drain with one "
                        "lagged transfer per window — logging never "
                        "blocks the dispatch pipeline (0 = legacy "
                        "blocking float() sync per log interval)")
    # Compile cache (compilecache/; ANALYSIS.md "Cold start & compile
    # cache"). Example — a preemption-resumed run that reloads its step
    # executables from disk instead of recompiling:
    #   python recipes/lm_pretrain.py --tiny --warmup \
    #       --compile-cache-dir /shared/pdt_cache
    # (or point every job at one cache: export JAX_COMPILATION_CACHE_DIR=...)
    p.add_argument("--compile-cache-dir", default=None,
                   help="persistent XLA compilation cache directory "
                        "(default <repo>/.jax_cache; an exported "
                        "JAX_COMPILATION_CACHE_DIR wins over this flag): a "
                        "relaunched or preemption-resumed run with the "
                        "same fingerprint loads executables from disk "
                        "instead of recompiling")
    p.add_argument("--warmup", action="store_true",
                   help="AOT-compile the run's program registry (train + "
                        "eval step) before step 1 — with a populated "
                        "--compile-cache-dir the goodput compile fraction "
                        "collapses; kind=\"warmup\" manifest records land "
                        "in the metrics JSONL")
    # Attribution & forensics (telemetry/; ANALYSIS.md "Performance
    # attribution & forensics"). Example — flag a wedged step and leave a
    # readable event ring behind:
    #   PDT_FAULT_PLAN='{"faults":[{"site":"train.step","kind":"hang",
    #       "at":10,"seconds":2}]}' python recipes/lm_pretrain.py --tiny \
    #       --metrics-out run.jsonl --cost-cards
    #   python scripts/telemetry_report.py run.jsonl   # anomaly + roofline
    p.add_argument("--cost-cards", action="store_true",
                   help="emit kind=\"program_cost\" records at fit end: "
                        "per-program FLOPs/bytes from the compiler joined "
                        "with measured step time into MFU and a "
                        "compute-vs-bandwidth roofline class (one extra "
                        "AOT compile per program, cache-hit when "
                        "--compile-cache-dir is set)")
    p.add_argument("--anomaly-threshold", type=float, default=8.0,
                   help="robust z-score bound for the streaming anomaly "
                        "sentinel over step-time/data-wait series "
                        "(kind=\"anomaly\" JSONL with context; 0 = off)")
    p.add_argument("--metrics-port", type=int, default=None,
                   help="serve live Prometheus-text /metrics on this "
                        "port (stdlib HTTP thread; 0 = ephemeral); "
                        "scripts/pdt_top.py is the JSONL-tailing twin")
    return p


def parse_args(description: str, argv=None) -> argparse.Namespace:
    p = _base_parser(description, save_dir="output",
                     batch_help="per-replica batch size (ref default 400)")
    p.add_argument("--data-dir", default=None, help="TPRC ImageNet directory")
    p.add_argument("--sync-bn", action="store_true",
                   help="BatchNorm statistics over the global batch (torch "
                        "SyncBatchNorm) instead of each replica's own "
                        "(DDP's default): a data-parallel run then matches "
                        "one device on the same global batch")
    p.add_argument("--raw", action="store_true",
                   help="use the decode-free raw split (<data-dir>/"
                        "{train,val}.rawtprc; pack with "
                        "scripts/pack_imagenet.py --raw)")
    p.add_argument("--raw-aug", default="rrc", choices=["rrc", "crop"],
                   help="raw-split train augmentation: rrc keeps the "
                        "reference's RandomResizedCrop semantics (applied "
                        "to the stored 256px image); crop is the classic "
                        "random-crop+flip — ~3x faster per core but a "
                        "different training distribution")
    return p.parse_args(argv)


def build_datasets(args):
    if args.synthetic or args.tiny:
        from pytorch_distributed_tpu.data import SyntheticImageClassification

        size = 16 if args.tiny else 224
        n_train, n_val = (256, 64) if args.tiny else (8192, 1024)
        if args.synthetic_size:
            n_train = args.synthetic_size
            n_val = max(n_train // 8, 1)
        classes = 10 if args.tiny else 1000
        return (
            SyntheticImageClassification(n_train, size, classes),
            SyntheticImageClassification(n_val, size, classes, seed=1),
            size,
            classes,
        )
    from pytorch_distributed_tpu.data.imagenet import DEFAULT_DATA_DIR, ImageNet

    data_dir = args.data_dir or DEFAULT_DATA_DIR
    if getattr(args, "raw", False):
        # decode-free fast path (pre-decoded uint8 records, native C
        # batch collate, device-side normalization): ~10-30x the JPEG
        # loader's throughput per core — scripts/bench_data.py. Pack with
        # scripts/pack_imagenet.py --raw.
        from pytorch_distributed_tpu.data import RawImageNet

        return (
            RawImageNet("train", data_dir=data_dir,
                        aug=getattr(args, "raw_aug", "rrc")),
            RawImageNet("val", data_dir=data_dir, aug="none"),
            224,
            1000,
        )
    # ref: hfai.datasets.ImageNet('train'/'val', transform), restnet_ddp.py:107,117
    return (
        ImageNet("train", data_dir=data_dir),
        ImageNet("val", data_dir=data_dir),
        224,
        1000,
    )


def build_model(args, num_classes: int, precision: str):
    dtype = jnp.bfloat16 if precision == "bf16" else jnp.float32
    bn_axis = DATA_AXIS if args.sync_bn else None
    if args.tiny:
        return ResNet(stage_sizes=(1, 1), block_cls=BasicBlock,
                      num_classes=num_classes, num_filters=8, dtype=dtype,
                      bn_cross_replica_axis=bn_axis)
    # ref: torchvision.models.resnet50(), restnet_ddp.py:98
    return resnet50(num_classes=num_classes, dtype=dtype,
                    bn_cross_replica_axis=bn_axis)


def build_trainer(args, mesh, precision: str = "fp32") -> Trainer:
    """Datasets, model and trainer from parsed flags."""
    train_ds, val_ds, image_size, num_classes = build_datasets(args)
    model = build_model(args, num_classes, precision)
    cfg = TrainerConfig(
        # ref hyperparameters: restnet_ddp.py:77-83, resnet_single_gpu.py:107-109
        epochs=args.epochs if args.epochs is not None else (2 if args.tiny else 100),
        batch_size=args.batch_size if args.batch_size is not None else (4 if args.tiny else 400),
        lr=0.1 if not args.tiny else 0.05,
        momentum=0.9,
        weight_decay=1e-4,
        lr_step_epochs=30,
        lr_gamma=0.1,
        precision=precision,
        save_dir=args.save_dir,
        log_every=args.log_every,
        num_workers=0 if args.tiny else 8,
        save_every_n_steps=args.save_every_n_steps,
        keep_last_ckpts=args.keep_last_ckpts,
        nan_guard=args.nan_guard,
        max_bad_steps=args.max_bad_steps,
        watchdog_timeout_s=args.watchdog_timeout,
        metrics_out=args.metrics_out,
        trace_dir=args.trace_dir,
        flush_every=args.flush_every,
        compile_cache_dir=compile_cache_dir(args.compile_cache_dir),
        warmup=args.warmup,
        cost_cards=args.cost_cards,
        anomaly_threshold=args.anomaly_threshold,
        metrics_port=args.metrics_port,
    )
    trainer = Trainer(
        model,
        train_ds,
        val_ds,
        cfg,
        mesh=mesh,
        suspend_watcher=SuspendWatcher(),
        input_shape=(1, image_size, image_size, 3),
    )
    rank0_print(
        f"devices: {jax.device_count()} ({jax.process_count()} hosts), "
        f"mesh {dict(mesh.shape)}, global batch "
        f"{global_batch_size(mesh, cfg.batch_size)}, precision {precision}"
    )
    return trainer


def run(args, mesh, precision: str = "fp32") -> dict:
    """Build everything and fit — the body shared by all four recipes."""
    summary = build_trainer(args, mesh, precision).fit()
    rank0_print(f"done: best acc1 {summary.get('best_acc', 0.0):.2f}")
    return summary


def parse_lm_args(description: str, argv=None) -> argparse.Namespace:
    """Arguments for the LM pretraining recipe (recipes/lm_pretrain.py)."""
    p = _base_parser(description, save_dir="output_lm",
                     batch_help="sequences per data-replica step")
    p.add_argument("--tokens", default=None,
                   help="flat int token array (.npy), windowed to --seq-len")
    p.add_argument("--seq-len", type=int, default=2048)
    p.add_argument("--vocab-size", type=int, default=32000)
    p.add_argument("--layers", type=int, default=12)
    p.add_argument("--heads", type=int, default=12)
    p.add_argument("--kv-heads", type=int, default=None,
                   help="grouped-query attention: K/V head count (must "
                        "divide --heads; default = MHA). Shrinks the "
                        "decode KV cache and kv projection by the group "
                        "factor")
    p.add_argument("--pos-embedding", default="learned",
                   choices=["learned", "rope"],
                   help="position encoding: GPT-2-style learned wpe table "
                        "or rotary (q/k rotation in attention, no table)")
    p.add_argument("--embed-dim", type=int, default=768)
    p.add_argument("--dropout", type=float, default=0.0)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--grad-clip-norm", type=float, default=0.0,
                   help="global-norm gradient clip (0 = off, the pre-r4 "
                        "behavior so published trajectories stay "
                        "reproducible; 1.0 is the usual LM setting). The "
                        "norm is sharding-correct under TP/SP/FSDP "
                        "(ops.optim.sharded_global_norm)")
    p.add_argument("--attention", default="flash",
                   choices=["dense", "blockwise", "flash", "ring",
                            "ring_flash"],
                   help="attention path (seq-sharded runs default to "
                        "ring_flash; pass ring for the XLA ring)")
    p.add_argument("--seq-parallel", type=int, default=2,
                   help="sequence-parallel degree (ring attention when > 1)")
    p.add_argument("--ring-layout", default="contiguous",
                   choices=["contiguous", "zigzag"],
                   help="causal-ring shard layout; zigzag balances the "
                        "causal critical path across seq shards "
                        "(parallel/sequence.py)")
    p.add_argument("--fsdp", action="store_true",
                   help="ZeRO-shard replicated params/optimizer over the "
                        "data axis (gather/scatter in the step; composes "
                        "with TP/EP/SP)")
    p.add_argument("--pipeline-stages", type=int, default=0,
                   help="train through the GPipe pipeline with this many "
                        "stages on the model axis (0 = off; excludes "
                        "--model-parallel/--seq-parallel)")
    p.add_argument("--pp-microbatches", type=int, default=8,
                   help="GPipe microbatches per step (clamped to the "
                        "per-shard batch; 8 is the measured default, "
                        "BENCH_PP.md)")
    p.add_argument("--model-parallel", type=int, default=1,
                   help="tensor-parallel degree")
    p.add_argument("--vocab-parallel", action="store_true",
                   help="Megatron vocab parallelism: shard wte + lm_head "
                        "vocab dims over the TP axis (needs "
                        "--model-parallel > 1; ~-44%% per-device state at "
                        "tp=2, BENCH_LM.md r5)")
    return p.parse_args(argv)
