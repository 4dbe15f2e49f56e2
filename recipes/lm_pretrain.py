"""Transformer LM pretraining over a (data, seq, model) mesh.

Beyond the reference's capability surface (it has no attention model,
SURVEY.md §5 long-context ABSENT) but a first-class recipe here: the same
zero-required-args ergonomics, trainer contracts (suspend/resume,
latest/best checkpoints, JSONL metrics), and env rendezvous as the ResNet
recipes, driving ``LMTrainer`` with ring-attention sequence parallelism
and optional tensor parallelism.

    python recipes/lm_pretrain.py --tiny            # CPU smoke (8 virtual devices)
    python recipes/lm_pretrain.py --tokens corpus.npy --seq-len 2048
    MASTER_IP=… WORLD_SIZE=… RANK=… python recipes/lm_pretrain.py   # pod

The mesh factors the device count as dp×sp×tp from --seq-parallel /
--model-parallel (default: sequence parallelism on, tp off). Token data is
a flat int array (.npy or memmap-able raw int32) windowed to --seq-len;
--synthetic generates deterministic fake tokens.
"""

from common import parse_lm_args  # noqa: E402  (bootstraps sys.path)

import pytorch_distributed_tpu as pdt

pdt.set_env("202607")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from pytorch_distributed_tpu.models.transformer import (  # noqa: E402
    TransformerConfig,
    tiny_config,
)
from pytorch_distributed_tpu.parallel import (  # noqa: E402
    global_batch_size,
    init_process_group,
    make_mesh,
)
from pytorch_distributed_tpu.train import LMTrainer, LMTrainerConfig  # noqa: E402
from pytorch_distributed_tpu.utils.env import compile_cache_dir  # noqa: E402
from pytorch_distributed_tpu.utils.logging import rank0_print  # noqa: E402
from pytorch_distributed_tpu.utils.suspend import SuspendWatcher  # noqa: E402


def build_token_datasets(args):
    if args.synthetic or args.tiny:
        from pytorch_distributed_tpu.data import SyntheticTokens

        vocab = 128 if args.tiny else args.vocab_size
        seq = 32 if args.tiny else args.seq_len
        n = args.synthetic_size or (64 if args.tiny else 4096)
        return (
            SyntheticTokens(n, seq, vocab),
            SyntheticTokens(max(n // 8, 8), seq, vocab, seed=1),
            seq,
            vocab,
        )
    import numpy as np

    from pytorch_distributed_tpu.data import TokenArrayDataset

    if not args.tokens:
        raise SystemExit("--tokens <corpus.npy> required without --synthetic")
    tokens = np.load(args.tokens, mmap_mode="r")
    n_val = max(len(tokens) // 100, args.seq_len)
    return (
        TokenArrayDataset(tokens[:-n_val], args.seq_len),
        TokenArrayDataset(tokens[-n_val:], args.seq_len),
        args.seq_len,
        args.vocab_size,
    )


def build_trainer(args, devices=None) -> LMTrainer:
    """The recipe's mesh, model config and trainer from parsed flags, over
    ``devices`` (default: all of them)."""
    devices = list(devices) if devices is not None else jax.devices()
    train_ds, val_ds, seq_len, vocab = build_token_datasets(args)

    sp = args.seq_parallel
    tp = args.model_parallel
    if args.pipeline_stages < 0:
        raise SystemExit(
            f"--pipeline-stages must be >= 1 (or 0 = off), got "
            f"{args.pipeline_stages}"
        )
    if args.pipeline_stages:
        # PP rides the model axis (stages); the batch shards over data
        # only, so seq-parallel (default 2) is overridden to 1. The TP
        # degree must stay 1 — the model config must NOT get a
        # model_axis; only the MESH carries the stage-sized axis
        # (TP-within-PP needs the train.pp API with a dedicated stage
        # axis).
        if tp > 1:
            raise SystemExit(
                "--pipeline-stages uses the model axis for stages; drop "
                "--model-parallel (TP-within-PP needs the train.pp API "
                "with a dedicated stage axis)"
            )
        if sp > 1:
            rank0_print(
                f"pipeline run: overriding --seq-parallel {sp} -> 1 "
                "(PP batches shard over data only)"
            )
        sp = 1
    mesh_mp = args.pipeline_stages or tp
    n = len(devices)
    if n % (sp * mesh_mp):
        raise SystemExit(
            f"{n} devices not divisible by sp*mp={sp * mesh_mp}"
        )
    mesh = make_mesh(devices, data_parallel=n // (sp * mesh_mp),
                     seq_parallel=sp, model_parallel=mesh_mp)

    # seq-sharded runs need a global (ring) attention; honor an explicit
    # ring variant from --attention, otherwise default to the Pallas-kernel
    # ring (ops/ring_flash.py — ~2.6x the XLA ring end-to-end, BENCH_LM.md)
    if sp > 1:
        attention = (args.attention
                     if args.attention in ("ring", "ring_flash")
                     else "ring_flash")
    else:
        attention = args.attention
    if args.tiny:
        model_cfg = tiny_config(
            # tiny exists for CPU smoke runs, where the Pallas kernels
            # would run in the (slow) interpreter: pin the XLA paths
            attention="ring" if sp > 1 else "dense",
            model_axis="model" if tp > 1 else None,
            tp_size=tp,
            vocab_parallel=args.vocab_parallel,
            dropout=args.dropout,
            ring_layout=args.ring_layout if sp > 1 else "contiguous",
        )
    else:
        model_cfg = TransformerConfig(
            vocab_size=vocab,
            num_layers=args.layers,
            num_heads=args.heads,
            num_kv_heads=args.kv_heads,
            pos_embedding=args.pos_embedding,
            embed_dim=args.embed_dim,
            max_seq_len=seq_len,
            dropout=args.dropout,
            dtype=jnp.bfloat16,
            attention=attention,
            model_axis="model" if tp > 1 else None,
            tp_size=tp,
            vocab_parallel=args.vocab_parallel,
            ring_layout=args.ring_layout if sp > 1 else "contiguous",
        )
    if args.vocab_parallel and args.pipeline_stages:
        raise SystemExit(
            "--vocab-parallel does not compose with --pipeline-stages "
            "(PPEmbed/PPHead are stage-replicated; train/pp.py)"
        )
    if args.vocab_parallel and tp <= 1:
        raise SystemExit("--vocab-parallel needs --model-parallel > 1")
    if args.save_every_n_steps < 0:
        raise SystemExit(
            f"--save-every-n-steps must be >= 0 (0 = off), got "
            f"{args.save_every_n_steps}"
        )
    if args.keep_last_ckpts < 1:
        raise SystemExit(
            f"--keep-last-ckpts must be >= 1, got {args.keep_last_ckpts}"
        )

    cfg = LMTrainerConfig(
        epochs=args.epochs if args.epochs is not None else (2 if args.tiny else 1),
        batch_size=args.batch_size if args.batch_size is not None
        else (2 if args.tiny else 8),
        lr=args.lr,
        warmup_steps=0 if args.tiny else 2000,
        save_dir=args.save_dir,
        log_every=args.log_every,
        num_workers=0 if args.tiny else 4,
        grad_clip_norm=args.grad_clip_norm,
        fsdp=args.fsdp,
        pipeline_stages=args.pipeline_stages,
        pp_microbatches=args.pp_microbatches,
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
    trainer = LMTrainer(model_cfg, train_ds, val_ds, cfg, mesh=mesh,
                        suspend_watcher=SuspendWatcher())
    rank0_print(
        f"devices: {n} ({jax.process_count()} hosts), "
        f"mesh {dict(mesh.shape)}, global batch "
        f"{global_batch_size(mesh, cfg.batch_size)} seqs × {seq_len} tokens, "
        f"attention {model_cfg.attention}, tp {tp}"
    )
    return trainer


def main() -> None:
    args = parse_lm_args(__doc__)
    init_process_group()
    summary = build_trainer(args).fit()
    rank0_print(f"done: best ppl {summary.get('best_ppl', float('inf')):.3f}")


if __name__ == "__main__":
    main()
