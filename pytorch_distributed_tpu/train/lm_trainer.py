"""LMTrainer: the full training loop for language models.

Round-1 built the compiled LM step (``train/lm.py``) but no loop around it
(VERDICT missing #8): no epochs, no eval, no checkpoint/suspend for LMs.
This is the LM counterpart of ``train.Trainer`` — same reference-derived
contracts (epoch loop + ``set_epoch`` reshuffle, seekable mid-epoch step
resume, suspend→checkpoint→yield with the multi-host any-reduce agreement,
latest/best artifacts, JSONL metrics; ``restnet_ddp.py:19-47,127-150``) —
over a (data, seq, model) mesh with TP/EP/SP-sharded or replicated state:

- state placement and gradient reduction follow ``shard_lm_state``'s spec
  tree; checkpoints store the canonical GLOBAL layout via
  ``checkpoint.gather_global`` (all-ranks collective, rank-0 write), so a
  dp×sp×tp checkpoint restores onto any other mesh shape;
- validation reports token perplexity (``make_lm_eval_step``: global
  psum'd loss-sum/token-count, dropout off);
- best.ckpt tracks LOWEST validation perplexity (the LM analog of the
  reference's best-accuracy tracking, ``restnet_ddp.py:145-150``);
- dropout is deterministic under resume: masks derive from
  (seed, state.step, shard coords), never from wall clock.

Batch layout: the loader yields host-local ``{"tokens","labels","weights"}``
[B_local, L]; ``shard_lm_batch`` places them P(data, seq) as global arrays.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from pytorch_distributed_tpu.compilecache.aot import (
    attribute_compile,
    program_load_if,
)
from pytorch_distributed_tpu.ops.optim import build_optimizer
from pytorch_distributed_tpu.ops.schedules import warmup_cosine
from pytorch_distributed_tpu.parallel import mesh as mesh_lib
from pytorch_distributed_tpu.telemetry import spans
from pytorch_distributed_tpu.train.base import SuspendableTrainer
from pytorch_distributed_tpu.train.lm import (
    create_lm_state,
    empty_lm_metrics,
    make_lm_eval_step,
    make_lm_train_step,
    shard_lm_state,
    shift_labels,
)
from pytorch_distributed_tpu.utils.checkpoint import Checkpointer
from pytorch_distributed_tpu.utils.logging import rank0_print
from pytorch_distributed_tpu.utils.profiling import MetricsLogger
from pytorch_distributed_tpu.utils.suspend import NullSuspendWatcher, SuspendWatcher


def lm_collate(samples) -> dict:
    """[L]-token samples → {"tokens", "labels", "weights"} [B, L]."""
    tokens = np.stack(samples).astype(np.int32)
    labels, weights = shift_labels(tokens)
    return {"tokens": tokens, "labels": labels, "weights": weights}


def shard_lm_batch(mesh, batch, data_axis=mesh_lib.DATA_AXIS,
                   seq_axis=mesh_lib.SEQ_AXIS, layout="contiguous"):
    """Host-local [B, L] arrays → global arrays sharded P(data, seq) —
    or P(data) alone on meshes without a seq axis (the PP×TP
    (data, stage, model) convention).

    ``layout="zigzag"``: every per-token array is host-permuted with
    ``parallel.sequence.zigzag_shard`` first, so the contiguous placement
    delivers chunk pair (r, 2s-1-r) to seq-shard r — tokens, labels, and
    weights permute identically and stay aligned; the LM steps feed wpe
    the matching position vector (train/lm.py ``_shard_positions``)."""
    if seq_axis in mesh.shape:
        sharding = NamedSharding(mesh, P(data_axis, seq_axis))
        s = mesh.shape[seq_axis]
    else:
        # PP×TP meshes carry (data, stage, model) — no seq axis; batches
        # shard over data only
        sharding = NamedSharding(mesh, P(data_axis))
        s = 1
    if layout == "zigzag" and s > 1:
        from pytorch_distributed_tpu.parallel.sequence import zigzag_shard

        batch = jax.tree.map(
            lambda x: zigzag_shard(np.asarray(x), s, axis=1), batch
        )
    return jax.tree.map(
        lambda x: jax.make_array_from_process_local_data(
            sharding, np.asarray(x)
        ),
        batch,
    )


@dataclasses.dataclass
class LMTrainerConfig:
    epochs: int = 1
    batch_size: int = 8  # sequences per data-replica step
    lr: float = 3e-4
    weight_decay: float = 0.1
    warmup_steps: int = 0
    min_lr_ratio: float = 0.1
    optimizer: str = "adamw"
    save_dir: str = "output_lm"
    log_every: int = 100
    num_workers: int = 0
    prefetch: int = 2
    seed: int = 0
    suspend_sync_every: int = 1  # see TrainerConfig.suspend_sync_every
    # Global-norm gradient clipping (0 = off). Correct under ANY sharding:
    # the norm psums each leaf's square-sum over the axes its spec shards
    # (ops.optim.sharded_global_norm) — the loss-spike control the
    # reference's SGD ResNet never needed but an LM does.
    grad_clip_norm: float = 0.0
    # FSDP/ZeRO for the LM: leaves the TP/EP rules leave replicated shard
    # over the data axis at rest; the step all_gathers them before the
    # forward and reduce-scatters their grads (train/lm.py round 4 —
    # composes with TP, EP, SP, clipping, and the sharded checkpointer).
    fsdp: bool = False
    # Pipeline parallelism: > 0 trains through the GPipe executor
    # (train/pp.py). Stages ride the mesh's model axis on the standard
    # (data, seq, model) mesh, or a dedicated "stage" axis on a
    # (data, stage, model) mesh — the latter composes TP-within-PP
    # (model_axis/tp_size set, Megatron collectives inside each stage).
    # The batch shards over data only (seq axis must be 1); FSDP is
    # rejected. pp_microbatches follows BENCH_PP.md's measured default.
    pipeline_stages: int = 0
    pp_microbatches: int = 8
    # Step-interval durability (0 = off; see TrainerConfig) — non-blocking
    # sharded step-<global_step>.ckpt saves with keep-last-K retention.
    save_every_n_steps: int = 0
    keep_last_ckpts: int = 3
    # Resilience guards — see TrainerConfig: compiled finite gate
    # (skip-on-NaN, no host sync), rollback after max_bad_steps
    # consecutive bad steps, per-step deadline watchdog. nan_guard does
    # not compose with pipeline_stages (the GPipe executor owns its own
    # update path).
    nan_guard: bool = False
    max_bad_steps: int = 0
    watchdog_timeout_s: float = 0.0
    # Telemetry — see TrainerConfig: metrics_out overrides the JSONL
    # path (rank-0 gated in MetricsLogger); flush_every sizes the
    # on-device metrics ring (sync-free log path, drained lagged one
    # transfer per window; 0 = legacy blocking float() per log
    # interval); trace_dir is where the span stream is written.
    metrics_out: Optional[str] = None
    trace_dir: Optional[str] = None
    flush_every: int = 32
    # Compile cache (compilecache/, ANALYSIS.md "Cold start & compile
    # cache"): compile_cache_dir points jax's persistent compilation
    # cache at a directory (an exported JAX_COMPILATION_CACHE_DIR wins —
    # utils.env.compile_cache_dir) so a relaunched or preemption-resumed
    # run loads its step executables from disk; warmup AOT-compiles the
    # program registry (train + eval step) before the first step, with
    # the wall time attributed to the goodput ledger's compile category
    # and kind="warmup" manifest records in the metrics JSONL.
    compile_cache_dir: Optional[str] = None
    warmup: bool = False
    # Elastic resume — see TrainerConfig: a run killed on mesh (4,2)
    # resumes on (2,2) or (8,1) (TP/FSDP state re-partitioned from the
    # rule tables, optimizer moments included); False = same-topology
    # restores only.
    elastic_resume: bool = True
    # Attribution & forensics — see TrainerConfig: anomaly sentinel over
    # step-time/data-wait (robust z, 0 = off), flight-recorder ring +
    # mirror + trigger dumps, fit-end per-program cost cards, live
    # Prometheus /metrics port.
    anomaly_threshold: float = 8.0
    anomaly_window: int = 64
    flightrec: bool = True
    cost_cards: bool = False
    metrics_port: Optional[int] = None


class LMTrainer(SuspendableTrainer):
    """Drives (TransformerConfig, token datasets) over a mesh."""

    def __init__(
        self,
        model_config,
        train_dataset,
        val_dataset,
        config: LMTrainerConfig,
        mesh: Optional[jax.sharding.Mesh] = None,
        suspend_watcher: Optional[SuspendWatcher] = None,
    ):
        with spans.tracer().span("trainer.build", trainer="lm"):
            self._build(model_config, train_dataset, val_dataset, config,
                        mesh, suspend_watcher)

    def _build(self, model_config, train_dataset, val_dataset, config, mesh,
               suspend_watcher) -> None:
        from pytorch_distributed_tpu.data import DataLoader, DistributedSampler

        self.config = config
        self.model_config = model_config
        self._init_compilecache()  # before any compile: init programs too
        self.mesh = mesh if mesh is not None else mesh_lib.make_mesh()
        self.watcher = suspend_watcher or NullSuspendWatcher()
        self.ckpt = Checkpointer(config.save_dir)

        n_local = mesh_lib.local_replica_count(self.mesh)
        local_batch = config.batch_size * n_local
        with spans.tracer().span("loader.build"):
            self.train_sampler = DistributedSampler(
                len(train_dataset), num_replicas=jax.process_count(),
                rank=jax.process_index(), shuffle=True, seed=config.seed,
            )
            self.val_sampler = DistributedSampler(
                len(val_dataset), num_replicas=jax.process_count(),
                rank=jax.process_index(), shuffle=False, seed=config.seed,
            )
            self.train_loader = DataLoader(
                train_dataset, batch_size=local_batch, sampler=self.train_sampler,
                num_workers=config.num_workers, drop_last=True,
                prefetch=config.prefetch, seed=config.seed, collate_fn=lm_collate,
            )
            self.val_loader = DataLoader(
                val_dataset, batch_size=local_batch, sampler=self.val_sampler,
                num_workers=config.num_workers, drop_last=False,
                prefetch=config.prefetch, seed=config.seed, collate_fn=lm_collate,
            )
        self._local_batch = local_batch

        steps_per_epoch = len(self.train_loader)
        schedule = warmup_cosine(
            config.lr,
            total_steps=max(steps_per_epoch * config.epochs, 1),
            warmup_steps=config.warmup_steps,
            final_lr=config.lr * config.min_lr_ratio,
        )
        tx = build_optimizer(
            config.optimizer, schedule, weight_decay=config.weight_decay
        )
        if config.pipeline_stages > 0 and config.nan_guard:
            raise ValueError(
                "nan_guard does not compose with pipeline_stages: the "
                "GPipe executor owns its own update path (train/pp.py)"
            )
        if config.pipeline_stages > 0:
            from pytorch_distributed_tpu.train.pp import (
                create_pp_lm_state,
                make_pp_lm_eval_step,
                make_pp_lm_train_step,
                shard_pp_state,
            )

            s = config.pipeline_stages
            # Two mesh conventions:
            # - plain PP: the standard (data, seq, model) mesh with the
            #   MODEL axis carrying the stages (model_config.model_axis
            #   must be None);
            # - TP-within-PP: a (data, stage, model) mesh — a dedicated
            #   "stage" axis for the pipeline ring, the model axis for
            #   the Megatron collectives (model_config.model_axis set).
            if "stage" in self.mesh.shape:
                stage_axis = "stage"
                if model_config.model_axis is not None and (
                    self.mesh.shape.get(model_config.model_axis, 1)
                    != model_config.tp_size
                ):
                    raise ValueError(
                        f"mesh {model_config.model_axis!r} size "
                        f"{self.mesh.shape.get(model_config.model_axis)} "
                        f"!= tp_size {model_config.tp_size}"
                    )
                if (model_config.model_axis is None
                        and self.mesh.shape.get(mesh_lib.MODEL_AXIS, 1) > 1):
                    raise ValueError(
                        "the mesh carries a model axis of size "
                        f"{self.mesh.shape[mesh_lib.MODEL_AXIS]} but the model config "
                        "has no model_axis — every chip on it would do "
                        "duplicate work; set model_axis/tp_size or size "
                        "the axis to 1"
                    )
            else:
                stage_axis = mesh_lib.MODEL_AXIS
                if model_config.model_axis is not None:
                    raise ValueError(
                        "TP-within-PP needs a dedicated stage axis — "
                        "build the mesh with axis_names=('data', 'stage', "
                        "'model') (stage size = pipeline_stages, model "
                        "size = tp_size); on the standard mesh the "
                        "trainer runs stages on the model axis"
                    )
            if self.mesh.shape.get(stage_axis, 1) != s:
                raise ValueError(
                    f"pipeline_stages={s} needs the mesh's {stage_axis!r} "
                    f"axis to carry the stages "
                    f"(got {self.mesh.shape.get(stage_axis)}); build the "
                    "mesh with that axis sized to pipeline_stages"
                )
            if self.mesh.shape.get(mesh_lib.SEQ_AXIS, 1) > 1:
                raise ValueError(
                    "the PP trainer shards batches over data only; use "
                    "seq_parallel=1 (ring attention cannot run inside a "
                    "pipeline stage)"
                )
            if config.fsdp:
                raise ValueError(
                    "fsdp does not compose with pipeline_stages in the "
                    "trainer (stage stacks already shard the model axis)"
                )
            with spans.tracer().span("state.init"):
                state = create_pp_lm_state(
                    model_config, s, tx, jax.random.key(config.seed)
                )
                self.state, self.state_specs = shard_pp_state(
                    self.mesh, state, axis=stage_axis, config=model_config
                )
            # microbatches divide the PER-DATA-SHARD batch, which is
            # config.batch_size by definition; clamp for small runs
            if config.pp_microbatches < 1:
                raise ValueError(
                    f"pp_microbatches must be >= 1, got "
                    f"{config.pp_microbatches}"
                )
            mb = min(config.pp_microbatches, config.batch_size)
            while config.batch_size % mb:
                mb -= 1
            if mb != config.pp_microbatches:
                rank0_print(
                    f"pp_microbatches {config.pp_microbatches} -> {mb} "
                    f"(must divide the per-shard batch {config.batch_size})"
                )
            self.train_step = make_pp_lm_train_step(
                self.mesh, model_config, self.state_specs,
                n_microbatches=mb,
                axis=stage_axis,
                dropout_seed=config.seed,
                grad_clip_norm=config.grad_clip_norm,
            )
            self.eval_step = make_pp_lm_eval_step(
                self.mesh, model_config, self.state_specs,
                n_microbatches=mb,
                axis=stage_axis,
            )
        else:
            with spans.tracer().span("state.init"):
                state = create_lm_state(
                    model_config, tx, jax.random.key(config.seed)
                )
                self.state, self.state_specs = shard_lm_state(
                    self.mesh, state, model_config, fsdp=config.fsdp
                )
            self.train_step = make_lm_train_step(
                self.mesh, state_specs=self.state_specs, config=model_config,
                dropout_seed=config.seed,
                grad_clip_norm=config.grad_clip_norm,
                fsdp=config.fsdp,
                nan_guard=config.nan_guard,
            )
            self.eval_step = make_lm_eval_step(
                self.mesh, state_specs=self.state_specs, config=model_config,
                fsdp=config.fsdp,
            )
        # pre-fault the checkpoint snapshot arena while the first step
        # compiles — the first non-blocking best-save then stalls only for
        # its memcpy (see utils.checkpoint._Arena)
        self.ckpt.warm_for({"state": self.state})

        self.best_ppl = float("inf")
        self.start_epoch = 0
        self.start_step = 0
        self._init_resilience()  # stepguard + watchdog + telemetry
        # rank-0 gating lives inside MetricsLogger now
        self.metrics_log = MetricsLogger(
            config.metrics_out
            or os.path.join(config.save_dir, "metrics.jsonl")
        )
        self._bind_observability()  # sentinel JSONL + live exporter

    # ---- program registry (compilecache/): the programs this trainer
    # compiles, with the batch avals the loader will actually produce ----

    def _registry_entries(self):
        sample = self.train_loader.collate_fn([self.train_loader.dataset[0]])
        gb = self._local_batch * jax.process_count()
        if mesh_lib.SEQ_AXIS in self.mesh.shape:
            spec = P(mesh_lib.DATA_AXIS, mesh_lib.SEQ_AXIS)
        else:  # PP (data, stage, model) meshes shard over data only
            spec = P(mesh_lib.DATA_AXIS)
        sharding = NamedSharding(self.mesh, spec)

        def batch_aval():
            return {
                k: jax.ShapeDtypeStruct(
                    (gb,) + np.asarray(v).shape[1:], np.asarray(v).dtype,
                    sharding=sharding,
                )
                for k, v in sample.items()
            }

        def train_avals():
            return [(self.state, batch_aval())]

        def eval_avals():
            # validate() zero-pads partial batches back to the full local
            # batch, so the eval step holds exactly ONE shape
            acc = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype,
                    sharding=mesh_lib.replicated_sharding(self.mesh),
                ),
                empty_lm_metrics(),
            )
            return [(self.state, batch_aval(), acc)]

        # train budget 2: steady-state entry + the donation/layout retrace
        # the first dispatch settles through — the same pair no_recompile's
        # warmup_steps=2 window forgives (analysis/guards.py)
        return [
            ("lm_train_step", self.train_step, train_avals, 2),
            ("lm_eval_step", self.eval_step, eval_avals, 1),
        ]

    # ---- checkpoint contract: shared machinery in train/base.py ----

    def _extra_payload(self) -> dict:
        return {"best_ppl": self.best_ppl}

    def _restore_extra(self, restored: dict) -> None:
        self.best_ppl = float(restored["best_ppl"])

    # ---- loops ----

    def _emit_train_record(self, rec: dict) -> None:
        """Print + JSONL one train log event — same arithmetic as the
        legacy blocking path, so the two series are bit-identical."""
        vals = {k: v for k, v in rec.items() if k not in ("epoch", "step")}
        rank0_print(
            f"epoch {rec['epoch']} step {rec['step']}: "
            f"loss {rec['loss']:.4f}"
        )
        self.metrics_log.log(
            kind="train", epoch=rec["epoch"], step=rec["step"], **vals
        )

    def _drain_train_records(self, records) -> dict:
        last: dict = {}
        for rec in records:
            self._emit_train_record(rec)
            last = {
                k: v for k, v in rec.items() if k not in ("epoch", "step")
            }
        return last

    def train_epoch(self, epoch: int, start_step: int = 0) -> dict:
        cfg = self.config
        last: dict = {}
        t0 = time.perf_counter()
        steps_done = 0
        it = enumerate(
            self.train_loader.iter_batches(start_step), start=start_step
        )
        while True:
            t_wait = time.perf_counter()
            with self.goodput.timed("data_wait"), \
                    spans.tracer().span("train.data_wait"):
                pair = next(it, None)
            self._observe_data_wait(time.perf_counter() - t_wait)
            if pair is None:
                break
            step, host_batch = pair
            host_batch = self._pre_step(host_batch)
            batch = shard_lm_batch(
                self.mesh, host_batch,
                layout=self.model_config.ring_layout,
            )
            # the run's first dispatch traces + compiles the step: split
            # its wall into compile (XLA backend / cache load) and trace
            # (Python lowering) so a warm start's goodput shows the cache
            # win; later recompiles are a guarded hazard, not steady state
            first = self._dispatched == 0
            with spans.tracer().step("train.step_dispatch", step), \
                    program_load_if(first, "lm_train_step"), \
                    attribute_compile(self.goodput if first else None):
                self.state, metrics = self.train_step(self.state, batch)
            self._dispatched += 1
            self._post_step(metrics)
            steps_done += 1
            if cfg.log_every and step % cfg.log_every == 0:
                if cfg.flush_every > 0:
                    # sync-free: push the replicated scalars into the
                    # device ring; records drain lagged, one transfer
                    # per flush_every log events
                    last = self._drain_train_records(
                        self._telemetry_append(
                            metrics, epoch=epoch, step=step
                        )
                    ) or last
                else:
                    # legacy blocking path (flush_every=0): float()
                    # syncs the dispatch pipeline at every log interval
                    last = {k: float(v) for k, v in metrics.items()}
                    self._emit_train_record(
                        dict(last, epoch=epoch, step=step)
                    )
            self._maybe_save_step(epoch, step)
            self._maybe_suspend(epoch, step)
        self._epoch_end_guard()  # drain the guard's lag window
        last = self._drain_train_records(self._telemetry_flush()) or last
        tokens_per_step = last.get("tokens")
        if steps_done:
            float(self.state.step)  # drain async dispatch before the clock
            elapsed = time.perf_counter() - t0
            # cost-card join: epoch wall attributed to the step program
            self.prog_times.observe_total(
                "lm_train_step", elapsed, steps_done
            )
            record = {
                "kind": "epoch_timing", "epoch": epoch, "steps": steps_done,
                "mean_ms": 1e3 * elapsed / steps_done,
            }
            if tokens_per_step:
                record["tokens_per_s"] = tokens_per_step * steps_done / elapsed
            self.metrics_log.log(**record)
        return last

    def validate(self) -> dict:
        acc = jax.device_put(
            empty_lm_metrics(), mesh_lib.replicated_sharding(self.mesh)
        )
        wrap_pad = self.val_sampler.local_padding_mask()
        for b, host_batch in enumerate(self.val_loader.iter_batches(0)):
            n = host_batch["tokens"].shape[0]
            # Zero the weight of wrap-padded duplicates (uneven
            # process splits repeat indices, torch-style) so the psum'd
            # loss_sum/tokens count each real sequence exactly once —
            # unbiased perplexity, unlike torch's duplicate counting.
            rows = wrap_pad[b * self._local_batch : b * self._local_batch + n]
            if rows.any():
                host_batch = dict(host_batch)
                host_batch["weights"] = (
                    host_batch["weights"] * ~rows[:, None]
                ).astype(np.float32)
            pad = self._local_batch - n
            if pad:
                # zero-weight padding rows keep the compiled batch shape
                # (one program, no recompiles) and contribute no loss/tokens
                host_batch = {
                    k: np.concatenate(
                        [v, np.zeros((pad,) + v.shape[1:], v.dtype)]
                    )
                    for k, v in host_batch.items()
                }
            with program_load_if(self._evaluated == 0, "lm_eval_step"):
                acc = self.eval_step(
                    self.state,
                    shard_lm_batch(self.mesh, host_batch,
                                   layout=self.model_config.ring_layout),
                    acc
                )
            self._evaluated += 1
        acc = jax.device_get(acc)
        tokens = float(acc["tokens"])
        if tokens == 0.0:
            raise ValueError(
                "validation saw zero tokens — the val dataset is smaller "
                "than one global batch on every host; shrink batch_size or "
                "grow the val split"
            )
        mean = float(acc["loss_sum"]) / tokens
        return {"loss": mean, "ppl": float(np.exp(min(mean, 30.0))),
                "tokens": tokens}

    def fit(self) -> dict:
        """Re-entrant epoch loop — see ``Trainer.fit``: RollbackRequested
        from the step guard restores the last good checkpoint and resumes
        from its epoch/step, identically on every rank."""
        from pytorch_distributed_tpu.resilience.stepguard import (
            RollbackRequested,
        )

        self.goodput.start()
        self.try_resume()
        self._run_warmup()  # AOT-compile the registry before step 1
        summary: dict = {}
        epoch = self.start_epoch
        while epoch < self.config.epochs:
            t0 = time.time()
            self.train_sampler.set_epoch(epoch)
            start_step = self.start_step if epoch == self.start_epoch else 0
            try:
                self.train_epoch(epoch, start_step)
            except RollbackRequested as err:
                self._rollback(err)  # restores state + start_epoch/step
                epoch = self.start_epoch
                continue
            # commit last epoch's pending best-save: its file write
            # overlapped this epoch's training; all ranks reach this point
            # together, so the commit barrier is safely ordered
            with self.goodput.timed("checkpoint"), \
                    spans.tracer().span("ckpt.save", commit=True):
                self.ckpt.wait()
            summary = self.validate()
            rank0_print(
                f"epoch {epoch}: val loss {summary['loss']:.4f} "
                f"ppl {summary['ppl']:.3f}"
            )
            if summary["ppl"] < self.best_ppl:
                self.best_ppl = summary["ppl"]
                # sharded, non-blocking: only the device→host snapshot runs
                # here; the file write rides a thread and the commit
                # (barrier + manifest) lands at the next wait() — a point
                # every rank reaches in the same order because the psum'd
                # ppl gives all ranks the same improvement decision
                with self.goodput.timed("checkpoint"), \
                        spans.tracer().span("ckpt.save", best=True):
                    self.ckpt.save_best_sharded(
                        self._payload_live(epoch + 1, 0), block=False
                    )
                rank0_print(f"new best ppl {self.best_ppl:.3f}, saved best.ckpt")
            self.metrics_log.log(kind="val", epoch=epoch,
                                 epoch_s=time.time() - t0, **summary)
            epoch += 1
        with self.goodput.timed("checkpoint"):
            self.ckpt.wait()  # commit any pending best-save before return
        if self.watchdog is not None:
            self.watchdog.stop()
        self._log_cost_cards()  # per-program MFU/roofline attribution
        self._log_goodput()
        self._save_traces()
        if self.exporter is not None:
            self.exporter.stop()
        self.start_step = 0
        summary["best_ppl"] = self.best_ppl
        return summary
