"""The trainer: one SPMD loop serving all four reference recipes.

The reference implements the same epoch loop four times (SURVEY.md §2a, R1-R4)
— the scripts differ only in how replicas communicate. Here the loop exists
once and the communication mode is the ``Mesh`` passed in:

    1-device mesh          ≙ resnet_single_gpu.py
    local 8-chip mesh      ≙ resnet_dp.py        (without D5's scatter cost)
    multi-host mesh        ≙ restnet_ddp.py      (rendezvous via parallel.init_process_group)
    + precision="bf16"     ≙ resnet_ddp_apex.py  (no scaler needed on TPU)

Reproduced behaviors (each is a cited shared behavior from SURVEY.md §2a):
epoch loop with ``set_epoch`` reshuffle (``restnet_ddp.py:135-137``),
mid-epoch step resume — seekable, not read-and-discard
(``restnet_ddp.py:22-23`` improved per §3.5), suspend poll → checkpoint →
yield (``restnet_ddp.py:36-47``), resume-load restoring
model/optimizer/scheduler/best_acc/epoch/step (``restnet_ddp.py:127-132``),
per-epoch validation with cross-replica reduction (``restnet_ddp.py:50-70``),
best-checkpoint tracking (``restnet_ddp.py:145-150``), epoch timing log
(``restnet_ddp.py:136-146``), step-progress log every 100 steps
(``resnet_single_gpu.py:23-24``).
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Optional

import jax
import numpy as np

from pytorch_distributed_tpu.compilecache.aot import (
    attribute_compile,
    program_load_if,
)
from pytorch_distributed_tpu.ops.metrics import ClassificationMetrics
from pytorch_distributed_tpu.ops.optim import sgd_with_weight_decay
from pytorch_distributed_tpu.ops.precision import DynamicLossScaler, NoOpLossScaler
from pytorch_distributed_tpu.ops.schedules import step_lr
from pytorch_distributed_tpu.parallel import mesh as mesh_lib
from pytorch_distributed_tpu.telemetry import spans
from pytorch_distributed_tpu.train.base import SuspendableTrainer
from pytorch_distributed_tpu.train.state import TrainState
from pytorch_distributed_tpu.train.step import make_eval_step, make_train_step
from pytorch_distributed_tpu.utils.checkpoint import Checkpointer
from pytorch_distributed_tpu.utils.logging import rank0_print
from pytorch_distributed_tpu.utils.profiling import MetricsLogger, trace
from pytorch_distributed_tpu.utils.suspend import NullSuspendWatcher, SuspendWatcher


@dataclasses.dataclass
class TrainerConfig:
    """Hyperparameters, defaulted to the reference's hardcoded values
    (``restnet_ddp.py:77-83``, ``resnet_single_gpu.py:107-109``)."""

    epochs: int = 100
    batch_size: int = 400  # per data-replica, like DDP's per-process bs
    lr: float = 0.1
    momentum: float = 0.9
    weight_decay: float = 1e-4
    lr_step_epochs: int = 30
    lr_gamma: float = 0.1
    precision: str = "fp32"  # fp32 | bf16 | fp16 (fp16 adds a dynamic scaler)
    label_smoothing: float = 0.0
    save_dir: str = "output"
    log_every: int = 100  # ref resnet_single_gpu.py:23
    num_workers: int = 8
    prefetch: int = 2
    seed: int = 0
    # multi-host suspend agreement: how often (steps) hosts agree on a
    # suspend landing on ANY of them. 1 (default) = every step — a SIGTERM
    # delivered to one host makes all hosts checkpoint and yield together
    # (one tiny host-level collective per step, only when process_count>1;
    # without it the survivors deadlock at their next collective).
    # 0 = primary-only polling, the reference's exact (unsafe) semantics.
    suspend_sync_every: int = 1
    # FSDP/ZeRO-3: shard params+optimizer over the data axis (~axis-size
    # less state memory; identical training math — parallel/fsdp.py).
    fsdp: bool = False
    # Global-norm gradient clipping (0 = off); sharding-correct under FSDP
    # (ops.optim.sharded_global_norm), applied after scaler unscale.
    grad_clip_norm: float = 0.0
    # Step-interval durability (0 = off, the reference's policy: saves only
    # on suspend and on val improvement). Every N steps a NON-BLOCKING
    # sharded save lands in step-<global_step>.ckpt; retention keeps the
    # newest keep_last_ckpts completed ones, and resume picks the newest
    # restorable checkpoint (train/base.py, utils/checkpoint.py round 5).
    save_every_n_steps: int = 0
    keep_last_ckpts: int = 3
    # Resilience guards (resilience/, ANALYSIS.md "Failure model"):
    # nan_guard compiles a finite gate into the train step — a non-finite
    # loss/grad step keeps the pre-step params on device (lax.cond, no
    # host sync) and reports step_good; after max_bad_steps consecutive
    # bad steps (0 = never) the trainer rolls back to the last good
    # checkpoint. watchdog_timeout_s > 0 arms a per-step deadline thread
    # that dumps all-thread stacks on stall and latches the suspend path.
    nan_guard: bool = False
    max_bad_steps: int = 0
    watchdog_timeout_s: float = 0.0
    # Telemetry (telemetry/, ANALYSIS.md "Observability & goodput"):
    # metrics_out overrides the JSONL stream path (default
    # <save_dir>/metrics.jsonl; rank-0 gating lives inside MetricsLogger);
    # flush_every sizes the on-device metrics ring — log-interval metric
    # scalars are pushed by a donated compiled program and drained with
    # ONE lagged host transfer per window, so logging never stalls the
    # dispatch pipeline (0 = the legacy blocking float() sync, kept for
    # bit-identity A/B); trace_dir says where the process's span
    # stream (telemetry.spans; always recorded) is written at fit end
    # (spans.trace.json — train.data_wait/train.step_dispatch/ckpt.*).
    metrics_out: Optional[str] = None
    trace_dir: Optional[str] = None
    flush_every: int = 32
    # Compile cache (compilecache/, ANALYSIS.md "Cold start & compile
    # cache"): compile_cache_dir points jax's persistent compilation
    # cache at a directory (an exported JAX_COMPILATION_CACHE_DIR wins —
    # utils.env.compile_cache_dir);
    # warmup AOT-compiles the train/eval program registry before the
    # first step (ledger compile attribution + kind="warmup" manifest).
    compile_cache_dir: Optional[str] = None
    warmup: bool = False
    # Elastic resume (reshard/, ANALYSIS.md "Elastic topology & reshard"):
    # restore checkpoints written on a DIFFERENT mesh shape by resolving
    # target shardings from this run's spec tree and assembling each
    # device's slices from the manifest block table — preemption can hand
    # back any topology. False refuses topology-mismatched candidates
    # (they fall through to older same-topology checkpoints).
    elastic_resume: bool = True
    # Attribution & forensics (telemetry/, ANALYSIS.md "Performance
    # attribution & forensics"): anomaly_threshold is the sentinel's
    # robust z-score bound over the step-time/data-wait series (0 = off;
    # MAD-based, immune to the first-step compile); flightrec keeps a
    # bounded ring of recent events mirrored to <save_dir>/flightrec.jsonl
    # and dumped atomically on stall/rollback/suspend/exception;
    # cost_cards emits kind="program_cost" records at fit end (one extra
    # AOT compile per program — a cache hit when compile_cache_dir is
    # set); metrics_port serves live Prometheus-text /metrics.
    anomaly_threshold: float = 8.0
    anomaly_window: int = 64
    flightrec: bool = True
    cost_cards: bool = False
    metrics_port: Optional[int] = None


class Trainer(SuspendableTrainer):
    """Drives (model, datasets) over a mesh with the config's recipe."""

    def __init__(
        self,
        model,
        train_dataset,
        val_dataset,
        config: TrainerConfig,
        mesh: Optional[jax.sharding.Mesh] = None,
        suspend_watcher: Optional[SuspendWatcher] = None,
        input_shape=(1, 224, 224, 3),
    ):
        with spans.tracer().span("trainer.build", trainer="image"):
            self._build(model, train_dataset, val_dataset, config, mesh,
                        suspend_watcher, input_shape)

    def _build(self, model, train_dataset, val_dataset, config, mesh,
               suspend_watcher, input_shape) -> None:
        from pytorch_distributed_tpu.data import DataLoader, DistributedSampler

        self.config = config
        self.model = model
        self._init_compilecache()  # before any compile: init programs too
        self.mesh = mesh if mesh is not None else mesh_lib.make_mesh()
        self.watcher = suspend_watcher or NullSuspendWatcher()
        self.ckpt = Checkpointer(config.save_dir)

        # Each process loads the shard its local chips will consume: sampler
        # splits by host (D10 semantics), loader batches local_replicas × bs.
        n_local = mesh_lib.local_replica_count(self.mesh)
        local_batch = config.batch_size * n_local
        with spans.tracer().span("loader.build"):
            self.train_sampler = DistributedSampler(
                len(train_dataset),
                num_replicas=jax.process_count(),
                rank=jax.process_index(),
                shuffle=True,
                seed=config.seed,
            )
            self.val_sampler = DistributedSampler(
                len(val_dataset),
                num_replicas=jax.process_count(),
                rank=jax.process_index(),
                shuffle=False,
                seed=config.seed,
            )
            self.train_loader = DataLoader(
                train_dataset,
                batch_size=local_batch,
                sampler=self.train_sampler,
                num_workers=config.num_workers,
                drop_last=True,
                prefetch=config.prefetch,
                seed=config.seed,
            )
            self.val_loader = DataLoader(
                val_dataset,
                batch_size=local_batch,
                sampler=self.val_sampler,
                num_workers=config.num_workers,
                drop_last=False,
                prefetch=config.prefetch,
                seed=config.seed,
            )

        steps_per_epoch = len(self.train_loader)
        schedule = step_lr(
            config.lr,
            steps_per_epoch,
            step_size_epochs=config.lr_step_epochs,
            gamma=config.lr_gamma,
        )
        tx = sgd_with_weight_decay(
            schedule, momentum=config.momentum, weight_decay=config.weight_decay
        )
        scaler = (
            DynamicLossScaler.create()
            if config.precision == "fp16"
            else NoOpLossScaler.create()
        )
        with spans.tracer().span("state.init"):
            state = TrainState.create(
                model, tx, jax.random.key(config.seed), input_shape, scaler=scaler
            )
            if config.fsdp:
                from pytorch_distributed_tpu.parallel.fsdp import shard_fsdp_state

                self.state, self.state_specs = shard_fsdp_state(self.mesh, state)
            else:
                # Replicated placement ≙ DDP's broadcast-from-rank-0
                # (restnet_ddp.py:99).
                self.state = jax.device_put(
                    state, mesh_lib.replicated_sharding(self.mesh)
                )
                self.state_specs = None

        self.train_step = make_train_step(
            self.mesh,
            label_smoothing=config.label_smoothing,
            state_specs=self.state_specs,
            grad_clip_norm=config.grad_clip_norm,
            nan_guard=config.nan_guard,
        )
        self.eval_step = make_eval_step(self.mesh, state_specs=self.state_specs)
        # pre-fault the checkpoint snapshot arena while the first step
        # compiles — the first non-blocking best-save then stalls only for
        # its memcpy (see utils.checkpoint._Arena)
        self.ckpt.warm_for({"state": self.state})

        self.best_acc = 0.0
        self.start_epoch = 0
        self.start_step = 0
        self._init_resilience()  # stepguard + watchdog + telemetry

        # Observability (SURVEY.md §5: the reference has only time.time()
        # prints; we keep those AND stream machine-readable metrics).
        # Rank-0 gating lives inside MetricsLogger now.
        self.metrics_log = MetricsLogger(
            config.metrics_out
            or os.path.join(config.save_dir, "metrics.jsonl")
        )
        self._bind_observability()  # sentinel JSONL + live exporter

    # ---- program registry (compilecache/): the programs this trainer
    # compiles, with the batch avals the loaders will actually produce ----

    def _registry_entries(self):
        from jax.sharding import PartitionSpec as P  # noqa: F401

        sample = self.train_loader.collate_fn([self.train_loader.dataset[0]])
        pc = jax.process_count()
        local_batch = self.train_loader.batch_size
        gb = local_batch * pc
        sharding = mesh_lib.batch_sharding(self.mesh)

        def aval_for(b):
            return {
                k: jax.ShapeDtypeStruct(
                    (b,) + np.asarray(v).shape[1:], np.asarray(v).dtype,
                    sharding=sharding,
                )
                for k, v in sample.items()
            }

        def train_avals():
            return [(self.state, aval_for(gb))]

        def eval_batch_sizes():
            # validate() pads a partial FINAL batch only up to replica
            # divisibility (duplicate-counting val semantics), so the
            # eval step holds one program per distinct global batch size:
            # the full batch, plus the padded remainder when the local
            # sample count doesn't divide evenly.
            n_local_samples = self.val_sampler.num_samples
            n_replicas = mesh_lib.local_replica_count(self.mesh)
            sizes = []
            if n_local_samples >= local_batch:
                sizes.append(gb)
            rem = n_local_samples % local_batch
            if rem:
                rem += (-rem) % n_replicas
                if rem * pc not in sizes:
                    sizes.append(rem * pc)
            return sizes

        def eval_avals():
            metrics = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype,
                    sharding=mesh_lib.replicated_sharding(self.mesh),
                ),
                ClassificationMetrics.empty(),
            )
            return [(self.state, aval_for(b), metrics)
                    for b in eval_batch_sizes()]

        # train budget 2: steady-state entry + the donation/layout retrace
        # the first dispatch settles through — the same pair no_recompile's
        # warmup_steps=2 window forgives (analysis/guards.py)
        return [
            ("train_step", self.train_step, train_avals, 2),
            ("eval_step", self.eval_step, eval_avals,
             max(len(eval_batch_sizes()), 1)),
        ]

    # ---- checkpoint contract (SURVEY.md §3.5): shared machinery in
    # train/base.py (payload gather, resume placement, suspend agreement);
    # the payload reads the trainer's LIVE best_acc, fixing the reference's
    # stale-best_acc bug (SURVEY.md §2a defects). ----

    def _extra_payload(self) -> dict:
        return {"best_acc": self.best_acc}

    def _restore_extra(self, restored: dict) -> None:
        self.best_acc = float(restored["best_acc"])

    # ---- the loops ----

    def _emit_train_record(self, rec: dict) -> None:
        """Print + JSONL one train log event (``rec`` carries the metric
        floats plus epoch/step). Same arithmetic as the legacy blocking
        path, so the two paths' series are bit-identical."""
        acc1 = 100.0 * rec["correct1"] / max(rec["count"], 1)
        rank0_print(
            f"epoch {rec['epoch']} step {rec['step']}: "
            f"loss {rec['loss']:.4f} acc1 {acc1:.2f}"
        )
        self.metrics_log.log(
            kind="train", epoch=rec["epoch"], step=rec["step"],
            loss=rec["loss"], acc1=acc1,
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
        """One training epoch (ref ``train``, ``restnet_ddp.py:19-47``)."""
        cfg = self.config
        last = {}
        global_bs = mesh_lib.global_batch_size(self.mesh, cfg.batch_size)
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
            batch = mesh_lib.shard_batch(self.mesh, host_batch)
            # the run's first dispatch traces + compiles the step: split
            # its wall into compile (XLA backend / cache load) and trace
            # (Python lowering) so a warm start's goodput shows the cache
            # win; later recompiles are a guarded hazard, not steady state
            first = self._dispatched == 0
            with spans.tracer().step("train.step_dispatch", step), \
                    program_load_if(first, "train_step"), \
                    attribute_compile(self.goodput if first else None):
                self.state, metrics = self.train_step(self.state, batch)
            self._dispatched += 1
            self._post_step(metrics)
            steps_done += 1
            if cfg.log_every and step % cfg.log_every == 0:
                if cfg.flush_every > 0:
                    # sync-free: push the device scalars into the ring;
                    # records surface lagged, one transfer per window
                    last = self._drain_train_records(
                        self._telemetry_append(
                            metrics, epoch=epoch, step=step
                        )
                    ) or last
                else:
                    # legacy blocking path (flush_every=0): float() syncs
                    # the dispatch pipeline at every log interval
                    last = {k: float(v) for k, v in metrics.items()}
                    self._emit_train_record(
                        dict(last, epoch=epoch, step=step)
                    )
            self._maybe_save_step(epoch, step)
            self._maybe_suspend(epoch, step)
        self._epoch_end_guard()  # drain the guard's lag window
        last = self._drain_train_records(self._telemetry_flush()) or last
        if steps_done:
            # Drain the async dispatch queue with a value fetch before
            # reading the clock — per-step host timestamps would measure
            # dispatch gaps, not device time (first epoch includes compile,
            # same caveat as the reference's epoch timing).
            float(self.state.step)
            elapsed = time.perf_counter() - t0
            # cost-card join: this epoch's synced wall attributed to the
            # train step program (telemetry/costmodel.py)
            self.prog_times.observe_total("train_step", elapsed, steps_done)
            self.metrics_log.log(
                kind="epoch_timing", epoch=epoch, steps=steps_done,
                mean_ms=1e3 * elapsed / steps_done,
                items_per_s=global_bs * steps_done / elapsed,
            )
        return last

    def validate(self) -> dict:
        """Validation epoch (ref ``validate``, ``restnet_ddp.py:50-72``):
        device-resident accumulators, one global psum'd result on every host."""
        metrics = jax.device_put(
            ClassificationMetrics.empty(), mesh_lib.replicated_sharding(self.mesh)
        )
        n_local = mesh_lib.local_replica_count(self.mesh)
        for host_batch in self.val_loader.iter_batches(0):
            # Wrap-pad a partial final batch to replica divisibility — the
            # same duplicate-counting semantics torch's non-drop_last
            # DistributedSampler gives the reference's val loop
            # (restnet_ddp.py:118, D10 padding).
            n = host_batch["image"].shape[0]
            pad = (-n) % n_local
            if pad:
                # np.resize tiles cyclically, so pad > n (tiny final batch,
                # many replicas) still fills correctly.
                host_batch = {
                    k: np.resize(v, (n + pad,) + v.shape[1:])
                    for k, v in host_batch.items()
                }
            batch = mesh_lib.shard_batch(self.mesh, host_batch)
            with program_load_if(self._evaluated == 0, "eval_step"):
                metrics = self.eval_step(self.state, batch, metrics)
            self._evaluated += 1
        return jax.device_get(metrics).summary()

    def fit(self) -> dict:
        """Full run: resume → epochs → validate → best tracking → timing
        (ref ``main`` of every recipe, e.g. ``restnet_ddp.py:135-150``).

        The epoch loop is re-entrant for rollback: when the step guard
        condemns the run (``RollbackRequested`` after ``max_bad_steps``
        consecutive non-finite steps), the last good checkpoint is
        restored and the loop continues from ITS epoch/step — which may
        rewind epochs. Every rank takes the same path (replicated guard
        metric), preserving collective ordering."""
        from pytorch_distributed_tpu.resilience.stepguard import (
            RollbackRequested,
        )

        self.goodput.start()
        self.try_resume()
        self._run_warmup()  # AOT-compile the registry before step 1
        summary: dict = {}
        first_epoch = self.start_epoch  # trace only the first epoch run
        epoch = self.start_epoch
        while epoch < self.config.epochs:
            t0 = time.time()
            self.train_sampler.set_epoch(epoch)  # ref restnet_ddp.py:137
            start_step = self.start_step if epoch == self.start_epoch else 0
            # jax.profiler capture when PDT_TRACE_DIR is set — first epoch of
            # this run only (tracing all epochs would buffer multi-GB of
            # events on the host).
            try:
                with trace(enabled=bool(os.environ.get("PDT_TRACE_DIR"))
                           and epoch == first_epoch):
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
                f"acc1 {summary['acc1']:.2f} acc5 {summary['acc5']:.2f}"
            )
            if summary["acc1"] > self.best_acc:
                self.best_acc = summary["acc1"]
                # sharded, non-blocking: only the device→host snapshot runs
                # here; the file write rides a thread and the commit
                # (barrier + manifest) lands at the next wait() — a point
                # every rank reaches in the same order because the psum'd
                # acc gives all ranks the same improvement decision
                with self.goodput.timed("checkpoint"), \
                        spans.tracer().span("ckpt.save", best=True):
                    self.ckpt.save_best_sharded(
                        self._payload_live(epoch + 1, 0), block=False
                    )
                rank0_print(f"new best acc1 {self.best_acc:.2f}, saved best.ckpt")
            epoch_s = time.time() - t0
            rank0_print(
                f"epoch {epoch} cost time: {epoch_s:.1f} s"
            )  # ref restnet_ddp.py:146
            self.metrics_log.log(
                kind="val", epoch=epoch, epoch_s=epoch_s, **summary
            )
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
        summary["best_acc"] = self.best_acc
        return summary
