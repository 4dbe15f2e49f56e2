"""Shared trainer machinery: the suspend/checkpoint/resume contract.

One home for the logic both trainers (image ``Trainer``, ``LMTrainer``)
must agree on — the reference's §3.5 fault-tolerance path plus this
framework's multi-host hardening. Keeping it in one place is load-bearing:
these are collective-ordering-sensitive code paths where two diverging
copies would deadlock pods.

Subclass contract:
  - ``self.config`` has ``suspend_sync_every``; ``self.watcher`` is a
    SuspendWatcher; ``self.ckpt`` a Checkpointer; ``self.mesh`` the mesh;
    ``self.state`` the TrainState; ``self.state_specs`` a spec tree or None.
  - ``_extra_payload()`` → dict of host-side scalars to checkpoint
    (best_acc / best_ppl, ...); ``_restore_extra(dict)`` applies them.
"""

from __future__ import annotations

import os
import time

import jax
import numpy as np

from pytorch_distributed_tpu.parallel import collectives, mesh as mesh_lib
from pytorch_distributed_tpu.resilience import faults
from pytorch_distributed_tpu.resilience.stepguard import (
    RollbackRequested,
    StepGuard,
)
from pytorch_distributed_tpu.resilience.watchdog import Watchdog
from pytorch_distributed_tpu.telemetry import (
    NULL_RECORDER,
    AnomalySentinel,
    GoodputLedger,
    ProgramTimes,
    spans,
)
from pytorch_distributed_tpu.utils.logging import rank0_print


class SuspendableTrainer:
    """Mixin implementing suspend agreement, payloads, and resume."""

    # resilience attributes; _init_resilience overrides them per config
    guard = None
    watchdog = None
    rollbacks = 0
    # telemetry attributes; _init_resilience overrides them per config
    goodput = None
    _ring = None
    _dispatched = 0
    _evaluated = 0
    # attribution & forensics (ISSUE 8); _init_resilience overrides
    sentinel = None
    flightrec = NULL_RECORDER
    exporter = None
    prog_times = None
    _last_step_t = None

    # ---- resilience plumbing (resilience/: stepguard, watchdog, faults).
    # Both trainers call _init_resilience from __init__ and bracket each
    # train step with _pre_step/_post_step; fit() catches
    # RollbackRequested and re-enters via _rollback. ----

    def _init_resilience(self) -> None:
        """Build the step guard and watchdog the config asks for. The
        guard exists whenever the compiled step emits ``step_good``
        (``nan_guard=True``); ``max_bad_steps=0`` means skip-only, no
        rollback. The goodput ledger (telemetry/) is
        built here too — the watchdog feeds the ledger its stall time —
        plus (ISSUE 8) the anomaly sentinel, flight recorder, and
        per-program time accumulator; the metrics JSONL is created after
        this runs, so the trainers bind it via ``_bind_observability``."""
        from pytorch_distributed_tpu.telemetry import FlightRecorder

        cfg = self.config
        self.goodput = GoodputLedger()
        self._ring = None  # built lazily from the first metrics dict
        self._dispatched = 0  # run-level step-dispatch count (compile attr)
        self._evaluated = 0  # eval-step calls (the first loads the program)
        self.prog_times = ProgramTimes()
        self._last_step_t = None
        threshold = getattr(cfg, "anomaly_threshold", 8.0)
        self.sentinel = (
            AnomalySentinel(
                threshold=threshold,
                window=getattr(cfg, "anomaly_window", 64),
            )
            if threshold and threshold > 0 else None
        )
        if self.sentinel is not None:
            # 10 ms scale floor: near-constant tiny-step series would
            # otherwise flag scheduler jitter (MAD ≈ 0 → any blip is ∞σ);
            # a stall must clear threshold × 10 ms above the baseline
            self.sentinel.detector("step_time").abs_floor = 0.01
            self.sentinel.detector("data_wait").abs_floor = 0.01
        rank0 = jax.process_index() == 0
        if getattr(cfg, "flightrec", True):
            self.flightrec = FlightRecorder(
                capacity=256,
                # durable per-event mirror (size-capped, rank 0): what a
                # SIGKILL'd run leaves behind for the relaunch to read
                mirror_path=os.path.join(cfg.save_dir, "flightrec.jsonl")
                if rank0 else None,
            )
            if rank0:
                self.flightrec.install_excepthook(
                    os.path.join(cfg.save_dir, "flightrec_dump.json")
                )
            if self.sentinel is not None:
                self.sentinel.flightrec = self.flightrec
        else:
            self.flightrec = NULL_RECORDER
        if getattr(cfg, "nan_guard", False):
            self.guard = StepGuard(
                max_bad_steps=getattr(cfg, "max_bad_steps", 0)
            )
        timeout = getattr(cfg, "watchdog_timeout_s", 0.0)
        if timeout and timeout > 0:
            self.watchdog = Watchdog(
                timeout,
                watcher=self.watcher,
                dump_path=os.path.join(cfg.save_dir, "watchdog_stall.log")
                if rank0
                else None,
                ledger=self.goodput,
                flightrec=self.flightrec,
                flightrec_path=os.path.join(
                    cfg.save_dir, "flightrec_stall.json"
                ) if rank0 else None,
            ).start()

    def _bind_observability(self) -> None:
        """Called by the trainers once ``self.metrics_log`` exists:
        attach the sentinel's JSONL stream and start the live
        Prometheus exporter when the config asks for one
        (``metrics_port``)."""
        if self.sentinel is not None:
            self.sentinel.metrics_log = getattr(self, "metrics_log", None)
        port = getattr(self.config, "metrics_port", None)
        if port is not None and jax.process_index() == 0:
            from pytorch_distributed_tpu.telemetry import MetricsExporter

            self.exporter = MetricsExporter(
                self._live_metrics, port=port
            ).start()

    def _live_metrics(self) -> dict:
        """The exporter's scrape callback: run-level host counters only
        (no device sync on the scrape path)."""
        out = dict(self.goodput.report()) if self.goodput else {}
        out["steps_dispatched"] = self._dispatched
        out["rollbacks"] = self.rollbacks
        if self.sentinel is not None:
            out["anomalies"] = self.sentinel.anomalies
        if self.watchdog is not None:
            out["watchdog_stalls"] = self.watchdog.stalls
        return out

    # ---- compile-cache plumbing (compilecache/: registry, AOT, warmup;
    # ANALYSIS.md "Cold start & compile cache"). Both trainers call
    # _init_compilecache FIRST in __init__ (so even flax init and
    # placement programs land in the persistent cache) and fit() calls
    # _run_warmup after resume. ----

    def _init_compilecache(self) -> None:
        """Turn jax's persistent compilation cache on when the config
        asks for one (config.compile_cache_dir; the directory itself is
        ``utils.env.compile_cache_dir``'s call — an exported
        JAX_COMPILATION_CACHE_DIR wins) — a relaunched/resumed run with
        the same fingerprint then loads its executables from disk
        instead of recompiling."""
        requested = getattr(self.config, "compile_cache_dir", None)
        if requested:
            from pytorch_distributed_tpu.utils.env import (
                enable_compile_cache,
            )

            enable_compile_cache(requested)

    def _registry_entries(self):
        """Subclass hook: ``[(name, jit_fn, avals_list_thunk,
        expect_entries)]`` — every compiled step program this trainer
        runs, with a lazy thunk producing the list of abstract argument
        tuples (live state + ShapeDtypeStructs carrying the REAL batch
        shardings) the program compiles for."""
        return []

    def program_registry(self):
        """The trainer's AOT program registry: train step + eval step(s),
        fingerprinted by (env, mesh, trainer config, model config). Warm
        thunks AOT-compile via ``lower(...).compile()`` — trainer steps
        must never EXECUTE during warmup (a dummy step would corrupt
        params/opt state), so the win is the persistent cache: the real
        first dispatch becomes a disk load."""
        from pytorch_distributed_tpu.compilecache import (
            ProgramRegistry,
            ProgramSpec,
            jit_cache_size,
            run_fingerprint,
        )

        reg = ProgramRegistry(run_fingerprint(
            mesh=self.mesh,
            extra=(self.config, getattr(self, "model_config", None)),
        ))
        for name, fn, avals_thunk, expect in self._registry_entries():
            def warm(execute, fn=fn, thunk=avals_thunk):
                for avals in thunk():
                    fn.lower(*avals).compile()

            def aot(fn=fn, thunk=avals_thunk):
                # cost-card statics from the steady-state (first) aval
                # variant; a multi-shape eval step's card covers shape 0
                avals = thunk()
                return fn.lower(*avals[0]).compile() if avals else None

            reg.add(ProgramSpec(
                name=name, warm=warm, priority=0, expect_entries=expect,
                cache_probe=lambda fn=fn: jit_cache_size(fn),
                aot=aot,
            ))
        return reg

    def compiled_program_names(self) -> list:
        """One element per live jit-cache entry of each step program —
        the observed side of the registry coverage guard."""
        from pytorch_distributed_tpu.compilecache import jit_cache_size

        names = []
        for name, fn, _thunk, _expect in self._registry_entries():
            n = jit_cache_size(fn)
            names.extend([name] * (n or 0))
        return names

    def assert_registry_covers(self) -> None:
        """Fail (CoverageError) if a step program compiled more variants
        than the registry predicted — the trainers' half of the
        acceptance guard (the serving half audits PagedEngine)."""
        self.program_registry().assert_covers(self.compiled_program_names())

    def _run_warmup(self) -> None:
        """``config.warmup``: AOT-compile every registry entry before the
        first step, attributing the wall time to the goodput ledger's
        ``compile`` category and appending ``kind="warmup"`` manifest
        records to the metrics JSONL."""
        if not getattr(self.config, "warmup", False):
            return
        from pytorch_distributed_tpu.compilecache import WarmupRunner

        runner = WarmupRunner(
            self.program_registry(),
            ledger=self.goodput,
            manifest=getattr(self, "metrics_log", None),
        )
        runner.run(background=False)  # AOT thunks are traffic-safe anyway
        s = runner.summary()
        rank0_print(
            f"warmup: {s['programs']} programs in {s['total_s']:.2f}s "
            f"({s['cache_hits']} cache hits, {s['fresh']} fresh; "
            f"fingerprint {s['fingerprint']})"
        )

    # ---- telemetry plumbing (telemetry/: device ring, spans, goodput).
    # The trainers push each log event's device metric scalars through
    # _telemetry_append instead of blocking on float(); records drain
    # lagged, one transfer per flush_every log events. ----

    def _telemetry_append(self, metrics: dict, **meta) -> list:
        """Push one log event into the device ring (no host sync);
        returns any records the push drained."""
        if self._ring is None:
            from pytorch_distributed_tpu.telemetry import DeviceMetricsRing

            self._ring = DeviceMetricsRing(
                list(metrics),
                capacity=max(getattr(self.config, "flush_every", 32), 1),
                sharding=mesh_lib.replicated_sharding(self.mesh),
            )
        return self._ring.append(metrics, **meta)

    def _telemetry_flush(self) -> list:
        """Drain everything buffered (epoch end); may sync on the last
        pushed step — the same point the epoch-timing record syncs."""
        return self._ring.flush() if self._ring is not None else []

    def _drain_train_records(self, records) -> dict:
        """Emit drained ring records (subclass formats them); returns the
        last record's metrics. Base default: nothing to emit."""
        return {}

    def _log_goodput(self) -> None:
        """Emit the run-level goodput record (fit end / pre-suspend)."""
        if self.goodput is not None and getattr(self, "metrics_log", None):
            self.metrics_log.log(kind="goodput", **self.goodput.report())

    def _log_cost_cards(self) -> None:
        """Emit one ``kind="program_cost"`` record per registry program
        (telemetry.costmodel), joining the compiler's FLOP/byte statics
        with the run's measured per-step wall. Gated behind
        ``config.cost_cards`` because the statics cost one extra
        ``lower(...).compile()`` per program (a disk hit when the
        persistent compile cache is on) — paid once at fit END, off the
        training critical path, and never on the pre-suspend fast path."""
        if not getattr(self.config, "cost_cards", False):
            return
        if jax.process_index() != 0:
            return
        from pytorch_distributed_tpu.telemetry import log_cost_cards

        log_cost_cards(
            self.program_registry(), self.prog_times,
            getattr(self, "metrics_log", None),
        )

    def _save_traces(self) -> None:
        """Write the process's span stream as a Chrome trace where
        ``config.trace_dir`` says (rank 0, fit end)."""
        trace_dir = getattr(self.config, "trace_dir", None)
        if trace_dir and jax.process_index() == 0:
            spans.tracer().save(os.path.join(trace_dir, "spans.trace.json"))

    def _pre_step(self, host_batch):
        """Once per train step, before device dispatch: apply any
        ``train.step`` fault directive — ``nan`` poisons the host batch
        (provoking NaN grads through the real compiled step), ``suspend``
        latches the watcher; ``kill``/``hang``/``raise`` execute inside
        fault_point itself."""
        spec = faults.fault_point("train.step")
        if spec is not None:
            if spec.kind == "nan":
                host_batch = faults.poison_batch(host_batch)
            elif spec.kind == "suspend":
                self.watcher.request_suspend()
        return host_batch

    def _post_step(self, metrics: dict) -> None:
        """After each step's dispatch: heartbeat the watchdog (beating
        here, not in _pre_step, keeps the first step's multi-second XLA
        compile outside the armed deadline window) and feed the guard its
        lagged ``step_good`` flag. The guard raises RollbackRequested
        (caught in fit) after K consecutive bad steps — deterministically
        on every rank, since the flag is a replicated psum'd metric.

        Forensics (ISSUE 8): the step lands one flight-recorder event
        (the ring's heartbeat — a post-mortem dump shows exactly which
        step the run died after) and its wall gap feeds the anomaly
        sentinel's ``step_time`` series. The gap is post_step→post_step,
        so a hang anywhere in the loop (data fetch, injected fault,
        dispatch) shows up; the first gap of a run (compile) is absorbed
        by the detector's warmup window."""
        if self.watchdog is not None:
            self.watchdog.beat()
        if self.guard is not None:
            self.guard.observe(metrics.get("step_good"))
        now = time.perf_counter()
        self.flightrec.record("step", n=self._dispatched)
        if self._last_step_t is not None and self.sentinel is not None:
            self.sentinel.observe(
                "step_time", now - self._last_step_t,
                step=self._dispatched,
            )
        self._last_step_t = now

    def _observe_data_wait(self, seconds: float) -> None:
        """Per-step data-wait observation for the sentinel (the trainers
        call this from their ``data_wait`` bracket)."""
        if self.sentinel is not None:
            self.sentinel.observe(
                "data_wait", seconds, step=self._dispatched
            )

    def _epoch_end_guard(self) -> None:
        if self.guard is not None:
            self.guard.flush()

    def _rollback(self, err: RollbackRequested) -> None:
        """Restore the newest restorable checkpoint after the guard gave
        up on skipping. Every rank raises at the same step (replicated
        metric) and reaches this together, so the collective-ordered
        resume path is safe. No checkpoint at all is fatal: training from
        a state the guard condemned would just NaN again."""
        self.rollbacks += 1
        rank0_print(f"stepguard: {err}; restoring last good checkpoint")
        # forensics: the condemned run's last events, dumped before the
        # replay overwrites the ring's recent history
        self.flightrec.record("rollback", n=self.rollbacks, reason=str(err))
        if jax.process_index() == 0:
            self.flightrec.dump(
                os.path.join(self.config.save_dir, "flightrec_dump.json"),
                "rollback",
            )
        # surface the condemned run's buffered log events before the
        # replay re-logs the same steps (keeps the JSONL ordered)
        self._drain_train_records(self._telemetry_flush())
        with self.goodput.timed("rollback"), \
                spans.tracer().span("train.rollback_replay"):
            self.ckpt.wait()  # commit/join any in-flight save first
            if not self.try_resume():
                raise RuntimeError(
                    "stepguard requested rollback but no restorable "
                    "checkpoint exists — enable save_every_n_steps (or "
                    "suspend saves) so a rollback target is available"
                ) from err
        self.guard.reset()

    # ---- checkpoint payloads (collective: call on ALL ranks) ----

    def _extra_payload(self) -> dict:
        return {}

    def _restore_extra(self, restored: dict) -> None:
        pass

    def _payload(self, epoch: int, step: int) -> dict:
        """LEGACY single-file payload: every array gathered to host.

        ``gather_global`` is a collective for cross-process-sharded states,
        so this MUST run on every process together; only the disk write is
        rank-0-gated (``restnet_ddp.py:36,145``). The default save path is
        now ``_payload_live`` + ``save_latest_sharded`` (no gather); this
        remains for the single-file interchange format."""
        from pytorch_distributed_tpu.utils.checkpoint import gather_global

        payload = {"state": gather_global(self.state), "epoch": epoch,
                   "step": step}
        payload.update(self._extra_payload())
        return payload

    def _payload_live(self, epoch: int, step: int) -> dict:
        """Payload with the state's live (device, possibly cross-process
        sharded) arrays — for ``save_sharded``, which writes each process's
        blocks from its own shards. NO gather, no full-state host copy."""
        payload = {"state": self.state, "epoch": epoch, "step": step}
        payload.update(self._extra_payload())
        return payload

    def _state_shardings(self):
        if self.state_specs is not None:
            return mesh_lib.specs_to_shardings(self.mesh, self.state_specs)
        return jax.tree.map(
            lambda _: mesh_lib.replicated_sharding(self.mesh), self.state
        )

    def try_resume(self) -> bool:
        """Restore the NEWEST restorable checkpoint: ``latest.ckpt``
        (suspend save) or a ``step-*.ckpt`` interval save, whichever
        carries the highest ``state/step`` (``restnet_ddp.py:127-132``
        restores only latest — interval saves are a durability policy the
        reference lacks, so a crash after them must not fall back to an
        older suspend artifact).

        ELASTIC (reshard/; ROADMAP item 4): target shardings come from
        THIS run's mesh and spec tree, never from the writer's layout, so
        a checkpoint written on mesh (4,2) restores onto (2,2) or (8,1)
        with optimizer state, data cursor and global step intact — each
        process assembles exactly the block slices its devices need.
        ``config.elastic_resume=False`` refuses topology-mismatched
        candidates instead (they fall through like corrupt ones). A
        cross-topology resume changes ``run_fingerprint`` (the mesh is
        part of it), so the writer's compile-cache artifacts are misses
        by construction; ``fit()`` runs ``_run_warmup`` AFTER this
        method, which re-AOT-compiles the registry for the new mesh
        before step 1 — no mid-run compiles after an elastic resume.

        Fallback restore: candidates are pre-validated (manifest + shard
        completeness + save token) and scanned newest-first; a candidate
        that still fails at load time — e.g. a token mismatch surfacing
        mid-read — is logged and the scan falls through to the next
        *complete* checkpoint instead of refusing to start. Validation
        reads the same shared-fs files on every rank, so all ranks pick
        the same candidate. Legacy single files restore via the full-
        host-numpy path, placed slice-wise — mesh-agnostic by
        construction."""
        from pytorch_distributed_tpu.reshard import (
            ReshardRefused,
            load_elastic,
            mesh_desc,
            payload_shardings,
        )

        self.ckpt.wait()
        allow = getattr(self.config, "elastic_resume", True)
        for path in self.ckpt.restorable_paths():
            try:
                template = self._payload_live(0, 0)
                shardings = payload_shardings(
                    self.mesh, template, self.state_specs
                )
                restored, info = load_elastic(
                    path, template, shardings,
                    mesh=self.mesh, allow_reshard=allow,
                )
                # no-op for placed sharded leaves; places the legacy
                # path's host arrays (slice-wise put already done there,
                # this is belt-and-braces for sharding-less entries)
                state = jax.device_put(
                    restored["state"], shardings["state"]
                )
            except ReshardRefused as e:
                rank0_print(f"resume: skipping {path}: {e}")
                continue
            except (OSError, ValueError, KeyError, RuntimeError) as e:
                rank0_print(
                    f"resume: {path} failed to load ({e}); falling back "
                    "to the next complete checkpoint"
                )
                continue
            self.state = state
            self.start_epoch = int(restored["epoch"])
            self.start_step = int(restored["step"])
            self._restore_extra(restored)
            if info.resharded:
                rank0_print(
                    f"elastic resume: {info.describe()} — "
                    "run_fingerprint changed with the mesh; warmup "
                    "re-AOT-compiles the program registry for this "
                    "topology before step 1"
                )
            rank0_print(
                f"resumed from {path}: "
                f"epoch {self.start_epoch} step {self.start_step}"
            )
            return True
        return False

    def _maybe_save_step(self, epoch: int, step: int) -> None:
        """Interval checkpoint hook: every ``save_every_n_steps`` train
        steps, a non-blocking sharded save of the live state to
        ``step-<global_step>.ckpt`` with keep-last-``keep_last_ckpts``
        retention. The save's internal ``wait()`` commits the previous
        in-flight save — every rank calls this at the same step, so the
        collective ordering matches the suspend/best paths."""
        every = getattr(self.config, "save_every_n_steps", 0)
        if every <= 0 or (step + 1) % every:  # negative = off, like 0
            return
        self.flightrec.record("ckpt_save", epoch=epoch, step=step)
        with self.goodput.timed("checkpoint"), \
                spans.tracer().span("ckpt.save", step=step):
            gstep = int(np.asarray(jax.device_get(self.state.step)))
            self.ckpt.save_step_sharded(
                self._payload_live(epoch, step + 1), gstep,
                keep_last=getattr(self.config, "keep_last_ckpts", 3),
                block=False,
            )

    # ---- the suspend agreement (ref restnet_ddp.py:36-47) ----

    def _maybe_suspend(self, epoch: int, step: int) -> None:
        """Poll → agree → checkpoint → yield.

        Multi-host with ``suspend_sync_every=N``: a locally-latched signal
        is ONLY acted on at agreement steps (step % N == 0), where every
        host all-reduces its flag — acting immediately on a local signal
        would send one host into the collective payload gather while the
        others run the next train step (mismatched collectives, permanent
        hang). The watcher latches, so deferring loses nothing.
        ``suspend_sync_every=0`` keeps the reference's primary-only
        semantics (unsafe by design, documented).
        """
        suspended = self.watcher.receive_suspend_command()
        sync = self.config.suspend_sync_every
        if sync and jax.process_count() > 1:
            if step % sync != 0:
                return  # defer to the next agreement step
            suspended = bool(
                collectives.all_reduce(np.float32(suspended), "max")
            )
        if not suspended:
            return
        # forensics first: the pre-suspend ring is the record of WHY the
        # run yielded (watchdog latch vs scheduler signal)
        self.flightrec.record("suspend", epoch=epoch, step=step)
        if jax.process_index() == 0:
            self.flightrec.dump(
                os.path.join(self.config.save_dir, "flightrec_dump.json"),
                "suspend",
            )
        # the run is about to yield: surface the ring's buffered log
        # events so the JSONL tail isn't lost with the process
        self._drain_train_records(self._telemetry_flush())
        # Sharded save: EVERY process writes its own blocks (no gather, no
        # full-state host copy on any rank); rank 0 adds the manifest; the
        # save's internal barrier guarantees all files landed before yield.
        with self.goodput.timed("checkpoint"), \
                spans.tracer().span("ckpt.save", step=step, suspend=True):
            self.ckpt.save_latest_sharded(
                self._payload_live(epoch, step + 1)
            )
            rank0_print(
                f"suspend: saved {self.ckpt.latest_path} at epoch {epoch} "
                f"step {step}"
            )
            self.ckpt.wait()
        # the run may not come back: record what this attempt's wall
        # time went to before yielding
        self._log_goodput()
        self._save_traces()
        self.watcher.go_suspend()
