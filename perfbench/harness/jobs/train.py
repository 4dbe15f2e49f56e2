"""Job kind ``train``: one of the program's trainers, driven through its
own ``train_epoch`` for a window of wall-clock seconds. The rate over the
window goes under the end-to-end metric the cell's file names
(``rate_metric``: tokens a second for a language model, images for an
image model).

The cell's file picks the trainer family (``"trainer": "lm"`` is
``LMTrainer`` as ``recipes/lm_pretrain.py`` builds it, ``"image"`` is
``Trainer`` as ``recipes/common.py`` builds it) and states every value
the recipe would set. The benchmark makes the weights from the seed and
lays them over the trainer's own; the trainer's loader is wrapped, not
replaced, so that its ``next`` can be timed and the window closed on the
clock. Set-up drives the SAME trainer object through its first three
steps (which the reference follows), warms it up, and hands it to the
window.
"""

from __future__ import annotations

import collections
import gc
import os
import time

import numpy as np

from perfbench.harness import checks, device as dev, tracing, traffic
from perfbench.harness.weights import CASTS


class WindowLoader:
    """The trainer's own loader behind a gate: each ``iter_batches`` hands
    on batches of ONE long-lived underlying iterator (its prefetch stays
    warm between calls) until a step count or a deadline is reached."""

    def __init__(self, inner, spans: tracing.Spans, keep_first: int):
        self.inner = inner
        self.spans = spans
        self.kept: list = []  # the first host batches, for the reference
        self.keep_first = keep_first
        self.steps_left = 0
        self.deadline = None
        self.on_batch = None  # called before each next(); the trace hook
        # results of the steps in flight, oldest first: the gate waits for
        # all but the newest ``lag``, so the host never runs further ahead
        # of the device than that and the window closes when it is due
        self.in_flight: collections.deque = collections.deque()
        self.lag = 2
        self._it = None
        self._epoch = 0
        self.served = 0

    def __getattr__(self, name):  # collate_fn, dataset, batch_size, ...
        return getattr(self.inner, name)

    def __len__(self):
        return len(self.inner)

    def arm(self, steps: int = 0, deadline: float | None = None) -> None:
        self.steps_left, self.deadline = steps, deadline

    def _next(self):
        if self._it is None:
            self._it = self.inner.iter_batches(0)
        try:
            return next(self._it)
        except StopIteration:
            self._epoch += 1
            self.inner.sampler.set_epoch(self._epoch)
            self._it = self.inner.iter_batches(0)
            return next(self._it)

    def iter_batches(self, start_batch: int = 0):
        while True:
            if self.deadline is not None:
                if time.perf_counter() >= self.deadline:
                    return
            elif self.steps_left <= 0:
                return
            while len(self.in_flight) > self.lag:
                self.in_flight.popleft().block_until_ready()
            if self.on_batch is not None:
                self.on_batch()
            with self.spans.span("loader.next"):
                batch = self._next()
            if len(self.kept) < self.keep_first:
                self.kept.append({k: np.array(v) for k, v in batch.items()})
            self.steps_left -= 1
            self.served += 1
            yield batch

    def close(self) -> None:
        if self._it is not None:
            self._it.close()
            self._it = None


def build_lm(cell, job: dict, cfg: dict, mix: dict, seed: int, devices,
             save_dir: str):
    import jax.numpy as jnp

    from pytorch_distributed_tpu.data import TokenArrayDataset
    from pytorch_distributed_tpu.models.transformer import TransformerConfig
    from pytorch_distributed_tpu.parallel import make_mesh
    from pytorch_distributed_tpu.train import LMTrainer, LMTrainerConfig
    from pytorch_distributed_tpu.utils.env import compile_cache_dir

    seq = int(mix["seq_len"])
    model_cfg = TransformerConfig(
        vocab_size=cfg["vocab_size"], num_layers=cfg["n_layer"],
        num_heads=cfg["n_head"], embed_dim=cfg["n_embd"], max_seq_len=seq,
        dropout=0.0, dtype=getattr(jnp, cfg["dtype"]),
        attention=job["attention"],
    )
    corpus = traffic.token_corpus(mix, seed, cfg["vocab_size"])
    batch = int(mix["batch_per_chip"])
    tcfg = LMTrainerConfig(
        epochs=1, batch_size=batch, lr=job["lr"],
        weight_decay=job["weight_decay"], warmup_steps=0,
        save_dir=save_dir, log_every=job["log_every"],
        num_workers=job["num_workers"], seed=int(seed) & 0x7FFFFFFF,
        compile_cache_dir=compile_cache_dir(),
    )
    mesh = make_mesh(devices, data_parallel=len(devices), seq_parallel=1,
                     model_parallel=1)
    trainer = LMTrainer(
        model_cfg, TokenArrayDataset(corpus, seq),
        TokenArrayDataset(corpus[: seq * batch * len(devices)], seq),
        tcfg, mesh=mesh)
    steps_per_epoch = len(trainer.train_loader)
    optim = {"name": "adamw", "lr": tcfg.lr, "weight_decay":
             tcfg.weight_decay, "total_steps": max(steps_per_epoch, 1),
             "warmup_steps": 0, "final_lr": tcfg.lr * tcfg.min_lr_ratio}
    per_sample = seq  # a sample is one token
    return trainer, optim, batch * len(devices) * per_sample, None


def build_image(cell, job: dict, cfg: dict, mix: dict, seed: int, devices,
                save_dir: str):
    import jax.numpy as jnp

    from pytorch_distributed_tpu.data import RawImageNet
    from pytorch_distributed_tpu.models.resnet import BottleneckBlock, ResNet
    from pytorch_distributed_tpu.parallel import make_mesh
    from pytorch_distributed_tpu.train import Trainer, TrainerConfig
    from pytorch_distributed_tpu.utils.env import compile_cache_dir

    data_dir = traffic.ensure_record_split(
        mix, os.path.join(cell.root, ".perfbench_cache", "records",
                          f"{mix['name']}-{mix['records']}x{mix['stored_px']}"))
    ds = RepeatedDataset(
        RawImageNet("train", data_dir=data_dir, crop_size=cfg["image_size"],
                    aug=mix["aug"]), int(mix["repeat"]))
    model = ResNet(stage_sizes=tuple(cfg["stage_sizes"]),
                   block_cls=BottleneckBlock, num_classes=cfg["num_classes"],
                   num_filters=cfg["width"], dtype=getattr(jnp, cfg["dtype"]))
    batch = int(mix["batch_per_chip"])
    tcfg = TrainerConfig(
        epochs=100, batch_size=batch, lr=job["lr"], momentum=job["momentum"],
        weight_decay=job["weight_decay"], lr_step_epochs=30, lr_gamma=0.1,
        precision="bf16" if cfg["dtype"] == "bfloat16" else "fp32",
        save_dir=save_dir, log_every=job["log_every"],
        num_workers=job["num_workers"], seed=int(seed) & 0x7FFFFFFF,
        compile_cache_dir=compile_cache_dir(),
    )
    trainer = Trainer(model, ds, ds, tcfg, mesh=make_mesh(devices),
                      input_shape=(1, cfg["image_size"], cfg["image_size"], 3))
    optim = {"name": "sgd", "lr": tcfg.lr, "momentum": tcfg.momentum,
             "weight_decay": tcfg.weight_decay}
    import jax

    # on the host: the step donates the state's buffers
    stats = jax.device_get(trainer.state.batch_stats)
    return trainer, optim, batch * len(devices), stats


class RepeatedDataset:
    """A record split read round and round: index i is record i mod n, so
    an epoch is as long as a real one while the file stays small. The
    augmentation still differs each time (its rng is seeded per index)."""

    def __init__(self, inner, times: int):
        self.inner, self.times = inner, times

    def __len__(self):
        return len(self.inner) * self.times

    def getitem_rng(self, i: int, rng):
        return self.inner.getitem_rng(i % len(self.inner), rng)

    def __getitem__(self, i: int):
        return self.inner[i % len(self.inner)]

    def collate_batch(self, indices, make_rng):
        """The split's whole-batch fast path (the C crop and collate), on
        the records the indices fall on. The split draws one rng an index,
        in order: each draw is seeded by the index asked for, not by the
        record it falls on, so that a record read twice is cropped anew."""
        asked = iter(indices)
        return self.inner.collate_batch(
            [i % len(self.inner) for i in indices],
            lambda _record: make_rng(next(asked)))


BUILDERS = {"lm": build_lm, "image": build_image}


def first_gradient(optim: dict, opt_state, weights):
    """The first gradient as the optimizer got it, from its state after
    one step: Adam's first moment is (1 - b1) g; SGD's trace is g + wd p."""
    import jax

    if optim["name"] == "adamw":
        return jax.tree.map(lambda m: m / (1 - 0.9), opt_state[0].mu)
    trace = opt_state[1].trace
    return jax.tree.map(lambda t, p: t - optim["weight_decay"] * p,
                        trace, weights)


def run(cell, seed: int, seconds: float, trace: bool, tiny: bool,
        control=None) -> dict:
    t_setup = time.perf_counter()
    import jax
    import jax.numpy as jnp

    devices = dev.require_devices(cell.chips, allow_cpu=tiny)
    dev.enable_compile_cache()
    job, cfg, mix = cell.sized(tiny)
    save_dir = os.path.join(cell.root, ".perfbench_cache", "runs", cell.name)
    os.makedirs(save_dir, exist_ok=True)
    ref = cell.reference()
    spans = tracing.Spans()

    trainer, optim, samples_per_step, aux0 = BUILDERS[job["trainer"]](
        cell, job, cfg, mix, seed, devices, save_dir)
    tracing.phase(t_setup, "trainer built")
    # the benchmark's weights from the seed, over the trainer's own
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                          trainer.state.params)
    # (made again from the seed wherever they are needed: a second copy
    # held through the steps would not fit beside the step's own memory)
    trainer.state = trainer.state.replace(params=jax.device_put(
        ref.init_params(seed, shapes),
        jax.tree.map(lambda x: x.sharding, trainer.state.params)))
    tracing.phase(t_setup, "weights from the seed laid over the trainer's")
    loader = WindowLoader(trainer.train_loader, spans, keep_first=3)
    trainer.train_loader = loader

    # the first three steps, through the window's own call and feed
    losses = []
    step_fn = trainer.train_step

    def recording_step(state, batch):
        state, metrics = step_fn(state, batch)
        if len(losses) < 3:
            losses.append(metrics["loss"])
        loader.in_flight.append(metrics["loss"])
        return state, metrics

    recording_step.lower = step_fn.lower
    recording_step._cache_size = step_fn._cache_size
    trainer.train_step = recording_step
    loader.arm(steps=1)
    trainer.train_epoch(0, 0)
    tracing.phase(t_setup, "first step")
    grad_norms = checks.leaf_norms(first_gradient(
        optim, trainer.state.opt_state, ref.init_params(seed, shapes)))
    loader.arm(steps=2)
    trainer.train_epoch(0, 1)
    tracing.phase(t_setup, "steps two and three")
    delta_norms = checks.leaf_norms(
        jax.tree.map(lambda a, b: a.astype(jnp.float32) - b,
                     trainer.state.params, ref.init_params(seed, shapes)))
    program = {"losses": [float(x) for x in losses],
               "grad_norms": grad_norms, "delta_norms": delta_norms}
    loader.arm(steps=int(job["warm_steps"]))
    trainer.train_epoch(0, 3)
    tracing.phase(t_setup, "warm-up steps; set-up ends")
    programs_before = len(trainer.compiled_program_names())
    setup_s = time.perf_counter() - t_setup

    # the window: samples over all of it, between two fences
    prof = None
    if trace:
        prof = tracing.ProfilerWindow(
            os.path.join(cell.root, ".perfbench_cache", "trace", cell.name),
            spans)
        t_trace = min(float(job["trace_seconds"]), seconds)
    served0 = loader.served
    t0 = time.perf_counter()
    deadline = t0 + seconds
    state = {"on": False}

    def maybe_start_trace():
        if not state["on"] and time.perf_counter() >= deadline - t_trace:
            state["on"] = True
            state["served"] = loader.served
            state["t"] = time.perf_counter()
            prof.start()

    if trace:
        loader.on_batch = maybe_start_trace
    loader.arm(deadline=deadline)
    trainer.train_epoch(0, served0)  # ends with a fence on the last step
    t1 = time.perf_counter()
    tracing.phase(t_setup, "window closed")
    loader.on_batch = None
    steps = loader.served - served0
    reduced = None
    counters = {"steps": steps, "window": (t0, t1),
                "samples_per_step": samples_per_step}
    if trace:
        if not state["on"]:
            raise RuntimeError("the window closed before the trace began")
        reduced = prof.stop()
        reduced = tracing.reduce_events(reduced)
        counters["traced_steps"] = loader.served - state["served"]
        counters["traced_window"] = (state["t"], t1)
    compiled_in_window = len(trainer.compiled_program_names()) - programs_before
    rate = steps * samples_per_step / (t1 - t0)

    # peak memory: the compiled step's own account, or the allocator's
    # peak where that is larger
    spec = next(s for s in trainer.program_registry()
                if s.name.endswith("train_step"))
    peak = dev.compiled_peak_bytes(spec.aot())
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))

    # free the program's state, then the reference on the same weights
    batches = loader.kept
    loader.close()
    n_rep = len(devices)
    trainer.state = None
    trainer.ckpt = None
    del trainer, step_fn, recording_step, spec
    gc.collect()

    # the reference takes a batch in blocks, one at a time: rows of a
    # language batch, or one replica's share of an image batch (whose
    # BatchNorm sees just those rows)
    rows = (int(job["reference_rows"]) if job["trainer"] == "lm"
            else samples_per_step // n_rep)

    def blocks_of(b):
        return {k: jnp.asarray(v).reshape((-1, rows) + v.shape[1:])
                for k, v in b.items()}

    t_ref = time.perf_counter()
    weights = ref.init_params(seed, shapes)
    aux = (jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), aux0)
           if aux0 is not None else None)
    reference = checks.reference_training(ref, optim, weights, aux, batches,
                                          blocks_of)
    tracing.phase(t_setup, "reference followed the first steps")
    results = checks.compare_training(program, reference, job["limits"])
    results.append(checks.check("compilations_in_window",
                                compiled_in_window, 0))
    from pytorch_distributed_tpu.compilecache import process_compile_totals

    hits, compile_s = process_compile_totals()
    info = {"reference_s": time.perf_counter() - t_ref, "steps": steps,
            "cache_hits": hits, "compile_s": compile_s,
            "setup_s": setup_s,
            "window_s": t1 - t0,
            "program_losses": program["losses"],
            "reference_losses": reference["losses"]}
    if control:
        lowered = checks.reference_training(ref, optim, weights, aux,
                                            batches, blocks_of,
                                            cast=CASTS[control])
        info["control"] = checks.compare_training(
            lowered, reference, job["limits"], "control_")
    return {
        "correct": all(c["ok"] for c in results),
        "attempted": steps, "failed": 0,
        "e2e": {job["rate_metric"]: rate, "setup_s": setup_s},
        "device": dev.device_record(devices, peak),
        "trace": reduced, "spans": spans, "counters": counters,
        "cell": cell, "config": cfg, "mix": mix, "checks": results,
        "info": info,
    }
