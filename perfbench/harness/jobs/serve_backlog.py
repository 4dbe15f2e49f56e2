"""Job kind ``serve-backlog``: the paged server behind ``FleetRouter``, in
process, under a closed backlog: the queue is never empty.

``FleetRouter`` (one replica) -> ``Scheduler`` -> ``PagedEngine``, built
as ``recipes/serve_lm.py`` builds them, with the sizes in the cell's file.
Traffic is the mix's fixed multiset in a seeded order
(``harness/traffic.py``). The first wave that fills the slots has its
outputs cut to seeded residual lives, and is admitted a few requests a
tick; a pre-roll runs until that wave's prefill lies wholly behind, then
some ticks more under the full backlog. The window is closed on the wall
clock at tick boundaries and counts every token delivered inside it.
"""

from __future__ import annotations

import gc
import os
import time

import numpy as np

from perfbench.harness import checks, device as dev, tracing, traffic
from perfbench.harness.weights import CASTS


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def reachable_widths(mix: dict, chunk: int, block_len: int,
                     table_width: int) -> list:
    """Table-slice widths a tick's chunk program can have under this mix:
    powers of two from the first chunk of the shortest prompt to the last
    chunk of the longest."""
    lo = _pow2(-(-chunk // block_len))
    top = -(-mix["prompt"]["max"] // chunk) * chunk
    hi = min(_pow2(-(-top // block_len)), table_width)
    out, w = [], lo
    while w <= hi:
        out.append(w)
        w <<= 1
    return out


class Served:
    """What the harness knows of one request."""

    __slots__ = ("prompt", "max_new", "submitted", "tokens", "times")

    def __init__(self, prompt, max_new, submitted):
        self.prompt, self.max_new, self.submitted = prompt, max_new, submitted
        self.tokens: list = []
        self.times: list = []


class Driver:
    """Keeps the backlog full, steps the router, books every token."""

    def __init__(self, router, stream, spans, backlog: int):
        self.router, self.stream, self.spans = router, stream, spans
        self.sched = router.replicas[0]
        self.backlog = backlog
        self.live: dict = {}
        self.done: list = []  # (rid, Served) in order of completion
        self.ticks: list = []  # (t_end, tokens, live_context_sum)
        self.attempted = 0
        self.failed = 0

    def submit(self) -> None:
        prompt, max_new = self.stream.next()
        with self.spans.span("submit"):
            rid = self.router.submit(prompt, max_new)
        self.attempted += 1
        if rid in self.router.rejected:
            self.failed += 1
            return
        self.live[rid] = Served(prompt, max_new, time.perf_counter())

    def tick(self) -> float:
        with self.spans.span("router.step"):
            out = self.router.step()
        now = time.perf_counter()
        for rid, tok in out:
            rec = self.live[rid]
            rec.tokens.append(int(tok))
            rec.times.append(now)
            if len(rec.tokens) >= rec.max_new:
                self.done.append((rid, self.live.pop(rid)))
        armed = self.sched.remaining > 0
        self.ticks.append((now, len(out),
                           int(self.sched.positions[armed].sum())))
        for _ in range(self.backlog - len(self.sched.queue)):
            self.submit()
        return now

    def prefill_behind(self) -> bool:
        return (not self.sched.queue and all(
            r.prefill_done >= r.length
            for r in self.sched.resident.values()))


def build(cell, job: dict, cfg: dict, seed: int, devices, ref):
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.fleet import FleetRouter, SLOConfig
    from pytorch_distributed_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )

    model_cfg = TransformerConfig(
        vocab_size=cfg["vocab_size"], num_layers=cfg["n_layer"],
        num_heads=cfg["n_head"], embed_dim=cfg["n_embd"],
        max_seq_len=cfg["n_positions"], dropout=0.0,
        dtype=getattr(jnp, cfg["dtype"]), attention="dense",
    )
    shapes = jax.eval_shape(
        TransformerLM(model_cfg).init, jax.random.key(0),
        jnp.zeros((1, 8), jnp.int32))["params"]
    weights = ref.init_params(seed, shapes, getattr(jnp, cfg["dtype"]))
    depth = 1 << 30  # a backlog is the point: the gate never sheds it
    router = FleetRouter(
        model_cfg, weights, n_replicas=1, devices=devices,
        slo=SLOConfig(spill_queue_depth=depth, shed_queue_depth=depth),
        retain_results=False, n_slots=job["slots"],
        n_blocks=job["blocks"], block_len=job["block_len"],
        prefill_chunk=job["prefill_chunk"],
        admit_per_step=job["admit_per_step"],
    )
    return router, weights


def warm(engine, job: dict, mix: dict, learn) -> None:
    """The decode tick and the chunk buckets this mix can reach, each run
    once inert; ``learn(label, thunk)`` runs the thunk (under a small
    profiler session of its own in a traced run)."""
    import jax

    def ready():
        jax.block_until_ready(engine.logits)

    learn("decode_tick", lambda: (engine.warm_decode(execute=True), ready()))
    widths = reachable_widths(mix, engine.chunk, engine.block_len,
                              engine.table_width)
    for k in job["warm_jobs"]:
        for w in widths:
            learn("prefill_chunk",
                  lambda k=k, w=w: (engine.warm_chunk(k, w, execute=True),
                                    ready()))


def run(cell, seed: int, seconds: float, trace: bool, tiny: bool,
        control=None) -> dict:
    t_setup = time.perf_counter()
    import jax

    devices = dev.require_devices(cell.chips, allow_cpu=tiny)
    dev.enable_compile_cache()
    job, cfg, mix = cell.sized(tiny)
    ref = cell.reference()
    spans = tracing.Spans()
    trace_dir = os.path.join(cell.root, ".perfbench_cache", "trace",
                             cell.name)

    router, weights = build(cell, job, cfg, seed, devices, ref)
    tracing.phase(t_setup, "weights and router built")
    engine = router.replicas[0].engine
    labels: dict = {}  # profiler module name -> program label

    def learn(label, thunk):
        if not trace:
            thunk()
            return
        prof = tracing.ProfilerWindow(trace_dir, tracing.Spans())
        prof.start()
        thunk()
        for dev_lines in prof.stop()["devices"].values():
            for name, _, _ in dev_lines["modules"]:
                labels[name] = label

    warm(engine, job, mix, learn)
    tracing.phase(t_setup, f"programs warm "
           f"({len(engine.compiled_program_names())} compiled)")
    stream = traffic.RequestStream(mix, seed, cfg["vocab_size"],
                                   first_wave=job["slots"])
    drv = Driver(router, stream, spans, backlog=0)
    # the first wave, a few a tick, so that the slots do not start in step
    while stream.issued < job["slots"]:
        for _ in range(job["fill_per_tick"]):
            drv.submit()
        drv.tick()
    while not drv.prefill_behind():
        drv.tick()
    tracing.phase(t_setup, f"first wave's prefill behind ({len(drv.ticks)} ticks)")
    drv.backlog = int(job["backlog"])
    for _ in range(int(job["preroll_ticks"])):
        drv.tick()
    tracing.phase(t_setup, f"pre-roll done; set-up ends "
           f"({len(engine.compiled_program_names())} programs compiled)")
    programs_before = len(engine.compiled_program_names())
    setup_s = time.perf_counter() - t_setup

    # the window, closed on the wall clock at tick boundaries
    t0 = drv.ticks[-1][0]
    first_tick = len(drv.ticks)
    deadline = t0 + seconds
    prof, traced_from = None, None
    t_trace = min(float(job["trace_seconds"]), seconds)
    now = t0
    while now < deadline:
        if trace and prof is None and now >= deadline - t_trace:
            prof = tracing.ProfilerWindow(trace_dir, spans)
            prof.start()
            traced_from = len(drv.ticks)
        now = drv.tick()
    t1 = now
    tracing.phase(t_setup, "window closed")
    reduced = None
    if trace:
        reduced = tracing.reduce_events(prof.stop())
        reduced["labels"] = labels
    compiled_in_window = (len(engine.compiled_program_names())
                          - programs_before)
    window_ticks = drv.ticks[first_tick:]
    tokens = sum(n for _, n, _ in window_ticks)
    rate = tokens / (t1 - t0)
    gaps, ttfts = [], []
    for _, rec in drv.done + list(drv.live.items()):
        ts = rec.times
        gaps.extend(b - a for a, b in zip(ts, ts[1:]) if t0 < b <= t1)
        if ts and t0 < ts[0] <= t1:
            ttfts.append(ts[0] - rec.submitted)
    gaps.sort()
    gap_p95 = gaps[min(len(gaps) - 1, int(0.95 * len(gaps)))]

    peak = dev.compiled_peak_bytes(engine.warm_decode(execute=False))
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))

    # correctness: a seeded sample of the requests the window finished,
    # the longest among them, against one reference pass each
    finished = [(rid, rec) for rid, rec in drv.done
                if rec.times and t0 < rec.times[-1] <= t1]
    counters = {
        "window": (t0, t1), "ticks": window_ticks,
        "traced_ticks": drv.ticks[traced_from:] if trace else [],
        "slots": job["slots"], "ttfts": ttfts, "gaps": len(gaps),
        "finished": len(finished),
    }
    attempted, failed = drv.attempted, drv.failed + len(router.failed)
    router.replicas.clear()
    del router, engine, drv.router, drv.sched
    gc.collect()
    t_ref = time.perf_counter()
    rng = np.random.default_rng([int(seed) & 0x7FFFFFFFFFFFFFFF, 0x7C])
    finished.sort(key=lambda x: -(len(x[1].prompt) + len(x[1].tokens)))
    n_check = min(int(job["check_requests"]), len(finished))
    picks = finished[:1] + [finished[i] for i in sorted(
        rng.choice(np.arange(1, len(finished)), size=max(n_check - 1, 0),
                   replace=False))] if finished else []
    worst = control_low = 0.0
    checked_tokens = 0
    control_gaps = []
    passes = (checks.logits_pass(ref),
              checks.logits_pass(ref, CASTS[control]) if control else None)
    for _, rec in picks:
        got = checks.served_token_gaps(passes, weights, rec.prompt,
                                       rec.tokens, cfg["n_positions"])
        worst = max(worst, got["gap"])
        checked_tokens += got["tokens"]
        if control:
            control_gaps.append(got["control_gap"])
    results = [
        checks.check("served_logit_gap_max", worst if picks else
                     float("inf"), job["limits"]["served_logit_gap"]),
        checks.check("failed_requests", failed, 0),
        checks.check("compilations_in_window", compiled_in_window, 0),
    ]
    from pytorch_distributed_tpu.compilecache import process_compile_totals

    hits, compile_s = process_compile_totals()
    tracing.phase(t_setup, "reference passes done")
    info = {"reference_s": time.perf_counter() - t_ref, "setup_s": setup_s,
            "cache_hits": hits, "compile_s": compile_s,
            "checked_requests": len(picks), "checked_tokens": checked_tokens,
            "window_s": t1 - t0, "tokens": tokens, "ticks": len(window_ticks),
            "finished": len(finished),
            "gap_samples": len(gaps)}
    if control:
        # the control's widest gap over the same requests
        info["control"] = [{"name": "control_served_logit_gap_max",
                            "value": max(control_gaps),
                            "limit": job["limits"]["served_logit_gap"],
                            "ok": max(control_gaps)
                            <= job["limits"]["served_logit_gap"]}]
    return {
        "correct": all(c["ok"] for c in results),
        "attempted": attempted, "failed": failed,
        "e2e": {"serve_tokens_per_s": rate, "gap_p95_ms": 1e3 * gap_p95,
                "setup_s": setup_s},
        "device": dev.device_record(devices, peak),
        "trace": reduced, "spans": spans, "counters": counters,
        "cell": cell, "config": cfg, "mix": mix, "checks": results,
        "info": info,
    }
