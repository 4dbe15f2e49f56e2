"""Profiling and observability.

The reference's entire observability story is wall-clock epoch timing via
``time.time()`` prints (``restnet_ddp.py:136-146``; SURVEY.md §5 "tracing:
ABSENT" — GPU util/memory in result.png were measured externally by the
cluster). This module is the in-framework replacement:

- ``trace``: ``jax.profiler`` capture behind a flag/env — one context
  manager wraps any region (an epoch, N steps) and writes a TensorBoard-
  loadable trace with XLA op/fusion timelines (the TPU answer to nvprof);
- ``StepTimer``: wall-clock step/epoch statistics with warmup exclusion —
  honest throughput numbers (first steps include compilation);
- ``device_duty_cycle``: the TPU analog of nvidia-smi "GPU util" — the
  fraction of wall time the device spent executing, derived by comparing
  back-to-back synced step time against dispatch-gap-free time;
- ``MetricsLogger``: JSONL metrics stream (step, loss, acc, lr, img/s) so
  runs are machine-comparable, not print-scraped.
"""

from __future__ import annotations

import atexit
import contextlib
import json
import os
import threading
import time
from typing import Iterator, Optional

import numpy as np


@contextlib.contextmanager
def trace(log_dir: Optional[str] = None, enabled: Optional[bool] = None) -> Iterator[None]:
    """``jax.profiler`` trace region.

    Enabled when ``enabled`` is True or env ``PDT_TRACE_DIR`` is set; traces
    land in ``log_dir`` (default the env value). View with TensorBoard's
    profile plugin or xprof.
    """
    env_dir = os.environ.get("PDT_TRACE_DIR")
    if enabled is None:
        enabled = env_dir is not None or log_dir is not None
    if not enabled:
        yield
        return
    import jax

    target = log_dir or env_dir or "/tmp/pdt_trace"
    os.makedirs(target, exist_ok=True)
    with jax.profiler.trace(target):
        yield


class StepTimer:
    """Wall-clock step statistics with warmup exclusion.

    ``tick()`` per step; ``summary(items_per_step)`` → mean/p50/p95 step ms
    and items/s over the post-warmup window.
    """

    def __init__(self, warmup_steps: int = 2):
        self.warmup_steps = warmup_steps
        self._times: list[float] = []
        self._last: Optional[float] = None

    def tick(self) -> None:
        now = time.perf_counter()
        if self._last is not None:
            self._times.append(now - self._last)
        self._last = now

    def reset(self) -> None:
        self._times.clear()
        self._last = None

    @property
    def steps(self) -> int:
        return max(len(self._times) - self.warmup_steps, 0)

    def summary(self, items_per_step: Optional[int] = None) -> dict:
        times = np.asarray(self._times[self.warmup_steps:])
        if times.size == 0:
            return {"steps": 0}
        out = {
            "steps": int(times.size),
            "mean_ms": float(times.mean() * 1e3),
            "p50_ms": float(np.percentile(times, 50) * 1e3),
            "p95_ms": float(np.percentile(times, 95) * 1e3),
        }
        if items_per_step:
            out["items_per_s"] = float(items_per_step / times.mean())
        return out


def _scalar_sync(tree) -> None:
    """Force completion by fetching the smallest DEVICE leaf.

    A value fetch cannot return before the work that produces it. On the
    runtime the 2026-07 numbers came from, ``block_until_ready`` was
    observed to return before device work drained, and device→host
    bandwidth was as low as ~24 MB/s — hence a fetch, and the cheapest
    one. On the local v5e (jax 0.9.0) the two agree: 20 chained
    ResNet-50 steps took 0.9410-0.9414 s under ``block_until_ready`` and
    0.9408-0.9410 s under a value fetch in four runs (chip_smoke.py's
    ``sync_check`` line, CHANGES.md PR 21). The method is kept so timings
    stay comparable with the 2026-07 rows.
    Non-array leaves (plain Python numbers) carry no device dependency and
    must not be chosen — fetching one would be a no-op "sync".
    """
    import jax

    device_leaves = [
        l for l in jax.tree.leaves(tree) if isinstance(l, jax.Array)
    ]
    if not device_leaves:
        return
    leaf = min(device_leaves, key=lambda l: l.size)
    np.asarray(jax.device_get(leaf))


def _file_busy_span_us(path: str):
    """(busy, span) microseconds for ONE profiler trace file, or None if
    it carries no device-track events."""
    import gzip

    with gzip.open(path, "rt") as f:
        data = json.load(f)
    events = data.get("traceEvents", [])
    pids = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pids[e["pid"]] = e.get("args", {}).get("name", "")
    dev_pids = {p for p, n in pids.items() if "/device:" in n and "CPU" not in n}
    if not dev_pids:
        return None
    intervals = sorted(
        (e["ts"], e["ts"] + e.get("dur", 0))
        for e in events
        if e.get("ph") == "X" and e.get("pid") in dev_pids
    )
    if not intervals:
        return None
    busy = 0.0
    cur_start, cur_end = intervals[0]
    for start, end in intervals[1:]:
        if start > cur_end:
            busy += cur_end - cur_start
            cur_start, cur_end = start, end
        else:
            cur_end = max(cur_end, end)
    busy += cur_end - cur_start
    span = max(end for _, end in intervals) - intervals[0][0]
    return busy, span


def trace_device_busy_s(trace_dir: str):
    """Device-busy and device-active-span seconds from the
    ``jax.profiler`` traces under ``trace_dir``.

    Parses the Chrome-trace JSON the profiler writes, takes every
    complete ("X") event on a device-named process track, and returns
    ``(busy, span)``: the length of the union of their time intervals
    (events nest, so summing durations would double-count) and the
    first-event-start → last-event-end span. A directory holding
    SEVERAL profiler runs (``plugins/profile/<run>/``) aggregates across
    all of them — per-run busy and span summed — instead of the old
    behavior of silently reading only the lexicographically newest run.
    Returns None if no trace/device events are found anywhere.
    """
    import glob

    paths = sorted(
        glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.trace.json.gz"))
    )
    busy = span = 0.0
    found = False
    for path in paths:
        bs = _file_busy_span_us(path)
        if bs is None:
            continue
        found = True
        busy += bs[0]
        span += bs[1]
    if not found:
        return None
    # trace timestamps are microseconds
    return busy / 1e6, span / 1e6


def device_duty_cycle(step_fn, carry, *args, iters: int = 10) -> float:
    """Measure the device-busy fraction for a compiled step (the TPU analog
    of the reference's "avg GPU util" column, result.png).

    ``step_fn(carry, *args)`` must return a tuple whose first element is the
    next carry (the TrainState convention) — chaining keeps donated buffers
    valid. Runs ``iters`` dependent executions under a ``jax.profiler``
    trace and returns device_busy_time over the device-active span (first
    event start → last event end). This replaces the round-1 per-step-sync
    estimate, which measures the host round trip of each sync (~95 ms on
    the 2026-07 runtime), not device idleness; wall clock around the trace
    context is also unusable because stopping the trace collects the event
    buffer.

    Returns NaN when no device trace is available (e.g. CPU backend).
    """
    import tempfile

    import jax

    out = step_fn(carry, *args)
    carry = out[0]
    _scalar_sync(out[1] if len(out) > 1 else carry)

    with tempfile.TemporaryDirectory() as td:
        with jax.profiler.trace(td):
            for _ in range(iters):
                out = step_fn(carry, *args)
                carry = out[0]
            _scalar_sync(out[1] if len(out) > 1 else carry)
        busy_span = trace_device_busy_s(td)
    if busy_span is None:
        return float("nan")
    busy, span = busy_span
    return min(busy / max(span, 1e-9), 1.0)


class MetricsLogger:
    """Append-only JSONL metrics stream — the one schema every telemetry
    producer (trainers, serving scheduler, goodput ledger) writes.

    Hardened per ISSUE 4: rank-0 gating lives INSIDE the class (callers
    used to have to remember it; ``rank0_only=False`` opts out for
    per-process streams), the file handle is registered with ``atexit``
    so a crash mid-run flushes the tail instead of losing it, reopening
    a path APPENDS (mode "a" — a resumed run extends its history), and
    the logger is a context manager. Line-buffered writes: every record
    is durable as soon as ``log`` returns.

    Rotation (ISSUE 8): ``max_bytes`` caps the stream for long runs —
    once the active file passes the cap it rotates to ``<path>.1``
    (replacing the previous generation) and a fresh file continues, so
    total disk stays bounded by ~2×``max_bytes`` while the newest
    history is always intact. Rotation is record-aligned (checked after
    a complete line), so neither generation ever holds a torn record.

    Thread-safe (round 16): the async host runtime's worker threads
    emit per-request records concurrently with the main loop, so the
    serialize+write+rotate sequence holds one lock — records from any
    thread land as whole lines, and rotation can never interleave with
    a write.
    """

    def __init__(self, path: Optional[str], rank0_only: bool = True,
                 max_bytes: Optional[int] = None):
        self.path = path
        self.max_bytes = max_bytes
        self.rotations = 0
        self._f = None
        self._lock = threading.Lock()
        if path and (not rank0_only or self._is_rank0()):
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            # a SIGKILL can leave a torn final line with no newline;
            # seal it before appending so the NEXT record stays
            # parseable (readers skip the torn fragment as one bad
            # line instead of losing two records merged into it)
            torn = False
            try:
                with open(path, "rb") as existing:
                    existing.seek(-1, os.SEEK_END)
                    torn = existing.read(1) != b"\n"
            except OSError:
                pass  # missing or empty file: nothing to seal
            self._f = open(path, "a", buffering=1)
            if torn:
                self._f.write("\n")
            atexit.register(self.close)

    @staticmethod
    def _is_rank0() -> bool:
        try:
            import jax

            return jax.process_index() == 0
        except Exception:  # no jax / uninitialized backend: single process
            return True

    def log(self, **record) -> None:
        if self._f is None:
            return
        record.setdefault("ts", time.time())
        line = json.dumps(record) + "\n"
        with self._lock:
            if self._f is None:  # closed by another thread
                return
            self._f.write(line)
            if (self.max_bytes is not None
                    and self._f.tell() >= self.max_bytes):
                self._rotate()

    def _rotate(self) -> None:
        """Roll the full active file to ``<path>.1`` (one kept
        generation) and continue on a fresh one."""
        self._f.close()
        try:
            os.replace(self.path, f"{self.path}.1")
        except OSError:
            pass  # a racing cleanup removed it: just reopen fresh
        self._f = open(self.path, "a", buffering=1)
        self.rotations += 1

    def close(self) -> None:
        if self._f is not None:
            try:
                atexit.unregister(self.close)
            except Exception:
                pass
            with self._lock:
                if self._f is not None:
                    self._f.close()
                    self._f = None

    def __enter__(self) -> "MetricsLogger":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
