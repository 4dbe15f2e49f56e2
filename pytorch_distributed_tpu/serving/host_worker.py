"""Worker-thread pool for the router's host loop (round 16).

A fleet's one host loop would otherwise run every replica's host work —
JSONL emission, gate-metric percentile math — on the critical path
between device dispatches. That work runs here: a small pool of named
daemon threads draining a FIFO queue of closures, so the main loop's
only job between ticks is dispatch and collect.

Thread-safety contract (the ``rules_threads`` inventory for this round;
ANALYSIS.md "Async host runtime" carries the full table):

- work items may touch ONLY (a) objects with their own locks
  (``MetricsLogger``, ``ReqTracer``), (b) data
  copied onto the closure at enqueue time (the retired ``Request``,
  copied latency-series value lists), and (c) caches guarded by a
  dedicated lock (the scheduler's gate-metrics snapshot). Scheduler and
  router internals (``resident``, ``queue``, ``ready``, the
  ``BlockAllocator``, block tables) are MAIN-THREAD-ONLY — no work item
  may reference them;
- pool counters (``submitted``/``completed``/``errors``) mutate only
  under ``self._lock``;
- worker errors never kill the serve loop mid-tick: they latch into
  ``errors`` and re-raise at the next ``flush()`` — the same
  fail-at-the-barrier contract as the async checkpoint writers.

Ordering: one shared FIFO queue, ``n_threads`` consumers — items START
in submission order but may complete out of order across threads.
Every consumer of worker output tolerates that: JSONL records are
independent lines (reports aggregate, never assume adjacency), and the
gate cache keeps only the newest snapshot (a stale refresh overwriting
a newer one loses at most one tick of percentile drift, which the
overlay of live counters in ``Scheduler.gate_metrics`` bounds anyway).
Causal span records (``kind="span"``) stay on the main thread — seq
order is their contract.
"""

from __future__ import annotations

import queue
import threading
from typing import Callable, List, Optional

#: sentinel a closing pool feeds each worker
_STOP = object()


class HostWorkerPool:
    """N named daemon threads draining one FIFO queue of closures.

    ``submit(fn)`` enqueues; ``flush()`` blocks until everything
    enqueued so far has run (and re-raises the first worker error);
    ``close()`` flushes and joins the threads; ``stop()`` ends them
    without waiting (a ``FleetRouter``'s pool stops when the router is
    collected). The threads are named ``pdt-host-0`` ... so a thread
    dump tells them from the loop's.
    """

    def __init__(self, n_threads: int = 2, name: str = "pdt-host"):
        if n_threads < 1:
            raise ValueError(f"n_threads must be >= 1, got {n_threads}")
        self._q: queue.Queue = queue.Queue()
        self._lock = threading.Lock()
        self._closed = False
        self.submitted = 0
        self.completed = 0
        self.errors: List[BaseException] = []
        self._threads = [
            threading.Thread(target=self._run, name=f"{name}-{i}",
                             daemon=True)
            for i in range(n_threads)
        ]
        for t in self._threads:
            t.start()

    def _run(self) -> None:
        while True:
            fn = self._q.get()
            if fn is _STOP:
                self._q.task_done()
                return
            try:
                fn()
            except BaseException as e:  # latch; re-raised at flush()
                with self._lock:
                    self.errors.append(e)
            finally:
                # a parked worker must not keep its last closure: it
                # captures a Scheduler, and through it the engine's
                # weights and pools
                fn = None
                with self._lock:
                    self.completed += 1
                self._q.task_done()

    def submit(self, fn: Callable[[], None]) -> None:
        """Enqueue one closure (FIFO start order). Raises after
        ``close()`` — a closed pool silently dropping work would lose
        JSONL records."""
        with self._lock:
            if self._closed:
                raise RuntimeError("HostWorkerPool is closed")
            self.submitted += 1
        self._q.put(fn)

    def flush(self) -> None:
        """Block until every submitted item has run; re-raise the first
        worker error (cleared, so a handled failure does not re-fire at
        every later barrier)."""
        self._q.join()
        with self._lock:
            errors, self.errors = self.errors, []
        if errors:
            raise RuntimeError(
                f"{len(errors)} host-worker task(s) failed"
            ) from errors[0]

    @property
    def pending(self) -> int:
        """Items submitted but not yet completed (approximate — racing
        a draining worker — but monotone-consistent enough for tests
        and the top view)."""
        with self._lock:
            return self.submitted - self.completed

    def stop(self) -> None:
        """Refuse new work and let every worker exit once what is queued
        has run, WITHOUT waiting for it. This is what a router's
        finalizer calls: the collector may run it on any thread, a
        worker's own included, where a join would never return.
        Idempotent."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
        for _ in self._threads:
            self._q.put(_STOP)

    def close(self) -> None:
        """``stop()``, then join every worker (each runs what was queued
        before its sentinel) and re-raise the first worker error.
        Idempotent."""
        self.stop()
        for t in self._threads:
            t.join()
        with self._lock:
            errors, self.errors = self.errors, []
        if errors:
            raise RuntimeError(
                f"{len(errors)} host-worker task(s) failed"
            ) from errors[0]
