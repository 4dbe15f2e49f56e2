"""Flight recorder: a bounded ring of recent events, dumped on disaster.

A crashed or stalled run used to leave only ``watchdog_stall.log`` — a
stack dump with no history. The flight recorder keeps the last
``capacity`` structured events (step results, decode ticks, admissions,
spills/sheds, handoffs, checkpoint saves, rollbacks, watchdog beats,
anomalies) in memory, and writes them out two ways:

- ``dump(path, reason)`` — an ATOMIC snapshot (tmp + ``os.replace``) of
  the whole ring with a header, taken at the trigger sites: watchdog
  stall, StepGuard rollback, suspend, and unhandled exception (the
  chained ``sys.excepthook``). A half-written dump can never exist.
- an optional **mirror**: every event also appends one line to a
  size-capped JSONL (``MetricsLogger`` with rotation), durable the
  moment ``record`` returns. SIGKILL runs no handlers — the mirror is
  what lets the resilience kill-matrix relaunch read the last events
  *before* the kill site even though the process never got to dump.

Recording is cheap (one dict build + deque append + one buffered write
when mirrored), so per-step / per-tick recording is fine; ``seq`` is a
monotone event counter, so a reader can detect the ring's horizon and
order events without trusting wall clocks.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from collections import deque
from typing import List, Optional


class FlightRecorder:
    """Bounded in-memory event ring with atomic dumps and an optional
    durable JSONL mirror."""

    def __init__(self, capacity: int = 256, mirror_path: Optional[str] = None,
                 mirror_max_bytes: int = 1 << 20, enabled: bool = True):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.enabled = bool(enabled)
        self._ring: deque = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._seq = 0
        self.dumps = 0
        self._mirror = None
        self._prev_excepthook = None
        self._excepthook_path: Optional[str] = None
        if mirror_path and self.enabled:
            from pytorch_distributed_tpu.utils.profiling import MetricsLogger

            # per-process stream (rank0_only=False): the crash child whose
            # death the mirror must survive is not always rank 0's twin
            self._mirror = MetricsLogger(
                mirror_path, rank0_only=False, max_bytes=mirror_max_bytes
            )

    # -- recording ---------------------------------------------------------

    def record(self, kind: str, **fields) -> None:
        if not self.enabled:
            return
        with self._lock:
            seq = self._seq
            self._seq += 1
            event = {"seq": seq, "ts": time.time(), "kind": kind, **fields}
            self._ring.append(event)
        if self._mirror is not None:
            # MetricsLogger is line-buffered: durable before return
            self._mirror.log(**event)

    def snapshot(self) -> List[dict]:
        with self._lock:
            return list(self._ring)

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    def census_decls(self):
        from pytorch_distributed_tpu.telemetry.census import Decl

        return [
            Decl("_ring", "fixed", cap=lambda r: r._ring.maxlen,
                 why="deque(maxlen=capacity): the bounded ring is the "
                     "module's whole design"),
        ]

    # -- dumping -----------------------------------------------------------

    def dump(self, path: str, reason: str) -> Optional[str]:
        """Atomic ring snapshot → ``path``. Never raises (a forensics
        write must not take down the run it is documenting); returns the
        path, or None on failure/disabled."""
        if not self.enabled:
            return None
        try:
            events = self.snapshot()
            payload = {
                "reason": reason,
                "pid": os.getpid(),
                "dumped_at": time.time(),
                "events": events,
                "first_seq": events[0]["seq"] if events else None,
                "last_seq": events[-1]["seq"] if events else None,
            }
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(payload, f)
            os.replace(tmp, path)
            self.dumps += 1
            self.record("dump", reason=reason, path=path)
            return path
        except Exception:
            return None

    # -- unhandled exceptions ----------------------------------------------

    def install_excepthook(self, path: str) -> None:
        """Chain onto ``sys.excepthook``: an unhandled exception dumps the
        ring (reason ``exception:<Type>``) before the previous hook runs.
        Idempotent; ``uninstall_excepthook`` restores the chain."""
        if self._prev_excepthook is not None or not self.enabled:
            self._excepthook_path = path
            return
        self._excepthook_path = path
        self._prev_excepthook = sys.excepthook

        def hook(exc_type, exc, tb):
            self.record("exception", type=exc_type.__name__, msg=str(exc))
            self.dump(self._excepthook_path, f"exception:{exc_type.__name__}")
            (self._prev_excepthook or sys.__excepthook__)(exc_type, exc, tb)

        self._hook = hook
        sys.excepthook = hook

    def uninstall_excepthook(self) -> None:
        if self._prev_excepthook is None:
            return
        if sys.excepthook is getattr(self, "_hook", None):
            sys.excepthook = self._prev_excepthook
        self._prev_excepthook = None

    def close(self) -> None:
        self.uninstall_excepthook()
        if self._mirror is not None:
            self._mirror.close()


def read_dump(path: str) -> dict:
    """Load a dump written by :meth:`FlightRecorder.dump`."""
    with open(path) as f:
        return json.load(f)


def read_mirror(path: str) -> List[dict]:
    """Events from a mirror JSONL (rotated generation first, so events
    come back in seq order even across a rotation boundary). Tolerates a
    torn final line — the one a SIGKILL can leave."""
    events: List[dict] = []
    for p in (f"{path}.1", path):
        if not os.path.exists(p):
            continue
        with open(p) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    events.append(json.loads(line))
                except json.JSONDecodeError:
                    continue  # torn tail: the kill mid-write
    return events


#: Shared no-op recorder: call sites thread a
#: recorder through without caring whether anyone is listening.
NULL_RECORDER = FlightRecorder(enabled=False)
