"""Host-resource monitor — RSS, gc population, tracemalloc top sites.

Round 21.  The serving stack measures program cost (PR 8), per-request
causality (PR 12), and device idleness (PR 13) — but nothing measures
the *host process itself*, and ROADMAP item 5's acceptance ("flat host
RSS and flat per-tick host wall at ≥100k sessions") is a host-memory
property.  ``ResourceMonitor`` samples on a tick-count cadence and
streams ``kind="resource"`` records through the same rotating
``MetricsLogger`` JSONL as every other telemetry kind, so a 100k-session
soak's resource history is itself memory-bounded (the log rotates; the
monitor keeps only a fixed ring of samples for slope fitting).

What a sample carries:

- ``rss_mib`` — resident set from ``/proc/self/status`` (``VmRSS``),
  falling back to ``resource.getrusage`` where /proc is absent
  (``ru_maxrss`` is a *peak*, not current — the record says which via
  ``rss_source`` so a slope fit over getrusage data is read as an
  upper bound).
- ``gc_objects`` — ``len(gc.get_objects())``; O(heap) to compute,
  which is why it rides the sample cadence, not the tick path.  Off
  by default via ``gc_objects=False`` for latency-sensitive runs.
- ``live`` / ``cumulative`` — the load axes the growth sentinel
  regresses against (live in-flight requests; sessions ever served).
- ``tick_wall_ms_mean`` — mean host wall per tick over the window
  since the previous sample, fed by ``tick(wall_s=...)``: the
  per-tick host-wall series for the scaling fit.
- optional ``tracemalloc`` top allocation sites every
  ``tracemalloc_every`` samples (0 = never start tracemalloc).
"""

from __future__ import annotations

import gc
import time
from collections import deque
from typing import List, Optional, Tuple

from .census import Decl

__all__ = ["ResourceMonitor", "NULL_MONITOR", "rss_mib"]

_PAGE_KIB = 1024.0


def _rss_proc_kib() -> Optional[float]:
    try:
        with open("/proc/self/status", "r") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return float(line.split()[1])
    except (OSError, ValueError, IndexError):
        return None
    return None


def _rss_rusage_kib() -> Optional[float]:
    try:
        import resource

        # Linux reports ru_maxrss in KiB; macOS in bytes. Either way it
        # is a high-water mark, not the current RSS.
        val = float(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss)
        import sys

        return val / 1024.0 if sys.platform == "darwin" else val
    except Exception:
        return None


def rss_mib() -> Tuple[float, str]:
    """Current resident set in MiB, plus which source produced it."""
    kib = _rss_proc_kib()
    if kib is not None:
        return kib / _PAGE_KIB, "proc"
    kib = _rss_rusage_kib()
    if kib is not None:
        return kib / _PAGE_KIB, "rusage_peak"
    return 0.0, "none"


class ResourceMonitor:
    """Samples host resources every ``every_ticks`` ticks.

    Call ``tick(live=..., cumulative=..., wall_s=...)`` once per
    scheduler/router step; it returns the sample record on sampling
    ticks and ``None`` otherwise.  ``sample()`` forces one immediately
    (used at soak start/end so the fit has endpoints).
    """

    def __init__(self, metrics_log=None, *, every_ticks: int = 256,
                 gc_objects: bool = True, tracemalloc_every: int = 0,
                 top_sites: int = 5, history: int = 4096,
                 enabled: bool = True):
        self.metrics_log = metrics_log
        self.every_ticks = max(1, int(every_ticks))
        self.gc_objects = bool(gc_objects)
        self.tracemalloc_every = int(tracemalloc_every)
        self.top_sites = int(top_sites)
        self.enabled = bool(enabled)
        self.ticks = 0
        self.samples = 0
        # (cumulative, rss_mib, tick_wall_ms_mean) per sample — the
        # growth sentinel's input; ring-bounded so the monitor itself
        # passes its own census.
        self.history: deque = deque(maxlen=history)
        self._wall_sum = 0.0
        self._wall_n = 0
        self._tm_started = False

    # -- census ----------------------------------------------------------
    def census_decls(self) -> List[Decl]:
        return [
            Decl("history", "fixed", cap=lambda m: m.history.maxlen,
                 why="deque(maxlen): fixed ring of (cumulative, rss, wall) "
                     "samples for slope fitting"),
        ]

    # -- sampling --------------------------------------------------------
    def tick(self, *, live: int = 0, cumulative: int = 0,
             wall_s: Optional[float] = None) -> Optional[dict]:
        if not self.enabled:
            return None
        self.ticks += 1
        if wall_s is not None:
            self._wall_sum += float(wall_s)
            self._wall_n += 1
        if self.ticks % self.every_ticks:
            return None
        return self.sample(live=live, cumulative=cumulative)

    def sample(self, *, live: int = 0, cumulative: int = 0) -> dict:
        rss, source = rss_mib()
        wall_ms = (1000.0 * self._wall_sum / self._wall_n
                   if self._wall_n else None)
        self._wall_sum, self._wall_n = 0.0, 0
        rec = {
            "kind": "resource",
            "tick": self.ticks,
            "rss_mib": round(rss, 3),
            "rss_source": source,
            "live": int(live),
            "cumulative": int(cumulative),
        }
        if wall_ms is not None:
            rec["tick_wall_ms_mean"] = round(wall_ms, 4)
        if self.gc_objects:
            rec["gc_objects"] = len(gc.get_objects())
            rec["gc_counts"] = list(gc.get_count())
        self.samples += 1
        if self.tracemalloc_every > 0:
            rec.update(self._tracemalloc_sites())
        self.history.append((int(cumulative), rss, wall_ms))
        if self.metrics_log is not None:
            self.metrics_log.log(**rec)
        return rec

    def _tracemalloc_sites(self) -> dict:
        import tracemalloc

        if not self._tm_started:
            # Start lazily on the first sampling tick so the monitor's
            # construction cost is zero when tracemalloc is unwanted.
            tracemalloc.start(1)
            self._tm_started = True
            return {}
        if self.samples % self.tracemalloc_every:
            return {}
        t0 = time.perf_counter()
        snap = tracemalloc.take_snapshot()
        stats = snap.statistics("lineno")[: self.top_sites]
        sites = [{"site": str(s.traceback[0]), "kib": round(s.size / 1024, 1),
                  "count": s.count} for s in stats]
        return {"tracemalloc_top": sites,
                "tracemalloc_snapshot_ms":
                    round(1000 * (time.perf_counter() - t0), 2)}

    def close(self) -> None:
        if self._tm_started:
            import tracemalloc

            tracemalloc.stop()
            self._tm_started = False

    # Series accessors for the growth sentinel -----------------------
    def rss_series(self) -> Tuple[List[float], List[float]]:
        xs = [h[0] for h in self.history]
        ys = [h[1] for h in self.history]
        return xs, ys

    def wall_series(self) -> Tuple[List[float], List[float]]:
        pts = [(h[0], h[2]) for h in self.history if h[2] is not None]
        return [p[0] for p in pts], [p[1] for p in pts]


NULL_MONITOR = ResourceMonitor(enabled=False)
