"""The warmup runtime: compile a registry's programs before they stall.

``WarmupRunner`` walks a ``ProgramRegistry`` in priority order and forces
each program compiled via its ``warm`` thunk:

- **priority 0** specs (decode tick, smallest prefill bucket, trainer
  steps) compile in the FOREGROUND, with ``execute=True`` — serving
  programs run once with inert inputs, so their jit call path is hot and
  the first real request pays nothing;
- remaining specs compile in a background thread (``background=True``)
  with ``execute=False`` — AOT lower+compile only, which is safe
  concurrently with live traffic (no donated-buffer execution) and
  populates the persistent compilation cache so the first real use of a
  large bucket pays a disk load, not an XLA compile. ``wait()`` joins.

Every compile is a ``program.load`` span in the process's span stream
(``aot.program_load``), adds its wall time to the goodput ledger's
``compile`` category (foreground only — background compiles don't stall
the run), and
appends one ``kind="warmup"`` manifest record (program, seconds,
``cache_hit`` from jax's persistent-cache monitoring events, fingerprint,
priority, background) to a ``MetricsLogger`` JSONL —
``scripts/telemetry_report.py`` renders these, and
``scripts/ci_check.sh --warmup-smoke`` gates on a warm run reporting
hits.
"""

from __future__ import annotations

import threading
import time
from typing import List, Optional

from pytorch_distributed_tpu.compilecache.aot import program_load
from pytorch_distributed_tpu.compilecache.registry import (
    ProgramRegistry,
    ProgramSpec,
)


class WarmupRunner:
    """Drives one registry through compilation; reusable stats object."""

    def __init__(self, registry: ProgramRegistry, *, ledger=None,
                 manifest=None):
        self.registry = registry
        self.ledger = ledger
        self.manifest = manifest  # a MetricsLogger (or None)
        self.records: List[dict] = []
        self._records_lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None

    def run(self, background: bool = True) -> "WarmupRunner":
        """Compile everything: priority <= 0 foreground (executed inert
        where the spec allows), the rest on a daemon thread when
        ``background`` — call ``wait()`` to join, or just start serving:
        the background portion only ever touches programs traffic hasn't
        reached yet, and a bucket traffic reaches first simply compiles
        on demand (the registry still predicted it)."""
        specs = sorted(self.registry, key=lambda s: s.priority)
        if background:
            fg = [s for s in specs if s.priority <= 0]
            bg = [s for s in specs if s.priority > 0]
        else:
            fg, bg = specs, []
        for spec in fg:
            self._compile_one(spec, execute=True, foreground=True)
        if bg:
            self._thread = threading.Thread(
                target=self._compile_batch, args=(bg,),
                name="compilecache-warmup", daemon=True,
            )
            self._thread.start()
        return self

    def wait(self, timeout: Optional[float] = None) -> None:
        if self._thread is not None:
            self._thread.join(timeout)

    def _compile_batch(self, specs: List[ProgramSpec]) -> None:
        for spec in specs:
            self._compile_one(spec, execute=False, foreground=False)

    def _compile_one(self, spec: ProgramSpec, *, execute: bool,
                     foreground: bool) -> None:
        t0 = time.perf_counter()
        with program_load(spec.name) as load:
            spec.warm(execute)
        seconds = time.perf_counter() - t0
        backend_s = min(load.compile_s, seconds)
        if foreground and self.ledger is not None:
            # split: "compile" is the XLA backend portion (collapses to a
            # disk load on a warm start), "trace" the Python residual
            self.ledger.add("compile", backend_s)
            self.ledger.add("trace", max(seconds - backend_s, 0.0))
        record = {
            "program": spec.name,
            "seconds": round(seconds, 6),
            "backend_compile_s": round(backend_s, 6),
            "cache_hit": load.cache_hit,
            "fingerprint": self.registry.fingerprint,
            "priority": spec.priority,
            "background": not foreground,
        }
        with self._records_lock:
            self.records.append(record)
        if self.manifest is not None:
            self.manifest.log(kind="warmup", **record)

    def summary(self) -> dict:
        """Aggregate over the records emitted so far (call ``wait()``
        first for a complete background picture)."""
        with self._records_lock:
            records = list(self.records)
        return {
            "programs": len(records),
            "cache_hits": sum(1 for r in records if r["cache_hit"]),
            "fresh": sum(1 for r in records if not r["cache_hit"]),
            "total_s": round(sum(r["seconds"] for r in records), 6),
            "backend_compile_s": round(
                sum(r["backend_compile_s"] for r in records), 6
            ),
            "fingerprint": self.registry.fingerprint,
        }
