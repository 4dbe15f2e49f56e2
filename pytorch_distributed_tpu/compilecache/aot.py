"""AOT compilation artifacts: the persistent XLA cache + ``jax.export``.

Two complementary layers, both keyed so stale entries are misses rather
than hazards:

- **persistent compilation cache** (``enable_persistent_cache``): jax's
  on-disk executable cache (``jax_compilation_cache_dir``), tuned so
  every program qualifies (the default 1 s minimum-compile-time floor
  would skip exactly the small programs our tests exercise). The cache
  key is XLA's — serialized HLO + compile options + backend — so a warm
  process re-running the same code path loads executables from disk
  instead of recompiling: the mechanism that collapses a resumed
  trainer's / relaunched server's compile fraction. ``CacheHitCounter``
  observes jax's own ``/jax/compilation_cache/cache_hits`` monitoring
  events (per-thread, so a background warmup thread can't cross-count a
  foreground compile) and is how the warmup manifest distinguishes
  ``cache`` from ``fresh``.
- **exported-program artifacts** (``save_exported``/``load_exported``):
  ``jax.export`` serializations of individual programs, stored under
  ``<cache_dir>/aot/<name>-<fingerprint>.jaxexport`` with an atomic
  tmp+rename write. Load is corruption-safe by contract: a truncated,
  garbage, or version-incompatible artifact logs a warning and returns
  ``None`` — the caller falls through to a fresh compile, never crashes
  (the same discipline as ``Checkpointer.restorable_paths`` scanning past
  torn checkpoints).
"""

from __future__ import annotations

import contextlib
import logging
import os
import threading
import time
from typing import Dict, Optional

from pytorch_distributed_tpu.telemetry import spans

logger = logging.getLogger("pytorch_distributed_tpu")

#: jax monitoring event recorded on every persistent-cache executable hit.
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
#: jax monitoring duration recorded around every XLA backend compile —
#: on a persistent-cache hit this wraps the (fast) disk load instead of
#: the compile, so it is THE honest "compile seconds" measure: it
#: collapses on a warm start while Python tracing/lowering time does not.
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_hit_counts: Dict[int, int] = {}
_compile_secs: Dict[int, float] = {}
_listener_lock = threading.Lock()
_listener_installed = False


def _reset_jax_cache_state() -> None:
    """Drop jax's lazily-initialized compilation-cache singleton so the
    NEXT compile re-reads ``jax_compilation_cache_dir``. jax binds the
    cache object on first use — without this, enabling (or re-pointing)
    the directory in a process that already compiled something is a
    silent no-op."""
    from jax.experimental.compilation_cache import compilation_cache

    compilation_cache.reset_cache()


def enable_persistent_cache(cache_dir: str) -> str:
    """Point jax's persistent compilation cache at ``cache_dir``.

    Sets the three knobs that matter: the directory itself, and the two
    size/time floors dropped to "cache everything" (tiny CPU test
    programs compile in milliseconds and would otherwise never be
    written, making warm-start untestable off-TPU). Safe to call more
    than once; later calls re-point the directory (the cache singleton
    is reset so the change takes effect even after compiles have
    happened). Returns the dir.
    """
    import jax

    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _reset_jax_cache_state()
    return cache_dir


def persistent_cache_dir() -> Optional[str]:
    """The active persistent-cache directory, or None when disabled."""
    import jax

    return getattr(jax.config, "jax_compilation_cache_dir", None)


def _install_listener() -> None:
    global _listener_installed
    with _listener_lock:
        if _listener_installed:
            return
        import jax.monitoring

        def _on_event(name: str, **kwargs) -> None:
            if name == _CACHE_HIT_EVENT:
                ident = threading.get_ident()
                with _listener_lock:
                    _hit_counts[ident] = _hit_counts.get(ident, 0) + 1

        def _on_duration(name: str, duration_secs: float, **kwargs) -> None:
            if name == _BACKEND_COMPILE_EVENT:
                ident = threading.get_ident()
                with _listener_lock:
                    _compile_secs[ident] = (
                        _compile_secs.get(ident, 0.0) + duration_secs
                    )

        # registered once per process and never cleared:
        # jax.monitoring.clear_event_listeners would nuke listeners we
        # don't own, so counters scope by thread + start offset instead
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listener_installed = True


def process_compile_totals() -> tuple:
    """``(persistent-cache hits, backend-compile seconds)`` summed over
    every thread since the listener went in (the first call installs
    it) — for a caller whose compiles happen on threads it does not own
    (a gateway's driver thread), where the per-thread counters below
    cannot see them."""
    _install_listener()
    with _listener_lock:
        return sum(_hit_counts.values()), sum(_compile_secs.values())


class CacheHitCounter:
    """Context manager counting persistent-cache hits on THIS thread.

    ``with CacheHitCounter() as c: compile_something()`` then ``c.hits``.
    Per-thread scoping means a foreground warmup and a background warmup
    thread each see only their own compiles' hits.
    """

    def __enter__(self) -> "CacheHitCounter":
        _install_listener()
        self._ident = threading.get_ident()
        with _listener_lock:
            self._start = _hit_counts.get(self._ident, 0)
        self.hits = 0
        return self

    def __exit__(self, *exc) -> None:
        with _listener_lock:
            self.hits = _hit_counts.get(self._ident, 0) - self._start


class BackendCompileTimer:
    """Context manager accumulating XLA backend-compile seconds on THIS
    thread (``/jax/core/compile/backend_compile_duration`` events). On a
    persistent-cache hit the event wraps the disk load, so ``seconds``
    is exactly the quantity a warm start collapses."""

    def __enter__(self) -> "BackendCompileTimer":
        _install_listener()
        self._ident = threading.get_ident()
        with _listener_lock:
            self._start = _compile_secs.get(self._ident, 0.0)
        self.seconds = 0.0
        return self

    def __exit__(self, *exc) -> None:
        with _listener_lock:
            self.seconds = _compile_secs.get(self._ident, 0.0) - self._start


class program_load:
    """The ``program.load`` span around the first compile (or load from
    the persistent cache) of the program ``name``: the one place a layer
    says "this call may stall for a program". After the block
    ``cache_hit`` and ``compile_s`` (this thread's backend-compile
    seconds: a disk load when the cache hit) are attributes, and the
    span's args. A load opened inside a load on the same
    thread (a registry thunk that calls the engine's ``warm_*``) is the
    same load: it measures, and records no second span."""

    def __init__(self, name: str):
        self.name = name
        self.cache_hit = False
        self.compile_s = 0.0

    def __enter__(self) -> "program_load":
        tr = spans.tracer()
        outer = tr.current()
        nested = outer is not None and outer.name == "program.load"
        self._hits = CacheHitCounter().__enter__()
        self._timer = BackendCompileTimer().__enter__()
        self._span = None if nested else tr.span(
            "program.load", program=self.name).__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        self._timer.__exit__(*exc)
        self._hits.__exit__(*exc)
        self.cache_hit = self._hits.hits > 0
        self.compile_s = self._timer.seconds
        if self._span is None:
            return False
        self._span.args.update(cache_hit=self.cache_hit,
                               compile_s=self.compile_s)
        return self._span.__exit__(*exc)


_HOT = contextlib.nullcontext()  # reusable: it holds no state


def program_load_if(cold: bool, name: str):
    """``program_load(name)`` around a call whose program has never run on
    this call path (it compiles, or loads from the persistent cache);
    nothing once the path is hot."""
    return program_load(name) if cold else _HOT


@contextlib.contextmanager
def attribute_compile(ledger):
    """Bracket a possibly-compiling call, splitting its wall time into
    the goodput ledger's ``compile`` (XLA backend compile / cache load —
    what a populated persistent cache eliminates) and ``trace`` (the
    Python tracing + lowering residual, which no disk cache can remove).
    ``ledger=None`` is a no-op bracket — call sites don't need a guard.
    """
    if ledger is None:
        yield
        return
    t0 = time.perf_counter()
    with BackendCompileTimer() as bc:
        yield
    wall = time.perf_counter() - t0
    compile_s = min(bc.seconds, wall)
    ledger.add("compile", compile_s)
    ledger.add("trace", max(wall - compile_s, 0.0))


# ---------------------------------------------------------------------------
# exported-program artifacts (jax.export)
# ---------------------------------------------------------------------------


def _safe_name(name: str) -> str:
    return "".join(c if (c.isalnum() or c in "._-") else "_" for c in name)


def artifact_path(cache_dir: str, name: str, fingerprint: str) -> str:
    """``<cache_dir>/aot/<name>-<fingerprint>.jaxexport`` — the
    fingerprint in the filename is the staleness gate: a different
    environment looks for a different file and simply misses."""
    return os.path.join(
        cache_dir, "aot", f"{_safe_name(name)}-{fingerprint}.jaxexport"
    )


def export_program(jit_fn, *avals):
    """Trace + lower ``jit_fn`` at ``avals`` into a serializable
    ``jax.export.Exported`` (no execution)."""
    from jax import export

    return export.export(jit_fn)(*avals)


def save_exported(cache_dir: str, name: str, fingerprint: str,
                  exported) -> str:
    """Serialize an ``Exported`` to its artifact path atomically
    (tmp + ``os.replace``: a concurrent reader sees the old file or the
    new one, never a torn write). Returns the path."""
    path = artifact_path(cache_dir, name, fingerprint)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    blob = exported.serialize()
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        f.write(blob)
    os.replace(tmp, path)
    return path


def load_exported(cache_dir: str, name: str, fingerprint: str):
    """Deserialize the artifact for (name, fingerprint), or ``None``.

    NEVER raises for a bad artifact: a missing file is a plain miss; a
    truncated/garbage/incompatible blob logs a warning naming the file
    and also returns ``None`` so the caller falls through to a fresh
    compile — a corrupt cache must cost a recompile, not a crash.
    """
    from jax import export

    path = artifact_path(cache_dir, name, fingerprint)
    try:
        with open(path, "rb") as f:
            blob = f.read()
    except FileNotFoundError:
        return None
    except OSError as e:
        logger.warning("compilecache: unreadable artifact %s (%s); "
                       "falling through to fresh compile", path, e)
        return None
    try:
        return export.deserialize(blob)
    except Exception as e:  # any deserialize failure = corrupt/stale
        logger.warning("compilecache: corrupt/stale artifact %s (%s); "
                       "falling through to fresh compile", path, e)
        return None
