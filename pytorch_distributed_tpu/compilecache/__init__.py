"""Cold-start elimination: AOT program registry, persistent compile
cache, warmup runtime.

The telemetry runtime (telemetry/) classifies "compile" as a first-class
goodput loss; this package is the machinery that REDUCES it. A run
enumerates every compiled program it will need (``registry``), compiles
them ahead of traffic in priority order (``warmup``), and persists the
executables across process restarts (``aot``) — so a preempted-and-
resumed trainer or a freshly launched server reaches full speed with a
near-zero compile fraction, and the first request into each serving
bucket never eats a multi-second mid-traffic stall.

ANALYSIS.md "Cold start & compile cache" documents fingerprint keying,
corruption fall-through, and warmup ordering; ``scripts/warmup.py`` is
the CLI, ``scripts/bench_coldstart.py`` the cold-vs-warm proof.
"""

from pytorch_distributed_tpu.compilecache.aot import (
    CacheHitCounter,
    enable_persistent_cache,
    export_program,
    load_exported,
    persistent_cache_dir,
    process_compile_totals,
    save_exported,
)
from pytorch_distributed_tpu.compilecache.registry import (
    CoverageError,
    ProgramRegistry,
    ProgramSpec,
    aot_spec,
    jit_cache_size,
    run_fingerprint,
    serving_registry,
)
from pytorch_distributed_tpu.compilecache.warmup import WarmupRunner

__all__ = [
    "CacheHitCounter",
    "CoverageError",
    "ProgramRegistry",
    "ProgramSpec",
    "WarmupRunner",
    "aot_spec",
    "enable_persistent_cache",
    "export_program",
    "jit_cache_size",
    "load_exported",
    "persistent_cache_dir",
    "process_compile_totals",
    "run_fingerprint",
    "save_exported",
    "serving_registry",
]
