"""Program registry: every compiled program a run needs, enumerated AHEAD
of execution.

The jit caches this repo guards (``analysis.guards.no_recompile``) answer
"did anything compile that shouldn't have?" *after* the fact. The registry
answers the dual question up front: given the configs a run already holds
(``TrainerConfig``/``LMTrainerConfig`` + model config + mesh, or a
``PagedEngine``'s slot/block/chunk geometry), list every program the run
will execute — train step, eval step(s), one chunk-prefill program per
(job-count, table-width) bucket, the decode tick — so that

- the **warmup runtime** (``compilecache.warmup``) can compile all of them
  before traffic / training starts, in priority order;
- the **coverage guard** (``ProgramRegistry.assert_covers``) can fail the
  run when a compiled program appears that no registry entry predicted —
  the registry provably covers what actually executes, the same
  build-real-trees-and-cross-check discipline as
  ``analysis/partition_coverage.py``;
- AOT artifacts (``compilecache.aot``) can be keyed by a stable
  **fingerprint** (jax/jaxlib version, backend, device kind, mesh shape,
  config extras) so a stale cache entry from a different environment is a
  miss, never a wrong program.

Specs carry a ``warm(execute)`` thunk — the strongest safe way to force
that program compiled. Serving programs can *execute* with inert inputs
(writes routed to the trash block; see ``PagedEngine.warm_chunk``), which
populates the jit call path itself: zero residual stall. Trainer steps
must not execute (a dummy step would corrupt training state), so their
thunks AOT-compile via ``jit(...).lower(...).compile()`` — which populates
the persistent compilation cache (``compilecache.aot``), making the real
first dispatch a disk hit instead of a fresh XLA compile.
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Callable, Dict, Iterable, Iterator, List, Optional


class CoverageError(AssertionError):
    """A compiled program exists that no registry entry predicted."""


@dataclasses.dataclass(frozen=True)
class ProgramSpec:
    """One compiled program a run will need.

    ``warm(execute)`` forces the program compiled; ``execute=True`` permits
    running it with inert inputs (only safe before/outside traffic — the
    caller decides), ``execute=False`` restricts the thunk to AOT
    lower+compile (safe concurrently; populates the persistent cache but
    not the jit call path). Thunks that cannot execute safely ignore the
    flag and always AOT-compile.

    ``expect_entries`` is the number of live jit-cache entries this
    program may legitimately hold (1 for a steady-state step; the eval
    step of a non-drop_last loader may hold one per distinct batch shape);
    ``cache_probe`` returns the live count when the program is backed by a
    single jit callable (None when it is not observable that way).

    ``aot`` (ISSUE 8, cost cards) returns the program's ``jax.stages.
    Compiled`` — ``lower(...).compile()`` at the spec's real avals — so
    ``telemetry.costmodel`` can pull ``cost_analysis()`` /
    ``memory_analysis()`` for every enumerated program. Calling it pays
    a trace + compile (a disk hit under ``enable_persistent_cache``);
    card builders invoke it on demand, off the hot path.
    """

    name: str
    warm: Callable[[bool], None]
    priority: int = 1  # 0 = serve-critical: compiled first, foreground
    expect_entries: int = 1
    cache_probe: Optional[Callable[[], Optional[int]]] = None
    aot: Optional[Callable[[], object]] = None


class ProgramRegistry:
    """Ordered, name-unique collection of ``ProgramSpec`` entries plus the
    run fingerprint that keys their AOT artifacts."""

    def __init__(self, fingerprint: str = ""):
        self.fingerprint = fingerprint
        self._specs: Dict[str, ProgramSpec] = {}

    def add(self, spec: ProgramSpec) -> ProgramSpec:
        if spec.name in self._specs:
            raise ValueError(f"duplicate program spec {spec.name!r}")
        self._specs[spec.name] = spec
        return spec

    def __iter__(self) -> Iterator[ProgramSpec]:
        return iter(self._specs.values())

    def __len__(self) -> int:
        return len(self._specs)

    @property
    def names(self) -> List[str]:
        return list(self._specs)

    def predicts(self, name: str) -> bool:
        return name in self._specs

    # ---- the coverage guard ----

    def assert_covers(self, observed: Iterable[str]) -> None:
        """Fail if ``observed`` contains a program (or more live cache
        entries of one) that the registry did not predict.

        ``observed`` is the run's live program inventory — e.g.
        ``PagedEngine.compiled_program_names()`` or a trainer's
        ``compiled_program_names()`` — with one element per live jit-cache
        entry, so multiplicity is checked too: a predicted program that
        retraced past its ``expect_entries`` budget is a coverage failure
        (that's a recompile the registry's enumeration didn't account
        for), same spirit as ``no_recompile``'s cache-growth check.
        """
        counts: Dict[str, int] = {}
        for name in observed:
            counts[name] = counts.get(name, 0) + 1
        unpredicted = sorted(n for n in counts if n not in self._specs)
        if unpredicted:
            raise CoverageError(
                f"compiled program(s) outside the registry: {unpredicted} "
                f"— the registry enumerated {sorted(self._specs)}; either "
                "the enumeration is missing a bucket/config variant or "
                "the run compiled something it was never meant to"
            )
        over = sorted(
            f"{n} ({c} entries > {self._specs[n].expect_entries} expected)"
            for n, c in counts.items()
            if c > self._specs[n].expect_entries
        )
        if over:
            raise CoverageError(
                f"program(s) retraced past their registry budget: {over} "
                "— shape/dtype drift compiled extra variants the registry "
                "did not predict"
            )


# ---------------------------------------------------------------------------
# fingerprints
# ---------------------------------------------------------------------------


def run_fingerprint(mesh=None, extra: Iterable = ()) -> str:
    """Stable hex key for the environment a compiled artifact is valid in.

    Folds in: jax + jaxlib versions, backend platform and device kind,
    device count, mesh axis names/sizes, and any caller extras (config
    reprs, dtypes, flags). Two runs agree on the fingerprint iff their
    artifacts are interchangeable; everything else is a cache miss by
    construction — stale artifacts can never load as wrong programs.
    """
    import jax
    import jaxlib

    parts = [
        f"jax={jax.__version__}",
        f"jaxlib={jaxlib.__version__}",
    ]
    try:
        devices = jax.devices()
        parts.append(f"backend={jax.default_backend()}")
        parts.append(f"device_kind={devices[0].device_kind}")
        parts.append(f"n_devices={len(devices)}")
    except Exception:  # uninitialized backend: version-only fingerprint
        parts.append("backend=uninitialized")
    if mesh is not None:
        parts.append(f"mesh={tuple(sorted(dict(mesh.shape).items()))}")
    for item in extra:
        parts.append(repr(item))
    return hashlib.sha256("|".join(parts).encode()).hexdigest()[:16]


# ---------------------------------------------------------------------------
# builders
# ---------------------------------------------------------------------------


def jit_cache_size(fn) -> Optional[int]:
    """Live jit-cache entry count of a ``jax.jit`` callable (None when the
    object carries no probe) — the same probe ``no_recompile`` watches."""
    probe = getattr(fn, "_cache_size", None)
    if callable(probe):
        try:
            return int(probe())
        except Exception:
            return None
    return None


def aot_spec(
    name: str,
    jit_fn,
    avals_thunk: Callable[[], tuple],
    *,
    priority: int = 1,
    expect_entries: int = 1,
) -> ProgramSpec:
    """Spec for a program that must NOT execute during warmup (trainer
    steps): ``warm`` AOT-compiles via ``lower(*avals).compile()``, which
    feeds the persistent compilation cache so the real first call is a
    disk hit. ``avals_thunk`` is lazy — avals (ShapeDtypeStructs carrying
    the REAL shardings, or live arrays) are built only if warmup runs."""

    def warm(execute: bool) -> None:  # execute ignored: AOT only
        jit_fn.lower(*avals_thunk()).compile()

    return ProgramSpec(
        name=name,
        warm=warm,
        priority=priority,
        expect_entries=expect_entries,
        cache_probe=lambda: jit_cache_size(jit_fn),
        aot=lambda: jit_fn.lower(*avals_thunk()).compile(),
    )


def serving_registry(engine, extra: Iterable = ()) -> ProgramRegistry:
    """Enumerate every program a ``PagedEngine`` can compile: one
    chunk-prefill program per (padded job count, table-slice width)
    bucket — the same pow2 bucketing ``run_chunks`` applies, read from
    ``engine.chunk_buckets()`` so registry and engine cannot drift — plus
    the shared decode tick.

    Priority order: the decode tick and the smallest prefill bucket are
    priority 0 (serve-critical — with them compiled the scheduler can
    admit and stream its first request), every larger bucket priority 1
    so a warmup runner can finish them in the background while serving
    has already started.
    """
    # what the two rules that choose a paged read answer for this
    # engine's chunk programs and its decode tick (imported here: the
    # trainers import this module and run none of it)
    from pytorch_distributed_tpu.ops import attention, paged_flash

    cfg = engine.config
    group = cfg.num_heads // (cfg.num_kv_heads or cfg.num_heads)
    chunk_read = attention.default_gather_impl(group * engine.chunk)
    split = paged_flash.auto_split_s(engine.table_width, engine.n_slots)
    reg = ProgramRegistry(
        run_fingerprint(
            mesh=engine.mesh,
            extra=(
                engine.config,
                f"n_slots={engine.n_slots}",
                f"block_len={engine.block_len}",
                f"chunk={engine.chunk}",
                f"temperature={engine.temperature}",
                f"top_k={engine.top_k}",
                # program-shape variants: the read each program family
                # compiles and the tick's flash-decoding workers (an
                # artifact built under a steered rule, as tests build
                # them, never loads under another); the pool
                # quantization changes every program's cache avals, so
                # artifacts must not be interchangeable across it
                f"read={engine.gather_impl}/{chunk_read}",
                f"split={split}",
                f"kv_dtype={getattr(engine, 'kv_dtype', None)}",
                f"prefix_cache={getattr(engine, 'prefix_cache', False)}",
                *extra,
            ),
        )
    )
    reg.add(ProgramSpec(
        name=engine.DECODE_PROGRAM,
        warm=lambda execute: engine.warm_decode(execute=execute),
        priority=0,
        aot=lambda: engine.warm_decode(execute=False),
    ))
    buckets = engine.chunk_buckets()
    smallest = min(buckets) if buckets else None
    for k_pad, wp in buckets:
        reg.add(ProgramSpec(
            name=engine.chunk_program_name(k_pad, wp),
            warm=(lambda execute, k=k_pad, w=wp:
                  engine.warm_chunk(k, w, execute=execute)),
            priority=0 if (k_pad, wp) == smallest else 1,
            aot=(lambda k=k_pad, w=wp:
                 engine.warm_chunk(k, w, execute=False)),
        ))
    # fleet disaggregation handoff programs (empty unless the engine was
    # built with handoff=True — read from the engine for the same
    # no-drift reason as chunk_buckets)
    for n_pad in engine.handoff_buckets():
        reg.add(ProgramSpec(
            name=engine.export_program_name(n_pad),
            warm=(lambda execute, n=n_pad:
                  engine.warm_export(n, execute=execute)),
            aot=lambda n=n_pad: engine.warm_export(n, execute=False),
        ))
        reg.add(ProgramSpec(
            name=engine.import_program_name(n_pad),
            warm=(lambda execute, n=n_pad:
                  engine.warm_import(n, execute=execute)),
            aot=lambda n=n_pad: engine.warm_import(n, execute=False),
        ))
    # copy-on-write block duplication (round 17 prefix sharing; absent
    # unless the engine was built with prefix_cache=True — same gating
    # story as handoff/swap). ONE program: a block copy has no chain-
    # length bucketing, and only the full-cover hit path runs it.
    if getattr(engine, "prefix_cache", False):
        reg.add(ProgramSpec(
            name=engine.BLOCK_COPY_PROGRAM,
            warm=lambda execute: engine.warm_block_copy(execute=execute),
            aot=lambda: engine.warm_block_copy(execute=False),
        ))
    # host-offload swap programs (round 13 pressure tier; empty unless
    # the engine was built with swap=True — read from the engine so the
    # registry and the swap path's lazy bucketing cannot drift)
    for n_pad in engine.swap_buckets():
        reg.add(ProgramSpec(
            name=engine.swap_out_program_name(n_pad),
            warm=(lambda execute, n=n_pad:
                  engine.warm_swap_out(n, execute=execute)),
            aot=lambda n=n_pad: engine.warm_swap_out(n, execute=False),
        ))
        reg.add(ProgramSpec(
            name=engine.swap_in_program_name(n_pad),
            warm=(lambda execute, n=n_pad:
                  engine.warm_swap_in(n, execute=execute)),
            aot=lambda n=n_pad: engine.warm_swap_in(n, execute=False),
        ))
    return reg
