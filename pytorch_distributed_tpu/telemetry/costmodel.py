"""Per-program cost cards: FLOP/byte accounting joined with measured time.

PR 4's telemetry answers *what* a run spent its wall on; this module
answers *why* a program takes the time it takes. For every program the
``compilecache.ProgramRegistry`` enumerates, a **cost card** records the
compiler's own static accounting — FLOPs and bytes accessed from
``Compiled.cost_analysis()``, argument/output/temp bytes from
``memory_analysis()`` — and, once the run has measured wall time for the
program (scheduler tick spans, trainer epoch timing), joins the two into
achieved FLOP/s, achieved HBM bandwidth, MFU against the device's peak,
and a compute-vs-bandwidth **roofline classification**: a program whose
arithmetic intensity (FLOP/B) sits below the device ridge point
(peak FLOP/s over peak B/s) cannot be compute-bound no matter how well it
is scheduled — exactly the analysis PERF_NOTES.md §4/§7 did by hand for
the ResNet step, now produced by the runtime for every program
(generalizing the one-off ``scripts/exp_resnet_roofline.py``).

Caveats, stated on the card rather than hidden:

- XLA's ``bytes accessed`` double-counts fused intermediates and
  aliased (donated) operands (PERF_NOTES §9 measured 40.6 GB reported
  vs 23.3 GB real HBM traffic). Round 20 subtracts the part the
  compiler itself reports — ``memory_analysis().alias_size_in_bytes``,
  the donated-operand overlap counted once as an argument and again as
  an output — into ``bytes_accessed_dedup``, which all derived rates
  (intensity, achieved GB/s, hbm_frac, the roofline bound) now use.
  The raw ``bytes_accessed`` stays on the card for comparability. The
  fusion share of the double-count is not separable from the analysis,
  so deduped GB/s is still an upper bound on real traffic — fine for
  *classification* (a program the metric calls bandwidth-bound is), a
  smaller overestimate for absolute bandwidth.
- Measured seconds are host wall around the dispatch (the spans the run
  already records). Programs whose results the caller materializes
  (decode tick, epoch-synced train steps) are honest; pure-dispatch
  spans under-report on async backends — the card carries ``calls`` so a
  reader can judge the join.

Ceilings come from ``device_ceilings()``: env overrides
``PDT_PEAK_FLOPS`` (FLOP/s) / ``PDT_PEAK_GBS`` (GB/s) first, then a
small builtin table of measured numbers (the v5e entries are this repo's
own measurements, PERF_NOTES §2/§7). Unknown device → no MFU/bound
columns, but the card (and achieved rates) still emit: attribution
degrades, never crashes.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Callable, Dict, List, Optional, Tuple

#: (peak FLOP/s, peak bytes/s) per jax device kind. v5e compute is the
#: bf16 datasheet peak (the MFU convention); bandwidth is the MEASURED
#: streaming ceiling (PERF_NOTES §7: 657 GB/s triad vs 819 datasheet) —
#: roofline fractions against what the chip actually streams.
DEVICE_CEILINGS: Dict[str, Tuple[float, float]] = {
    "TPU v5 lite": (197e12, 657e9),
    "TPU v5e": (197e12, 657e9),
    "TPU v4": (275e12, 1228e9),
}


def device_ceilings(device_kind: Optional[str] = None):
    """``(peak_flops, peak_bytes_s)`` for the active device, or
    ``(None, None)`` when unknown. Env ``PDT_PEAK_FLOPS`` /
    ``PDT_PEAK_GBS`` override both the table and the unknown case — the
    knob CI uses to render full roofline tables on the CPU backend."""
    flops = os.environ.get("PDT_PEAK_FLOPS")
    gbs = os.environ.get("PDT_PEAK_GBS")
    if flops or gbs:
        return (
            float(flops) if flops else None,
            float(gbs) * 1e9 if gbs else None,
        )
    if device_kind is None:
        try:
            import jax

            device_kind = jax.devices()[0].device_kind
        except Exception:
            return None, None
    return DEVICE_CEILINGS.get(device_kind, (None, None))


#: env overrides for the host↔device link (GB/s), the PDT_PEAK_* knob
#: family extended to the swap path: CI pins these to steer the
#: swap-vs-recompute decision deterministically on the CPU backend.
LINK_ENV_H2D = "PDT_PEAK_H2D_GBS"
LINK_ENV_D2H = "PDT_PEAK_D2H_GBS"

_link_cache: Optional[Tuple[float, float]] = None


def link_bandwidth(probe_mb: int = 4,
                   reps: int = 3) -> Tuple[Optional[float], Optional[float]]:
    """``(h2d_bytes_s, d2h_bytes_s)`` of the host↔device link.

    Env overrides ``PDT_PEAK_H2D_GBS``/``PDT_PEAK_D2H_GBS`` first
    (deterministic CI), else ONE measured probe per process — a
    ``probe_mb`` buffer put/get round (median of ``reps``), the in-tree
    twin of ``scripts/bench_serving.py``'s ``link_probe`` — cached
    module-global so the serve loop never re-pays it. A backend that
    cannot run the probe yields ``(None, None)``: the decision degrades
    to its stated default, never crashes."""
    global _link_cache
    h2d_env = os.environ.get(LINK_ENV_H2D)
    d2h_env = os.environ.get(LINK_ENV_D2H)
    if h2d_env and d2h_env:
        return float(h2d_env) * 1e9, float(d2h_env) * 1e9
    if _link_cache is None:
        try:
            import time

            import jax
            import numpy as np

            buf = np.ones(probe_mb << 20, np.uint8)

            def med(f):
                times = []
                for _ in range(reps):
                    t0 = time.perf_counter()
                    f()
                    times.append(time.perf_counter() - t0)
                return max(float(np.median(times)), 1e-9)

            dev = jax.block_until_ready(jax.device_put(buf))  # warm path
            h2d_s = med(
                lambda: jax.block_until_ready(jax.device_put(buf))
            )
            d2h_s = med(lambda: np.asarray(jax.device_get(dev)))
            _link_cache = (buf.nbytes / h2d_s, buf.nbytes / d2h_s)
        except Exception:
            _link_cache = (0.0, 0.0)  # probe failed: remembered as unknown
    h2d = float(h2d_env) * 1e9 if h2d_env else (_link_cache[0] or None)
    d2h = float(d2h_env) * 1e9 if d2h_env else (_link_cache[1] or None)
    return h2d, d2h


@dataclasses.dataclass(frozen=True)
class SwapDecision:
    """One preemption's swap-vs-recompute verdict, with the predicted
    costs that produced it — logged verbatim (``kind="preempt"``) so the
    crossover is auditable against measured walls after the fact."""

    choice: str  # "swap" | "recompute"
    swap_s: Optional[float]
    recompute_s: Optional[float]
    bytes_to_move: int
    chunks: int
    reason: str


def swap_vs_recompute(
    bytes_to_move: int,
    *,
    chunks: int = 0,
    chunk_wall_s: Optional[float] = None,
    h2d_bytes_s: Optional[float] = None,
    d2h_bytes_s: Optional[float] = None,
) -> SwapDecision:
    """The measured crossover (vLLM's preemption choice, with this
    repo's numbers in it): predicted swap cost is the chain's bytes
    through the MEASURED link both ways (d2h now + h2d at restore);
    predicted recompute cost is the resume-prefill's chunk count times
    the chunk program's MEASURED per-call wall (``ProgramTimes`` — the
    cost-card join, not a FLOP guess). Link rates default from
    ``link_bandwidth()`` (env-overridable). When one side is
    unmeasurable the other wins; when neither is, swap is the stated
    default (same-host d2h/h2d is cheap everywhere this repo runs;
    recompute burns accelerator FLOPs the pool is starved for)."""
    if h2d_bytes_s is None or d2h_bytes_s is None:
        h2d0, d2h0 = link_bandwidth()
        h2d_bytes_s = h2d_bytes_s if h2d_bytes_s is not None else h2d0
        d2h_bytes_s = d2h_bytes_s if d2h_bytes_s is not None else d2h0
    swap_s = (
        bytes_to_move * (1.0 / h2d_bytes_s + 1.0 / d2h_bytes_s)
        if h2d_bytes_s and d2h_bytes_s else None
    )
    recompute_s = (
        chunks * chunk_wall_s
        if chunk_wall_s is not None and chunks > 0 else None
    )
    if swap_s is None and recompute_s is None:
        choice, reason = "swap", "unmeasured-default"
    elif recompute_s is None:
        choice, reason = "swap", "recompute-unmeasured"
    elif swap_s is None:
        choice, reason = "recompute", "link-unmeasured"
    else:
        choice = "swap" if swap_s <= recompute_s else "recompute"
        reason = "measured-crossover"
    return SwapDecision(choice=choice, swap_s=swap_s,
                        recompute_s=recompute_s,
                        bytes_to_move=int(bytes_to_move), chunks=chunks,
                        reason=reason)


def extract_costs(compiled) -> dict:
    """Static cost fields from a ``jax.stages.Compiled`` (or ``Lowered``).

    ``cost_analysis()`` is one dict (jax 0.9.0). Any backend that cannot
    produce an analysis yields an empty dict: a cost card with unknown
    FLOPs is still a card."""
    out: dict = {}
    try:
        ca = compiled.cost_analysis()
        if ca:
            if ca.get("flops") is not None:
                out["flops"] = float(ca["flops"])
            if ca.get("bytes accessed") is not None:
                out["bytes_accessed"] = float(ca["bytes accessed"])
    except Exception:
        pass
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            arg = int(getattr(ma, "argument_size_in_bytes", 0))
            outb = int(getattr(ma, "output_size_in_bytes", 0))
            tmp = int(getattr(ma, "temp_size_in_bytes", 0))
            alias = int(getattr(ma, "alias_size_in_bytes", 0))
            out["argument_bytes"] = arg
            out["output_bytes"] = outb
            out["temp_bytes"] = tmp
            out["alias_bytes"] = alias
            # live working set while the program runs — the number that
            # decides whether two programs can overlap in HBM. Donated
            # operands (the pool, the logits buffer) appear in BOTH the
            # argument and output totals but occupy one allocation, so
            # the aliased overlap is subtracted once.
            out["peak_bytes"] = arg + outb + tmp - alias
    except Exception:
        pass
    return out


@dataclasses.dataclass
class CostCard:
    """One program's static cost accounting plus its measured join."""

    program: str
    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    argument_bytes: Optional[int] = None
    output_bytes: Optional[int] = None
    temp_bytes: Optional[int] = None
    alias_bytes: Optional[int] = None
    peak_bytes: Optional[int] = None
    # measured join (ProgramTimes): host wall attributed to this program
    calls: int = 0
    total_s: float = 0.0

    @property
    def bytes_accessed_dedup(self) -> Optional[float]:
        """``bytes accessed`` minus the aliased (donated) operand bytes
        XLA counted twice — the traffic figure every derived rate uses
        (PERF_NOTES §9). Floored at zero: the analysis pair comes from
        two separate compiler queries and is not guaranteed coherent."""
        if self.bytes_accessed is None:
            return None
        return max(self.bytes_accessed - (self.alias_bytes or 0), 0.0)

    @property
    def intensity(self) -> Optional[float]:
        """Arithmetic intensity, FLOP per deduped byte accessed."""
        if not self.flops or not self.bytes_accessed_dedup:
            return None
        return self.flops / self.bytes_accessed_dedup

    def record(self, peak_flops: Optional[float] = None,
               peak_bytes_s: Optional[float] = None) -> dict:
        """The flat ``kind="program_cost"`` JSONL record: statics,
        measured join, and every derived rate the ceilings allow."""
        rec: dict = {"program": self.program, "calls": self.calls}
        for k in ("flops", "bytes_accessed", "argument_bytes",
                  "output_bytes", "temp_bytes", "alias_bytes",
                  "peak_bytes"):
            v = getattr(self, k)
            if v is not None:
                rec[k] = v
        if self.bytes_accessed_dedup is not None:
            rec["bytes_accessed_dedup"] = self.bytes_accessed_dedup
        if self.intensity is not None:
            rec["intensity_flop_b"] = round(self.intensity, 3)
        if self.calls and self.total_s > 0:
            mean_s = self.total_s / self.calls
            rec["total_s"] = round(self.total_s, 6)
            rec["mean_s"] = round(mean_s, 6)
            if self.flops:
                rec["achieved_flops_s"] = self.flops / mean_s
                if peak_flops:
                    rec["mfu"] = round(self.flops / mean_s / peak_flops, 5)
            if self.bytes_accessed_dedup:
                rec["achieved_bytes_s"] = self.bytes_accessed_dedup / mean_s
                if peak_bytes_s:
                    rec["hbm_frac"] = round(
                        self.bytes_accessed_dedup / mean_s / peak_bytes_s, 5
                    )
        if peak_flops and peak_bytes_s and self.intensity is not None:
            ridge = peak_flops / peak_bytes_s
            rec["ridge_flop_b"] = round(ridge, 3)
            rec["bound"] = (
                "compute" if self.intensity >= ridge else "bandwidth"
            )
        return rec


class ProgramTimes:
    """Per-program measured wall accumulator — the join side of a cost
    card. ``observe(name, seconds)`` adds one call;
    ``observe_total(name, seconds, calls)`` adds a pre-aggregated window
    (epoch timing). Thread-safe enough for the single-writer call sites
    (scheduler tick loop, trainer epoch end)."""

    def __init__(self):
        self._acc: Dict[str, Tuple[int, float]] = {}

    def observe(self, name: str, seconds: float) -> None:
        self.observe_total(name, seconds, 1)

    def observe_total(self, name: str, seconds: float, calls: int) -> None:
        if calls < 1 or seconds < 0:
            return
        n, s = self._acc.get(name, (0, 0.0))
        self._acc[name] = (n + calls, s + float(seconds))

    def __contains__(self, name: str) -> bool:
        return name in self._acc

    def get(self, name: str) -> Tuple[int, float]:
        return self._acc.get(name, (0, 0.0))

    def items(self):
        return self._acc.items()

    def census_decls(self):
        from pytorch_distributed_tpu.telemetry.census import Decl

        return [
            Decl("_acc", "fixed", cap=256,
                 why="(calls, total_s) aggregate per program name — "
                     "O(registered programs), not O(observations); the "
                     "ProgramRegistry is a small closed set"),
        ]


def build_cost_cards(registry, times: Optional[ProgramTimes] = None,
                     ) -> List[CostCard]:
    """One card per registry program, in registry order.

    Statics come from each spec's ``aot`` thunk (``lower(...).compile()``
    — a persistent-cache hit when ``enable_persistent_cache`` ran, a
    fresh XLA compile otherwise; that cost is why trainers gate card
    emission behind ``cost_cards=True`` and pay it once at fit end, off
    the training critical path). A spec without an ``aot`` thunk, or one
    whose compile/analysis fails, still yields a card — with the static
    fields unknown — so "every program in the registry has a cost card"
    holds unconditionally."""
    cards = []
    for spec in registry:
        card = CostCard(program=spec.name)
        aot = getattr(spec, "aot", None)
        if aot is not None:
            try:
                compiled = aot()
                if compiled is not None:
                    for k, v in extract_costs(compiled).items():
                        setattr(card, k, v)
            except Exception:
                pass  # unanalyzable program: card ships without statics
        if times is not None:
            card.calls, card.total_s = times.get(spec.name)
        cards.append(card)
    return cards


def log_cost_cards(registry, times, metrics_log, *,
                   fingerprint: Optional[str] = None) -> List[dict]:
    """Build every card, join, and emit one ``kind="program_cost"``
    JSONL record per program. Returns the records (emitted or not — a
    ``metrics_log`` of None still returns them for callers that render
    directly)."""
    peak_flops, peak_bytes_s = device_ceilings()
    records = []
    for card in build_cost_cards(registry, times):
        rec = card.record(peak_flops, peak_bytes_s)
        rec["fingerprint"] = (
            fingerprint if fingerprint is not None else registry.fingerprint
        )
        records.append(rec)
        if metrics_log is not None:
            metrics_log.log(kind="program_cost", **rec)
    return records
