"""Render telemetry JSONL into the summary table bench.py consumes.

Reads one or more ``MetricsLogger`` JSONL streams (a training run's
``metrics.jsonl``, a serving run's ``--metrics-out`` file, or both) and
produces, from the JSONL alone:

- the **goodput breakdown** of a training run — productive / compile /
  data-wait / checkpoint / rollback / stall fractions (summing to 1)
  from the ``kind="goodput"`` record, plus the train-series shape
  (steps logged, final loss) and epoch timing;
- **serving latency percentiles** — TTFT and per-output-token p50/p95
  (and queue wait) recomputed exactly from the per-request
  ``kind="request"`` records (falling back to the
  ``kind="serving_summary"`` percentiles when only the summary was
  kept);
- the **fleet section** (round 10; ``fleet/``) — per-replica
  TTFT/queue-wait p50/p95/p99, shed rate (explicit rejects with
  reasons), spill rate (requests routed off their affinity replica),
  and handoff counts, from the same ``kind="request"`` records (which
  carry ``replica_id``/``rejected``/``reject_reason``/``spilled``) plus
  the ``kind="fleet_summary"`` rollup;
- the **cost/roofline table** (round 11; ``telemetry/costmodel.py``) —
  one row per program from ``kind="program_cost"`` records: calls, mean
  ms, achieved GFLOP/s and GB/s, arithmetic intensity, MFU and the
  compute-vs-bandwidth bound (ceiling columns render "-" when no device
  ceiling is known; set PDT_PEAK_FLOPS / PDT_PEAK_GBS);
- the **anomaly section** (round 11; ``telemetry/anomaly.py``) — count
  per series plus the latest excursions with their z-scores, from
  ``kind="anomaly"`` records;
- the **pressure section** (round 13; KV offload + preemption) —
  preempt rate, per-direction swap p50/p95 and bytes moved, swap-vs-
  recompute decision counts and the predicted-cost crossover histogram,
  from ``kind="preempt"``/``kind="swap"`` records;
- the **prefix section** (round 17; prefix-sharing KV cache) — hit
  rate, covered-prefix fraction, shared-blocks-per-hit percentiles,
  COW copies and admission-path evictions, from ``kind="prefix"``
  per-admission records plus the fleet rollup;
- the **host-resource section** (round 21; ``telemetry/hostprof.py``)
  — RSS and per-tick host-wall growth fits against cumulative sessions
  (slopes per 10k, flat/linear/superlinear verdicts), gc population and
  tracemalloc top sites, from ``kind="resource"`` monitor samples;
- the **structure-census section** (round 21; ``telemetry/census.py``)
  — sweep totals, bound violations and undeclared containers (both
  failures), worst bound ratio, and peak structure sizes, from
  ``kind="census"`` sweep records;
- the **http-ingress section** (round 22; ``gateway/server.py``) — one
  record per ``/v1/generate`` connection: status histogram (200 served
  / 429 shed / 400 malformed), disconnect→cancel counts,
  over-the-wire TTFT percentiles, bytes out and the worst inter-token
  stream gap, from ``kind="http"`` records;
- the **request-trace section** (round 14; ``telemetry/reqtrace.py``) —
  lifecycle trace counts, completeness (every span closed, parents
  acyclic), open spans, and phase totals from ``kind="span"`` records
  (``scripts/explain_request.py`` reconstructs any single rid).

Usage:
    python scripts/telemetry_report.py RUN.jsonl [SERVE.jsonl ...] [--json]

Human-readable tables by default; ``--json`` appends one flat JSON dict
(bench.py record style) as the last line. Exits non-zero if NO goodput
record and NO serving latencies were found — the ci_check.sh
``--telemetry-smoke`` gate.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from pytorch_distributed_tpu.telemetry.goodput import (  # noqa: E402
    GOODPUT_CATEGORIES,
)
from pytorch_distributed_tpu.telemetry.latency import (  # noqa: E402
    percentiles,
)


def load_records(paths: List[str]) -> List[dict]:
    records = []
    for path in paths:
        with open(path) as f:
            for i, line in enumerate(f):
                line = line.strip()
                if not line:
                    continue
                try:
                    records.append(json.loads(line))
                except json.JSONDecodeError as e:
                    raise SystemExit(
                        f"{path}:{i + 1}: not JSONL ({e})"
                    ) from e
    return records


def _fmt_row(label: str, *cells) -> str:
    return "  " + label.ljust(20) + "".join(str(c).rjust(16) for c in cells)


def goodput_section(records: List[dict], out: dict) -> List[str]:
    """Goodput breakdown from the newest ``kind="goodput"`` record."""
    gps = [r for r in records if r.get("kind") == "goodput"]
    if not gps:
        return []
    gp = gps[-1]  # the run's final (cumulative) ledger report
    lines = ["== goodput =="]
    lines.append(_fmt_row("category", "seconds", "fraction"))
    total_frac = gp["goodput_frac"]
    lines.append(_fmt_row(
        "productive", f"{gp['productive_s']:.2f}",
        f"{gp['goodput_frac']:.3f}",
    ))
    for cat in GOODPUT_CATEGORIES:
        # .get: records written before a category existed (e.g. "trace",
        # added with compilecache/) render as zero rather than erroring
        total_frac += gp.get(f"{cat}_frac", 0.0)
        lines.append(_fmt_row(
            cat, f"{gp.get(f'{cat}_s', 0.0):.2f}",
            f"{gp.get(f'{cat}_frac', 0.0):.3f}"
        ))
    lines.append(_fmt_row("wall", f"{gp['wall_s']:.2f}",
                          f"{total_frac:.3f}"))
    out["goodput_frac"] = round(gp["goodput_frac"], 4)
    out["goodput_wall_s"] = round(gp["wall_s"], 2)
    for cat in GOODPUT_CATEGORIES:
        out[f"goodput_{cat}_frac"] = round(gp.get(f"{cat}_frac", 0.0), 4)
    return lines


def train_section(records: List[dict], out: dict) -> List[str]:
    trains = [r for r in records if r.get("kind") == "train"]
    epochs = [r for r in records if r.get("kind") == "epoch_timing"]
    if not trains and not epochs:
        return []
    lines = ["== training =="]
    if trains:
        last = trains[-1]
        lines.append(
            f"  {len(trains)} log events; last: epoch {last.get('epoch')} "
            f"step {last.get('step')} loss {last.get('loss', float('nan')):.4f}"
        )
        out["train_log_events"] = len(trains)
        out["train_last_loss"] = last.get("loss")
    for r in epochs:
        rate = r.get("tokens_per_s") or r.get("items_per_s")
        rate_s = f", {rate:.0f}/s" if rate else ""
        lines.append(
            f"  epoch {r['epoch']}: {r['steps']} steps, "
            f"{r['mean_ms']:.1f} ms/step{rate_s}"
        )
    if epochs:
        out["train_mean_step_ms"] = round(epochs[-1]["mean_ms"], 2)
    return lines


def warmup_section(records: List[dict], out: dict) -> List[str]:
    """Warmup manifest (``kind="warmup"`` from compilecache.WarmupRunner):
    how many programs compiled ahead of traffic, how many were
    persistent-cache hits, and the XLA-backend share of the time — the
    cold-vs-warm start comparison surface."""
    warms = [r for r in records if r.get("kind") == "warmup"]
    if not warms:
        return []
    hits = sum(1 for r in warms if r.get("cache_hit"))
    total = sum(r.get("seconds", 0.0) for r in warms)
    backend = sum(r.get("backend_compile_s", 0.0) for r in warms)
    lines = ["== warmup =="]
    lines.append(
        f"  {len(warms)} programs in {total:.2f}s "
        f"({hits} cache hits, {len(warms) - hits} fresh; "
        f"backend compile {backend:.2f}s)"
    )
    slowest = max(warms, key=lambda r: r.get("seconds", 0.0))
    lines.append(
        f"  slowest: {slowest.get('program')} "
        f"{slowest.get('seconds', 0.0):.2f}s"
        f"{' (hit)' if slowest.get('cache_hit') else ''}"
    )
    out["warmup_programs"] = len(warms)
    out["warmup_cache_hits"] = hits
    out["warmup_total_s"] = round(total, 3)
    out["warmup_backend_compile_s"] = round(backend, 3)
    return lines


def serving_section(records: List[dict], out: dict) -> List[str]:
    reqs = [r for r in records if r.get("kind") == "request"]
    summaries = [r for r in records if r.get("kind") == "serving_summary"]
    if not reqs and not summaries:
        return []
    lines = ["== serving latency =="]
    if reqs:
        # exact recomputation from the raw per-request records
        ttft = [r["ttft_s"] for r in reqs if "ttft_s" in r]
        # warm-only TTFT: requests whose lifetime saw no compile stall
        # (cold=False; records predating the flag count as warm) — the
        # honest SLO series a cold first-bucket request would pollute
        ttft_warm = [r["ttft_s"] for r in reqs
                     if "ttft_s" in r and not r.get("cold")]
        cold = sum(1 for r in reqs if r.get("cold"))
        queue = [r["queue_wait_s"] for r in reqs if "queue_wait_s" in r]
        gaps = [g for r in reqs for g in r.get("token_gaps_s", [])]
        lines.append(
            f"  {len(reqs)} requests ({cold} cold), "
            f"{sum(r.get('new_tokens', 0) for r in reqs)} tokens"
        )
        out["serving_requests"] = len(reqs)
        out["serving_cold_requests"] = cold
        for name, vals in (("ttft", ttft), ("ttft_warm", ttft_warm),
                           ("token_lat", gaps), ("queue_wait", queue)):
            ps = percentiles(vals, qs=(50, 95))
            if not ps:
                continue
            lines.append(_fmt_row(
                name,
                f"p50 {ps['p50'] * 1e3:.1f}ms",
                f"p95 {ps['p95'] * 1e3:.1f}ms",
            ))
            out[f"serving_{name}_p50_ms"] = round(ps["p50"] * 1e3, 3)
            out[f"serving_{name}_p95_ms"] = round(ps["p95"] * 1e3, 3)
    elif summaries:
        s = summaries[-1]
        for name in ("ttft", "token_lat", "queue_wait"):
            p50, p95 = s.get(f"{name}_p50_s"), s.get(f"{name}_p95_s")
            if p50 is None:
                continue
            lines.append(_fmt_row(
                name, f"p50 {p50 * 1e3:.1f}ms", f"p95 {p95 * 1e3:.1f}ms"
            ))
            out[f"serving_{name}_p50_ms"] = round(p50 * 1e3, 3)
            out[f"serving_{name}_p95_ms"] = round(p95 * 1e3, 3)
    if summaries:
        s = summaries[-1]
        for k in ("tokens_per_s", "occupancy_mean", "padding_waste_frac"):
            if k in s:
                out[f"serving_{k}"] = round(float(s[k]), 4)
    return lines


def fleet_section(records: List[dict], out: dict) -> List[str]:
    """Per-replica latency percentiles + shed/spill accounting from the
    fleet-stamped request records (``replica_id`` present since round
    10) and the ``kind="fleet_summary"`` rollup."""
    reqs = [r for r in records
            if r.get("kind") == "request" and "replica_id" in r]
    summaries = [r for r in records if r.get("kind") == "fleet_summary"]
    if not reqs and not summaries:
        return []
    lines = ["== fleet =="]
    served = [r for r in reqs if not r.get("rejected")]
    shed = [r for r in reqs if r.get("rejected")]
    spilled = sum(1 for r in served if r.get("spilled"))
    by_rep: dict = {}
    for r in served:
        by_rep.setdefault(r["replica_id"], []).append(r)
    out["fleet_replicas"] = len(by_rep)
    out["fleet_requests"] = len(reqs)
    out["fleet_shed"] = len(shed)
    out["fleet_shed_rate"] = (
        round(len(shed) / len(reqs), 4) if reqs else 0.0
    )
    out["fleet_spill_rate"] = (
        round(spilled / len(served), 4) if served else 0.0
    )
    lines.append(
        f"  {len(reqs)} requests over {len(by_rep)} replica(s); "
        f"shed {len(shed)} ({out['fleet_shed_rate']:.1%}), "
        f"spilled {spilled} ({out['fleet_spill_rate']:.1%})"
    )
    if shed:
        reasons: dict = {}
        for r in shed:
            reasons[r.get("reject_reason", "?")] = (
                reasons.get(r.get("reject_reason", "?"), 0) + 1
            )
        lines.append("  shed reasons: " + ", ".join(
            f"{k}={v}" for k, v in sorted(reasons.items())
        ))
    for rep_id, rs in sorted(by_rep.items()):
        cells = [f"{len(rs)} reqs"]
        for name, key in (("ttft", "ttft_s"), ("queue", "queue_wait_s")):
            ps = percentiles([r[key] for r in rs if key in r])
            if not ps:
                continue
            cells.append(
                f"{name} " + "/".join(
                    f"{ps[q] * 1e3:.1f}" for q in ("p50", "p95", "p99")
                ) + "ms"
            )
            for q in ("p50", "p95", "p99"):
                out[f"fleet_r{rep_id}_{name}_{q}_ms"] = round(
                    ps[q] * 1e3, 3
                )
        lines.append("  " + f"replica {rep_id}".ljust(12)
                     + "  ".join(str(c).rjust(30) for c in cells))
    if summaries:
        s = summaries[-1]
        for k in ("handoffs", "recommended_replicas_peak", "replicas",
                  "disaggregated"):
            if k in s:
                out[f"fleet_{k}"] = s[k]
        if s.get("handoffs"):
            lines.append(
                f"  {s['handoffs']} prefill→decode handoffs"
                + (f", mean {s['handoff_mean_s'] * 1e3:.2f}ms"
                   if "handoff_mean_s" in s else "")
            )
    return lines


def cost_section(records: List[dict], out: dict) -> List[str]:
    """Per-program MFU/roofline table from ``kind="program_cost"``
    records (newest record per program wins — a rerun's cards supersede
    the first run's). The in-runtime generalization of the one-off
    ``scripts/exp_resnet_roofline.py`` table."""
    cards: dict = {}
    for r in records:
        if r.get("kind") == "program_cost":
            cards[r["program"]] = r  # newest wins
    if not cards:
        return []

    def fmt(v, scale=1.0, digits=1):
        return f"{v / scale:.{digits}f}" if v is not None else "-"

    lines = ["== program cost / roofline =="]
    lines.append(_fmt_row(
        "program", "calls", "mean_ms", "GFLOP/s", "GB/s", "F/B", "MFU",
        "bound",
    ))
    measured = 0
    # measured programs first (by total time, attribution order), then
    # the cold remainder alphabetically
    ordered = sorted(
        cards.values(),
        key=lambda r: (-(r.get("total_s") or 0.0), r["program"]),
    )
    for r in ordered:
        if r.get("calls"):
            measured += 1
        lines.append(_fmt_row(
            r["program"][:20],
            r.get("calls", 0),
            fmt(r.get("mean_s"), 1e-3, 3) if r.get("calls") else "-",
            fmt(r.get("achieved_flops_s"), 1e9),
            fmt(r.get("achieved_bytes_s"), 1e9),
            fmt(r.get("intensity_flop_b"), 1.0),
            f"{r['mfu']:.4f}" if r.get("mfu") is not None else "-",
            r.get("bound", "-"),
        ))
    out["cost_programs"] = len(cards)
    out["cost_measured_programs"] = measured
    mfus = [r["mfu"] for r in cards.values() if r.get("mfu") is not None]
    if mfus:
        out["cost_mfu_max"] = round(max(mfus), 5)
    bw = [r for r in cards.values() if r.get("bound") == "bandwidth"]
    if any("bound" in r for r in cards.values()):
        out["cost_bandwidth_bound"] = len(bw)
    return lines


def pressure_section(records: List[dict], out: dict) -> List[str]:
    """KV pressure tier (round 13; ``serving/`` offload + preemption):
    preempt rate, swap walls, and the swap-vs-recompute decision
    crossover, from ``kind="preempt"`` / ``kind="swap"`` records."""
    preempts = [r for r in records if r.get("kind") == "preempt"]
    swaps = [r for r in records if r.get("kind") == "swap"]
    if not preempts and not swaps:
        return []
    lines = ["== kv pressure =="]
    reqs = [r for r in records
            if r.get("kind") == "request" and not r.get("rejected")]
    rate = len(preempts) / len(reqs) if reqs else 0.0
    by_choice: dict = {}
    for r in preempts:
        by_choice[r.get("decision", "?")] = (
            by_choice.get(r.get("decision", "?"), 0) + 1
        )
    lines.append(
        f"  {len(preempts)} preemptions"
        + (f" over {len(reqs)} requests ({rate:.1%})" if reqs else "")
        + "; decisions: " + ", ".join(
            f"{k}={v}" for k, v in sorted(by_choice.items())
        )
    )
    out["pressure_preempts"] = len(preempts)
    out["pressure_preempt_rate"] = round(rate, 4)
    out["pressure_decision_swap"] = by_choice.get("swap", 0)
    out["pressure_decision_recompute"] = by_choice.get("recompute", 0)
    ok = [r for r in swaps if r.get("ok")]
    fails = [r for r in swaps if not r.get("ok")]
    out["pressure_swap_aborts"] = len(fails)
    for direction in ("out", "in"):
        walls = [r["wall_s"] for r in ok
                 if r.get("direction") == direction and "wall_s" in r]
        if not walls:
            continue
        ps = percentiles(walls, qs=(50, 95))
        moved = sum(r.get("bytes", 0) for r in ok
                    if r.get("direction") == direction)
        lines.append(_fmt_row(
            f"swap_{direction}", f"{len(walls)}x",
            f"p50 {ps['p50'] * 1e3:.2f}ms",
            f"p95 {ps['p95'] * 1e3:.2f}ms",
            f"{moved / 2**20:.2f}MiB",
        ))
        out[f"pressure_swap_{direction}_p95_ms"] = round(
            ps["p95"] * 1e3, 3
        )
        out[f"pressure_swap_{direction}_bytes"] = moved
    # decision-crossover histogram: predicted swap/recompute cost ratio
    # per preemption, bucketed in octaves around the crossover at 1 —
    # shows WHERE on the curve this workload's preemptions landed
    ratios = [
        r["predicted_swap_s"] / r["predicted_recompute_s"]
        for r in preempts
        if r.get("predicted_swap_s") and r.get("predicted_recompute_s")
    ]
    if ratios:
        edges = (0.25, 0.5, 1.0, 2.0, 4.0)
        labels = ["<1/4x", "1/4-1/2x", "1/2-1x", "1-2x", "2-4x", ">4x"]
        counts = [0] * (len(edges) + 1)
        for v in ratios:
            i = sum(v >= e for e in edges)
            counts[i] += 1
        lines.append("  swap/recompute predicted-cost crossover: "
                     + ", ".join(f"{l}={c}" for l, c in
                                 zip(labels, counts) if c))
        for l, c in zip(labels, counts):
            out[f"pressure_crossover_{l}"] = c
    return lines


def prefix_section(records: List[dict], out: dict) -> List[str]:
    """Prefix cache (round 17; ``serving/`` radix reuse + COW): hit
    rate, covered-prefix fraction, sharing/COW/eviction totals, from
    ``kind="prefix"`` per-admission records plus the fleet/serving
    summary rollups."""
    recs = [r for r in records if r.get("kind") == "prefix"]
    if not recs:
        return []
    lines = ["== prefix cache =="]
    hits = [r for r in recs if r.get("covered", 0) > 0]
    covered = sum(r.get("covered", 0) for r in recs)
    prompt = sum(r.get("prompt_len", 0) for r in recs)
    cows = sum(1 for r in recs if r.get("cow"))
    evicted = sum(r.get("evicted", 0) for r in recs)
    lines.append(
        f"  {len(recs)} prefix admissions, {len(hits)} hits "
        f"({len(hits) / len(recs):.1%}); covered {covered} of "
        f"{prompt} prompt tokens ({covered / max(prompt, 1):.1%})"
    )
    lines.append(
        f"  cow copies: {cows}; admission-path evictions: {evicted}"
    )
    shared = [r.get("shared_blocks", 0) for r in hits]
    if shared:
        ps = percentiles([float(s) for s in shared], qs=(50, 95))
        lines.append(_fmt_row(
            "shared blocks/hit", f"p50 {ps['p50']:.0f}",
            f"p95 {ps['p95']:.0f}",
        ))
    # the fleet rollup, when present, carries the allocator's census
    fleets = [r for r in records if r.get("kind") == "fleet_summary"
              and "prefix_hits" in r]
    if fleets:
        f = fleets[-1]
        lines.append(
            f"  fleet: hit rate {f.get('prefix_hit_rate', 0.0):.1%}, "
            f"evictions {f.get('prefix_evictions', 0)}, "
            f"shared blocks now {f.get('prefix_shared_blocks', 0)}, "
            f"affinity sessions {f.get('affinity_sessions', 0)} "
            f"(evicted {f.get('affinity_evictions', 0)})"
        )
    out["prefix_admissions"] = len(recs)
    out["prefix_hits"] = len(hits)
    out["prefix_hit_rate"] = round(len(hits) / len(recs), 4)
    out["prefix_covered_tokens"] = covered
    out["prefix_covered_frac"] = round(covered / max(prompt, 1), 4)
    out["prefix_cow_copies"] = cows
    out["prefix_evictions"] = evicted
    return lines


def span_section(records: List[dict], out: dict) -> List[str]:
    """Request-lifecycle traces (round 14; ``kind="span"`` from
    ``telemetry.reqtrace``): trace count, completeness, open (in-flight
    or abandoned) spans, and lifecycle phase totals —
    ``scripts/explain_request.py`` is the per-rid deep dive."""
    from pytorch_distributed_tpu.telemetry.reqtrace import (
        span_records,
        trace_rids,
        validate_trace,
    )

    spans = span_records(records)
    if not spans:
        return []
    rids = trace_rids(records)
    complete = sum(1 for r in rids if not validate_trace(records, r))
    begins = {(r["trace"], r["span"]) for r in spans
              if r.get("ev") == "begin"}
    ends = {(r["trace"], r["span"]) for r in spans if r.get("ev") == "end"}
    open_spans = len(begins - ends)
    by_phase: dict = {}
    for r in spans:
        if r.get("ev") == "end":
            continue
        if r.get("ev") == "begin":
            by_phase[r.get("name", "?")] = (
                by_phase.get(r.get("name", "?"), 0) + 1
            )
    lines = ["== request traces =="]
    lines.append(
        f"  {len(rids)} traces ({complete} complete, "
        f"{len(rids) - complete} incomplete), {len(spans)} span records, "
        f"{open_spans} open spans"
    )
    top = sorted(by_phase.items(), key=lambda kv: (-kv[1], kv[0]))[:8]
    lines.append("  phases: " + ", ".join(f"{n}={c}" for n, c in top))
    out["span_traces"] = len(rids)
    out["span_complete_traces"] = complete
    out["span_open"] = open_spans
    out["span_records"] = len(spans)
    return lines


def resource_section(records: List[dict], out: dict) -> List[str]:
    """Host resources (round 21; ``kind="resource"`` from
    ``telemetry.hostprof.ResourceMonitor``): RSS and per-tick host-wall
    growth fits against cumulative sessions — the soak's headline — plus
    the newest gc population and tracemalloc top sites when sampled."""
    from pytorch_distributed_tpu.telemetry.scaling import fit_growth

    recs = [r for r in records if r.get("kind") == "resource"]
    if not recs:
        return []
    lines = ["== host resources =="]
    first, last = recs[0], recs[-1]
    lines.append(
        f"  {len(recs)} samples; rss {first.get('rss_mib', 0.0):.1f} → "
        f"{last.get('rss_mib', 0.0):.1f} MiB "
        f"({last.get('rss_source', '?')}); live {last.get('live', 0)}, "
        f"cumulative {last.get('cumulative', 0)} sessions"
    )
    xs = [r.get("cumulative", 0) for r in recs]
    rss_fit = fit_growth(xs, [r.get("rss_mib", 0.0) for r in recs],
                         rel_floor=0.005, abs_floor=1.0)
    walls = [(r.get("cumulative", 0), r["tick_wall_ms_mean"])
             for r in recs if "tick_wall_ms_mean" in r]
    lines.append(
        f"  rss slope {rss_fit['slope'] * 1e4:+.2f} MiB/10k sessions "
        f"({rss_fit['verdict']})"
    )
    out["resource_samples"] = len(recs)
    out["resource_rss_mib_final"] = round(last.get("rss_mib", 0.0), 1)
    out["resource_rss_slope_mib_per_10k"] = round(
        rss_fit["slope"] * 1e4, 3)
    out["resource_rss_verdict"] = rss_fit["verdict"]
    if walls:
        wall_fit = fit_growth([w[0] for w in walls],
                              [w[1] for w in walls], abs_floor=0.05)
        lines.append(
            f"  host wall slope {wall_fit['slope'] * 1e4:+.3f} ms/10k "
            f"sessions ({wall_fit['verdict']}; shared-CPU smoke alarm, "
            f"not a proof — see ANALYSIS.md)"
        )
        out["resource_wall_slope_ms_per_10k"] = round(
            wall_fit["slope"] * 1e4, 4)
        out["resource_wall_verdict"] = wall_fit["verdict"]
    if "gc_objects" in last:
        lines.append(f"  gc objects: {last['gc_objects']}")
        out["resource_gc_objects_final"] = last["gc_objects"]
    sited = [r for r in recs if r.get("tracemalloc_top")]
    if sited:
        lines.append("  tracemalloc top sites (newest sample):")
        for s in sited[-1]["tracemalloc_top"][:5]:
            lines.append(
                f"    {s.get('kib', 0.0):>10.1f} KiB  "
                f"x{s.get('count', 0):<8} {s.get('site', '?')}"
            )
        out["resource_tracemalloc_samples"] = len(sited)
    return lines


def census_section(records: List[dict], out: dict) -> List[str]:
    """Bounded-structure census (round 21; ``kind="census"`` from
    ``telemetry.census.StructCensus``): sweep totals, any bound
    violations or undeclared containers (both are failures), the worst
    bound ratio seen, and the largest structures at their peaks."""
    recs = [r for r in records if r.get("kind") == "census"]
    if not recs:
        return []
    lines = ["== structure census =="]
    violations = sum(r.get("violations", 0) for r in recs)
    undeclared: set = set()
    peaks: dict = {}
    worst_frac, worst_name = 0.0, ""
    for r in recs:
        undeclared.update(r.get("undeclared", []))
        for k, v in (r.get("structures") or {}).items():
            if v > peaks.get(k, -1):
                peaks[k] = v
        if r.get("worst_ratio", 0.0) > worst_frac:
            worst_frac = r["worst_ratio"]
            worst_name = r.get("worst_name", "")
    ok = not violations and not undeclared
    lines.append(
        f"  {len(recs)} sweeps over {len(peaks)} structures: "
        + ("all bounds held"
           if ok else f"{violations} VIOLATIONS, "
                      f"{len(undeclared)} undeclared")
    )
    if worst_name:
        lines.append(
            f"  worst bound ratio {worst_frac:.2f} ({worst_name})"
        )
    if undeclared:
        lines.append("  undeclared: " + ", ".join(sorted(undeclared)))
    for r in recs:
        for v in r.get("violation_details", [])[:5]:
            lines.append(
                f"  VIOLATION {v['name']}: size {v['size']} > bound "
                f"{v['bound']} ({v['kind']})"
            )
    top = sorted(peaks.items(), key=lambda kv: -kv[1])[:8]
    lines.append("  peak sizes: " + ", ".join(
        f"{k}={v}" for k, v in top))
    out["census_sweeps"] = len(recs)
    out["census_violations"] = violations
    out["census_undeclared"] = len(undeclared)
    out["census_ok"] = ok
    out["census_worst_frac"] = round(worst_frac, 4)
    return lines


def ingress_section(records: List[dict], out: dict) -> List[str]:
    """HTTP front door (round 22; ``kind="http"`` from
    ``gateway/server.py``): one record per ``/v1/generate`` connection.
    Status histogram (the SLOGate ladder over the wire: 200 served,
    429 shed, 400 malformed), disconnect→cancel counts, TTFT measured
    at the socket, bytes out, and the worst inter-token stream gap."""
    recs = [r for r in records if r.get("kind") == "http"]
    if not recs:
        return []
    lines = ["== http ingress =="]
    statuses: dict = {}
    for r in recs:
        statuses[r.get("status", 0)] = statuses.get(r.get("status", 0),
                                                    0) + 1
    served = statuses.get(200, 0)
    shed = statuses.get(429, 0)
    disconnects = sum(1 for r in recs if r.get("disconnect"))
    cancelled = sum(1 for r in recs
                    if r.get("disconnect") and r.get("outcome") ==
                    "cancelled")
    lines.append(
        f"  {len(recs)} connections: "
        + ", ".join(f"{s}={n}" for s, n in sorted(statuses.items()))
        + (f"; 429 rate {shed / len(recs):.1%}" if shed else "")
    )
    lines.append(
        f"  disconnects {disconnects} ({cancelled} cancelled "
        f"mid-stream); bytes out "
        f"{sum(r.get('bytes', 0) or 0 for r in recs)}"
    )
    ttfts = [r["ttft_wire"] for r in recs
             if r.get("ttft_wire") is not None]
    if ttfts:
        pct = percentiles(ttfts, qs=(50, 95))
        p50, p95 = pct["p50"], pct["p95"]
        lines.append(
            f"  ttft over the wire p50 {p50 * 1e3:.1f} ms / "
            f"p95 {p95 * 1e3:.1f} ms ({len(ttfts)} streams)"
        )
        out["http_ttft_wire_p50_ms"] = round(p50 * 1e3, 2)
        out["http_ttft_wire_p95_ms"] = round(p95 * 1e3, 2)
    gaps = [r["gap_max_ms"] for r in recs if r.get("gap_max_ms")]
    if gaps:
        lines.append(f"  worst stream gap {max(gaps):.1f} ms")
        out["http_worst_gap_ms"] = round(max(gaps), 2)
    out["http_connections"] = len(recs)
    out["http_served"] = served
    out["http_shed"] = shed
    out["http_rejected"] = statuses.get(400, 0)
    out["http_disconnects"] = disconnects
    out["http_cancelled"] = cancelled
    return lines


def anomaly_section(records: List[dict], out: dict) -> List[str]:
    """Sentinel hits (``kind="anomaly"``): per-series counts and the
    latest excursions with their z-scores and baselines."""
    hits = [r for r in records if r.get("kind") == "anomaly"]
    if not hits:
        return []
    by_series: dict = {}
    for r in hits:
        by_series.setdefault(r.get("series", "?"), []).append(r)
    lines = ["== anomalies =="]
    lines.append("  " + ", ".join(
        f"{s}={len(rs)}" for s, rs in sorted(by_series.items())
    ))
    for r in hits[-5:]:
        src = f" [{r['source']}]" if r.get("source") else ""
        lines.append(
            f"  {r.get('series', '?')}{src}: value "
            f"{r.get('value', float('nan')):.4g} vs median "
            f"{r.get('median', float('nan')):.4g} "
            f"(z={r.get('zscore', float('nan')):.1f})"
        )
    out["anomalies"] = len(hits)
    for s, rs in sorted(by_series.items()):
        out[f"anomalies_{s}"] = len(rs)
    return lines


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("paths", nargs="+", help="telemetry JSONL file(s)")
    p.add_argument("--json", action="store_true",
                   help="append one flat JSON dict (bench.py style)")
    p.add_argument("--require", default=None,
                   help="comma list of sections that MUST be present "
                        "(goodput, serving, warmup, fleet, pressure, "
                        "prefix, spans, cost, resource, "
                        "census, http, anomaly) — exit non-zero "
                        "otherwise; the ci_check.sh --telemetry-smoke, "
                        "--warmup-smoke, --fleet-smoke, --obs-smoke, "
                        "--pressure-smoke, --trace-smoke, "
                        "--prefix-smoke, --soak-smoke "
                        "and --gateway-smoke gates")
    args = p.parse_args(argv)

    records = load_records(args.paths)
    out: dict = {}
    lines: List[str] = []
    lines += goodput_section(records, out)
    lines += warmup_section(records, out)
    lines += train_section(records, out)
    lines += serving_section(records, out)
    lines += fleet_section(records, out)
    lines += pressure_section(records, out)
    lines += prefix_section(records, out)
    lines += span_section(records, out)
    lines += cost_section(records, out)
    lines += resource_section(records, out)
    lines += census_section(records, out)
    lines += ingress_section(records, out)
    lines += anomaly_section(records, out)
    if not lines:
        print(f"no telemetry records in {args.paths}", file=sys.stderr)
        return 2
    print("\n".join(lines))
    present = {
        "goodput": "goodput_frac" in out,
        "serving": "serving_ttft_p50_ms" in out,
        "warmup": "warmup_programs" in out,
        "fleet": "fleet_replicas" in out,
        "pressure": out.get("pressure_preempts", 0) > 0,
        "prefix": out.get("prefix_admissions", 0) > 0,
        "spans": out.get("span_traces", 0) > 0,
        "cost": out.get("cost_programs", 0) > 0,
        "resource": out.get("resource_samples", 0) > 0,
        "census": out.get("census_sweeps", 0) > 0,
        "http": out.get("http_connections", 0) > 0,
        "anomaly": out.get("anomalies", 0) > 0,
    }
    if not any(present.values()):
        print("no goodput record, serving latencies, warmup manifest, "
              "fleet records, pressure records, cost cards, or anomalies "
              "found", file=sys.stderr)
        return 2
    required = {s for s in (args.require or "").split(",") if s}
    unknown = required - set(present)
    if unknown:
        print(f"--require: unknown sections {sorted(unknown)}",
              file=sys.stderr)
        return 2
    missing = sorted(s for s in required if not present[s])
    if missing:
        print(f"--require: missing section(s) {missing}", file=sys.stderr)
        return 2
    if args.json:
        print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
