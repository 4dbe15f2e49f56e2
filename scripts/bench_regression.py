"""Round-over-round bench regression gate with per-key noise bands.

The BENCH_r0N.json trajectory is the repo's performance history, but
nothing ever COMPARED two rounds — an 11.99 s vs 0.59 s swing (ADVICE r5
§4) sat in the record for a round before a human noticed. This script
diffs the newest round against the previous one, key by key, with noise
bands wide enough that the documented measurement weather (run-to-run
timing ±6%, shared-disk bandwidth 2×; PERF_NOTES §5/§8) does not page anyone,
and exits non-zero when a key regresses OUTSIDE its band — the optional
``ci_check.sh --bench-regression`` gate.

Direction is inferred from the key: throughput-like keys (``*_img_s``,
``*_tok_s``, ``*_tflops``, ``*_gb_s``, ``*_mb_s``, ``*_per_s``,
``*_frac`` where higher is better is NOT assumed — fractions are
skipped) regress when they DROP below ``previous × (1 - band)``;
latency/time keys (``*_ms``, ``*_s``) regress when they RISE above
``previous × (1 + band)``. Keys that are not numbers, appear in only one
round, or match the skip list are reported as informational.

Bands: 10% default; disk/checkpoint keys get 150% (the measured 2×
disk-weather swing, PERF_NOTES §8) — a regression there must be
structural, not meteorological. Override any band with
``--band key=frac`` (repeatable).

``--blocksan-off`` is a separate structural gate (round 18): with
``PDT_BLOCKSAN`` unset, the block-lifecycle sanitizer must be fully
detached — ``maybe_sanitizer()`` returns None and a fresh
``BlockAllocator`` carries ``sanitizer=None``, so every hook site in the
hot alloc/free path costs one attribute load + is-None branch and the
bench numbers above measure the same code the seed measured. It also
micro-times alloc/free cycles detached vs attached (informational, with
a generous flake-proof bound) and exits non-zero if the detached path is
somehow slower than the attached one.

Usage:
    python scripts/bench_regression.py CURRENT.json PREVIOUS.json [--json]
    python scripts/bench_regression.py --auto [--dir .]   # two newest rounds
    python scripts/bench_regression.py --blocksan-off [--json]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Dict, Optional, Tuple

REPO_DEFAULT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: default relative noise band
DEFAULT_BAND = 0.10
#: key-pattern bands for known-noisy measurements (first match wins)
BAND_OVERRIDES: Tuple[Tuple[str, float], ...] = (
    # prefix-cache keys (round 17): token accounting is deterministic
    # per trace but the ratio moves with trace mix; hit rate is bounded
    # in [0, 1]
    (r"serving_prefix_hit_rate", 0.25),
    (r"^serving_prefix_", 0.5),
    # round-21 soak keys: the growth SLOPES are the claim (down is
    # good; direction overrides below), but their magnitudes ride the
    # same shared-box weather as the wall-clock bench — a slope near
    # zero makes relative bands twitchy, so the band is wide and the
    # census/verdict gates (strings + ci_check --soak-smoke) carry the
    # hard pass/fail instead
    (r"^serving_soak_", 1.5),
    # shared-disk weather moves raw bandwidth 2x day to day (PERF_NOTES
    # §8); anything disk-bound inherits that swing
    (r"^ckpt_", 1.5),
    (r"disk", 1.5),
    # single-sample latency spreads on a contended 1-core box
    (r"stall", 1.5),
    (r"wall_s$", 0.5),
)

#: keys that are configuration, not measurement — plus the same-run
#: link probes (ADVICE §6): they exist to EXPLAIN cross-day swings
#: (environment weather co-quoted with every serving row), so gating
#: them would page on the weather itself
SKIP_PATTERNS = (
    r"batch_size$", r"^platform$", r"^device$", r"^unit$", r"^metric$",
    r"_mode$", r"^host_cores$", r"params_m$", r"bytes_mb$", r"_len$",
    r"slots$", r"_lens$", r"tokens$", r"_frac$", r"vs_baseline",
    r"^probe_",
)

_HIGHER_BETTER = re.compile(
    r"(_img_s|_tok_s|tok_s$|_tflops|_gb_s|_mb_s|_per_s|throughput|"
    r"goodput|_speedup|duty_cycle|_ratio.*over|img_s$)"
)
_LOWER_BETTER = re.compile(r"(_ms$|_s$|_ms_|latency|overhead)")


def band_for(key: str, overrides: Dict[str, float]) -> float:
    if key in overrides:
        return overrides[key]
    for pattern, band in BAND_OVERRIDES:
        if re.search(pattern, key):
            return band
    return DEFAULT_BAND


#: direction overrides checked BEFORE the skip list: rates and ratios
#: are normally configuration-like and skipped, but these are
#: direction-aware measurements
DIRECTION_OVERRIDES: Tuple[Tuple[str, str], ...] = (
    # prefix-cache keys (round 17): hit rate and the off/on token ratio
    # regress DOWN (less sharing); admitted tokens and fresh blocks per
    # request regress UP (sharing doing less work per request is the
    # whole point)
    (r"serving_prefix_hit_rate", "up"),
    (r"serving_prefix_admit_tok_ratio", "up"),
    (r"serving_prefix_admit_tok_per_req", "down"),
    (r"serving_prefix_fresh_blocks_per_req", "down"),
    # round-21 soak slopes: MiB (or ms) per 10k sessions — growth is
    # the regression, shrinking is the win; RSS final rides along.
    # Verdict/census keys are strings (auto-skipped) and *_frac keys
    # hit the skip list — the soak-smoke gate enforces those exactly.
    (r"serving_soak_rss_slope", "down"),
    (r"serving_soak_host_wall_slope", "down"),
    (r"serving_soak_rss_mib", "down"),
)


def direction(key: str) -> Optional[str]:
    """'up' = higher is better, 'down' = lower is better, None = skip.
    Throughput patterns win over the time-suffix patterns (a *_tok_s key
    is a rate even though it ends in _s)."""
    for pattern, sense in DIRECTION_OVERRIDES:
        if re.search(pattern, key):
            return sense
    for pattern in SKIP_PATTERNS:
        if re.search(pattern, key):
            return None
    if _HIGHER_BETTER.search(key):
        return "up"
    if _LOWER_BETTER.search(key):
        return "down"
    return None


def load_round(path: str) -> dict:
    """A bench dict from either shape: the driver's
    ``{"parsed": {...}}`` envelope or a flat metrics dict."""
    with open(path) as f:
        data = json.load(f)
    if isinstance(data, dict) and isinstance(data.get("parsed"), dict):
        data = data["parsed"]
    if not isinstance(data, dict):
        raise SystemExit(f"{path}: not a bench dict")
    return data


def compare(current: dict, previous: dict,
            overrides: Optional[Dict[str, float]] = None) -> dict:
    """{'regressions': [...], 'improvements': [...], 'within': n,
    'skipped': n} — each regression row carries key, previous, current,
    band, and the relative change."""
    overrides = overrides or {}
    regressions, improvements = [], []
    within = skipped = 0
    for key in sorted(set(current) & set(previous)):
        cur, prev = current[key], previous[key]
        if (not isinstance(cur, (int, float))
                or not isinstance(prev, (int, float))
                or isinstance(cur, bool) or isinstance(prev, bool)):
            skipped += 1
            continue
        sense = direction(key)
        if sense is None or prev == 0:
            skipped += 1
            continue
        band = band_for(key, overrides)
        rel = (cur - prev) / abs(prev)
        row = {"key": key, "previous": prev, "current": cur,
               "rel_change": round(rel, 4), "band": band}
        worse = rel < -band if sense == "up" else rel > band
        better = rel > band if sense == "up" else rel < -band
        if worse:
            regressions.append(row)
        elif better:
            improvements.append(row)
        else:
            within += 1
    return {
        "regressions": regressions,
        "improvements": improvements,
        "within": within,
        "skipped": skipped,
    }


def newest_rounds(directory: str) -> Tuple[str, str]:
    rounds = sorted(glob.glob(os.path.join(directory, "BENCH_r[0-9]*.json")))
    if len(rounds) < 2:
        raise SystemExit(
            f"--auto needs >= 2 BENCH_r0N.json files in {directory}, "
            f"found {len(rounds)}"
        )
    return rounds[-1], rounds[-2]


def blocksan_off_nil(emit_json: bool = False) -> int:
    """The blocksan-off overhead gate: prove the sanitizer is detached
    when ``PDT_BLOCKSAN`` is unset (structural nil — each hook site is a
    single is-None branch) and that detached alloc/free cycles are not
    slower than attached ones (generous 1.5x bound: timing is
    informational, the structural checks are the gate)."""
    import time as _time

    os.environ.pop("PDT_BLOCKSAN", None)
    sys.path.insert(0, REPO_DEFAULT)
    from pytorch_distributed_tpu.analysis.blocksan import (
        BlockSanitizer, maybe_sanitizer,
    )
    from pytorch_distributed_tpu.serving.kv_pool import BlockAllocator

    assert maybe_sanitizer() is None, \
        "PDT_BLOCKSAN unset but maybe_sanitizer() armed a sanitizer"
    alloc = BlockAllocator(n_blocks=64)
    assert alloc.sanitizer is None, \
        "fresh BlockAllocator arrived with a sanitizer attached"

    def cycles(a, n=2000):
        t0 = _time.perf_counter()
        for i in range(n):
            a.alloc(1, 4)
            a.free(1)
        return (_time.perf_counter() - t0) / n * 1e9  # ns per cycle

    cycles(alloc, 200)  # warm both paths before timing
    off_ns = cycles(alloc)
    san = BlockSanitizer()
    san.attach(alloc, name="bench")
    cycles(alloc, 200)
    on_ns = cycles(alloc)
    san.assert_clean()
    row = {
        "blocksan_off_ns_per_cycle": round(off_ns),
        "blocksan_on_ns_per_cycle": round(on_ns),
        "blocksan_off_detached": True,
    }
    print(f"blocksan-off: detached (structural nil), "
          f"{row['blocksan_off_ns_per_cycle']} ns/cycle off vs "
          f"{row['blocksan_on_ns_per_cycle']} ns/cycle on")
    if emit_json:
        print(json.dumps(row))
    if off_ns > on_ns * 1.5:
        print(f"blocksan-off: detached path SLOWER than attached "
              f"({off_ns:.0f} ns vs {on_ns:.0f} ns) — hook sites are "
              f"doing work while detached", file=sys.stderr)
        return 1
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("paths", nargs="*",
                   help="CURRENT.json PREVIOUS.json (or use --auto)")
    p.add_argument("--auto", action="store_true",
                   help="compare the two newest BENCH_r0N.json in --dir")
    p.add_argument("--dir", default=REPO_DEFAULT, help="round directory")
    p.add_argument("--band", action="append", default=[],
                   metavar="KEY=FRAC", help="override one key's band")
    p.add_argument("--json", action="store_true",
                   help="append the comparison as one JSON line")
    p.add_argument("--blocksan-off", action="store_true",
                   help="assert the block-lifecycle sanitizer is fully "
                        "detached (nil overhead) when PDT_BLOCKSAN is "
                        "unset, then exit")
    args = p.parse_args(argv)

    if args.blocksan_off:
        return blocksan_off_nil(emit_json=args.json)
    if args.auto:
        cur_path, prev_path = newest_rounds(args.dir)
    elif len(args.paths) == 2:
        cur_path, prev_path = args.paths
    else:
        p.error("pass CURRENT.json PREVIOUS.json, or --auto")
    overrides = {}
    for spec in args.band:
        key, _, frac = spec.partition("=")
        if not frac:
            p.error(f"--band needs KEY=FRAC, got {spec!r}")
        overrides[key] = float(frac)

    result = compare(load_round(cur_path), load_round(prev_path), overrides)
    print(f"bench regression: {os.path.basename(cur_path)} vs "
          f"{os.path.basename(prev_path)}")
    print(f"  within band: {result['within']}, improvements: "
          f"{len(result['improvements'])}, skipped: {result['skipped']}")
    for row in result["improvements"]:
        print(f"  + {row['key']}: {row['previous']} -> {row['current']} "
              f"({row['rel_change']:+.1%})")
    for row in result["regressions"]:
        print(f"  ! REGRESSION {row['key']}: {row['previous']} -> "
              f"{row['current']} ({row['rel_change']:+.1%}, band "
              f"±{row['band']:.0%})")
    if args.json:
        print(json.dumps({
            "bench_regressions": len(result["regressions"]),
            "bench_improvements": len(result["improvements"]),
            "bench_within_band": result["within"],
            "regression_keys": [r["key"] for r in result["regressions"]],
        }))
    return 1 if result["regressions"] else 0


if __name__ == "__main__":
    sys.exit(main())
