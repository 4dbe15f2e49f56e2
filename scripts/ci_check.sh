#!/usr/bin/env bash
# CI gate: static analysis first (cheap, seconds), then the tier-1 test
# suite from ROADMAP.md. jaxlint exits non-zero on any finding that is
# neither fixed, suppressed inline ('# jaxlint: disable=<rule> -- why'),
# nor recorded with a reason in scripts/jaxlint_baseline.json — so NEW
# hazards fail the build while the reviewed pre-existing ones don't.
#
# Usage: scripts/ci_check.sh [--lint-only|--lint-incremental|
#                             --resilience-smoke|--serving-smoke|
#                             --telemetry-smoke|--warmup-smoke|--reshard-smoke|
#                             --fleet-smoke|--obs-smoke|--kernel-smoke|
#                             --pressure-smoke|--trace-smoke|
#                             --prefix-smoke|--blocksan-smoke|
#                             --chaos-smoke|
#                             --soak-smoke|--gateway-smoke|
#                             --bench-regression]
#
# --lint-incremental: jaxlint via the content-hash cache
# (.jaxlint_cache.json) — unchanged files serve from cache, cross-module
# rules re-run on any change; the cheap per-commit gate. The full run
# (every other mode) stays the default and carries a 30 s timing budget
# plus a SARIF 2.1.0 artifact at output/jaxlint.sarif for CI annotation
# surfaces.
#
# --resilience-smoke: lint, then ONE crash-recovery cycle from the
# kill-matrix (SIGKILL mid-shard-write → relaunch → assert resume) —
# the cheap end-to-end proof that crash recovery still works, without
# the full tier-1 suite or the whole @crash matrix.
#
# --serving-smoke: lint, then ONE paged-engine submit/decode/drain
# cycle (tests/test_paged_serving.py::test_serving_smoke) — the cheap
# end-to-end proof the paged serving path still admits, decodes, and
# returns its blocks, without the parity/TP tier.
#
# --kernel-smoke: lint, then one pallas-gather serve cycle per pool
# dtype (int8/fp8; token-identical to generate; Pallas interpreter on
# CPU) + the int8/fp8 logit-error bounds + the split-S parity bound.
#
# --telemetry-smoke: lint, then one short LM training run and one
# paged-serving cycle with --metrics-out, then telemetry_report.py must
# parse BOTH JSONLs and print a goodput breakdown + TTFT/per-token
# p50/p95 (it exits non-zero otherwise) — the end-to-end proof the
# observability pipeline (device ring → JSONL → report) still closes.
#
# --reshard-smoke: lint, then ONE cross-topology kill-and-resume cycle
# (SIGKILL a run on mesh (4,1,2) mid-save, relaunch it on (2,1,2) at the
# same global batch → elastic resume must reshard the checkpoint and
# finish the run) — the cheap end-to-end proof that a preempted run can
# resume on whatever topology the scheduler hands back, without the
# full cross-topology kill matrix.
#
# --fleet-smoke: lint, then the round-10 fleet cycle on one short seeded
# bursty trace: a 2-replica router (session affinity + SLO gate) and a
# disaggregated prefill/decode pair (KV-block handoff) both serve the
# trace through recipes/serve_lm.py, and telemetry_report.py must print
# the fleet section (per-replica percentiles, shed/spill rates) from
# their JSONLs — the cheap end-to-end proof the fleet layer still
# routes, hands off, and reports (~15 s).
#
# --obs-smoke: lint, then the round-11 attribution/forensics cycle: one
# tiny LM run with a seeded train.step HANG (the sentinel must flag it)
# and --cost-cards, a second tiny LM run with a seeded SUSPEND (the
# flight recorder must leave an atomic dump), and one serve cycle with
# --cost-cards — then telemetry_report.py must render the per-program
# MFU/roofline table and >=1 anomaly (--require cost,anomaly) and the
# flight-recorder dump must parse (~30 s).
#
# --pressure-smoke: lint, then the round-13 KV pressure cycle: one
# short over-committed serve (2-replica fleet, a pool holding ~3 chains
# per replica, bursty trace, tight shed bound, --preempt) must finish
# with >=1 preempt AND >=1 restore AND ZERO sheds (the preempt rung
# replacing the reject), then telemetry_report.py must render the
# pressure section (--require pressure: preempt rate, swap p95,
# decision crossover) from the JSONL alone (~30 s).
#
# --trace-smoke: lint, then the round-14 request-lifecycle tracing
# cycle: one disaggregated 2-replica serve (prefill/decode split, small
# decode pool, --preempt --swap-policy swap so the handoff pump's
# pressure rung forces at least one swap-path preemption) over a seeded
# bursty trace, then explain_request.py --assert-complete must
# reconstruct a single closed acyclic span tree for BOTH a preempted
# AND a handed-off rid (found by predicate, not hard-coded), a
# Perfetto-loadable Chrome trace must parse, and telemetry_report.py
# must render the request-trace section (--require spans) (~20 s).
#
# --prefix-smoke: lint, then the round-17 prefix-sharing cycle: one
# short seeded shared-system-prompt trace through the 2-replica
# session-affinity fleet with the radix prefix cache OFF then ON
# (bench_serving.py --prefix) must report hit rate > 0, a >= 1.5x
# admitted-prefill-token reduction, and BIT-IDENTICAL greedy token
# streams across the A/B; then telemetry_report.py must render the
# prefix section (--require prefix: hit rate, covered fraction, COW
# count) from the ON run's JSONL alone (~40 s).
#
# --blocksan-smoke: lint, then the round-18 block-lifecycle sanitizer
# cycle: one short disaggregated serve under PDT_BLOCKSAN=1 (preempt +
# swap so the trace crosses admit/prefix-share/COW/swap/restore/handoff/
# retire), then the SAME serve with an injected kv.swap_out_d2h fault —
# both runs' JSONLs must carry kind="sanitizer" quiesce records with
# ok=true and ZERO violation records (the shadow ledger matched the
# allocator even through the fault) (~40 s).
#
# --chaos-smoke: lint, then the round-19 replica-failure cycle: one
# 2-replica serve under PDT_BLOCKSAN=1 with an injected serve.dispatch
# kill (replica dies mid-flight, every stream recovers via re-dispatch)
# plus an already-expired admission (deadline shed), streamed to JSONL —
# then explain_request.py must find a redispatched rid by predicate,
# render its replica-hop chain, and close its span tree, and find the
# deadline rid's terminal outcome; the fleet_summary must carry the
# failure-plane counters. The fast chaos grid itself rides tier-1
# (tests/test_chaos_matrix.py, non-@slow); the full fault×state grid is
# @slow (~30 s).
#
# --soak-smoke: lint, then the round-21 scale-observatory cycle in
# miniature: ~2k heavy-tail sessions streamed through the 2-replica
# fleet with retention off (bench_serving.py --soak), the host-resource
# monitor + structure census + growth sentinel armed, and the metrics
# log capped small enough to force a rotation — the run must finish
# with the census verdict ok (zero bound violations, zero undeclared
# containers), a non-growing RSS verdict, and telemetry_report.py must
# render the resource AND census sections from the rotated JSONL alone
# (--require resource,census). The 100k-session run this miniaturizes
# is the @slow soak + the BENCH_r09 row (~60 s).
#
# --gateway-smoke: lint, then the round-22 HTTP front-door cycle under
# the block sanitizer: a 2-replica async fleet behind gateway.Gateway
# on an ephemeral port serves one request to completion over SSE and
# one that hangs up after its first token — the disconnect must reach
# FleetRouter.cancel (blocks freed; the drain's fleet-wide ledger
# quiesce proves it leak-free), explain_request.py --find cancelled
# must reconstruct the hung-up request's span tree closed
# outcome=cancelled, and telemetry_report.py must render the ingress
# section from the kind="http" records (--require http).
#
# --bench-regression: lint, then compare the two newest BENCH_r0N.json
# rounds key-by-key with per-key noise bands (scripts/bench_regression.py
# --auto); exits non-zero on any regression outside its band. Optional —
# run it when a new BENCH round lands.
#
# --warmup-smoke: lint, then the compile-cache round trip: prewarm a tiny
# LM serving registry into a fresh cache (scripts/warmup.py), re-run the
# prewarmer with --expect-hits (every program must now load from the
# persistent cache), then a cold-vs-warm serve cycle via
# scripts/bench_coldstart.py asserting the warm run's goodput compile
# fraction is below the cold run's (the full >=5x gate is
# bench_coldstart's default; the smoke uses --min-ratio 1.0 so a
# contended CI core can't flake it).
set -euo pipefail
cd "$(dirname "$0")/.."

if [[ "${1:-}" == "--lint-incremental" ]]; then
    echo "== jaxlint (incremental, content-hash cache) =="
    JAX_PLATFORMS=cpu python scripts/jaxlint.py --incremental \
        pytorch_distributed_tpu/
    exit 0
fi

echo "== jaxlint (full tree, 30s budget, SARIF artifact) =="
mkdir -p output
JAX_PLATFORMS=cpu python scripts/jaxlint.py pytorch_distributed_tpu/ \
    --sarif-out output/jaxlint.sarif --max-seconds 30

if [[ "${1:-}" == "--lint-only" ]]; then
    exit 0
fi

if [[ "${1:-}" == "--resilience-smoke" ]]; then
    echo "== resilience smoke (kill mid-shard-write, relaunch, resume) =="
    JAX_PLATFORMS=cpu python -m pytest tests/test_resilience.py -q \
        -m crash -k shard_write -p no:cacheprovider -p no:xdist \
        -p no:randomly
    exit 0
fi

if [[ "${1:-}" == "--reshard-smoke" ]]; then
    echo "== reshard smoke (kill on mesh (4,2), elastic resume on (2,2)) =="
    JAX_PLATFORMS=cpu python -m pytest \
        tests/test_reshard.py::test_reshard_smoke_kill_and_cross_mesh_resume \
        -q -p no:cacheprovider -p no:xdist -p no:randomly
    exit 0
fi

if [[ "${1:-}" == "--serving-smoke" ]]; then
    echo "== serving smoke (paged submit → decode → drain) =="
    JAX_PLATFORMS=cpu python -m pytest \
        tests/test_paged_serving.py::test_serving_smoke -q \
        -p no:cacheprovider -p no:xdist -p no:randomly
    exit 0
fi

if [[ "${1:-}" == "--kernel-smoke" ]]; then
    echo "== kernel smoke (pallas gather + quantized pools + split-S) =="
    # one full pallas-path serve cycle per pool dtype, token-identical
    # to the generate reference (interpret mode on CPU), the int8/fp8
    # logit-error bounds, the split-S-vs-single-worker parity bound
    JAX_PLATFORMS=cpu python -m pytest \
        tests/test_paged_kernel.py::test_kernel_smoke \
        tests/test_paged_kernel.py::test_int8_pool_logit_error_bound \
        tests/test_paged_kernel.py::test_fp8_pool_logit_error_bound \
        tests/test_paged_kernel.py::test_fp8_serve_cycle_split_s \
        tests/test_paged_kernel.py::test_split_s_matches_single_worker -q \
        -p no:cacheprovider -p no:xdist -p no:randomly
    exit 0
fi

if [[ "${1:-}" == "--fleet-smoke" ]]; then
    echo "== fleet smoke (trace -> 2-replica router + disagg P/D -> report) =="
    smoke=$(mktemp -d)
    trap 'rm -rf "$smoke"' EXIT
    JAX_PLATFORMS=cpu python scripts/bench_serving.py \
        --gen-trace "$smoke/trace.jsonl" --trace-duration 30 \
        --trace-base-rate 0.5 --trace-prompt-max 88
    JAX_PLATFORMS=cpu python recipes/serve_lm.py --tiny --replicas 2 \
        --slots 4 --max-new 8 --trace "$smoke/trace.jsonl" \
        --slo-ttft-ms 5000 --metrics-out "$smoke/fleet.jsonl"
    JAX_PLATFORMS=cpu python recipes/serve_lm.py --tiny --replicas 2 \
        --disaggregate --slots 4 --max-new 8 \
        --trace "$smoke/trace.jsonl" --metrics-out "$smoke/disagg.jsonl"
    JAX_PLATFORMS=cpu python scripts/telemetry_report.py \
        "$smoke/fleet.jsonl" "$smoke/disagg.jsonl" --json --require fleet
    exit 0
fi

if [[ "${1:-}" == "--warmup-smoke" ]]; then
    echo "== warmup smoke (prewarm → cache-hit gate → cold-vs-warm serve) =="
    smoke=$(mktemp -d)
    trap 'rm -rf "$smoke"' EXIT
    JAX_PLATFORMS=cpu python scripts/warmup.py --tiny \
        --compile-cache-dir "$smoke/cc" --slots 4 --json
    JAX_PLATFORMS=cpu python scripts/warmup.py --tiny \
        --compile-cache-dir "$smoke/cc" --slots 4 --expect-hits --json
    JAX_PLATFORMS=cpu python scripts/bench_coldstart.py --mode serve \
        --requests 24 --max-new 16 --min-ratio 1.0 \
        --json "$smoke/coldstart.json"
    JAX_PLATFORMS=cpu python scripts/telemetry_report.py \
        "$smoke/cc/warmup_manifest.jsonl" --json --require warmup
    exit 0
fi

if [[ "${1:-}" == "--pressure-smoke" ]]; then
    echo "== pressure smoke (over-committed serve -> preempt+restore, zero sheds) =="
    smoke=$(mktemp -d)
    trap 'rm -rf "$smoke"' EXIT
    JAX_PLATFORMS=cpu python scripts/bench_serving.py \
        --gen-trace "$smoke/trace.jsonl" --trace-duration 30 \
        --trace-base-rate 0.7 --trace-prompt-max 88
    JAX_PLATFORMS=cpu python recipes/serve_lm.py --tiny --replicas 2 \
        --slots 4 --n-blocks 13 --max-new 8 --preempt \
        --slo-shed-depth 4 --trace "$smoke/trace.jsonl" \
        --metrics-out "$smoke/pressure.jsonl"
    python - "$smoke/pressure.jsonl" <<'PY'
import json, sys
records = [json.loads(l) for l in open(sys.argv[1]) if l.strip()]
fleet = [r for r in records if r.get("kind") == "fleet_summary"][-1]
assert fleet["shed"] == 0, f"pressure tier shed {fleet['shed']} requests"
assert fleet["preempts"] >= 1, "over-committed cycle never preempted"
assert fleet["restores"] >= 1, "no preempted request was restored"
assert fleet["restores"] == fleet["preempts"], fleet
print(f"pressure: {fleet['preempts']} preempts, {fleet['restores']} "
      f"restores, 0 sheds, {fleet['swap_bytes']} swap bytes")
PY
    JAX_PLATFORMS=cpu python scripts/telemetry_report.py \
        "$smoke/pressure.jsonl" --json --require pressure
    exit 0
fi

if [[ "${1:-}" == "--trace-smoke" ]]; then
    echo "== trace smoke (disagg serve + forced preempt -> causal traces) =="
    smoke=$(mktemp -d)
    trap 'rm -rf "$smoke"' EXIT
    JAX_PLATFORMS=cpu python scripts/bench_serving.py \
        --gen-trace "$smoke/trace.jsonl" --trace-duration 30 \
        --trace-base-rate 0.7 --trace-prompt-max 88
    JAX_PLATFORMS=cpu python recipes/serve_lm.py --tiny --replicas 2 \
        --disaggregate --slots 4 --n-blocks 13 --max-new 8 \
        --preempt --swap-policy swap --trace "$smoke/trace.jsonl" \
        --metrics-out "$smoke/spans.jsonl"
    JAX_PLATFORMS=cpu python scripts/explain_request.py \
        "$smoke/spans.jsonl" --find handed-off --assert-complete
    JAX_PLATFORMS=cpu python scripts/explain_request.py \
        "$smoke/spans.jsonl" --find preempted --assert-complete \
        --perfetto "$smoke/requests.trace.json"
    python - "$smoke/requests.trace.json" <<'PY'
import json, sys
trace = json.load(open(sys.argv[1]))
events = trace["traceEvents"]
assert any(e.get("ph") == "X" for e in events), "no complete spans"
assert any(e.get("ph") == "s" for e in events), "no handoff flow arrows"
print(f"perfetto trace: {len(events)} events OK")
PY
    JAX_PLATFORMS=cpu python scripts/telemetry_report.py \
        "$smoke/spans.jsonl" --json --require spans
    exit 0
fi

if [[ "${1:-}" == "--prefix-smoke" ]]; then
    echo "== prefix smoke (shared-prompt trace -> radix reuse A/B -> report) =="
    smoke=$(mktemp -d)
    trap 'rm -rf "$smoke"' EXIT
    JAX_PLATFORMS=cpu python scripts/bench_serving.py \
        --gen-trace "$smoke/trace.jsonl" --trace-duration 30 \
        --trace-base-rate 0.5 --trace-sessions 8 \
        --trace-prompt-median 12 --trace-prompt-max 32 \
        --trace-max-new-median 6 --trace-max-new-max 12
    JAX_PLATFORMS=cpu python scripts/bench_serving.py --prefix \
        --trace "$smoke/trace.jsonl" --prefix-out "$smoke/prefix.jsonl" \
        > "$smoke/prefix.json"
    python - "$smoke/prefix.json" <<'PY'
import json, sys
row = json.loads(open(sys.argv[1]).read().strip().splitlines()[-1])
assert row["serving_prefix_hit_rate"] > 0, row
assert row["serving_prefix_tokens_identical"] is True, \
    "prefix sharing changed a greedy token stream"
ratio = row["serving_prefix_admit_tok_ratio_off_over_on"]
assert ratio >= 1.5, f"admitted-prefill tokens only {ratio}x lower"
print(f"prefix: hit rate {row['serving_prefix_hit_rate']:.0%}, "
      f"admitted-prefill tokens {ratio}x lower, "
      f"{row['serving_prefix_cow_copies']} cow copies, tokens identical "
      f"(backend={row['serving_prefix_backend']})")
PY
    JAX_PLATFORMS=cpu python scripts/telemetry_report.py \
        "$smoke/prefix.jsonl" --json --require prefix > /dev/null
    echo "prefix smoke OK"
    exit 0
fi

if [[ "${1:-}" == "--blocksan-smoke" ]]; then
    echo "== blocksan smoke (PDT_BLOCKSAN=1 serve, clean + faulted -> ledger ok) =="
    smoke=$(mktemp -d)
    trap 'rm -rf "$smoke"' EXIT
    JAX_PLATFORMS=cpu python scripts/bench_serving.py \
        --gen-trace "$smoke/trace.jsonl" --trace-duration 30 \
        --trace-base-rate 0.7 --trace-prompt-max 88
    # clean pass: disagg + preempt/swap so the ledger sees every
    # lifecycle edge (alloc, share, COW, swap-out/in, handoff, retire)
    JAX_PLATFORMS=cpu PDT_BLOCKSAN=1 python recipes/serve_lm.py --tiny \
        --replicas 2 --disaggregate --slots 4 --n-blocks 13 --max-new 8 \
        --preempt --swap-policy swap --trace "$smoke/trace.jsonl" \
        --metrics-out "$smoke/blocksan.jsonl"
    # faulted pass: first swap-out D2H gather dies mid-window — the
    # revert path must leave the ledger just as clean
    JAX_PLATFORMS=cpu PDT_BLOCKSAN=1 \
        PDT_FAULT_PLAN='{"faults":[{"site":"kv.swap_out_d2h","kind":"raise","at":1}]}' \
        python recipes/serve_lm.py --tiny \
        --replicas 2 --disaggregate --slots 4 --n-blocks 13 --max-new 8 \
        --preempt --swap-policy swap --trace "$smoke/trace.jsonl" \
        --metrics-out "$smoke/blocksan_fault.jsonl"
    python - "$smoke/blocksan.jsonl" "$smoke/blocksan_fault.jsonl" <<'PY'
import json, sys
for path in sys.argv[1:]:
    rows = [json.loads(l) for l in open(path) if l.strip()]
    san = [r for r in rows if r.get("kind") == "sanitizer"]
    bad = [r for r in san if r["ev"] == "violation"]
    quiesce = [r for r in san if r["ev"] == "quiesce"]
    assert not bad, f"{path}: blocksan violations: {bad}"
    assert quiesce, f"{path}: no quiesce record — sanitizer never armed"
    assert all(q["ok"] for q in quiesce), quiesce
    print(f"{path.rsplit('/', 1)[-1]}: {len(quiesce)} quiesce record(s) "
          f"ok, 0 violations")
PY
    echo "blocksan smoke OK"
    exit 0
fi

if [[ "${1:-}" == "--chaos-smoke" ]]; then
    echo "== chaos smoke (replica kill -> re-dispatch + deadline shed -> explain) =="
    smoke=$(mktemp -d)
    trap 'rm -rf "$smoke"' EXIT
    JAX_PLATFORMS=cpu python - "$smoke/chaos.jsonl" <<'PY'
import os
import sys

os.environ["PDT_BLOCKSAN"] = "1"
import jax
import jax.numpy as jnp
import numpy as np

from pytorch_distributed_tpu.fleet import FleetRouter
from pytorch_distributed_tpu.models.transformer import (
    TransformerLM, tiny_config,
)
from pytorch_distributed_tpu.resilience import faults
from pytorch_distributed_tpu.resilience.faults import FaultPlan, FaultSpec
from pytorch_distributed_tpu.telemetry.reqtrace import ReqTracer
from pytorch_distributed_tpu.utils.profiling import MetricsLogger

cfg = tiny_config(attention="dense", max_seq_len=96)
params = TransformerLM(cfg).init(
    jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
)["params"]
mlog = MetricsLogger(sys.argv[1])
router = FleetRouter(
    cfg, params, n_replicas=2, n_slots=3, block_len=8, prefill_chunk=8,
    fail_threshold=1, metrics_log=mlog, reqtrace=ReqTracer(sink=mlog),
)
rng = np.random.default_rng(0)
prompts = [rng.integers(1, cfg.vocab_size, (9 + i,)).astype(np.int32)
           for i in range(3)]
faults.install_plan(FaultPlan([
    FaultSpec(site="serve.dispatch", kind="raise", at=2, times=1)
]))
try:
    rids = [router.submit(p, 6) for p in prompts]
    # a request whose budget is already spent sheds at admission
    expired = router.submit(prompts[0], 6, deadline_s=-0.01)
    out = router.drain(max_steps=4000)
finally:
    faults.clear_plan()
assert all(len(out[r]) == 6 for r in rids), "a stream did not recover"
assert router.rejected[expired] == "deadline-expired"
m = router.metrics()
assert m["replica_deaths"] == 1 and m["redispatched"] >= 1, m
router.blocksan.assert_clean()
router.log_summary()
mlog.close()
print(f"chaos serve: {len(rids)} streams recovered off a dead replica, "
      f"1 deadline shed, ledger clean")
PY
    JAX_PLATFORMS=cpu python scripts/explain_request.py \
        "$smoke/chaos.jsonl" --find redispatched --assert-complete \
        | tee "$smoke/explain.txt"
    grep -q "replica hops:" "$smoke/explain.txt" \
        || { echo "explain output missing the replica-hop chain"; exit 1; }
    JAX_PLATFORMS=cpu python scripts/explain_request.py \
        "$smoke/chaos.jsonl" --find deadline --assert-complete \
        > "$smoke/deadline.txt"
    grep -q "terminal outcome: DEADLINE" "$smoke/deadline.txt" \
        || { echo "explain output missing the deadline outcome"; exit 1; }
    python - "$smoke/chaos.jsonl" <<'PY'
import json, sys
rows = [json.loads(l) for l in open(sys.argv[1]) if l.strip()]
from pytorch_distributed_tpu.telemetry.schema import validate_stream
assert validate_stream(rows) == [], validate_stream(rows)[:5]
health = [r for r in rows if r.get("kind") == "health"]
assert {"draining", "dead"} <= {r["state"] for r in health}, health
fleet = [r for r in rows if r.get("kind") == "fleet_summary"][-1]
assert fleet["replica_deaths"] == 1 and fleet["redispatched"] >= 1
print(f"telemetry: {len(health)} health transitions on the wire, "
      f"fleet_summary carries the failure plane")
PY
    echo "chaos smoke OK"
    exit 0
fi

if [[ "${1:-}" == "--soak-smoke" ]]; then
    echo "== soak smoke (2k-session stream -> census ok, flat RSS, report) =="
    smoke=$(mktemp -d)
    trap 'rm -rf "$smoke"' EXIT
    # small log cap so the rotation path is exercised, not just present
    JAX_PLATFORMS=cpu python scripts/bench_serving.py --soak \
        --soak-requests 2000 --soak-log "$smoke/soak.jsonl" \
        --soak-log-mb 0.25 > "$smoke/soak.json"
    python - "$smoke/soak.json" "$smoke/soak.jsonl" <<'PY'
import json, os, sys
row = json.loads(open(sys.argv[1]).read().strip().splitlines()[-1])
assert row["serving_soak_sessions"] == 2000, row["serving_soak_sessions"]
assert row["serving_soak_census_verdict"] == "ok", row
assert row["serving_soak_census_violations"] == 0, row
assert row["serving_soak_census_undeclared"] == 0, row
assert row["serving_soak_undeclared_at_start"] == 0, row
# 2k sessions is far too short for a slope claim; the gate is only
# that the sentinel did not see runaway growth at this scale
assert row["serving_soak_rss_verdict"] in ("flat", "linear", "insufficient"), row
assert row["serving_soak_rss_slope_mib_per_10k"] < 50.0, row
assert row["serving_soak_results_dropped"] > 0, \
    "streaming retention kept results — soak would accumulate them"
assert row["serving_soak_rotations"] >= 1, \
    "log cap never rotated — rotation path untested"
assert os.path.exists(sys.argv[2] + ".1"), "rotated mirror missing"
print(f"soak smoke: {row['serving_soak_completed']} completed / "
      f"{row['serving_soak_shed']} shed over {row['serving_soak_ticks']} "
      f"ticks, census ok ({row['serving_soak_census_sweeps']} sweeps, "
      f"worst bound {row['serving_soak_census_worst_frac']:.0%}), "
      f"rss {row['serving_soak_rss_mib_final']:.0f} MiB "
      f"({row['serving_soak_rss_verdict']}), "
      f"{row['serving_soak_rotations']} log rotation(s)")
PY
    JAX_PLATFORMS=cpu python scripts/telemetry_report.py \
        "$smoke/soak.jsonl" --json --require resource,census > /dev/null
    echo "soak smoke OK"
    exit 0
fi

if [[ "${1:-}" == "--gateway-smoke" ]]; then
    echo "== gateway smoke (SSE serve + mid-stream hangup -> cancel, ledger clean) =="
    smoke=$(mktemp -d)
    trap 'rm -rf "$smoke"' EXIT
    JAX_PLATFORMS=cpu python - "$smoke/gw.jsonl" <<'PY'
import os
import sys
import time

os.environ["PDT_BLOCKSAN"] = "1"
import jax
import jax.numpy as jnp
import numpy as np

from pytorch_distributed_tpu.fleet import FleetRouter
from pytorch_distributed_tpu.gateway import Gateway, generate, open_stream
from pytorch_distributed_tpu.models.transformer import (
    TransformerLM, tiny_config,
)
from pytorch_distributed_tpu.telemetry.reqtrace import ReqTracer
from pytorch_distributed_tpu.utils.profiling import MetricsLogger

cfg = tiny_config(attention="dense", max_seq_len=96)
params = TransformerLM(cfg).init(
    jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
)["params"]
mlog = MetricsLogger(sys.argv[1])
router = FleetRouter(
    cfg, params, n_replicas=2, n_slots=3, block_len=8, prefill_chunk=8,
    retain_results=False, metrics_log=mlog,
    reqtrace=ReqTracer(sink=mlog),
)
gw = Gateway(router, port=0, metrics_log=mlog)
gw.start()
base = f"http://127.0.0.1:{gw.port}"
rng = np.random.default_rng(0)
prompt = rng.integers(1, cfg.vocab_size, (9,)).astype(np.int32)
# request 1: a full SSE stream to completion over a real socket
out = generate(base, prompt, 6)
assert out["status"] == 200 and out["outcome"] == "complete", out
assert len(out["tokens"]) == 6, out
# request 2: hang up after the first token — the disconnect→cancel path
st = open_stream(base, prompt, 40)
next(st.events())
st.close()
deadline = time.time() + 30
while time.time() < deadline and gw.metrics()["gateway_cancels"] < 1:
    time.sleep(0.05)
assert gw.metrics()["gateway_cancels"] >= 1, gw.metrics()
gw.stop()
router.drain(max_steps=4000)
router.blocksan.assert_clean()
assert router.metrics()["cancelled"] >= 1, router.metrics()
router.log_summary()
mlog.close()
print("gateway serve: 1 stream completed, 1 hangup cancelled, "
      "ledger clean")
PY
    JAX_PLATFORMS=cpu python scripts/explain_request.py \
        "$smoke/gw.jsonl" --find cancelled --assert-complete \
        > "$smoke/cancel.txt"
    grep -q "terminal outcome: CANCELLED" "$smoke/cancel.txt" \
        || { echo "explain output missing the cancelled outcome"; exit 1; }
    JAX_PLATFORMS=cpu python scripts/telemetry_report.py \
        "$smoke/gw.jsonl" --json --require http > /dev/null
    # the two heavy gateway tests are @slow (fast tier sits ~60 s under
    # its cap); node-id selection ignores -m, so they run here instead
    JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider \
        -p no:xdist -p no:randomly \
        "tests/test_gateway.py::test_disconnect_storm_leaks_zero_blocks" \
        "tests/test_gateway.py::test_serve_lm_http_port_recipe"
    echo "gateway smoke OK"
    exit 0
fi

if [[ "${1:-}" == "--bench-regression" ]]; then
    echo "== bench regression (newest round vs previous, noise-banded) =="
    python scripts/bench_regression.py --auto --json
    # round 18: the bench numbers are only comparable if the sanitizer
    # really is detached when PDT_BLOCKSAN is unset
    JAX_PLATFORMS=cpu python scripts/bench_regression.py --blocksan-off
    exit 0
fi

if [[ "${1:-}" == "--obs-smoke" ]]; then
    echo "== observability smoke (hang -> anomaly; suspend -> dump; cost cards) =="
    smoke=$(mktemp -d)
    trap 'rm -rf "$smoke"' EXIT
    # CPU has no builtin roofline ceilings; pin synthetic ones so the
    # report's MFU/bound columns render (the numbers gate presence, not
    # magnitude)
    export PDT_PEAK_FLOPS=1e12 PDT_PEAK_GBS=100
    # run A: seeded hang at step 12 of 16 (--batch-size 1 -> 16 steps,
    # past the sentinel's warmup window) -> kind="anomaly"; fit-end cost
    # cards
    JAX_PLATFORMS=cpu \
        XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8" \
        PDT_FAULT_PLAN='{"faults":[{"site":"train.step","kind":"hang","at":12,"seconds":1.0}]}' \
        python recipes/lm_pretrain.py --tiny --epochs 1 --batch-size 1 \
        --save-dir "$smoke/lm" --metrics-out "$smoke/lm.jsonl" --cost-cards
    # run B: seeded suspend -> checkpoint-then-yield leaves the atomic
    # flight-recorder dump (exit 0 via the suspend path)
    JAX_PLATFORMS=cpu \
        XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8" \
        PDT_FAULT_PLAN='{"faults":[{"site":"train.step","kind":"suspend","at":4}]}' \
        python recipes/lm_pretrain.py --tiny --epochs 1 \
        --save-dir "$smoke/lm2" --metrics-out "$smoke/lm2.jsonl" || true
    python - "$smoke/lm2/flightrec_dump.json" <<'PY'
import json, sys
dump = json.load(open(sys.argv[1]))
assert dump["reason"] == "suspend" and dump["events"], dump.get("reason")
print(f"flight recorder: {len(dump['events'])} events, reason={dump['reason']}")
PY
    # serve cycle with cost cards
    JAX_PLATFORMS=cpu python recipes/serve_lm.py --tiny --requests 6 \
        --slots 4 --max-new 8 --metrics-out "$smoke/serve.jsonl" --cost-cards
    # the gate: roofline table + >=1 anomaly, from the JSONLs alone
    JAX_PLATFORMS=cpu python scripts/telemetry_report.py \
        "$smoke/lm.jsonl" "$smoke/serve.jsonl" --json --require cost,anomaly
    exit 0
fi

if [[ "${1:-}" == "--telemetry-smoke" ]]; then
    echo "== telemetry smoke (train + serve → JSONL → report) =="
    smoke=$(mktemp -d)
    trap 'rm -rf "$smoke"' EXIT
    # the tiny LM recipe needs the 8 virtual CPU devices its docstring
    # prescribes (dp2 × sp2 × tp1 by default)
    JAX_PLATFORMS=cpu \
        XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8" \
        python recipes/lm_pretrain.py --tiny --epochs 1 \
        --save-dir "$smoke/lm" --metrics-out "$smoke/lm.jsonl" \
        --flush-every 4 --trace-dir "$smoke/traces"
    JAX_PLATFORMS=cpu python recipes/serve_lm.py --tiny --requests 6 \
        --slots 4 --max-new 8 --metrics-out "$smoke/serve.jsonl"
    JAX_PLATFORMS=cpu python scripts/telemetry_report.py \
        "$smoke/lm.jsonl" "$smoke/serve.jsonl" --json \
        --require goodput,serving
    exit 0
fi

echo "== tier-1 tests =="
# the ROADMAP.md tier-1 verify command, verbatim
set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)"
exit $rc
