"""Ragged-serving throughput (VERDICT r3 #10 done-condition: measured
tok/s at batch 32).

GPT-2-small-shaped decode config, 32 requests with random prompt lengths
in [16, 256] right-padded to 256, greedy. Measures:

- ragged prefill latency (one batched causal forward, all 32 prompts);
- steady-state DECODE throughput (tokens/s across the 32 slots) via the
  chained generate_ragged scan — timing per PERF_NOTES.md (scalar-fetch
  sync, round-trip subtracted).

Usage: python scripts/bench_serving.py [--slots 32]
       python scripts/bench_serving.py --paged-latency   # TTFT/token p50/p95
       python scripts/bench_serving.py --paged-latency --trace T.jsonl
       python scripts/bench_serving.py --gen-trace T.jsonl [--trace-seed 0
           --trace-duration 240 --trace-base-rate 0.32 --trace-burst-mult 4
           --trace-prompt-median 24 --trace-prompt-max 96
           --trace-max-new-median 12 --trace-prefill-heavy]
       python scripts/bench_serving.py --fleet [--trace T.jsonl]   # 1r vs 2r
       python scripts/bench_serving.py --disagg [--trace T.jsonl]  # colo vs PD
       python scripts/bench_serving.py --pressure [--pressure-sessions 100000
           --pressure-blocks 13 --pressure-duration 90]  # preempt vs shed-only
       python scripts/bench_serving.py --soak [--soak-requests 100000
           --soak-log soak.jsonl --soak-slots 8 --soak-replicas 2]
           # round 21 scale observatory: stream >=100k unique-session
           # requests, census + RSS/host-wall growth fits (serving_soak_*)
       python scripts/bench_serving.py --http [--http-requests 48
           --http-replicas 2 --http-disconnect-every 6 --http-out h.jsonl]
           # round 22 front door: real sockets against gateway.Gateway —
           # over-the-wire TTFT, SSE gap p95, 429 rate at the door, and
           # cancel-to-block-free latency (serving_http_*)

Round 13 (pressure tier): ``--pressure`` replays one over-committed
bursty trace (default 100k session ids on a pool holding ~3 chains per
replica) through a shed-only fleet vs the same fleet with host offload
+ the SLO gate's preempt rung, and reports within-SLO goodput, shed
rates, preempt/restore counts, and swap p95 (``serving_pressure_*``).

Round 10 (fleet/): ``--gen-trace`` emits the reusable seeded
bursty/heavy-tail JSONL trace; ``--fleet`` replays ONE trace through a
1-replica and a 2-replica router at the SAME offered per-tick load and
reports goodput — completed tokens/s whose TTFT met the SLO —
(``serving_fleet_goodput_tok_s_*``); ``--disagg`` replays a
prefill-heavy bursty trace through two colocated mixed replicas vs a
disaggregated prefill+decode pair and reports the decode-token p95
(``serving_fleet_decode_token_p95_ms_*``). Both warm every replica
first so the A/B compares serving, not compile stalls.
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax
import jax.numpy as jnp
import numpy as np

from bench import measure_roundtrip_s  # noqa: E402  (scripts on path via cwd)


def _gpt2_model(max_seq_len=1024, dtype=None, **over):
    """One GPT-2-small-shaped serving config + init — shared by every
    measurement here so the stall numbers can never drift to a different
    model than the tick rate they are combined with."""
    from pytorch_distributed_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )

    cfg = TransformerConfig(
        vocab_size=32000, num_layers=12, num_heads=12, embed_dim=768,
        max_seq_len=max_seq_len,
        dtype=dtype if dtype is not None else jnp.bfloat16,
        attention="dense", **over,
    )
    params = TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return cfg, params


def measure(slots: int = 32, max_new: int = 64) -> dict:
    from pytorch_distributed_tpu.models.generate import (
        generate_ragged,
        ragged_prefill,
    )

    cfg, params = _gpt2_model()

    rng = np.random.default_rng(0)
    lengths = rng.integers(16, 257, slots).astype(np.int32)
    prompts = np.zeros((slots, 256), np.int32)
    for i, l in enumerate(lengths):
        prompts[i, :l] = rng.integers(1, cfg.vocab_size, l)
    prompts_j = jnp.asarray(prompts)
    lengths_j = jnp.asarray(lengths)

    # prefill latency (compile, then time the steady call)
    pf = jax.jit(lambda p, pr, ln: ragged_prefill(cfg, p, pr, ln))
    cache, last = pf(params, prompts_j, lengths_j)
    float(jnp.sum(last[:, :1]))
    t0 = time.perf_counter()
    cache, last = pf(params, prompts_j, lengths_j)
    float(jnp.sum(last[:, :1]))
    prefill_s = max(
        time.perf_counter() - t0 - measure_roundtrip_s(), 1e-6
    )

    # decode throughput: the full ragged generate (prefill + max_new
    # decode steps); subtract the measured prefill to isolate decode.
    # THREE runs, quoted median + min-max spread: serving decode showed a
    # ±14% run-to-run band on the 2026-07 runtime — a single sample
    # measures the run's weather, not the decoder.
    out = generate_ragged(cfg, params, prompts_j, lengths_j,
                          jax.random.key(1), max_new_tokens=max_new)
    int(np.asarray(out)[0, 0])  # compile + drain
    decode_rates = []
    for _ in range(3):
        t0 = time.perf_counter()
        out = generate_ragged(cfg, params, prompts_j, lengths_j,
                              jax.random.key(1), max_new_tokens=max_new)
        int(np.asarray(out)[0, 0])
        total_s = max(
            time.perf_counter() - t0 - measure_roundtrip_s(), 1e-6
        )
        decode_s = max(total_s - prefill_s, 1e-6)
        decode_rates.append(slots * max_new / decode_s)
    decode_tok_s = float(np.median(decode_rates))

    return {
        "serving_slots": slots,
        "serving_prompt_lens": f"{int(lengths.min())}-{int(lengths.max())}",
        "serving_max_new_tokens": max_new,
        "serving_prefill_ms": round(prefill_s * 1e3, 1),
        "serving_prefill_prompt_tok_s": round(
            float(lengths.sum()) / prefill_s
        ),
        "serving_decode_tok_s": round(decode_tok_s),
        "serving_decode_tok_s_min": round(min(decode_rates)),
        "serving_decode_tok_s_max": round(max(decode_rates)),
        # per-TICK latency (all slots advance one token per tick)
        "serving_decode_ms_per_token": round(
            slots * 1e3 / decode_tok_s, 2
        ),
        "device": str(jax.devices()[0]),
    }


def measure_admission_stall(slots: int = 32, n: int = 10,
                            tick_ms: float | None = None) -> dict:
    """Per-admission decode stall of the ContinuousBatcher (VERDICT r4
    next #7).

    ``submit`` runs a full batch-1 prefill + row insert while every
    active decode lane waits — that wall time IS the stall each
    admission imposes on the other ``slots-1`` requests. Measured as
    DEVICE program time (chained dispatch, one scalar sync, the measured
    value-fetch round trip subtracted — ~95 ms on the 2026-07 runtime,
    where it would have swamped the ~17 ms program; well under a
    millisecond on a local chip).
    Reported per prefill bucket, plus the closed-form steady-state
    throughput under Poisson arrivals at the equilibrium rate
    (every completed request replaced: λ_eq = slots / T_request), which
    is what a Poisson trace converges to when the system is kept full.
    """
    from pytorch_distributed_tpu.models.generate import ContinuousBatcher

    cfg, params = _gpt2_model()
    # the DENSE layout's stall — the number the paged engine exists to
    # beat; measure_paged_admission reports the paged counterpart
    b = ContinuousBatcher(cfg, params, n_slots=slots, prefill_bucket=128,
                          cache_layout="dense")

    rng = np.random.default_rng(0)
    out: dict = {"serving_stall_slots": slots}

    # per-bucket SUBMIT program time — prefill + in-program row insert
    # (one donated program; the standalone insert measured ~8 ms of
    # full-cache copy, which dies when the write shares the producer's
    # program). This wall time is exactly the stall every active decode
    # lane sees per admission.
    stall_by_bucket = {}
    slot = jnp.asarray(0)
    for width in (128, 256):
        prompt = jnp.asarray(
            rng.integers(1, cfg.vocab_size, (1, width)).astype(np.int32)
        )
        length = jnp.asarray([width - 7], jnp.int32)
        for _ in range(3):  # compile + settle donation/layout
            b.cache, b.logits = b._submit_one(
                params, prompt, length, b.cache, b.logits, slot
            )
        float(jnp.sum(b.logits[:1, :1]))
        t0 = time.perf_counter()
        for _ in range(n):
            b.cache, b.logits = b._submit_one(
                params, prompt, length, b.cache, b.logits, slot
            )
        float(jnp.sum(b.logits[:1, :1]))
        dt = time.perf_counter() - t0
        stall_by_bucket[width] = (
            max(dt - measure_roundtrip_s(), dt / 2) / n * 1e3
        )
        out[f"serving_admission_stall_ms_b{width}"] = round(
            stall_by_bucket[width], 2
        )

    # decode tick time from the spread-quoted headline measurement
    # (pass tick_ms when the caller already ran measure() — bench.py)
    if tick_ms is None:
        tick_ms = measure(slots=slots, max_new=64)[
            "serving_decode_ms_per_token"
        ]
    out["serving_decode_tick_ms"] = tick_ms

    # Steady state under Poisson arrivals at the equilibrium rate (system
    # kept full): each request = one admission stall + max_new ticks
    # shared with the other slots. Effective tok/s =
    # slots*max_new / (slots*stall + max_new*tick).
    stall = stall_by_bucket[256]  # median prompt ~200 tokens → 256 bucket
    for max_new in (64, 256):
        eff = slots * max_new / (
            slots * stall + max_new * tick_ms
        ) * 1e3
        out[f"serving_equilibrium_tok_s_new{max_new}"] = round(eff)
        out[f"serving_admission_overhead_frac_new{max_new}"] = round(
            slots * stall / (slots * stall + max_new * tick_ms), 3
        )
    return out


def measure_paged_admission(slots: int = 32, n: int = 10,
                            tick_ms: float | None = None) -> dict:
    """Per-admission cost of the PAGED engine (the round-6 tentpole) and
    the equilibrium short-output throughput model it implies — the
    admission-heavy workload where the dense layout paid its ~30% tax.

    An admission here is ``ContinuousBatcher.submit`` on the default
    paged layout: block-chain allocation (host) + one chunk program per
    prompt chunk writing into FRESH blocks — O(prompt), never touching
    resident KV. Timed as chained dispatch over ``n`` admissions into
    distinct slots with ONE sync, round-trip subtracted (same method as
    the dense stall). Reported per prefill-chunk bucket alongside the
    same closed-form equilibrium throughput the dense measurement uses,
    so ``serving_paged_admission_overhead_frac_new64`` is directly
    comparable with ``serving_admission_overhead_frac_new64``.
    """
    from pytorch_distributed_tpu.models.generate import ContinuousBatcher

    cfg, params = _gpt2_model()
    b = ContinuousBatcher(cfg, params, n_slots=slots, prefill_bucket=128)
    rng = np.random.default_rng(0)
    out: dict = {
        "serving_paged_block_len": b.engine.block_len,
        "serving_paged_chunk": b.engine.chunk,
    }

    stall_by_bucket = {}
    for width in (128, 256):
        prompt = rng.integers(
            1, cfg.vocab_size, (width - 7,)
        ).astype(np.int32)
        for _ in range(2):  # compile + settle donation
            b.submit(prompt, 1)
            b.step()  # budget 1: retires, frees the slot and its blocks
        jax.block_until_ready(b.logits)
        t0 = time.perf_counter()
        for _ in range(n):
            b.submit(prompt, 1)
        jax.block_until_ready(b.logits)
        dt = time.perf_counter() - t0
        while any(b.remaining > 0):
            b.step()
        stall_by_bucket[width] = (
            max(dt - measure_roundtrip_s(), dt / 2) / n * 1e3
        )
        out[f"serving_paged_admission_stall_ms_b{width}"] = round(
            stall_by_bucket[width], 2
        )

    if tick_ms is None:
        tick_ms = measure(slots=slots, max_new=64)[
            "serving_decode_ms_per_token"
        ]
    stall = stall_by_bucket[256]
    for max_new in (64, 256):
        eff = slots * max_new / (slots * stall + max_new * tick_ms) * 1e3
        out[f"serving_paged_equilibrium_tok_s_new{max_new}"] = round(eff)
        out[f"serving_paged_admission_overhead_frac_new{max_new}"] = round(
            slots * stall / (slots * stall + max_new * tick_ms), 3
        )
    return out


def measure_paged_latency(slots: int = 16, requests: int = 48,
                          max_new: int = 32, trace=None,
                          tick_s: float = 1.0) -> dict:
    """End-to-end latency percentiles of the paged scheduler under a
    queued multi-tenant workload (ISSUE 4: the one metric a
    vLLM/Orca-style continuous batcher exists to control, previously
    unreported). Drives ``serving.Scheduler`` with ``requests`` random
    prompts (3x oversubscribed vs ``slots``), exact host-side TTFT /
    per-output-token / queue-wait series from the scheduler's own
    timestamps — no extra syncs beyond the token fetch every tick
    already pays.

    Pass ``trace`` (round 10: a ``fleet.traffic`` trace, e.g. from
    ``--gen-trace``) to replace the all-at-once equilibrium submission
    with seeded bursty heavy-tail arrivals replayed in the step domain
    — the same file the fleet benches consume."""
    from pytorch_distributed_tpu.serving import Scheduler

    cfg, params = _gpt2_model()
    rng = np.random.default_rng(0)
    sched = Scheduler(cfg, params, n_slots=slots, prefill_chunk=64,
                      admit_per_step=4)
    if trace is not None:
        from pytorch_distributed_tpu.fleet import (
            clamp_trace,
            prompt_for,
            replay_trace,
        )

        trace = clamp_trace(trace, cfg.max_seq_len, sched.engine.chunk)
        requests = len(trace)
        replay_trace(
            trace,
            lambda r: sched.submit(prompt_for(r, cfg.vocab_size),
                                   r.max_new),
            sched.step,
            lambda: not sched.queue and not sched.resident,
            tick_s=tick_s,
        )
    else:
        lens = rng.integers(16, 257, requests)
        for l in lens:
            sched.submit(
                rng.integers(1, cfg.vocab_size,
                             size=int(l)).astype(np.int32),
                max_new,
            )
        sched.drain()
    m = sched.metrics()
    out = {
        "serving_paged_lat_slots": slots,
        "serving_paged_lat_requests": requests,
        "serving_paged_lat_traffic": (
            "trace" if trace is not None else "equilibrium"
        ),
        "serving_paged_lat_max_new": max_new,
        "serving_paged_tokens_per_s": round(m["tokens_per_s"], 1),
    }
    for name in ("ttft", "token_lat", "queue_wait"):
        for q in ("p50", "p95"):
            key = f"{name}_{q}_s"
            if key in m:
                out[f"serving_paged_{name}_{q}_ms"] = round(
                    m[key] * 1e3, 2
                )
    return out


# ---------------------------------------------------------------------------
# fleet layer (round 10): traces, router goodput A/B, disaggregation A/B
# ---------------------------------------------------------------------------


def _tiny_model(max_seq_len=128):
    """Tiny fp32 config for the fleet benches — the router simulation's
    point is scheduling/latency structure, not model FLOPs, and the
    GPT-2 shape would put a CPU A/B in the minutes."""
    import jax.numpy as jnp

    from pytorch_distributed_tpu.models.transformer import (
        TransformerLM,
        tiny_config,
    )

    cfg = tiny_config(attention="dense", max_seq_len=max_seq_len,
                      dtype=jnp.float32)
    params = TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return cfg, params


def default_fleet_trace(seed: int = 0, prefill_heavy: bool = False):
    """The bench's stock bursty heavy-tail trace, sized so ~0.46
    requests arrive per tick — above one 4-slot replica's ~0.29/tick
    service capacity (≈ ceil(prompt/chunk) + max_new ticks per request)
    and below two replicas' — the regime where the router A/B is
    meaningful. ``prefill_heavy`` doubles prompt lengths and halves
    outputs (the disaggregation stressor)."""
    from pytorch_distributed_tpu.fleet import generate_trace

    return generate_trace(
        seed=seed, duration_s=240.0, base_rate=0.5,
        burst_rate_mult=4.0, burst_every_s=40.0, burst_len_s=6.0,
        sessions=16,
        prompt_median=48 if prefill_heavy else 24, prompt_sigma=0.8,
        prompt_min=4, prompt_max=96,
        max_new_median=6 if prefill_heavy else 12, max_new_sigma=0.6,
        max_new_min=2, max_new_max=24,
    )


def _replay_fleet(cfg, params, trace, n_replicas, *, disaggregate=False,
                  slo=None, slots=4, tick_s=1.0, warmup=True,
                  seed=0, **router_kwargs):
    """Build a router, warm it, replay the trace; returns
    ``(router, per-request records, wall_s, ticks)`` — records read back
    from a throwaway JSONL stream so goodput-within-SLO can be computed
    from the same per-request schema telemetry_report consumes."""
    import json as _json
    import tempfile

    from pytorch_distributed_tpu.fleet import (
        FleetRouter,
        prompt_for,
        replay_trace,
    )
    from pytorch_distributed_tpu.utils.profiling import MetricsLogger

    with tempfile.NamedTemporaryFile(mode="r", suffix=".jsonl") as tf:
        mlog = MetricsLogger(tf.name)
        router = FleetRouter(
            cfg, params, n_replicas=n_replicas,
            disaggregate=disaggregate, slo=slo, seed=seed,
            metrics_log=mlog, n_slots=slots, block_len=16,
            prefill_chunk=32, admit_per_step=4, **router_kwargs,
        )
        if warmup:
            router.warmup()
        t0 = time.perf_counter()
        ticks = replay_trace(
            trace,
            lambda r: router.submit(prompt_for(r, cfg.vocab_size),
                                    r.max_new, session=r.session),
            router.step,
            lambda: router.idle,
            tick_s=tick_s,
        )
        wall = time.perf_counter() - t0
        mlog.close()
        records = [_json.loads(line) for line in tf.read().splitlines()
                   if line.strip()]
    return router, records, wall, ticks


def _goodput_tok_per_s(records, ticks: int, tick_s: float,
                       slo_ttft_ticks: float) -> float:
    """Completed tokens per NOMINAL second within the SLO: only requests
    whose step-domain TTFT met the target count — the metric a fleet
    exists to maximize (raw tokens/s rewards serving a backlog nobody is
    waiting for). Both the TTFT and the denominator live in the step
    domain (ticks x nominal tick_s): the single-process simulation turns
    every replica's crank from one host loop, so machine wall time is
    shared across replicas and would misprice an N-replica fleet that
    real deployments run on N times the hardware; tick latencies measure
    the SCHEDULE, identically on any host."""
    good = sum(
        r.get("new_tokens", 0) for r in records
        if r.get("kind") == "request" and not r.get("rejected")
        and r.get("ttft_steps", float("inf")) <= slo_ttft_ticks
    )
    return good / max(ticks * tick_s, 1e-9)


def measure_fleet(trace=None, slo_ttft_ticks: float | None = None,
                  slots: int = 4) -> dict:
    """The router A/B (acceptance: ISSUE 7): ONE bursty heavy-tail
    trace, same offered per-tick load, served by 1 replica vs 2 — the
    2-replica router must sustain higher goodput (tokens per nominal
    second whose step-domain TTFT met the SLO; see
    ``_goodput_tok_per_s`` for why the accounting lives in ticks). The
    SLO defaults to 3x the 2-replica fleet's own TTFT p95 in ticks —
    "what a provisioned fleet achieves, with headroom"; the gate (spill
    at queue 4, shed at 24) is identical in both runs, so the single
    replica queues past the SLO and sheds where the pair spills."""
    from pytorch_distributed_tpu.fleet import SLOConfig
    from pytorch_distributed_tpu.telemetry import percentiles

    cfg, params = _tiny_model()
    if trace is None:
        trace = default_fleet_trace()
    slo = SLOConfig(spill_queue_depth=4, shed_queue_depth=24)
    r2, rec2, _, ticks2 = _replay_fleet(cfg, params, trace, 2, slo=slo,
                                        slots=slots)
    r1, rec1, _, ticks1 = _replay_fleet(cfg, params, trace, 1, slo=slo,
                                        slots=slots)
    m2, m1 = r2.metrics(), r1.metrics()

    def ttft_ticks_p95(records):
        ps = percentiles(
            [r["ttft_steps"] for r in records
             if r.get("kind") == "request" and "ttft_steps" in r],
            qs=(95,),
        )
        return ps.get("p95", 0.0)

    if slo_ttft_ticks is None:
        slo_ttft_ticks = 3.0 * max(ttft_ticks_p95(rec2), 1.0)
    g2 = _goodput_tok_per_s(rec2, ticks2, 1.0, slo_ttft_ticks)
    g1 = _goodput_tok_per_s(rec1, ticks1, 1.0, slo_ttft_ticks)
    return {
        "serving_fleet_trace_requests": len(trace),
        "serving_fleet_slots_per_replica": slots,
        "serving_fleet_slo_ttft_ticks": round(slo_ttft_ticks, 1),
        "serving_fleet_goodput_tok_s_1r": round(g1, 2),
        "serving_fleet_goodput_tok_s_2r": round(g2, 2),
        "serving_fleet_goodput_ratio_2r_over_1r": round(
            g2 / max(g1, 1e-9), 2
        ),
        "serving_fleet_shed_rate_1r": round(m1["shed_rate"], 4),
        "serving_fleet_shed_rate_2r": round(m2["shed_rate"], 4),
        "serving_fleet_spill_rate_2r": round(m2["spill_rate"], 4),
        "serving_fleet_ttft_p95_ticks_1r": round(ttft_ticks_p95(rec1), 1),
        "serving_fleet_ttft_p95_ticks_2r": round(ttft_ticks_p95(rec2), 1),
        "serving_fleet_recommend_peak_1r": m1["recommended_replicas_peak"],
        "device": str(jax.devices()[0]),
    }


def measure_disagg(trace=None, slots: int = 4) -> dict:
    """The disaggregation A/B (acceptance: ISSUE 7): a prefill-heavy
    bursty trace through (a) two COLOCATED mixed replicas and (b) one
    prefill + one decode replica (decode sized 2x — a decode slot is
    held ~max_new ticks vs ~ceil(prompt/chunk) for prefill; sizing roles
    independently is disaggregation's point).

    The headline is decode-token p95 as REPLICA-ATTRIBUTED latency —
    the wall cost of the serving replica's own token-producing tick
    (``Scheduler.tick_lat``). Colocated, a resident stream's token is
    data-dependent on the chunk program sharing its pool and device, so
    prefill bursts land inside every stream's tick; disaggregated, the
    decode replica's tick runs decode only and the burst cost collapses
    into the counted, timed KV handoffs. (The raw inter-token wall gap
    is reported too, but in this one-loop single-host simulation it
    sums EVERY replica's step — real fleets run replicas on separate
    hosts — so the replica-attributed number is the honest one; same
    simulation-correction argument as the step-domain goodput.) TTFT
    for both sides is reported — the handoff queue makes disaggregated
    TTFT worse; that tradeoff is the point."""
    cfg, params = _tiny_model()
    if trace is None:
        trace = default_fleet_trace(prefill_heavy=True)
    rc, recc, _, _ = _replay_fleet(cfg, params, trace, 2, slots=slots)
    rd, recd, _, _ = _replay_fleet(cfg, params, trace, 2,
                                   disaggregate=True, slots=slots,
                                   decode_slots=2 * slots,
                                   handoffs_per_tick=2)
    mc, md = rc.metrics(), rd.metrics()

    def tick_p95_ms(router, roles):
        from pytorch_distributed_tpu.telemetry import percentiles

        vals = [v for s, role in zip(router.replicas, router.roles)
                if role in roles for v in s.tick_lat.values]
        return percentiles(vals, qs=(95,)).get("p95", 0.0) * 1e3

    def gap_p95_ms(records):
        from pytorch_distributed_tpu.telemetry import percentiles

        gaps = [g for r in records if r.get("kind") == "request"
                for g in r.get("token_gaps_s", [])]
        return percentiles(gaps, qs=(95,)).get("p95", 0.0) * 1e3

    pc = tick_p95_ms(rc, ("mixed",))
    pd = tick_p95_ms(rd, ("decode",))
    return {
        "serving_fleet_disagg_trace_requests": len(trace),
        "serving_fleet_decode_token_p95_ms_colocated": round(pc, 2),
        "serving_fleet_decode_token_p95_ms_disagg": round(pd, 2),
        "serving_fleet_decode_p95_ratio_colo_over_disagg": round(
            pc / max(pd, 1e-9), 2
        ),
        "serving_fleet_loop_gap_p95_ms_colocated": round(
            gap_p95_ms(recc), 2
        ),
        "serving_fleet_loop_gap_p95_ms_disagg": round(
            gap_p95_ms(recd), 2
        ),
        "serving_fleet_handoffs": md["handoffs"],
        "serving_fleet_handoff_ms_mean": round(
            md.get("handoff_mean_s", 0.0) * 1e3, 2
        ),
        "serving_fleet_ttft_p95_ms_colocated": round(
            mc.get("ttft_p95_s", 0.0) * 1e3, 1
        ),
        "serving_fleet_ttft_p95_ms_disagg": round(
            md.get("ttft_p95_s", 0.0) * 1e3, 1
        ),
        "device": str(jax.devices()[0]),
    }


def measure_tp_virtual(slots: int = 8, tp: int = 2) -> dict:
    """TP batcher decode rate on the VIRTUAL CPU mesh — a functionality
    row, not a performance claim (tp>1 needs more chips than this
    environment has; re-measure on real multi-chip hardware). Parity is
    tested in tests/test_serving_tp.py."""
    import dataclasses

    from pytorch_distributed_tpu.models.generate import generate_ragged_tp
    from pytorch_distributed_tpu.parallel import make_mesh

    if len(jax.devices()) < tp:
        return {"serving_tp_error": f"needs {tp} devices"}
    # ONE init with the replicated twin (a TP config cannot init outside
    # shard_map — tp_reduce's psum has no axis); the TP cfg is a replace
    rep, params = _gpt2_model(max_seq_len=512, dtype=jnp.float32)
    cfg = dataclasses.replace(rep, model_axis="model", tp_size=tp)
    mesh = make_mesh(jax.devices()[:tp], data_parallel=1, seq_parallel=1,
                     model_parallel=tp)
    rng = np.random.default_rng(0)
    lengths = rng.integers(16, 129, slots).astype(np.int32)
    prompts = np.zeros((slots, 128), np.int32)
    for i, l in enumerate(lengths):
        prompts[i, :l] = rng.integers(1, cfg.vocab_size, l)
    args = (jnp.asarray(prompts), jnp.asarray(lengths),
            jax.random.key(1))
    out = generate_ragged_tp(mesh, cfg, params, *args, max_new_tokens=16)
    int(np.asarray(out)[0, 0])
    t0 = time.perf_counter()
    out = generate_ragged_tp(mesh, cfg, params, *args, max_new_tokens=16)
    int(np.asarray(out)[0, 0])
    dt = time.perf_counter() - t0
    return {
        "serving_tp_virtual_tok_s": round(slots * 16 / dt),
        "serving_tp_degree": tp,
        "serving_tp_note": "virtual CPU mesh: functionality, not perf",
    }


def measure_pressure(trace=None, slots: int = 4, n_blocks: int = 13,
                     sessions: int = 100_000,
                     duration_s: float = 90.0) -> dict:
    """The pressure-tier A/B (ISSUE 11): ONE over-committed bursty trace
    (sessions ≫ pool chains — default 100k session ids over a pool that
    holds ~3 chains per replica) served by (a) a shed-only fleet (the
    pre-round-13 ladder: queue then reject) and (b) the same fleet with
    the KV pressure tier on (host offload + the SLO gate's preempt
    rung). The headline is goodput — completed tokens per nominal
    second whose step-domain TTFT met the SLO (same accounting as
    ``measure_fleet``) — plus the shed rates the preempt rung exists to
    zero and the measured swap walls behind the decision model."""
    from pytorch_distributed_tpu.fleet import SLOConfig, generate_trace
    from pytorch_distributed_tpu.telemetry import percentiles

    cfg, params = _tiny_model()
    if trace is None:
        trace = generate_trace(
            seed=0, duration_s=duration_s, base_rate=0.7,
            burst_rate_mult=4.0, burst_every_s=20.0, burst_len_s=4.0,
            sessions=sessions,
            prompt_median=24, prompt_sigma=0.8, prompt_min=4,
            prompt_max=96, max_new_median=10, max_new_sigma=0.6,
            max_new_min=2, max_new_max=24,
        )
    slo = SLOConfig(spill_queue_depth=2, shed_queue_depth=8)
    shed_only, rec_s, _, ticks_s = _replay_fleet(
        cfg, params, trace, 2, slo=slo, slots=slots, n_blocks=n_blocks,
    )
    pressured, rec_p, _, ticks_p = _replay_fleet(
        cfg, params, trace, 2, slo=slo, slots=slots, n_blocks=n_blocks,
        offload=True, preempt_on_oom=True,
    )
    ms, mp = shed_only.metrics(), pressured.metrics()

    def ttft_ticks_p95(records):
        ps = percentiles(
            [r["ttft_steps"] for r in records
             if r.get("kind") == "request" and "ttft_steps" in r],
            qs=(95,),
        )
        return ps.get("p95", 0.0)

    slo_ttft_ticks = 3.0 * max(ttft_ticks_p95(rec_p), 1.0)
    g_shed = _goodput_tok_per_s(rec_s, ticks_s, 1.0, slo_ttft_ticks)
    g_pre = _goodput_tok_per_s(rec_p, ticks_p, 1.0, slo_ttft_ticks)
    swaps = [r for r in rec_p if r.get("kind") == "swap" and r.get("ok")]
    swap_walls = [r["wall_s"] for r in swaps if "wall_s" in r]
    swap_p95 = percentiles(swap_walls, qs=(95,)).get("p95", 0.0)
    return {
        "serving_pressure_trace_requests": len(trace),
        "serving_pressure_sessions": sessions,
        "serving_pressure_pool_blocks": n_blocks,
        "serving_pressure_slo_ttft_ticks": round(slo_ttft_ticks, 1),
        "serving_pressure_goodput_tok_s_shed_only": round(g_shed, 2),
        "serving_pressure_goodput_tok_s_preempt": round(g_pre, 2),
        "serving_pressure_goodput_ratio": round(
            g_pre / max(g_shed, 1e-9), 2
        ),
        "serving_pressure_shed_rate_shed_only": round(
            ms["shed_rate"], 4
        ),
        "serving_pressure_shed_rate_preempt": round(mp["shed_rate"], 4),
        "serving_pressure_sheds_preempt": mp["shed"],
        "serving_pressure_preempts": mp["preempts"],
        "serving_pressure_restores": mp["restores"],
        "serving_pressure_swap_mib": round(
            mp["swap_bytes"] / 2**20, 2
        ),
        "serving_pressure_swap_p95_ms": round(swap_p95 * 1e3, 3),
        "device": str(jax.devices()[0]),
    }


def measure_prefix(trace=None, slots: int = 8, prefix_len: int = 64,
                   replicas: int = 2, out_path: str = None) -> dict:
    """The prefix-sharing A/B (ISSUE 15): ONE seeded shared-system-
    prompt trace — every request is a ``prefix_len``-token shared
    system prefix plus its own heavy-tail tail
    (``fleet.shared_prefix_prompt_for``) — served by the same 2-replica
    session-affinity fleet with the radix prefix cache OFF and ON.

    Headline: **admitted-prefill tokens per request** (the prompt
    tokens the chunk programs actually process at admission — a hit
    skips its covered prefix; the acceptance gate wants >= 2x lower
    with sharing on) plus admission latency, fresh pool blocks
    allocated per request, hit rate, COW copies, and a token-identity
    check (greedy streams must be bit-equal across the A/B, prefix off
    vs on). Wall-millisecond magnitudes are backend-marked
    (``gather_ab_backend`` convention): on the CPU simulation they
    describe host scheduling, not TPU serving."""
    import dataclasses as _dc
    import tempfile

    from pytorch_distributed_tpu.fleet import (
        FleetRouter,
        SLOConfig,
        generate_trace,
        replay_trace,
        shared_prefix_prompt_for,
    )
    from pytorch_distributed_tpu.utils.profiling import MetricsLogger

    cfg, params = _tiny_model()
    if trace is None:
        trace = generate_trace(
            seed=0, duration_s=120.0, base_rate=0.5,
            burst_rate_mult=4.0, burst_every_s=30.0, burst_len_s=4.0,
            sessions=8,
            prompt_median=12, prompt_sigma=0.8, prompt_min=4,
            prompt_max=32, max_new_median=6, max_new_sigma=0.6,
            max_new_min=2, max_new_max=12,
        )
    # fit prefix + tail + decode budget into the config (the shared
    # prefix rides on TOP of the trace's prompt_len)
    tail_max = max(4, (cfg.max_seq_len - prefix_len) // 3)
    new_max = max(2, (cfg.max_seq_len - prefix_len) // 8)
    trace = [
        _dc.replace(r, prompt_len=min(r.prompt_len, tail_max),
                    max_new=min(r.max_new, new_max))
        for r in trace
    ]
    slo = SLOConfig(spill_queue_depth=4, shed_queue_depth=64,
                    prefix_sticky_depth=8)

    def run(prefix_on, path):
        mlog = MetricsLogger(path)
        router = FleetRouter(
            cfg, params, n_replicas=replicas, slo=slo, seed=0,
            metrics_log=mlog, n_slots=slots, block_len=16,
            prefill_chunk=32, admit_per_step=4,
            prefix_cache=prefix_on,
        )
        router.warmup()
        t0 = time.perf_counter()
        ticks = replay_trace(
            trace,
            lambda r: router.submit(
                shared_prefix_prompt_for(r, cfg.vocab_size, prefix_len),
                r.max_new, session=r.session,
            ),
            router.step,
            lambda: router.idle,
        )
        wall = time.perf_counter() - t0
        m = router.metrics()
        router.log_summary()
        # exact admission latency across the fleet (weighted by each
        # replica's admissions, steps and wall both)
        per = [s.metrics() for s in router.replicas]
        admitted = sum(p["admitted"] for p in per) or 1
        adm_steps = sum(
            p["admission_latency_steps_mean"] * p["admitted"] for p in per
        ) / admitted
        adm_s = sum(
            p["admission_latency_s_mean"] * p["admitted"] for p in per
        ) / admitted
        fresh = sum(
            s.engine.allocator.fresh_allocated for s in router.replicas
        )
        m["admitted"] = sum(p["admitted"] for p in per)
        mlog.close()
        return router, m, ticks, wall, adm_steps, adm_s, fresh

    with tempfile.NamedTemporaryFile(suffix=".jsonl") as tf:
        r_off, m_off, _, wall_off, st_off, s_off, fresh_off = run(
            False, tf.name
        )
    r_on, m_on, _, wall_on, st_on, s_on, fresh_on = run(
        True, out_path if out_path else None
    )
    reqs = max(m_on["completed"], 1)
    tok_on = m_on["admitted_prefill_tokens"] / max(m_on["admitted"], 1)
    tok_off = m_off["admitted_prefill_tokens"] / max(m_off["admitted"], 1)
    identical = r_on.results == r_off.results
    return {
        "serving_prefix_trace_requests": len(trace),
        "serving_prefix_prefix_len": prefix_len,
        "serving_prefix_replicas": replicas,
        "serving_prefix_hit_rate": round(m_on["prefix_hit_rate"], 4),
        "serving_prefix_covered_frac": round(
            m_on["prefix_covered_tokens"]
            / max(m_on["prefix_covered_tokens"]
                  + m_on["admitted_prefill_tokens"], 1), 4
        ),
        "serving_prefix_admit_tok_per_req_on": round(tok_on, 2),
        "serving_prefix_admit_tok_per_req_off": round(tok_off, 2),
        "serving_prefix_admit_tok_ratio_off_over_on": round(
            tok_off / max(tok_on, 1e-9), 2
        ),
        "serving_prefix_fresh_blocks_per_req_on": round(
            fresh_on / max(m_on["admitted"], 1), 2
        ),
        "serving_prefix_fresh_blocks_per_req_off": round(
            fresh_off / max(m_off["admitted"], 1), 2
        ),
        "serving_prefix_admission_steps_mean_on": round(st_on, 2),
        "serving_prefix_admission_steps_mean_off": round(st_off, 2),
        "serving_prefix_admission_ms_mean_on": round(s_on * 1e3, 3),
        "serving_prefix_admission_ms_mean_off": round(s_off * 1e3, 3),
        "serving_prefix_cow_copies": m_on["prefix_cow_copies"],
        "serving_prefix_evictions": m_on["prefix_evictions"],
        "serving_prefix_shared_blocks_peak": m_on["prefix_shared_blocks"],
        "serving_prefix_completed": reqs,
        "serving_prefix_tokens_identical": identical,
        "serving_prefix_wall_s_on": round(wall_on, 2),
        "serving_prefix_wall_s_off": round(wall_off, 2),
        # CPU-honesty label (gather_ab_backend convention, PR 10): the
        # token-accounting claims hold anywhere; the wall/ms magnitudes
        # are TPU claims only when this says tpu
        "serving_prefix_backend": jax.default_backend(),
        "device": str(jax.devices()[0]),
    }


# ---------------------------------------------------------------------------
# scale observatory soak (round 21): the ROADMAP-item-5 100k-session run
# ---------------------------------------------------------------------------


def measure_soak(requests: int = 100_000, out_path: str | None = None,
                 seed: int = 0, slots: int = 8, replicas: int = 2,
                 every_ticks: int | None = None,
                 log_max_bytes: int = 4 << 20) -> dict:
    """The scale-observatory soak (ISSUE 19 / ROADMAP item 5): stream a
    ``requests``-session heavy-tail trace — every request its OWN
    session id, the million-user shape that stresses the affinity LRU
    hardest — through a ``replicas``-replica fleet, and prove host cost
    O(live batch), not O(sessions ever):

    - the trace is NEVER materialized (``iter_trace``/``replay_stream``,
      one-request lookahead) and the router runs streaming retention
      (``retain_results=False``), so the harness itself is O(live);
    - ``ResourceMonitor`` samples RSS + mean per-tick host wall on a
      tick-count cadence into the rotating MetricsLogger JSONL
      (rotation is exercised — the per-request records alone overflow
      ``log_max_bytes`` many times over);
    - ``StructCensus`` sweeps every declared container in the fleet on
      the same cadence (undeclared containers or bound violations fail
      the run's verdict);
    - ``GrowthSentinel``/``fit_growth`` regress RSS and per-tick wall
      against cumulative sessions; slopes are quoted per 10k sessions.

    HONESTY (``serving_soak_backend``): on the shared-CPU runner the
    wall slope is a smoke alarm (neighbors steal the core; the MAD
    floor absorbs it), while the RSS slope and the census verdict are
    real host-memory claims on any backend — see ANALYSIS.md "Scale
    observatory". Profiling that is O(launches) stays OFF (no
    reqtrace): per-tick wall comes from the monitor.
    """
    import tempfile

    from pytorch_distributed_tpu.fleet import (
        FleetRouter,
        iter_trace,
        prompt_for,
        replay_stream,
    )
    from pytorch_distributed_tpu.telemetry import (
        GrowthSentinel,
        ResourceMonitor,
        StructCensus,
        rss_mib,
        undeclared_containers,
    )
    from pytorch_distributed_tpu.utils.profiling import MetricsLogger

    cfg, params = _tiny_model()
    # Sample cadence: ~256 ticks at soak scale, scaled down for smokes
    # so short runs still give the fits >= min_samples points.
    if every_ticks is None:
        every_ticks = max(8, min(256, requests // 32))
    tmp = None
    if out_path is None:
        tmp = tempfile.TemporaryDirectory()
        out_path = os.path.join(tmp.name, "soak.jsonl")
    mlog = MetricsLogger(out_path, max_bytes=log_max_bytes)
    router = FleetRouter(
        cfg, params, n_replicas=replicas, seed=seed, metrics_log=mlog,
        n_slots=slots, block_len=16, prefill_chunk=32, admit_per_step=8,
        retain_results=False, prefix_cache=True,
    )
    router.warmup()
    census = StructCensus(mlog)
    census.register_many(router.census_owners())
    monitor = ResourceMonitor(mlog, every_ticks=every_ticks,
                              gc_objects=True, tracemalloc_every=32,
                              top_sites=5)
    census.register("monitor", monitor)
    sentinel = GrowthSentinel()
    census.register("sentinel", sentinel)
    undeclared_at_start = sorted(
        u for name, obj in census.owners()
        for u in undeclared_containers(obj))
    rss0, rss_src = rss_mib()

    submitted = [0]
    peak_live = [0]
    worst = [0.0, ""]  # max worst_ratio across sweeps + its structure

    def submit(r):
        router.submit(prompt_for(r, cfg.vocab_size), r.max_new,
                      session=r.session)
        submitted[0] += 1

    def tick():
        t0 = time.perf_counter()
        router.step()
        dt = time.perf_counter() - t0
        live = router.live_requests()
        if live > peak_live[0]:
            peak_live[0] = live
        rec = monitor.tick(live=live, cumulative=submitted[0], wall_s=dt)
        if rec is not None:
            sweep = census.sweep(live=live, replicas=replicas,
                                 tick=monitor.ticks, live_slack=4 * slots)
            # The observatory's own rings (monitor history, sentinel
            # series) grow by construction until their caps fill; the
            # census audits those caps. Size-growth flags are for the
            # FLEET's structures.
            sentinel.observe_sizes(submitted[0], {
                k: v for k, v in sweep["structures"].items()
                if not k.startswith(("monitor.", "sentinel."))})
            if sweep["worst_ratio"] > worst[0]:
                worst[0], worst[1] = sweep["worst_ratio"], sweep["worst_name"]

    # Offered load ~1.6 req/tick against ~2.3 req/tick of fleet service
    # capacity (ceil(prompt/chunk) + max_new slot-ticks per request):
    # heavily loaded, never divergent. duration_s is an over-generous
    # horizon; islice ends the stream at exactly ``requests``.
    import itertools

    arrivals = itertools.islice(
        iter_trace(seed=seed, duration_s=1e12, base_rate=2.0,
                   burst_rate_mult=4.0, burst_every_s=40.0,
                   burst_len_s=6.0, prompt_median=16, prompt_max=64,
                   max_new_median=6, max_new_max=12,
                   unique_sessions=True),
        requests,
    )
    t_start = time.perf_counter()
    ticks = replay_stream(arrivals, submit, tick,
                          lambda: router.idle, tick_s=0.6)
    wall = time.perf_counter() - t_start
    final = monitor.sample(live=router.live_requests(),
                           cumulative=submitted[0])
    census.sweep(live=router.live_requests(), replicas=replicas,
                 tick=monitor.ticks, live_slack=4 * slots)
    m = router.metrics()
    mlog.close()
    monitor.close()

    # Growth fits against cumulative sessions. RSS gets a tight relative
    # floor (0.5% of the level — the jax runtime's ~1 GiB baseline would
    # otherwise hide tens of MiB of leak behind the default 5%); the
    # shared-CPU wall series keeps the default.
    from pytorch_distributed_tpu.telemetry import fit_growth

    rss_fit = fit_growth(*monitor.rss_series(), rel_floor=0.005,
                         abs_floor=1.0)
    wall_fit = fit_growth(*monitor.wall_series(), abs_floor=0.05)
    out = {
        "serving_soak_backend": jax.default_backend(),
        "serving_soak_sessions": submitted[0],
        "serving_soak_completed": m["completed"],
        "serving_soak_shed": m["shed"],
        "serving_soak_ticks": ticks,
        "serving_soak_wall_s": round(wall, 1),
        "serving_soak_rss_source": rss_src,
        "serving_soak_rss_mib_start": round(rss0, 1),
        "serving_soak_rss_mib_final": round(final["rss_mib"], 1),
        "serving_soak_rss_slope_mib_per_10k": round(
            rss_fit["slope"] * 1e4, 3),
        "serving_soak_rss_verdict": rss_fit["verdict"],
        "serving_soak_host_wall_slope_ms_per_10k": round(
            wall_fit["slope"] * 1e4, 4),
        "serving_soak_host_wall_verdict": wall_fit["verdict"],
        "serving_soak_census_sweeps": census.sweeps,
        "serving_soak_census_violations": census.total_violations,
        "serving_soak_census_undeclared": census.total_undeclared,
        "serving_soak_census_verdict": census.verdict(),
        "serving_soak_census_worst_frac": round(worst[0], 4),
        "serving_soak_census_worst_name": worst[1],
        "serving_soak_undeclared_at_start": len(undeclared_at_start),
        "serving_soak_size_flags": ",".join(
            f for f in sentinel.flags()) or "none",
        "serving_soak_peak_live": peak_live[0],
        "serving_soak_results_dropped": m["results_dropped"],
        "serving_soak_rotations": mlog.rotations,
        "serving_soak_tokens_out": m["tokens_out"],
        "serving_soak_tokens_per_s": round(
            m["tokens_out"] / max(wall, 1e-9), 1),
        "device": str(jax.devices()[0]),
    }
    if tmp is not None:
        tmp.cleanup()
    return out


def measure_http(requests: int = 48, seed: int = 0, slots: int = 4,
                 replicas: int = 2, disconnect_every: int = 6,
                 max_conc: int = 8, time_scale: float = 0.05,
                 out_path: str | None = None) -> dict:
    """The HTTP front door measured OVER THE WIRE (ISSUE 20): a real
    socket per request against ``gateway.Gateway`` on an ephemeral
    port, paced by the stock bursty trace (time-scaled so the bench
    stays in seconds). Every ``disconnect_every``-th request hangs up
    after its first token — the disconnect→cancel path is part of the
    steady state being measured, not a separate scenario.

    Reports what in-process benches cannot see: TTFT measured at the
    socket (``serving_http_ttft_wire_*`` — admission + first decode +
    serialization + kernel send), the inter-token stream gap p95 (the
    SSE jitter a client actually experiences), the 429 shed rate at
    the door, and the cancel-to-block-free latency (socket close →
    ``FleetRouter.cancel`` freed the KV blocks).

    HONESTY (``serving_http_backend``): loopback TCP on a shared CPU
    host — wire latencies carry the host's scheduler noise and a tiny
    model's decode rate; magnitudes are structural (is TTFT dominated
    by queueing? do gaps spike at bursts?), not device claims.
    """
    import itertools
    import tempfile
    import threading

    from pytorch_distributed_tpu.fleet import (
        FleetRouter,
        iter_trace,
        prompt_for,
    )
    from pytorch_distributed_tpu.gateway import (
        Gateway,
        generate,
        open_stream,
    )
    from pytorch_distributed_tpu.utils.profiling import MetricsLogger

    cfg, params = _tiny_model()
    tmp = None
    if out_path is None:
        tmp = tempfile.TemporaryDirectory()
        out_path = os.path.join(tmp.name, "http.jsonl")
    mlog = MetricsLogger(out_path)
    router = FleetRouter(
        cfg, params, n_replicas=replicas, seed=seed, metrics_log=mlog,
        n_slots=slots, block_len=16, prefill_chunk=32,
        retain_results=False,
    )
    router.warmup()
    gw = Gateway(router, port=0, metrics_log=mlog)
    gw.start()
    base = f"http://127.0.0.1:{gw.port}"

    trace = list(itertools.islice(
        iter_trace(seed=seed, duration_s=1e12, base_rate=2.0,
                   burst_rate_mult=4.0, burst_every_s=40.0,
                   burst_len_s=6.0, prompt_median=16, prompt_max=64,
                   max_new_median=6, max_new_max=12,
                   unique_sessions=True),
        requests,
    ))
    statuses: list = []
    disconnects = [0]
    gate = threading.Semaphore(max_conc)
    lock = threading.Lock()

    def run_one(i, req, t_start):
        # pace to the (scaled) trace arrival, bounded concurrency
        delay = req.t * time_scale - (time.perf_counter() - t_start)
        if delay > 0:
            time.sleep(delay)
        prompt = prompt_for(req, cfg.vocab_size, seed=seed)
        with gate:
            if disconnect_every and i % disconnect_every == \
                    disconnect_every - 1:
                try:
                    st = open_stream(base, prompt, req.max_new,
                                     session=req.session, timeout=60.0)
                    next(st.events())
                    st.close()
                    with lock:
                        statuses.append(200)
                        disconnects[0] += 1
                except Exception:
                    with lock:
                        statuses.append(-1)
                return
            out = generate(base, prompt, req.max_new,
                           session=req.session, timeout=60.0)
            with lock:
                statuses.append(out["status"])

    t_start = time.perf_counter()
    threads = [threading.Thread(target=run_one, args=(i, r, t_start),
                                daemon=True)
               for i, r in enumerate(trace)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300.0)
    wall = time.perf_counter() - t_start
    gm = gw.metrics()
    gw.stop()
    router.drain(max_steps=20_000)
    m = router.metrics()
    mlog.close()

    served = sum(1 for s in statuses if s == 200)
    shed = sum(1 for s in statuses if s == 429)
    out = {
        "serving_http_backend": jax.default_backend(),
        "serving_http_requests": len(statuses),
        "serving_http_served": served,
        "serving_http_shed": shed,
        "serving_http_429_rate": round(shed / max(len(statuses), 1), 4),
        "serving_http_errors": sum(1 for s in statuses
                                   if s not in (200, 429)),
        "serving_http_disconnects": disconnects[0],
        "serving_http_cancelled": m["cancelled"],
        "serving_http_wall_s": round(wall, 2),
        "serving_http_tokens_out": m["tokens_out"],
        "serving_http_ttft_wire_p50_ms": round(
            gm.get("gateway_ttft_wire_p50_s", 0.0) * 1e3, 2),
        "serving_http_ttft_wire_p95_ms": round(
            gm.get("gateway_ttft_wire_p95_s", 0.0) * 1e3, 2),
        "serving_http_gap_p95_ms": round(
            gm.get("gateway_gap_p95_s", 0.0) * 1e3, 2),
        "serving_http_worst_gap_ms": gm.get("gateway_worst_gap_ms", 0.0),
        "serving_http_cancel_free_p95_ms": round(
            gm.get("gateway_cancel_free_p95_s", 0.0) * 1e3, 2),
        "serving_http_bytes_out": gm.get("gateway_bytes_out", 0),
        "device": str(jax.devices()[0]),
    }
    if tmp is not None:
        tmp.cleanup()
    return out


def link_probe(mb: int = 16, reps: int = 5) -> dict:
    """Same-run bandwidth/link probe, co-quoted with every serving bench
    row (ISSUE 8, ADVICE §6 — the ckpt bench's same-minute disk-probe
    pattern applied to serving): cross-day serving swings on the 2026-07
    runtime tracked the LINK and the shared host, not the engine, so each
    row carries the medium it was measured through.

    Three rates, median of ``reps``: host memcpy (the shared-box
    contention proxy — the round-5 stall transients were pure user-time
    memcpy slowdowns), host→device put, and device→host get of the same
    buffer (the ~24 MB/s device→host hazard PERF_NOTES §1 documents)."""
    import numpy as np

    buf = np.ones(mb * 2**20, np.uint8)

    def med(f):
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            f()
            times.append(time.perf_counter() - t0)
        return float(np.median(times))

    host_s = med(lambda: buf.copy())
    dev = None

    def h2d():
        nonlocal dev
        dev = jax.block_until_ready(jax.device_put(buf))

    h2d_s = med(h2d)
    d2h_s = med(lambda: np.asarray(jax.device_get(dev)))
    return {
        "probe_mb": mb,
        "probe_host_memcpy_mb_s": round(mb / host_s, 1),
        "probe_h2d_mb_s": round(mb / h2d_s, 1),
        "probe_d2h_mb_s": round(mb / d2h_s, 1),
    }


def _argval(flag: str, default, cast=float):
    if flag in sys.argv:
        return cast(sys.argv[sys.argv.index(flag) + 1])
    return default


def _cli_trace():
    """--trace PATH → loaded trace (or None)."""
    path = _argval("--trace", None, str)
    if path is None:
        return None
    from pytorch_distributed_tpu.fleet import load_trace

    return load_trace(path)


def main() -> None:
    slots = 32
    if "--slots" in sys.argv:
        slots = int(sys.argv[sys.argv.index("--slots") + 1])
    if "--gen-trace" in sys.argv:
        from pytorch_distributed_tpu.fleet import generate_trace, save_trace

        path = sys.argv[sys.argv.index("--gen-trace") + 1]
        heavy = "--trace-prefill-heavy" in sys.argv
        kw = dict(
            seed=_argval("--trace-seed", 0, int),
            duration_s=_argval("--trace-duration", 240.0),
            base_rate=_argval("--trace-base-rate", 0.32),
            burst_rate_mult=_argval("--trace-burst-mult", 4.0),
            burst_every_s=_argval("--trace-burst-every", 40.0),
            burst_len_s=_argval("--trace-burst-len", 6.0),
            sessions=_argval("--trace-sessions", 16, int),
            prompt_median=_argval("--trace-prompt-median",
                                  48 if heavy else 24, int),
            prompt_max=_argval("--trace-prompt-max", 96, int),
            max_new_median=_argval("--trace-max-new-median",
                                   6 if heavy else 12, int),
            max_new_max=_argval("--trace-max-new-max", 24, int),
        )
        trace = generate_trace(**kw)
        save_trace(path, trace, **kw)
        print(json.dumps({"trace_path": path, "requests": len(trace), **kw}))
        return
    # same-run link probe co-quoted with every measured row (ADVICE §6):
    # a cross-day swing in any serving number below is attributable —
    # either the probes moved with it (environment weather) or they
    # didn't (a real engine change)
    probe = link_probe()
    if "--fleet" in sys.argv:
        print(json.dumps({**measure_fleet(
            trace=_cli_trace(),
            slo_ttft_ticks=_argval("--slo-ttft-ticks", None),
        ), **probe}))
        return
    if "--disagg" in sys.argv:
        print(json.dumps({**measure_disagg(trace=_cli_trace()), **probe}))
        return
    if "--prefix" in sys.argv:
        print(json.dumps({**measure_prefix(
            trace=_cli_trace(),
            slots=_argval("--prefix-slots", 8, int),
            prefix_len=_argval("--prefix-len", 64, int),
            replicas=_argval("--prefix-replicas", 2, int),
            out_path=_argval("--prefix-out", None, str),
        ), **probe}))
        return
    if "--soak" in sys.argv:
        print(json.dumps({**measure_soak(
            requests=_argval("--soak-requests", 100_000, int),
            out_path=_argval("--soak-log", None, str),
            slots=_argval("--soak-slots", 8, int),
            replicas=_argval("--soak-replicas", 2, int),
            every_ticks=_argval("--soak-every", None, int),
            log_max_bytes=int(_argval("--soak-log-mb", 4.0) * 2**20),
        ), **probe}))
        return
    if "--http" in sys.argv:
        print(json.dumps({**measure_http(
            requests=_argval("--http-requests", 48, int),
            slots=_argval("--http-slots", 4, int),
            replicas=_argval("--http-replicas", 2, int),
            disconnect_every=_argval("--http-disconnect-every", 6, int),
            out_path=_argval("--http-out", None, str),
        ), **probe}))
        return
    if "--pressure" in sys.argv:
        print(json.dumps({**measure_pressure(
            trace=_cli_trace(),
            slots=_argval("--pressure-slots", 4, int),
            n_blocks=_argval("--pressure-blocks", 13, int),
            sessions=_argval("--pressure-sessions", 100_000, int),
            duration_s=_argval("--pressure-duration", 90.0),
        ), **probe}))
        return
    if "--stall" in sys.argv:
        print(json.dumps({**measure_admission_stall(slots), **probe}))
        return
    if "--paged-stall" in sys.argv:
        print(json.dumps({**measure_paged_admission(slots), **probe}))
        return
    if "--paged-latency" in sys.argv:
        print(json.dumps({**measure_paged_latency(trace=_cli_trace()),
                          **probe}))
        return
    if "--tp-virtual" in sys.argv:
        print(json.dumps({**measure_tp_virtual(), **probe}))
        return
    print(json.dumps({**measure(slots), **probe}))


if __name__ == "__main__":
    main()
