"""Paged-KV continuous-batching serving demo (round 6 tentpole).

Drives ``serving.Scheduler`` — the block-pooled KV cache with O(prompt)
admission, chunked prefill interleaved with decode, FIFO queueing on pool
OOM — over a synthetic multi-tenant workload, and prints the scheduler's
exact host-side metrics (occupancy, padding waste, admission latency,
queue depth, tokens/s). Zero required args; CPU-runnable:

    python recipes/serve_lm.py --tiny                 # CPU smoke
    python recipes/serve_lm.py --requests 64 --slots 16 --max-new 32
    python recipes/serve_lm.py --dense                # r4 layout A/B

``--dense`` runs the same workload through the legacy dense
``ContinuousBatcher`` layout (one max_seq_len KV row per slot, admission
copying the full row) for an on-box A/B of the admission tax the paged
engine removes; ANALYSIS.md "Serving engine" documents the design.

Telemetry (round 7; ANALYSIS.md "Observability & goodput"):
``--metrics-out serve.jsonl`` streams one ``kind="request"`` record per
retirement (queue wait, TTFT, inter-token gaps) plus a final
``kind="serving_summary"`` with the scheduler's percentile metrics —
feed it to ``scripts/telemetry_report.py`` for TTFT/per-token p50/p95;
``--trace-dir DIR`` says where the process's span stream (always
recorded: ``telemetry.spans``) is written at exit, as a Chrome trace,
``DIR/spans.trace.json``; each program's launch lies there split into
``engine.{chunk,decode}.build``, ``.put`` and ``.call``, no profiler needed.

Elastic load (round 9; ANALYSIS.md "Elastic topology & reshard"):
``--restore CKPT`` serves a TRAINER checkpoint — sharded directory or
legacy single file, written on ANY mesh shape — with the params
re-partitioned from the serving rule table at ``--tp N``'s degree
(reading only the params blocks, never the optimizer moments):

    python recipes/serve_lm.py --tiny --restore out_lm/latest.ckpt --tp 2

Fleet (round 10; ANALYSIS.md "Serving fleet"): ``--replicas N`` serves
through ``fleet.FleetRouter`` — N single-process replica engines with
session-affinity routing and the SLO admission gate (``--slo-ttft-ms``
sets the TTFT target it spills/sheds against); ``--disaggregate`` splits
the replicas into prefill-only and decode roles with KV-block handoff
(``--prefill-replicas`` sizes the split); ``--trace T.jsonl`` replays a
seeded bursty heavy-tail traffic trace (``scripts/bench_serving.py
--gen-trace``) instead of the all-at-once synthetic workload:

    python scripts/bench_serving.py --gen-trace /tmp/t.jsonl --trace-duration 30
    python recipes/serve_lm.py --tiny --replicas 2 --trace /tmp/t.jsonl \
        --slo-ttft-ms 500 --metrics-out fleet.jsonl
    python recipes/serve_lm.py --tiny --replicas 2 --disaggregate

KV pressure (round 13; ANALYSIS.md "KV pressure & preemption"):
``--preempt`` turns memory pressure into preemptions instead of waits
or sheds — idle chains swap to a host-RAM block store (or recompute,
whichever the measured cost card says is cheaper) and restore before
their next tick; ``--n-blocks`` sizes the pool small to provoke it:

    python recipes/serve_lm.py --tiny --requests 24 --slots 4 \
        --n-blocks 12 --preempt --metrics-out pressure.jsonl

Request tracing (round 14; ANALYSIS.md "Request-lifecycle tracing"):
whenever ``--metrics-out`` is on, every request's lifecycle rides the
JSONL as a causal span tree (``kind="span"``: gate decision → queue →
prefill → handoff → decode windows → preempt/park/restore → retire).
``scripts/explain_request.py`` reconstructs any rid's story and
``--assert-complete`` gates on a closed acyclic tree; ``--swap-policy
swap`` forces the preemption path the trace smoke audits
(predicted-vs-measured swap wall in every preempt span):

    python recipes/serve_lm.py --tiny --replicas 2 --disaggregate \
        --preempt --swap-policy swap --metrics-out spans.jsonl
    python scripts/explain_request.py spans.jsonl --find preempted

Front door (round 22; ANALYSIS.md "Front door"): ``--http-port PORT``
(0 picks an ephemeral port, printed at startup) serves the fleet over
HTTP instead of replaying the synthetic workload — ``POST
/v1/generate`` streams tokens as Server-Sent Events with
``X-Deadline-Ms`` mapped onto the admission deadline, SLO sheds
surfacing as 429 + ``Retry-After``, and client disconnects cancelling
the request (KV blocks freed, span tree closed ``outcome=cancelled``);
``GET /v1/health`` is the round-19 health plane and ``/metrics`` the
Prometheus text. ``--http-duration`` bounds the serve window:

    python recipes/serve_lm.py --tiny --replicas 2 --http-port 8080 \
        --slo-ttft-ms 500 --metrics-out http.jsonl

Cold start (round 8; ANALYSIS.md "Cold start & compile cache"):
``--warmup`` compiles every registry program (decode tick + all prefill
buckets) before admitting traffic, and ``--compile-cache-dir`` points
jax's persistent compilation cache at a directory so a relaunched server
loads those programs from disk — ``scripts/warmup.py`` prewarms the
cache out-of-band and ``scripts/bench_coldstart.py`` proves the
compile-fraction collapse.
"""

from common import parse_args  # noqa: F401  (bootstraps sys.path)

import argparse
import json
import time

import numpy as np

import pytorch_distributed_tpu as pdt

pdt.set_env("202607")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from pytorch_distributed_tpu.models.generate import (  # noqa: E402
    ContinuousBatcher,
)
from pytorch_distributed_tpu.models.transformer import (  # noqa: E402
    TransformerConfig,
    TransformerLM,
    tiny_config,
)
from pytorch_distributed_tpu.serving import Scheduler  # noqa: E402
from pytorch_distributed_tpu.utils.logging import rank0_print  # noqa: E402


def _parse(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--tiny", action="store_true",
                   help="tiny config (CPU smoke)")
    p.add_argument("--requests", type=int, default=24,
                   help="synthetic requests to serve")
    p.add_argument("--slots", type=int, default=8, help="decode lanes")
    p.add_argument("--max-new", type=int, default=16,
                   help="decode budget per request")
    p.add_argument("--block-len", type=int, default=16,
                   help="KV block length (paged layout)")
    p.add_argument("--n-blocks", type=int, default=None,
                   help="KV pool size in blocks (default: capacity "
                        "parity with the dense layout; set it SMALL to "
                        "over-commit the pool and exercise the round-13 "
                        "pressure tier)")
    # KV pressure tier (round 13; ANALYSIS.md "KV pressure & preemption")
    p.add_argument("--preempt", action="store_true",
                   help="enable the KV pressure tier: host-RAM offload "
                        "+ preempt-and-restore. Single scheduler: pool "
                        "OOM preempts the LRU resident chain instead of "
                        "making the queue wait for a retirement. Fleet: "
                        "the SLO gate's preempt rung turns would-be "
                        "sheds into cheap preemptions")
    p.add_argument("--swap-policy", choices=("auto", "swap", "recompute"),
                   default="auto",
                   help="preemption path: 'auto' takes the measured "
                        "swap-vs-recompute crossover per request; "
                        "'swap'/'recompute' force one side (the trace "
                        "smoke forces swap so the predicted-vs-measured "
                        "wall lands in every preempt span)")
    p.add_argument("--slo-shed-depth", type=int, default=None,
                   help="fleet shed queue depth (with --preempt the "
                        "gate preempts instead of shedding at this "
                        "bound; spill bound is set to a quarter of it)")
    p.add_argument("--prefill-chunk", type=int, default=32,
                   help="prefill chunk length (paged) / bucket (dense)")
    p.add_argument("--admit-per-step", type=int, default=4,
                   help="max admissions per scheduler tick")
    p.add_argument("--chunk-bucket-floor", type=int, nargs=2,
                   default=(1, 1), metavar=("JOBS", "WIDTH"),
                   help="narrowest chunk-prefill bucket (padded jobs, "
                        "table-slice width in blocks; powers of two): "
                        "fewer programs to compile and load at start-up "
                        "for some padding a chunk")
    p.add_argument("--max-chunk-jobs", type=int, default=None,
                   help="most prompts one chunk-prefill program takes a "
                        "tick (the oldest first; default: every slot): "
                        "with --chunk-bucket-floor it bounds the chunk "
                        "programs to compile")
    p.add_argument("--kv-dtype", choices=("int8", "fp8", "fp8_e5m2"),
                   default=None,
                   help="quantize the KV block pool: 'int8' (+fp32 "
                        "per-row scales, ~2D/(D+4) blocks at fixed pool "
                        "bytes) or 'fp8'/'fp8_e5m2' (e4m3/e5m2 + int8 "
                        "exponent scales, ~2D/(D+1))")
    p.add_argument("--prefix-cache", action="store_true",
                   help="round-17 prefix-sharing KV cache: radix reuse "
                        "of full prompt blocks with copy-on-write — a "
                        "shared-system-prompt request admits in O(new "
                        "tokens); greedy streams stay token-identical")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--dense", action="store_true",
                   help="run the r4 dense layout instead (A/B reference)")
    p.add_argument("--metrics-out", default=None,
                   help="JSONL telemetry stream: per-request latency "
                        "records + a serving_summary (read with "
                        "scripts/telemetry_report.py)")
    p.add_argument("--trace-dir", default=None,
                   help="where the span stream (always recorded; "
                        "router.*/sched.*/engine.*/program.load/req.queue) "
                        "is written at exit: <dir>/spans.trace.json")
    # Compile cache (compilecache/; ANALYSIS.md "Cold start & compile
    # cache"). Example — prewarm once, then every server start is warm:
    #   python scripts/warmup.py --tiny --compile-cache-dir /tmp/cc
    #   python recipes/serve_lm.py --tiny --warmup --compile-cache-dir /tmp/cc
    p.add_argument("--compile-cache-dir", default=None,
                   help="persistent XLA compilation cache directory "
                        "(default <repo>/.jax_cache; an exported "
                        "JAX_COMPILATION_CACHE_DIR wins over this flag): "
                        "a relaunched server loads its bucket programs "
                        "from disk instead of recompiling mid-traffic")
    p.add_argument("--warmup", action="store_true",
                   help="compile every registry program (decode tick + "
                        "all prefill buckets) before admitting traffic — "
                        "zero cold requests; paged layout only")
    # Elastic load (reshard/; ANALYSIS.md "Elastic topology & reshard"):
    # serve a TRAINER checkpoint at whatever TP degree this fleet runs —
    # the params are re-partitioned from the serving rule table, never
    # from the layout the trainer saved (a dp4xtp2 training checkpoint
    # serves on tp1 single-chip replicas or a tp4 latency mesh alike).
    p.add_argument("--restore", default=None, metavar="CKPT",
                   help="load model params from a trainer checkpoint "
                        "(sharded dir or legacy single file) instead of "
                        "random init — any writer topology")
    p.add_argument("--tp", type=int, default=1,
                   help="serving tensor-parallel degree (needs that many "
                        "devices; params are placed per the serving TP "
                        "rules at THIS degree, whatever degree wrote the "
                        "checkpoint)")
    # Fleet (fleet/; ANALYSIS.md "Serving fleet")
    p.add_argument("--replicas", type=int, default=1,
                   help="serve through a FleetRouter with this many "
                        "replicas (session-affinity routing + SLO "
                        "admission gate); 1 without --trace/--disaggregate "
                        "keeps the single-scheduler path")
    p.add_argument("--disaggregate", action="store_true",
                   help="split replicas into prefill-only and decode "
                        "roles with KV-block handoff (needs --replicas "
                        ">= 2)")
    p.add_argument("--prefill-replicas", type=int, default=1,
                   help="prefill replicas when --disaggregate")
    p.add_argument("--slo-ttft-ms", type=float, default=None,
                   help="TTFT p95 target for the admission gate: a "
                        "replica whose live p95 exceeds it is spilled "
                        "around; every replica past the shed queue "
                        "depth => explicit reject")
    p.add_argument("--trace", default=None, metavar="JSONL",
                   help="replay a traffic trace (bench_serving.py "
                        "--gen-trace) instead of submitting the "
                        "synthetic workload all at once")
    # Attribution & forensics (telemetry/; ANALYSIS.md "Performance
    # attribution & forensics")
    p.add_argument("--cost-cards", action="store_true",
                   help="after the serve cycle, emit one "
                        "kind=\"program_cost\" record per registry "
                        "program (compiler FLOPs/bytes joined with "
                        "measured tick wall → MFU/roofline; "
                        "telemetry_report.py renders the table). "
                        "AOT-compiles every not-yet-compiled bucket "
                        "once, after traffic; paged layout only")
    p.add_argument("--metrics-port", type=int, default=None,
                   help="serve live Prometheus-text /metrics while the "
                        "cycle runs (stdlib HTTP thread)")
    p.add_argument("--http-port", type=int, default=None, metavar="PORT",
                   help="serve the HTTP/SSE front door (gateway/) on "
                        "PORT (0 = ephemeral) instead of replaying the "
                        "synthetic workload: POST /v1/generate streams "
                        "tokens, GET /v1/health is the health plane, "
                        "/metrics the Prometheus text; implies the "
                        "fleet layout and streaming retention")
    p.add_argument("--http-duration", type=float, default=10.0,
                   help="seconds to keep the front door up "
                        "(--http-port)")
    p.add_argument("--model-json", default=None, metavar="FILE",
                   help="build the model from a JSON file's ``program`` "
                        "block (TransformerConfig's fields by name; with "
                        "--tiny its ``tiny`` block's), e.g. "
                        "perfbench/configs/ouro-2.6b.json, instead of the "
                        "default 12-layer GPT-2 block")
    return p.parse_args(argv)


def _model(args):
    tp = dict(model_axis="model", tp_size=args.tp) if args.tp > 1 else {}
    if args.model_json:
        import json

        with open(args.model_json) as f:
            described = json.load(f)
        if args.tiny:
            described = described["tiny"]
        cfg = TransformerConfig(**described["program"], attention="dense",
                                dropout=0.0, **tp)
    elif args.tiny:
        cfg = tiny_config(attention="dense", max_seq_len=128, **tp)
    else:
        cfg = TransformerConfig(
            vocab_size=32_000, num_layers=12, num_heads=12, embed_dim=768,
            max_seq_len=2048, attention="dense", dropout=0.0, **tp,
        )
    mesh = None
    if args.tp > 1:
        from pytorch_distributed_tpu.parallel import make_mesh

        mesh = make_mesh(jax.devices()[: args.tp], data_parallel=1,
                         seq_parallel=1, model_parallel=args.tp)
    if args.restore:
        from pytorch_distributed_tpu.reshard import load_trainer_params

        params, info = load_trainer_params(args.restore, cfg, mesh=mesh)
        rank0_print(f"restore: {info.describe()}")
        return cfg, params, mesh
    params = TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return cfg, params, mesh


def _prompts(args, cfg):
    rng = np.random.default_rng(args.seed)
    lens = rng.integers(4, cfg.max_seq_len - args.max_new,
                        size=args.requests)
    return [rng.integers(1, cfg.vocab_size, size=l).astype(np.int32)
            for l in lens]


# the live front-door instance when --http-port is up — an in-process
# driver (a test thread, a notebook) polls serve_lm.GATEWAY.port instead
# of scraping stdout for the ephemeral port
GATEWAY = None


def main() -> None:
    global GATEWAY
    args = _parse()
    from pytorch_distributed_tpu.utils.env import enable_compile_cache

    # before the model init below: its programs land in the cache too
    enable_compile_cache(args.compile_cache_dir)
    cfg, params, mesh = _model(args)
    prompts = _prompts(args, cfg)
    from pytorch_distributed_tpu.telemetry import (
        NULL_REQTRACER,
        ReqTracer,
        spans,
    )
    from pytorch_distributed_tpu.utils.profiling import MetricsLogger

    mlog = MetricsLogger(args.metrics_out)
    # request-lifecycle tracing (round 14): whenever the JSONL stream is
    # on, every request's causal span tree rides along as kind="span"
    # records — scripts/explain_request.py reconstructs any rid from it
    reqtrace = (
        ReqTracer(mlog) if args.metrics_out and not args.dense
        else NULL_REQTRACER
    )
    t0 = time.perf_counter()
    http_mode = args.http_port is not None
    fleet_mode = (args.replicas > 1 or args.disaggregate or args.trace
                  or http_mode)
    if args.dense and (args.cost_cards or args.metrics_port is not None):
        raise SystemExit("--cost-cards/--metrics-port need the paged "
                         "layout (program registry + scheduler metrics); "
                         "drop --dense")
    exporter = None
    if fleet_mode and args.dense:
        raise SystemExit("--replicas/--disaggregate/--trace need the "
                         "paged layout; drop --dense")
    if fleet_mode and args.tp > 1:
        raise SystemExit("fleet replicas are single-device in this "
                         "round; drop --tp or --replicas")
    if fleet_mode:
        from pytorch_distributed_tpu.fleet import (
            FleetRouter,
            SLOConfig,
            clamp_trace,
            load_trace,
            prompt_for,
            replay_trace,
        )

        slo_kw = {}
        if args.slo_ttft_ms is not None:
            slo_kw["ttft_p95_ms"] = args.slo_ttft_ms
        if args.slo_shed_depth is not None:
            slo_kw["shed_queue_depth"] = args.slo_shed_depth
            slo_kw["spill_queue_depth"] = max(1, args.slo_shed_depth // 4)
        slo = SLOConfig(**slo_kw)
        pressure_kw = (
            dict(offload=True, preempt_on_oom=True,
                 swap_policy=args.swap_policy)
            if args.preempt else {}
        )
        router = FleetRouter(
            cfg, params, n_replicas=max(args.replicas, 2)
            if args.disaggregate else args.replicas,
            disaggregate=args.disaggregate,
            n_prefill=args.prefill_replicas, slo=slo, seed=args.seed,
            metrics_log=mlog, reqtrace=reqtrace,
            # the front door streams: results dropped at retire (the
            # connection consumed them token by token)
            retain_results=not http_mode,
            n_slots=args.slots,
            block_len=args.block_len, prefill_chunk=args.prefill_chunk,
            admit_per_step=args.admit_per_step, n_blocks=args.n_blocks,
            kv_dtype=args.kv_dtype, prefix_cache=args.prefix_cache,
            chunk_bucket_floor=tuple(args.chunk_bucket_floor),
            max_chunk_jobs=args.max_chunk_jobs,
            **pressure_kw,
        )
        if args.warmup:
            router.warmup()
        if args.metrics_port is not None:
            from pytorch_distributed_tpu.telemetry import MetricsExporter

            exporter = MetricsExporter(
                router.metrics, port=args.metrics_port
            ).start()
            rank0_print(f"metrics: http://127.0.0.1:{exporter.port}/metrics")
        if http_mode:
            from pytorch_distributed_tpu.gateway import Gateway

            GATEWAY = gw = Gateway(router, port=args.http_port,
                                   metrics_log=mlog)
            gw.start()
            rank0_print(
                f"gateway: http://127.0.0.1:{gw.port}/v1/generate "
                f"(health /v1/health, metrics /metrics; up for "
                f"{args.http_duration:.0f}s)")
            try:
                time.sleep(args.http_duration)
            finally:
                gw.stop()
                router.drain()
        elif args.trace:
            trace = clamp_trace(
                load_trace(args.trace), cfg.max_seq_len,
                args.prefill_chunk,
            )
            replay_trace(
                trace,
                lambda r: router.submit(prompt_for(r, cfg.vocab_size),
                                        r.max_new, session=r.session),
                router.step,
                lambda: router.idle,
            )
            # the fleet is idle here, so this runs only the drain
            # epilogue: the host-work flush barrier and (under
            # PDT_BLOCKSAN=1) the fleet-wide ledger quiesce check
            router.drain()
        else:
            for i, p in enumerate(prompts):
                router.submit(p, args.max_new, session=i % 8)
            router.drain()
        metrics = {"layout": "fleet", **router.metrics()}
        router.log_summary()
        if args.cost_cards:
            for rep in router.replicas:
                rep.log_cost_cards()
        if exporter is not None:
            exporter.stop()
        metrics["wall_s"] = round(time.perf_counter() - t0, 2)
        mlog.close()
        if args.trace_dir:
            import os

            spans.tracer().save(
                os.path.join(args.trace_dir, "spans.trace.json"))
        rank0_print(json.dumps(metrics, indent=2))
        return
    if args.dense:
        if args.warmup:
            raise SystemExit("--warmup needs the paged layout (the dense "
                             "ContinuousBatcher has no program registry); "
                             "drop --dense")
        if args.kv_dtype or args.prefix_cache:
            raise SystemExit("--kv-dtype/--prefix-cache are block-pool "
                             "knobs; drop --dense")
        if args.preempt or args.n_blocks is not None:
            raise SystemExit("--preempt/--n-blocks are block-pool knobs "
                             "(the pressure tier swaps BLOCKS); drop "
                             "--dense")
        if args.tp > 1:
            raise SystemExit("--tp > 1 needs the paged layout; drop "
                             "--dense")
        # r4 layout: no queue — submit when a slot frees, the admission
        # itself copying the slot's full max_seq_len KV row
        b = ContinuousBatcher(
            cfg, params, n_slots=args.slots, seed=args.seed,
            prefill_bucket=args.prefill_chunk, cache_layout="dense",
        )
        waiting = list(prompts)
        done = 0
        while waiting or any(b.remaining > 0):
            while waiting and b.free_slots():
                b.submit(waiting.pop(0), args.max_new)
            done += len(b.step())
        metrics = {"layout": "dense", "tokens_out": done}
    else:
        s = Scheduler(
            cfg, params, n_slots=args.slots, block_len=args.block_len,
            prefill_chunk=args.prefill_chunk, n_blocks=args.n_blocks,
            admit_per_step=args.admit_per_step, seed=args.seed,
            mesh=mesh, metrics_log=mlog,
            reqtrace=reqtrace,
            kv_dtype=args.kv_dtype,
            offload=args.preempt, preempt_on_oom=args.preempt,
            swap_policy=args.swap_policy,
            prefix_cache=args.prefix_cache,
            chunk_bucket_floor=tuple(args.chunk_bucket_floor),
            max_chunk_jobs=args.max_chunk_jobs,
        )
        if args.warmup:
            # everything foreground + executed inert: the serve loop below
            # admits immediately after, so every request must be warm
            runner = s.warmup(background=False)
            ws = runner.summary()
            rank0_print(
                f"warmup: {ws['programs']} programs in "
                f"{ws['total_s']:.2f}s ({ws['cache_hits']} cache hits)"
            )
        if args.metrics_port is not None:
            from pytorch_distributed_tpu.telemetry import MetricsExporter

            exporter = MetricsExporter(
                s.metrics, port=args.metrics_port
            ).start()
            rank0_print(f"metrics: http://127.0.0.1:{exporter.port}/metrics")
        for p in prompts:
            s.submit(p, args.max_new)
        streams = s.drain()
        metrics = {"layout": "paged", **s.metrics()}
        if args.cost_cards:
            s.log_cost_cards()
        if exporter is not None:
            exporter.stop()
        assert len(streams) == args.requests
    metrics["wall_s"] = round(time.perf_counter() - t0, 2)
    mlog.log(kind="serving_summary", **metrics)
    mlog.close()
    if args.trace_dir:
        import os

        spans.tracer().save(
            os.path.join(args.trace_dir, "spans.trace.json"))
    rank0_print(json.dumps(metrics, indent=2))


if __name__ == "__main__":
    main()
