"""Unified observability runtime: device metrics, spans, goodput, latency.

The reference's entire observability story is rank-0 ``time.time()`` epoch
prints with GPU util measured externally by the cluster (PAPER.md §5
"tracing: ABSENT"); round 1 replaced the prints with a JSONL stream
(``utils.profiling.MetricsLogger``) but left three holes this package
closes:

- ``device_metrics`` — a fixed-shape, donated on-device ring buffer the
  trainers push each log-interval's metric scalars into, drained every
  ``flush_every`` windows with ONE lagged host transfer. Replaces the
  per-log-interval blocking ``float()`` sync that stalled the dispatch
  pipeline in both trainers; the logged series is bit-identical to the
  blocking path (same f32 scalars, one hop through the buffer).
- ``spans`` — the process's ONE span stream
  (``spans.tracer()``): every component records into it by default, into
  a bounded ring, on the ``time.perf_counter`` clock; each span is also a
  ``jax.profiler.TraceAnnotation("pdt:<name>")``, so under a profiler
  session it lies beside the XLA operations. Nothing is threaded through
  constructors; ``--trace-dir`` only says where ``save()`` writes.
- ``goodput`` — a run-level ledger classifying wall time into
  productive-step vs compile, data wait, checkpoint stall, rollback
  replay, and watchdog stall; fractions sum to 1 by construction.
- ``latency`` — exact host-side latency series with percentile
  summaries (TTFT, per-output-token, queue wait for the serving
  scheduler).

Round 11 adds the attribution-and-forensics layer (ANALYSIS.md
"Performance attribution & forensics"):

- ``costmodel`` — per-program cost cards: ``Compiled.cost_analysis()``
  FLOP/byte statics for every ``compilecache.ProgramRegistry`` program,
  joined with measured span/tick times into MFU, achieved bandwidth, and
  a compute-vs-bandwidth roofline classification (``kind="program_cost"``
  JSONL);
- ``anomaly`` — streaming median/MAD z-score detectors over step-time,
  data-wait, TTFT, and queue-depth series (``kind="anomaly"`` with a
  context window); a recently-anomalous serving replica reads as hot to
  the fleet ``SLOGate``;
- ``flightrec`` — a bounded ring of recent structured events, dumped
  atomically on watchdog stall, rollback, suspend, and unhandled
  exception, with a size-capped durable JSONL mirror the kill-matrix
  relaunch reads;
- ``export`` — a stdlib-HTTP Prometheus-text ``/metrics`` thread
  (``scripts/pdt_top.py`` is the JSONL-tailing terminal twin).

Round 14 adds the causal join layer (ANALYSIS.md "Request-lifecycle
tracing"):

- ``reqtrace`` — per-request lifecycle traces: rid-keyed span trees
  (gate decision → queue → prefill → handoff → decode windows →
  preempt/park/restore → retire) as a versioned ``kind="span"`` JSONL
  stream, with a completeness validator and a Perfetto/Chrome-trace
  exporter (``scripts/explain_request.py`` is the forensics CLI);
- ``schema`` — the JSONL record-kind registry: required keys per kind
  with a validator, so emitter drift breaks CI instead of the report.

Round 21 adds the scale observatory (ANALYSIS.md "Scale observatory"):

- ``hostprof`` — a ``ResourceMonitor`` sampling host RSS
  (``/proc/self/status``, ``getrusage`` fallback), gc population, and
  optional tracemalloc top sites on a tick-count cadence
  (``kind="resource"`` JSONL);
- ``census`` — the bounded-structure census: every long-lived
  container on the swept serving classes declares its bound class
  (fixed / O(live) / O(replicas) / unbounded-by-design) and a sweep
  audits actual ``len()`` against it (``kind="census"``; an undeclared
  container is itself a finding);
- ``scaling`` — a growth sentinel regressing RSS, per-tick host wall,
  and structure sizes against session counts with MAD-floored
  flagging, so "flat host cost at 100k sessions" is a checked verdict
  (``bench_serving.py --soak``), not an impression.

Everything reports through the one JSONL schema of
``utils.profiling.MetricsLogger``; ``scripts/telemetry_report.py``
renders a run's JSONL into the summary table ``bench.py`` consumes.
ANALYSIS.md "Observability & goodput" documents the schema.
"""

from pytorch_distributed_tpu.telemetry.anomaly import (
    AnomalySentinel,
    StreamingDetector,
)
from pytorch_distributed_tpu.telemetry.census import (
    Decl,
    StructCensus,
    audit_owner,
    undeclared_containers,
)
from pytorch_distributed_tpu.telemetry.costmodel import (
    CostCard,
    ProgramTimes,
    SwapDecision,
    build_cost_cards,
    device_ceilings,
    link_bandwidth,
    log_cost_cards,
    swap_vs_recompute,
)
from pytorch_distributed_tpu.telemetry.device_metrics import DeviceMetricsRing
from pytorch_distributed_tpu.telemetry.export import (
    MetricsExporter,
    prometheus_text,
)
from pytorch_distributed_tpu.telemetry.flightrec import (
    NULL_RECORDER,
    FlightRecorder,
)
from pytorch_distributed_tpu.telemetry.goodput import (
    GOODPUT_CATEGORIES,
    GoodputLedger,
)
from pytorch_distributed_tpu.telemetry.hostprof import (
    NULL_MONITOR,
    ResourceMonitor,
    rss_mib,
)
from pytorch_distributed_tpu.telemetry.latency import LatencySeries, percentiles
from pytorch_distributed_tpu.telemetry.reqtrace import (
    NULL_REQTRACER,
    SPAN_SCHEMA_VERSION,
    ReqTracer,
    build_tree,
    chrome_trace,
    save_chrome_trace,
    span_records,
    trace_rids,
    validate_trace,
)
from pytorch_distributed_tpu.telemetry.scaling import (
    GrowthSentinel,
    fit_growth,
    mad_scale,
)
from pytorch_distributed_tpu.telemetry.schema import (
    REQUIRED_KEYS,
    validate_record,
    validate_stream,
)
from pytorch_distributed_tpu.telemetry.spans import SpanTracer, tracer

__all__ = [
    "AnomalySentinel",
    "StreamingDetector",
    "Decl",
    "StructCensus",
    "audit_owner",
    "undeclared_containers",
    "NULL_MONITOR",
    "ResourceMonitor",
    "rss_mib",
    "GrowthSentinel",
    "fit_growth",
    "mad_scale",
    "CostCard",
    "ProgramTimes",
    "SwapDecision",
    "build_cost_cards",
    "device_ceilings",
    "link_bandwidth",
    "log_cost_cards",
    "swap_vs_recompute",
    "DeviceMetricsRing",
    "MetricsExporter",
    "prometheus_text",
    "NULL_RECORDER",
    "FlightRecorder",
    "GOODPUT_CATEGORIES",
    "GoodputLedger",
    "LatencySeries",
    "percentiles",
    "NULL_REQTRACER",
    "SPAN_SCHEMA_VERSION",
    "ReqTracer",
    "build_tree",
    "chrome_trace",
    "save_chrome_trace",
    "span_records",
    "trace_rids",
    "validate_trace",
    "REQUIRED_KEYS",
    "validate_record",
    "validate_stream",
    "SpanTracer",
    "tracer",
]
