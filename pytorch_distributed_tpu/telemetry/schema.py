"""JSONL schema registry: the one place each record ``kind`` is declared.

Every telemetry producer in this repo writes through
``utils.profiling.MetricsLogger``, but until round 14 the record shapes
lived only in the emitters — ``telemetry_report.py`` and ``pdt_top.py``
discovered drift at render time (a silently absent key degrades a
section, never fails a build). This module makes the contract explicit:
``REQUIRED_KEYS`` names the keys every record of a kind must carry,
``validate_record`` checks one record, ``validate_stream`` a whole run.
``tests/test_reqtrace.py`` replays every emitter against it, so a
producer dropping or renaming a key breaks CI instead of the report.

The registry is deliberately a FLOOR, not a straitjacket: emitters may
add keys freely (reports use ``.get`` for optional ones); only removing
a required key — the ones consumers index unconditionally — is a
schema break. Unknown kinds pass by default (``strict=True`` flags
them), so an experiment can stream new record kinds without registering
first; promotion to the registry happens when a consumer starts
depending on them.
"""

from __future__ import annotations

from typing import Dict, FrozenSet, Iterable, List

#: required keys per record kind. ``ts`` is stamped by MetricsLogger
#: itself and therefore not listed. Span records are versioned
#: separately (``v``; reqtrace.SPAN_SCHEMA_VERSION) and their per-``ev``
#: shapes are refined by ``_SPAN_EV_KEYS`` below.
REQUIRED_KEYS: Dict[str, FrozenSet[str]] = {
    # serving/scheduler.py per-retirement + fleet shed records
    "request": frozenset(
        {"rid", "replica_id", "rejected", "prompt_len", "new_tokens"}
    ),
    # serving/scheduler.py preempt decision (round 13)
    "preempt": frozenset(
        {"rid", "replica_id", "reason", "decision", "decision_reason",
         "predicted_swap_s", "predicted_recompute_s"}
    ),
    # serving/scheduler.py swap-out/in outcomes
    "swap": frozenset({"rid", "replica_id", "direction", "ok"}),
    # serving/scheduler.py shared-prefix admissions (round 17)
    "prefix": frozenset(
        {"rid", "replica_id", "prompt_len", "covered", "shared_blocks",
         "cow"}
    ),
    # telemetry/reqtrace.py lifecycle spans (round 14)
    "span": frozenset({"v", "ev", "trace", "span", "seq", "t"}),
    # telemetry/goodput.py ledger report
    "goodput": frozenset({"goodput_frac", "productive_s", "wall_s"}),
    # telemetry/anomaly.py sentinel hits
    "anomaly": frozenset({"series", "value", "median", "mad", "zscore"}),
    # telemetry/costmodel.py per-program cost cards
    "program_cost": frozenset({"program", "calls"}),
    # fleet/router.py run rollup
    "fleet_summary": frozenset(
        {"replicas", "submitted", "shed", "spilled", "handoffs",
         "preempts", "restores", "tokens_out"}
    ),
    # recipes/serve_lm.py single-scheduler rollup
    "serving_summary": frozenset({"tokens_out", "completed"}),
    # compilecache/warmup.py per-program manifest
    "warmup": frozenset({"program", "seconds", "cache_hit"}),
    # analysis/blocksan.py block-lifecycle sanitizer (round 18);
    # per-``ev`` shapes refined by ``_SANITIZER_EV_KEYS`` below
    "sanitizer": frozenset({"ev", "shadow", "replica_id"}),
    # fleet/router.py replica health transitions (round 19): one record
    # per state-machine edge (healthy/suspect/dead/draining/rejoining)
    "health": frozenset({"replica_id", "state", "prev", "reason", "tick"}),
    # telemetry/hostprof.py host-resource samples (round 21): RSS in MiB
    # plus the load axes the growth sentinel regresses against;
    # gc/tracemalloc/tick-wall fields are optional extras
    "resource": frozenset({"rss_mib", "rss_source", "live", "cumulative"}),
    # telemetry/census.py bounded-structure sweeps (round 21): per-sweep
    # verdict + per-structure sizes; violation_details/undeclared carry
    # the loud-finding payloads
    "census": frozenset({"ok", "violations", "structures", "worst_ratio"}),
    # gateway/server.py per-connection ingress records (round 22): one
    # per /v1/generate connection — rid (-1 when rejected before
    # admission), HTTP status, the X-Deadline-Ms budget (null when
    # absent), whether the client disconnected, SSE bytes written, and
    # TTFT measured over the wire (null when no token ever reached the
    # socket); outcome/tokens/reason/gap_max_ms/open/queued ride as
    # optional extras
    "http": frozenset(
        {"rid", "route", "status", "deadline", "disconnect", "bytes",
         "ttft_wire"}
    ),
}

#: additional required keys per span ``ev`` (see reqtrace module docs)
_SPAN_EV_KEYS: Dict[str, FrozenSet[str]] = {
    "begin": frozenset({"name"}),
    "end": frozenset({"dur_s"}),
    "event": frozenset({"name"}),
    "link": frozenset({"dst", "name"}),
}

#: additional required keys per sanitizer ``ev`` (analysis/blocksan.py)
_SANITIZER_EV_KEYS: Dict[str, FrozenSet[str]] = {
    "violation": frozenset({"class", "block", "owner", "site"}),
    "quiesce": frozenset({"ok", "live_blocks", "violations"}),
}


def validate_record(record: dict, strict: bool = False) -> List[str]:
    """Errors for one record (empty list == conformant). ``strict``
    additionally flags kinds the registry does not know."""
    kind = record.get("kind")
    if kind is None:
        return ["record has no 'kind' key"]
    required = REQUIRED_KEYS.get(kind)
    if required is None:
        return [f"unknown kind {kind!r}"] if strict else []
    errors = [
        f"kind={kind}: missing required key {k!r}"
        for k in sorted(required) if k not in record
    ]
    for refined, table in (("span", _SPAN_EV_KEYS),
                           ("sanitizer", _SANITIZER_EV_KEYS)):
        if kind != refined:
            continue
        ev = record.get("ev")
        ev_keys = table.get(ev)
        if ev_keys is None:
            errors.append(f"kind={kind}: unknown ev {ev!r}")
        else:
            errors.extend(
                f"kind={kind} ev={ev}: missing required key {k!r}"
                for k in sorted(ev_keys) if k not in record
            )
    return errors


def validate_stream(records: Iterable[dict],
                    strict: bool = False) -> List[str]:
    """Errors across a record stream, each prefixed with its index —
    the CI conformance gate (and a debugging aid: the index is the JSONL
    line number for an unrotated stream)."""
    errors: List[str] = []
    for i, record in enumerate(records):
        errors.extend(f"record {i}: {e}"
                      for e in validate_record(record, strict=strict))
    return errors
