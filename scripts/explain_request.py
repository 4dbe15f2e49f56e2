"""explain_request: reconstruct one request's causal story from JSONL.

Give it a rid and the telemetry JSONL(s) a serve run wrote
(``--metrics-out``; the ``kind="span"`` stream from
``telemetry.reqtrace``) and it replays the request's whole lifecycle as
a tree — where it waited, which replica served each phase, whether it
was handed off prefill→decode, whether it was preempted and why the
decision chose swap over recompute (predicted vs measured wall), and
each phase's wall next to the measured per-program cost cards
(``kind="program_cost"``, PR 8) where one applies:

    python scripts/explain_request.py serve.jsonl --rid 17
    python scripts/explain_request.py serve.jsonl --find preempted
    python scripts/explain_request.py serve.jsonl --rid 17 --assert-complete
    python scripts/explain_request.py serve.jsonl --perfetto out.trace.json

``--find preempted|handed-off|shed|redispatched|failed|deadline|cancelled|any``
picks the first rid whose trace matches the predicate — the CI smoke
uses it to assert a preempted AND a handed-off request both left
complete traces without hard-coding rids; the round-19 predicates pick
out the failure plane (``redispatched`` = replayed off a dead replica,
with the replica-hop chain rendered under the tree; ``failed`` /
``deadline`` = root span closed with that terminal outcome). ``--assert-complete`` exits non-zero unless the trace
is a closed acyclic tree: every span ended exactly once, every parent
opened earlier in the same trace, exactly one root, no orphan events —
the ``scripts/ci_check.sh --trace-smoke`` gate. ``--perfetto`` writes
the whole stream as Chrome-trace JSON (one process per request, one
thread row per replica, flow arrows across the handoff) loadable in
Perfetto / chrome://tracing.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from pytorch_distributed_tpu.telemetry.reqtrace import (  # noqa: E402
    SpanNode,
    build_tree,
    save_chrome_trace,
    span_records,
    trace_rids,
    validate_trace,
)


def load_records(paths: List[str]) -> List[dict]:
    records = []
    for path in paths:
        # include the rotated generation first, as flightrec readers do
        for p in (f"{path}.1", path):
            if not os.path.exists(p):
                if p == path:
                    raise SystemExit(f"{path}: no such file")
                continue
            with open(p) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        records.append(json.loads(line))
                    except json.JSONDecodeError:
                        continue  # torn tail: a kill mid-write
    return records


# ---- predicates for --find -------------------------------------------------


def _trace_has(records: List[dict], rid: int, name: str,
               **attrs) -> bool:
    for r in span_records(records, rid):
        if r.get("name") != name:
            continue
        if all(r.get(k) == v for k, v in attrs.items()):
            return True
    return False


def _root_outcome(records: List[dict], rid: int) -> Optional[str]:
    """The rid's terminal outcome: the ``outcome`` attribute on the end
    record of its root span (``name="request"``, no parent). None when
    the root never closed — the trace is still open or torn."""
    recs = span_records(records, rid)
    roots = {r["span"] for r in recs
             if r.get("ev") == "begin" and r.get("name") == "request"}
    for r in recs:
        if r.get("ev") == "end" and r.get("span") in roots:
            return r.get("outcome")
    return None


FINDERS = {
    "preempted": lambda recs, rid: (
        _trace_has(recs, rid, "preempt")
        and _trace_has(recs, rid, "restore")
    ),
    "handed-off": lambda recs, rid: _trace_has(recs, rid, "handoff"),
    "shed": lambda recs, rid: _trace_has(recs, rid, "gate", action="shed"),
    # round-19 failure plane: requests that died with their replica and
    # replayed elsewhere, exhausted the attempt cap, or missed their SLO
    "redispatched": lambda recs, rid: _trace_has(recs, rid, "redispatch"),
    "failed": lambda recs, rid: _root_outcome(recs, rid) == "failed",
    "deadline": lambda recs, rid: _root_outcome(recs, rid) == "deadline",
    # round 22: requests cancelled mid-flight (client hung up on the
    # HTTP front door, or an explicit FleetRouter.cancel)
    "cancelled": lambda recs, rid: _root_outcome(recs, rid) == "cancelled",
    "any": lambda recs, rid: True,
}


def find_rid(records: List[dict], what: str) -> Optional[int]:
    pred = FINDERS[what]
    for rid in trace_rids(records):
        if pred(records, rid):
            return rid
    return None


# ---- rendering -------------------------------------------------------------


def _program_costs(records: List[dict]) -> dict:
    cards = {}
    for r in records:
        if r.get("kind") == "program_cost":
            cards[r["program"]] = r  # newest wins
    return cards


def _fmt_ms(seconds) -> str:
    return f"{seconds * 1e3:.2f}ms" if seconds is not None else "?"


def render_node(node: SpanNode, t_root: float, costs: dict,
                lines: List[str], depth: int = 0) -> None:
    pad = "  " * depth
    rep = node.record.get("replica")
    where = f" [r{rep}]" if rep is not None else ""
    attrs = node.attrs()
    if node.is_event:
        detail = ", ".join(f"{k}={v}" for k, v in attrs.items())
        lines.append(
            f"{pad}· {node.name}{where} @+{_fmt_ms(node.t0 - t_root)}"
            + (f"  ({detail})" if detail else "")
        )
    else:
        dur = f" ({_fmt_ms(node.dur_s)})" if node.dur_s is not None \
            else "  [OPEN]"
        detail = ", ".join(f"{k}={v}" for k, v in attrs.items())
        cost = ""
        prog = attrs.get("program")
        if prog and prog in costs and costs[prog].get("mean_s"):
            card = costs[prog]
            cost = f"  [card: {_fmt_ms(card['mean_s'])}/call]"
        lines.append(
            f"{pad}- {node.name}{where} +{_fmt_ms(node.t0 - t_root)}"
            f"{dur}" + (f"  {detail}" if detail else "") + cost
        )
    for child in node.children:
        render_node(child, t_root, costs, lines, depth + 1)


def phase_walls(root: SpanNode) -> dict:
    """Total wall per phase name across the tree (decode windows and
    repeated prefills sum) — the per-phase attribution line."""
    acc: dict = {}

    def walk(n: SpanNode):
        if not n.is_event and n.dur_s is not None and n is not root:
            acc[n.name] = acc.get(n.name, 0.0) + n.dur_s
        for c in n.children:
            walk(c)

    walk(root)
    return acc


def explain(records: List[dict], rid: int, out=None) -> int:
    """Render rid's causal story; returns 0, or 2 when the trace is
    missing entirely. ``out`` defaults to the CURRENT sys.stdout (late
    bound — an import-time default would pin whatever stream was active
    when the module first loaded, e.g. a pytest capture buffer)."""
    out = out if out is not None else sys.stdout
    recs = span_records(records, rid)
    if not recs:
        print(f"rid {rid}: no span records (was the run traced? "
              f"serve with --metrics-out and request tracing on)",
              file=sys.stderr)
        return 2
    errors = validate_trace(records, rid)
    root = build_tree(records, rid)
    costs = _program_costs(records)
    lines = [
        f"== request {rid} =="
        + (f"  [{len(errors)} completeness issue(s)]" if errors else
           "  [complete]")
    ]
    if root is None:
        lines.append("  (no root span — begin records only; partial "
                     "trace below)")
        for r in recs:
            lines.append(f"  {r}")
    else:
        render_node(root, root.t0, costs, lines)
        walls = phase_walls(root)
        if walls:
            lines.append("per-phase wall: " + ", ".join(
                f"{name} {_fmt_ms(s)}" for name, s in
                sorted(walls.items(), key=lambda kv: -kv[1])
            ))
        # the preempt audit: predicted vs measured, per sub-tree
        def preempts(n):
            if n.name == "preempt" and not n.is_event:
                yield n
            for c in n.children:
                yield from preempts(c)

        for p in preempts(root):
            a = p.attrs()
            swaps = [c for c in p.children
                     if c.name in ("swap_out", "swap_in")
                     and not c.is_event]
            measured = sum(c.attrs().get("wall_s") or 0.0 for c in swaps)
            lines.append(
                f"preempt audit: chose {a.get('decision')} "
                f"({a.get('decision_reason')}); predicted swap "
                f"{_fmt_ms(a.get('predicted_swap_s'))} vs recompute "
                + (_fmt_ms(a.get('predicted_recompute_s'))
                   if a.get('predicted_recompute_s') is not None
                   else "? (no measured chunk wall yet)")
                + (f"; measured swap {_fmt_ms(measured)}" if swaps else "")
            )
    # round-19 failure plane: the replica-hop chain — each hop is a
    # replica death that replayed this request elsewhere (``replayed``
    # counts already-delivered tokens re-prefilled, not regenerated)
    hops = [r for r in recs
            if r.get("ev") == "event" and r.get("name") == "redispatch"]
    if hops:
        chain = f"r{hops[0].get('src')}"
        for h in hops:
            chain += (f" ✝→ r{h.get('dst')} (attempt {h.get('attempt')},"
                      f" replayed {h.get('replayed')} tok)")
        lines.append(f"replica hops: {chain}")
    outcome = _root_outcome(records, rid)
    if outcome == "failed":
        lines.append("terminal outcome: FAILED — re-dispatch attempt "
                     "cap exhausted; the stream never completed")
    elif outcome == "deadline":
        lines.append("terminal outcome: DEADLINE — the request's SLO "
                     "budget lapsed before completion")
    elif outcome == "cancelled":
        lines.append("terminal outcome: CANCELLED — the caller hung up "
                     "(or cancelled explicitly); KV blocks freed "
                     "mid-flight")
    for e in errors:
        lines.append(f"INCOMPLETE: {e}")
    print("\n".join(lines), file=out)
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("paths", nargs="+", help="telemetry JSONL file(s)")
    p.add_argument("--rid", type=int, default=None,
                   help="request id to explain")
    p.add_argument("--find", choices=sorted(FINDERS), default=None,
                   help="pick the first rid whose trace matches the "
                        "predicate (preempted = preempt AND restore "
                        "events present; handed-off = a prefill→decode "
                        "handoff span)")
    p.add_argument("--assert-complete", action="store_true",
                   help="exit non-zero unless the trace is a closed, "
                        "acyclic, single-root span tree (CI gate)")
    p.add_argument("--perfetto", default=None, metavar="OUT",
                   help="also write the whole stream as Chrome-trace "
                        "JSON (Perfetto-loadable)")
    args = p.parse_args(argv)
    if (args.rid is None) == (args.find is None):
        p.error("exactly one of --rid / --find is required")

    records = load_records(args.paths)
    rid = args.rid
    if rid is None:
        rid = find_rid(records, args.find)
        if rid is None:
            print(f"--find {args.find}: no matching trace in "
                  f"{args.paths}", file=sys.stderr)
            return 2
        print(f"--find {args.find}: rid {rid}")
    rc = explain(records, rid)
    if rc:
        return rc
    if args.perfetto:
        path = save_chrome_trace(records, args.perfetto)
        print(f"perfetto trace: {path}")
    if args.assert_complete:
        errors = validate_trace(records, rid)
        if errors:
            print(f"--assert-complete: trace {rid} has "
                  f"{len(errors)} issue(s)", file=sys.stderr)
            return 2
        print(f"--assert-complete: trace {rid} OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
