"""Bounded-structure census — declared bounds for long-lived containers.

Round 21.  ROADMAP item 5 demands host bookkeeping that stays O(live
batch), not O(sessions ever served) — the bug class only a scale
harness surfaces (the unbounded affinity table fixed in PR 15, the
``ReqTracer`` root map and redispatch-origin map fixed this round).
The census turns "we believe this dict is bounded" into a checked
invariant: every long-lived container on a swept object *declares* its
identity and bound class, a sweep audits actual ``len()`` against the
declared bound each sample, and an **undeclared** container on a swept
object is itself a loud finding — new code can't silently add
unbounded state.

Bound classes (``Decl.kind``):

``fixed``
    Capacity set at construction (slot tables, rings, LRU caps).  The
    declared ``cap`` is audited: ``len() > cap`` is a violation.
``live``
    O(live requests).  Audited against the ``live`` count the sweeper
    passes (``FleetRouter.live_requests()``): a structure that keeps
    entries for *retired* rids grows past ``live`` and flags.  This is
    the class whose violation means an O(sessions-ever) host leak.
``replicas``
    O(fleet size).  Audited against ``replicas`` when given.
``unbounded``
    Unbounded *by design* (the scheduler queue under admission
    backpressure, ``ReqTracer.records`` in keep-mode tests).  Never
    flags; the declaration exists so the ``why`` is written down and
    the meta-test knows the container was considered, not missed.

``kind`` and ``cap`` may be callables of the owner so a declaration
can depend on runtime mode — ``FleetRouter.results`` is
unbounded-by-design under the default drain() contract but proven
O(live) when the router runs with ``retain_results=False`` (the soak
configuration).
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

__all__ = [
    "Decl",
    "StructCensus",
    "audit_owner",
    "undeclared_containers",
]

# Container types the undeclared-sweep treats as "long-lived structure
# that could grow".  numpy arrays are fixed-shape buffers, not growth
# candidates, and are deliberately excluded.
_CONTAINER_TYPES = (dict, list, set, frozenset, deque)

_KINDS = ("fixed", "live", "replicas", "unbounded")


@dataclasses.dataclass(frozen=True)
class Decl:
    """One declared container: where it lives, how it's bounded, why."""

    attr: str  # attribute path on the owner; "." means the owner itself
    kind: Union[str, Callable[[Any], str]]
    cap: Union[None, int, Callable[[Any], Optional[int]]] = None
    why: str = ""
    # For kind="live": entries per live request (a request can hold
    # several open spans, a few queued tokens, ...). Audited bound is
    # per_live * live + live_slack.
    per_live: int = 1

    def kind_for(self, owner: Any) -> str:
        k = self.kind(owner) if callable(self.kind) else self.kind
        if k not in _KINDS:
            raise ValueError(f"unknown bound class {k!r} for {self.attr!r}")
        return k

    def cap_for(self, owner: Any) -> Optional[int]:
        c = self.cap(owner) if callable(self.cap) else self.cap
        return None if c is None else int(c)


def _resolve(owner: Any, attr: str) -> Any:
    if attr == ".":
        return owner
    obj = owner
    for part in attr.split("."):
        obj = getattr(obj, part, None)
        if obj is None:
            return None
    return obj


def undeclared_containers(obj: Any, decls: Optional[Sequence[Decl]] = None,
                          ) -> List[str]:
    """Direct container attributes of ``obj`` not covered by a declaration.

    Coverage is by first path component: ``Decl(attr="ttft.values")``
    does not cover a hypothetical ``self.ttft`` dict — only a dotted
    reach *through* a non-container attribute.  The meta-test asserts
    this returns ``[]`` for every swept class.
    """
    if decls is None:
        decls = obj.census_decls() if hasattr(obj, "census_decls") else []
    # A dotted decl ("ttft.values") reaches *through* a non-container
    # attribute; only undotted decls name a direct container attr.
    covered = {d.attr for d in decls if "." not in d.attr}
    out = []
    for name, val in vars(obj).items():
        if isinstance(val, _CONTAINER_TYPES) and name not in covered:
            out.append(name)
    return sorted(out)


def audit_owner(name: str, obj: Any, *, live: Optional[int] = None,
                replicas: Optional[int] = None, live_slack: int = 0,
                ) -> Tuple[Dict[str, int], List[dict], List[str]]:
    """Audit one owner: (sizes, violations, undeclared).

    ``sizes`` maps ``"{name}.{attr}"`` to current ``len()``.
    ``violations`` carry the declared bound that was exceeded.
    """
    decls = obj.census_decls() if hasattr(obj, "census_decls") else []
    sizes: Dict[str, int] = {}
    violations: List[dict] = []
    for d in decls:
        target = _resolve(obj, d.attr)
        if target is None:
            continue
        try:
            size = len(target)
        except TypeError:
            continue
        qname = f"{name}.{d.attr}" if d.attr != "." else name
        sizes[qname] = size
        kind = d.kind_for(obj)
        cap = d.cap_for(obj)
        bound: Optional[int] = None
        if kind == "fixed":
            bound = cap
        elif kind == "live":
            if live is not None:
                bound = d.per_live * live + live_slack
                if cap is not None and cap:
                    bound = min(bound, cap)
        elif kind == "replicas":
            bound = cap if cap is not None else replicas
        if bound is not None and size > bound:
            violations.append({"name": qname, "size": size, "kind": kind,
                               "bound": bound, "why": d.why})
    undeclared = [f"{name}.{a}" for a in undeclared_containers(obj, decls)]
    return sizes, violations, undeclared


class StructCensus:
    """Registry of swept owners + the periodic sweep.

    ``register`` objects (or a whole fleet via the owners list the
    router exposes), then call ``sweep(live=...)`` on a sample cadence.
    Each sweep emits one ``kind="census"`` record through
    ``metrics_log`` (same rotating JSONL as every other telemetry
    kind) and accumulates peak sizes + violation totals for the
    end-of-run verdict.
    """

    def __init__(self, metrics_log=None):
        self.metrics_log = metrics_log
        self._owners: List[Tuple[str, Any]] = []
        self.sweeps = 0
        self.total_violations = 0
        self.total_undeclared = 0
        self.peak: Dict[str, int] = {}

    def register(self, name: str, obj: Any) -> None:
        self._owners.append((name, obj))

    def register_many(self, owners: Sequence[Tuple[str, Any]]) -> None:
        for name, obj in owners:
            self.register(name, obj)

    def owners(self) -> List[Tuple[str, Any]]:
        return list(self._owners)

    def sweep(self, *, live: Optional[int] = None,
              replicas: Optional[int] = None, tick: Optional[int] = None,
              live_slack: int = 0) -> dict:
        structures: Dict[str, int] = {}
        violations: List[dict] = []
        undeclared: List[str] = []
        for name, obj in self._owners:
            sizes, viol, undecl = audit_owner(
                name, obj, live=live, replicas=replicas,
                live_slack=live_slack)
            structures.update(sizes)
            violations.extend(viol)
            undeclared.extend(undecl)
        worst_name, worst_ratio = "", 0.0
        for name, obj in self._owners:
            decls = (obj.census_decls()
                     if hasattr(obj, "census_decls") else [])
            for d in decls:
                qname = f"{name}.{d.attr}" if d.attr != "." else name
                if qname not in structures:
                    continue
                kind = d.kind_for(obj)
                if kind == "fixed":
                    denom = d.cap_for(obj)
                elif kind == "live":
                    denom = d.per_live * live if live else None
                elif kind == "replicas":
                    denom = d.cap_for(obj) or replicas
                else:
                    continue
                if not denom:
                    continue
                ratio = structures[qname] / denom
                if ratio > worst_ratio:
                    worst_name, worst_ratio = qname, ratio
        for qname, size in structures.items():
            if size > self.peak.get(qname, -1):
                self.peak[qname] = size
        self.sweeps += 1
        self.total_violations += len(violations)
        self.total_undeclared += len(set(undeclared))
        rec = {
            "kind": "census",
            "tick": tick,
            "live": live,
            "structures": structures,
            "violations": len(violations),
            "violation_details": violations,
            "undeclared": sorted(set(undeclared)),
            "worst_ratio": round(worst_ratio, 4),
            "worst_name": worst_name,
            "ok": not violations and not undeclared,
        }
        if self.metrics_log is not None:
            self.metrics_log.log(**rec)
        return rec

    def verdict(self) -> str:
        """"ok" iff no sweep ever saw a violation or undeclared container."""
        if self.total_violations:
            return f"violations:{self.total_violations}"
        if self.total_undeclared:
            return f"undeclared:{self.total_undeclared}"
        return "ok" if self.sweeps else "no-sweeps"
