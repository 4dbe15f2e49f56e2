"""pdt_top: a live terminal view over the unified telemetry JSONL.

``telemetry_report.py`` is the post-hoc renderer; this is the `top`-style
live twin for a run in flight — tail one or more ``MetricsLogger`` JSONL
streams (a trainer's ``metrics.jsonl``, a server's ``--metrics-out``, a
fleet's shared stream) and re-render an aggregate view every
``--interval`` seconds:

- **train**: last epoch/step/loss, mean step ms over the tail window;
- **goodput**: the latest ledger fractions;
- **serving/fleet**: request + token counts, TTFT / per-token p50/p95
  over the last ``--window`` retirements, per-replica queue depth and
  role from the newest ``fleet_summary``;
- **anomalies**: per-series counts plus the most recent excursion;
- **cost**: the top measured programs by attributed wall (once
  ``kind="program_cost"`` cards exist);
- **inflight** (round 14): requests currently in flight, sourced from
  the lifecycle span stream — roots begun but not yet ended;
- **pressure** (round 14): preempt count/rate and decision mix, parked
  chains from the newest ``fleet_summary``, swap bytes moved and
  aborts, from ``kind="preempt"``/``kind="swap"`` records;
- **resource** (round 21): newest RSS and its live slope against
  cumulative sessions (``kind="resource"`` monitor samples), plus the
  newest census sweep's verdict and worst bound ratio
  (``kind="census"``) — the scale observatory's in-flight view;
- **gateway** (round 22): front-door connection count, live open SSE
  streams and queued ingress (the newest ``kind="http"`` record's
  gauges), 429/400 counters, client disconnects, and the worst
  inter-token stream gap seen over the wire.

Only new bytes are read per refresh (the files are followed, not
re-parsed), so tailing a long run is O(new events). ``--once`` renders
the current state and exits — the testable/scriptable mode. The HTTP
counterpart for scrapers is ``telemetry.export.MetricsExporter``
(``--metrics-port`` on every recipe).

Usage:
    python scripts/pdt_top.py RUN.jsonl [SERVE.jsonl ...] [--interval 2]
    python scripts/pdt_top.py fleet.jsonl --once
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, List

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from pytorch_distributed_tpu.telemetry.latency import (  # noqa: E402
    percentiles,
)


class Tail:
    """Incremental JSONL reader: ``poll()`` returns only new records.
    Tolerates a torn final line (kept pending until its newline lands)
    and a file that does not exist yet."""

    def __init__(self, path: str):
        self.path = path
        self._pos = 0
        self._pending = ""

    def poll(self) -> List[dict]:
        try:
            with open(self.path) as f:
                f.seek(self._pos)
                chunk = f.read()
                self._pos = f.tell()
        except FileNotFoundError:
            return []
        records = []
        buf = self._pending + chunk
        lines = buf.split("\n")
        self._pending = lines[-1]  # "" on a clean newline-terminated tail
        for line in lines[:-1]:
            line = line.strip()
            if not line:
                continue
            try:
                records.append(json.loads(line))
            except json.JSONDecodeError:
                continue
        return records


class View:
    """Rolling aggregate state over the record stream."""

    def __init__(self, window: int = 256):
        self.window = window
        self.n_records = 0
        self.last: Dict[str, dict] = {}  # kind -> newest record
        self.requests: List[dict] = []  # tail window of retirements
        self.anomaly_counts: Dict[str, int] = {}
        self.last_anomaly: dict = {}
        self.cost: Dict[str, dict] = {}
        self.sheds = 0
        self.tokens = 0
        # pressure tier counters (kind="preempt"/"swap" records)
        self.preempts = 0
        self.preempt_decisions: Dict[str, int] = {}
        self.swap_bytes = 0
        self.swap_aborts = 0
        # prefix cache (round 17; kind="prefix" per-admission records):
        # lifetime totals plus a tail window for the live hit rate
        self.prefix_admissions = 0
        self.prefix_hits = 0
        self.prefix_covered = 0
        self.prefix_prompt = 0
        self.prefix_cows = 0
        self.recent_prefix: List[dict] = []
        # request-lifecycle spans (kind="span"): open span set and open
        # ROOTS — the live in-flight-requests gauge
        self.open_spans: set = set()
        self.open_roots: set = set()
        self.span_records = 0
        # host resources (round 21; kind="resource"/"census"): tail
        # window of monitor samples for the live RSS slope, plus the
        # newest census sweep's verdict
        self.resources: List[dict] = []
        self.census_violations = 0
        # HTTP front door (round 22; kind="http" per-connection
        # records): lifetime counters plus the newest record's live
        # open/queued gauges and the worst inter-token stream gap
        self.http_conns = 0
        self.http_429 = 0
        self.http_400 = 0
        self.http_disconnects = 0
        self.http_worst_gap_ms = 0.0

    def feed(self, records: List[dict]) -> None:
        for r in records:
            self.n_records += 1
            kind = r.get("kind", "?")
            self.last[kind] = r
            if kind == "request":
                if r.get("rejected"):
                    self.sheds += 1
                else:
                    self.tokens += r.get("new_tokens", 0)
                    self.requests.append(r)
                    if len(self.requests) > self.window:
                        self.requests.pop(0)
            elif kind == "anomaly":
                s = r.get("series", "?")
                self.anomaly_counts[s] = self.anomaly_counts.get(s, 0) + 1
                self.last_anomaly = r
            elif kind == "program_cost":
                self.cost[r["program"]] = r
            elif kind == "preempt":
                self.preempts += 1
                d = r.get("decision", "?")
                self.preempt_decisions[d] = (
                    self.preempt_decisions.get(d, 0) + 1
                )
            elif kind == "swap":
                if r.get("ok"):
                    self.swap_bytes += r.get("bytes", 0)
                else:
                    self.swap_aborts += 1
            elif kind == "prefix":
                self.prefix_admissions += 1
                if r.get("covered", 0) > 0:
                    self.prefix_hits += 1
                self.prefix_covered += r.get("covered", 0)
                self.prefix_prompt += r.get("prompt_len", 0)
                if r.get("cow"):
                    self.prefix_cows += 1
                self.recent_prefix.append(r)
                if len(self.recent_prefix) > self.window:
                    self.recent_prefix.pop(0)
            elif kind == "resource":
                self.resources.append(r)
                if len(self.resources) > self.window:
                    self.resources.pop(0)
            elif kind == "census":
                self.census_violations += r.get("violations", 0)
            elif kind == "http":
                self.http_conns += 1
                status = r.get("status", 0)
                if status == 429:
                    self.http_429 += 1
                elif status == 400:
                    self.http_400 += 1
                if r.get("disconnect"):
                    self.http_disconnects += 1
                gap = r.get("gap_max_ms") or 0.0
                if gap > self.http_worst_gap_ms:
                    self.http_worst_gap_ms = gap
            elif kind == "span":
                self.span_records += 1
                key = (r.get("trace"), r.get("span"))
                if r.get("ev") == "begin":
                    self.open_spans.add(key)
                    if not r.get("parent"):
                        self.open_roots.add(key)
                elif r.get("ev") == "end":
                    self.open_spans.discard(key)
                    self.open_roots.discard(key)

    # ---- rendering -------------------------------------------------------

    def lines(self) -> List[str]:
        out = [f"pdt_top — {self.n_records} records "
               f"({time.strftime('%H:%M:%S')})"]
        train = self.last.get("train")
        if train:
            loss = train.get("loss")
            out.append(
                f"train    epoch {train.get('epoch')} step "
                f"{train.get('step')}"
                + (f"  loss {loss:.4f}" if loss is not None else "")
            )
        et = self.last.get("epoch_timing")
        if et:
            rate = et.get("tokens_per_s") or et.get("items_per_s")
            out.append(
                f"steps    {et['steps']} @ {et['mean_ms']:.1f} ms"
                + (f"  ({rate:.0f}/s)" if rate else "")
            )
        gp = self.last.get("goodput")
        if gp:
            out.append(
                f"goodput  {gp['goodput_frac']:.3f} productive  "
                f"compile {gp.get('compile_frac', 0.0):.3f}  "
                f"data {gp.get('data_wait_frac', 0.0):.3f}  "
                f"stall {gp.get('stall_frac', 0.0):.3f}"
            )
        if self.requests:
            ttft = percentiles(
                [r["ttft_s"] for r in self.requests if "ttft_s" in r],
                qs=(50, 95),
            )
            gaps = percentiles(
                [g for r in self.requests
                 for g in r.get("token_gaps_s", [])],
                qs=(50, 95),
            )
            line = (f"serving  {len(self.requests)} recent reqs, "
                    f"{self.tokens} tokens, {self.sheds} shed")
            if ttft:
                line += (f"  ttft {ttft['p50'] * 1e3:.1f}/"
                         f"{ttft['p95'] * 1e3:.1f} ms")
            if gaps:
                line += (f"  tok {gaps['p50'] * 1e3:.1f}/"
                         f"{gaps['p95'] * 1e3:.1f} ms")
            out.append(line)
        if self.http_conns:
            # front-door row (round 22): the newest record carries the
            # live open-streams / queued-ingress gauges as extras
            newest = self.last.get("http") or {}
            line = (f"gateway  {self.http_conns} conns, "
                    f"{newest.get('open', 0)} open streams, "
                    f"{newest.get('queued', 0)} queued  "
                    f"429={self.http_429}  400={self.http_400}  "
                    f"disconnects={self.http_disconnects}")
            if self.http_worst_gap_ms:
                line += f"  worst gap {self.http_worst_gap_ms:.1f} ms"
            out.append(line)
        if self.span_records:
            # in-flight = roots begun but not yet ended in the stream —
            # the live gauge the lifecycle traces give for free
            out.append(
                f"inflight {len(self.open_roots)} requests "
                f"({len(self.open_spans)} open spans, "
                f"{self.span_records} span records)"
            )
        if self.preempts or self.swap_bytes:
            served = len(self.requests) + self.sheds
            rate = self.preempts / served if served else 0.0
            fs = self.last.get("fleet_summary") or {}
            parked = fs.get("parked")
            out.append(
                f"pressure {self.preempts} preempts ({rate:.1%})"
                + (f"  parked={parked}" if parked is not None else "")
                + f"  swap {self.swap_bytes / 2**20:.2f} MiB"
                + (f"  aborts={self.swap_aborts}"
                   if self.swap_aborts else "")
                + ("  [" + ", ".join(
                    f"{k}={v}" for k, v in
                    sorted(self.preempt_decisions.items())) + "]"
                   if self.preempt_decisions else "")
            )
        if self.prefix_admissions:
            recent_hits = sum(
                1 for r in self.recent_prefix if r.get("covered", 0) > 0
            )
            out.append(
                f"prefix   {self.prefix_admissions} admissions, "
                f"hit {self.prefix_hits / self.prefix_admissions:.1%}"
                f" (recent {recent_hits}/{len(self.recent_prefix)})  "
                f"covered {self.prefix_covered}/{self.prefix_prompt} tok "
                f"({self.prefix_covered / max(self.prefix_prompt, 1):.0%})"
                + (f"  cow={self.prefix_cows}" if self.prefix_cows else "")
            )
        if self.resources:
            # live host-resource row (round 21): newest RSS + the slope
            # over the tailed window, regressed against cumulative
            # sessions — the in-flight view of the soak's headline fit
            from pytorch_distributed_tpu.telemetry.scaling import (
                fit_growth,
            )

            newest = self.resources[-1]
            line = (f"resource rss {newest.get('rss_mib', 0.0):.0f} MiB "
                    f"({newest.get('rss_source', '?')})  "
                    f"live {newest.get('live', 0)} / "
                    f"{newest.get('cumulative', 0)} sessions")
            fit = fit_growth(
                [r.get("cumulative", 0) for r in self.resources],
                [r.get("rss_mib", 0.0) for r in self.resources],
                rel_floor=0.005, abs_floor=1.0)
            if fit["verdict"] != "insufficient":
                line += (f"  slope {fit['slope'] * 1e4:+.1f} MiB/10k "
                         f"({fit['verdict']})")
            census = self.last.get("census")
            if census:
                worst = census.get("worst_ratio", 0.0)
                line += (f"  census "
                         + ("ok" if census.get("ok") else "NOT-OK")
                         + (f" worst {census.get('worst_name', '')}"
                            f"={worst:.2f}" if worst else ""))
                if self.census_violations:
                    line += f"  violations={self.census_violations}"
            out.append(line)
        fs = self.last.get("fleet_summary")
        if fs:
            reps = fs.get("replicas", 0)
            per = []
            for i in range(reps):
                role = fs.get(f"r{i}_role", "?")
                q = fs.get(f"r{i}_queue_depth", "?")
                per.append(f"r{i}({role}) q={q}")
            out.append(
                f"fleet    {reps} replicas, "
                f"{fs.get('handoffs', 0)} handoffs, "
                f"shed {fs.get('shed_rate', 0.0):.1%}  " + "  ".join(per)
            )
        if self.anomaly_counts:
            last = self.last_anomaly
            out.append(
                "anomaly  " + ", ".join(
                    f"{s}={n}" for s, n in sorted(self.anomaly_counts.items())
                )
                + (f"  last: {last.get('series')} z={last.get('zscore')}"
                   if last else "")
            )
        measured = sorted(
            (r for r in self.cost.values() if r.get("calls")),
            key=lambda r: -(r.get("total_s") or 0.0),
        )
        for r in measured[:3]:
            mfu = f" mfu {r['mfu']:.4f}" if r.get("mfu") is not None else ""
            bound = f" [{r['bound']}]" if r.get("bound") else ""
            out.append(
                f"cost     {r['program'][:28]}  "
                f"{r.get('mean_s', 0.0) * 1e3:.2f} ms × {r['calls']}"
                f"{mfu}{bound}"
            )
        return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("paths", nargs="+", help="telemetry JSONL file(s)")
    p.add_argument("--interval", type=float, default=2.0,
                   help="refresh seconds (follow mode)")
    p.add_argument("--window", type=int, default=256,
                   help="retirements kept for the rolling percentiles")
    p.add_argument("--once", action="store_true",
                   help="render the current state once and exit")
    args = p.parse_args(argv)

    tails = [Tail(path) for path in args.paths]
    view = View(window=args.window)
    while True:
        for tail in tails:
            view.feed(tail.poll())
        text = "\n".join(view.lines())
        if args.once:
            print(text)
            return 0
        # clear + home, then the frame — a plain-terminal live view
        sys.stdout.write("\x1b[2J\x1b[H" + text + "\n")
        sys.stdout.flush()
        try:
            time.sleep(args.interval)
        except KeyboardInterrupt:
            return 0


if __name__ == "__main__":
    sys.exit(main())
