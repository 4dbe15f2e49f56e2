"""Request-lifecycle causal tracing (round 14 tentpole): span trees
across admission → prefill → handoff → decode → preempt → restore, the
completeness validator, the explain_request forensics CLI, the Perfetto
exporter, the JSONL schema registry, SpanTracer's per-thread stacks, and
the Prometheus exporter under concurrent scrapes."""

import json
import os
import threading
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_tpu.analysis.core import LintContext, parse_file
from pytorch_distributed_tpu.analysis.rules_threads import check_threads
from pytorch_distributed_tpu.fleet import FleetRouter
from pytorch_distributed_tpu.fleet.admission import (
    SHED,
    Decision,
    trace_decision,
)
from pytorch_distributed_tpu.models.transformer import (
    TransformerLM,
    tiny_config,
)
from pytorch_distributed_tpu.serving import Scheduler
from pytorch_distributed_tpu.telemetry import (
    NULL_REQTRACER,
    AnomalySentinel,
    MetricsExporter,
    ReqTracer,
    build_tree,
    chrome_trace,
    validate_stream,
    validate_trace,
)
from pytorch_distributed_tpu.telemetry import spans as span_stream
from pytorch_distributed_tpu.telemetry.reqtrace import span_records
from pytorch_distributed_tpu.utils.profiling import MetricsLogger

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _import_script(name):
    """Import a scripts/ module without leaving scripts/ on sys.path."""
    import importlib
    import sys

    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        return importlib.import_module(name)
    finally:
        sys.path.pop(0)


@pytest.fixture(scope="module")
def model():
    cfg = tiny_config(attention="dense", max_seq_len=64)
    params = TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return cfg, params


def _prompts(lens, cfg, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, size=l).astype(np.int32)
            for l in lens]


@pytest.fixture(scope="module")
def pressure_run(model):
    """Standalone scheduler, forced-swap preemption mid-decode: the
    preempt→park→restore sub-tree with predicted-vs-measured walls."""
    cfg, params = model
    tracer = ReqTracer()
    s = Scheduler(cfg, params, n_slots=2, block_len=8, prefill_chunk=8,
                  offload=True, swap_policy="swap", reqtrace=tracer)
    prompts = _prompts((12, 9), cfg)
    rids = [s.submit(p, 6) for p in prompts]
    streams = {}
    for _ in range(32):  # arm rid0's decode lane, then preempt it
        for rid, tok in s.step():
            streams.setdefault(rid, []).append(tok)
        if streams.get(rids[0]):
            break
    decision = s.preempt(rids[0], reason="test")
    assert decision is not None and decision.choice == "swap"
    for rid, toks in s.drain().items():
        streams.setdefault(rid, []).extend(toks)
    # token identity across the preemption (vs an unpreempted reference)
    ref = Scheduler(cfg, params, n_slots=2, block_len=8, prefill_chunk=8)
    ref_rids = [ref.submit(p, 6) for p in prompts]
    ref_streams = ref.drain()
    assert [streams[r] for r in rids] == [ref_streams[r] for r in ref_rids]
    return tracer.records, rids


@pytest.fixture(scope="module")
def disagg_run(model, tmp_path_factory):
    """Disaggregated 2-replica fleet over a small decode pool: handoff
    spans + flow links, plus the handoff-pressure preempt rung."""
    cfg, params = model
    path = str(tmp_path_factory.mktemp("reqtrace") / "fleet.jsonl")
    mlog = MetricsLogger(path)
    tracer = ReqTracer(mlog, keep=True)
    r = FleetRouter(cfg, params, n_replicas=2, disaggregate=True,
                    metrics_log=mlog, reqtrace=tracer, n_slots=4,
                    block_len=8, prefill_chunk=8, n_blocks=7,
                    offload=True, swap_policy="swap")
    rids = [r.submit(p, 5, session=i)
            for i, p in enumerate(_prompts((12, 14, 9), cfg))]
    r.drain()
    r.log_summary()
    mlog.close()
    with open(path) as f:
        file_records = [json.loads(line) for line in f if line.strip()]
    return tracer.records, file_records, rids, r


# ---------------------------------------------------------------------------
# the trace trees
# ---------------------------------------------------------------------------


def _spans(records, rid, name):
    return [r for r in span_records(records, rid)
            if r.get("name") == name and r.get("ev") == "begin"]


def test_pressure_trace_complete_with_predicted_vs_measured(pressure_run):
    records, rids = pressure_run
    assert validate_trace(records) == []
    rid = rids[0]
    preempts = _spans(records, rid, "preempt")
    assert len(preempts) == 1
    p = preempts[0]
    assert p["decision"] == "swap" and p["predicted_swap_s"] > 0
    # the swap_out close carries measured wall NEXT TO the predicted cost
    swap_out = _spans(records, rid, "swap_out")[0]
    end = next(r for r in span_records(records, rid)
               if r.get("ev") == "end" and r["span"] == swap_out["span"])
    assert end["ok"] and end["wall_s"] > 0
    assert end["predicted_s"] == p["predicted_swap_s"]
    for name in ("parked", "swap_in"):
        assert _spans(records, rid, name), name
    assert any(r.get("name") == "restore" for r in
               span_records(records, rid))
    # two decode windows: the preempted one and the resumed one
    windows = _spans(records, rid, "decode")
    assert len(windows) == 2
    ends = {r["span"]: r for r in span_records(records, rid)
            if r.get("ev") == "end"}
    assert ends[windows[0]["span"]]["outcome"] == "preempted"
    assert windows[1]["resumed"] == "swap"
    # root closed with the stream's outcome
    root = next(r for r in span_records(records, rid)
                if r.get("ev") == "begin" and not r.get("parent"))
    assert ends[root["span"]]["outcome"] == "complete"
    assert ends[root["span"]]["preempts"] == 1


def test_kv_chain_transitions_annotated(pressure_run):
    records, rids = pressure_run
    names = [r["name"] for r in span_records(records, rids[0])
             if r.get("ev") == "event" and r["name"].startswith("kv_")]
    # admission alloc ... swap-out window, free, swap-in realloc ... retire
    assert names[0] == "kv_alloc"
    assert names[-1] == "kv_free"
    states = [r["state"] for r in span_records(records, rids[0])
              if r.get("name") == "kv_state"]
    assert states == ["swapping-out", "resident", "swapping-in",
                      "resident"]


def test_disagg_handoff_is_one_tree_across_replicas(disagg_run):
    records, _file_records, rids, router = disagg_run
    assert validate_trace(records) == []
    for rid in rids:
        handoff = _spans(records, rid, "handoff")
        assert len(handoff) == 1, f"rid {rid}"
        h = handoff[0]
        assert h["src"] == 0 and h["dst"] == 1 and h["bytes"] > 0
        # prefill on r0, the adopted decode window on r1 — one trace
        assert _spans(records, rid, "prefill")[0]["replica"] == 0
        decode = _spans(records, rid, "decode")
        assert decode[0]["replica"] == 1 and decode[0]["adopted"] is True
        # the flow link lands on the adopted decode window
        links = [r for r in span_records(records, rid)
                 if r.get("ev") == "link"]
        assert any(link["span"] == h["span"]
                   and link["dst"] == decode[0]["span"] for link in links)
        # handoff_wait opened on the prefill replica and closed at
        # complete_handoff
        wait = _spans(records, rid, "handoff_wait")
        assert wait and wait[0]["replica"] == 0
    # the small decode pool forced the handoff-pressure rung at least
    # once — preempt spans carry the routing reason
    preempts = [r for rid in rids for r in _spans(records, rid, "preempt")]
    assert preempts and all(
        p["reason"] == "handoff-pressure" for p in preempts
    )
    assert router.metrics()["preempt_routes"] >= 1


def test_shed_decision_closes_root_immediately():
    tracer = ReqTracer()
    trace_decision(tracer, 5, Decision(SHED, -1, "queue_depth"),
                   session=3, prompt_len=16)
    assert validate_trace(tracer.records) == []
    end = next(r for r in tracer.records if r.get("ev") == "end")
    assert end["outcome"] == "shed" and end["reason"] == "queue_depth"
    gate = next(r for r in tracer.records if r.get("name") == "gate")
    assert gate["action"] == "shed"


def test_logical_clock_is_strictly_monotone_across_threads():
    tracer = ReqTracer()
    n, per = 8, 50

    def worker(rid):
        root = tracer.open_root(rid)
        for i in range(per):
            tracer.event(rid, f"e{i}", parent=root)
        tracer.end(root)

    threads = [threading.Thread(target=worker, args=(rid,))
               for rid in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    seqs = [r["seq"] for r in tracer.records]
    assert sorted(seqs) == list(range(n * (per + 2)))
    assert validate_trace(tracer.records) == []


def test_null_tracer_is_inert():
    assert NULL_REQTRACER.begin(1, "x") == 0
    assert NULL_REQTRACER.open_root(1) == 0
    NULL_REQTRACER.end(0)
    NULL_REQTRACER.event(1, "x")
    NULL_REQTRACER.link(1, 0, 0)
    assert NULL_REQTRACER.records == []


def test_reserved_attr_keys_are_rejected():
    tracer = ReqTracer()
    with pytest.raises(ValueError, match="reserved"):
        tracer.begin(1, "x", seq=3)


def test_validator_catches_unclosed_orphaned_and_multiroot():
    tracer = ReqTracer()
    root = tracer.open_root(1)
    child = tracer.begin(1, "phase")
    tracer.end(child)
    tracer.end(root)
    records = list(tracer.records)
    assert validate_trace(records) == []
    # drop the child's end: unclosed
    broken = [r for r in records
              if not (r.get("ev") == "end" and r["span"] == child)]
    assert any("never closed" in e for e in validate_trace(broken))
    # orphan parent: a span naming a parent never opened in this trace
    orphan = records + [{
        "kind": "span", "v": 1, "ev": "begin", "trace": 1, "span": 99,
        "parent": 42, "name": "ghost", "seq": 100, "t": 0.0,
    }]
    errs = validate_trace(orphan)
    assert any("parent 42" in e for e in errs)
    assert any("never closed" in e for e in errs)  # the ghost itself
    # second root
    two_roots = records + [{
        "kind": "span", "v": 1, "ev": "begin", "trace": 1, "span": 100,
        "name": "request", "seq": 101, "t": 0.0,
    }, {
        "kind": "span", "v": 1, "ev": "end", "trace": 1, "span": 100,
        "seq": 102, "t": 0.0, "dur_s": 0.0,
    }]
    assert any("exactly one root" in e for e in validate_trace(two_roots))


# ---------------------------------------------------------------------------
# exporters and CLIs
# ---------------------------------------------------------------------------


def test_chrome_trace_export_tracks_and_flow_arrows(disagg_run):
    records, _file_records, rids, _router = disagg_run
    trace = chrome_trace(records)
    events = trace["traceEvents"]
    xs = [e for e in events if e.get("ph") == "X"]
    assert xs and all(e["dur"] >= 0 for e in xs)
    # one process per request, thread rows per replica
    assert {e["pid"] for e in xs} == set(rids)
    names = {e["args"]["name"] for e in events
             if e.get("name") == "process_name"}
    assert names == {f"request {rid}" for rid in rids}
    flows = [e for e in events if e.get("ph") in ("s", "f")]
    assert len(flows) >= 2 * len(rids)  # one arrow pair per handoff
    json.dumps(trace)  # serializable as-is


def test_explain_request_cli_and_assert_complete(disagg_run, tmp_path,
                                                 capsys):
    explain_request = _import_script("explain_request")
    _records, file_records, rids, _router = disagg_run
    path = tmp_path / "run.jsonl"
    with open(path, "w") as f:
        for r in file_records:
            f.write(json.dumps(r) + "\n")
    rc = explain_request.main(
        [str(path), "--rid", str(rids[0]), "--assert-complete",
         "--perfetto", str(tmp_path / "out.trace.json")]
    )
    out = capsys.readouterr().out
    assert rc == 0
    assert "[complete]" in out and "handoff" in out
    assert "per-phase wall" in out
    assert json.load(open(tmp_path / "out.trace.json"))["traceEvents"]
    # --find predicates locate a handed-off rid without hard-coding
    rc = explain_request.main([str(path), "--find", "handed-off",
                              "--assert-complete"])
    assert rc == 0
    # a torn stream (one end record dropped) must FAIL the gate
    spans = [r for r in file_records if r.get("kind") == "span"]
    drop = next(r for r in spans
                if r.get("ev") == "end" and r["trace"] == rids[0])
    with open(path, "w") as f:
        for r in file_records:
            if r is not drop:
                f.write(json.dumps(r) + "\n")
    rc = explain_request.main([str(path), "--rid", str(rids[0]),
                               "--assert-complete"])
    assert rc == 2
    assert "INCOMPLETE" in capsys.readouterr().out


def test_pdt_top_renders_inflight_and_pressure_rows(disagg_run,
                                                    tmp_path, capsys):
    pdt_top = _import_script("pdt_top")
    _records, file_records, _rids, _router = disagg_run
    path = tmp_path / "run.jsonl"
    with open(path, "w") as f:
        for r in file_records:
            f.write(json.dumps(r) + "\n")
        # one still-open root: the in-flight gauge must count it
        f.write(json.dumps({
            "kind": "span", "v": 1, "ev": "begin", "trace": 999,
            "span": 100000, "name": "request", "seq": 100000, "t": 0.0,
        }) + "\n")
    assert pdt_top.main([str(path), "--once"]) == 0
    out = capsys.readouterr().out
    assert "inflight 1 requests" in out
    assert "pressure" in out and "swap" in out


def test_telemetry_report_require_spans(disagg_run, tmp_path):
    import subprocess
    import sys

    _records, file_records, _rids, _router = disagg_run
    path = tmp_path / "run.jsonl"
    with open(path, "w") as f:
        for r in file_records:
            f.write(json.dumps(r) + "\n")
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts/telemetry_report.py"),
         str(path), "--json", "--require", "spans"],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert res.returncode == 0, res.stdout + res.stderr
    assert "request traces" in res.stdout


# ---------------------------------------------------------------------------
# schema registry: replay every emitter, assert conformance
# ---------------------------------------------------------------------------


def test_every_emitter_conforms_to_schema_registry(disagg_run, model,
                                                   tmp_path):
    cfg, params = model
    _records, file_records, _rids, router = disagg_run
    # the fleet run covers request/span/preempt/swap/fleet_summary;
    # replay the remaining emitters into a fresh stream
    path = tmp_path / "extra.jsonl"
    with MetricsLogger(str(path)) as mlog:
        rep = router.replicas[1]
        mlog.log(kind="serving_summary", **rep.metrics())
        mlog.log(kind="goodput", **rep.goodput.report())
        sentinel = AnomalySentinel(threshold=4.0, metrics_log=mlog,
                                   min_samples=8)
        for _ in range(12):
            sentinel.observe("tick_time", 0.01)
        assert sentinel.observe("tick_time", 10.0) is not None
    with open(path) as f:
        extra = [json.loads(line) for line in f if line.strip()]
    kinds = {r.get("kind") for r in file_records} | {
        r.get("kind") for r in extra
    }
    assert {"request", "span", "preempt", "swap", "fleet_summary",
            "serving_summary", "goodput", "anomaly"} <= kinds
    errors = validate_stream(file_records + extra)
    assert errors == [], errors[:10]


def test_schema_registry_flags_drift():
    from pytorch_distributed_tpu.telemetry.schema import validate_record

    assert validate_record({"rid": 1}) == ["record has no 'kind' key"]
    errs = validate_record({"kind": "request", "rid": 1})
    assert any("replica_id" in e for e in errs)
    # span ev refinement
    errs = validate_record({"kind": "span", "v": 1, "ev": "begin",
                            "trace": 1, "span": 1, "seq": 0, "t": 0.0})
    assert errs == ["kind=span ev=begin: missing required key 'name'"]
    # unknown kinds pass unless strict
    assert validate_record({"kind": "experiment"}) == []
    assert validate_record({"kind": "experiment"}, strict=True)


# ---------------------------------------------------------------------------
# SpanTracer: per-thread stacks (satellite for ROADMAP item 3's threads)
# ---------------------------------------------------------------------------


def test_spantracer_per_thread_stacks_do_not_interleave():
    tracer = span_stream.tracer()
    tracer.clear()
    barrier = threading.Barrier(2)
    errors = []

    def worker(name):
        try:
            with tracer.span(f"outer_{name}"):
                barrier.wait(timeout=5)  # both outers open concurrently
                assert tracer.current().name == f"outer_{name}"
                with tracer.span(f"inner_{name}"):
                    barrier.wait(timeout=5)
                    assert tracer.current().name == f"inner_{name}"
        except Exception as e:  # surfaced below; a thread must not die mute
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(n,))
               for n in ("a", "b")]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert errors == []
    assert tracer.current() is None  # main thread never opened a span
    events = {e.name: e for e in tracer.events()}
    assert len(events) == 4
    for name in ("a", "b"):
        inner, outer = events[f"inner_{name}"], events[f"outer_{name}"]
        # each inner's parent comes from ITS OWN thread's stack
        assert inner.parent_id == outer.id and inner.tid == outer.tid
        assert outer.parent_id is None


def test_rules_threads_passes_telemetry_modules_clean():
    ctx = LintContext(modules=[], mesh_axes=set(), axis_constants={})
    for rel in ("pytorch_distributed_tpu/telemetry/spans.py",
                "pytorch_distributed_tpu/telemetry/reqtrace.py",
                "pytorch_distributed_tpu/telemetry/schema.py"):
        mod = parse_file(os.path.join(REPO, rel), REPO)
        findings = check_threads(mod, ctx)
        assert findings == [], [f.render() for f in findings]


# ---------------------------------------------------------------------------
# /metrics exporter under concurrent scrapes during span emission
# ---------------------------------------------------------------------------


def test_metrics_exporter_concurrent_scrapes_no_torn_lines():
    tracer = ReqTracer()
    state = {"ticks": 0}

    def collect():
        # a collect() racing the emitting loop, as a live fleet's would
        return {"ticks": state["ticks"],
                "open_spans": len(tracer.open_spans()),
                "inflight": len(tracer.open_traces())}

    stop = threading.Event()
    results = {}

    def scraper(i):
        seen = []
        while not stop.is_set():
            with urllib.request.urlopen(
                f"http://127.0.0.1:{exporter.port}/metrics", timeout=5
            ) as resp:
                body = resp.read().decode()
            for line in body.strip().splitlines():
                # no torn lines: every line is a comment or "name value"
                if line.startswith("#"):
                    assert line.startswith("# TYPE pdt_"), line
                    continue
                name, value = line.split(" ")
                assert name.startswith("pdt_")
                float(value)
            seen.append(
                float(next(ln.split(" ")[1]
                           for ln in body.splitlines()
                           if ln.startswith("pdt_ticks "))))
        results[i] = seen

    with MetricsExporter(collect, port=0) as exporter:
        scrapers = [threading.Thread(target=scraper, args=(i,))
                    for i in range(3)]
        for t in scrapers:
            t.start()
        for tick in range(200):  # emit spans while scrapes are in flight
            state["ticks"] = tick + 1
            rid = tick % 7
            root = tracer.open_root(rid)
            span = tracer.begin(rid, "phase", parent=root)
            tracer.event(rid, "tick", parent=span, i=tick)
            tracer.end(span)
        stop.set()
        for t in scrapers:
            t.join()
    for seen in results.values():
        assert seen, "scraper never completed a scrape"
        # the counter is monotone across one scraper's sequential reads
        assert all(b >= a for a, b in zip(seen, seen[1:])), seen
