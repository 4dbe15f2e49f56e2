"""HTTP/SSE front door (round 22): the gateway over a live fleet.

The claims under test, each of which is a wire-level contract the
in-process serving stack never had to keep before:

1. FIDELITY — the SSE stream is token-identical to an in-process
   ``FleetRouter`` replay of the same prompts (greedy decode is
   deterministic; the gateway must add transport, not entropy), and
   the terminal ``done`` event carries the true outcome + usage.
2. CONTROL-PLANE MAPPING — ``X-Deadline-Ms`` becomes the PR 17
   admission deadline (a lapsed budget sheds as HTTP 429 with
   ``Retry-After`` and the gate's reason), ``/v1/health`` is the PR 19
   health plane verbatim, ``/metrics`` carries both fleet and gateway
   gauges.
3. DISCONNECT → CANCEL — closing the client socket mid-stream reaches
   ``FleetRouter.cancel``: blocks free (a disconnect STORM under
   ``PDT_BLOCKSAN=1`` quiesces clean), the span tree closes
   ``outcome=cancelled``, and the cancel-to-block-free latency is
   observed.
4. HARDENING — malformed ingress (bad JSON, non-numeric deadline,
   oversized prompt, bad types) is a 400 with a JSON error body; a
   stack trace never reaches the socket.
5. HYGIENE — every gateway container is census-declared and the
   ``kind="http"`` JSONL it emits validates against the schema
   registry.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from pytorch_distributed_tpu.telemetry import undeclared_containers
from pytorch_distributed_tpu.telemetry.census import audit_owner
from pytorch_distributed_tpu.telemetry.reqtrace import ReqTracer
from pytorch_distributed_tpu.telemetry.schema import validate_stream
from pytorch_distributed_tpu.utils.profiling import MetricsLogger


# ---------------------------------------------------------------------------
# fixtures: one shared gateway over a 2-replica fleet + the in-process
# reference transcript collected BEFORE the gateway takes the router
# ---------------------------------------------------------------------------

N_REF = 3  # reference prompts replayed over the wire


@pytest.fixture(scope="module")
def tiny_model():
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.models.transformer import (
        TransformerLM,
        tiny_config,
    )

    cfg = tiny_config(attention="dense", max_seq_len=96)
    params = TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return cfg, params


def _build_router(cfg, params, **kw):
    from pytorch_distributed_tpu.fleet import FleetRouter

    kw.setdefault("n_replicas", 2)
    kw.setdefault("n_slots", 3)
    kw.setdefault("block_len", 8)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("retain_results", False)
    return FleetRouter(cfg, params, **kw)


def _prompts(cfg, n=N_REF, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, (9 + 3 * i,)).astype(np.int32)
            for i in range(n)]


@pytest.fixture(scope="module")
def gw_env(tiny_model, tmp_path_factory):
    from pytorch_distributed_tpu.gateway import Gateway

    cfg, params = tiny_model
    prompts = _prompts(cfg)

    # in-process reference: the SAME prompts through a plain router.
    # retain_results=False drops transcripts at retire, so collect from
    # step() directly — exactly what the gateway's driver does.
    # n_replicas=1: routing never changes a request's greedy stream, and
    # one engine init keeps the module fixture cheap in the fast tier.
    ref_router = _build_router(cfg, params, n_replicas=1)
    ref_rids = [ref_router.submit(p, 6) for p in prompts]
    reference = {rid: [] for rid in ref_rids}
    for _ in range(4000):
        if ref_router.idle:
            break
        for rid, tok in ref_router.step():
            reference[rid].append(int(tok))
    ref_router.drain(max_steps=100)
    ref_tokens = [reference[rid] for rid in ref_rids]
    assert all(len(t) == 6 for t in ref_tokens)

    path = str(tmp_path_factory.mktemp("gw") / "gw.jsonl")
    mlog = MetricsLogger(path)
    router = _build_router(cfg, params, metrics_log=mlog,
                           reqtrace=ReqTracer(mlog))
    gw = Gateway(router, port=0, metrics_log=mlog)
    gw.start()
    env = {
        "base": f"http://127.0.0.1:{gw.port}",
        "gw": gw,
        "router": router,
        "cfg": cfg,
        "prompts": prompts,
        "ref_tokens": ref_tokens,
        "jsonl": path,
    }
    yield env
    gw.stop()
    router.drain(max_steps=4000)
    mlog.close()


def _http_records(path):
    rows = [json.loads(l) for l in open(path) if l.strip()]
    return [r for r in rows if r.get("kind") == "http"]


def _wait(pred, timeout=20.0, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


# ---------------------------------------------------------------------------
# 1. fidelity: the wire adds transport, not entropy
# ---------------------------------------------------------------------------

def test_sse_stream_token_identical_to_inprocess(gw_env):
    from pytorch_distributed_tpu.gateway import generate

    for prompt, expect in zip(gw_env["prompts"], gw_env["ref_tokens"]):
        out = generate(gw_env["base"], prompt, 6)
        assert out["status"] == 200, out
        assert out["outcome"] == "complete", out
        assert out["tokens"] == expect, (
            "HTTP stream diverged from the in-process replay")
        assert out["usage"] == {"prompt_tokens": len(prompt),
                                "completion_tokens": 6}
        assert out["rid"] >= 0


def test_sse_events_ordered_and_indexed(gw_env):
    from pytorch_distributed_tpu.gateway import open_stream

    with open_stream(gw_env["base"], gw_env["prompts"][0], 5) as st:
        events = list(st.events())
    names = [n for n, _ in events]
    assert names == ["token"] * 5 + ["done"]
    assert [d["i"] for n, d in events if n == "token"] == list(range(5))
    done = events[-1][1]
    assert done["outcome"] == "complete"
    assert done["usage"]["completion_tokens"] == 5


# ---------------------------------------------------------------------------
# 2. control-plane mapping: deadline, shed ladder, health, metrics
# ---------------------------------------------------------------------------

def test_lapsed_deadline_sheds_as_429_with_retry_after(gw_env):
    from pytorch_distributed_tpu.gateway import generate

    out = generate(gw_env["base"], gw_env["prompts"][0], 5, deadline_ms=0)
    assert out["status"] == 429, out
    assert out["reason"] == "deadline-expired", out
    assert out["retry_after"] == "1"
    assert out["error"] == "shed"


def test_generous_deadline_admits(gw_env):
    from pytorch_distributed_tpu.gateway import generate

    out = generate(gw_env["base"], gw_env["prompts"][0], 4,
                   deadline_ms=60_000)
    assert out["status"] == 200 and out["outcome"] == "complete", out


def test_health_endpoint_is_the_health_plane(gw_env):
    from pytorch_distributed_tpu.gateway import health

    snap = health(gw_env["base"])
    assert len(snap["replicas"]) == 2
    for i, rec in enumerate(snap["replicas"]):
        assert rec["replica"] == i
        assert rec["state"] in ("healthy", "suspect", "dead",
                                "draining", "rejoining")
    assert snap["routable"] == 2  # nothing has been failed here
    # verbatim the router's plane, not a paraphrase
    assert [r["state"] for r in snap["replicas"]] == \
        [h["state"] for h in gw_env["router"].health]


def test_metrics_endpoint_carries_fleet_and_gateway_gauges(gw_env):
    from pytorch_distributed_tpu.gateway import metrics_text

    text = metrics_text(gw_env["base"])
    for key in ("pdt_gateway_open_streams", "pdt_gateway_connections",
                "pdt_gateway_http_429", "pdt_completed"):
        assert any(line.startswith(key + " ") for line
                   in text.splitlines()), f"{key} missing from /metrics"


# ---------------------------------------------------------------------------
# 3. disconnect → cancel
# ---------------------------------------------------------------------------

def test_mid_stream_disconnect_cancels_request(gw_env):
    from pytorch_distributed_tpu.gateway import open_stream

    gw, router = gw_env["gw"], gw_env["router"]
    cancelled0 = router.metrics()["cancelled"]
    gw_cancel0 = gw.metrics()["gateway_cancels"]

    st = open_stream(gw_env["base"], gw_env["prompts"][0], 40)
    it = st.events()
    name, data = next(it)          # stream is live past admission
    assert name == "token" and data["i"] == 0
    st.close()                     # hang up mid-stream

    assert _wait(lambda: gw.metrics()["gateway_cancels"] > gw_cancel0), \
        "disconnect never reached FleetRouter.cancel"
    assert _wait(lambda: router.metrics()["cancelled"] > cancelled0)
    # the stream table does not retain the hung-up rid
    assert _wait(lambda: gw.metrics()["gateway_open_streams"] == 0)
    # cancel-to-block-free latency was observed
    assert gw.metrics()["gateway_cancel_free_count"] >= 1


def test_disconnect_record_and_span_outcome_cancelled(gw_env):
    """The JSONL trail of the disconnect above: an ``http`` record with
    ``disconnect=true`` and a root span closed ``outcome=cancelled``."""
    recs = _http_records(gw_env["jsonl"])
    dis = [r for r in recs if r.get("disconnect")]
    assert dis, "no disconnect http record written"
    assert dis[-1]["status"] == 200 and dis[-1]["outcome"] == "cancelled"

    rows = [json.loads(l) for l in open(gw_env["jsonl"]) if l.strip()]
    ends = [r for r in rows if r.get("kind") == "span"
            and r.get("ev") == "end" and r.get("outcome") == "cancelled"]
    assert ends, "no span closed outcome=cancelled"


@pytest.mark.slow  # fast tier sits ~60 s under its cap; ci_check.sh
# --gateway-smoke runs this by node id (node-id selection ignores -m)
def test_disconnect_storm_leaks_zero_blocks(tiny_model, tmp_path,
                                            monkeypatch):
    """6 concurrent streams all hang up after the first token, under the
    block sanitizer: every cancel must free its blocks — quiesce clean."""
    from pytorch_distributed_tpu.gateway import Gateway, open_stream

    monkeypatch.setenv("PDT_BLOCKSAN", "1")
    cfg, params = tiny_model
    mlog = MetricsLogger(str(tmp_path / "storm.jsonl"))
    router = _build_router(cfg, params, metrics_log=mlog,
                           reqtrace=ReqTracer(mlog))
    assert router.blocksan is not None
    gw = Gateway(router, port=0, metrics_log=mlog)
    gw.start()
    base = f"http://127.0.0.1:{gw.port}"
    prompts = _prompts(cfg, n=6, seed=3)

    hung = []

    def _one(prompt):
        st = open_stream(base, prompt, 40, timeout=30.0)
        next(st.events())  # first token over the wire, then hang up
        st.close()
        hung.append(1)

    try:
        threads = [threading.Thread(target=_one, args=(p,), daemon=True)
                   for p in prompts]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60.0)
        assert len(hung) == 6
        assert _wait(lambda: gw.metrics()["gateway_cancels"] >= 6,
                     timeout=30.0), gw.metrics()
        assert _wait(lambda: gw.metrics()["gateway_open_streams"] == 0)
    finally:
        gw.stop()
        router.drain(max_steps=4000)
        mlog.close()
    # the storm's whole point: cancel freed every block, provably
    router.blocksan.assert_clean()
    assert router.metrics()["cancelled"] >= 6


# ---------------------------------------------------------------------------
# 4. malformed-input hardening: 400 + JSON body, never a stack trace
# ---------------------------------------------------------------------------

def _raw_post(base, body: bytes, headers=None):
    """POST raw bytes; return (status, parsed-json-body)."""
    req = urllib.request.Request(
        base + "/v1/generate", data=body,
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST")
    try:
        with urllib.request.urlopen(req, timeout=15.0) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        raw = e.read().decode("utf-8", "replace")
        assert "Traceback" not in raw, raw  # hardening: no stack traces
        return e.code, json.loads(raw)      # and ALWAYS a JSON body


def test_bad_json_is_400(gw_env):
    status, body = _raw_post(gw_env["base"], b'{"prompt": [1, 2')
    assert status == 400 and body["error"] == "bad-json", body


def test_non_numeric_deadline_is_400(gw_env):
    status, body = _raw_post(
        gw_env["base"],
        json.dumps({"prompt": [1, 2, 3], "max_new_tokens": 4}).encode(),
        headers={"X-Deadline-Ms": "soon"})
    assert status == 400 and body["error"] == "bad-deadline", body


def test_oversized_prompt_is_400_not_a_crash(gw_env):
    # 200 tokens > max_seq_len=96: the scheduler's admission validator
    # raises ValueError; the gateway must surface it as a 400
    big = list(range(1, 201))
    status, body = _raw_post(
        gw_env["base"],
        json.dumps({"prompt": big, "max_new_tokens": 4}).encode())
    assert status == 400 and body["error"] == "invalid-request", body
    assert "detail" in body


@pytest.mark.parametrize("payload,err", [
    ({"max_new_tokens": 4}, "bad-prompt"),                # missing
    ({"prompt": [], "max_new_tokens": 4}, "bad-prompt"),  # empty
    ({"prompt": [1, "a"], "max_new_tokens": 4}, "bad-prompt"),
    ({"prompt": [1, 2], "max_new_tokens": 0}, "bad-max-new-tokens"),
    ({"prompt": [1, 2], "max_new_tokens": 4, "session": "x"},
     "bad-session"),
])
def test_bad_payload_types_are_400(gw_env, payload, err):
    status, body = _raw_post(gw_env["base"],
                             json.dumps(payload).encode())
    assert status == 400 and body["error"] == err, body


def test_gateway_still_serves_after_the_abuse(gw_env):
    """Hardening is only real if the gateway SURVIVES it routable."""
    from pytorch_distributed_tpu.gateway import generate

    out = generate(gw_env["base"], gw_env["prompts"][1], 3)
    assert out["status"] == 200 and out["outcome"] == "complete", out


# ---------------------------------------------------------------------------
# 5. hygiene: census decls + JSONL schema conformance
# ---------------------------------------------------------------------------

def test_gateway_census_declared_and_bounded(gw_env):
    gw = gw_env["gw"]
    owners = gw.census_owners()
    assert owners, "gateway exposed no census owners"
    for name, obj in owners:
        assert undeclared_containers(obj) == []
        _, viol, undecl = audit_owner(name, obj, live=0, live_slack=4)
        assert viol == [] and undecl == [], (viol, undecl)


@pytest.mark.slow  # spins the whole serve_lm recipe; --gateway-smoke
# runs it by node id
def test_serve_lm_http_port_recipe(monkeypatch):
    """``recipes/serve_lm.py --http-port 0``: the recipe stands up the
    front door on an ephemeral port (exposed as ``serve_lm.GATEWAY``
    for in-process drivers), serves a real request, and shuts down
    clean when the duration lapses."""
    import importlib.util
    import os
    import sys

    from pytorch_distributed_tpu.gateway import generate

    recipes = os.path.join(os.path.dirname(__file__), os.pardir,
                           "recipes")
    monkeypatch.syspath_prepend(recipes)
    spec = importlib.util.spec_from_file_location(
        "serve_lm", os.path.join(recipes, "serve_lm.py"))
    serve_lm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(serve_lm)
    monkeypatch.setattr(sys, "argv", [
        "serve_lm.py", "--tiny", "--replicas", "2",
        "--http-port", "0", "--http-duration", "6"])
    th = threading.Thread(target=serve_lm.main, daemon=True)
    th.start()
    try:
        assert _wait(lambda: serve_lm.GATEWAY is not None
                     and serve_lm.GATEWAY.port, timeout=90.0), \
            "recipe never brought the gateway up"
        base = f"http://127.0.0.1:{serve_lm.GATEWAY.port}"
        out = generate(base, [5, 6, 7, 8], 3)
        assert out["status"] == 200 and out["outcome"] == "complete", out
    finally:
        th.join(timeout=90.0)
    assert not th.is_alive(), "recipe did not shut down after duration"


def test_http_jsonl_validates_against_schema(gw_env):
    recs = _http_records(gw_env["jsonl"])
    assert len(recs) >= 5, "the module's traffic left too few records"
    assert validate_stream(recs) == [], validate_stream(recs)[:3]
    statuses = {r["status"] for r in recs}
    assert {200, 400, 429} <= statuses, statuses
    # rejected-before-admission records carry rid=-1 by contract
    assert all(r["rid"] == -1 for r in recs if r["status"] == 400)
