"""The readers of the tick's launch path (PR 38): each on a hand-made ring
with known values, ``None`` where the program has no such spans (a parent
from before them) or no stream at all, the identity that ties them to
``tick_exposed_host_ms``, the manifest's entries, and through the serving
cell's CPU rehearsal."""

import json
import math
import os
import subprocess
import sys

import pytest

from perfbench.harness.manifest import Cell
from perfbench.metrics import _launch_path, _spans
from perfbench.tests.test_span_metrics import REHEARSAL
from pytorch_distributed_tpu.telemetry.spans import SpanTracer

SERVE = "gpt2-medium.chat-backlog"
SERVING_CELLS = [SERVE, "ouro-2.6b.reason-backlog",
                 "zaya1-8b.reason-long-backlog",
                 "ling-3.0-flash.doc-reason-backlog"]
LAYERS = {"tick_host_path_ms": "routing and scheduling",
          "launch_build_ms": "programs", "launch_put_ms": "programs",
          "launch_call_ms": "programs", "relaunch_lag_ms": "programs"}
T0, T1 = 100.0, 110.0  # the window
MS = 1e-3


def outcome():
    return {"counters": {"window": (T0, T1)}, "e2e": {"setup_s": 40.0}}


@pytest.fixture
def ring(monkeypatch):
    tr = SpanTracer()
    monkeypatch.setattr(_spans, "stream", lambda: tr)
    return tr


def read(name):
    return Cell(SERVE).reader(name)(outcome())


def launch(ring, step, prog, t, build, put, call, gap=0.0):
    """One program's launch path from ``t``: ``build``, then the launch
    span holding ``put`` and ``call`` (``gap`` between the two), all in
    seconds; returns where the call returned."""
    ring.record(f"engine.{prog}.build", t, t + build, cause=step)
    t += build
    end = t + put + gap + call
    span = ring.record(f"engine.{prog}.launch", t, end, cause=step)
    ring.record(f"engine.{prog}.put", t, t + put, cause=span)
    ring.record(f"engine.{prog}.call", end - call, end, cause=span)
    return end


def tick(ring, t, launches, before=1 * MS, between=0.5 * MS, new=True):
    """One ``router.step`` from ``t``: a wait of 5 ms, ``before`` of
    scheduling, then each of ``launches`` (program, build, put, call in ms),
    ``between`` apart. With ``new`` false the launches carry their launch
    spans only, as a parent's ring does. Returns the step's end."""
    wait_end = t + 5 * MS
    at = wait_end + before
    spans = []
    for prog, build, put, call in launches:
        if new:
            spans.append((prog, at, build * MS, put * MS, call * MS))
        else:
            spans.append((prog, at + build * MS, (put + call) * MS))
        at += (build + put + call) * MS + between
    end = at + 0.2 * MS
    step = ring.record("router.step", t, end)
    ring.record("engine.collect.wait", t, wait_end, cause=step)
    for s in spans:
        if new:
            launch(ring, step, *s)
        else:
            ring.record(f"engine.{s[0]}.launch", s[1], s[1] + s[2],
                        cause=step)
    return end


def fill(ring, new=True):
    """Four ticks in the window and two that are not: the first launch is
    the chunk program in two, the decode tick in one, and one launches
    nothing; one step straddles the window's start and one lies after it."""
    tick(ring, T0 - 0.004, [("chunk", 9.0, 9.0, 9.0)], new=new)  # straddles
    t = T0 + 1.0
    #            build put  call        build put  call
    t = tick(ring, t, [("chunk", 0.3, 0.5, 2.0), ("decode", 0.2, 0.4, 1.5)],
             new=new)
    t = tick(ring, t + 0.01, [("decode", 0.1, 0.3, 1.0)], new=new)
    t = tick(ring, t + 0.01, [], new=new)
    t = tick(ring, t + 0.01, [("chunk", 0.5, 0.7, 3.0),
                              ("decode", 0.2, 0.4, 1.9)], new=new)
    tick(ring, T1 + 1.0, [("chunk", 9.0, 9.0, 9.0)], new=new)  # after it


def test_the_manifest_lists_each_metric_in_the_four_serving_cells(manifest):
    rows = {m["name"]: m for m in manifest["per_layer"]}
    for name, layer in LAYERS.items():
        row = rows[name]
        assert row["workloads"] == SERVING_CELLS
        assert (row["layer"], row["source"], row["moves"], row["unit"],
                row["better"]) == (layer, "program_span",
                                   "serve_tokens_per_s", "ms", "lower")
        for cell in SERVING_CELLS:
            assert name in {m["name"] for m in Cell(cell).per_layer()}
        assert name not in {
            m["name"] for m in Cell("gpt2-medium.pretrain").per_layer()}
    # appended: the accepted entries stay where they were
    assert [m["name"] for m in manifest["per_layer"]][-5:] == list(LAYERS)


def test_ticks_are_the_windows_steps_that_hold_a_wait(ring):
    fill(ring)
    ticks = _launch_path.ticks(outcome())
    assert [[l["program"] for l in t["launches"]] for t in ticks] == [
        ["chunk", "decode"], ["decode"], [], ["chunk", "decode"]]
    first = ticks[0]["launches"][0]
    assert first["build"].name == "engine.chunk.build"
    assert first["put"].t1 <= first["call"].t0
    # a step that collected nothing (the first of a run) is no tick
    ring.clear()
    step = ring.record("router.step", T0 + 1, T0 + 1.01)
    launch(ring, step, "decode", T0 + 1.001, 1 * MS, 1 * MS, 1 * MS)
    assert _launch_path.ticks(outcome()) == []


def test_each_reader_on_known_values(ring):
    fill(ring)
    # first launches: chunk (0.3, 0.5, 2.0), decode (0.1, 0.3, 1.0), chunk
    # (0.5, 0.7, 3.0); the straddling and the late 9 ms launches are out
    assert read("launch_build_ms") == pytest.approx(0.3)
    assert read("launch_put_ms") == pytest.approx(0.5)
    assert read("launch_call_ms") == pytest.approx(2.0)
    # wait's end -> 1 ms of scheduling -> build, put, call
    assert read("tick_host_path_ms") == pytest.approx(
        sorted([1 + 0.3 + 0.5 + 2.0, 1 + 0.1 + 0.3 + 1.0,
                1 + 0.5 + 0.7 + 3.0])[1])
    # the two ticks with both programs: 0.5 ms between the launches, then
    # the decode tick's build, put and call
    assert read("relaunch_lag_ms") == pytest.approx(
        (0.5 + 0.2 + 0.4 + 1.5 + 0.5 + 0.2 + 0.4 + 1.9) / 2)


def test_the_path_is_the_exposed_interval_and_the_first_put_and_call(ring):
    """ISSUE 38's identity: ``tick_exposed_host_ms`` ends where the first
    launch span opens, so it holds the scheduling and the first ``build``;
    the path adds that launch's ``put`` and ``call``."""
    t = T0 + 1.0
    for _ in range(5):  # equal ticks: a median of sums is the sum of medians
        t = tick(ring, t, [("chunk", 0.3, 0.5, 2.0),
                           ("decode", 0.2, 0.4, 1.5)]) + 0.01
    exposed = read("tick_exposed_host_ms")
    assert exposed == pytest.approx(1 + 0.3)
    assert read("tick_host_path_ms") == pytest.approx(
        exposed + read("launch_put_ms") + read("launch_call_ms"))
    # statements between put and call lie in the path and in neither part
    ring.clear()
    step = ring.record("router.step", T0 + 1, T0 + 1.02)
    ring.record("engine.collect.wait", T0 + 1, T0 + 1.005, cause=step)
    launch(ring, step, "decode", T0 + 1.006, 0.2 * MS, 0.5 * MS, 2 * MS,
           gap=0.1 * MS)
    assert read("tick_host_path_ms") == pytest.approx(
        read("tick_exposed_host_ms") + 0.5 + 2.0 + 0.1)
    assert read("relaunch_lag_ms") is None  # one program only


def test_a_ring_without_the_new_names_reads_nothing(ring, monkeypatch):
    """The parent's ring (launch spans, no build, put or call), an empty
    ring, and a program with no stream: ``None``, and nothing raises; the
    accepted twin still reads the parent's ring."""
    fill(ring, new=False)
    for name in LAYERS:
        assert read(name) is None
    assert read("tick_exposed_host_ms") is not None
    ring.clear()
    for name in LAYERS:
        assert read(name) is None
    monkeypatch.setattr(_spans, "stream", lambda: None)
    for name in LAYERS:
        assert read(name) is None


def test_cpu_rehearsal_gives_every_launch_path_metric(root):
    """The serving job at its toy size on the CPU, untraced, then each
    reader on what it returns (as ``test_span_metrics.py`` drives it)."""
    names = sorted(LAYERS) + ["tick_exposed_host_ms"]
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", REHEARSAL, SERVE] + names,
                       cwd=root, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["correct"] is True
    v = got["values"]
    for name in names:
        assert v[name] is not None and math.isfinite(v[name]) and (
            v[name] > 0), (name, v[name])
    # the path holds the exposed interval (its first build within) and the
    # first launch's put and call; medians, so to within their skew
    assert v["tick_host_path_ms"] >= v["launch_call_ms"]
    assert v["tick_host_path_ms"] >= v["tick_exposed_host_ms"]
