"""The readers of the program's own span stream: each on a hand-made ring
with known values, ``None`` where its spans are absent or the program has
no stream at all (a parent from before it), and through both cells' CPU
rehearsal."""

import math

import pytest

from perfbench.harness.manifest import Cell
from perfbench.metrics import _spans
from pytorch_distributed_tpu.telemetry.spans import SpanTracer

TRAIN, SERVE = "gpt2-medium.pretrain", "gpt2-medium.chat-backlog"
NEW = {
    TRAIN: {"setup_program_load_s", "setup_build_s",
            "trainer_data_wait_share", "step_dispatch_host_ms",
            "flash_attention_step_ms"},
    SERVE: {"setup_program_load_s", "setup_build_s", "tick_exposed_host_ms",
            "gate_decide_ms", "queue_wait_p50_ms"},
}
T0, T1, SETUP = 100.0, 110.0, 40.0  # the window, and set-up before it


def outcome(device_ops=()):
    return {"counters": {"window": (T0, T1)},
            "e2e": {"setup_s": SETUP},
            # 10.5 steps of 0.4 s fill a traced window of 4.2 s
            "trace": {"device_ops": [list(x) for x in device_ops],
                      "busy_s": 4.2, "window_s": 4.2,
                      "modules": {"jit_lm_train_step(1)": [0.4] * 10,
                                  "jit_other(2)": [0.001] * 3}}}


@pytest.fixture
def ring(monkeypatch):
    tr = SpanTracer()
    monkeypatch.setattr(_spans, "stream", lambda: tr)
    return tr


def reader(cell, name):
    return Cell(cell).reader(name)


def test_the_manifest_lists_each_new_metric_in_its_cells(manifest):
    rows = {m["name"]: m for m in manifest["per_layer"]}
    for cell, names in NEW.items():
        for name in names:
            assert cell in rows[name]["workloads"]
            assert name in {m["name"] for m in Cell(cell).per_layer()}
    assert rows["flash_attention_step_ms"]["source"] == "device_trace"
    assert all(rows[n]["moves"] == "setup_s"
               for n in ("setup_program_load_s", "setup_build_s"))


def test_set_up_readers_split_build_from_program_load(ring):
    build = ring.record("trainer.build", 62.0, 75.0)
    ring.record("program.load", 64.0, 66.0, cause=build)  # inside the build
    ring.record("program.load", 80.0, 98.0)  # the first step's
    ring.record("program.load", 10.0, 20.0)  # an earlier run's, same process
    ring.record("program.load", 104.0, 105.0)  # in the window: not set-up
    assert reader(TRAIN, "setup_program_load_s")(outcome()) == 20.0
    assert reader(TRAIN, "setup_build_s")(outcome()) == 13.0 - 2.0
    ring.clear()
    ring.record("router.build", 61.0, 70.0)
    assert reader(SERVE, "setup_build_s")(outcome()) == 9.0
    assert reader(SERVE, "setup_program_load_s")(outcome()) is None


def test_trainer_loop_readers(ring):
    for i in range(5):  # steps of 2 s: 0.1 s waiting, 0.3 s dispatching
        ring.record("train.data_wait", T0 + 2 * i + 1, T0 + 2 * i + 1.1)
        ring.record("train.step_dispatch", T0 + 2 * i + 1.1,
                    T0 + 2 * i + 1.4)
    ring.record("train.data_wait", T0 - 0.3, T0 + 0.2)  # clipped: 0.2 s
    ring.record("train.step_dispatch", 50.0, 59.0)  # set-up's: not counted
    share = reader(TRAIN, "trainer_data_wait_share")
    assert share(outcome()) == pytest.approx(100.0 * (5 * 0.1 + 0.2) / 10.0)
    assert reader(TRAIN, "step_dispatch_host_ms")(outcome()) == (
        pytest.approx(300.0))


def test_tick_exposed_host_is_wait_end_to_next_launch(ring):
    t = T0
    for gap, chunk in ((0.004, True), (0.006, False), (0.010, True)):
        ring.record("engine.collect.wait", t, t + 0.2)
        t += 0.2 + gap
        if chunk:  # the tick's first launch is the chunk program's
            ring.record("engine.chunk.launch", t, t + 0.001)
            ring.record("engine.decode.launch", t + 0.002, t + 0.003)
        else:
            ring.record("engine.decode.launch", t, t + 0.001)
        t += 0.01
    ring.record("engine.collect.wait", t, t + 0.2)  # no launch follows
    assert reader(SERVE, "tick_exposed_host_ms")(outcome()) == (
        pytest.approx(6.0))


def test_gate_and_queue_readers(ring):
    for i, ms in enumerate((2.0, 4.0, 9.0)):
        ring.record("router.gate", T0 + i, T0 + i + ms / 1e3, rid=i)
    ring.record("router.gate", 50.0, 50.5, rid=9)  # before the window
    assert reader(SERVE, "gate_decide_ms")(outcome()) == pytest.approx(4.0)
    # submitted in set-up, admitted in the window: counted by admission
    ring.record("req.queue", 80.0, 101.0, rid=0)
    ring.record("req.queue", 90.0, 103.0, rid=1)
    ring.record("req.queue", 100.5, 109.0, rid=2)
    ring.record("req.queue", 70.0, 99.0, rid=3)  # admitted before it
    assert reader(SERVE, "queue_wait_p50_ms")(outcome()) == (
        pytest.approx(13000.0))


def test_flash_attention_step_reads_the_named_kernels():
    ops = [("flash_fwd_bf16_256_1024_64_", 0.315),
           ("flash_bwd_fused_bf16_256_1_1024_64_", 0.525),
           ("multiply_reduce_fusion_f32_1024_", 0.36)]
    read = reader(TRAIN, "flash_attention_step_ms")
    # 0.84 of 4.2 busy seconds: a fifth of a 400 ms step
    assert read(outcome(ops)) == pytest.approx(80.0)
    split = [("jvp_flash_fwd__bf16_256_1024_64_", 0.21),
             ("flash_bwd_dq_bf16_256_1024_64_", 0.105),
             ("flash_bwd_dkv_bf16_256_1024_64_", 0.105)]
    assert read(outcome(split)) == pytest.approx(40.0)
    # device_ops holds the ten costliest only: a kernel that fell out of
    # it must not leave a partial sum standing for the whole
    assert read(outcome(ops[:1])) is None
    assert read(outcome(ops[1:])) is None
    assert read(outcome([("attn_bf16_256_1024_64_", 0.36)])) is None


@pytest.mark.parametrize("cell", [TRAIN, SERVE])
def test_readers_report_nothing_without_spans(cell, ring, monkeypatch):
    """An empty ring, and a program with no stream (the parent of the PR
    that brought it): every reader returns ``None`` and does not raise."""
    for name in NEW[cell]:
        assert reader(cell, name)(outcome()) is None
    monkeypatch.setattr(_spans, "stream", lambda: None)
    for name in NEW[cell]:
        assert reader(cell, name)(outcome()) is None


REHEARSAL = """
import json, sys
from perfbench.harness.manifest import Cell
cell = Cell(sys.argv[1])
out = cell.job_module().run(cell, seed=4300000007, seconds=2.0, trace=False,
                            tiny=True)
print(json.dumps({"correct": out["correct"], "setup_s": out["e2e"]["setup_s"],
                  "values": {n: cell.reader(n)(out) for n in sys.argv[2:]}}))
"""


@pytest.mark.parametrize("cell", [TRAIN, SERVE])
def test_cpu_rehearsal_gives_every_span_metric(root, cell):
    """The job at its toy size on the CPU, then each reader on what it
    returns. (``run.py --trace 1 --tiny 1`` itself refuses: the CPU
    backend has no device plane, ``test_cells.py``. The span readers need
    none; the kernels' reader is checked above on a hand-made line.)"""
    import json
    import os
    import subprocess
    import sys

    names = sorted(NEW[cell] - {"flash_attention_step_ms"})
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", REHEARSAL, cell] + names,
                       cwd=root, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["correct"] is True
    for name in names:
        v = got["values"][name]
        assert v is not None and math.isfinite(v) and v >= 0, (name, v)
    # set-up's two parts lie inside set-up
    v = got["values"]
    assert v["setup_program_load_s"] > 0 and v["setup_build_s"] > 0
    assert v["setup_program_load_s"] + v["setup_build_s"] <= got["setup_s"]
