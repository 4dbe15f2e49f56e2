import json
import os

import pytest

from perfbench.harness import tracing


@pytest.fixture(scope="module")
def events(root):
    with open(os.path.join(root, "perfbench", "fixtures",
                           "trace_small.json")) as f:
        return json.load(f)


def test_known_busy_idle_and_kernel_numbers(events):
    r = tracing.reduce_events(events)
    # busy, operations and programs together: [1000,6000) + [7000,9000) +
    # [12000,17000) = 12,000 ns; the window span is 20,000 ns long, all of
    # it counted, and the device's events cover [1000,17000) of it
    assert r["window_s"] == pytest.approx(20e-6)
    assert r["busy_s"] == pytest.approx(12e-6)
    assert r["coverage"] == pytest.approx(0.8)
    # single operations cover [1000,17000): all three executions lie in it
    assert r["modules_covered"] == {"jit_body(111)": 2, "jit_body(222)": 1}
    ops = dict(r["device_ops"])
    assert ops["copy_bf16_2561_16_16_64_"] == pytest.approx(6e-6)
    assert ops["fusion_f32_64_50257_"] == pytest.approx(2.5e-6)
    assert ops["multiply_reduce_fusion_bf16_64_16_64_"] == pytest.approx(1e-6)
    # a while contains its body's operations: not listed beside them
    assert not any(k.startswith("while") for k in ops)
    assert r["device_ops"][0][0] == "copy_bf16_2561_16_16_64_"
    # the decode program ran twice for 5 us, the chunk program once for 2
    assert r["modules"]["jit_body(111)"] == pytest.approx([5e-6, 5e-6])
    assert r["modules"]["jit_body(222)"] == pytest.approx([2e-6])


def test_idle_gaps_go_to_the_span_the_host_was_in(events):
    gaps = dict(tracing.reduce_events(events)["idle_gaps"])
    # idle at the window's head [0,1000) and inside it [6000,7000), both
    # under the first router.step; [9000,12000), whose middle 10,500 is
    # under no span; and at the tail [17000,20000), under the second
    # router.step: a device that stands still at either end is idle
    assert gaps["router.step"] == pytest.approx(5000e-9)
    assert gaps[tracing.NO_SPAN] == pytest.approx(3000e-9)
    assert sum(gaps.values()) == pytest.approx(8e-6)


def test_exposed_collective_time(events):
    r = tracing.reduce_events(events)
    # all-reduce [15500,16500); a fusion runs from 16000: 500 ns exposed
    assert r["collective_exposed_s"] == pytest.approx(0.5e-6)


def test_no_device_operation_is_an_error():
    with pytest.raises(RuntimeError):
        tracing.reduce_events({"devices": {}, "spans": []})


def test_op_label():
    assert tracing.op_label(
        "%select_convert_fusion = f32[64,64,16,16,64]{4,3,2,1,0} fusion(...)"
    ) == "select_convert_fusion_f32_64_64_16_16_64_"
    assert tracing.op_label(
        "%fusion.12.1 = (bf16[4096,16,16,64]{3,2,1,0}, bf16[2]{0}) fusion()"
    ) == "fusion_bf16_4096_16_16_64_"
    assert tracing.is_collective("%all-reduce-start.3 = f32[8]{0} "
                                 "all-reduce-start(f32[8]{0} %x)")
    assert not tracing.is_collective("%fusion.3 = f32[8]{0} fusion()")


def test_interval_arithmetic():
    u = tracing.union([[5, 7], [0, 2], [1, 3], [7, 8], [10, 10]])
    assert u == [[0, 3], [5, 8]]
    assert tracing.length(u) == 6
    assert tracing.subtract([[0, 10]], [[1, 2], [4, 6], [9, 12]]) == [
        [0, 1], [2, 4], [6, 9]]
    assert tracing.subtract([[0, 3], [5, 8]], [[2, 6]]) == [[0, 2], [6, 8]]


def test_spans_on_the_host_clock():
    s = tracing.Spans()
    with s.span("a"):
        pass
    s.records.append(("a", 10.0, 12.0))
    s.records.append(("b", 11.0, 11.5))
    assert s.total("a", 10.5, 11.0) == pytest.approx(0.5)
    assert s.total("b") == pytest.approx(0.5)
