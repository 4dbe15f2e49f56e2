"""``paged_attention_busy_share`` on hand-made traces: with the fused
paged kernel among the costliest operations, and without it."""

import pytest

from perfbench.harness.manifest import Cell

CELLS = ["gpt2-medium.chat-backlog", "ouro-2.6b.reason-backlog"]
NAME = "paged_attention_busy_share"


def outcome(device_ops, busy_s=5.0):
    return {"trace": {"device_ops": [list(x) for x in device_ops],
                      "busy_s": busy_s, "window_s": 6.0}}


@pytest.mark.parametrize("cell", CELLS)
def test_the_manifest_lists_the_metric_in_both_serving_cells(manifest, cell):
    row = next(m for m in manifest["per_layer"] if m["name"] == NAME)
    assert row == {"name": NAME, "unit": "%", "better": "lower",
                   "source": "device_trace", "layer": "kernels",
                   "moves": "serve_tokens_per_s", "workloads": CELLS}
    assert NAME in {m["name"] for m in Cell(cell).per_layer()}
    assert NAME not in {m["name"]
                        for m in Cell("gpt2-medium.pretrain").per_layer()}


@pytest.mark.parametrize("cell", CELLS)
def test_it_is_the_named_kernels_share_of_busy_time(cell):
    read = Cell(cell).reader(NAME)
    ops = [("paged_decode_attn_bf16_64_16_8_64_", 1.5),  # the tick's
           ("fusion_bf16_64_16_64_", 0.7),
           ("paged_decode_attn_bf16_4_16_32_64_", 0.25),  # a chunk bucket's
           ("convolution_add_fusion_f32_1024_50257_", 0.2)]
    assert read(outcome(ops)) == pytest.approx(100.0 * 1.75 / 5.0)
    assert read(outcome(ops[:2])) == pytest.approx(30.0)


@pytest.mark.parametrize("cell", CELLS)
def test_a_program_without_the_kernel_reports_nothing(cell):
    """The dense gather's operations (the parent's trace), an empty
    trace, and a window in which the device did nothing."""
    read = Cell(cell).reader(NAME)
    dense = [("reshape_f32_64_1024_16_64_", 2.295),
             ("select_convert_fusion_f32_64_64_16_1024_", 1.117),
             ("fusion_bf16_4096_16_1024_", 0.667)]
    assert read(outcome(dense)) is None
    assert read(outcome([])) is None
    assert read(outcome([("paged_decode_attn_bf16_64_16_8_64_", 0.0)],
                        busy_s=0.0)) is None
