"""The looped configuration's cell: its files, its CPU rehearsal, the count
of what a looped tick needs, and its two readers on hand-made data."""

import json
import os

import pytest

from conftest import run_cell
from perfbench.harness import opcount_looped, traffic
from perfbench.harness.manifest import Cell
from perfbench.metrics import _spans
from pytorch_distributed_tpu.telemetry.spans import SpanTracer

CELL = "ouro-2.6b.reason-backlog"
PUBLISHED = {"embed_dim": 2048, "num_layers": 48, "vocab_size": 49152,
             "mlp": "swiglu", "mlp_dim": 5632, "ut_steps": 4}


def test_the_configuration_is_the_catalogs_and_only_the_context_is_cut(root):
    cfg = json.load(open(os.path.join(root, "perfbench", "configs",
                                      "ouro-2.6b.json")))
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(r for r in map(json.loads, open(catalog))
                   if r["name"] == "Ouro-2.6B")
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert cfg[key] == value, key
    assert cfg["reduced"] == ["n_positions"]
    p = cfg["program"]
    assert (p["num_layers"], p["embed_dim"], p["num_heads"], p["mlp_dim"],
            p["vocab_size"], p["ut_steps"], p["rope_theta"]) == (
        cfg["num_hidden_layers"], cfg["hidden_size"],
        cfg["num_attention_heads"], cfg["intermediate_size"],
        cfg["vocab_size"], cfg["total_ut_steps"], cfg["rope_theta"])
    assert p["embed_dim"] // p["num_heads"] == cfg["head_dim"]
    assert p["norm_eps"] == cfg["rms_norm_eps"]
    assert p["max_seq_len"] == cfg["n_positions"] == 640


def test_the_cell_fills_the_chip_as_its_file_says(root):
    cell = Cell(CELL, root)
    job, program = cell.job, cell.config["program"]
    per_token = (program["ut_steps"] * program["num_layers"] * 2
                 * program["embed_dim"] * 2)
    assert per_token == 1_572_864  # 1.5 MiB of K/V a token
    pool = job["blocks"] * job["block_len"] * per_token
    assert pool == 289 * 25_165_824
    weights = 2 * (program["num_layers"]
                   * (opcount_looped.layer_matmul_params(program)
                      + 4 * program["embed_dim"])
                   + 2 * program["embed_dim"] * program["vocab_size"]
                   + program["embed_dim"] + program["embed_dim"] + 1)
    assert weights == 2 * 2_667_974_657
    assert 0.25 * 16e9 < weights + pool < 13e9
    # the mix: 64 pairs, none longer than the context served
    pairs = traffic.length_multiset(cell.traffic)
    assert len(pairs) == 64
    assert max(p + o for p, o in pairs) <= cell.config["n_positions"]
    assert sum(o for _, o in pairs) > 1.8 * sum(p for p, _ in pairs)


def test_the_rehearsal_is_correct_and_the_control_is_not(root):
    rc, line, out, err = run_cell(root, CELL, "--control", "fp8")
    assert rc == 0, err[-3000:]
    assert line["correct"] is True, out[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "gap_p95_ms",
                                    "setup_s"}
    assert line["device"]["platform"] == "cpu"
    control = line["info"]["control"][0]
    assert control["ok"] is False and control["value"] > 3 * control["limit"]


def test_a_looped_tick_against_a_hand_count():
    toy = {"embed_dim": 4, "num_layers": 2, "vocab_size": 10,
           "mlp": "swiglu", "mlp_dim": 6, "ut_steps": 3}
    # a layer: 4 x 16 (q, k, v, o) + 3 x 24 (gate, up, down) = 136
    assert opcount_looped.layer_matmul_params(toy) == 136
    assert opcount_looped.layer_matmul_params(
        dict(toy, mlp="gelu", mlp_dim=None)) == 4 * 16 + 2 * 4 * 16
    flops, bytes_ = opcount_looped.looped_decode_tick_need(
        toy, live_slots=5, live_context=70)
    # weights: 3 passes x 2 layers x 136 + head 40 = 856 parameters, 2 B
    # each; 5 embedding rows of 4 x 2 B; K and V of 70 live positions and
    # 5 new rows in 6 cache layers: 75 x 6 x 2 x 4 x 2 B
    assert bytes_ == 856 * 2 + 5 * 4 * 2 + 75 * 6 * 2 * 4 * 2
    # 2 operations a parameter a slot; QK^T and PV over 70 positions in 6
    # cache layers: 70 x 6 x 2 x 2 x 4
    assert flops == 5 * 2 * 856 + 70 * 6 * 2 * 2 * 4
    # the published sizes: 51,380,224 a layer in matrices, 4.93 GB a pass
    assert opcount_looped.layer_matmul_params(PUBLISHED) == 51_380_224
    _, b = opcount_looped.looped_decode_tick_need(PUBLISHED, 0, 0)
    assert b == 2 * (4 * 48 * 51_380_224 + 2048 * 49152)
    assert 19.7e9 < b < 20.0e9  # 24 ms of a v5e's 819 GB/s


@pytest.fixture
def ring(monkeypatch):
    tr = SpanTracer()
    monkeypatch.setattr(_spans, "stream", lambda: tr)
    return tr


def outcome(program=PUBLISHED, tick_s=0.4):
    return {"counters": {"window": (100.0, 110.0),
                         "traced_ticks": [(101.0, 15, 3000), (102.0, 16, 3200),
                                          (103.0, 0, 0)]},
            "e2e": {"setup_s": 40.0},
            "config": {"program": program},
            "device": {"kind": "TPU v5 lite"},
            "trace": {"modules": {"jit_decode_tick(7)": [tick_s] * 3,
                                  "jit_chunk(9)": [0.01]},
                      "labels": {"jit_decode_tick(7)": "decode_tick",
                                 "jit_chunk(9)": "prefill_chunk"}}}


def test_pool_reserved_share_reads_admissions_free_blocks(ring):
    read = Cell(CELL).reader("pool_reserved_share")
    assert read(outcome()) is None  # no stream yet: nothing, no error
    ring.record("pool.alloc", 70.0, 71.0, blocks=289, cache_layers=192,
                weight_layers=48, block_bytes=25_165_824)
    ring.record("sched.admit", 90.0, 90.1, queued=3, free_blocks=0,
                waited=True)  # set-up's: not counted
    for i, free in enumerate((72, 36, 108)):
        ring.record("sched.admit", 101.0 + i, 101.1 + i, queued=32,
                    free_blocks=free, waited=False)
    assert read(outcome()) == pytest.approx(100.0 * (1 - 72 / 288))
    # a program whose spans carry no free_blocks (the parent's) reads nothing
    ring.clear()
    ring.record("pool.alloc", 70.0, 71.0, blocks=289)
    ring.record("sched.admit", 101.0, 101.1, queued=32)
    assert read(outcome()) is None


def test_looped_roofline_reads_the_traced_ticks():
    read = Cell(CELL).reader("looped_decode_tick_roofline")
    slots, context = 15.5, 3100.0  # the two ticks that delivered tokens
    _, bytes_ = opcount_looped.looped_decode_tick_need(PUBLISHED, slots,
                                                       context)
    assert read(outcome()) == pytest.approx(100.0 * bytes_ / 819e9 / 0.4)
    assert 6.0 < read(outcome()) < 9.0
    # a tick faster than the chip's memory allows is a fault, raised
    with pytest.raises(ArithmeticError):
        read(outcome(tick_s=0.02))
    # a configuration that does not loop, or has no program block
    assert read(outcome(program=dict(PUBLISHED, ut_steps=1))) is None
    assert read(dict(outcome(), config={"n_embd": 4})) is None


def test_the_manifest_gives_the_cell_its_metrics(manifest):
    cell = Cell(CELL)
    assert cell.chips == 1 and cell.job["job"] == "serve-backlog-program"
    assert {m["name"] for m in cell.end_to_end()} == {
        "serve_tokens_per_s", "gap_p95_ms", "setup_s"}
    names = {m["name"] for m in cell.per_layer()}
    assert {"looped_decode_tick_roofline", "pool_reserved_share",
            "decode_occupancy", "decode_tick_device_ms",
            "prefill_chunk_device_ms", "tick_host_ms",
            "tick_exposed_host_ms", "setup_program_load_s"} <= names
    # the GPT-2 tick's count is not applied to another block kind
    assert "decode_tick_roofline" not in names
    assert "decode_tick_roofline" in {
        m["name"] for m in Cell("gpt2-medium.chat-backlog").per_layer()}
