"""The ling configuration's cell: its files, its CPU rehearsal, the count of
what its decode tick needs, and its three readers on hand-made data."""

import json
import os

import pytest

from conftest import run_cell
from perfbench.harness import opcount_hybrid, traffic
from perfbench.harness.manifest import Cell
from perfbench.metrics import _spans
from pytorch_distributed_tpu.telemetry.spans import SpanTracer

CELL = "ling-3.0-flash.doc-reason-backlog"
REDUCED = {"num_hidden_layers": (6, 42), "num_experts": (128, 512),
           "vocab_size": (39296, 157184), "n_positions": (3072, 262144)}


@pytest.fixture(scope="module")
def config(root):
    with open(os.path.join(root, "perfbench", "configs",
                           "ling-3.0-flash.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def published(config):
    return config["program"]


def test_the_configuration_is_the_catalogs_and_no_width_is_cut(config):
    cfg = config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(r for r in map(json.loads, open(catalog))
                   if r["name"] == "Ling-3.0-flash")
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in REDUCED:
                assert cfg[key] == value, key
    assert cfg["reduced"] == list(REDUCED)
    for key, (held, was) in REDUCED.items():
        assert cfg[key] == held and cfg["published"][key] == was
    p = cfg["program"]
    assert (p["num_layers"], p["embed_dim"], p["num_heads"], p["head_dim"],
            p["vocab_size"], p["kv_lora_rank"], p["qk_rope_head_dim"],
            p["mlp_dim"], p["moe_dim"], p["moe_shared_dim"], p["moe_top_k"],
            p["moe_n_group"], p["moe_topk_group"], p["moe_routed_scale"],
            p["first_k_dense_replace"], p["layer_group_size"], p["norm_eps"],
            p["rope_theta"], p["use_bias"], p["max_seq_len"]) == (
        cfg["num_hidden_layers"], cfg["hidden_size"],
        cfg["num_attention_heads"], cfg["head_dim"], cfg["vocab_size"],
        cfg["kv_lora_rank"], cfg["qk_rope_head_dim"],
        cfg["intermediate_size"], cfg["moe_intermediate_size"],
        cfg["moe_shared_expert_intermediate_size"],
        cfg["num_experts_per_tok"], cfg["n_group"], cfg["topk_group"],
        cfg["routed_scaling_factor"], cfg["first_k_dense_replace"],
        cfg["layer_group_size"], cfg["rms_norm_eps"], cfg["rope_theta"],
        cfg["use_bias"], cfg["n_positions"])
    from pytorch_distributed_tpu.models.transformer import KDAttention

    assert cfg["short_conv_kernel_size"] == KDAttention.TAPS == (
        opcount_hybrid.CONV_TAPS)
    assert cfg["kda_lower_bound"] == KDAttention.LOWER_BOUND
    # the router scores all the published experts; a quarter are held
    assert p["n_experts"] == cfg["published"]["num_experts"] == 512
    assert p["experts_held"] == [0, cfg["num_experts"]]
    assert cfg["qk_nope_head_dim"] == cfg["v_head_dim"] == p["head_dim"]
    # no clamp is computed: the limits are 0 in every layer held
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        assert len(cfg[key]) == 42 and not any(cfg[key][:p["num_layers"]])
    for key in ("assumed", "departures_of_the_program", "deployment",
                "reduced_why"):
        assert cfg[key], key
    assert "28 v5e chips" in cfg["deployment"]
    assert "multi-token-prediction" in cfg["reduced_why"]


def test_the_cell_fills_the_chip_as_its_file_says(root, published):
    cell = Cell(CELL, root)
    job = cell.job
    parts = opcount_hybrid.sublayer_params(published)
    assert opcount_hybrid.layer_kinds(published) == (5, 1, 2, 4)
    assert 62.9e6 < parts["kda"] < 63.1e6 and 31.9e6 < parts["mla"] < 32.1e6
    assert parts["dense"] == 3 * 2560 * 6144
    assert parts["expert"] == 3 * 2560 * 768
    weights = (5 * parts["kda"] + parts["mla"] + 2 * parts["dense"]
               + 4 * (parts["routing"] + 128 * parts["expert"])
               + 6 * parts["norms"] + 2560 + 2 * 2560 * 39296)
    assert 3.68e9 < weights < 3.70e9  # 7.38 GB in bfloat16
    state, conv = opcount_hybrid.slot_state_bytes(published)
    assert state == 5 * 32 * 128 * 128 * 4 and conv == 5 * 3 * 12288 * 2
    slots = (job["slots"] + 1) * (state + conv)
    assert 2.7e9 < slots < 2.9e9
    assert opcount_hybrid.latent_row(published) == 640
    pool = job["blocks"] * job["block_len"] * 640 * 2
    assert 1.3e9 < pool < 1.4e9
    # every slot can hold n_positions (49,153 blocks would do; ISSUE 36
    # names 65,537: 4,096 positions a slot)
    assert job["blocks"] == 65537 > job["slots"] * (3072 // job["block_len"])
    assert 11e9 < 2 * weights + slots + pool < 12e9  # of 16 GB: 72%
    # the mix: 64 pairs, none longer than the context served
    pairs = traffic.length_multiset(cell.traffic)
    assert len(pairs) == 64
    assert max(p + o for p, o in pairs) <= cell.config["n_positions"]
    assert (min(p for p, _ in pairs), max(p for p, _ in pairs)) == (192, 2048)
    assert (min(o for _, o in pairs), max(o for _, o in pairs)) == (128, 1024)
    # the tick and TWO chunk programs: every reachable width lands on 128
    assert len(job["warm_jobs"]) == 2
    assert job["chunk_bucket_floor"][0] == job["warm_jobs"][0]
    assert job["max_chunk_jobs"] == job["warm_jobs"][1]
    assert job["chunk_bucket_floor"][1] == -(-2048 // job["block_len"]) == 128


def test_the_rehearsal_is_correct_and_the_control_is_not(root):
    """The float32 toy serves the reference's own tokens (gap 0). The
    control reads what float8 moves a logit by, which follows the seed's
    tokens at toy widths: one of two seeds must show it over the limit."""
    controls = []
    for seed in ("5", "3600000077"):
        rc, line, out, err = run_cell(root, CELL, "--control", "fp8",
                                      seed=seed)
        assert rc == 0, err[-3000:]
        assert line["correct"] is True, out[-3000:]
        assert line["failed"] == 0 and line["attempted"] > 0
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
        assert line["device"]["platform"] == "cpu"
        controls.append(line["info"]["control"][0])
        if not controls[-1]["ok"]:
            break
    assert controls[-1]["ok"] is False, controls


def test_a_tick_against_a_hand_count():
    toy = {"embed_dim": 4, "num_layers": 3, "vocab_size": 10, "num_heads": 2,
           "head_dim": 2, "layer_group_size": 3, "kv_lora_rank": 3,
           "qk_rope_head_dim": 2, "mlp_dim": 6,
           "n_experts": 8, "moe_dim": 5, "moe_shared_dim": 5,
           "first_k_dense_replace": 1}
    assert opcount_hybrid.layer_kinds(toy) == (2, 1, 1, 2)
    parts = opcount_hybrid.sublayer_params(toy)
    # inner 4: qkv 4 x 12, taps 4 x 12, gate_f 16 + dt_bias 4 + A_log 2,
    # beta 8, gate_o 16, the output norm 2, proj 16
    assert parts["kda"] == 48 + 48 + 16 + 4 + 2 + 8 + 16 + 2 + 16
    # q 4 x 2 x 4, kv_a 4 x 5, its norm 3, kv_b 3 x 2 x 4, gate 8, proj 16
    assert parts["mla"] == 32 + 20 + 3 + 24 + 8 + 16
    assert parts["dense"] == 72 and parts["expert"] == 60
    assert parts["routing"] == 32 + 8 + 60 and parts["norms"] == 8
    assert opcount_hybrid.latent_row(toy) == 128
    assert opcount_hybrid.slot_state_bytes(toy) == (2 * 2 * 2 * 2 * 4,
                                                    2 * 3 * 12 * 2)
    need = opcount_hybrid.hybrid_decode_tick_need(
        toy, live_slots=5, live_context=70, experts_hit=1.5, pairs_here=4)
    always = 2 * 160 + 103 + 72 + 2 * 100 + 3 * 8 + 4
    assert need["state_bytes"] == 5 * 2 * 64
    # the weights once with 1.5 experts hit in each of 2 expert layers, the
    # head and 5 embedding rows; the state and the taps read and written;
    # 70 live latent rows and 5 new ones of 128 lanes in one layer
    assert need["bytes"] == ((always + 2 * 1.5 * 60 + 40 + 5 * 4) * 2
                             + 5 * 2 * 64 + 5 * 2 * 144 + 75 * 128 * 2)
    assert need["flops"] == (2 * (5 * (always + 40) + 2 * 4 * 60)
                             + 5 * 2 * 8 * 2 * 4 + 70 * 2 * 2 * 2 * 128)


def test_the_published_tick_reads_what_the_issue_reckons(published):
    full = opcount_hybrid.hybrid_decode_tick_need(published, 256, 256 * 960,
                                                  128, 512)
    none = opcount_hybrid.hybrid_decode_tick_need(published, 0, 0, 0, 0)
    # other weights 1.3-1.5 ms at 819 GB/s, the head slice among them
    assert 1.0e9 < none["bytes"] < 1.4e9
    experts = 4 * 128 * 3 * 2560 * 768 * 2
    assert 6.0e9 < experts < 6.1e9  # 7.4 ms
    assert full["state_bytes"] == 256 * 2 * 5 * 32 * 128 * 128 * 4  # 6.6 ms
    rows = 256 * 961 * 640 * 2
    assert full["bytes"] == pytest.approx(
        none["bytes"] + experts + full["state_bytes"] + rows
        + 256 * (2 * 5 * 3 * 12288 * 2 + 2560 * 2))
    assert 13e9 < full["bytes"] < 14e9  # a floor of about 16.5 ms


@pytest.fixture
def ring(monkeypatch):
    tr = SpanTracer()
    monkeypatch.setattr(_spans, "stream", lambda: tr)
    return tr


def outcome(program, tick_s=0.036):
    return {"counters": {"window": (100.0, 110.0), "slots": 256,
                         "ticks": [(101.0, 250, 200_000)],
                         "traced_ticks": [(101.0, 240, 220_000),
                                          (102.0, 248, 236_000),
                                          (103.0, 0, 0)]},
            "e2e": {"setup_s": 40.0},
            "config": {"program": program},
            "device": {"kind": "TPU v5 lite"},
            "trace": {"modules": {"jit_decode_tick(7)": [tick_s] * 3,
                                  "jit_chunk(9)": [0.05]},
                      "labels": {"jit_decode_tick(7)": "decode_tick",
                                 "jit_chunk(9)": "prefill_chunk"}}}


def tick_spans(ring):
    state = 257 * 5 * 32 * 128 * 128 * 4
    ring.record("pool.alloc", 70.0, 71.0, blocks=65537, state_bytes=state)
    ring.record("sched.collect.process", 90.0, 90.1, expert_tokens_peak=9.0,
                experts_hit=3.0, routed=9, pairs=64)  # set-up's: not counted
    for i, (hit, routed, lanes) in enumerate(((126.0, 470, 240),
                                              (127.0, 510, 248))):
        ring.record("sched.collect.process", 101.0 + i, 101.1 + i,
                    expert_tokens_peak=11.0, experts_hit=hit, routed=routed,
                    pairs=8 * lanes)
        ring.record("engine.decode.launch", 101.2 + i, 101.3 + i,
                    lanes=lanes, state_rows=lanes)
    ring.record("sched.collect.process", 103.0, 103.1)  # a tick of no lane


def test_the_three_readers_read_the_ticks_spans(ring, published):
    cell = Cell(CELL)
    roofline = cell.reader("hybrid_decode_tick_roofline")
    state = cell.reader("state_bytes_per_tick_share")
    here = cell.reader("expert_pairs_here_share")
    # a program whose spans carry nothing (the parent's): nothing, no error
    for read in (roofline, state, here):
        assert read(outcome(published)) is None
    ring.record("sched.collect.process", 101.0, 101.1, routed=5)
    ring.record("engine.decode.launch", 101.2, 101.3, lanes=3)
    for read in (roofline, state, here):
        assert read(outcome(published)) is None
    ring.clear()
    tick_spans(ring)
    need = opcount_hybrid.hybrid_decode_tick_need(
        published, 244.0, 228_000.0, 126.5, 490.0)
    assert roofline(outcome(published)) == pytest.approx(
        100.0 * need["bytes"] / 819e9 / 0.036)
    assert 40.0 < roofline(outcome(published)) < 50.0
    assert state(outcome(published)) == pytest.approx(
        100.0 * need["state_bytes"] / need["bytes"])
    assert 40.0 < state(outcome(published)) < 50.0
    assert here(outcome(published)) == pytest.approx(
        100.0 * (470 / 1920 + 510 / 1984) / 2)
    # a tick faster than the chip's memory allows is a fault, raised
    with pytest.raises(ArithmeticError):
        roofline(outcome(published, tick_s=0.008))
    # another block kind, or no program block
    other = dict(published, attn_kind="cca")
    assert roofline(outcome(other)) is None and state(outcome(other)) is None
    assert roofline(dict(outcome(published), config={"n_embd": 4})) is None


def test_the_manifest_gives_the_cell_its_metrics(manifest):
    cell = Cell(CELL)
    assert cell.chips == 1 and cell.job["job"] == "serve-backlog-program"
    assert {m["name"] for m in cell.end_to_end()} == {
        "serve_tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer()}
    assert {"hybrid_decode_tick_roofline", "state_bytes_per_tick_share",
            "expert_pairs_here_share", "expert_load_peak_ratio",
            "experts_hit_share", "paged_attention_busy_share",
            "paged_live_share", "pool_reserved_share", "decode_occupancy",
            "decode_tick_device_ms", "prefill_chunk_device_ms",
            "tick_host_ms", "tick_exposed_host_ms", "gate_decide_ms",
            "queue_wait_p50_ms", "lagged_step_share", "setup_program_load_s",
            "setup_build_s"} <= names
    # the other block kinds' tick counts are not applied to this one
    assert not {"decode_tick_roofline", "looped_decode_tick_roofline",
                "cca_moe_decode_tick_roofline"} & names
    for other in ("gpt2-medium.chat-backlog", "ouro-2.6b.reason-backlog",
                  "zaya1-8b.reason-long-backlog"):
        assert not {"hybrid_decode_tick_roofline", "expert_pairs_here_share",
                    "state_bytes_per_tick_share"} & {
            m["name"] for m in Cell(other).per_layer()}
    assert "ling-3.0-flash" in [c["name"] for c in manifest["configs"]]
    assert all(w["chips"] == 1 for w in manifest["workloads"])
    assert len(manifest["workloads"]) == 5
