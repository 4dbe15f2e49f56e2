"""The qwen3-next configuration's cell: its files, its CPU rehearsal, the
count of what its decode tick needs, and its three readers on hand-made
data."""

import json
import os

import pytest

from conftest import run_cell
from perfbench.harness import opcount_gdn_moe, traffic
from perfbench.harness.manifest import Cell
from perfbench.metrics import _spans
from pytorch_distributed_tpu.telemetry.spans import SpanTracer

CELL = "qwen3-next-80b-a3b.doc-chat-backlog"
REDUCED = {"num_hidden_layers": (4, 48), "num_experts": (256, 512),
           "vocab_size": (75968, 151936), "n_positions": (4864, 262144)}


@pytest.fixture(scope="module")
def config(root):
    with open(os.path.join(root, "perfbench", "configs",
                           "qwen3-next-80b-a3b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def published(config):
    return config["program"]


def test_the_configuration_is_the_catalogs_and_no_width_is_cut(config):
    cfg = config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(r for r in map(json.loads, open(catalog))
                   if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in REDUCED:
                assert cfg[key] == value, key
    assert cfg["reduced"] == list(REDUCED)
    for key, (held, was) in REDUCED.items():
        assert cfg[key] == held and cfg["published"][key] == was
    p = cfg["program"]
    assert (p["num_layers"], p["embed_dim"], p["num_heads"],
            p["num_kv_heads"], p["head_dim"], p["vocab_size"],
            p["linear_num_heads"], p["linear_num_key_heads"],
            p["linear_head_dim"], p["linear_head_dim"], p["moe_dim"],
            p["moe_shared_dim"], p["moe_top_k"], p["layer_group_size"],
            p["norm_eps"], p["rope_theta"], p["rotary_share"],
            p["max_seq_len"]) == (
        cfg["num_hidden_layers"], cfg["hidden_size"],
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"], cfg["vocab_size"], cfg["linear_num_value_heads"],
        cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
        cfg["linear_value_head_dim"], cfg["moe_intermediate_size"],
        cfg["shared_expert_intermediate_size"], cfg["num_experts_per_tok"],
        cfg["full_attention_interval"], cfg["rms_norm_eps"],
        cfg["rope_theta"], cfg["partial_rotary_factor"], cfg["n_positions"])
    from pytorch_distributed_tpu.models.transformer import GatedDeltaNet

    assert cfg["linear_conv_kernel_dim"] == GatedDeltaNet.TAPS == (
        opcount_gdn_moe.CONV_TAPS)
    # the router scores all the published experts; half are held
    assert p["n_experts"] == cfg["published"]["num_experts"] == 512
    assert p["experts_held"] == [0, cfg["num_experts"]]
    assert p["moe_router"] == "softmax" and cfg["norm_topk_prob"] is True
    assert (p["attn_kind"], p["full_attn_kind"]) == ("gdn", "mha")
    assert p["qk_norm"] and p["attn_gate"] and p["moe_shared_gate"]
    # every layer has the experts, none leads dense, K/V is never windowed
    assert cfg["mlp_only_layers"] == [] and cfg["decoder_sparse_step"] == 1
    assert cfg["use_sliding_window"] is False
    assert cfg["tie_word_embeddings"] is False
    for key in ("assumed", "departures_of_the_program", "deployment",
                "reduced_why", "router_draw"):
        assert cfg[key], key
    assert "24 v5e chips" in cfg["deployment"]
    assert "multi-token-prediction" in cfg["reduced_why"]
    # the toy keeps the stack's shape: the same kinds, options and router
    tiny = cfg["tiny"]["program"]
    assert {k for k in p if p[k] != tiny[k]} <= {
        "vocab_size", "num_heads", "head_dim", "embed_dim", "max_seq_len",
        "linear_num_heads", "linear_num_key_heads", "linear_head_dim",
        "n_experts", "moe_top_k", "moe_dim", "moe_shared_dim",
        "experts_held"}


def test_the_cell_fills_the_chip_as_its_file_says(root, published):
    cell = Cell(CELL, root)
    job = cell.job
    parts = opcount_gdn_moe.sublayer_params(published)
    assert opcount_gdn_moe.layer_kinds(published) == (3, 1)
    assert opcount_gdn_moe.linear_widths(published) == (2048, 4096, 8192)
    assert 33.7e6 < parts["gdn"] < 33.8e6 and 27.2e6 < parts["full"] < 27.3e6
    assert 37.9e6 < parts["gdn"] + parts["routing"] < 38.0e6
    assert 31.4e6 < parts["full"] + parts["routing"] < 31.5e6
    assert parts["expert"] == 3 * 2048 * 512
    weights = (3 * parts["gdn"] + parts["full"]
               + 4 * (parts["routing"] + 256 * parts["expert"]
                      + parts["norms"]) + 2048 + 2 * 2048 * 75968)
    assert 3.67e9 < weights < 3.69e9  # 7.36 GB in bfloat16
    state, conv = opcount_gdn_moe.slot_state_bytes(published)
    assert state == 3 * 32 * 128 * 128 * 4 and conv == 3 * 3 * 8192 * 2
    slots = (job["slots"] + 1) * (state + conv)
    assert 1.6e9 < slots < 1.7e9
    assert opcount_gdn_moe.kv_row_values(published) * 2 == 2048  # B a token
    pool = job["blocks"] * job["block_len"] * 2048
    assert 2.1e9 < pool < 2.2e9
    assert job["blocks"] >= 40961 and job["slots"] >= 128  # ISSUE 41's floors
    assert 11e9 < 2 * weights + slots + pool < 11.5e9  # of 16 GB: 70%
    # the mix: 64 pairs, none longer than the context served
    pairs = traffic.length_multiset(cell.traffic)
    assert len(pairs) == 64
    assert max(p + o for p, o in pairs) <= cell.config["n_positions"]
    assert (min(p for p, _ in pairs), max(p for p, _ in pairs)) == (256, 4096)
    assert (min(o for _, o in pairs), max(o for _, o in pairs)) == (96, 768)
    assert (job["prefill_chunk"], job["admit_per_step"], job["backlog"],
            job["fill_per_tick"], job["trace_seconds"],
            job["check_requests"]) == (128, 4, 64, 4, 6, 4)
    # the tick and at most TWO chunk programs: every reachable width lands
    # on the longest prompt's 256 blocks
    assert 1 <= len(job["warm_jobs"]) <= 2
    assert job["chunk_bucket_floor"][0] == job["warm_jobs"][0]
    assert job["max_chunk_jobs"] == job["warm_jobs"][-1]
    assert job["chunk_bucket_floor"][1] == -(-4096 // job["block_len"]) == 256


def test_the_rehearsal_is_correct_and_the_control_is_not(root):
    """The float32 toy serves the reference's own tokens (gap 0). The
    control reads what float8 moves a logit by, which follows the seed's
    tokens at toy widths: one of two seeds must show it over the limit."""
    controls = []
    for seed in ("5", "4100000077"):
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


TOY = {"embed_dim": 4, "num_layers": 4, "vocab_size": 10, "num_heads": 4,
       "num_kv_heads": 2, "head_dim": 2, "layer_group_size": 4,
       "linear_num_heads": 4, "linear_num_key_heads": 2, "linear_head_dim": 3,
       "qk_norm": True, "attn_gate": True, "n_experts": 8, "moe_dim": 5,
       "moe_shared_dim": 5, "moe_shared_gate": True}


def test_a_tick_against_a_hand_count():
    toy = TOY
    assert opcount_gdn_moe.layer_kinds(toy) == (3, 1)
    assert opcount_gdn_moe.linear_widths(toy) == (6, 12, 24)
    parts = opcount_gdn_moe.sublayer_params(toy)
    # qkvz 4 x 36, ba 4 x 8, taps 4 x 24, a rate and a bias a state head,
    # the output norm 3, proj 12 x 4
    assert parts["gdn"] == 144 + 32 + 96 + 8 + 3 + 48
    # the doubled q 4 x 4 x 4, kv 4 x 2 x 2 x 2, two norms of 2, proj 8 x 4
    assert parts["full"] == 64 + 32 + 4 + 32
    # the router 4 x 8, the shared expert 3 x 4 x 5 and its gate 4
    assert parts["routing"] == 32 + 60 + 4
    assert parts["expert"] == 60 and parts["norms"] == 8
    assert opcount_gdn_moe.kv_row_values(toy) == 8
    assert opcount_gdn_moe.slot_state_bytes(toy) == (3 * 4 * 3 * 3 * 4,
                                                     3 * 3 * 24 * 2)
    need = opcount_gdn_moe.gdn_moe_decode_tick_need(
        toy, live_slots=5, live_context=70, experts_hit=1.5, pairs_here=6)
    always = 3 * 331 + 132 + 4 * (96 + 8) + 4
    assert need["state_bytes"] == 5 * 2 * 432
    assert need["kv_bytes"] == 75 * 8 * 2
    # the weights once with 1.5 experts hit in each of 4 layers, the head
    # and 5 embedding rows; the state and the taps read and written; 70
    # live key and value rows and 5 new ones in the one full layer
    assert need["bytes"] == ((always + 4 * 1.5 * 60 + 40 + 5 * 4) * 2
                             + 5 * 2 * 432 + 5 * 2 * 432 + 75 * 8 * 2)
    assert need["flops"] == (2 * (5 * (always + 40) + 4 * 6 * 60)
                             + 5 * 3 * 8 * 4 * 9 + 70 * 2 * 2 * 4 * 2)
    # without the three options the full layer is the plain grouped one
    plain = dict(toy, qk_norm=False, attn_gate=False, moe_shared_gate=False)
    parts = opcount_gdn_moe.sublayer_params(plain)
    assert parts["full"] == 32 + 32 + 32 and parts["routing"] == 32 + 60


def test_the_published_tick_reads_what_the_issue_reckons(published):
    full = opcount_gdn_moe.gdn_moe_decode_tick_need(
        published, 256, 256 * 1400, 254.3, 1280)
    none = opcount_gdn_moe.gdn_moe_decode_tick_need(published, 0, 0, 0, 0)
    # other weights and the head slice: 0.6 GB, 0.7 ms at 819 GB/s
    assert 0.55e9 < none["bytes"] < 0.65e9
    experts = 4 * 254.3 * 3 * 2048 * 512 * 2
    assert 6.3e9 < experts < 6.5e9  # 7.8 ms
    assert full["state_bytes"] == 256 * 2 * 3 * 32 * 128 * 128 * 4  # 3.9 ms
    assert 3.2e9 < full["state_bytes"] < 3.25e9
    assert full["kv_bytes"] == 256 * 1401 * 2048  # 0.9 ms
    assert full["bytes"] == pytest.approx(
        none["bytes"] + experts + full["state_bytes"] + full["kv_bytes"]
        + 256 * (2 * 3 * 3 * 8192 * 2 + 2048 * 2))
    assert 10.9e9 < full["bytes"] < 11.2e9  # a floor of about 13.5 ms


@pytest.fixture
def ring(monkeypatch):
    tr = SpanTracer()
    monkeypatch.setattr(_spans, "stream", lambda: tr)
    return tr


def outcome(program, tick_s=0.034):
    return {"counters": {"window": (100.0, 110.0), "slots": 256,
                         "ticks": [(101.0, 250, 300_000)],
                         "traced_ticks": [(101.0, 240, 330_000),
                                          (102.0, 248, 354_000),
                                          (103.0, 0, 0)]},
            "e2e": {"setup_s": 40.0},
            "config": {"program": program},
            "device": {"kind": "TPU v5 lite"},
            "trace": {"modules": {"jit_decode_tick(7)": [tick_s] * 3,
                                  "jit_chunk(9)": [0.05]},
                      "labels": {"jit_decode_tick(7)": "decode_tick",
                                 "jit_chunk(9)": "prefill_chunk"}}}


def tick_spans(ring, **alloc):
    state = 257 * 3 * 32 * 128 * 128 * 4
    ring.record("pool.alloc", 70.0, 71.0, blocks=65537, state_bytes=state,
                pool_layers=1, latent_row_bytes=0, **alloc)
    ring.record("sched.collect.process", 90.0, 90.1, expert_tokens_peak=9.0,
                experts_hit=3.0, routed=9, pairs=64)  # set-up's: not counted
    for i, (hit, routed, lanes) in enumerate(((253.0, 1190, 240),
                                              (255.0, 1250, 248))):
        ring.record("sched.collect.process", 101.0 + i, 101.1 + i,
                    expert_tokens_peak=13.0, experts_hit=hit, routed=routed,
                    pairs=10 * lanes)
        ring.record("engine.decode.launch", 101.2 + i, 101.3 + i,
                    lanes=lanes, state_rows=lanes)
    ring.record("sched.collect.process", 103.0, 103.1)  # a tick of no lane


def test_the_three_readers_read_the_ticks_spans(ring, published):
    cell = Cell(CELL)
    roofline = cell.reader("gdn_moe_decode_tick_roofline")
    state = cell.reader("gdn_state_bytes_per_tick_share")
    rows = cell.reader("kv_bytes_per_tick_share")
    # a program whose spans carry nothing (the parent's): nothing, no error
    for read in (roofline, state, rows):
        assert read(outcome(published)) is None
    ring.record("sched.collect.process", 101.0, 101.1, routed=5)
    ring.record("engine.decode.launch", 101.2, 101.3, lanes=3)
    for read in (roofline, state, rows):
        assert read(outcome(published)) is None
    ring.clear()
    tick_spans(ring)  # a pool.alloc from before ``kv_row_bytes``
    assert rows(outcome(published)) is None
    assert state(outcome(published)) is not None
    ring.clear()
    tick_spans(ring, kv_row_bytes=2048)
    need = opcount_gdn_moe.gdn_moe_decode_tick_need(
        published, 244.0, 342_000.0, 254.0, 1220.0)
    assert roofline(outcome(published)) == pytest.approx(
        100.0 * need["bytes"] / 819e9 / 0.034)
    assert 35.0 < roofline(outcome(published)) < 45.0
    assert state(outcome(published)) == pytest.approx(
        100.0 * need["state_bytes"] / need["bytes"])
    assert 25.0 < state(outcome(published)) < 30.0
    assert rows(outcome(published)) == pytest.approx(
        100.0 * need["kv_bytes"] / need["bytes"])
    assert 5.0 < rows(outcome(published)) < 8.0
    # a tick faster than the chip's memory allows is a fault, raised
    with pytest.raises(ArithmeticError):
        roofline(outcome(published, tick_s=0.008))
    # another block kind, or no program block
    other = dict(published, attn_kind="kda")
    for read in (roofline, state, rows):
        assert read(outcome(other)) is None
        assert read(dict(outcome(published), config={"n_embd": 4})) is None


def test_the_manifest_gives_the_cell_its_metrics(manifest):
    cell = Cell(CELL)
    assert cell.chips == 1 and cell.job["job"] == "serve-backlog-program"
    assert {m["name"] for m in cell.end_to_end()} == {
        "serve_tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer()}
    assert {"gdn_moe_decode_tick_roofline", "gdn_state_bytes_per_tick_share",
            "kv_bytes_per_tick_share", "expert_pairs_here_share",
            "expert_load_peak_ratio", "experts_hit_share",
            "paged_attention_busy_share", "paged_live_share",
            "pool_reserved_share", "decode_occupancy",
            "decode_tick_device_ms", "prefill_chunk_device_ms",
            "tick_host_ms", "tick_exposed_host_ms", "gate_decide_ms",
            "queue_wait_p50_ms", "lagged_step_share", "setup_program_load_s",
            "setup_build_s", "tick_host_path_ms", "launch_build_ms",
            "launch_put_ms", "launch_call_ms", "relaunch_lag_ms"} <= names
    # the other block kinds' tick counts are not applied to this one
    assert not {"decode_tick_roofline", "looped_decode_tick_roofline",
                "cca_moe_decode_tick_roofline", "hybrid_decode_tick_roofline",
                "state_bytes_per_tick_share"} & names
    for other in ("gpt2-medium.chat-backlog", "ouro-2.6b.reason-backlog",
                  "zaya1-8b.reason-long-backlog",
                  "ling-3.0-flash.doc-reason-backlog"):
        assert not {"gdn_moe_decode_tick_roofline", "kv_bytes_per_tick_share",
                    "gdn_state_bytes_per_tick_share"} & {
            m["name"] for m in Cell(other).per_layer()}
    assert "qwen3-next-80b-a3b" in [c["name"] for c in manifest["configs"]]
    assert all(w["chips"] == 1 for w in manifest["workloads"])
    assert manifest["workloads"][-1]["name"] == CELL
