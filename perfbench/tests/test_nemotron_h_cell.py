"""The nemotron-h configuration's cell: its files, its CPU rehearsal, the
count of what its decode tick needs, and its two readers on hand-made
data."""

import json
import os

import pytest

from conftest import run_cell
from perfbench.harness import opcount_mamba_moe, traffic
from perfbench.harness.manifest import Cell
from perfbench.metrics import _spans
from pytorch_distributed_tpu.telemetry.spans import SpanTracer

CELL = "nemotron-3-nano-30b-a3b.assistant-backlog"
REDUCED = {"num_hidden_layers": (9, 52), "n_routed_experts": (64, 128),
           "vocab_size": (65536, 131072), "n_positions": (2560, 262144)}


@pytest.fixture(scope="module")
def config(root):
    with open(os.path.join(root, "perfbench", "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def published(config):
    return config["program"]


def test_the_configuration_is_the_catalogs_and_no_width_is_cut(config):
    cfg = config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(r for r in map(json.loads, open(catalog))
                   if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
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
            p["mamba_num_heads"], p["mamba_head_dim"], p["mamba_state_size"],
            p["mamba_n_groups"], p["moe_dim"], p["moe_shared_dim"],
            p["moe_top_k"], p["moe_routed_scale"], p["moe_n_group"],
            p["moe_topk_group"], p["norm_eps"], p["mlp"],
            p["max_seq_len"]) == (
        cfg["num_hidden_layers"], cfg["hidden_size"],
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"], cfg["vocab_size"], cfg["mamba_num_heads"],
        cfg["mamba_head_dim"], cfg["ssm_state_size"], cfg["n_groups"],
        cfg["moe_intermediate_size"],
        cfg["moe_shared_expert_intermediate_size"],
        cfg["num_experts_per_tok"], cfg["routed_scaling_factor"],
        cfg["n_group"], cfg["topk_group"], cfg["layer_norm_epsilon"],
        cfg["mlp_hidden_act"], cfg["n_positions"])
    # the widths, as published
    assert (p["embed_dim"], p["mamba_num_heads"], p["mamba_head_dim"],
            p["mamba_state_size"], p["mamba_n_groups"], p["num_heads"],
            p["num_kv_heads"], p["head_dim"], p["moe_top_k"], p["moe_dim"],
            p["moe_shared_dim"], p["moe_routed_scale"]) == (
        2688, 64, 64, 128, 8, 32, 2, 128, 6, 1856, 3712, 2.5)
    # the blocks that run are the published pattern's first nine letters
    assert p["layer_pattern"] == cfg["hybrid_override_pattern"][:9] == (
        "MEMEM*EME")
    assert len(cfg["hybrid_override_pattern"]) == 52 == (
        cfg["published"]["num_hidden_layers"])
    assert [cfg["hybrid_override_pattern"].count(c) for c in "ME*"] == [
        23, 23, 6]
    from pytorch_distributed_tpu.models.transformer import Mamba2Mixer

    assert cfg["conv_kernel"] == Mamba2Mixer.TAPS == (
        opcount_mamba_moe.CONV_TAPS)
    assert cfg["use_conv_bias"] is True
    # the router scores all the published experts; half are held
    assert p["n_experts"] == cfg["published"]["n_routed_experts"] == 128
    assert p["experts_held"] == [0, cfg["n_routed_experts"]]
    assert p["moe_router"] == "sigmoid" and cfg["norm_topk_prob"] is True
    assert p["pos_embedding"] == "none" and cfg["n_shared_experts"] == 1
    assert cfg["sliding_window"] is None
    assert cfg["tie_word_embeddings"] is False
    for key in ("assumed", "departures_of_the_program", "deployment",
                "reduced_why", "router_draw"):
        assert cfg[key], key
    for key in ("inner_width", "gated_norm", "no_rotation", "router_groups",
                "chunk_size", "fused_order", "time_step"):
        assert cfg["assumed"][key], key
    assert "12 v5e chips" in cfg["deployment"]
    # the toy keeps the stack's shape: the same pattern, options and router
    tiny = cfg["tiny"]["program"]
    assert {k for k in p if p[k] != tiny[k]} <= {
        "vocab_size", "num_heads", "head_dim", "embed_dim", "max_seq_len",
        "mamba_num_heads", "mamba_head_dim", "mamba_state_size",
        "mamba_n_groups", "n_experts", "moe_top_k", "moe_dim",
        "moe_shared_dim", "experts_held"}


def test_the_cell_fills_the_chip_as_its_file_says(root, published):
    cell = Cell(CELL, root)
    job = cell.job
    parts = opcount_mamba_moe.sublayer_params(published)
    assert opcount_mamba_moe.layer_kinds(published) == {
        "M": 4, "E": 4, "*": 1, "-": 0}
    assert opcount_mamba_moe.mamba_widths(published) == (4096, 6144)
    assert 38.7e6 < parts["M"] < 38.8e6 and 23.3e6 < parts["*"] < 23.5e6
    assert 20.2e6 < parts["E"] < 20.4e6  # the router and the shared expert
    assert parts["expert"] == 2 * 2688 * 1856
    weights = (4 * parts["M"] + parts["*"]
               + 4 * (parts["E"] + 64 * parts["expert"])
               + 2688 + 2 * 2688 * 65536)
    assert 3.16e9 < weights < 3.17e9  # 6.33 GB in bfloat16, as published
    # HELD, an expert's two matrices are 3,072 x 2,048 (whole 512-wide
    # tiles of the grouped product): 1.33 GB more
    from pytorch_distributed_tpu.models.moe import grouped_width

    held = weights + 4 * 64 * 2 * (
        grouped_width(2688) * grouped_width(1856) - 2688 * 1856)
    assert 3.82e9 < held < 3.84e9  # 7.67 GB
    state, conv = opcount_mamba_moe.slot_state_bytes(published)
    assert state == 4 * 64 * 64 * 128 * 4 and conv == 4 * 3 * 6144 * 2
    slots = (job["slots"] + 1) * (state + conv)
    assert 2.19e9 < slots < 2.2e9
    assert opcount_mamba_moe.kv_row_values(published) * 2 == 1024  # B a token
    pool = job["blocks"] * job["block_len"] * 1024
    assert 0.67e9 < pool < 0.68e9
    assert job["blocks"] == 40961 and job["slots"] >= 256
    assert 10.4e9 < 2 * held + slots + pool < 10.7e9  # of 16 GB: 66%
    # the mix: 64 pairs, none longer than the context served
    pairs = traffic.length_multiset(cell.traffic)
    assert len(pairs) == 64
    assert max(p + o for p, o in pairs) <= cell.config["n_positions"]
    assert (min(p for p, _ in pairs), max(p for p, _ in pairs)) == (71, 2048)
    assert (min(o for _, o in pairs), max(o for _, o in pairs)) == (38, 512)
    assert 486 < sum(p for p, _ in pairs) / 64 < 488
    assert 187 < sum(o for _, o in pairs) / 64 < 189
    assert (job["prefill_chunk"], job["admit_per_step"], job["backlog"],
            job["fill_per_tick"], job["trace_seconds"],
            job["check_requests"]) == (128, 4, 64, 4, 6, 4)
    # the tick and ONE chunk program: every reachable width lands on the
    # longest prompt's 128 blocks
    assert len(job["warm_jobs"]) == 1
    assert job["chunk_bucket_floor"][0] == job["warm_jobs"][0] == (
        job["max_chunk_jobs"])
    assert job["chunk_bucket_floor"][1] == -(-2048 // job["block_len"]) == 128


def test_the_rehearsal_is_correct_and_the_control_is_not(root):
    """The float32 toy serves the reference's own tokens (gap 0). The
    control reads what float8 moves a logit by, which follows the seed's
    tokens at toy widths: one of two seeds must show it over the limit."""
    controls = []
    for seed in ("5", "4400000077"):
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


TOY = {"embed_dim": 4, "num_layers": 5, "layer_pattern": "ME*E-",
       "vocab_size": 10, "num_heads": 4, "num_kv_heads": 2, "head_dim": 2,
       "mamba_num_heads": 4, "mamba_head_dim": 3, "mamba_state_size": 5,
       "mamba_n_groups": 2, "n_experts": 8, "moe_dim": 5,
       "moe_shared_dim": 7, "mlp_dim": 6}


def test_a_tick_against_a_hand_count():
    toy = TOY
    assert opcount_mamba_moe.layer_kinds(toy) == {
        "M": 1, "E": 2, "*": 1, "-": 1}
    assert opcount_mamba_moe.mamba_widths(toy) == (12, 32)
    parts = opcount_mamba_moe.sublayer_params(toy)
    # in_proj 4 x (12 + 32 + 4), taps and bias 5 x 32, a rate, a bias and a
    # skip a head, the group norm 12, proj 12 x 4, the block's norm 4
    assert parts["M"] == 192 + 160 + 12 + 12 + 48 + 4
    # q 4 x 4 x 2, kv 4 x 2 x 2 x 2, proj 8 x 4, the norm
    assert parts["*"] == 32 + 32 + 32 + 4
    # the router 4 x 8 and its bias 8, the shared expert 2 x 4 x 7, the norm
    assert parts["E"] == 32 + 8 + 56 + 4
    assert parts["-"] == 2 * 4 * 6 + 4
    assert parts["expert"] == 2 * 4 * 5  # two matrices: no gate
    assert opcount_mamba_moe.kv_row_values(toy) == 8
    assert opcount_mamba_moe.slot_state_bytes(toy) == (4 * 3 * 5 * 4,
                                                       3 * 32 * 2)
    need = opcount_mamba_moe.mamba_moe_decode_tick_need(
        toy, live_slots=5, live_context=70, experts_hit=1.5, pairs_here=6)
    always = 428 + 100 + 2 * 100 + 52 + 4
    assert need["state_bytes"] == 5 * 2 * 240
    assert need["kv_bytes"] == 75 * 8 * 2
    # the weights once with 1.5 experts hit in each of 2 expert blocks, the
    # head and 5 embedding rows; the state and the taps read and written;
    # 70 live key and value rows and 5 new ones in the one attention block
    assert need["bytes"] == ((always + 2 * 1.5 * 40 + 40 + 5 * 4) * 2
                             + 5 * 2 * 240 + 5 * 2 * 192 + 75 * 8 * 2)
    assert need["flops"] == (2 * (5 * (always + 40) + 2 * 6 * 40)
                             + 5 * 5 * 60 + 70 * 2 * 2 * 4 * 2)


def test_the_published_tick_reads_what_the_issue_reckons(published):
    full = opcount_mamba_moe.mamba_moe_decode_tick_need(
        published, 250, 250 * 580, 64, 750)
    none = opcount_mamba_moe.mamba_moe_decode_tick_need(published, 0, 0, 0, 0)
    # the Mamba-2 matrices 0.31, attention 0.05, routers and shared experts
    # 0.16, the head slice 0.35: 0.87 GB, 1.1 ms at 819 GB/s
    assert 0.86e9 < none["bytes"] < 0.88e9
    experts = 4 * 64 * 2 * 2688 * 1856 * 2
    assert 5.1e9 < experts < 5.12e9  # 6.2 ms: all 64 held experts are hit
    assert full["state_bytes"] == 250 * 2 * 4 * 64 * 64 * 128 * 4  # 5.1 ms
    assert 4.19e9 < full["state_bytes"] < 4.2e9
    assert full["kv_bytes"] == 250 * 581 * 1024  # 0.2 ms
    assert full["bytes"] == pytest.approx(
        none["bytes"] + experts + full["state_bytes"] + full["kv_bytes"]
        + 250 * (2 * 4 * 3 * 6144 * 2 + 2688 * 2))
    assert 10.3e9 < full["bytes"] < 10.5e9  # a floor of about 12.7 ms
    assert 0.39 < full["state_bytes"] / full["bytes"] < 0.42


@pytest.fixture
def ring(monkeypatch):
    tr = SpanTracer()
    monkeypatch.setattr(_spans, "stream", lambda: tr)
    return tr


def outcome(program, tick_s=0.034):
    return {"counters": {"window": (100.0, 110.0), "slots": 256,
                         "ticks": [(101.0, 250, 140_000)],
                         "traced_ticks": [(101.0, 248, 143_000),
                                          (102.0, 252, 147_000),
                                          (103.0, 0, 0)]},
            "e2e": {"setup_s": 40.0},
            "config": {"program": program},
            "device": {"kind": "TPU v5 lite"},
            "trace": {"modules": {"jit_decode_tick(7)": [tick_s] * 3,
                                  "jit_chunk(9)": [0.05]},
                      "labels": {"jit_decode_tick(7)": "decode_tick",
                                 "jit_chunk(9)": "prefill_chunk"}}}


def tick_spans(ring):
    state = 257 * 4 * 64 * 64 * 128 * 4
    ring.record("pool.alloc", 70.0, 71.0, blocks=40961, state_bytes=state,
                pool_layers=1, latent_row_bytes=0, kv_row_bytes=1024)
    ring.record("sched.collect.process", 90.0, 90.1, expert_tokens_peak=9.0,
                experts_hit=3.0, routed=9, pairs=64)  # set-up's: not counted
    for i, (hit, routed, lanes) in enumerate(((64.0, 740, 248),
                                              (64.0, 760, 252))):
        ring.record("sched.collect.process", 101.0 + i, 101.1 + i,
                    expert_tokens_peak=20.0, experts_hit=hit, routed=routed,
                    pairs=6 * lanes)
        ring.record("engine.decode.launch", 101.2 + i, 101.3 + i,
                    lanes=lanes, state_rows=lanes)
    ring.record("sched.collect.process", 103.0, 103.1)  # a tick of no lane


def test_the_two_readers_read_the_ticks_spans(ring, published):
    cell = Cell(CELL)
    roofline = cell.reader("mamba_moe_decode_tick_roofline")
    state = cell.reader("ssm_state_bytes_per_tick_share")
    # a program whose spans carry nothing (the parent's): nothing, no error
    for read in (roofline, state):
        assert read(outcome(published)) is None
    ring.record("sched.collect.process", 101.0, 101.1, routed=5)
    ring.record("engine.decode.launch", 101.2, 101.3, lanes=3)
    for read in (roofline, state):
        assert read(outcome(published)) is None
    ring.clear()
    tick_spans(ring)
    need = opcount_mamba_moe.mamba_moe_decode_tick_need(
        published, 250.0, 145_000.0, 64.0, 750.0)
    assert roofline(outcome(published)) == pytest.approx(
        100.0 * need["bytes"] / 819e9 / 0.034)
    assert 35.0 < roofline(outcome(published)) < 40.0
    assert state(outcome(published)) == pytest.approx(
        100.0 * need["state_bytes"] / need["bytes"])
    assert 39.0 < state(outcome(published)) < 42.0
    # a tick faster than the chip's memory allows is a fault, raised
    with pytest.raises(ArithmeticError):
        roofline(outcome(published, tick_s=0.008))
    # another block kind, or no program block
    other = {k: v for k, v in published.items() if k != "layer_pattern"}
    for read in (roofline, state):
        assert read(outcome(other)) is None
        assert read(outcome(dict(published, layer_pattern="*-*-"))) is None
        assert read(dict(outcome(published), config={"n_embd": 4})) is None


def test_the_manifest_gives_the_cell_its_metrics(manifest):
    cell = Cell(CELL)
    assert cell.chips == 1 and cell.job["job"] == "serve-backlog-program"
    assert {m["name"] for m in cell.end_to_end()} == {
        "serve_tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer()}
    assert {"mamba_moe_decode_tick_roofline",
            "ssm_state_bytes_per_tick_share", "expert_pairs_here_share",
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
                "gdn_moe_decode_tick_roofline", "state_bytes_per_tick_share",
                "gdn_state_bytes_per_tick_share",
                "kv_bytes_per_tick_share"} & names
    for other in ("gpt2-medium.chat-backlog", "ouro-2.6b.reason-backlog",
                  "zaya1-8b.reason-long-backlog",
                  "ling-3.0-flash.doc-reason-backlog",
                  "qwen3-next-80b-a3b.doc-chat-backlog"):
        assert not {"mamba_moe_decode_tick_roofline",
                    "ssm_state_bytes_per_tick_share"} & {
            m["name"] for m in Cell(other).per_layer()}
    assert "nemotron-3-nano-30b-a3b" in [c["name"]
                                         for c in manifest["configs"]]
    assert all(w["chips"] == 1 for w in manifest["workloads"])
    assert CELL in [w["name"] for w in manifest["workloads"]]
