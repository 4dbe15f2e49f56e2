"""The glm configuration's cell: its files, its CPU rehearsal, the count of
what its decode tick needs, and its two readers on hand-made data."""

import json
import os

import pytest

from conftest import run_cell
from perfbench.harness import opcount_mla_moe, traffic
from perfbench.harness.manifest import Cell
from perfbench.metrics import _spans
from pytorch_distributed_tpu.telemetry.spans import SpanTracer

CELL = "glm-4.7-flash.doc-chat-backlog"
REDUCED = {"num_hidden_layers": (6, 47), "n_positions": (4864, 202752)}


@pytest.fixture(scope="module")
def config(root):
    with open(os.path.join(root, "perfbench", "configs",
                           "glm-4.7-flash.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def published(config):
    return config["program"]


def test_the_configuration_is_the_catalogs_and_no_width_is_cut(config):
    cfg = config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(r for r in map(json.loads, open(catalog))
                   if r["name"] == "GLM-4.7-Flash")
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in REDUCED:
                assert cfg[key] == value, key
        assert cfg["published"]["n_positions"] == row["context_length"]
    assert cfg["reduced"] == list(REDUCED)
    for key, (held, was) in REDUCED.items():
        assert cfg[key] == held and cfg["published"][key] == was
    assert cfg["model_type"] == cfg["reference"] == "glm4_moe_lite"
    p = cfg["program"]
    assert (p["num_layers"], p["embed_dim"], p["num_heads"], p["head_dim"],
            p["qk_rope_head_dim"], p["v_head_dim"], p["q_lora_rank"],
            p["kv_lora_rank"], p["vocab_size"], p["mlp_dim"], p["moe_dim"],
            p["moe_shared_dim"], p["n_experts"], p["moe_top_k"],
            p["moe_routed_scale"], p["moe_n_group"], p["moe_topk_group"],
            p["first_k_dense_replace"], p["norm_eps"], p["rope_theta"],
            p["max_seq_len"]) == (
        cfg["num_hidden_layers"], cfg["hidden_size"],
        cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
        cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["q_lora_rank"],
        cfg["kv_lora_rank"], cfg["vocab_size"], cfg["intermediate_size"],
        cfg["moe_intermediate_size"],
        cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        cfg["n_routed_experts"], cfg["num_experts_per_tok"],
        cfg["routed_scaling_factor"], cfg["n_group"], cfg["topk_group"],
        cfg["first_k_dense_replace"], cfg["rms_norm_eps"], cfg["rope_theta"],
        cfg["n_positions"])
    # the widths, as published
    assert (p["embed_dim"], p["num_heads"], p["head_dim"],
            p["qk_rope_head_dim"], p["v_head_dim"], p["q_lora_rank"],
            p["kv_lora_rank"], p["mlp_dim"], p["moe_dim"], p["moe_top_k"],
            p["moe_routed_scale"]) == (
        2048, 20, 192, 64, 256, 768, 512, 10240, 1536, 4, 1.8)
    # EVERY layer latent, no gate, every expert and the whole vocabulary here
    assert p["attn_kind"] == "mla" and "layer_group_size" not in p
    assert p["mla_head_gate"] is False and "experts_held" not in p
    assert p["n_experts"] == 64 and p["vocab_size"] == 154880
    assert p["moe_router"] == "sigmoid" and cfg["norm_topk_prob"] is True
    assert cfg["topk_method"] == "noaux_tc" and cfg["rope_scaling"] is None
    assert cfg["tie_word_embeddings"] is False
    # the multi-token-prediction module: the key kept, the module left out
    assert cfg["num_nextn_predict_layers"] == 1
    assert "LEFT OUT" in cfg["reduced_why"] and "draft" in cfg["reduced_why"]
    for key in ("assumed", "departures_of_the_program", "deployment",
                "reduced_why", "router_draw", "program_why"):
        assert cfg[key], key
    for key in ("rotary", "scale", "router", "norms", "dtype", "weights"):
        assert cfg["assumed"][key], key
    assert "8 v5e chips" in cfg["deployment"]
    assert "WHOLE on its chip" in cfg["deployment"]
    # the toy keeps the stack's shape: the same kinds, options and router
    tiny = cfg["tiny"]["program"]
    assert {k for k in p if p[k] != tiny[k]} <= {
        "vocab_size", "num_layers", "num_heads", "head_dim", "embed_dim",
        "max_seq_len", "q_lora_rank", "kv_lora_rank", "qk_rope_head_dim",
        "v_head_dim", "mlp_dim", "n_experts", "moe_dim", "moe_shared_dim"}
    assert tiny["v_head_dim"] != tiny["head_dim"] and tiny["num_heads"] % 8


def test_the_cell_fills_the_chip_as_its_file_says(root, published, config):
    cell = Cell(CELL, root)
    job = cell.job
    parts = opcount_mla_moe.sublayer_params(published)
    assert opcount_mla_moe.layer_kinds(published) == (1, 5)
    # q_a, its norm, q_b; kv_a, its norm, kv_b; o
    assert parts["mla"] == (2048 * 768 + 768 + 768 * 20 * 256 + 2048 * 576
                            + 512 + 512 * 20 * 448 + 5120 * 2048) == 21759232
    assert parts["expert"] == 3 * 2048 * 1536 == 9437184
    assert parts["routing"] == 2048 * 64 + 64 + 9437184
    assert parts["dense"] == 3 * 2048 * 10240
    expert_layer = (parts["mla"] + parts["norms"] + parts["routing"]
                    + 64 * parts["expert"])
    dense_layer = parts["mla"] + parts["norms"] + parts["dense"]
    assert (expert_layer, dense_layer) == (635311424, 84677888)
    weights = dense_layer + 5 * expert_layer + 2 * 2048 * 154880 + 2048
    assert weights == 3895625536  # 7.79 GB in bfloat16
    for said in ("3,895.6M", "7.79 GB", "635,311,424", "84,677,888",
                 "21,759,232"):
        assert said in config["deployment"], said
    row = opcount_mla_moe.latent_row(published)
    assert row == 640  # 512 + 64, padded to whole 128-lane tiles
    pool = job["blocks"] * job["block_len"] * row * 2 * 6
    logits = job["slots"] * 154880 * 4
    assert job["block_len"] == 16 and job["blocks"] % 2 == 1  # the trash block
    # of 16 GB: over the floor of a quarter, under what leaves the programs
    # their temporaries
    assert 0.25 * 16e9 < 2 * weights + pool + logits < 14e9
    # the mix: 64 pairs, none longer than the context served
    pairs = traffic.length_multiset(cell.traffic)
    assert len(pairs) == 64
    assert max(p + o for p, o in pairs) <= cell.config["n_positions"]
    assert (min(p for p, _ in pairs), max(p for p, _ in pairs)) == (256, 4096)
    assert (min(o for _, o in pairs), max(o for _, o in pairs)) == (96, 768)
    # a request reserves its prompt and its output: every slot's fits
    reserved = sum(-(-(p + o) // job["block_len"]) for p, o in pairs) / 64
    assert reserved * job["slots"] < job["blocks"] - 1
    assert (job["prefill_chunk"], job["admit_per_step"], job["backlog"],
            job["fill_per_tick"], job["trace_seconds"],
            job["check_requests"]) == (128, 4, 64, 4, 6, 4)
    # the tick and ONE chunk program: every reachable width lands on the
    # longest prompt's 256 blocks
    assert len(job["warm_jobs"]) == 1
    assert job["chunk_bucket_floor"][0] == job["warm_jobs"][0] == (
        job["max_chunk_jobs"])
    assert job["chunk_bucket_floor"][1] == -(-4096 // job["block_len"]) == 256
    for key in ("limits_why", "pool_why", "rate_why", "buckets_why",
                "preroll_why"):
        assert len(job[key]) > 100, key


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


TOY = {"embed_dim": 4, "num_layers": 3, "first_k_dense_replace": 1,
       "vocab_size": 10, "num_heads": 3, "head_dim": 2, "qk_rope_head_dim": 2,
       "v_head_dim": 5, "q_lora_rank": 6, "kv_lora_rank": 7, "mlp_dim": 8,
       "n_experts": 9, "moe_dim": 5, "moe_shared_dim": 7,
       "mla_head_gate": False}


def test_a_tick_against_a_hand_count():
    toy = TOY
    assert opcount_mla_moe.layer_kinds(toy) == (1, 2)
    assert opcount_mla_moe.latent_row(toy) == 128  # 7 + 2, padded
    parts = opcount_mla_moe.sublayer_params(toy)
    # q_a 4 x 6, its norm 6, q_b 6 x 3 x 4; kv_a 4 x 9, its norm 7,
    # kv_b 7 x 3 x (2 + 5); o 15 x 4
    assert parts["mla"] == 24 + 6 + 72 + 36 + 7 + 147 + 60 == 352
    assert parts["dense"] == 3 * 4 * 8
    # the router 4 x 9 and its bias 9, the shared expert 3 x 4 x 7
    assert parts["routing"] == 36 + 9 + 84
    assert parts["expert"] == 3 * 4 * 5 and parts["norms"] == 8
    # ling's spelling: one query matrix, values as wide as the keys, a gate
    ling = {k: v for k, v in toy.items()
            if k not in ("q_lora_rank", "v_head_dim", "mla_head_gate")}
    assert opcount_mla_moe.sublayer_params(ling)["mla"] == (
        4 * 3 * 4 + 36 + 7 + 7 * 3 * 4 + 4 * 3 + 6 * 4)
    need = opcount_mla_moe.mla_moe_decode_tick_need(
        toy, live_slots=5, live_context=70, experts_hit=1.5, pairs_here=6)
    always = 3 * (352 + 8) + 96 + 2 * 129 + 4
    assert need["latent_bytes"] == 75 * 3 * 128 * 2
    # the weights once with 1.5 experts hit in each of 2 expert layers, the
    # head and 5 embedding rows; 70 live rows and 5 new ones in each of 3
    # layers; 5 rows of float32 logits
    assert need["bytes"] == ((always + 2 * 1.5 * 60 + 40 + 5 * 4) * 2
                             + 75 * 3 * 128 * 2 + 5 * 10 * 4)
    assert need["flops"] == (2 * (5 * (always + 40) + 2 * 6 * 60)
                             + 70 * 3 * 2 * 2 * 3 * 128)


def test_the_published_tick_reads_what_the_issue_reckons(published):
    full = opcount_mla_moe.mla_moe_decode_tick_need(
        published, 192, 192 * 1512, 64, 768)
    none = opcount_mla_moe.mla_moe_decode_tick_need(published, 0, 0, 0, 0)
    # attention 0.26, the dense MLP 0.13, routers and shared experts 0.10,
    # the head 0.63: 1.12 GB
    assert 1.11e9 < none["bytes"] < 1.13e9
    experts = 5 * 64 * 9437184 * 2
    assert 6.03e9 < experts < 6.05e9  # all 64 experts of 5 layers are hit
    assert full["latent_bytes"] == 192 * 1513 * 7680  # 2.2 GB
    assert full["bytes"] == pytest.approx(
        none["bytes"] + experts + full["latent_bytes"]
        + 192 * (2048 * 2 + 154880 * 4))
    assert 9.4e9 < full["bytes"] < 9.6e9  # a floor of about 11.6 ms
    assert 0.22 < full["latent_bytes"] / full["bytes"] < 0.25
    # 51,200 operations a live row a layer: 40 a byte of the row
    rows = full["flops"] - opcount_mla_moe.mla_moe_decode_tick_need(
        published, 192, 0, 64, 768)["flops"]
    assert rows == 192 * 1512 * 6 * 51200
    # bound by bytes: the operations take a sixth of the bytes' time
    assert full["flops"] / 197e12 < 0.2 * full["bytes"] / 819e9


@pytest.fixture
def ring(monkeypatch):
    tr = SpanTracer()
    monkeypatch.setattr(_spans, "stream", lambda: tr)
    return tr


def outcome(program, tick_s=0.019):
    return {"counters": {"window": (100.0, 110.0), "slots": 192,
                         "ticks": [(101.0, 190, 280_000)],
                         "traced_ticks": [(101.0, 188, 284_000),
                                          (102.0, 192, 292_000),
                                          (103.0, 0, 0)]},
            "e2e": {"setup_s": 40.0},
            "config": {"program": program},
            "device": {"kind": "TPU v5 lite"},
            "trace": {"modules": {"jit_decode_tick(7)": [tick_s] * 3,
                                  "jit_chunk(9)": [0.05]},
                      "labels": {"jit_decode_tick(7)": "decode_tick",
                                 "jit_chunk(9)": "prefill_chunk"}}}


def tick_spans(ring):
    ring.record("pool.alloc", 70.0, 71.0, blocks=32769, state_bytes=0,
                pool_layers=6, latent_row_bytes=1280, kv_row_bytes=0)
    ring.record("sched.collect.process", 90.0, 90.1, expert_tokens_peak=9.0,
                experts_hit=3.0, routed=9, pairs=9)  # set-up's: not counted
    for i, (hit, lanes) in enumerate(((63.0, 188), (64.0, 192))):
        ring.record("sched.collect.process", 101.0 + i, 101.1 + i,
                    expert_tokens_peak=20.0, experts_hit=hit,
                    routed=4 * lanes, pairs=4 * lanes)
    ring.record("sched.collect.process", 103.0, 103.1)  # a tick of no lane


def test_the_two_readers_read_the_ticks_spans(ring, published):
    cell = Cell(CELL)
    roofline = cell.reader("mla_moe_decode_tick_roofline")
    latent = cell.reader("latent_bytes_per_tick_share")
    pairs_here = cell.reader("expert_pairs_here_share")
    # a program whose spans carry nothing (the parent's): nothing, no error
    for read in (roofline, latent):
        assert read(outcome(published)) is None
    ring.record("sched.collect.process", 101.0, 101.1, routed=5)
    ring.record("pool.alloc", 70.0, 71.0, blocks=9)
    for read in (roofline, latent):
        assert read(outcome(published)) is None
    ring.clear()
    tick_spans(ring)
    need = opcount_mla_moe.mla_moe_decode_tick_need(
        published, 190.0, 288_000.0, 63.5, 760.0)
    assert roofline(outcome(published)) == pytest.approx(
        100.0 * need["bytes"] / 819e9 / 0.019)
    assert 55.0 < roofline(outcome(published)) < 65.0
    assert latent(outcome(published)) == pytest.approx(
        100.0 * need["latent_bytes"] / need["bytes"])
    assert latent(outcome(published)) == pytest.approx(
        100.0 * 288_190 * 1280 * 6 / need["bytes"])
    assert 21.0 < latent(outcome(published)) < 25.0
    # every expert is held: every pair a layer routed landed here
    assert pairs_here(outcome(published)) == pytest.approx(100.0)
    # a tick faster than the chip's memory allows is a fault, raised
    with pytest.raises(ArithmeticError):
        roofline(outcome(published, tick_s=0.008))
    # another block kind, or no program block
    for read in (roofline, latent):
        assert read(outcome(dict(published, attn_kind="kda"))) is None
        assert read(outcome(dict(published, n_experts=0))) is None
        assert read(dict(outcome(published), config={"n_embd": 4})) is None


def test_the_manifest_gives_the_cell_its_metrics(manifest):
    cell = Cell(CELL)
    assert cell.chips == 1 and cell.job["job"] == "serve-backlog-program"
    assert cell.entry["traffic"] == "doc-chat-backlog"
    assert {m["name"] for m in cell.end_to_end()} == {
        "serve_tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer()}
    assert {"mla_moe_decode_tick_roofline", "latent_bytes_per_tick_share",
            "expert_pairs_here_share", "expert_load_peak_ratio",
            "experts_hit_share", "paged_attention_busy_share",
            "paged_live_share", "pool_reserved_share", "decode_occupancy",
            "decode_tick_device_ms", "prefill_chunk_device_ms",
            "tick_host_ms", "tick_exposed_host_ms", "gate_decide_ms",
            "queue_wait_p50_ms", "lagged_step_share", "setup_program_load_s",
            "setup_build_s", "tick_host_path_ms", "launch_build_ms",
            "launch_put_ms", "launch_call_ms", "relaunch_lag_ms"} <= names
    # the other block kinds' tick counts are not applied to this one
    assert not {"decode_tick_roofline", "looped_decode_tick_roofline",
                "cca_moe_decode_tick_roofline", "hybrid_decode_tick_roofline",
                "gdn_moe_decode_tick_roofline",
                "mamba_moe_decode_tick_roofline",
                "state_bytes_per_tick_share",
                "gdn_state_bytes_per_tick_share",
                "ssm_state_bytes_per_tick_share",
                "kv_bytes_per_tick_share"} & names
    for other in ("gpt2-medium.chat-backlog", "ouro-2.6b.reason-backlog",
                  "zaya1-8b.reason-long-backlog",
                  "ling-3.0-flash.doc-reason-backlog",
                  "qwen3-next-80b-a3b.doc-chat-backlog",
                  "nemotron-3-nano-30b-a3b.assistant-backlog"):
        assert not {"mla_moe_decode_tick_roofline",
                    "latent_bytes_per_tick_share"} & {
            m["name"] for m in Cell(other).per_layer()}
    # "contains", never "equals" or "last": a later PR appends
    assert "glm-4.7-flash" in [c["name"] for c in manifest["configs"]]
    assert CELL in [w["name"] for w in manifest["workloads"]]
    assert all(w["chips"] == 1 for w in manifest["workloads"])
