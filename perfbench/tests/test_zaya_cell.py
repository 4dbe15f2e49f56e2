"""The zaya configuration's cell: its files, its CPU rehearsal, the count of
what its decode tick needs, and its three readers on hand-made data."""

import json
import os

import pytest

from conftest import run_cell
from perfbench.harness import opcount_cca_moe, traffic
from perfbench.harness.manifest import Cell
from perfbench.metrics import _spans
from pytorch_distributed_tpu.telemetry.spans import SpanTracer

CELL = "zaya1-8b.reason-long-backlog"
REDUCED = {"num_hidden_layers": (20, 40), "n_positions": (2560, 131072)}


@pytest.fixture(scope="module")
def published(root):
    with open(os.path.join(root, "perfbench", "configs",
                           "zaya1-8b.json")) as f:
        return json.load(f)["program"]


def test_the_configuration_is_the_catalogs_and_only_depth_and_context_are_cut(
        root):
    cfg = json.load(open(os.path.join(root, "perfbench", "configs",
                                      "zaya1-8b.json")))
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(r for r in map(json.loads, open(catalog))
                   if r["name"] == "ZAYA1-8B")
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in REDUCED:
                assert cfg[key] == value, key
    assert cfg["reduced"] == list(REDUCED)
    for key, (held, was) in REDUCED.items():
        assert cfg[key] == held and cfg["published"][key] == was
    p = cfg["program"]
    assert (p["num_layers"], p["embed_dim"], p["num_heads"],
            p["num_kv_heads"], p["head_dim"], p["vocab_size"], p["n_experts"],
            p["moe_top_k"], p["moe_dim"], p["router_dim"], p["norm_eps"],
            p["rotary_share"], p["tie_embeddings"], p["use_bias"]) == (
        cfg["num_hidden_layers"], cfg["hidden_size"],
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"], cfg["vocab_size"], cfg["num_experts"],
        cfg["num_experts_per_tok"], cfg["moe_intermediate_size"],
        cfg["router_hidden_size"], cfg["rms_norm_eps"],
        cfg["partial_rotary_factor"], cfg["tie_word_embeddings"],
        cfg["attention_bias"])
    assert p["rope_theta"] == cfg["rope_parameters"]["hybrid"]["rope_theta"]
    from pytorch_distributed_tpu.models.transformer import CCAttention

    assert cfg["cca_time0"] == cfg["cca_time1"] == CCAttention.TAPS
    assert p["max_seq_len"] == cfg["n_positions"]
    for key in ("assumed", "departures_of_the_program", "deployment",
                "router_draw"):
        assert cfg[key], key


def test_the_cell_fills_the_chip_as_its_file_says(root, published):
    cell = Cell(CELL, root)
    job = cell.job
    per_token = published["num_layers"] * 2 * 2 * 128 * 2
    assert per_token == 20_480  # 20 KB of K/V a token
    pool = job["blocks"] * job["block_len"] * per_token
    assert pool == 9217 * 327_680
    parts = opcount_cca_moe.layer_params(published)
    layer = (parts["attention"] + parts["router"] + parts["scales"]
             + 16 * parts["expert"])
    assert 207.5e6 < layer < 207.7e6
    weights = 2 * (20 * layer + 2048 * 262_272 + 2048)
    assert 9.37e9 < weights < 9.39e9
    tails = 20 * (job["slots"] + 1) * opcount_cca_moe.tail_width(
        published) * 2
    assert opcount_cca_moe.tail_width(published) == 2688
    logits = job["slots"] * 262_272 * 4
    assert 12e9 < weights + pool + tails + logits < 13.5e9
    # the mix: 64 pairs, none longer than the context served, decode-heavy
    pairs = traffic.length_multiset(cell.traffic)
    assert len(pairs) == 64
    assert max(p + o for p, o in pairs) <= cell.config["n_positions"]
    assert min(p for p, _ in pairs) == 32 and max(p for p, _ in pairs) == 512
    assert min(o for _, o in pairs) >= 128 and max(o for _, o in pairs) == 2048
    assert sum(o for _, o in pairs) > 4 * sum(p for p, _ in pairs)
    # the tick and TWO chunk programs: every reachable width lands on 32
    assert job["chunk_bucket_floor"] == [2, 32] and job["warm_jobs"] == [2, 4]
    assert -(-512 // job["block_len"]) == 32


def test_the_rehearsal_is_correct_and_the_control_is_not(root):
    """The float32 toy serves the reference's own tokens (gap 0). The
    control reads what float8 moves a logit by, which is small at toy
    widths and follows the seed's tokens: one of two seeds must show it
    over the limit."""
    controls = []
    for seed in ("5", "77"):
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
    assert controls[-1]["value"] > 2 * controls[-1]["limit"]


def test_the_window_reads_the_live_context_on_its_way_up(root):
    """What ``paged_live_share`` finds in this cell (21.6-21.7%, my chip
    runs, PR 31), replayed on the host: the first wave's outputs are cut
    to residual lives but its contexts start at the prompts, an output
    of 2,048 tokens outlasts the window's 1,340 ticks, and so the window
    sees the live context climb from 190 positions a lane towards the
    651 of a server long in service (27.9% of the kernel's grid steps)."""
    import math
    import statistics

    cell = Cell(CELL, root)
    slots, chunk = cell.job["slots"], cell.job["prefill_chunk"]
    pairs = traffic.length_multiset(cell.traffic)
    lived = sum(p * o + o * o / 2 for p, o in pairs) / sum(
        o for _, o in pairs)
    assert 640 < lived < 660

    def replay(seed, before, window):
        stream = traffic.RequestStream(cell.traffic, seed, 1000,
                                       first_wave=slots)
        lanes, live, tiles = [None] * slots, [], []
        for _ in range(before + window):
            free = [i for i, lane in enumerate(lanes) if lane is None]
            for i in free[:cell.job["admit_per_step"]]:
                prompt, out = stream.next()
                lanes[i] = [len(prompt), out, -(-len(prompt) // chunk)]
            live.append(0), tiles.append(0)
            for i, lane in enumerate(lanes):
                if lane is None:
                    continue
                if lane[2]:  # a chunk of its prompt a tick
                    lane[2] -= 1
                    continue
                live[-1] += lane[0]
                tiles[-1] += lane[0] // 128 + 1
                lane[0], lane[1] = lane[0] + 1, lane[1] - 1
                if not lane[1]:
                    lanes[i] = None
        table = slots * math.ceil(cell.config["n_positions"] / 128)
        return (statistics.fmean(live[before:]) / slots,
                100.0 * statistics.fmean(tiles[before:]) / table)

    for seed in (1, 2, 3):
        positions, share = replay(seed, before=60, window=1340)
        assert 470 < positions < 520 and 21.0 < share < 22.8
    positions, share = replay(1, before=4000, window=4000)
    assert 630 < positions < 670 and 27.0 < share < 29.0


def test_the_lean_control_reads_the_harness_numbers(root, monkeypatch):
    """``controls/zaya_lean.py``'s comparison against ``checks.
    served_token_gaps`` on the toy: the same served gap and the same
    control gap from a shorter padding and no slices, and the expert-only
    control beside them (its cast reaches the expert layer alone: with
    the experts' cast the identity it reads the sound gap)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench.harness import checks
    from perfbench.harness.manifest import load_module
    from perfbench.harness.weights import CASTS
    from pytorch_distributed_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )

    lean = load_module(os.path.join(root, "perfbench", "controls",
                                    "zaya_lean.py"), "zaya_lean")
    monkeypatch.setattr(lean, "PAD", 8)
    cell = Cell(CELL, root)
    _, cfg, _ = cell.sized(True)
    ref = cell.reference()
    ref.configure(cfg["program"])
    shapes = jax.eval_shape(
        TransformerLM(TransformerConfig(**cfg["program"])).init,
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    weights = ref.init_params(11, shapes)
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, cfg["vocab_size"], 7).astype(np.int32)
    served = [int(t) for t in rng.integers(1, cfg["vocab_size"], 12)]

    def experts_pass(cast):
        return jax.jit(lambda p, t: ref.logits(p, t[None], cast,
                                               "experts")[0])

    passes = (checks.logits_pass(ref), checks.logits_pass(ref, CASTS["fp8"]))
    want = checks.served_token_gaps(passes, weights, prompt, served,
                                    cfg["n_positions"])
    got = lean.lean_gaps(passes + (experts_pass(CASTS["fp8"]),), weights,
                         prompt, served, cfg["n_positions"])
    assert got["tokens"] == want["tokens"] == 12
    assert got["gap"] == pytest.approx(want["gap"], abs=1e-5)
    assert got["control_gap"] == pytest.approx(want["control_gap"], abs=1e-5)
    assert set(got["scopes"]) == {"sound", "all", "experts"}
    assert got["scopes"]["sound"]["gap"] == got["gap"]
    assert got["scopes"]["sound"]["off_first"] > 0.5  # random tokens served
    same = lean.lean_gaps(passes[:1] + (experts_pass(lambda x: x),), weights,
                          prompt, served, cfg["n_positions"])
    # the float32 reference's own first tokens
    assert same["control_gap"] == 0.0
    assert same["scopes"]["all"]["off_first"] == 0.0
    with pytest.raises(ValueError, match="scope"):
        ref.logits(weights, jnp.zeros((1, 4), jnp.int32), None, "head")


def test_a_tick_against_a_hand_count():
    toy = {"embed_dim": 4, "num_layers": 2, "vocab_size": 10, "num_heads": 2,
           "num_kv_heads": 1, "head_dim": 2, "n_experts": 3, "moe_dim": 5,
           "router_dim": 2}
    parts = opcount_cca_moe.layer_params(toy)
    # latent (2 + 1) x 2 = 6; qkv 4 x (6 + 2) = 32, proj 2 x 2 x 4 = 16,
    # conv1 2 x 6 + 6, conv2 3 x 2 x 2 x 2 + 6, one key temperature
    assert parts["attention"] == 32 + 16 + 18 + 30 + 1
    # down 4 x 2, W1 and W2 2 x 2 each, W3 2 x 3, norm 2, mix 1, bias 3
    assert parts["router"] == 8 + 8 + 6 + 2 + 1 + 3
    assert parts["scales"] == 10 * 4 and parts["expert"] == 3 * 4 * 5
    assert opcount_cca_moe.tail_width(toy) == 2 * 6 + 1
    flops, bytes_ = opcount_cca_moe.cca_moe_decode_tick_need(
        toy, live_slots=5, live_context=70, experts_hit=2.5)
    always = 97 + 28 + 40
    # weights: 2 layers x (165 + 2.5 experts of 60) + head 40 + final norm
    # 4; K and V of 70 live positions and 5 new rows in 2 layers of 1 x 2
    # lanes; 5 tails read and written in 2 layers
    assert bytes_ == ((2 * (always + 150) + 40 + 4) * 2
                      + 75 * 2 * 2 * 2 * 2 + 5 * 2 * 2 * 13 * 2)
    # a token: 2 layers x (165 + ONE expert) + head, twice; attention over
    # 70 positions in 2 layers, QK^T and PV, 2 heads of 2
    assert flops == 5 * 2 * (2 * (always + 60) + 40) + 70 * 2 * 2 * 2 * 4


def test_the_published_tick_reads_what_the_issue_reckons(published):
    parts = opcount_cca_moe.layer_params(published)
    assert parts["expert"] == 3 * 2048 * 2048
    assert 5.5e6 < parts["attention"] < 5.6e6  # 5.24M projections + convs
    assert 0.65e6 < parts["router"] < 0.67e6
    _, all_hit = opcount_cca_moe.cca_moe_decode_tick_need(published, 0, 0, 16)
    _, half = opcount_cca_moe.cca_moe_decode_tick_need(published, 0, 0, 8)
    assert 9.37e9 < all_hit < 9.39e9  # every weight once: 11.5 ms at 819 GB/s
    assert all_hit - half == 20 * 8 * parts["expert"] * 2
    # 128 lanes of 700 positions: 1.8 GB of K/V beside the weights
    _, live = opcount_cca_moe.cca_moe_decode_tick_need(
        published, 128, 128 * 700, 16)
    assert 1.8e9 < live - all_hit < 1.9e9


@pytest.fixture
def ring(monkeypatch):
    tr = SpanTracer()
    monkeypatch.setattr(_spans, "stream", lambda: tr)
    return tr


def outcome(program, tick_s=0.030):
    return {"counters": {"window": (100.0, 110.0),
                         "traced_ticks": [(101.0, 120, 80_000),
                                          (102.0, 124, 84_000),
                                          (103.0, 0, 0)]},
            "e2e": {"setup_s": 40.0},
            "config": {"program": program},
            "device": {"kind": "TPU v5 lite"},
            "trace": {"modules": {"jit_decode_tick(7)": [tick_s] * 3,
                                  "jit_chunk(9)": [0.01]},
                      "labels": {"jit_decode_tick(7)": "decode_tick",
                                 "jit_chunk(9)": "prefill_chunk"}}}


def tick_spans(ring):
    ring.record("sched.collect.process", 90.0, 90.1, expert_tokens_peak=9.0,
                experts_hit=3.0, routed=9)  # set-up's: not counted
    for i, (peak, hit, routed) in enumerate(((14.0, 16.0, 120),
                                             (15.5, 15.0, 124))):
        ring.record("sched.collect.process", 101.0 + i, 101.1 + i,
                    expert_tokens_peak=peak, experts_hit=hit, routed=routed)
    ring.record("sched.collect.process", 103.0, 103.1)  # a tick of no lane


def test_the_expert_readers_read_the_ticks_spans(ring, published):
    cell = Cell(CELL)
    peak = cell.reader("expert_load_peak_ratio")
    share = cell.reader("experts_hit_share")
    # a program whose spans carry nothing (the parent's): nothing, no error
    assert peak(outcome(published)) is None
    assert share(outcome(published)) is None
    ring.record("sched.collect.process", 101.0, 101.1)
    assert peak(outcome(published)) is None
    assert share(outcome(published)) is None
    ring.clear()
    tick_spans(ring)
    assert peak(outcome(published)) == pytest.approx(
        (14.0 * 16 / 120 + 15.5 * 16 / 124) / 2)
    assert share(outcome(published)) == pytest.approx(100.0 * 15.5 / 16)
    # a configuration without experts reports nothing
    assert peak(outcome({"embed_dim": 4})) is None
    assert share(dict(outcome(published), config={"n_embd": 4})) is None


def test_the_roofline_reads_the_traced_ticks_and_the_experts_hit(
        ring, published):
    read = Cell(CELL).reader("cca_moe_decode_tick_roofline")
    assert read(outcome(published)) is None  # no experts_hit in the stream
    tick_spans(ring)
    _, bytes_ = opcount_cca_moe.cca_moe_decode_tick_need(
        published, 122.0, 82_000.0, 15.5)
    assert read(outcome(published)) == pytest.approx(
        100.0 * bytes_ / 819e9 / 0.030)
    assert 40.0 < read(outcome(published)) < 50.0
    # a tick faster than the chip's memory allows is a fault, raised
    with pytest.raises(ArithmeticError):
        read(outcome(published, tick_s=0.008))
    # another block kind, or no program block
    assert read(outcome(dict(published, attn_kind="mha"))) is None
    assert read(dict(outcome(published), config={"n_embd": 4})) is None


def test_the_manifest_gives_the_cell_its_metrics(manifest):
    cell = Cell(CELL)
    assert cell.chips == 1 and cell.job["job"] == "serve-backlog-program"
    # a backlog cell: tokens a second is what it is held to (the gap's
    # tail follows the seed's live context: PERF.md section 2)
    assert {m["name"] for m in cell.end_to_end()} == {
        "serve_tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer()}
    assert {"cca_moe_decode_tick_roofline", "expert_load_peak_ratio",
            "experts_hit_share", "paged_attention_busy_share",
            "paged_live_share", "pool_reserved_share", "decode_occupancy",
            "decode_tick_device_ms", "prefill_chunk_device_ms",
            "tick_host_ms", "tick_exposed_host_ms", "gate_decide_ms",
            "queue_wait_p50_ms", "setup_program_load_s",
            "setup_build_s"} <= names
    # the other block kinds' tick counts are not applied to this one
    assert not {"decode_tick_roofline", "looped_decode_tick_roofline"} & names
    for other in ("gpt2-medium.chat-backlog", "ouro-2.6b.reason-backlog"):
        assert not {"cca_moe_decode_tick_roofline", "experts_hit_share",
                    "expert_load_peak_ratio"} & {
            m["name"] for m in Cell(other).per_layer()}
    # one configuration, one cell and three metrics were appended
    assert manifest["configs"][-1]["name"] == "zaya1-8b"
    assert manifest["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in manifest["per_layer"][-3:]] == [
        "cca_moe_decode_tick_roofline", "expert_load_peak_ratio",
        "experts_hit_share"]
