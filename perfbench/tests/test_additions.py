"""A configuration, a traffic mix, a cell and a per-layer metric added
purely as new files plus entries, in a throw-away copy: no line of the
harness is edited."""

import json
import os
import shutil

import pytest

from conftest import copy_checkout, run_cell


def throwaway_copy(root, tmp_path):
    copy, manifest = copy_checkout(root, tmp_path)
    bench = copy / "perfbench"

    cfg = json.load(open(bench / "configs" / "gpt2-medium.json"))
    cfg["tiny"].update(n_embd=16, n_layer=1, n_head=2)
    json.dump(cfg, open(bench / "configs" / "toy-lm.json", "w"))
    mix = json.load(open(bench / "traffic" / "chat-backlog.json"))
    mix["multiset"] = 8
    mix["pairing"] = [3, 1, 4, 0, 5, 7, 2, 6]
    mix["tiny"] = {"prompt": {"median": 6, "sigma": 0.3, "min": 3, "max": 9},
                   "output": {"median": 3, "sigma": 0.3, "min": 2, "max": 5}}
    json.dump(mix, open(bench / "traffic" / "toy-mix.json", "w"))
    shutil.copy(bench / "cells" / "gpt2-medium.chat-backlog.json",
                bench / "cells" / "toy-lm.toy-mix.json")
    (bench / "metrics" / "toy_ticks.py").write_text(
        '"""Ticks in the window (a throw-away reader)."""\n\n\n'
        "def read(outcome):\n"
        "    return float(len(outcome['counters']['ticks'])) or None\n")
    manifest["configs"].append({
        "name": "toy-lm", "source": "https://example.org/toy",
        "file": "perfbench/configs/toy-lm.json", "reduced": [],
        "why": "a throw-away configuration"})
    manifest["workloads"].append({
        "name": "toy-lm.toy-mix", "config": "toy-lm", "traffic": "toy-mix",
        "chips": 1, "why": "a throw-away cell"})
    for m in manifest["end_to_end"]:
        if m["name"] in ("serve_tokens_per_s", "gap_p95_ms"):
            m["workloads"].append("toy-lm.toy-mix")
    manifest["per_layer"].append({
        "name": "toy_ticks", "unit": "ticks", "better": "higher",
        "source": "program_counter", "layer": "routing and scheduling",
        "moves": "serve_tokens_per_s", "workloads": ["toy-lm.toy-mix"]})
    json.dump(manifest, open(copy / "BENCHMARK.json", "w"))
    return str(copy)


def test_additions_are_data(root, tmp_path):
    copy = throwaway_copy(root, tmp_path)
    rc, line, out, err = run_cell(copy, "toy-lm.toy-mix")
    assert rc == 0, err[-3000:]
    assert line["correct"] is True
    assert set(line["metrics"]) == {"serve_tokens_per_s", "gap_p95_ms",
                                    "setup_s"}
    # the cells that were there still run from the copy, untouched
    rc, old, _, err = run_cell(copy, "gpt2-medium.chat-backlog")
    assert rc == 0 and old["correct"] is True, err[-3000:]

    # the new reader is found by its name and reads the run's counters
    import sys

    sys.path.insert(0, copy)
    try:
        from perfbench import run as run_mod
        from perfbench.harness.manifest import Cell

        cell = Cell("toy-lm.toy-mix", copy)
        assert "toy_ticks" in [m["name"] for m in cell.per_layer()]
        assert "toy_ticks" not in [
            m["name"]
            for m in Cell("gpt2-medium.chat-backlog", copy).per_layer()]
        with open(os.path.join(root, "perfbench", "fixtures",
                               "trace_small.json")) as f:
            from perfbench.harness import tracing

            reduced = tracing.reduce_events(json.load(f))
        outcome = {
            "correct": True, "attempted": 3, "failed": 0,
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                       "memory_peak_bytes": 1},
            "trace": dict(reduced, labels={"jit_body(111)": "decode_tick",
                                           "jit_body(222)": "prefill_chunk"}),
            "counters": {"ticks": [(1.0, 60, 5000), (2.0, 62, 5100)],
                         "traced_ticks": [(1.0, 60, 5000), (2.0, 62, 5100)],
                         "window": (0.0, 2.0), "slots": 64, "ttfts": [0.5]},
            "config": dict(cell.config, **cell.config["tiny"]), "cell": cell,
        }
        line = run_mod.result_line(cell, outcome, trace=True)
        assert line["metrics"]["toy_ticks"] == {"value": 2.0, "unit": "ticks"}
        approx = pytest.approx
        assert line["metrics"]["decode_tick_device_ms"]["value"] == approx(
            0.005)
        assert line["metrics"]["prefill_chunk_device_ms"]["value"] == approx(
            0.002)
        assert line["metrics"]["decode_occupancy"]["value"] == approx(
            100 * 61 / 64)
        assert line["device"]["busy_s"] > 0 and "breakdown" in line
        # the roofline reader refuses the impossible: a 5 us tick for
        # 0.7 GB of weights is far beyond the peak
        with pytest.raises(ArithmeticError):
            cell.reader("decode_tick_roofline")(
                dict(outcome, config=json.load(open(os.path.join(
                    root, "perfbench", "configs", "gpt2-medium.json")))))
    finally:
        sys.path.remove(copy)
