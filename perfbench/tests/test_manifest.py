"""BENCHMARK.json against the contract's limits, and every name it holds
against the files the harness finds by that name."""

import os
import re

import pytest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_keys_and_counts(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= len(manifest["workloads"]) <= 24
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)
    assert all(w["chips"] in (1, 4) for w in manifest["workloads"])
    # the whole check fits its limit with all 24 cells
    r = manifest["run_seconds"]
    assert (2 + 14 * 24) * (r + 60) + 24 * 180 + 1200 <= 43200


def test_names_and_units_hold_only_the_allowed_characters(manifest):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in manifest[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
    assert len(names) == len(set(names))
    for w in manifest["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["reduced"]) <= 16


def test_every_name_leads_to_its_files(manifest, root):
    bench = os.path.join(root, "perfbench")
    cells = {w["name"] for w in manifest["workloads"]}
    configs = {c["name"] for c in manifest["configs"]}
    e2e = {m["name"] for m in manifest["end_to_end"]}
    assert "setup_s" in e2e
    for c in manifest["configs"]:
        assert os.path.isfile(os.path.join(root, c["file"]))
        assert c["file"].startswith("perfbench/")
    assert configs == {w["config"] for w in manifest["workloads"]}
    for w in manifest["workloads"]:
        assert os.path.isfile(os.path.join(bench, "cells",
                                           w["name"] + ".json"))
        assert os.path.isfile(os.path.join(bench, "traffic",
                                           w["traffic"] + ".json"))
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    for m in manifest["per_layer"]:
        assert os.path.isfile(os.path.join(bench, "metrics",
                                           m["name"] + ".py")), m["name"]
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            assert w in cells
    for m in manifest["end_to_end"]:
        for w in m.get("workloads", []):
            assert w in cells
    # every cell reports setup_s, another end-to-end metric and a layer's
    from perfbench.harness.manifest import Cell

    for w in cells:
        cell = Cell(w, root)
        assert len(cell.end_to_end()) >= 2
        assert len(cell.per_layer()) >= 1
        for m in cell.per_layer():
            assert callable(cell.reader(m["name"]))


def test_files_under_paths_are_named_from_a_names_characters(root, manifest):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in manifest["paths"]:
        for base, dirs, files in os.walk(os.path.join(root, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), root)
                assert ok.match(rel) and len(rel) <= 200, rel
