"""Find a cell's files by the names in BENCHMARK.json. Nothing here knows
any cell, configuration, mix or metric by name: a later PR adds files and
an entry, and edits no file that is there."""

from __future__ import annotations

import importlib.util
import json
import os

from perfbench.harness import traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # the checkout


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def merged(base: dict, tiny: bool) -> dict:
    """A file's values, with its ``tiny`` block laid over them for the CPU
    rehearsal at toy sizes."""
    out = {k: v for k, v in base.items() if k != "tiny"}
    if tiny:
        out.update(base.get("tiny", {}))
    return out


def load_module(path: str, name: str):
    """Import one file of the benchmark by path (readers, references)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with everything it names, resolved."""

    def __init__(self, workload: str, root: str = ROOT):
        self.root = root
        self.bench = os.path.join(root, "perfbench")
        self.manifest = load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if workload not in cells:
            raise SystemExit(f"perfbench: no workload {workload!r}; "
                             f"known: {sorted(cells)}")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in self.manifest["configs"]}
        self.config_entry = configs[self.entry["config"]]
        self.config = load_json(os.path.join(root, self.config_entry["file"]))
        self.traffic = traffic.load_mix(self.bench, self.entry["traffic"])
        self.job = load_json(os.path.join(self.bench, "cells",
                                          workload + ".json"))

    def reference(self):
        """The configuration's plain reference, beside its file of sizes."""
        return load_module(
            os.path.join(self.bench, "references",
                         self.config["reference"] + ".py"),
            "perfbench_reference_" + self.config["reference"].replace("-", "_"),
        )

    def sized(self, tiny: bool) -> tuple:
        """(job, configuration, traffic mix) at full or at toy size."""
        return (merged(self.job, tiny), merged(self.config, tiny),
                merged(self.traffic, tiny))

    def job_module(self):
        kind = self.job["job"]
        return load_module(
            os.path.join(self.bench, "harness", "jobs",
                         kind.replace("-", "_") + ".py"),
            "perfbench_job_" + kind.replace("-", "_"),
        )

    def _metrics(self, group: str) -> list:
        out = []
        for m in self.manifest[group]:
            if "workloads" in m and self.name not in m["workloads"]:
                continue
            out.append(m)
        return out

    def end_to_end(self) -> list:
        """End-to-end metrics this cell reports. One without a
        ``workloads`` key is reported by every cell (``setup_s``)."""
        return self._metrics("end_to_end")

    def per_layer(self) -> list:
        """Per-layer metrics whose reader may find something here: those
        that list this cell, and those without a list that move an
        end-to-end metric this cell reports."""
        mine = {m["name"] for m in self.end_to_end()}
        return [m for m in self._metrics("per_layer") if m["moves"] in mine]

    def reader(self, metric: str):
        return load_module(
            os.path.join(self.bench, "metrics", metric + ".py"),
            "perfbench_metric_" + metric.replace("-", "_").replace(".", "_"),
        ).read
