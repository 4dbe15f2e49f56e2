"""Run one cell of the benchmark once.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (build, weights from the seed, warm-up of every shape the cell
uses) is timed as ``setup_s``; then one window of ``--seconds`` is
measured; then what the timed path produced is compared with the
configuration's plain reference. Standard output ends with one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
and, with ``--trace 1``, ``breakdown``. With ``--trace 0`` the metrics are
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics.

A run that finds no TPU, or fewer chips than the cell asks for, exits
non-zero and prints no result. ``--tiny 1`` is the rehearsal on the CPU
that the tests use: toy sizes from the cell's own files, and a result
that says ``cpu``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def parse(argv) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--tiny", type=int, choices=(0, 1), default=0,
                   help="CPU rehearsal at the toy sizes in the cell's files")
    p.add_argument("--control", default=None,
                   help="also read the lower-precision control (a cast of "
                        "harness/weights.py); never part of a result")
    return p.parse_args(argv)


def result_line(cell, outcome: dict, trace: bool) -> dict:
    """The contract's one JSON object from what a job returns."""
    device = dict(outcome["device"])
    metrics = {}
    if trace:
        reduced = outcome["trace"]
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        device["trace_coverage"] = reduced["coverage"]
        for m in cell.per_layer():
            value = cell.reader(m["name"])(outcome)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        for m in cell.end_to_end():
            metrics[m["name"]] = {"value": float(outcome["e2e"][m["name"]]),
                                  "unit": m["unit"]}
    line = {
        "correct": bool(outcome["correct"]),
        "attempted": int(outcome["attempted"]),
        "failed": int(outcome["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if trace:
        line["breakdown"] = {"device_ops": outcome["trace"]["device_ops"],
                             "idle_gaps": outcome["trace"]["idle_gaps"]}
    for key in ("checks", "info"):
        if key in outcome:
            line[key] = outcome[key]
    if trace and "info" in line:  # what this run read end to end, beside
        line["info"] = dict(line["info"], end_to_end=outcome["e2e"])
    return line


def main(argv=None) -> int:
    args = parse(sys.argv[1:] if argv is None else argv)
    tiny = bool(args.tiny)
    if tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from perfbench.harness.manifest import Cell

    cell = Cell(args.workload)
    if tiny and "xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell.chips}").strip()
    outcome = cell.job_module().run(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        tiny=tiny, control=args.control)
    for c in outcome.get("checks", []):
        print(f"check {c['name']}: value {c['value']:.6g} limit "
              f"{c['limit']:.6g} {'ok' if c['ok'] else 'FAILED'}")

    def others():
        return [t.name for t in threading.enumerate()
                if t is not threading.main_thread() and not t.daemon]

    waited = 0.0
    while others() and waited < 10.0:  # a cancelled pool's workers unwind
        time.sleep(0.05)
        waited += 0.05
    leftover = others()
    if leftover:
        print(f"perfbench: threads still running: {leftover}",
              file=sys.stderr)
        return 3
    print(json.dumps(result_line(cell, outcome, bool(args.trace))),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
