"""Host spans, the profiler window, and the reduction from a trace to
numbers. The reduction is the yardstick: it lives here, is checked on a
small recorded trace (``perfbench/fixtures``), and no later PR edits it.

A trace is reduced through a light form, ``{"devices": {plane: {"ops":
[[name, start_ns, dur_ns]], "modules": [...], "async": [...]}}, "spans":
[[name, start_ns, dur_ns]]}``, so the arithmetic can be tested without
the profiler.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
import sys
import time

SPAN_PREFIX = "bench:"
WINDOW_SPAN = SPAN_PREFIX + "window"
NO_SPAN = "_no_benchmark_span_"

_COLLECTIVE = ("all-reduce", "reduce-scatter", "all-gather",
               "collective-permute", "all-to-all")
_OP_RE = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)*\s*=\s*\(?\s*"
                    r"([a-z]+\d*)\[([\d,]*)\]")


def phase(t0: float, what: str) -> None:
    """One line on standard error: seconds since ``t0``, and what is done."""
    print(f"perfbench: {time.perf_counter() - t0:8.2f} s  {what}",
          file=sys.stderr, flush=True)


class Spans:
    """Host spans around the benchmark's calls into the program, on the
    host clock and, while a profiler session is open, in the profiler's
    trace as ``bench:<name>`` annotations."""

    def __init__(self):
        self.records: list = []  # (name, t0, t1) perf_counter seconds
        self.annotate = False

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        if self.annotate:
            import jax

            with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
                yield
        else:
            yield
        self.records.append((name, t0, time.perf_counter()))

    def total(self, name: str, t_lo: float = float("-inf"),
              t_hi: float = float("inf")) -> float:
        return sum(min(t1, t_hi) - max(t0, t_lo)
                   for n, t0, t1 in self.records
                   if n == name and t1 > t_lo and t0 < t_hi)


class ProfilerWindow:
    """One profiler session written under ``trace_dir`` (inside the
    checkout; removed when read). The Python tracer is off: it slows the
    host loop that the window measures."""

    def __init__(self, trace_dir: str, spans: Spans):
        self.dir = trace_dir
        self.spans = spans
        self._cm = None

    def start(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.spans.annotate = True
        self._cm = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._cm.__enter__()

    def stop(self) -> dict:
        import jax

        self._cm.__exit__(None, None, None)
        self.spans.annotate = False
        jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not paths:
            raise RuntimeError(f"the profiler wrote no trace under {self.dir}")
        events = xplane_events(max(paths, key=os.path.getmtime))
        shutil.rmtree(self.dir, ignore_errors=True)
        return events


def xplane_events(path: str) -> dict:
    """The light form of one ``.xplane.pb``."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            lines = {"ops": [], "modules": [], "async": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules",
                       "Async XLA Ops": "async"}.get(line.name)
                if key is None:
                    continue
                lines[key] = [[e.name, float(e.start_ns),
                               float(e.duration_ns)] for e in line.events]
            if lines["ops"] or lines["modules"]:
                devices[plane.name] = lines
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append([e.name, float(e.start_ns),
                                      float(e.duration_ns)])
    return {"devices": devices, "spans": spans}


def op_label(name: str) -> str:
    """``%copy.5 = bf16[2561,16,16,64]{...} copy(...)`` ->
    ``copy_bf16_2561_16_16_64_``: the operation without its serial
    number, with the type and shape of what it produces."""
    m = _OP_RE.match(name)
    if not m:
        return re.sub(r"[^\w\-.]+", "_", name)[:64]
    base, dtype, dims = m.groups()
    return f"{base}_{dtype}_{dims.replace(',', '_')}_"


def _base(name: str) -> str:
    m = _OP_RE.match(name)
    return m.group(1) if m else name.lstrip("%").split(" ")[0].split(".")[0]


def is_collective(name: str) -> bool:
    return _base(name).startswith(_COLLECTIVE)


def union(intervals: list) -> list:
    """Sorted disjoint union of [start, end) intervals."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(intervals: list) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: list, b: list) -> list:
    """Parts of the disjoint sorted intervals ``a`` not covered by ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def _clip(events: list, lo: float, hi: float) -> list:
    return [[max(s, lo), min(s + d, hi)] for _, s, d in events
            if s + d > lo and s < hi]


def reduce_events(events: dict) -> dict:
    """Busy and idle seconds, the costliest operations, the longest idle
    gaps by the benchmark span the host was in, per-program times and the
    exposed part of collectives, all inside the ``bench:window`` span: the
    whole of it, so that a device that stands still at its head or tail
    counts as idle. ``coverage`` is the share of the span between the
    first and the last device event recorded in it: well under 1, either
    the device stood still there or the profiler stopped recording (it did
    on four chips, PERF.md section 7), and the idle share says which only
    together with the run's rate. Times are seconds; per-device quantities
    are averaged over devices."""
    window = [s for s in events["spans"] if s[0] == WINDOW_SPAN]
    devices = events["devices"]
    if not devices:
        raise RuntimeError("no operation ran on a device in the traced "
                           "window: the trace holds no device plane")
    if window:
        lo, hi = window[0][1], window[0][1] + window[0][2]
    else:
        lo = min(e[1] for d in devices.values() for e in d["ops"])
        hi = max(e[1] + e[2] for d in devices.values() for e in d["ops"])
    seen = [e for d in devices.values() for e in d["ops"] + d["modules"]
            if e[1] + e[2] > lo and e[1] < hi]
    if not seen:
        raise RuntimeError("no operation ran on a device in the traced "
                           "window")
    coverage = (min(hi, max(e[1] + e[2] for e in seen))
                - max(lo, min(e[1] for e in seen))) / (hi - lo)
    host = [(n[len(SPAN_PREFIX):], s, s + d) for n, s, d in events["spans"]
            if n != WINDOW_SPAN]
    busy = exposed = 0.0
    op_time: dict = {}
    gaps: dict = {}
    modules: dict = {}
    covered: dict = {}  # executions inside the span single operations cover
    for dev in devices.values():
        ops = [e for e in dev["ops"] if e[1] + e[2] > lo and e[1] < hi]
        # a program's execution counts as busy too: the profiler stops
        # recording single operations when its buffer is full (a ResNet
        # step has thousands), and goes on recording programs
        all_busy = union(_clip(ops + dev["modules"], lo, hi))
        busy += length(all_busy)
        if ops:
            c_lo = min(e[1] for e in ops)
            c_hi = max(e[1] + e[2] for e in ops)
            for name, s, d in dev["modules"]:
                if s >= max(c_lo, lo) and s + d <= min(c_hi, hi):
                    covered[name] = covered.get(name, 0) + 1
        for name, s, d in ops:
            # a while or a conditional contains its body's operations:
            # count leaves, which is what the time goes to
            op_time[op_label(name)] = op_time.get(op_label(name), 0.0) + (
                min(s + d, hi) - max(s, lo))
        compute = union(_clip([e for e in ops if not is_collective(e[0])],
                              lo, hi))
        coll = union(_clip([e for e in ops + dev.get("async", [])
                            if is_collective(e[0])], lo, hi))
        exposed += length(subtract(coll, compute))
        for s, e in subtract([[lo, hi]], all_busy):
            mid = 0.5 * (s + e)
            inside = [h for h in host if h[1] <= mid < h[2]]
            # the innermost span the host was in at the gap's middle
            label = (min(inside, key=lambda h: h[2] - h[1])[0]
                     if inside else NO_SPAN)
            gaps[label] = gaps.get(label, 0.0) + (e - s)
        for name, s, d in dev["modules"]:
            if s >= lo and s + d <= hi:
                modules.setdefault(name, []).append(d)
    n = len(devices)
    nested = _nested_labels(devices)
    top_ops = sorted(((k, v / n * 1e-9) for k, v in op_time.items()
                      if k not in nested), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(((k, v / n * 1e-9) for k, v in gaps.items()),
                      key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": busy / n * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "coverage": coverage,
        "device_ops": [[k, v] for k, v in top_ops],
        "idle_gaps": [[k, v] for k, v in top_gaps],
        "collective_exposed_s": exposed / n * 1e-9,
        # program name -> list of device durations (seconds), all devices
        "modules": {k: [d * 1e-9 for d in v] for k, v in modules.items()},
        "modules_covered": covered,
        "devices": n,
    }


def _nested_labels(devices: dict) -> set:
    """Labels of operations that contain other operations (``while``,
    ``conditional``, ``call``): their time is their bodies', listed once."""
    out = set()
    for dev in devices.values():
        for name, _, _ in dev["ops"]:
            if _base(name) in ("while", "conditional", "call"):
                out.add(op_label(name))
    return out
