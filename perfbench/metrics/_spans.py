"""Shared by the readers of the program's OWN span stream
(``pytorch_distributed_tpu/telemetry/spans.py``: one ring a process, on
the ``time.perf_counter`` clock of ``outcome["counters"]["window"]``). The
ring is the process's, so it survives the jobs' ``del router`` / ``del
trainer``. A program that has no such stream (a parent from before it)
gives ``None``, and every reader built on it reports nothing."""

import statistics


def stream():
    """The program's tracer, or ``None`` where it has none."""
    try:
        from pytorch_distributed_tpu.telemetry import spans
    except ImportError:
        return None
    get = getattr(spans, "tracer", None)
    return get() if get is not None else None


def window(outcome) -> tuple:
    t0, t1 = outcome["counters"]["window"]
    return float(t0), float(t1)


def setup_interval(outcome) -> tuple:
    """Set-up as the job timed it: the ``setup_s`` seconds that end where
    the window starts (a little slack for the statements between)."""
    t0, _ = window(outcome)
    return t0 - float(outcome["e2e"]["setup_s"]) - 1.0, t0 + 0.5


def inside(name: str, lo: float, hi: float) -> list:
    """Records called ``name`` that lie wholly inside ``[lo, hi]``."""
    tr = stream()
    if tr is None:
        return []
    return [e for e in tr.events(name, lo, hi) if e.t0 >= lo and e.t1 <= hi]


def in_window(outcome, name: str) -> list:
    return inside(name, *window(outcome))


def in_setup(outcome, name: str) -> list:
    return inside(name, *setup_interval(outcome))


def median_ms(records: list):
    return (1e3 * statistics.median(e.t1 - e.t0 for e in records)
            if records else None)
