"""``lagged_step_share`` on made-up spans: with the ``in_flight`` argument
the router's step records since it keeps a tick in flight, without it (the
parent's spans), and through a serving cell's CPU rehearsal."""

import pytest

from perfbench.harness.manifest import Cell
from perfbench.metrics import _spans
from pytorch_distributed_tpu.telemetry.spans import SpanTracer

CELLS = ["gpt2-medium.chat-backlog", "ouro-2.6b.reason-backlog",
         "zaya1-8b.reason-long-backlog"]
NAME = "lagged_step_share"
OUTCOME = {"counters": {"window": (100.0, 110.0)}, "e2e": {"setup_s": 40.0}}


@pytest.fixture
def ring(monkeypatch):
    tr = SpanTracer()
    monkeypatch.setattr(_spans, "stream", lambda: tr)
    return tr


@pytest.mark.parametrize("cell", CELLS)
def test_the_manifest_lists_the_metric_in_the_serving_cells(manifest, cell):
    row = dict(next(m for m in manifest["per_layer"] if m["name"] == NAME))
    # a later PR may append a serving cell to the list, and nothing else
    assert row.pop("workloads")[:len(CELLS)] == CELLS
    assert row == {"name": NAME, "unit": "%", "better": "higher",
                   "source": "program_span",
                   "layer": "routing and scheduling",
                   "moves": "serve_tokens_per_s"}
    assert NAME in {m["name"] for m in Cell(cell).per_layer()}
    assert NAME not in {m["name"]
                        for m in Cell("gpt2-medium.pretrain").per_layer()}


@pytest.mark.parametrize("cell", CELLS)
def test_it_is_the_share_of_steps_entered_with_a_tick_in_flight(ring, cell):
    read = Cell(cell).reader(NAME)
    assert read(OUTCOME) is None  # no span yet: nothing, no error
    ring.record("router.step", 90.0, 90.1, in_flight=0)  # set-up's
    ring.record("router.step", 99.95, 100.05, in_flight=0)  # straddles t0
    for i, n in enumerate((1, 1, 0, 2, 1)):
        ring.record("router.step", 101.0 + i, 101.1 + i, in_flight=n)
    assert read(OUTCOME) == pytest.approx(100.0 * 4 / 5)
    ring.clear()
    for i in range(3):  # a loop that fetches inside its launch
        ring.record("router.step", 101.0 + i, 101.1 + i, in_flight=0)
    assert read(OUTCOME) == 0.0


@pytest.mark.parametrize("cell", CELLS)
def test_steps_without_the_argument_report_nothing(ring, cell):
    """The parent's spans: a ``router.step`` with no arguments at all."""
    read = Cell(cell).reader(NAME)
    for i in range(3):
        ring.record("router.step", 101.0 + i, 101.1 + i)
    ring.record("engine.decode.launch", 101.0, 101.1, lanes=61)
    assert read(OUTCOME) is None


def test_a_rehearsed_cell_runs_nearly_every_step_with_a_tick_in_flight(root):
    """The job driven untraced on the CPU at toy size (``run.py --trace 1
    --tiny 1`` refuses there), then the reader on what it left in the
    program's ring: the router the job builds, with no loop argument,
    keeps a tick in flight."""
    cell = Cell(CELLS[0], root)
    outcome = cell.job_module().run(cell, seed=4300000007, seconds=1.0,
                                    trace=False, tiny=True)
    assert outcome["correct"], outcome["checks"]
    share = cell.reader(NAME)(outcome)
    assert share is not None and share >= 95.0
