"""``paged_live_share`` on made-up spans: with the tile arguments the
program records since its kernel stages tiles, and without them."""

import pytest

from perfbench.harness.manifest import Cell
from perfbench.metrics import _spans
from pytorch_distributed_tpu.telemetry.spans import SpanTracer

CELLS = ["gpt2-medium.chat-backlog", "ouro-2.6b.reason-backlog"]
NAME = "paged_live_share"
OUTCOME = {"counters": {"window": (100.0, 110.0)}, "e2e": {"setup_s": 40.0}}


@pytest.fixture
def ring(monkeypatch):
    tr = SpanTracer()
    monkeypatch.setattr(_spans, "stream", lambda: tr)
    return tr


@pytest.mark.parametrize("cell", CELLS)
def test_the_manifest_lists_the_metric_in_both_serving_cells(manifest, cell):
    row = next(m for m in manifest["per_layer"] if m["name"] == NAME)
    assert row == {"name": NAME, "unit": "%", "better": "higher",
                   "source": "program_span", "layer": "kernels",
                   "moves": "serve_tokens_per_s", "workloads": CELLS}
    assert NAME in {m["name"] for m in Cell(cell).per_layer()}
    assert NAME not in {m["name"]
                        for m in Cell("gpt2-medium.pretrain").per_layer()}


@pytest.mark.parametrize("cell", CELLS)
def test_it_is_the_mean_of_live_tiles_over_the_tables_tiles(ring, cell):
    read = Cell(cell).reader(NAME)
    assert read(OUTCOME) is None  # no span yet: nothing, no error
    ring.record("pool.alloc", 70.0, 71.0, blocks=2561, read="pallas",
                table_blocks=4096, tile_blocks=8, table_tiles=512)
    ring.record("engine.decode.launch", 90.0, 90.1, lanes=64,
                live_blocks=700, live_tiles=500)  # set-up's: not counted
    for i, tiles in enumerate((100, 120, 140)):
        ring.record("engine.decode.launch", 101.0 + i, 101.1 + i, lanes=61,
                    live_blocks=716, live_tiles=tiles)
    assert read(OUTCOME) == pytest.approx(100.0 * 120 / 512)


@pytest.mark.parametrize("cell", CELLS)
def test_spans_without_the_tile_arguments_report_nothing(ring, cell):
    """The parent's spans: ``table_blocks`` and ``live_blocks`` only."""
    read = Cell(cell).reader(NAME)
    ring.record("pool.alloc", 70.0, 71.0, blocks=2561, read="pallas",
                table_blocks=4096)
    ring.record("engine.decode.launch", 101.0, 101.1, lanes=61,
                live_blocks=716)
    assert read(OUTCOME) is None
    # the tables' tiles without a tick in the window
    ring.clear()
    ring.record("pool.alloc", 70.0, 71.0, blocks=2561, table_tiles=512)
    assert read(OUTCOME) is None
