"""Layer: trainer loop. ``data_wait_share`` for the cells that report
``train_images_per_s`` (a per-layer metric moves one end-to-end metric, so
the quantity has a name a rate). Source: program_span."""

from perfbench.metrics.data_wait_share import read  # noqa: F401
