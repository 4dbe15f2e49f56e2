"""Job kind ``serve-backlog-program``: job kind ``serve-backlog`` for a
configuration whose file spells the program's own ``TransformerConfig``.

``serve_backlog.build`` reads GPT-2's key names (``n_layer``, ``n_head``,
``n_embd``); a configuration of another block kind carries a ``program``
block instead, ``TransformerConfig``'s fields by their own names, and this
job passes them through. The driver, the warm-up, the window and the
comparison that decides ``correct`` are ``serve_backlog``'s own, unedited:
its ``run`` calls the module's global ``build``, so this file puts its
``build`` there. ``run`` itself reads only ``vocab_size`` and
``n_positions`` from the configuration, which the file carries beside the
published keys. A reference that needs more than the parameter tree (how
many passes a looped stack runs) is told through its ``configure``. A cell
may bound the chunk programs the server compiles (``chunk_bucket_floor``,
``max_chunk_jobs``: the engine's options, by their own names); the
warm-up's requests then land on the buckets that run.
"""

from __future__ import annotations

from perfbench.harness.jobs import serve_backlog


def build(cell, job: dict, cfg: dict, seed: int, devices, ref):
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.fleet import FleetRouter, SLOConfig
    from pytorch_distributed_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )

    dtype = getattr(jnp, cfg["dtype"])
    model_cfg = TransformerConfig(**cfg["program"], dropout=0.0, dtype=dtype,
                                  attention="dense")
    if hasattr(ref, "configure"):
        ref.configure(cfg["program"])
    shapes = jax.eval_shape(
        TransformerLM(model_cfg).init, jax.random.key(0),
        jnp.zeros((1, 8), jnp.int32))["params"]
    weights = ref.init_params(seed, shapes, dtype)
    depth = 1 << 30  # a backlog is the point: the gate never sheds it
    router = FleetRouter(
        model_cfg, weights, n_replicas=1, devices=devices,
        slo=SLOConfig(spill_queue_depth=depth, shed_queue_depth=depth),
        retain_results=False, n_slots=job["slots"],
        n_blocks=job["blocks"], block_len=job["block_len"],
        prefill_chunk=job["prefill_chunk"],
        admit_per_step=job["admit_per_step"],
        chunk_bucket_floor=tuple(job.get("chunk_bucket_floor", (1, 1))),
        max_chunk_jobs=job.get("max_chunk_jobs"),
    )
    return router, weights


def run(cell, seed: int, seconds: float, trace: bool, tiny: bool,
        control=None) -> dict:
    serve_backlog.build = build
    return serve_backlog.run(cell, seed, seconds, trace, tiny, control)
