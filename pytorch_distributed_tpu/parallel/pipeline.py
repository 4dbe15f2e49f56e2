"""Pipeline parallelism: a GPipe-style microbatch executor under shard_map.

Completes the framework's parallelism catalogue (dp/sp/tp/ep in
``mesh``/``sequence``/``tensor``/``models.moe``; pp here — all absent from
the reference, SURVEY.md §2c). TPU-first shape discipline:

- stages live on the ``model`` mesh axis; stage s holds its own slice of
  the layer stack (placement-sharded params, like TP/EP);
- the schedule is one ``lax.scan`` over M + S - 1 ticks; each tick every
  stage computes its current microbatch and ``ppermute``s the activation to
  its successor — the classic GPipe pipeline with bubble fraction
  (S-1)/(M+S-1), all static shapes, no data-dependent control flow;
- warm-up/drain bubbles are computed-but-masked (XLA cannot skip them
  without dynamic shapes); outputs are collected at the LAST stage and are
  valid there — combine with an out_spec that reads the final stage's
  shard, or psum-mask as needed by the caller;
- the whole schedule differentiates through scan + ppermute, so the same
  executor trains (backward replays the ring in reverse).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from pytorch_distributed_tpu.parallel.mesh import MODEL_AXIS


def gpipe(
    stage_fn: Callable[..., Any],
    stage_params: Any,
    microbatches: jax.Array,
    *,
    axis: str = MODEL_AXIS,
    remat: bool = True,
    has_aux: bool = False,
):
    """Run microbatches through the stage pipeline (call under shard_map).

    Args:
      stage_fn: ``(stage_params, x, mb_idx) -> y`` (or ``-> (y, aux)`` with
        ``has_aux``) — one stage's computation; every stage must map the
        same activation shape to itself (uniform-width pipeline, e.g. a
        slice of transformer blocks). ``mb_idx`` is the index of the
        microbatch this tick computes on THIS stage (clipped during
        warm-up/drain) — derive dropout rngs from it so the pipelined run
        reproduces the sequential reference's masks exactly. A 2-arg
        ``(stage_params, x)`` stage_fn (the pre-r3 contract) is also
        accepted and simply doesn't receive the index.
      stage_params: THIS stage's parameters (the local shard of a
        stage-stacked tree).
      microbatches: ``[M, ...]`` — the full input, identical on every stage
        (stage 0 consumes it; others ignore theirs).
      has_aux: ``stage_fn`` also returns a scalar auxiliary loss (e.g. MoE
        load balancing); contributions from warm-up/drain ticks — garbage
        activations — are masked OUT (their gradients too), and the summed
        real-tick aux is returned alongside the outputs.

    Returns: ``[M, ...]`` outputs (with ``has_aux``: ``(outputs, aux)``),
    VALID ON THE LAST STAGE (other stages hold garbage from their position
    in the ring; ``aux`` is valid on EVERY stage for its own real ticks) —
    select stage S-1's output copy via ``last_stage_value`` or a psum-mask.
    """
    # r2→r3 API compatibility: stage_fns written against the 2-arg contract
    # ``(stage_params, x)`` (before mb_idx existed for dropout parity) are
    # accepted and simply don't receive the index. Detected once at trace
    # time from the signature. CONTRACT for opaque signatures (ADVICE r4
    # #2): ``*args`` callables and C callables whose signature cannot be
    # inspected are assumed mb_idx-AWARE and receive the 3-arg call
    # ``(stage_params, x, mb_idx)`` — a legacy 2-arg wrapper written as
    # ``lambda *a: f(*a[:2])``-style must accept (and may ignore) the
    # third argument, or expose a real 2-positional signature to opt out.
    import inspect

    try:
        params = list(inspect.signature(stage_fn).parameters.values())
        pos = [p for p in params
               if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)]
        if any(p.kind == p.VAR_POSITIONAL for p in params):
            takes_mb_idx = True
        elif len(pos) < 3:
            takes_mb_idx = False
        elif pos[2].default is inspect.Parameter.empty:
            takes_mb_idx = True
        else:
            # A defaulted third positional is ambiguous: a pre-r3 fn like
            # ``(params, x, train=False)`` must NOT receive the traced
            # index in ``train``. Only a parameter literally named mb_idx
            # opts in.
            takes_mb_idx = pos[2].name == "mb_idx"
    except (TypeError, ValueError):  # builtins / C callables
        takes_mb_idx = True

    s = jax.lax.psum(1, axis)
    my = jax.lax.axis_index(axis)
    m = microbatches.shape[0]
    mb_shape = microbatches.shape[1:]

    # Send each stage's activation to its successor; the ring wraps only to
    # keep the permutation total (stage 0 ignores what it receives).
    perm = [(i, (i + 1) % s) for i in range(s)]

    def tick(carry, t):
        incoming, outputs, aux_acc = carry
        # Stage 0 feeds microbatch t while t < M; later stages consume what
        # arrived from their predecessor last tick. Stage ``my`` works on
        # microbatch t - my when my <= t < my + M (else a garbage tick).
        feed = microbatches[jnp.clip(t, 0, m - 1)]
        x = jnp.where(my == 0, feed, incoming)
        mb_idx = jnp.clip(t - my, 0, m - 1)
        call_args = (
            (stage_params, x, mb_idx) if takes_mb_idx else (stage_params, x)
        )
        if has_aux:
            y, aux = stage_fn(*call_args)
            real = ((t >= my) & (t < my + m)).astype(aux.dtype)
            aux_acc = aux_acc + real * aux
        else:
            y = stage_fn(*call_args)
        # The last stage banks its result at output slot t - (S-1) (valid
        # once the pipeline is full).
        slot = jnp.clip(t - (s - 1), 0, m - 1)
        valid = (t >= s - 1) & (jnp.asarray(my) == s - 1)
        current = jax.lax.dynamic_index_in_dim(outputs, slot, keepdims=False)
        banked = jnp.where(valid, y, current)
        outputs = jax.lax.dynamic_update_index_in_dim(outputs, banked, slot, 0)
        incoming = jax.lax.ppermute(y, axis, perm)
        return (incoming, outputs, aux_acc), None

    if remat:
        tick = jax.checkpoint(tick)

    init = (
        jnp.zeros(mb_shape, microbatches.dtype),
        jnp.zeros((m,) + mb_shape, microbatches.dtype),
        jnp.zeros((), jnp.float32),
    )
    (_, outputs, aux), _ = jax.lax.scan(tick, init, jnp.arange(m + s - 1))
    return (outputs, aux) if has_aux else outputs


def last_stage_value(x: jax.Array, axis: str = MODEL_AXIS) -> jax.Array:
    """Broadcast the LAST stage's copy of ``x`` to every stage (psum-mask —
    one collective), turning gpipe's stage-local outputs into a replicated
    value usable by loss code on any stage."""
    s = jax.lax.psum(1, axis)
    my = jax.lax.axis_index(axis)
    mask = (my == s - 1).astype(x.dtype)
    return jax.lax.psum(x * mask, axis)
