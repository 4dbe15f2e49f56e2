"""Sync-free device metrics: a donated on-device ring, drained lagged.

The problem (ISSUE 4): both trainers materialized their log-interval
metrics with ``float(v)`` — a blocking device→host sync that stalls the
async dispatch pipeline every ``log_every`` steps. On the 2026-07 runtime
one such round trip measured ~95 ms (PERF_NOTES.md; not re-measured),
which at ``log_every=100`` is real goodput lost to printing a loss.

The fix: the trainer pushes each log event's replicated metric scalars
into a fixed-shape ``[capacity, n_metrics]`` f32 device buffer via a tiny
compiled ``dynamic_update_slice`` program that DONATES the buffer and the
write index — pure device work, dispatched asynchronously, zero host
transfers, zero allocations after the first window. When a window fills,
the buffer is handed to an async host copy and a fresh one is minted
on-device; the *previous* window — whose copy has long since completed —
is read then, so the host never blocks on in-flight device work. The
values make exactly one f32 hop through the buffer, so the drained
series is bit-identical to what the blocking ``float()`` path logged.

``flush()`` (epoch end) force-drains both the pending window and the
partial current one; that read may wait on the last pushed step, which
is the same sync the epoch-timing record already pays.

The push is its own jitted program, *outside* the train step: wrapping
the step with ``analysis.no_recompile`` (jit-cache growth + implicit
transfer guard) stays green with telemetry enabled —
``tests/test_telemetry.py`` proves it.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence


class DeviceMetricsRing:
    """Fixed-shape on-device metrics ring with lagged, windowed drain.

    ``names``    ordered metric keys; every ``append`` must supply each.
    ``capacity`` window length: the drain interval (``flush_every``).
    ``sharding`` optional ``jax.sharding.Sharding`` for the buffer —
                 pass the mesh's replicated sharding when the pushed
                 scalars are replicated global arrays (mixing a
                 single-device buffer with mesh-replicated operands is a
                 jit device-mismatch error).

    ``append(metrics, **meta)`` pushes one row (device work only) and
    returns the drained records of the PREVIOUS window when the current
    one just filled — each record is ``{**meta, name: float, ...}`` in
    push order. ``flush()`` drains everything still buffered.
    """

    def __init__(
        self,
        names: Sequence[str],
        capacity: int = 32,
        sharding: Optional[Any] = None,
    ):
        import jax
        import jax.numpy as jnp

        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        if not names:
            raise ValueError("names must be non-empty")
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate metric names: {list(names)}")
        self.names: List[str] = list(names)
        self.capacity = int(capacity)
        n = len(self.names)
        cap = self.capacity

        def _push(buf, idx, vals):
            row = jnp.stack(
                [jnp.asarray(v).astype(jnp.float32) for v in vals]
            )
            buf = jax.lax.dynamic_update_slice(
                buf, row[None, :], (idx % cap, jnp.zeros((), jnp.int32))
            )
            return buf, idx + 1

        def _fresh():
            return (
                jnp.zeros((cap, n), jnp.float32),
                jnp.zeros((), jnp.int32),
            )

        out_sh = (sharding, sharding) if sharding is not None else None
        # donation keeps the window buffer at one allocation for the
        # whole run; the index scalar rides along
        self._push = jax.jit(_push, donate_argnums=(0, 1))
        self._fresh = (
            jax.jit(_fresh, out_shardings=out_sh)
            if out_sh is not None
            else jax.jit(_fresh)
        )
        self._buf, self._idx = self._fresh()
        self._metas: List[dict] = []
        self._pending = None  # (buf, metas) awaiting its lagged host read
        self.pushed = 0
        self.drained = 0

    # ---- the hot path ----------------------------------------------------

    def append(self, metrics: Dict[str, Any], **meta) -> List[dict]:
        """Push one row of device scalars; never blocks on device work.

        Returns drained records (possibly empty): when this push fills
        the window, the previous window — already host-resident — is
        materialized and returned, and the filled one starts its async
        host copy.
        """
        vals = tuple(metrics[name] for name in self.names)
        self._buf, self._idx = self._push(self._buf, self._idx, vals)
        self._metas.append(dict(meta))
        self.pushed += 1
        if len(self._metas) >= self.capacity:
            return self._rotate()
        return []

    def _rotate(self) -> List[dict]:
        out = self._harvest()
        buf, metas = self._buf, self._metas
        try:
            buf.copy_to_host_async()  # overlap the D2H with training
        except AttributeError:  # non-jax.Array stand-ins in unit tests
            pass
        self._pending = (buf, metas)
        self._buf, self._idx = self._fresh()
        self._metas = []
        return out

    # ---- the (lagged) host reads -----------------------------------------

    def _rows(self, buf, metas: List[dict]) -> List[dict]:
        import jax
        import numpy as np

        arr = np.asarray(jax.device_get(buf))
        out = []
        for i, meta in enumerate(metas):
            rec = dict(meta)
            for j, name in enumerate(self.names):
                rec[name] = float(arr[i, j])
            out.append(rec)
        self.drained += len(out)
        return out

    def _harvest(self) -> List[dict]:
        if self._pending is None:
            return []
        buf, metas = self._pending
        self._pending = None
        return self._rows(buf, metas)

    def flush(self) -> List[dict]:
        """Force-drain the pending window AND the current partial one
        (epoch end / run end). May block on the last pushed step."""
        out = self._harvest()
        if self._metas:
            out.extend(self._rows(self._buf, self._metas))
            self._buf, self._idx = self._fresh()
            self._metas = []
        return out

    @property
    def buffered(self) -> int:
        """Rows pushed but not yet drained (pending + current window)."""
        pend = len(self._pending[1]) if self._pending is not None else 0
        return pend + len(self._metas)
