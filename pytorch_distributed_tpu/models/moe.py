"""Mixture-of-Experts MLP with expert parallelism (GShard/Switch style).

Absent from the reference (SURVEY.md §2c: EP out of scope) but part of this
framework's first-class parallelism set. TPU-first shape discipline
throughout: routing is static-shape capacity-based dispatch (one-hot
einsums, no gather/scatter, no data-dependent shapes), so the whole layer
compiles into the surrounding step.

Expert parallelism rides the **data** axis: DP ranks hold different tokens
and different expert shards (the classic GShard identification of the
expert axis with the data axis), so a single ``lax.all_to_all`` per
direction moves each token to its expert's owner and back. Expert weights
are stored GLOBAL-shaped ``[E, ...]`` and sharded by placement
(``P(data)`` on the expert dim — same design as the TP rules), which keeps
checkpoints layout-independent; gradients of sharded expert weights are
local to their owner, handled by the spec-driven reduction in
``train.lm.make_lm_train_step``.

Routing: top-1 (Switch Transformer) with capacity ``ceil(cf · T / E)``;
over-capacity tokens fall through to the residual path. The Switch
load-balancing auxiliary loss is sowed (pre-weighted) into the
``aux_loss`` collection; the LM step collects and adds it.

Interaction with tensor parallelism: with ``model_axis``/``tp_size`` set,
the expert HIDDEN dim partitions over the model axis (Megatron column/row
split inside each expert: ``w_up`` is column-parallel, ``w_down``
row-parallel with one psum) — TP buys real FLOPs in MoE blocks. Router,
dispatch, and the capacity buffers stay replicated across the model axis
(every TP rank routes identically), so the all_to_all expert exchange is
unchanged. With ``model_axis=None`` (default) every model rank computes
the full expert MLP redundantly — correct, just wasteful, kept for
mesh-without-TP layouts.
"""

from __future__ import annotations

import math
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp


def topk_dispatch(
    router_logits: jax.Array,  # [T, E] fp32
    capacity: int,
    k: int = 1,
):
    """Static-shape top-k routing (k=1: Switch; k=2: GShard).

    Returns (dispatch [T, E, C] f32 0/1, combine [T, E, C] f32
    gate-weighted, aux_loss scalar, stats dict). Capacity is filled in
    choice-rank priority (all first choices place before any second
    choice, the GShard rule); assignments beyond capacity are dropped —
    ``stats["dropped_frac"]`` is the fraction of tokens with NO surviving
    route (their block output is the residual alone).

    Gates: k=1 uses the raw chosen probability (Switch); k>1 normalizes the
    chosen probabilities to sum to 1 (GShard), keeping the layer's output
    scale constant in k.
    """
    t, e = router_logits.shape
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    topv, topi = jax.lax.top_k(probs, k)  # [T, k]
    if k == 1:
        gates = topv
    else:
        gates = topv / jnp.maximum(jnp.sum(topv, axis=-1, keepdims=True), 1e-9)

    dispatch = jnp.zeros((t, e, capacity), jnp.float32)
    combine = jnp.zeros((t, e, capacity), jnp.float32)
    counts = jnp.zeros((e,), jnp.float32)  # buffer fill from earlier ranks
    for r in range(k):  # k is a small static constant
        onehot = jax.nn.one_hot(topi[:, r], e, dtype=jnp.float32)  # [T, E]
        # Position of each token within its expert's buffer: tokens placed
        # by earlier choice-ranks (counts) go first, then arrival order.
        position = (jnp.cumsum(onehot, axis=0) - 1.0 + counts) * onehot
        pos_tok = jnp.sum(position, axis=-1).astype(jnp.int32)  # [T]
        keep = (pos_tok < capacity).astype(jnp.float32)  # [T]
        disp_r = (
            onehot[:, :, None]
            * jax.nn.one_hot(pos_tok, capacity, dtype=jnp.float32)[:, None, :]
            * keep[:, None, None]
        )  # [T, E, C]
        dispatch = dispatch + disp_r
        combine = combine + disp_r * gates[:, r][:, None, None]
        counts = counts + jnp.sum(onehot * keep[:, None], axis=0)

    # Switch/GShard load-balancing loss on FIRST-choice statistics:
    # E · Σ_e (token fraction)·(mean prob).
    first = jax.nn.one_hot(topi[:, 0], e, dtype=jnp.float32)
    aux = e * jnp.sum(jnp.mean(first, axis=0) * jnp.mean(probs, axis=0))
    routed = jnp.sum(dispatch, axis=(1, 2))  # [T] surviving routes per token
    stats = {"dropped_frac": jnp.mean((routed == 0.0).astype(jnp.float32))}
    return dispatch, combine, aux, stats


def top1_dispatch(router_logits: jax.Array, capacity: int):
    """Switch-style top-1 routing (back-compat wrapper over
    ``topk_dispatch``): returns (dispatch, combine, aux_loss)."""
    dispatch, combine, aux, _ = topk_dispatch(router_logits, capacity, k=1)
    return dispatch, combine, aux


class MoEMLP(nn.Module):
    """Switch-style MoE replacement for the dense transformer MLP.

    Attributes mirror TransformerConfig: ``n_experts`` global experts with
    hidden width ``mlp_dim``; ``ep_size``/``expert_axis`` enable expert
    parallelism over a mesh axis (weights locally ``[E/ep, ...]`` under
    shard_map, globally ``[E, ...]``).
    """

    n_experts: int
    mlp_dim: int
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    top_k: int = 1
    ep_size: int = 1
    expert_axis: Optional[str] = None
    tp_size: int = 1
    model_axis: Optional[str] = None
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, l, d = x.shape
        t = b * l
        e = self.n_experts
        e_local = e // self.ep_size
        f_local = self.mlp_dim // self.tp_size
        if self.mlp_dim % self.tp_size:
            raise ValueError(
                f"mlp_dim {self.mlp_dim} not divisible by tp_size "
                f"{self.tp_size}"
            )
        x_flat = x.reshape(t, d)

        router = nn.Dense(e, use_bias=False, dtype=jnp.float32, name="router")
        logits = router(x_flat.astype(jnp.float32))
        capacity = max(math.ceil(self.capacity_factor * self.top_k * t / e), 1)
        dispatch, combine, aux, stats = topk_dispatch(
            logits, capacity, k=self.top_k
        )
        self.sow("aux_loss", "moe", self.aux_loss_weight * aux)
        # Observability: capacity drops are otherwise silent (a dropped
        # token's block output is just the residual). The LM step reports
        # the mean over layers/shards as metrics["moe_dropped_frac"].
        self.sow("moe_stats", "dropped_frac", stats["dropped_frac"])

        # Parameters keep GLOBAL shapes (placement shards them: expert dim
        # over the data axis for EP, hidden dim over the model axis for
        # TP); under shard_map flax sees the LOCAL slices.
        w_up = self.param(
            "w_up",
            nn.initializers.variance_scaling(2.0, "fan_in", "truncated_normal"),
            (e_local, d, f_local),
        )
        w_down = self.param(
            "w_down",
            nn.initializers.variance_scaling(2.0, "fan_in", "truncated_normal"),
            (e_local, f_local, d),
        )

        # [T, E, C] × [T, D] → per-expert buffers [E, C, D]
        expert_in = jnp.einsum(
            "tec,td->ecd", dispatch.astype(self.dtype), x_flat.astype(self.dtype)
        )

        if self.expert_axis and self.ep_size > 1:
            # Ship each expert's buffer to its owner: [E, C, D] →
            # [ep, E_local, C, D], exchange over the axis, gather the ep
            # source chunks along capacity.
            xe = expert_in.reshape(self.ep_size, e_local, capacity, d)
            xe = jax.lax.all_to_all(
                xe, self.expert_axis, split_axis=0, concat_axis=0, tiled=False
            )  # [ep(src), E_local, C, D]
            xe = jnp.moveaxis(xe, 0, 1).reshape(e_local, self.ep_size * capacity, d)
        else:
            xe = expert_in  # [E(=E_local), C, D]

        # Megatron split inside each expert: w_up column-parallel (local
        # hidden slice), w_down row-parallel — the partial outputs sum over
        # the model axis with ONE psum. The f/g custom-VJP pair keeps the
        # backward exact: tp_copy (identity fwd, psum bwd) guards the
        # replicated input of the column-parallel matmul, tp_reduce (psum
        # fwd, identity bwd) combines the row-parallel partials.
        if self.model_axis and self.tp_size > 1:
            from pytorch_distributed_tpu.parallel.tensor import tp_copy

            xe = tp_copy(xe, self.model_axis)
        h = jnp.einsum("ecd,edf->ecf", xe, w_up.astype(self.dtype))
        h = nn.gelu(h)
        ye = jnp.einsum("ecf,efd->ecd", h, w_down.astype(self.dtype))
        if self.model_axis and self.tp_size > 1:
            from pytorch_distributed_tpu.parallel.tensor import tp_reduce

            ye = tp_reduce(ye, self.model_axis)

        if self.expert_axis and self.ep_size > 1:
            ye = ye.reshape(e_local, self.ep_size, capacity, d)
            ye = jnp.moveaxis(ye, 1, 0)  # [ep(src), E_local, C, D]
            ye = jax.lax.all_to_all(
                ye, self.expert_axis, split_axis=0, concat_axis=0, tiled=False
            )  # back at the token owner: [ep(dest), E_local, C, D]
            ye = ye.reshape(e, capacity, d)

        out = jnp.einsum("tec,ecd->td", combine.astype(self.dtype), ye)
        return out.reshape(b, l, d)


class DroplessMoE(nn.Module):
    """Top-1 expert layer that drops nothing (``TransformerConfig.
    moe_kind="dropless"``): SwiGLU experts of ``moe_dim`` features, the
    program's live tokens sorted by expert, the group sizes taken, and the
    experts' matrices run as grouped products (``jax.lax.ragged_dot``: on
    a TPU XLA's own grouped-matmul kernel, which reads an expert's matrix
    only if a token went to it). Rows that are not ``live`` (a chunk's
    padding, an inactive decode lane) sort behind every group and join
    none.

    The router is a small MLP on a ``router_dim``-wide projection of the
    token that ADDS the previous layer's router state (``router_mix``
    times it): a second stream carried from block to block beside ``x``.
    It runs in float32 at the
    HIGHEST matrix precision whatever the compute dtype (on a TPU a
    float32 product is otherwise one bfloat16 pass): a top-1 choice
    between near-equal logits is where a rounding shows, and the router's
    matrices are a three-hundredth of a layer's arithmetic.
    ``router_bias`` enters the choice and not the weight.

    Returns ``(out, router_state)``; sows the tokens each expert took
    (``[n_experts]`` int32) as ``moe_stats/expert_tokens``.
    """

    n_experts: int
    moe_dim: int
    router_dim: int
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, router_state=None, live=None):
        b, l, d = x.shape
        t, e, f = b * l, self.n_experts, self.moe_dim
        f32 = jnp.float32
        xf = x.reshape(t, d)

        def dense(width, name):
            return nn.Dense(width, use_bias=False, dtype=f32, name=name,
                            precision=jax.lax.Precision.HIGHEST)

        state = dense(self.router_dim, "router_down")(xf.astype(f32))
        # in every layer's tree, the first's too (which adds nothing: the
        # state before the first layer is zero)
        mix = self.param("router_mix", nn.initializers.ones, (1,))
        if router_state is not None:
            state = state + mix.astype(f32) * router_state.reshape(
                t, self.router_dim)
        z = nn.RMSNorm(epsilon=self.norm_eps, dtype=f32,
                       name="router_norm")(state)
        for i in (1, 2):
            z = nn.gelu(dense(self.router_dim, f"router_w{i}")(z))
        z = dense(e, "router_w3")(z)
        probs = jax.nn.softmax(z, axis=-1)
        bias = self.param("router_bias", nn.initializers.zeros, (e,))
        choice = jnp.argmax(probs + bias.astype(f32), axis=-1)
        gate = jnp.take_along_axis(probs, choice[:, None], axis=1)[:, 0]
        if live is not None:
            # a dead row's expert is one past the last: it sorts behind
            # every group and its gate is zero
            choice = jnp.where(live.reshape(t), choice, e)
            gate = jnp.where(live.reshape(t), gate, 0.0)
        sizes = jnp.sum(choice[:, None] == jnp.arange(e)[None, :], axis=0,
                        dtype=jnp.int32)
        self.sow("moe_stats", "expert_tokens", sizes)
        order = jnp.argsort(choice)  # stable: arrival order inside a group
        xs = xf.astype(self.dtype)[order]
        init = nn.initializers.variance_scaling(1.0, "fan_in", "normal",
                                                in_axis=1, out_axis=2,
                                                batch_axis=0)
        # gate and up side by side: one grouped product for both
        w_in = self.param("w_gate_up", init, (e, d, 2 * f))
        w_down = self.param("w_down", init, (e, f, d))
        gu = jax.lax.ragged_dot(xs, w_in.astype(self.dtype), sizes)
        hidden = nn.silu(gu[:, :f]) * gu[:, f:]
        ys = jax.lax.ragged_dot(hidden, w_down.astype(self.dtype), sizes)
        out = jnp.zeros_like(ys).at[order].set(ys)
        out = (out.astype(f32) * gate[:, None]).astype(self.dtype)
        if live is not None:  # what a grouped product leaves in a row of
            # no group is not promised to be a number
            out = jnp.where(live.reshape(t, 1), out, jnp.zeros((), out.dtype))
        return out.reshape(b, l, d), state.reshape(b, l, self.router_dim)
