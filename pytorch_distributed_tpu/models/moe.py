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


#: widths of the tiles XLA's grouped-product kernel takes on a TPU: a
#: ``ragged_dot`` whose contracted and output widths are whole tiles of 512
#: runs them 512 x 512 a grid step, any other width 128 x 128: sixteen times
#: the steps, and a step costs its fixed half microsecond whatever it moves
#: (64 experts of 2,688 x 1,856: 9.6 ms a product where its bytes take 0.8,
#: PERF.md section 6, PR 44). The two-matrix stacks are still HELD in these
#: tiles though the chip's product is ``ops.grouped_matmul`` since PR 45,
#: which takes any width: dropping the zeros changes the held shapes
GROUPED_TILE = 512


def grouped_width(width: int) -> int:
    """The width a two-matrix expert stack is HELD at: ``width`` rounded up
    to whole ``GROUPED_TILE``s, zeros beyond it; a width under one tile (a
    toy's) stays as it is."""
    if width < GROUPED_TILE:
        return width
    return -(-width // GROUPED_TILE) * GROUPED_TILE


def grouped_rows(tokens: int, top_k: int) -> int:
    """The row tile the grouped products of a program of ``tokens`` rows,
    ``top_k`` pairs a row, compile with, and so WHICH product they are: the
    one place it is decided, from the backend alone. On a TPU
    ``ops.grouped_matmul``'s (``row_tile`` of the pair rows: 128, or all of
    them where there are fewer); 0 on every other backend, which keeps
    ``jax.lax.ragged_dot`` (the kernel would run in the Pallas interpreter
    there)."""
    if jax.default_backend() != "tpu":
        return 0
    from pytorch_distributed_tpu.ops.grouped_matmul import row_tile

    return row_tile(tokens * top_k)


def program_grouped_rows(config, tokens: int) -> int:
    """``grouped_rows`` of the ``DroplessMoE`` layers a ``TransformerConfig``
    builds, in a program of ``tokens`` rows; 0 where it builds none.
    ``PagedEngine`` puts the tick's answer on ``pool.alloc``'s span."""
    if not config.n_experts or config.moe_kind != "dropless":
        return 0
    # the MLP router is top-1 and takes no ``top_k``
    return grouped_rows(
        tokens, 1 if config.moe_router == "mlp" else config.moe_top_k)


def _zero_padded(init, shape):
    """``init`` drawn at ``shape`` and laid into the corner of an array of
    zeros: ``(rng, held shape, dtype) -> array``."""
    def padded(rng, held, dtype=jnp.float32):
        return jnp.pad(init(rng, shape, dtype),
                       [(0, h - s) for h, s in zip(held, shape)])
    return padded


class DroplessMoE(nn.Module):
    """Expert layer that drops nothing (``TransformerConfig.
    moe_kind="dropless"``): SwiGLU experts of ``moe_dim`` features (or,
    with ``relu2``, experts of TWO matrices, ``relu(x W_up)^2 W_down``, the
    shared expert alike: ``TransformerConfig.mlp="relu2"``; their stacks
    are held ``[experts, grouped_width(d), grouped_width(moe_dim)]`` and its
    transpose, zero beyond the model's own widths), the
    program's live (token, expert) PAIRS sorted by expert, the group sizes
    taken, and the experts' matrices run as grouped products
    (``grouped_rows``: on a TPU ``ops.grouped_matmul``, a kernel whose row
    tile fits a group, elsewhere ``jax.lax.ragged_dot``; either reads an
    expert's matrix only if a pair went to it and promises nothing of a row
    that joined no group). Each live
    token contributes ``top_k`` pairs; the pairs of rows that are not
    ``live`` (a chunk's padding, an inactive decode lane), and the pairs
    whose expert this shard does not hold, sort behind every group and join
    none. A token's output is the weighted sum of its pairs' rows.

    Three routers, all in float32 at the HIGHEST matrix precision whatever
    the compute dtype (on a TPU a float32 product is otherwise one bfloat16
    pass: a choice between near-equal scores is where a rounding shows,
    and a router is a few hundredths of a layer's arithmetic), the first
    two with a ``router_bias`` that enters the choice and not the weight:

    ``router="mlp"`` (top-1): a small MLP on a ``router_dim``-wide
    projection of the token that ADDS the previous layer's router state
    (``router_mix`` times it), a second stream carried from block to block
    beside ``x``; softmax probabilities, the chosen one the weight.

    ``router="sigmoid"`` (``top_k`` >= 1): one matrix and a sigmoid, a score
    an expert; the ``n_experts`` in ``n_group`` groups, a group scored by
    the sum of its two best, the ``topk_group`` best groups open, the
    ``top_k`` best experts inside them; weights ``routed_scale * s_i /
    sum_sel s_j``.

    ``router="softmax"`` (``top_k`` >= 1): one matrix and a softmax over
    all ``n_experts``, the ``top_k`` most probable, weights ``p_i / sum_sel
    p_j``; no groups, no bias, no scale.

    Behind either one-matrix router: ``held`` ``(lo, hi)``: this shard
    holds experts ``[lo, hi)`` of the ``n_experts`` it scores, its matrices
    are theirs alone, and the output is ITS part of the routed sum; nothing
    stands in for the shards that hold the rest. ``shared_dim``: a shared
    expert of that many features, added for every token, scaled by a scalar
    ``sigmoid(x w)`` a token where ``shared_gate``.

    Returns ``(out, router_state)`` (the state None behind a one-matrix
    router); sows the pairs each held expert took (``[experts held]``
    int32) as ``moe_stats/expert_tokens``.
    """

    n_experts: int
    moe_dim: int
    router_dim: Optional[int] = None
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32
    router: str = "mlp"
    top_k: int = 1
    n_group: int = 1
    topk_group: int = 1
    routed_scale: float = 1.0
    shared_dim: Optional[int] = None
    held: Optional[tuple] = None
    shared_gate: bool = False
    relu2: bool = False

    @nn.nowrap
    def _f32_dense(self, width, name):
        return nn.Dense(width, use_bias=False, dtype=jnp.float32, name=name,
                        precision=jax.lax.Precision.HIGHEST)

    @nn.nowrap
    def _route_mlp(self, xf, router_state):
        """(chosen expert [T], its probability [T], router state)."""
        t, e, f32 = xf.shape[0], self.n_experts, jnp.float32
        state = self._f32_dense(self.router_dim, "router_down")(
            xf.astype(f32))
        # in every layer's tree, the first's too (which adds nothing: the
        # state before the first layer is zero)
        mix = self.param("router_mix", nn.initializers.ones, (1,))
        if router_state is not None:
            state = state + mix.astype(f32) * router_state.reshape(
                t, self.router_dim)
        z = nn.RMSNorm(epsilon=self.norm_eps, dtype=f32,
                       name="router_norm")(state)
        for i in (1, 2):
            z = nn.gelu(self._f32_dense(self.router_dim, f"router_w{i}")(z))
        z = self._f32_dense(e, "router_w3")(z)
        probs = jax.nn.softmax(z, axis=-1)
        bias = self.param("router_bias", nn.initializers.zeros, (e,))
        choice = jnp.argmax(probs + bias.astype(f32), axis=-1)
        gate = jnp.take_along_axis(probs, choice[:, None], axis=1)[:, 0]
        return choice, gate, state

    @nn.nowrap
    def _route_sigmoid(self, xf):
        """(chosen experts [T, k], their weights [T, k])."""
        e, g, f32 = self.n_experts, self.n_group, jnp.float32
        scores = jax.nn.sigmoid(self._f32_dense(e, "router")(xf.astype(f32)))
        bias = self.param("router_bias", nn.initializers.zeros, (e,))
        sel = scores + bias.astype(f32)
        if g > 1:
            best2 = jax.lax.top_k(sel.reshape(-1, g, e // g), 2)[0]
            kept = jax.lax.top_k(jnp.sum(best2, -1), self.topk_group)[1]
            open_ = jnp.any(kept[..., None] == jnp.arange(g), axis=-2)
            sel = jnp.where(jnp.repeat(open_, e // g, axis=-1), sel,
                            -jnp.inf)
        choice = jax.lax.top_k(sel, self.top_k)[1]
        w = jnp.take_along_axis(scores, choice, axis=-1)
        return choice, self.routed_scale * w / jnp.sum(w, -1, keepdims=True)

    @nn.nowrap
    def _route_softmax(self, xf):
        """(chosen experts [T, k], their weights [T, k])."""
        probs = jax.nn.softmax(self._f32_dense(self.n_experts, "router")(
            xf.astype(jnp.float32)), axis=-1)
        w, choice = jax.lax.top_k(probs, self.top_k)
        return choice, w / jnp.sum(w, -1, keepdims=True)

    @nn.compact
    def __call__(self, x, router_state=None, live=None):
        b, l, d = x.shape
        t, f, k = b * l, self.moe_dim, self.top_k
        f32 = jnp.float32
        xf = x.reshape(t, d)
        lo, hi = self.held or (0, self.n_experts)
        e = hi - lo  # the experts whose matrices are here
        state = None
        # a pair that joins no group has its expert one past the last: it
        # sorts behind every group and its weight is zero
        if self.router == "mlp":
            choice, gate, state = self._route_mlp(xf, router_state)
            if live is not None:
                choice = jnp.where(live.reshape(t), choice, e)
                gate = jnp.where(live.reshape(t), gate, 0.0)
        else:
            choice, gate = (self._route_sigmoid(xf)
                            if self.router == "sigmoid"
                            else self._route_softmax(xf))
            choice, gate = choice.reshape(t * k) - lo, gate.reshape(t * k)
            joins = (choice >= 0) & (choice < e)
            if live is not None:
                joins = joins & jnp.repeat(live.reshape(t), k)
            choice = jnp.where(joins, choice, e)
            gate = jnp.where(joins, gate, 0.0)
        sizes = jnp.sum(choice[:, None] == jnp.arange(e)[None, :], axis=0,
                        dtype=jnp.int32)
        self.sow("moe_stats", "expert_tokens", sizes)
        order = jnp.argsort(choice)  # stable: arrival order inside a group
        if grouped_rows(t, k):
            from pytorch_distributed_tpu.ops.grouped_matmul import (
                grouped_matmul as product,
            )
        else:
            product = jax.lax.ragged_dot
        # a pair's token (top-1: the pair is the token)
        xs = xf.astype(self.dtype)[order if k == 1 else order // k]
        init = nn.initializers.variance_scaling(1.0, "fan_in", "normal",
                                                in_axis=1, out_axis=2,
                                                batch_axis=0)
        if self.relu2:
            # two matrices an expert, no gate; both stacks held in whole
            # tiles of the grouped product (``grouped_width``: zeros beyond
            # d and f, which neither product sees: the padded columns of
            # ``xs`` are zero, relu(0)^2 is zero, and the padded outputs
            # are cut)
            dh, fh = grouped_width(d), grouped_width(f)
            w_in = self.param("w_up", _zero_padded(init, (e, d, f)),
                              (e, dh, fh))
            w_down = self.param("w_down", _zero_padded(init, (e, f, d)),
                                (e, fh, dh))
            hidden = jnp.square(nn.relu(product(
                jnp.pad(xs, ((0, 0), (0, dh - d))), w_in.astype(self.dtype),
                sizes)))
            ys = product(hidden, w_down.astype(self.dtype), sizes)[:, :d]
        else:
            # gate and up side by side: one grouped product for both
            w_in = self.param("w_gate_up", init, (e, d, 2 * f))
            w_down = self.param("w_down", init, (e, f, d))
            gu = product(xs, w_in.astype(self.dtype), sizes)
            hidden = nn.silu(gu[:, :f]) * gu[:, f:]
            ys = product(hidden, w_down.astype(self.dtype), sizes)
        out = jnp.zeros_like(ys).at[order].set(ys)
        # what a grouped product leaves in a row of no group is not promised
        # to be a number (the chip's kernel never writes it), and a zero
        # weight does not make it one: every path SELECTS such rows away.
        # Behind the MLP router the rows of no group are exactly the rows
        # that are not ``live`` (every expert is held), which the last
        # statement of this method selects to zero
        out = out.astype(f32) * gate[:, None]
        if self.router != "mlp":
            # then a token's pairs add up
            out = jnp.where(joins[:, None], out, 0.0)
            out = jnp.sum(out.reshape(t, k, d), axis=1)
        out = out.astype(self.dtype)
        if self.shared_dim is not None:
            sf = self.shared_dim
            if self.relu2:
                hidden = jnp.square(nn.relu(nn.Dense(
                    sf, use_bias=False, dtype=self.dtype,
                    name="shared_up")(xf)))
            else:
                gu = nn.Dense(2 * sf, use_bias=False, dtype=self.dtype,
                              name="shared_gate_up")(xf)
                hidden = nn.silu(gu[:, :sf]) * gu[:, sf:]
            shared = nn.Dense(d, use_bias=False, dtype=self.dtype,
                              name="shared_down")(hidden)
            if self.shared_gate:
                shared = (shared.astype(f32) * jax.nn.sigmoid(nn.Dense(
                    1, use_bias=False, dtype=self.dtype,
                    name="shared_gate")(xf).astype(f32))).astype(self.dtype)
            out = out + shared
        if live is not None:
            out = jnp.where(live.reshape(t, 1), out, jnp.zeros((), out.dtype))
        return out.reshape(b, l, d), (
            None if state is None else state.reshape(b, l, self.router_dim))
