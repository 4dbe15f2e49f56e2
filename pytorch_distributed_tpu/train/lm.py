"""Compiled LM training step over a (data, seq) mesh.

The image trainer's step (``train/step.py``) parallelizes over ``data``
only; language-model training adds the ``seq`` axis: the token sequence is
split across devices, attention goes global through the ring
(``parallel.sequence``), and gradients are combined over BOTH axes — every
device holds a full replica of the parameters, sharded activations only.
This is the long-context training configuration the reference cannot
express (SURVEY.md §2c: SP/CP absent).

Layout:
  tokens/labels  [B, L] → P(data, seq)    (labels are next-token targets,
                                           shifted on the host so the
                                           shard-boundary token's target
                                           lives with its logits)
  params/opt     replicated               (pure DP+SP; TP is the mesh's
                                           third axis, unused here)
  grad combine   psum over (data, seq) of each device's share of the
                 global-mean loss gradient
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from pytorch_distributed_tpu.ops.fused_ce import fused_linear_cross_entropy
from pytorch_distributed_tpu.ops.losses import cross_entropy_loss
from pytorch_distributed_tpu.ops.optim import (
    clip_grads_by_global_norm,
    spec_axes,
)
from pytorch_distributed_tpu.parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    SEQ_AXIS,
    shard_map,
)
from pytorch_distributed_tpu.resilience.stepguard import finite_ok, guard_state
from pytorch_distributed_tpu.train.state import TrainState


def shift_labels(tokens, pad_id: int = 0):
    """Host-side next-token targets: labels[t] = tokens[t+1]; the final
    position predicts ``pad_id`` and is masked by ``weights``."""
    import numpy as np

    labels = np.concatenate(
        [tokens[:, 1:], np.full((tokens.shape[0], 1), pad_id, tokens.dtype)], axis=1
    )
    weights = np.ones_like(tokens, np.float32)
    weights[:, -1] = 0.0
    return labels, weights


def create_lm_state(
    config,
    tx,
    rng: jax.Array,
    init_len: Optional[int] = None,
) -> TrainState:
    """TrainState for a TransformerLM.

    Parameters are initialized through a dense-attention twin of the config
    (identical parameter tree; ring attention needs a mesh axis context that
    does not exist at init time), then the state's ``apply_fn`` is the real
    configured model.
    """
    import dataclasses

    from pytorch_distributed_tpu.models.transformer import TransformerLM

    # Init twin: dense attention (ring needs a mesh axis context that does
    # not exist at init) and no TP collectives. Parameter shapes are global
    # either way, so the produced tree serves every parallel layout.
    dense_cfg = dataclasses.replace(
        config, attention="dense", model_axis=None, tp_size=1,
        expert_axis=None, ep_size=1, ring_layout="contiguous",
    )
    init_model = TransformerLM(dense_cfg)
    state = TrainState.create(
        init_model,
        tx,
        rng,
        (1, init_len or min(config.max_seq_len, 128)),
        input_dtype=jnp.int32,
    )
    return state.replace(apply_fn=TransformerLM(config).apply)


# Megatron-style placement for TransformerLM parameters (paths from the flax
# module tree). Column-parallel layers shard their output dim, row-parallel
# their input dim; layernorms and wpe stay replicated. wte and lm_head stay
# replicated by DEFAULT; ``config.vocab_parallel`` shards their vocab dim
# (``_vocab_rules`` — conditional, like the MoE placements).
TRANSFORMER_TP_RULES = (
    (r"attn/qkv/kernel", P(None, None, MODEL_AXIS, None)),  # [E,3,H,D] → H
    (r"attn/qkv/bias", P(None, MODEL_AXIS, None)),  # [3,H,D] → H
    # GQA's split projections (models/transformer.py num_kv_heads)
    (r"attn/q/kernel", P(None, MODEL_AXIS, None)),  # [E,H,D] → H
    (r"attn/q/bias", P(MODEL_AXIS, None)),  # [H,D]
    (r"attn/kv/kernel", P(None, None, MODEL_AXIS, None)),  # [E,2,Hkv,D]
    (r"attn/kv/bias", P(None, MODEL_AXIS, None)),  # [2,Hkv,D]
    (r"attn/proj/kernel", P(MODEL_AXIS, None, None)),  # [H,D,E] → H
    (r"mlp_up/kernel", P(None, MODEL_AXIS)),  # [E,4E] → 4E
    (r"mlp_up/bias", P(MODEL_AXIS,)),  # [4E]
    # the gated MLP's second column-parallel input projection (mlp="swiglu")
    (r"mlp_gate/kernel", P(None, MODEL_AXIS)),
    (r"mlp_gate/bias", P(MODEL_AXIS,)),
    (r"mlp_down/kernel", P(MODEL_AXIS, None)),  # [4E,E] → 4E
)

# MoE expert weights shard on TWO independent axes (models/moe.py): the
# expert dim over the DATA axis (GShard expert parallelism, when
# ep_size == data-axis size) and the expert HIDDEN dim over the MODEL axis
# (Megatron split inside each expert, when tp_size > 1). Rules are built
# per-config in lm_state_specs since both placements are conditional.


def _moe_rules(config):
    ep = (
        config.expert_axis
        if config.expert_axis is not None and config.ep_size > 1
        else None
    )
    tp = (
        config.model_axis
        if config.model_axis is not None and config.tp_size > 1
        else None
    )
    return (
        (r"moe/w_up", P(ep, None, tp)),  # [E, D, F]
        (r"moe/w_down", P(ep, tp, None)),  # [E, F, D]
    )


def _vocab_rules(config):
    """Vocab-parallel placements (config.vocab_parallel): wte shards its
    vocab rows, lm_head its vocab columns, both over the model axis."""
    tp = (
        config.model_axis
        if config.model_axis is not None and config.tp_size > 1
        else None
    )
    return (
        (r"wte/embedding", P(tp, None)),  # [V, E] → V
        (r"lm_head/kernel", P(None, tp)),  # [E, V] → V
    )


def _uses_vocab_parallel(config) -> bool:
    """Delegates to ``TransformerConfig.uses_vocab_parallel`` — the ONE
    predicate the model's head/embedding branch also consults, so the
    placement rules here and the collective branch in
    ``models/transformer.py`` cannot diverge (ADVICE r5 #3). The inline
    fallback covers duck-typed test configs without the method."""
    if config is None:
        return False
    fn = getattr(config, "uses_vocab_parallel", None)
    if fn is not None:
        return bool(fn())
    return (
        getattr(config, "vocab_parallel", False)
        and config.model_axis is not None
        and config.tp_size > 1
    )


def _has_moe_params(params) -> bool:
    from pytorch_distributed_tpu.parallel.tensor import path_str

    return any(
        "moe/w_" in path_str(p)
        for p, _ in jax.tree_util.tree_flatten_with_path(params)[0]
    )


def lm_state_specs(state: TrainState, rules=None, config=None) -> TrainState:
    """PartitionSpec pytree shaped like ``state``: params by the TP (and,
    when the config runs expert-parallel, EP) rules, optimizer state
    following its embedded parameter copies, everything else replicated.

    ``config`` (the TransformerConfig) is required when the params contain
    MoE experts — whether they shard over the data axis depends on its
    ``ep_size``, which the parameter tree alone cannot reveal.
    """
    from pytorch_distributed_tpu.parallel.tensor import (
        match_partition_rules,
        opt_state_specs,
    )

    if rules is None:
        rules = TRANSFORMER_TP_RULES
        if _has_moe_params(state.params):
            if config is None:
                raise ValueError(
                    "state contains MoE expert weights; pass the "
                    "TransformerConfig so their placement (ep_size/"
                    "expert_axis/tp_size) is known"
                )
            rules = rules + _moe_rules(config)
        if _uses_vocab_parallel(config):
            rules = rules + _vocab_rules(config)
    param_specs = match_partition_rules(rules, state.params)
    return state.replace(
        step=P(),
        params=param_specs,
        batch_stats=jax.tree.map(lambda _: P(), state.batch_stats),
        opt_state=opt_state_specs(state.params, param_specs, state.tx),
        scaler=jax.tree.map(lambda _: P(), state.scaler),
    )


def shard_lm_state(
    mesh: Mesh, state: TrainState, config=None, fsdp: bool = False
) -> Tuple[TrainState, TrainState]:
    """Place a (host or replicated) state onto the mesh per the TP/EP rules.

    Returns (placed_state, spec_state). For tp=1 meshes the specs shard
    nothing (every spec axis has size 1) and this is plain replication.
    ``config`` is required for MoE models (see ``lm_state_specs``) and is
    validated against the mesh: expert parallelism must span exactly the
    data axis, and a seq-sharded mesh requires ring attention.

    ``fsdp=True`` additionally ZeRO-shards the leaves the TP/EP rules
    leave REPLICATED over the data axis (storage only — the train step
    all_gathers them before the forward and reduce-scatters their grads;
    ``parallel.fsdp``). TP/EP placements are untouched, so FSDP composes
    with every other axis.
    """
    if config is not None:
        check_seq_parallel_attention(mesh, config)
    if config is not None and config.ep_size > 1:
        if config.expert_axis != DATA_AXIS:
            raise ValueError(
                f"expert_axis must be {DATA_AXIS!r} (the EP placement rule "
                f"shards experts over it), got {config.expert_axis!r}"
            )
        if config.ep_size != mesh.shape[DATA_AXIS]:
            raise ValueError(
                f"ep_size {config.ep_size} must equal the mesh's data axis "
                f"size {mesh.shape[DATA_AXIS]} (experts shard over the full "
                "data axis)"
            )
    from pytorch_distributed_tpu.parallel.mesh import specs_to_shardings

    specs = lm_state_specs(state, config=config)
    if fsdp:
        specs = _overlay_fsdp_specs(specs, state, mesh, config)
    return jax.device_put(state, specs_to_shardings(mesh, specs)), specs


def _lm_placement_rules(tree, config):
    """The TP(+EP) rule set for a params-shaped tree (paths only); MoE
    trees require the config so EP's data-axis expert shards are
    distinguishable from FSDP storage shards."""
    rules = TRANSFORMER_TP_RULES
    if _has_moe_params(tree):
        if config is None:
            raise ValueError(
                "FSDP over a MoE state needs the TransformerConfig — "
                "without it EP's data-axis expert shards are "
                "indistinguishable from FSDP storage shards"
            )
        rules = rules + _moe_rules(config)
    if _uses_vocab_parallel(config):
        rules = rules + _vocab_rules(config)
    return rules


def _rule_claimed(name: str, rules, mesh: Mesh) -> bool:
    """True if a TP/EP rule EFFECTIVELY claims this path: a matched rule
    whose every named mesh axis has size 1 shards nothing (tp=1 meshes —
    the Megatron specs are vacuous there, so the block matrices, most of
    the model, correctly fall through to ZeRO). The ONE shared claim
    test for the overlay and the step."""
    import re

    for pattern, spec in rules:
        if re.search(pattern, name):
            return any(mesh.shape.get(a, 1) > 1 for a in spec_axes(spec))
    return False


def lm_fsdp_membership(params, mesh: Mesh, config=None,
                       data_axis: str = DATA_AXIS):
    """Boolean params-shaped tree: which leaves the FSDP overlay shards —
    big enough for ``fsdp_dim`` and not effectively rule-claimed.
    ``params`` must carry GLOBAL shapes (use outside shard_map; local
    tracer shapes would misapply the min-shard threshold)."""
    from pytorch_distributed_tpu.parallel.fsdp import fsdp_dim
    from pytorch_distributed_tpu.parallel.tensor import path_str

    rules = _lm_placement_rules(params, config)
    data_size = mesh.shape[data_axis]

    def member(path, leaf):
        shape = getattr(leaf, "shape", ())
        if fsdp_dim(shape, data_size) is None:
            return False  # tiny / indivisible: replicate
        return not _rule_claimed(path_str(path), rules, mesh)

    return jax.tree_util.tree_map_with_path(member, params)


def _fsdp_gather_tree(specs_params, mesh: Mesh, config=None,
                      data_axis: str = DATA_AXIS):
    """Step-side gather mask, derived from the overlay's OUTPUT (the
    storage spec tree) so it cannot diverge from the storage decision:
    a leaf is gathered iff its storage spec names the data axis and no
    rule effectively claims it (EP expert shards also name data — the
    shared ``_rule_claimed`` excludes them)."""
    from pytorch_distributed_tpu.parallel.tensor import path_str

    rules = _lm_placement_rules(specs_params, config)

    def is_gather(path, storage):
        if data_axis not in spec_axes(storage):
            return False
        return not _rule_claimed(path_str(path), rules, mesh)

    return jax.tree_util.tree_map_with_path(is_gather, specs_params)


def _overlay_fsdp_specs(specs: TrainState, state: TrainState, mesh: Mesh,
                        config=None) -> TrainState:
    """ZeRO overlay: every ``lm_fsdp_membership`` leaf gets the FSDP
    data-axis placement (largest divisible dim); opt-state follows.
    Rule-claimed leaves keep their compute placement."""
    from pytorch_distributed_tpu.parallel.fsdp import fsdp_param_specs
    from pytorch_distributed_tpu.parallel.tensor import opt_state_specs

    fsdp_specs = fsdp_param_specs(state.params, mesh, DATA_AXIS)
    members = lm_fsdp_membership(state.params, mesh, config)
    param_specs = jax.tree.map(
        lambda tp_spec, fs_spec, m: fs_spec if m else tp_spec,
        specs.params, fsdp_specs, members,
    )
    return specs.replace(
        params=param_specs,
        opt_state=opt_state_specs(state.params, param_specs, state.tx),
    )


def _shard_positions(config, lq: int, seq_axis: str):
    """This shard's ABSOLUTE token positions: ``(positions, offset)``.

    Contiguous layout: ``positions=None`` and the scalar shard offset (the
    convention every attention path accepts). Zigzag: a [lq] position
    VECTOR following the chunk-pair map (shard r holds chunks
    (r, 2s-1-r) of the 2s-chunk decomposition) and offset 0 — wpe must
    embed the true absolute positions even though the shard's tokens are
    not contiguous."""
    if (
        config is not None
        and getattr(config, "ring_layout", "contiguous") == "zigzag"
    ):
        c = lq // 2
        r = jax.lax.axis_index(seq_axis)
        s = jax.lax.psum(1, seq_axis)
        positions = jnp.concatenate([
            r * c + jnp.arange(c), (2 * s - 1 - r) * c + jnp.arange(c)
        ])
        return positions, 0
    return None, jax.lax.axis_index(seq_axis) * lq


def check_seq_parallel_attention(mesh: Mesh, config, seq_axis: str = SEQ_AXIS):
    """Refuse silently-wrong sequence parallelism.

    Under a seq-sharded shard_map, dense/blockwise/flash attention computes
    shard-LOCAL attention — each shard only attends to its own tokens — and
    trains on wrong math without any error. Only the ring variants go
    global. Raise up front instead of producing a subtly broken model.
    """
    if (
        seq_axis in mesh.shape
        and mesh.shape[seq_axis] > 1
        and getattr(config, "attention", None) not in ("ring", "ring_flash")
    ):
        raise ValueError(
            f"mesh shards the sequence axis {seq_axis!r} "
            f"(size {mesh.shape[seq_axis]}) but config.attention="
            f"{getattr(config, 'attention', None)!r}: non-ring attention is "
            "shard-local under sequence parallelism and computes the wrong "
            "function. Use attention='ring'/'ring_flash' (or a seq-axis "
            "size of 1)."
        )


def _lm_loss_sum(apply_out, params, batch, config, use_fused, block_n):
    """Weighted CE sum for one step's model output — the ONE loss tail
    both the train and eval steps use. ``apply_out`` is post-ln_f hidden
    states (fused path) or full logits (``use_fused=False``; under
    vocab_parallel the model already all_gathered them)."""
    if use_fused:
        return fused_linear_cross_entropy(
            apply_out,
            params["lm_head"]["kernel"],
            batch["labels"],
            batch["weights"],
            block_n=block_n,
            compute_dtype=config.dtype,
            # vocab-parallel head: the kernel leaf here is the LOCAL
            # [E, V/tp] shard; the fused CE combines the streamed softmax
            # stats across shards and psums dx the row-parallel way
            vocab_axis=(
                config.model_axis if _uses_vocab_parallel(config) else None
            ),
        )
    per_tok = cross_entropy_loss(
        apply_out.reshape(-1, apply_out.shape[-1]),
        batch["labels"].reshape(-1),
        reduction="none",
    )
    return jnp.sum(per_tok * batch["weights"].reshape(-1))


def make_lm_train_step(
    mesh: Mesh,
    data_axis: str = DATA_AXIS,
    seq_axis: str = SEQ_AXIS,
    state_specs: Optional[TrainState] = None,
    config=None,
    dropout_seed: int = 0,
    grad_clip_norm: float = 0.0,
    fsdp: bool = False,
    fused_ce: bool = True,
    fused_ce_block_n: int = 512,
    nan_guard: bool = False,
) -> Callable[[TrainState, dict], Tuple[TrainState, dict]]:
    """Build ``step(state, batch) -> (state, metrics)``.

    ``batch``: {"tokens": [B, L] i32, "labels": [B, L] i32,
    "weights": [B, L] f32} as global arrays sharded P(data, seq).
    ``state_specs``: TrainState-shaped PartitionSpec tree (from
    ``lm_state_specs``) when parameters are tensor-parallel; default fully
    replicated. Gradients are psum'd over (data, seq) only — the model-axis
    collectives live inside the model via tp_copy/tp_reduce, which leave
    sharded-param grads local and replicated-param grads already complete.
    ``config`` (the TransformerConfig), when given, is validated against the
    mesh: a seq-sharded mesh requires ring attention
    (``check_seq_parallel_attention``); it also enables dropout rng
    plumbing when ``config.dropout > 0``.

    Dropout rng: derived per step from (``dropout_seed``, ``state.step``,
    this shard's data/seq coordinates) — a resumed run reproduces the exact
    masks of an uninterrupted one, and model-axis replicas (which hold
    replicated activations at every dropout site) share one mask.

    ``fused_ce`` (default, requires ``config``): the loss tail runs
    ``ops.fused_ce.fused_linear_cross_entropy`` — the lm_head matmul is
    streamed blockwise into the logsumexp, so the fp32 ``[B, L, V]``
    logits tensor never exists in HBM (the r4 memory wall at bs8/L4096).
    Numerically it accumulates logits in fp32 where the unfused path
    materialized bf16 — equal-or-better. ``fused_ce=False`` or
    ``config=None`` keeps the materialized-logits path.

    ``nan_guard`` adds the resilience finite gate (resilience.stepguard):
    a non-finite global loss or gradient keeps the pre-step params and
    optimizer state via an on-device ``lax.cond`` select (``step`` still
    advances) and emits the replicated ``step_good`` metric. The verdict
    is ``pmin``'d over EVERY mesh axis: TP/EP-sharded gradient leaves
    legitimately differ across their axes, and a NaN visible to only one
    shard must flip the decision for all of them.
    """
    if config is not None:
        check_seq_parallel_attention(mesh, config, seq_axis)
    use_dropout = config is not None and getattr(config, "dropout", 0.0) > 0.0
    use_fused = fused_ce and config is not None
    axes = (data_axis, seq_axis)
    if fsdp and state_specs is None:
        raise ValueError(
            "fsdp=True needs state_specs (from shard_lm_state(..., "
            "fsdp=True)) — the gather/scatter dims live in the spec tree"
        )
    gather_tree = (
        _fsdp_gather_tree(state_specs.params, mesh, config, data_axis)
        if fsdp else None
    )

    def _local_step(state: TrainState, batch: dict):
        lq = batch["tokens"].shape[1]
        positions, offset = _shard_positions(config, lq, seq_axis)
        # Token count is param-independent, so its psum can live outside the
        # differentiated function. No param-dependent psum may sit inside
        # loss_fn: under shard_map a psum transposes to another psum, which
        # would scale the gradient by the axis size.
        global_count = jax.lax.psum(jnp.sum(batch["weights"]), axes)

        n_shards = jax.lax.psum(1, axes)

        if use_dropout:
            # Same key on every model-axis replica; unique per (step,
            # data, seq) shard.
            key = jax.random.fold_in(
                jax.random.key(dropout_seed), state.step
            )
            shard = jax.lax.axis_index(data_axis) * jax.lax.psum(
                1, seq_axis
            ) + jax.lax.axis_index(seq_axis)
            rngs = {"dropout": jax.random.fold_in(key, shard)}
        else:
            rngs = None

        if gather_tree is not None:
            # ZeRO unshard: all_gather only the FSDP-owned storage shards
            # (TP/EP leaves stay compute-sharded); XLA overlaps the
            # gathers with the forward ops that consume them.
            from pytorch_distributed_tpu.parallel.fsdp import gather_params

            model_params = gather_params(
                state.params, state_specs.params, data_axis,
                mask=gather_tree,
            )
        else:
            model_params = state.params

        def loss_fn(params):
            hidden_or_logits, mutated = state.apply_fn(
                {"params": params},
                batch["tokens"],
                position_offset=offset,
                positions=positions,
                mutable=["aux_loss", "moe_stats"],
                rngs=rngs,
                return_hidden=use_fused,
            )
            loss_sum = _lm_loss_sum(
                hidden_or_logits, params, batch, config, use_fused,
                fused_ce_block_n,
            )
            # This device's share of the global mean loss; sowed auxiliary
            # losses (MoE load balancing, pre-weighted) enter as their
            # across-shards mean.
            local = loss_sum / jnp.maximum(global_count, 1.0)
            for leaf in jax.tree.leaves(mutated.get("aux_loss", {})):
                local = local + leaf / n_shards
            return local, mutated

        # local_loss_i = s_i / C  ⇒  psum(grad local_loss_i) = grad of the
        # global mean loss w.r.t. the replicated params.
        (local_loss, mutated), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(model_params)
        loss = jax.lax.psum(local_loss, axes)
        if state_specs is None:
            grads = jax.lax.psum(grads, axes)
        else:
            # A parameter sharded over some axis (TP over model, EP over
            # data) owns its gradient there; psum only over the axes its
            # spec does NOT shard. FSDP leaves (storage shards, gathered
            # above) take the ZeRO reduce-scatter instead: psum_scatter
            # over data returns exactly the shard this device owns, SUM
            # semantics matching the share-of-global-mean loss convention,
            # then a plain psum over the seq axis completes the combine.
            from pytorch_distributed_tpu.parallel.fsdp import _sharded_dim

            def _reduce(g, spec, is_fsdp=False):
                if is_fsdp:
                    d = _sharded_dim(spec, data_axis)
                    g = jax.lax.psum_scatter(
                        g, data_axis, scatter_dimension=d, tiled=True
                    )
                    return jax.lax.psum(g, seq_axis)
                named = spec_axes(spec)
                ax = tuple(a for a in axes if a not in named)
                return jax.lax.psum(g, ax) if ax else g

            if gather_tree is not None:
                grads = jax.tree.map(
                    _reduce, grads, state_specs.params, gather_tree
                )
            else:
                grads = jax.tree.map(_reduce, grads, state_specs.params)
        count = global_count

        grad_norm = None
        if grad_clip_norm:
            # After the reduction above each leaf's grad is complete for
            # its own shard and replicated elsewhere — exactly the
            # precondition sharded_global_norm expects (it psums square-
            # sums over the axes each spec shards).
            grads, grad_norm = clip_grads_by_global_norm(
                grads, grad_clip_norm,
                state_specs.params if state_specs is not None else None,
            )

        updates, new_opt_state = state.tx.update(grads, state.opt_state, state.params)
        new_params = jax.tree.map(jnp.add, state.params, updates)
        new_state = state.replace(
            step=state.step + 1,
            params=new_params,
            opt_state=new_opt_state,
        )
        metrics = {"loss": loss, "tokens": count}
        if nan_guard:
            # pmin over every mesh axis: TP/EP gradient shards differ per
            # axis, and one shard's NaN must veto the update everywhere —
            # otherwise devices diverge on the select and the state splits
            good = (
                jax.lax.pmin(
                    finite_ok(loss, grads).astype(jnp.int32),
                    tuple(mesh.axis_names),
                )
                > 0
            )
            new_state = guard_state(good, new_state, state)
            metrics["step_good"] = good.astype(jnp.float32)
        if grad_norm is not None:
            metrics["grad_norm"] = grad_norm  # PRE-clip norm observable
        moe_stats = jax.tree.leaves(mutated.get("moe_stats", {}))
        if moe_stats:
            # mean over MoE layers, then over shards: the observable for
            # silent capacity drops (VERDICT r1 weak #6)
            local_frac = sum(moe_stats) / len(moe_stats)
            metrics["moe_dropped_frac"] = jax.lax.pmean(local_frac, axes)
        return new_state, metrics

    state_spec = state_specs if state_specs is not None else P()
    sharded = shard_map(
        _local_step,
        mesh=mesh,
        in_specs=(state_spec, P(data_axis, seq_axis)),
        out_specs=(state_spec, P()),
        check_vma=False,
    )
    # the profiler's module is jit_<__name__>: the registry's name
    sharded.__name__ = "lm_train_step"
    return jax.jit(sharded, donate_argnums=(0,))


def make_lm_eval_step(
    mesh: Mesh,
    data_axis: str = DATA_AXIS,
    seq_axis: str = SEQ_AXIS,
    state_specs: Optional[TrainState] = None,
    config=None,
    fsdp: bool = False,
    fused_ce: bool = True,
    fused_ce_block_n: int = 512,
) -> Callable[[TrainState, dict, dict], dict]:
    """Compiled evaluation step: ``eval_step(state, batch, acc) -> acc``.

    ``acc`` is a device-resident ``{"loss_sum", "tokens"}`` accumulator
    (start it at zeros); perplexity = exp(loss_sum / tokens) on the host
    after the epoch. Forward runs with ``train=False`` (dropout off); the
    per-token loss sum and token count are psum'd over (data, seq) so every
    shard (and host) carries the global totals — the reference's
    reduce-to-0 superset, same as the image eval step.

    MoE configs evaluate with RELAXED capacity (4× the train
    capacity_factor, clamped to n_experts): under tight train-time
    capacity, the routing a token gets depends on which other rows share
    its batch — zero-weight padding rows could displace real tokens'
    routes and make reported perplexity vary with the val-set padding.
    True dropless eval (capacity_factor = n_experts ⇒ capacity = k·T)
    would make the one-hot [T, E, C] dispatch tensors quadratic in local
    token count — terabytes at recipe defaults — so the bound is a modest
    multiple instead: at 4× the expected per-expert load, displacement of
    a real token requires an 4×-overloaded expert, which top-k routing on
    a trained router essentially never produces; routing is
    near-deterministic while dispatch stays O(T·E·C) with C ≪ T.
    """
    if config is not None:
        check_seq_parallel_attention(mesh, config, seq_axis)
    axes = (data_axis, seq_axis)
    use_fused = fused_ce and config is not None
    eval_apply = None
    if config is not None and getattr(config, "n_experts", 0):
        import dataclasses

        from pytorch_distributed_tpu.models.transformer import TransformerLM

        eval_cf = min(4.0 * config.capacity_factor, float(config.n_experts))
        eval_cfg = dataclasses.replace(config, capacity_factor=eval_cf)
        eval_apply = TransformerLM(eval_cfg).apply

    if fsdp and state_specs is None:
        raise ValueError(
            "fsdp=True needs state_specs (from shard_lm_state(..., "
            "fsdp=True))"
        )
    eval_gather_tree = (
        _fsdp_gather_tree(state_specs.params, mesh, config, data_axis)
        if fsdp else None
    )

    def _local_eval(state: TrainState, batch: dict, acc: dict):
        lq = batch["tokens"].shape[1]
        positions, offset = _shard_positions(config, lq, seq_axis)
        apply_fn = eval_apply if eval_apply is not None else state.apply_fn
        if eval_gather_tree is not None:
            from pytorch_distributed_tpu.parallel.fsdp import gather_params

            model_params = gather_params(
                state.params, state_specs.params, data_axis,
                mask=eval_gather_tree,
            )
        else:
            model_params = state.params
        out = apply_fn(
            {"params": model_params},
            batch["tokens"],
            position_offset=offset,
            positions=positions,
            train=False,
            return_hidden=use_fused,
        )
        loss_sum = _lm_loss_sum(
            out, model_params, batch, config, use_fused, fused_ce_block_n
        )
        return {
            "loss_sum": acc["loss_sum"] + jax.lax.psum(loss_sum, axes),
            "tokens": acc["tokens"]
            + jax.lax.psum(jnp.sum(batch["weights"]), axes),
        }

    state_spec = state_specs if state_specs is not None else P()
    sharded = shard_map(
        _local_eval,
        mesh=mesh,
        in_specs=(state_spec, P(data_axis, seq_axis), P()),
        out_specs=P(),
        check_vma=False,
    )
    # the profiler's module is jit_<__name__>: the registry's name
    sharded.__name__ = "lm_eval_step"
    return jax.jit(sharded, donate_argnums=(2,))


def empty_lm_metrics() -> dict:
    return {"loss_sum": jnp.zeros((), jnp.float32),
            "tokens": jnp.zeros((), jnp.float32)}
