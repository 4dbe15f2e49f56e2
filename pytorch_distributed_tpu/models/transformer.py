"""Causal transformer LM, designed for sequence parallelism from the start.

The reference has no attention model (SURVEY.md §5: long-context ABSENT);
this is the framework's long-context workhorse. TPU-first choices:

- the module computes on a *local sequence shard*: every position-dependent
  op (positional embedding, causal mask) takes a ``position_offset``, so the
  same module runs unsharded (offset 0) or under ``shard_map`` with the
  sequence split over the ``seq`` mesh axis — where ``attention="ring"``
  makes each block attend globally via ``parallel.sequence.ring_attention``;
- pre-LN blocks, GELU MLP, learned positional embeddings; LayerNorm/softmax
  statistics in fp32, matmuls in the configured compute dtype (bf16 on MXU);
- ``attention="blockwise"`` gives O(L·block) memory single-device attention
  (``ops.attention.blockwise_attention``) for long context without a mesh;
- no data-dependent Python control flow: one XLA program per shape.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.linen.dtypes import promote_dtype

from pytorch_distributed_tpu.ops import attention as attention_ops
from pytorch_distributed_tpu.ops.attention import (
    blockwise_attention,
    dense_attention,
)
from pytorch_distributed_tpu.parallel.mesh import SEQ_AXIS


@dataclasses.dataclass(unsafe_hash=True)
class TransformerConfig:
    vocab_size: int = 32000
    num_layers: int = 12
    num_heads: int = 12
    embed_dim: int = 768
    mlp_ratio: int = 4
    max_seq_len: int = 2048
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16
    attention: str = "dense"  # dense | blockwise | flash | ring | ring_flash
    block_size: int = 512  # kv block for blockwise attention
    seq_axis: str = SEQ_AXIS  # mesh axis for attention="ring"
    # Grouped-query attention: K/V get num_kv_heads heads (must divide
    # num_heads), each shared by a GROUP of num_heads/num_kv_heads query
    # heads — the KV decode cache and the kv projection shrink by the
    # group factor (the Llama-family serving-memory trade). None = MHA
    # with the fused qkv projection (checkpoint layout unchanged); GQA
    # uses separate "q"/"kv" projections. K/V repeat to full heads at
    # compute, so every attention path (dense/flash/ring/...) is
    # unchanged downstream.
    num_kv_heads: Optional[int] = None
    # Position encoding: "learned" (GPT-2-style wpe table, the default)
    # or "rope" (rotary embeddings applied to q/k INSIDE attention — no
    # wpe parameter, unbounded-length friendly). Rotation happens before
    # any attention path runs, with each token's ABSOLUTE position baked
    # in — so ring/zigzag/flash/decode all inherit it unchanged (K is
    # rotated before it travels the ring, and the KV cache stores
    # rotated keys).
    pos_embedding: str = "learned"
    rope_theta: float = 10000.0
    # Ring shard layout: "contiguous" (shard i = tokens [i*L, (i+1)*L)) or
    # "zigzag" (shard i = chunks (i, 2s-1-i) — balances the causal ring's
    # critical path, halving the max per-rank block area at sp=8;
    # ops/ring_flash.py). Zigzag batches must be host-permuted with
    # parallel.sequence.zigzag_shard (train.lm_trainer.shard_lm_batch does
    # it from this flag) and wpe positions follow the chunk map (the LM
    # steps pass a position VECTOR).
    ring_layout: str = "contiguous"
    # Megatron-style tensor parallelism: set model_axis to the mesh's model
    # axis name and tp_size to its size when running under shard_map with
    # params sharded by ``train.lm.TRANSFORMER_TP_RULES``. Parameters keep
    # GLOBAL shapes in the state (sharding is placement; checkpoints are
    # interchangeable across tp degrees); tp_size tells the module the LOCAL
    # feature widths flax should expect at apply time. None/1 = no TP.
    model_axis: Optional[str] = None
    tp_size: int = 1
    # Megatron vocab parallelism (round 5): shard the wte embedding's and
    # lm_head's VOCAB dim over the model axis. The embedding does a
    # masked local lookup psum'd across shards; the loss tail feeds the
    # LOCAL head shard to the fused CE's cross-shard logsumexp
    # (ops/fused_ce.py vocab_axis); the logits path (generate/eval
    # fallback) all_gathers the vocab dim. Cuts the lm_head+wte param,
    # grad, and optimizer memory — and the fused-CE block compute — by
    # tp. Effective only when model_axis/tp_size are set, like every
    # other TP switch; parameters keep GLOBAL shapes in the state.
    vocab_parallel: bool = False
    # Mixture-of-Experts (models/moe.py): n_experts > 0 replaces the dense
    # MLP with a Switch-style MoE in every ``moe_every``-th block. Expert
    # parallelism rides the data axis: set expert_axis/ep_size to the mesh's
    # data axis name/size (weights stay global-shaped; placement shards
    # them, like TP).
    n_experts: int = 0
    moe_every: int = 2
    capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    moe_top_k: int = 1  # 1 = Switch, 2 = GShard top-2
    expert_axis: Optional[str] = None
    ep_size: int = 1
    # The block's description. The defaults are the GPT-2 block this
    # module always ran (LayerNorm before each sublayer, a GELU MLP of
    # ``embed_dim * mlp_ratio`` features, biased input projections):
    # same parameter names, same tree, same programs.
    # ``norm``: "layernorm" (scale and bias) or "rmsnorm" (scale only),
    # float32 statistics either way, at ``norm_eps``.
    norm: str = "layernorm"
    norm_eps: float = 1e-6
    # ``mlp``: "gelu" (``mlp_up`` -> GELU -> ``mlp_down``) or "swiglu"
    # (``SiLU(mlp_gate(x)) * mlp_up(x)`` -> ``mlp_down``). ``mlp_dim`` is
    # the hidden width in FEATURES (5632/2048 is no integer ratio); None
    # = ``embed_dim * mlp_ratio``.
    mlp: str = "gelu"
    mlp_dim: Optional[int] = None
    # Sandwich norm: each sublayer's OUTPUT is normed (``ln1_post``,
    # ``ln2_post``) before it joins the residual stream.
    post_norm: bool = False
    # Bias on the input projections (qkv / q / kv, mlp_up, mlp_gate). The
    # row-parallel output projections never carry one.
    use_bias: bool = True
    # Looped (universal) transformer: the stack of ``block{l}`` runs
    # ``ut_steps`` times over the SAME parameters; ``ln_f`` closes every
    # pass and the normed state goes on; pass t attends only to the keys
    # and values pass t wrote, so the cache holds an entry per (pass,
    # layer). ``exit_gate`` (a logit per position and pass) is in the
    # tree; every token runs every pass: ``early_exit_threshold`` 1.0 is
    # the only value accepted (adaptive exit would give the tokens of
    # one tick different numbers of passes; ROADMAP B-mech).
    ut_steps: int = 1
    early_exit_threshold: float = 1.0
    # ``head_dim``: the width of one attention head where it is not
    # ``embed_dim / num_heads`` (attention whose inner width is not the
    # model's). None = ``embed_dim // num_heads``.
    head_dim: Optional[int] = None
    # ``attn_kind``: "mha" (``Attention``: q, k, v straight from the
    # normed state) or "cca" (``CCAttention``: attention inside a
    # compressed latent of ``num_heads`` query and ``num_kv_heads`` narrow
    # heads of ``head_dim``, two causal two-tap convolutions over the q
    # and k latents, values whose second half comes from the
    # previous token, L2-normed q and k with a learned key temperature).
    # Its cache has a second kind of state beside the K/V pool: a TAIL of
    # ``cca_tail_width`` values a request a layer (the previous token's
    # latents), which belongs to the request and not to a block.
    attn_kind: str = "mha"
    # share of every head's dims that RoPE rotates (the first
    # ``rotary_share * head_dim``; the rest pass through)
    rotary_share: float = 1.0
    # the head is ``wte``'s transpose: no ``lm_head`` leaf
    tie_embeddings: bool = False
    # a sublayer joins the stream as (s_x * x + b_x) + (s_f * f + b_f),
    # four learned vectors of ``embed_dim`` a sublayer (``rs1``, ``rs2``)
    residual_scaling: bool = False
    # ``moe_kind``: "capacity" (``moe.MoEMLP``: Switch/GShard dispatch
    # into ``[T, E, C]`` buffers, GELU experts, tokens over capacity
    # dropped) or "dropless" (``moe.DroplessMoE``: top-1, the live tokens
    # sorted by expert and the three SwiGLU matrices of ``moe_dim``
    # features run as grouped products; no token is dropped). ``router_dim``
    # is the dropless router's width: an MLP on a ``router_dim``-wide
    # projection that adds the previous layer's router state, a second
    # stream carried from block to block beside ``x``. A dropless config
    # states both widths and has an expert layer in every block.
    moe_kind: str = "capacity"
    moe_dim: Optional[int] = None
    router_dim: Optional[int] = None
    # Two more attention kinds, whose inner width ``num_heads * head_dim``
    # is their own and whose layers need RoPE's positions only inside
    # "mla". "kda" (``KDAttention``): delta-rule linear attention with a
    # gate a channel bounded below, q, k and v through short depthwise
    # causal convolutions (the bound and the taps are constants of the
    # module); its cache is a float32 STATE ``[num_heads, head_dim, head_dim]``
    # and the convolutions' last inputs, both a request's and not a
    # block's. "mla" (``MLAttention``): latent attention, whose cache is
    # ONE row a token for all heads (``kv_lora_rank`` normed latent values
    # and ``qk_rope_head_dim`` rotated key dims), in either published
    # spelling: queries straight from the token, values as wide as the
    # unrotated keys and a gate a head, or a compressed query
    # (``q_lora_rank``), values of a width of their own (``v_head_dim``)
    # and no gate (``mla_head_gate``; the three keys are further down).
    # ``attn_kind="mla"`` alone makes EVERY layer latent: the pool of rows
    # is then the only cache. ``layer_group_size`` > 0
    # mixes kinds in one stack: with a linear ``attn_kind`` ("kda", "gdn")
    # every ``layer_group_size``-th layer is ``full_attn_kind``, "mla"
    # unless told (``attn_kind_at``).
    kv_lora_rank: Optional[int] = None
    qk_rope_head_dim: Optional[int] = None
    layer_group_size: int = 0
    # A dropless expert layer behind the other router: ``moe_router``
    # "mlp" (an MLP with state across layers, top-1, softmax) or "sigmoid"
    # (one matrix, sigmoid scores, a selection bias, the experts in
    # ``moe_n_group`` groups of which the ``moe_topk_group`` best are open,
    # ``moe_top_k`` experts a token, their weights renormalised and scaled
    # by ``moe_routed_scale``). ``moe_shared_dim``: a shared expert of that
    # many features beside the routed ones. ``experts_held`` ``(lo, hi)``:
    # this shard holds experts ``[lo, hi)`` of the ``n_experts`` the router
    # scores and returns its part of the routed sum (None: all of them).
    # The first ``first_k_dense_replace`` layers keep the dense MLP.
    moe_router: str = "mlp"
    moe_n_group: int = 1
    moe_topk_group: int = 1
    moe_routed_scale: float = 1.0
    moe_shared_dim: Optional[int] = None
    experts_held: Optional[tuple] = None
    first_k_dense_replace: int = 0
    # A second linear kind and a second full kind in a mixed stack.
    # ``attn_kind="gdn"`` (``GatedDeltaNet``): the delta rule with ONE
    # decay a head a token, unbounded below; ``linear_num_heads`` state
    # (value) heads of ``linear_head_dim``, served in equal groups by
    # ``linear_num_key_heads`` q/k heads (None: the attention's
    # ``num_heads`` / one key head a state head / ``head_dim``); its cache
    # is ``KDAttention``'s two per-slot leaves. ``full_attn_kind``: what
    # every ``layer_group_size``-th layer of a mixed stack is, "mla" or
    # "mha" (``Attention`` over a real K/V pool: ``num_heads``,
    # ``num_kv_heads`` and ``head_dim`` describe THAT layer). Three options
    # of ``Attention``: an inner width of its own (``head_dim``),
    # ``qk_norm`` (an RMSNorm a head on q and on k, one learned scale of
    # ``head_dim`` each, before RoPE; RoPE turns the first ``rotary_share``
    # of a head) and ``attn_gate`` (the q projection is doubled a head, and
    # the sigmoid of its second half scales the attention's output
    # elementwise before the output projection).
    full_attn_kind: str = "mla"
    linear_num_heads: Optional[int] = None
    linear_num_key_heads: Optional[int] = None
    linear_head_dim: Optional[int] = None
    qk_norm: bool = False
    attn_gate: bool = False
    # The third dropless router, ``moe_router="softmax"``: one matrix, a
    # softmax over all ``n_experts``, the ``moe_top_k`` largest, their
    # weights renormalised to sum to one; no groups, bias or scale.
    # ``moe_shared_gate``: the shared expert's output is scaled by a
    # scalar ``sigmoid(x w)`` a token.
    moe_shared_gate: bool = False
    # A stack whose blocks are ONE sublayer each behind one norm, ``x + f(
    # ln1(x))``, in an order that is data: ``layer_pattern`` holds a letter
    # a layer, ``num_layers`` of them: "M" a Mamba-2 mixer (``Mamba2Mixer``:
    # ``mamba_num_heads`` heads of ``mamba_head_dim``, a float32 state
    # ``[mamba_head_dim, mamba_state_size]`` a head that is a request's,
    # B and C shared by the heads of each of ``mamba_n_groups`` groups), "*"
    # ``Attention`` (``num_heads`` / ``num_kv_heads`` / ``head_dim`` describe
    # it), "E" the dropless expert layer behind a one-matrix router, "-" the
    # dense MLP. ``attn_kind_at``, ``moe_at``, ``attn_kinds`` and
    # ``slot_state`` answer from the string; "E" and "-" layers own no cache.
    # ``mlp="relu2"``: ``relu(mlp_up(x))^2 -> mlp_down``, two matrices, for
    # the dense MLP, the routed and the shared experts alike.
    # ``pos_embedding="none"``: no position table and no rotation (the
    # state-space layers carry the order).
    layer_pattern: Optional[str] = None
    mamba_num_heads: Optional[int] = None
    mamba_head_dim: Optional[int] = None
    mamba_state_size: Optional[int] = None
    mamba_n_groups: Optional[int] = None
    # Three more keys of "mla" layers, each defaulting to what the layer ran
    # before it had them. ``q_lora_rank``: the query is compressed too,
    # ``c_q = RMSNorm(x W_qa)`` of that many values, and the heads' queries
    # come from it (``q_a``, ``q_a_norm``, ``q_b``; None: one matrix ``q``
    # straight from the token). ``v_head_dim``: a head's VALUE width where
    # it is not ``head_dim``, the width of its unrotated keys (the fold's
    # ``W_UK`` and ``W_UV`` are then slices of different widths of
    # ``kv_b``). ``mla_head_gate``: a scalar gate a head, ``sigmoid(x
    # W_gh)``, scales the layer's output before its projection.
    q_lora_rank: Optional[int] = None
    v_head_dim: Optional[int] = None
    mla_head_gate: bool = True

    def __post_init__(self):
        if self.attn_kind not in ("mha", "cca", "kda", "mla", "gdn"):
            raise ValueError(
                f"attn_kind {self.attn_kind!r} must be 'mha', 'cca', 'kda', "
                "'mla' or 'gdn'")
        if self.experts_held is not None:  # a JSON list hashes as a tuple
            self.experts_held = tuple(int(i) for i in self.experts_held)
        self._check_layer_pattern()
        self._check_linear_and_latent()
        self._check_expert_router()
        if self.moe_kind not in ("capacity", "dropless"):
            raise ValueError(
                f"moe_kind {self.moe_kind!r} must be 'capacity' or "
                "'dropless'")
        if self.head_dim is not None and self.head_dim < 1:
            raise ValueError(f"head_dim must be >= 1, got {self.head_dim}")
        if not 0.0 < self.rotary_share <= 1.0 or (
                self.pos_embedding == "rope"
                and int(self.rotary_share * self.head_width) % 2):
            raise ValueError(
                f"rotary_share {self.rotary_share} must be in (0, 1] and "
                f"leave an even number of the head's {self.head_width} "
                "dims to rotate")
        if self.attn_kind == "cca":
            if self.num_kv_heads is None or self.pos_embedding != "rope":
                raise ValueError(
                    "attn_kind='cca' needs num_kv_heads (the latent's "
                    "narrow heads) and pos_embedding='rope'")
            if (self.tp_size > 1 or self.ut_steps > 1
                    or self.attention not in ("dense", "blockwise", "flash")):
                raise ValueError(
                    "attn_kind='cca' runs on one shard and one pass: the "
                    "convolutions need the previous token, which a "
                    "sequence shard does not hold, and the tail is one "
                    f"row a request (tp_size {self.tp_size}, ut_steps "
                    f"{self.ut_steps}, attention {self.attention!r})")
        if (self.qk_norm or self.attn_gate) and "mha" not in self.attn_kinds:
            raise ValueError(
                "qk_norm and attn_gate are options of 'mha' layers "
                f"(Attention); this stack's are {self.attn_kinds}")
        if self.attn_gate and self.num_kv_heads is None:
            raise ValueError(
                "attn_gate doubles the separate q projection a head: it "
                "needs num_kv_heads (= num_heads for no grouping)")
        if self.moe_kind == "dropless" and self.moe_router == "mlp":
            if not self.n_experts or self.moe_top_k != 1:
                raise ValueError(
                    "moe_kind='dropless' behind the MLP router is top-1 "
                    f"over n_experts > 0 experts (n_experts "
                    f"{self.n_experts}, moe_top_k {self.moe_top_k})")
            if self.moe_dim is None or self.router_dim is None:
                raise ValueError(
                    "moe_kind='dropless' needs moe_dim (an expert's "
                    "features) and router_dim (the router MLP's width)")
            if self.moe_every != 1:
                raise ValueError(
                    "moe_kind='dropless' has an expert layer in every "
                    "block (moe_every 1): the router's state runs through "
                    f"all of them, got moe_every {self.moe_every}")
        if self.moe_kind == "dropless":
            if self.ep_size > 1 or self.tp_size > 1 or self.ut_steps > 1:
                raise ValueError(
                    "moe_kind='dropless' holds every expert on one shard "
                    "and carries its router stream through one pass "
                    f"(ep_size {self.ep_size}, tp_size {self.tp_size}, "
                    f"ut_steps {self.ut_steps})")
        elif self.moe_dim is not None or self.router_dim is not None:
            raise ValueError(
                "moe_dim and router_dim describe moe_kind='dropless' only")
        if self.tie_embeddings and self.vocab_parallel:
            raise ValueError(
                "tie_embeddings with vocab_parallel is not supported")
        if self.norm not in ("layernorm", "rmsnorm"):
            raise ValueError(
                f"norm {self.norm!r} must be 'layernorm' or 'rmsnorm'"
            )
        if self.mlp not in ("gelu", "swiglu", "relu2"):
            raise ValueError(
                f"mlp {self.mlp!r} must be 'gelu', 'swiglu' or 'relu2'")
        if self.mlp_dim is not None and self.mlp_dim < 1:
            raise ValueError(f"mlp_dim must be >= 1, got {self.mlp_dim}")
        if (self.n_experts and self.moe_kind == "capacity"
                and (self.mlp != "gelu" or self.mlp_dim is not None)):
            raise ValueError(
                "the MoE block (models/moe.py) has GELU experts of "
                "embed_dim * mlp_ratio features: mlp='swiglu' and mlp_dim "
                "describe the dense MLP only"
            )
        if self.ut_steps < 1:
            raise ValueError(f"ut_steps must be >= 1, got {self.ut_steps}")
        if self.early_exit_threshold != 1.0:
            raise ValueError(
                f"early_exit_threshold {self.early_exit_threshold} is not "
                "supported: adaptive exit is not implemented, every token "
                "runs all ut_steps passes (threshold 1.0)"
            )
        if self.ring_layout not in ("contiguous", "zigzag"):
            raise ValueError(
                f"ring_layout {self.ring_layout!r} must be 'contiguous' or "
                "'zigzag'"
            )
        if self.ring_layout == "zigzag" and self.attention not in (
            "ring", "ring_flash"
        ):
            raise ValueError(
                f"ring_layout='zigzag' only applies to ring attention "
                f"(got attention={self.attention!r}); the layout is a "
                "causal-ring scheduling balance, meaningless elsewhere"
            )
        if self.n_experts and self.n_experts % self.ep_size:
            raise ValueError(
                f"n_experts {self.n_experts} not divisible by ep_size {self.ep_size}"
            )
        if self.n_experts and not 1 <= self.moe_top_k <= self.n_experts:
            raise ValueError(
                f"moe_top_k {self.moe_top_k} must be in [1, n_experts="
                f"{self.n_experts}]"
            )
        if self.head_dim is None and self.embed_dim % self.num_heads:
            raise ValueError(
                f"embed_dim {self.embed_dim} not divisible by num_heads {self.num_heads}"
            )
        if self.num_heads % self.tp_size:
            raise ValueError(
                f"num_heads {self.num_heads} not divisible by tp_size {self.tp_size}"
            )
        if self.pos_embedding not in ("learned", "rope", "none"):
            raise ValueError(
                f"pos_embedding {self.pos_embedding!r} must be 'learned', "
                "'rope' or 'none'"
            )
        if self.pos_embedding == "rope" and self.head_width % 2:
            raise ValueError(
                f"rope needs an even head_dim, got {self.head_width}"
            )
        if self.rope_theta <= 0.0:
            raise ValueError(
                f"rope_theta must be > 0, got {self.rope_theta}"
            )
        if self.num_kv_heads is not None:
            if self.num_kv_heads < 1:
                raise ValueError(
                    f"num_kv_heads must be >= 1, got {self.num_kv_heads}"
                )
            if self.num_heads % self.num_kv_heads:
                raise ValueError(
                    f"num_heads {self.num_heads} not divisible by "
                    f"num_kv_heads {self.num_kv_heads}"
                )
            if self.num_kv_heads % self.tp_size:
                raise ValueError(
                    f"num_kv_heads {self.num_kv_heads} not divisible by "
                    f"tp_size {self.tp_size} (each TP rank needs whole KV "
                    "heads)"
                )
        if self.vocab_parallel and self.vocab_size % self.tp_size:
            raise ValueError(
                f"vocab_size {self.vocab_size} not divisible by tp_size "
                f"{self.tp_size} (vocab_parallel shards the vocab dim)"
            )
        if self.tp_size > 1 and self.model_axis is None:
            raise ValueError(
                f"tp_size {self.tp_size} > 1 requires model_axis: without "
                "the axis name the TP collectives are skipped and the model "
                "silently trains with thin local shards"
            )
        if self.mlp_width % self.tp_size:
            raise ValueError(
                f"mlp width {self.mlp_width} not divisible "
                f"by tp_size {self.tp_size}"
            )
        if not 0.0 <= self.dropout < 1.0:
            raise ValueError(f"dropout must be in [0, 1), got {self.dropout}")

    def _check_layer_pattern(self):
        """What a stack of one-sublayer blocks (``layer_pattern``) can run,
        and the keys that describe its "M" layers only."""
        widths = (self.mamba_num_heads, self.mamba_head_dim,
                  self.mamba_state_size, self.mamba_n_groups)
        pattern = self.layer_pattern
        if "M" not in (pattern or "") and widths != (None,) * 4:
            raise ValueError(
                "mamba_num_heads, mamba_head_dim, mamba_state_size and "
                "mamba_n_groups describe the 'M' layers of a layer_pattern "
                "only")
        if pattern is None:
            return
        if set(pattern) - set("ME*-") or len(pattern) != self.num_layers:
            raise ValueError(
                f"layer_pattern {pattern!r} must hold one of 'M' (Mamba-2), "
                "'E' (experts), '*' (attention) or '-' (dense MLP) for each "
                f"of num_layers {self.num_layers} layers")
        if (self.attn_kind != "mha" or self.layer_group_size
                or self.first_k_dense_replace):
            raise ValueError(
                "layer_pattern gives every layer's kind itself: attn_kind "
                "stays 'mha' (the '*' layers), layer_group_size and "
                "first_k_dense_replace 0")
        if ("E" in pattern) != bool(self.n_experts) or (
                "E" in pattern and (self.moe_kind != "dropless"
                                    or self.moe_router == "mlp")):
            raise ValueError(
                "the 'E' layers of a layer_pattern are moe_kind='dropless' "
                "behind a one-matrix router over n_experts > 0 experts, and "
                f"a pattern without one has none (n_experts {self.n_experts}, "
                f"moe_kind {self.moe_kind!r}, moe_router {self.moe_router!r})")
        if "M" not in pattern:
            return
        if None in widths or min(widths) < 1 or (
                self.mamba_num_heads % self.mamba_n_groups):
            raise ValueError(
                f"an 'M' layer's {self.mamba_num_heads} heads of "
                f"{self.mamba_head_dim} with a state of "
                f"{self.mamba_state_size} must be whole groups of its "
                f"{self.mamba_n_groups} B/C groups")
        if (self.tp_size > 1 or self.ut_steps > 1
                or self.attention != "dense"):
            raise ValueError(
                "an 'M' layer runs on one shard, one pass and "
                "attention='dense': the recurrence and the convolution need "
                "every earlier token and the state is one a request "
                f"(tp_size {self.tp_size}, ut_steps {self.ut_steps}, "
                f"attention {self.attention!r})")

    def _check_linear_and_latent(self):
        """What the linear kinds ("kda", "gdn") and "mla" can run, and the
        keys that describe them only."""
        kinds = set(self.attn_kinds)
        linear = self.attn_kind in ("kda", "gdn")
        if self.layer_group_size < 0 or (
                self.layer_group_size and not linear):
            raise ValueError(
                f"layer_group_size {self.layer_group_size} mixes full "
                "layers into a stack of attn_kind='kda' or 'gdn' (every "
                "layer_group_size-th layer), got attn_kind "
                f"{self.attn_kind!r}")
        if self.full_attn_kind not in ("mla", "mha") or (
                self.full_attn_kind != "mla" and not self.layer_group_size):
            raise ValueError(
                f"full_attn_kind {self.full_attn_kind!r} must be 'mla' or "
                "'mha', and names every layer_group_size-th layer of a "
                f"mixed stack (layer_group_size {self.layer_group_size})")
        if "mla" in kinds:
            if not self.kv_lora_rank or not self.qk_rope_head_dim or (
                    self.qk_rope_head_dim % 2):
                raise ValueError(
                    "latent attention ('mla') needs kv_lora_rank (the "
                    "latent's width) and an even qk_rope_head_dim (the "
                    f"rotated key dims), got {self.kv_lora_rank} and "
                    f"{self.qk_rope_head_dim}")
            if any(w is not None and w < 1
                   for w in (self.q_lora_rank, self.v_head_dim)):
                raise ValueError(
                    "q_lora_rank (the compressed query's width) and "
                    "v_head_dim (a head's value width) must be >= 1, got "
                    f"{self.q_lora_rank} and {self.v_head_dim}")
        elif (self.kv_lora_rank, self.qk_rope_head_dim, self.q_lora_rank,
              self.v_head_dim, self.mla_head_gate) != (None,) * 4 + (True,):
            raise ValueError(
                "kv_lora_rank and qk_rope_head_dim describe latent "
                "attention ('mla' layers) only, as do q_lora_rank, "
                "v_head_dim and mla_head_gate")
        described = (self.linear_num_heads, self.linear_num_key_heads,
                     self.linear_head_dim)
        if "gdn" not in kinds:
            if described != (None, None, None):
                raise ValueError(
                    "linear_num_heads, linear_num_key_heads and "
                    "linear_head_dim describe attn_kind='gdn' only")
        elif (min(self.linear_heads, self.linear_key_heads,
                  self.linear_head_width) < 1
              or self.linear_heads % self.linear_key_heads):
            raise ValueError(
                f"the gated delta rule's {self.linear_heads} state heads "
                f"of {self.linear_head_width} must be whole groups of its "
                f"{self.linear_key_heads} key heads")
        if not kinds & {"kda", "mla", "gdn"}:
            return
        if self.head_dim is None or self.pos_embedding != "rope" or (
                self.num_kv_heads is not None and "mha" not in kinds):
            raise ValueError(
                f"attn_kind {self.attn_kind!r} needs head_dim (the inner "
                "width is num_heads * head_dim), no num_kv_heads (a state "
                "or a latent row serves every head; grouped K/V heads are "
                "a full_attn_kind='mha' layer's) and pos_embedding='rope'")
        if (self.tp_size > 1 or self.ut_steps > 1
                or self.attention != "dense"):
            raise ValueError(
                f"attn_kind {self.attn_kind!r} runs on one shard, one pass "
                "and attention='dense': the recurrence and the convolutions "
                "need every earlier token, the state is one a request, and "
                "latent keys are wider than their values (tp_size "
                f"{self.tp_size}, ut_steps {self.ut_steps}, attention "
                f"{self.attention!r})")

    def _check_expert_router(self):
        """The one-matrix routers' keys ("sigmoid", "softmax"), and that
        nothing else carries them."""
        if self.moe_router not in ("mlp", "sigmoid", "softmax"):
            raise ValueError(
                f"moe_router {self.moe_router!r} must be 'mlp', 'sigmoid' "
                "or 'softmax'")
        groups = (self.moe_n_group, self.moe_topk_group,
                  self.moe_routed_scale)
        if self.moe_router == "mlp":
            if groups + (self.moe_shared_dim, self.experts_held,
                         self.first_k_dense_replace, self.moe_shared_gate
                         ) != (1, 1, 1.0, None, None, 0, False):
                raise ValueError(
                    "moe_n_group, moe_topk_group, moe_routed_scale, "
                    "moe_shared_dim, moe_shared_gate, experts_held and "
                    "first_k_dense_replace describe the one-matrix routers "
                    "(moe_router 'sigmoid', 'softmax') only")
            return
        if self.moe_router == "softmax" and groups != (1, 1, 1.0):
            raise ValueError(
                "moe_n_group, moe_topk_group and moe_routed_scale describe "
                "moe_router='sigmoid' only: the softmax router has no "
                "groups and its weights sum to one")
        e, g = self.n_experts, self.moe_n_group
        if self.moe_kind != "dropless" or not e or self.moe_dim is None or (
                self.router_dim is not None
                or (self.moe_every != 1 and self.layer_pattern is None)):
            raise ValueError(
                f"moe_router={self.moe_router!r} is moe_kind='dropless' "
                "over n_experts > 0 experts of moe_dim features (the "
                "'sigmoid' and 'softmax' routers alike), an expert layer "
                "in every block from first_k_dense_replace on (moe_every "
                "1) or where a layer_pattern says, and no router_dim (one "
                "matrix, no MLP)")
        if g < 1 or e % g or not 1 <= self.moe_topk_group <= g or not (
                1 <= self.moe_top_k <= self.moe_topk_group * (e // g)):
            raise ValueError(
                f"the router's {e} experts split into moe_n_group {g} "
                f"groups, 1 <= moe_topk_group {self.moe_topk_group} <= "
                f"{g} stay open and moe_top_k {self.moe_top_k} experts must "
                "fit inside them")
        if e // g < 2 and g > 1:
            raise ValueError(
                "a group's score is the sum of its two best experts: "
                f"moe_n_group {g} leaves {e // g} an expert group")
        if self.experts_held is not None:
            lo, hi = self.experts_held if len(
                self.experts_held) == 2 else (0, 0)
            if not 0 <= lo < hi <= e:
                raise ValueError(
                    f"experts_held {self.experts_held!r} must be (lo, hi) "
                    f"with 0 <= lo < hi <= n_experts {e}")
        if self.moe_shared_dim is not None and self.moe_shared_dim < 1:
            raise ValueError(
                f"moe_shared_dim must be >= 1, got {self.moe_shared_dim}")
        if self.moe_shared_gate and self.moe_shared_dim is None:
            raise ValueError(
                "moe_shared_gate scales the shared expert: it needs "
                "moe_shared_dim")
        if not 0 <= self.first_k_dense_replace <= self.num_layers:
            raise ValueError(
                f"first_k_dense_replace {self.first_k_dense_replace} must "
                f"lie in [0, num_layers {self.num_layers}]")

    def attn_kind_at(self, layer: int) -> str:
        """The attention of layer ``layer``: ``attn_kind``, but
        ``full_attn_kind`` at every ``layer_group_size``-th layer of a
        mixed stack; under a ``layer_pattern`` what its letter says, None
        for a layer that has none."""
        if self.layer_pattern is not None:  # None: an "E" or "-" layer
            return {"M": "mamba2", "*": "mha"}.get(self.layer_pattern[layer])
        if self.layer_group_size and (layer + 1) % self.layer_group_size == 0:
            return self.full_attn_kind
        return self.attn_kind

    def moe_at(self, layer: int) -> bool:
        """Whether layer ``layer``'s MLP is the expert layer."""
        if self.layer_pattern is not None:
            return self.layer_pattern[layer] == "E"
        return (bool(self.n_experts)
                and layer % self.moe_every == self.moe_every - 1
                and layer >= self.first_k_dense_replace)

    @property
    def attn_kinds(self) -> tuple:
        """The kinds of attention in the stack, a name once."""
        return tuple(sorted({self.attn_kind_at(i)
                             for i in range(self.num_layers)} - {None}))

    @property
    def slot_state(self) -> bool:
        """Whether the cache holds state that is a REQUEST's and not a
        block's (``serving.kv_pool.SLOT_LEAVES``): "cca"'s tail, "kda"'s
        state and convolution inputs, "gdn"'s and "mamba2"'s."""
        return bool({"cca", "kda", "gdn", "mamba2"} & set(self.attn_kinds))

    @property
    def linear_heads(self) -> int:
        """State (value) heads of a "gdn" layer."""
        return (self.num_heads if self.linear_num_heads is None
                else self.linear_num_heads)

    @property
    def linear_key_heads(self) -> int:
        """q/k heads of a "gdn" layer, each serving an equal group of its
        state heads."""
        return (self.linear_heads if self.linear_num_key_heads is None
                else self.linear_num_key_heads)

    @property
    def linear_head_width(self) -> int:
        """Features of one "gdn" head (keys and values alike)."""
        return (self.head_width if self.linear_head_dim is None
                else self.linear_head_dim)

    @property
    def latent_row_width(self) -> int:
        """Values in a token's ONE cache row where the stack has latent
        attention: the normed latent, the rotated key dims, and zeros up to
        the next multiple of 128 lanes (the fused read's products take
        whole lane tiles; no product sees the padding). 0 elsewhere."""
        if "mla" not in self.attn_kinds:
            return 0
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def head_width(self) -> int:
        """Features of one attention head."""
        return (self.head_dim if self.head_dim is not None
                else self.embed_dim // self.num_heads)

    @property
    def value_head_width(self) -> int:
        """Features of one head's VALUES in an "mla" layer: ``v_head_dim``,
        or the head's unrotated key width where none is given."""
        return (self.v_head_dim if self.v_head_dim is not None
                else self.head_width)

    @property
    def cca_tail_width(self) -> int:
        """Values a request holds a layer beside its K/V blocks where
        ``attn_kind`` is "cca": the previous token's q and k latents
        before and after the first convolution, and its shifted value
        half. 0 for every other attention."""
        if self.attn_kind != "cca":
            return 0
        d = self.head_width
        latent = (self.num_heads + self.num_kv_heads) * d
        return 2 * latent + self.num_kv_heads * d // 2

    @property
    def mlp_width(self) -> int:
        """Hidden features of the dense MLP."""
        return (self.mlp_dim if self.mlp_dim is not None
                else self.embed_dim * self.mlp_ratio)

    def uses_vocab_parallel(self) -> bool:
        """THE vocab-parallel predicate — the one place the condition
        lives. The model's head/embedding branch, the TP placement rules
        (``train/lm.py``), and the serving rule builder
        (``models/generate.py``) all call this, so they cannot diverge on
        edge cases (e.g. ``model_axis`` set with ``tp_size == 1``, where
        sharding the vocab dim would be vacuous but the collective branch
        is not free)."""
        return (
            self.vocab_parallel
            and self.model_axis is not None
            and self.tp_size > 1
        )


def _rope_rotate(x, positions, theta: float, share: float = 1.0):
    """Rotary embedding on ``x`` [B, L, H, D] at absolute ``positions``
    ([1, L] shared or [B, L] per-request), interleaved-pair convention.
    fp32 trig regardless of compute dtype. ``share`` < 1 rotates only the
    first ``share * D`` dims of every head; the rest pass through."""
    d = x.shape[-1]
    if int(share * d) < d:
        rot = int(share * d)
        return jnp.concatenate(
            [_rope_rotate(x[..., :rot], positions, theta), x[..., rot:]],
            axis=-1)
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[..., None] * inv  # [B?, L, D/2]
    cos = jnp.cos(ang)[:, :, None, :]  # [B?, L, 1, D/2] broadcasts over H
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., ::2].astype(jnp.float32), x[..., 1::2].astype(jnp.float32)
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _norm(cfg: TransformerConfig, name: str):
    """The config's normalisation layer, float32 statistics and output."""
    kind = nn.RMSNorm if cfg.norm == "rmsnorm" else nn.LayerNorm
    return kind(epsilon=cfg.norm_eps, dtype=jnp.float32, name=name)


class RowsDense(nn.Module):
    """``nn.DenseGeneral``'s parameters under its names (``kernel``
    ``in_shape + features``, ``bias`` ``features``, the same initial
    values from the same key) with the product taken on FLAT rows:
    ``[..., prod(in_shape)]`` in, ``[..., prod(features)]`` out. The TPU
    compiler lays a product whose last axis is a head's 64 columns out
    with the SEQUENCE minor (a 64-wide minor axis half-fills a 128-lane
    tile), so a consumer that wants rows pays a relayout of the whole
    array; a product whose last axis is the whole row stays row-major,
    as the MLP's do."""

    in_shape: tuple
    features: tuple
    use_bias: bool = True
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        flat = (math.prod(self.in_shape), math.prod(self.features))

        def kernel_init(rng, shape, dtype=jnp.float32):
            # DenseGeneral's: drawn on the flat shape, then reshaped
            return nn.linear.default_kernel_init(rng, flat, dtype).reshape(
                shape)

        kernel = self.param("kernel", kernel_init,
                            self.in_shape + self.features)
        bias = (self.param("bias", nn.initializers.zeros_init(),
                           self.features) if self.use_bias else None)
        x, kernel, bias = promote_dtype(x, kernel, bias, dtype=self.dtype)
        out = x @ kernel.reshape(flat)
        return out if bias is None else out + bias.reshape(flat[1])


class Attention(nn.Module):
    config: TransformerConfig
    deterministic: bool = True
    decode: bool = False
    prefill: bool = False

    @nn.compact
    def __call__(self, x, position_offset, positions=None,
                 block_tables=None, pass_index=None):
        cfg = self.config
        b, l, e = x.shape
        head_dim = cfg.head_width
        looped = cfg.ut_steps > 1
        if looped and pass_index is None:
            raise ValueError(
                "a looped config (ut_steps > 1) needs pass_index=: the "
                "cache holds an entry per (pass, layer); TransformerLM "
                "provides it"
            )
        if cfg.model_axis:
            from pytorch_distributed_tpu.parallel.tensor import tp_copy

            x = tp_copy(x, cfg.model_axis)  # column-parallel qkv below
        heads_local = cfg.num_heads // cfg.tp_size
        # The training flash path reads q, k and v where the fused
        # product lies: [B, L, 3·H·D] rows in, [B, L, H·D] rows out to
        # ``project``, no [B, L, H, D] array between (``RowsDense`` keeps
        # both products row-major on the chip). Same parameters as the
        # other paths, which take the product head by head.
        packed = (
            cfg.attention == "flash" and cfg.num_kv_heads is None
            and block_tables is None and not (self.decode or self.prefill)
            and not cfg.qk_norm and cfg.pos_embedding != "rope"
        )
        if packed:
            qkv_rows = RowsDense(
                (e,), (3, heads_local, head_dim), dtype=cfg.dtype,
                name="qkv", use_bias=cfg.use_bias,
            )(x)
            q = k = v = None
            kv_group = 1
        elif cfg.num_kv_heads is None:
            qkv = nn.DenseGeneral(
                (3, heads_local, head_dim), dtype=cfg.dtype, name="qkv",
                use_bias=cfg.use_bias,
            )(x)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]  # [B,L,H,D]
            kv_group = 1
        else:
            # GQA: separate projections; K/V carry num_kv_heads heads —
            # the cache below inherits the narrow head count (the serving
            # memory win), and compute repeats to full heads afterwards.
            kv_heads_local = cfg.num_kv_heads // cfg.tp_size
            kv_group = heads_local // kv_heads_local
            q = nn.DenseGeneral(
                (heads_local, (2 if cfg.attn_gate else 1) * head_dim),
                dtype=cfg.dtype, name="q", use_bias=cfg.use_bias,
            )(x)
            if cfg.attn_gate:  # a head's columns are [q | gate]
                q, gate = q[..., :head_dim], q[..., head_dim:]
            kv = nn.DenseGeneral(
                (2, kv_heads_local, head_dim), dtype=cfg.dtype, name="kv",
                use_bias=cfg.use_bias,
            )(x)
            k, v = kv[:, :, 0], kv[:, :, 1]  # [B, L, H_kv_loc, D]

        if cfg.qk_norm:
            # an RMSNorm a head, one learned scale of head_dim for every
            # head, float32 statistics; before RoPE and the cache write
            q = nn.RMSNorm(epsilon=cfg.norm_eps, dtype=jnp.float32,
                           name="q_norm")(q).astype(cfg.dtype)
            k = nn.RMSNorm(epsilon=cfg.norm_eps, dtype=jnp.float32,
                           name="k_norm")(k).astype(cfg.dtype)

        def project(out):
            """The output projection, behind the gate where there is one
            (row-parallel and bias-free: the TP psum must not add a bias
            tp times)."""
            if cfg.attn_gate:
                out = (out.astype(jnp.float32) * jax.nn.sigmoid(
                    gate.astype(jnp.float32))).astype(cfg.dtype)
            if packed:  # [B, L, H·D] rows
                return RowsDense(
                    (heads_local, head_dim), (e,), use_bias=False,
                    dtype=cfg.dtype, name="proj",
                )(out)
            return nn.DenseGeneral(
                e, axis=(-2, -1), use_bias=False, dtype=cfg.dtype,
                name="proj",
            )(out)

        if cfg.pos_embedding == "rope":
            # Rotate BEFORE the cache write and before any attention path
            # runs: absolute positions are baked into q/k, so the ring
            # variants ship pre-rotated keys and the cache stores rotated
            # keys — downstream stays position-agnostic. The positions
            # are RESOLVED by the caller (TransformerLM / PPStage) — one
            # source of truth, never re-derived here where it could drift
            # from the wpe/cache-write/mask convention.
            if positions is None:
                raise ValueError(
                    "pos_embedding='rope' needs the resolved positions= "
                    "array ([L] shared or [B, L] per-request); "
                    "TransformerLM and train.pp.PPStage provide it"
                )
            rpos = positions[None] if positions.ndim == 1 else positions
            q = _rope_rotate(q, rpos, cfg.rope_theta, cfg.rotary_share)
            k = _rope_rotate(k, rpos, cfg.rope_theta, cfg.rotary_share)

        if block_tables is not None:
            # Paged serving (serving/): the cache is a block POOL
            # [n_blocks, block_len, H_kv*D] shared by every request (a
            # row is a position's heads side by side: kv_pool.
            # pool_leaf_shape — the leaf the chip keeps row-major), and
            # this request's logical positions map to pool blocks through
            # its block-table row. One path serves BOTH chunked prefill
            # (l == chunk) and decode (l == 1): write the chunk at its
            # absolute positions, then attend against the gathered chain —
            # which includes the chunk just written, so intra-chunk
            # causality falls out of the same mask as cross-chunk.
            # ``position_offset`` stays the single source of position
            # truth: the block/offset write indices, the attention mask,
            # and the positional embedding all derive from the same [B]
            # start vector.
            if not (self.decode or self.prefill):
                raise ValueError(
                    "block_tables= is the paged SERVING cache layout; it "
                    "requires decode or prefill mode"
                )
            from pytorch_distributed_tpu.ops.attention import paged_attention

            kv_heads = k.shape[2]
            ck = self.variable("cache", "key", _need_pool)
            cv = self.variable("cache", "value", _need_pool)
            # A looped config's leaf is [n_blocks, passes, block_len,
            # H_kv*D] (kv_pool.pool_leaf_shape): block b of pass t is row
            # b*passes + t of the leaf seen as [n_blocks*passes, ...] — a
            # view, the leaf is row-major — so this pass scatters into and
            # gathers from the CARRIED buffer in place through shifted
            # block ids, and never slices its share out of the pool.
            stored = ck.value.shape
            block_len = stored[-2]
            # the backend and this program's rows choose the read, once
            # for the scatter and the read (looked up through the module:
            # a test steers the rule there)
            gather_impl = attention_ops.default_gather_impl(
                rows=l * (q.shape[2] // kv_heads))

            def pool_view(x):
                return x.reshape((-1,) + x.shape[-2:]) if looped else x

            if looped:
                block_tables = block_tables * cfg.ut_steps + pass_index
            pos = jnp.asarray(position_offset, jnp.int32)
            if pos.ndim != 1:
                raise ValueError(
                    "paged mode takes a [B] position_offset vector (each "
                    "request's write start), got a scalar"
                )
            p = pos[:, None] + jnp.arange(l)  # [B, l] absolute positions
            blk = jnp.take_along_axis(block_tables, p // block_len, axis=1)
            off = p % block_len
            # Scatter the chunk into the pool. Index pairs are unique per
            # request (each owns its blocks); the engine routes inactive
            # slots' writes to the trash block, where duplicate hits are
            # harmless garbage.
            from pytorch_distributed_tpu.serving.kv_pool import (
                is_quantized_pool,
            )

            if is_quantized_pool(ck.value.dtype):
                # quantized pool (serving.kv_pool kv_dtype="int8"/
                # "fp8"/"fp8_e5m2"): quantize-on-scatter — each written
                # KV row stores quantized values plus its per-head scale
                # (fp32 multiplier for int8, int8 exponent for fp8) in
                # the scale siblings, at the same (block, offset)
                # indices. The read path below dequantizes (in-VMEM for
                # the pallas spelling). Intra-chunk attention therefore
                # also reads quantized KV — the same values every later
                # chunk and decode tick will see, so the stream has ONE
                # consistent quantization, not an exact-then-quantized
                # seam. Where the read is the kernel the quantization is
                # one kernel (ops.paged_flash.paged_quantize_scatter:
                # rows and scales together, placed by the same in-place
                # .at[].set); the jnp spelling below is the dense/
                # interpret reference — both call
                # kv_pool.quantize_rows, so the pools are bit-identical
                # across spellings.
                cks = self.variable("cache", "key_scale", _need_pool)
                cvs = self.variable("cache", "value_scale", _need_pool)
                pools = [pool_view(c.value) for c in (ck, cv, cks, cvs)]
                if gather_impl == "pallas":
                    from pytorch_distributed_tpu.ops.paged_flash import (
                        paged_quantize_scatter,
                    )

                    pools = paged_quantize_scatter(k, v, blk, off, *pools)
                else:
                    from pytorch_distributed_tpu.serving.kv_pool import (
                        quantize_kv,
                    )

                    kq, ks_rows = quantize_kv(k, ck.value.dtype)
                    vq, vs_rows = quantize_kv(v, cv.value.dtype)
                    rows = (blk.reshape(-1), off.reshape(-1))
                    pools = [
                        pool.at[rows].set(new.reshape(b * l, -1))
                        for pool, new in zip(pools,
                                             (kq, vq, ks_rows, vs_rows))
                    ]
                k_pool, v_pool, k_scale, v_scale = pools
                out = paged_attention(
                    q, k_pool, v_pool, block_tables, p,
                    gather_impl=gather_impl,
                    k_scale=k_scale, v_scale=v_scale,
                )
                for c, pool in zip((ck, cv, cks, cvs), pools):
                    c.value = pool.reshape(c.value.shape)
            else:
                k_pool = pool_view(ck.value).at[
                    blk.reshape(-1), off.reshape(-1)
                ].set(k.astype(cfg.dtype).reshape(b * l, kv_heads * head_dim))
                v_pool = pool_view(cv.value).at[
                    blk.reshape(-1), off.reshape(-1)
                ].set(v.astype(cfg.dtype).reshape(b * l, kv_heads * head_dim))
                out = paged_attention(
                    q, k_pool, v_pool, block_tables, p,
                    gather_impl=gather_impl,
                )
                ck.value = k_pool.reshape(stored)
                cv.value = v_pool.reshape(stored)
            out = project(out)
            if cfg.model_axis:
                from pytorch_distributed_tpu.parallel.tensor import tp_reduce

                out = tp_reduce(out, cfg.model_axis)
            return out

        if self.decode or self.prefill:
            # KV cache. ``position_offset`` is the single source of
            # position truth — the write index, the attention mask, AND
            # the positional embedding all derive from it, so they cannot
            # silently disagree (no per-layer counter to drift). In decode
            # mode it may be a PER-REQUEST [B] vector (ragged serving:
            # each request writes its own cache slot).
            max_len = cfg.max_seq_len
            kv_heads = k.shape[2]  # H_kv_local under GQA, H_local for MHA
            # a looped config keeps a [B, max_len, ...] entry per pass,
            # stacked on a leading axis this pass indexes
            lead = (cfg.ut_steps,) if looped else ()
            at = (pass_index,) if looped else ()
            shape = lead + (b, max_len, kv_heads, head_dim)
            ck = self.variable(
                "cache", "key", lambda: jnp.zeros(shape, cfg.dtype)
            )
            cv = self.variable(
                "cache", "value", lambda: jnp.zeros(shape, cfg.dtype)
            )
            pos = jnp.asarray(position_offset, jnp.int32)
            if self.decode and pos.ndim == 1:
                # per-request slot write (l == 1, asserted below)
                rows = at + (jnp.arange(b), pos)
                ck.value = ck.value.at[rows].set(k[:, 0].astype(cfg.dtype))
                cv.value = cv.value.at[rows].set(v[:, 0].astype(cfg.dtype))
            else:
                k_new, v_new = k.astype(cfg.dtype), v.astype(cfg.dtype)
                if looped:
                    k_new, v_new = k_new[None], v_new[None]
                ck.value = jax.lax.dynamic_update_slice(
                    ck.value, k_new, at + (0, pos, 0, 0)
                )
                cv.value = jax.lax.dynamic_update_slice(
                    cv.value, v_new, at + (0, pos, 0, 0)
                )

        if self.decode:
            # Single-token step attending against the cache (O(L) per
            # token); parity vs the full causal forward is tested in
            # tests/test_generate.py.
            assert l == 1, f"decode mode processes one token/step, got {l}"
            pos = jnp.asarray(position_offset, jnp.int32)
            pos_b = pos if pos.ndim == 1 else jnp.full((b,), pos)
            scale = head_dim**-0.5
            k_seen, v_seen = ck.value, cv.value
            if looped:  # this pass's entry (a copy: the paged pool is
                # the serving layout, this one serves generate())
                k_seen, v_seen = k_seen[pass_index], v_seen[pass_index]
            if kv_group > 1:
                # GQA decode: grouped einsum directly against the NARROW
                # cache — no widened K/V tensor ever materializes, so the
                # decode memory traffic (the bottleneck GQA targets)
                # really is 1/group of MHA's. Query head qh maps to
                # narrow head qh // group, matching the repeat layout
                # the train path uses.
                qg = (q.astype(jnp.float32) * scale).reshape(
                    b, 1, kv_heads, kv_group, head_dim
                )
                s = jnp.einsum(
                    "bqhgd,bkhd->bhgqk", qg,
                    k_seen.astype(jnp.float32),
                )  # [B, H_kv, G, 1, max_len]
                mask = (jnp.arange(cfg.max_seq_len)[None, None, None, None]
                        <= pos_b[:, None, None, None, None])
                s = jnp.where(mask, s, -1e30)
                p = jax.nn.softmax(s, axis=-1)
                out = jnp.einsum(
                    "bhgqk,bkhd->bqhgd", p, v_seen.astype(jnp.float32)
                ).reshape(b, 1, heads_local, head_dim).astype(cfg.dtype)
            else:
                s = jnp.einsum(
                    "bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                    k_seen.astype(jnp.float32),
                )  # [B, H, 1, max_len]
                mask = (jnp.arange(cfg.max_seq_len)[None, None, None, :]
                        <= pos_b[:, None, None, None])
                s = jnp.where(mask, s, -1e30)
                p = jax.nn.softmax(s, axis=-1)
                out = jnp.einsum(
                    "bhqk,bkhd->bqhd", p, v_seen.astype(jnp.float32)
                ).astype(cfg.dtype)
            out = project(out)
            if cfg.model_axis:
                from pytorch_distributed_tpu.parallel.tensor import tp_reduce

                out = tp_reduce(out, cfg.model_axis)
            return out
        # prefill falls through: one BATCHED causal forward over the prompt
        # (the cache write above is its only side effect)

        if kv_group > 1:
            # GQA: widen K/V to the full head count for the attention
            # paths below — they all see plain MHA shapes (the cache
            # above already stored the NARROW heads; this is compute-side
            # only)
            k = jnp.repeat(k, kv_group, axis=2)
            v = jnp.repeat(v, kv_group, axis=2)

        if cfg.attention == "ring":
            from pytorch_distributed_tpu.parallel.sequence import ring_attention

            if cfg.ring_layout == "zigzag":
                # zigzag derives chunk positions from the ring index with
                # a document-rooted convention; the trainer feeds wpe a
                # matching position VECTOR (train/lm.py) and batches are
                # host-permuted, so base is 0 here.
                out = ring_attention(
                    q, k, v, axis=cfg.seq_axis, causal=True, layout="zigzag"
                )
            else:
                # The kernel derives each shard's position as
                # base + index*L; recover the document base from the
                # caller's absolute offset so any position_offset
                # convention stays consistent with the mask.
                base = position_offset - jax.lax.axis_index(cfg.seq_axis) * l
                out = ring_attention(
                    q, k, v, axis=cfg.seq_axis, causal=True, base_offset=base
                )
        elif cfg.attention == "ring_flash":
            from pytorch_distributed_tpu.ops.ring_flash import (
                ring_flash_attention,
            )

            # Same ring schedule, Pallas flash kernels per visiting shard
            # (ops/ring_flash.py). Causal structure comes from ring
            # positions, which is exact for any uniform position offset.
            # Blocks must DIVIDE the kernel's working length — the shard
            # under the contiguous layout, a HALF-shard chunk under zigzag
            # — and should stay lane-aligned: prefer the largest
            # 128-multiple divisor within block_size; small shards run as
            # one block; anything else (e.g. L_local=250) is rejected
            # rather than silently degenerating to tiny unaligned blocks.
            zig = cfg.ring_layout == "zigzag"
            lw = l // 2 if zig else l
            limit = min(cfg.block_size, lw)
            blk = max(
                (c for c in range(128, limit + 1, 128) if lw % c == 0),
                default=None,
            )
            if blk is None and lw <= limit and (lw < 128 or lw % 8 == 0):
                blk = lw  # single-block shard (small/test shapes)
            if blk is None:
                raise ValueError(
                    f"ring_flash: no usable block size for working length "
                    f"{lw} (block_size {cfg.block_size}); pad the sequence "
                    "so it has a 128-multiple divisor, or use "
                    "attention='ring'"
                )
            out = ring_flash_attention(
                q, k, v, axis=cfg.seq_axis, causal=True,
                block_q=blk, block_k=blk, layout=cfg.ring_layout,
            )
        elif cfg.attention == "blockwise":
            out = blockwise_attention(
                q, k, v, causal=True, block_size=min(cfg.block_size, l),
                q_offset=position_offset, k_offset=position_offset,
            )
        elif cfg.attention == "flash":
            from pytorch_distributed_tpu.ops.flash_attention import (
                flash_attention,
                flash_attention_qkv,
            )

            # Pallas kernel path. The kernel masks from position 0, which is
            # exact for any equal-offset self-attention: the causal
            # predicate (k_off + j <= q_off + i) is offset-invariant when
            # q_off == k_off, as it is here.
            if packed:
                out = flash_attention_qkv(qkv_rows, heads_local, causal=True)
            else:
                out = flash_attention(q, k, v, causal=True)
        elif cfg.attention == "dense":
            out = dense_attention(
                q, k, v, causal=True,
                q_offset=position_offset, k_offset=position_offset,
            )
        else:
            raise ValueError(f"unknown attention {self.config.attention!r}")
        out = project(out)
        if cfg.model_axis:
            from pytorch_distributed_tpu.parallel.tensor import tp_reduce

            out = tp_reduce(out, cfg.model_axis)
        # Residual dropout AFTER tp_reduce: activations here are replicated
        # across the model axis, and the step derives the dropout rng from
        # (seed, step, data/seq coords) only — model-axis replicas see the
        # same mask and stay bitwise identical (train/lm.py rng plumbing).
        if cfg.dropout:
            out = nn.Dropout(cfg.dropout, deterministic=self.deterministic)(out)
        return out


def _need_pool(*_a):
    raise ValueError(
        "paged attention needs the pool cache passed in (apply with "
        "{'cache': serving.kv_pool.init_paged_cache(...)}); there is no "
        "in-module init for it"
    )


class CCAttention(nn.Module):
    """Compressed convolutional attention (``attn_kind="cca"``; Zyphra,
    arXiv:2510.04476, as ``perfbench/references/zaya.py`` writes it down).

    One fused projection takes the normed state to the q and k LATENTS
    ``u`` (``(H + H_kv) * D`` channels) and to the value halves; two
    causal two-tap convolutions run over ``u`` (depthwise, then one
    ``D x D`` block a head); q and k are the convolved latents plus the
    mean of the unconvolved q and k latents of their group, L2-normed a
    head (k times a learned temperature), RoPE on the first
    ``rotary_share`` of every head; the second half of a token's value
    comes from the PREVIOUS token. Attention itself is grouped-head
    attention over ``H_kv`` narrow heads.

    What the previous token contributes (its ``u``, its first
    convolution's output, its shifted value half: ``cca_tail_width``
    values) is the layer's TAIL. The full-sequence forward shifts along
    the sequence and starts from zeros. With a cache the tail is a
    second kind of state beside the keys and values: one row a request
    (``cache/tail``), read by the next chunk or tick, zero for a row that
    starts at position 0 whatever the row held, and written from the
    row's last REAL position (``lengths``). In the paged layout the leaf
    is ``[n_slots + 1, width]`` and ``slots`` names each row's slot (the
    last row takes what padding jobs and inactive lanes write).
    """

    #: taps of each convolution: the current token and ONE before it,
    #: which is all the tail holds
    TAPS = 2

    config: TransformerConfig
    deterministic: bool = True
    decode: bool = False
    prefill: bool = False

    @nn.compact
    def __call__(self, x, position_offset, positions=None,
                 block_tables=None, slots=None, lengths=None):
        cfg = self.config
        b, l, e = x.shape
        f32 = jnp.float32
        h, h_kv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_width
        group = h // h_kv
        latent, half = (h + h_kv) * d, h_kv * d // 2
        if positions is None:
            raise ValueError("CCAttention needs the resolved positions=")
        rpos = positions[None] if positions.ndim == 1 else positions
        cached = self.decode or self.prefill
        if block_tables is not None and not cached:
            raise ValueError(
                "block_tables= is the paged SERVING cache layout; it "
                "requires decode or prefill mode")

        proj_in = nn.Dense(latent + 2 * half, use_bias=False,
                           dtype=cfg.dtype, name="qkv")(x)
        normal = nn.initializers.normal(0.02)
        conv1_w = self.param("conv1_kernel", normal, (self.TAPS, latent))
        conv1_b = self.param("conv1_bias", nn.initializers.zeros, (latent,))
        conv2_w = self.param("conv2_kernel", normal, (h + h_kv, self.TAPS, d, d))
        conv2_b = self.param("conv2_bias", nn.initializers.zeros, (latent,))
        k_temp = self.param("k_temp", nn.initializers.ones, (h_kv,))

        # ---- the tail this call starts from ----
        pos = jnp.asarray(position_offset, jnp.int32)
        tail_var = None
        if cached:
            if block_tables is not None:
                if pos.ndim != 1 or slots is None or lengths is None:
                    raise ValueError(
                        "paged CCAttention takes a [B] position_offset "
                        "vector and slots= and lengths= (each row's slot "
                        "in the tail leaf and its real length)")
                tail_var = self.variable("cache", "tail", _need_pool)
                held = tail_var.value[slots]
            else:
                tail_var = self.variable(
                    "cache", "tail",
                    lambda: jnp.zeros((b, cfg.cca_tail_width), cfg.dtype))
                held = tail_var.value
            starts = pos if pos.ndim == 1 else jnp.full((b,), pos)
            prev = jnp.where((starts == 0)[:, None],
                             jnp.zeros((), held.dtype), held)
        else:
            prev = jnp.zeros((b, cfg.cca_tail_width), cfg.dtype)
        prev = prev.astype(cfg.dtype)[:, None]  # [B, 1, width]

        def shifted(cur, before):
            """``cur`` one position later, ``before`` in front."""
            return jnp.concatenate([before, cur[:, :-1]], axis=1)

        # ---- latents, convolutions, values ----
        u, vv = proj_in[..., :latent], proj_in[..., latent:]
        u_prev = shifted(u, prev[..., :latent])
        c1 = (conv1_w[0].astype(f32) * u_prev.astype(f32)
              + conv1_w[1].astype(f32) * u.astype(f32)
              + conv1_b.astype(f32)).astype(cfg.dtype)
        c1_prev = shifted(c1, prev[..., latent:2 * latent])
        taps = jnp.concatenate(
            [c1_prev.reshape(b, l, h + h_kv, d),
             c1.reshape(b, l, h + h_kv, d)], axis=-1)  # [B, L, heads, 2D]
        c2 = jnp.einsum(
            "blgk,gkd->blgd", taps,
            conv2_w.astype(cfg.dtype).reshape(h + h_kv, 2 * d, d),
            preferred_element_type=f32,
        ) + conv2_b.astype(f32).reshape(h + h_kv, d)
        qt = u[..., :h * d].astype(f32).reshape(b, l, h_kv, group, d)
        kt = u[..., h * d:].astype(f32).reshape(b, l, h_kv, d)
        mq = 0.5 * (qt + kt[:, :, :, None])
        mk = 0.5 * (jnp.mean(qt, axis=3) + kt)
        q = c2[:, :, :h] + mq.reshape(b, l, h, d)
        k = c2[:, :, h:] + mk

        def unit(t):
            return t * jax.lax.rsqrt(
                jnp.sum(jnp.square(t), -1, keepdims=True) + 1e-12)

        q = unit(q) * (d ** 0.5)
        k = unit(k) * (d ** 0.5) * k_temp.astype(f32)[:, None]
        q = _rope_rotate(q, rpos, cfg.rope_theta, cfg.rotary_share)
        k = _rope_rotate(k, rpos, cfg.rope_theta, cfg.rotary_share)
        q, k = q.astype(cfg.dtype), k.astype(cfg.dtype)
        v_now = vv[..., :half]
        v_before = shifted(vv[..., half:], prev[..., 2 * latent:])
        v = jnp.concatenate([v_now, v_before], axis=-1).reshape(
            b, l, h_kv, d)

        if tail_var is not None:
            rows = jnp.concatenate([u, c1, vv[..., half:]], axis=-1)
            if lengths is None:
                last = rows[:, -1]
            else:  # the row's last REAL position, not its padding's
                at = jnp.maximum(lengths, 1) - 1
                last = jnp.take_along_axis(rows, at[:, None, None],
                                           axis=1)[:, 0]
            last = last.astype(tail_var.value.dtype)
            if block_tables is not None:
                tail_var.value = tail_var.value.at[slots].set(last)
            else:
                tail_var.value = last

        if block_tables is not None:
            # the K/V pool, as Attention's paged branch keeps it: the
            # chunk scattered at its absolute positions, then attention
            # against the chain through the rule's read (a tick's
            # ``group`` rows a narrow head take the fused kernel on a TPU)
            from pytorch_distributed_tpu.ops.attention import paged_attention

            ck = self.variable("cache", "key", _need_pool)
            cv = self.variable("cache", "value", _need_pool)
            block_len = ck.value.shape[-2]
            gather_impl = attention_ops.default_gather_impl(rows=l * group)
            p = pos[:, None] + jnp.arange(l)
            blk = jnp.take_along_axis(block_tables, p // block_len, axis=1)
            at = (blk.reshape(-1), (p % block_len).reshape(-1))
            ck.value = ck.value.at[at].set(
                k.astype(ck.value.dtype).reshape(b * l, h_kv * d))
            cv.value = cv.value.at[at].set(
                v.astype(cv.value.dtype).reshape(b * l, h_kv * d))
            out = paged_attention(q, ck.value, cv.value, block_tables, p,
                                  gather_impl=gather_impl)
        elif self.decode:
            # generate()'s dense cache: one token a request against
            # [B, max_seq_len, H_kv, D]
            assert l == 1, f"decode mode processes one token/step, got {l}"
            shape = (b, cfg.max_seq_len, h_kv, d)
            ck = self.variable("cache", "key",
                               lambda: jnp.zeros(shape, cfg.dtype))
            cv = self.variable("cache", "value",
                               lambda: jnp.zeros(shape, cfg.dtype))
            at = (jnp.arange(b), starts)
            ck.value = ck.value.at[at].set(k[:, 0])
            cv.value = cv.value.at[at].set(v[:, 0])
            qg = (q.astype(f32) * d ** -0.5).reshape(b, 1, h_kv, group, d)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, ck.value.astype(f32))
            seen = (jnp.arange(cfg.max_seq_len)[None, None, None, None]
                    <= starts[:, None, None, None, None])
            pr = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
            out = jnp.einsum("bhgqk,bkhd->bqhgd", pr,
                             cv.value.astype(f32)).reshape(
                b, 1, h, d).astype(cfg.dtype)
        else:
            if self.prefill:
                shape = (b, cfg.max_seq_len, h_kv, d)
                for name, new in (("key", k), ("value", v)):
                    var = self.variable(
                        "cache", name, lambda: jnp.zeros(shape, cfg.dtype))
                    var.value = jax.lax.dynamic_update_slice(
                        var.value, new.astype(cfg.dtype), (0, pos, 0, 0))
            kw, vw = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
            if cfg.attention == "blockwise":
                out = blockwise_attention(
                    q, kw, vw, causal=True,
                    block_size=min(cfg.block_size, l),
                    q_offset=position_offset, k_offset=position_offset)
            elif cfg.attention == "flash":
                from pytorch_distributed_tpu.ops.flash_attention import (
                    flash_attention,
                )

                out = flash_attention(q, kw, vw, causal=True)
            else:
                out = dense_attention(
                    q, kw, vw, causal=True,
                    q_offset=position_offset, k_offset=position_offset)
        out = nn.DenseGeneral(e, axis=(-2, -1), use_bias=False,
                              dtype=cfg.dtype, name="proj")(out)
        if cfg.dropout:
            out = nn.Dropout(cfg.dropout,
                             deterministic=self.deterministic)(out)
        return out


#: the ``attn_kind``s whose layer runs the delta rule, of those that keep a
#: float32 recurrent state a request (``SLOT_STATE_LAYERS``)
DELTA_RULE_KINDS = frozenset({"kda", "gdn"})


def slot_state_update(kinds) -> str:
    """Which update of the recurrent state a paged decode tick compiles for
    layers of the ``attn_kind``s ``kinds`` (a layer asks with its own, the
    engine with the stack's: ``PagedEngine`` puts the answer on
    ``pool.alloc``'s span): the ONE place it is decided, from the backend
    and the layer kind. ``"pallas"``: ``ops/state_update.py``'s kernel on
    the cache leaf where it lies, a head's state read once and written
    once, for the delta rule (``KDAttention``, ``GatedDeltaNet``) on a TPU.
    ``"xla"``: the ``jax.numpy`` update (``delta_rule_update``,
    ``ssm_update``) around a gather, two selects and a scatter, on every
    other backend (the kernel would run in the Pallas interpreter there) and
    for ``Mamba2Mixer`` everywhere: its reductions run over the state's
    lanes, and such a kernel read 21% SLOWER on the chip than XLA's fusions
    (PERF.md section 6, PR 46 / PR 47). ``""``: no layer keeps a state.
    Every other program (a chunk program, ``generate``'s own cache) runs the
    ``jax.numpy`` spelling everywhere."""
    kinds = set(kinds)
    if not kinds & set(SLOT_STATE_LAYERS):
        return ""
    on_chip = jax.default_backend() == "tpu"
    return "pallas" if on_chip and kinds & DELTA_RULE_KINDS else "xla"


def delta_rule_update(s, q_t, k_t, v_t, a_t, b_t):
    """One token of the delta rule: ``s`` [B, H, D, D], ``q_t``, ``k_t``,
    ``v_t`` [B, H, D], the decay ``a_t`` [B, H, D] a channel or [B, H, 1] a
    head, beta [B, H]. Returns (the new state, ``o_t``). Float32 multiplies
    and sums on the vector unit: what the new state shows the query is what
    the decayed old one shows it plus the written row's share, ``S'^T q =
    S^T (alpha * q) + (v - seen) (beta k . q)``, so both reductions read the
    OLD state and nothing reads the state just written. This spelling is the
    specification and what every program but a TPU's paged decode tick
    runs; XLA compiles it as THREE passes over the state (a fusion reads it
    for both reductions, a second reads it again and writes the new one), so
    that tick runs ``ops/state_update.py::delta_rule_tick`` instead
    (``slot_state_update``): the same sums on the cache leaf, once read and
    once written."""
    seen = jnp.sum((k_t * a_t)[..., None] * s, axis=-2)
    read = jnp.sum((q_t * a_t)[..., None] * s, axis=-2)
    write = b_t[..., None] * k_t  # [B, H, D]: beta k
    new = v_t - seen
    s = a_t[..., None] * s + write[..., None] * new[..., None, :]
    return s, read + new * jnp.sum(write * q_t, -1, keepdims=True)


def _in_blocks(x, n: int, c: int):
    """``x`` [B, L, ...] as ``n`` blocks of ``c`` positions, the scan's
    axis first: [n, B, c, ...], zeros behind L."""
    b, l = x.shape[:2]
    x = jnp.pad(x, ((0, 0), (0, n * c - l)) + ((0, 0),) * (x.ndim - 2))
    return jnp.moveaxis(x.reshape((b, n, c) + x.shape[2:]), 1, 0)


def delta_rule_blocks(s0, q, k, v, g, beta, block: int):
    """The delta rule over a sequence, ``block`` positions a step, from
    state ``s0`` [B, H, D, D]: ``q``, ``k``, ``v`` are [B, L, H, D], the
    log decay ``g`` [B, L, H, D] a channel or [B, L, H, 1] a head (the
    scalar decay is the special case: every product below broadcasts it),
    ``beta`` [B, L, H], all float32. Returns (the state after position L -
    1, every position's ``o`` [B, L, H, D]). The same function as a token
    at a time: inside a block, with ``G_t`` the running sum of ``g`` and
    ``u_t = beta_t (v_t - k_t^T Diag(alpha_t) S_{t-1})`` the row a token
    writes,

        S_t = Diag(e^{G_t}) S_0 + sum_{s<=t} Diag(e^{G_t - G_s}) k_s u_s^T
        u_t = beta_t (v_t - (k_t e^{G_t})^T S_0
                      - sum_{s<t} (k_t e^{G_t - G_s} . k_s) u_s)

    so the ``u`` of a block solve a unit lower-triangular system by
    forward substitution, the state is read and written once a block and
    the products with it run on the matrix unit at the highest precision.
    Every decay is a ratio of a later to an earlier position, at most 1:
    nothing overflows however strong the gate."""
    b, l, h, d = q.shape
    c = min(block, l)
    n = -(-l // c)
    lower = jnp.tril(jnp.ones((c, c), bool))
    high = jax.lax.Precision.HIGHEST

    def step(s, xs):
        q, k, v, g, beta = xs
        run = jnp.cumsum(g, axis=1)  # G_t [B, c, H, D]
        # e^{G_t - G_s} for s <= t, 0 above the diagonal [B, t, s, H, D]
        decay = jnp.exp(jnp.where(
            lower[None, :, :, None, None],
            run[:, :, None] - run[:, None, :], -jnp.inf))
        kk = jnp.sum(k[:, :, None] * k[:, None, :] * decay, axis=-1)
        qk = jnp.sum(q[:, :, None] * k[:, None, :] * decay, axis=-1)
        grown = jnp.exp(run)
        rhs = beta[..., None] * (v - jnp.einsum(
            "bthk,bhkv->bthv", k * grown, s, precision=high))
        below = beta[:, :, None] * jnp.where(
            jnp.tril(lower, -1)[None, :, :, None], kk, 0.0)
        u = jnp.zeros_like(rhs)
        for t in range(c):  # forward substitution, a row a step
            u = u.at[:, t].set(rhs[:, t] - jnp.sum(
                below[:, t][..., None] * u, axis=1))
        o = jnp.einsum("bthk,bhkv->bthv", q * grown, s, precision=high
                       ) + jnp.sum(qk[..., None] * u[:, None], axis=2)
        s = grown[:, -1][..., None] * s + jnp.einsum(
            "bshk,bshv->bhkv", k * jnp.exp(run[:, -1:] - run), u,
            precision=high)
        return s, o

    s1, o = jax.lax.scan(step, s0, tuple(_in_blocks(x, n, c)
                                         for x in (q, k, v, g, beta)))
    return s1, jnp.moveaxis(o, 0, 1).reshape(b, n * c, h, d)[:, :l]


class _SlotStateAttention(nn.Module):
    """What the recurrent layers (``KDAttention``, ``GatedDeltaNet``,
    ``Mamba2Mixer``) share: a request carries from one call to the next no
    K/V row but a float32 STATE (``cache/state``: ``[H, D, D]`` for the
    delta rule, ``[H, P, N]`` for Mamba-2) and the convolutions'
    last ``TAPS - 1`` inputs (``cache/conv``): one row a request, zero for
    a row that starts at position 0 whatever the row held, advanced over
    the row's REAL positions only (``lengths``). In the paged layout the
    leaves are ``[n_slots + 1, ...]``. A chunk program reads and writes the
    rows ``slots`` names (the last row is the trash row of padding jobs)
    and runs the recurrence ``BLOCK`` positions a step
    (``delta_rule_blocks``, ``ssm_blocks``); a decode tick's row ``i`` IS
    slot ``i`` (the engine's tick has a lane a slot), so the tick updates
    the leaves where they lie, and a lane that is not live (``lengths`` 0:
    inactive, or in mid-prefill) keeps what it held. Where
    ``slot_state_update`` answers ``"pallas"`` for the subclass's ``KIND``
    the tick's update is ``ops/state_update.py``'s kernel: it takes the leaf
    itself, aliased onto its output, and the two selects (a fresh row's
    zeros, a dead lane's held bits) are its flags a lane."""

    #: the subclass's ``attn_kind``
    KIND = None
    #: taps of the depthwise convolutions: the current token and three
    #: before it
    TAPS = 4
    #: positions one step of the sequence recurrence takes
    BLOCK = 16

    config: TransformerConfig
    deterministic: bool = True
    decode: bool = False
    prefill: bool = False

    @nn.nowrap
    def _held(self, b, l, position_offset, block_tables, slots, lengths,
              state, conv_width):
        """The state and the convolution inputs this call starts from:
        ``(s0, c0, real, keep)``, ``real`` each row's real positions and
        ``keep(window, s1)`` what writes the row's new state and last
        inputs back (``window``: ``c0`` in front of this call's inputs).
        ``state`` is the shape of ONE row's state, the subclass's own
        (``[H, D, D]`` for the delta rule, ``[H, P, N]`` for Mamba-2).
        Where the call is a paged decode tick's token and
        ``slot_state_update`` answers ``"pallas"``, ``s0`` is None: nothing
        gathers the rows, and ``keep`` takes for ``s1`` the kernel's update
        ``(leaf, fresh=, live=) -> (leaf, out)`` (``ops/state_update.py``)
        and returns its ``out``."""
        cfg, f32, taps = self.config, jnp.float32, self.TAPS
        cached = self.decode or self.prefill
        paged = block_tables is not None
        if paged and not cached:
            raise ValueError(
                "block_tables= is the paged SERVING cache layout; it "
                "requires decode or prefill mode")
        pos = jnp.asarray(position_offset, jnp.int32)
        state_var = conv_var = None
        tick = paged and self.decode
        # a tick's token where the kernel works on the leaf
        kernel = tick and l == 1 and slot_state_update(
            (self.KIND,)) == "pallas"
        if cached:
            if paged:
                if pos.ndim != 1 or lengths is None or (
                        slots is None and not tick):
                    raise ValueError(
                        f"paged {type(self).__name__} takes a [B] "
                        "position_offset vector and lengths= (each row's "
                        "real length), and a chunk program slots= (each "
                        "row's slot)")
                state_var = self.variable("cache", "state", _need_pool)
                conv_var = self.variable("cache", "conv", _need_pool)
                if tick and state_var.value.shape[0] != b + 1:
                    raise ValueError(
                        "a paged decode tick has a lane a slot: row i reads "
                        f"and writes slot i's state, got {b} rows over "
                        f"{state_var.value.shape[0] - 1} slots")
                rows = slice(0, b) if tick else slots
                held_s = None if kernel else state_var.value[rows]
                held_c = conv_var.value[rows]
            else:
                state_var = self.variable(
                    "cache", "state",
                    lambda: jnp.zeros((b,) + state, f32))
                conv_var = self.variable(
                    "cache", "conv",
                    lambda: jnp.zeros((b, taps - 1, conv_width), cfg.dtype))
                held_s, held_c = state_var.value, conv_var.value
            starts = pos if pos.ndim == 1 else jnp.full((b,), pos)
            fresh = starts == 0
            s0 = None if kernel else jnp.where(
                fresh[:, None, None, None], 0.0, held_s)
            c0 = jnp.where(fresh[:, None, None],
                           jnp.zeros((), held_c.dtype), held_c)
        else:
            s0 = jnp.zeros((b,) + state, f32)
            c0 = jnp.zeros((b, taps - 1, conv_width), cfg.dtype)
        real = (jnp.full((b,), l, jnp.int32) if lengths is None
                else lengths.astype(jnp.int32))

        def keep(window, s1):
            if state_var is None:
                return None
            # the last T - 1 inputs behind the row's last REAL position
            at = real[:, None] + jnp.arange(taps - 1)[None, :]
            c1 = jnp.take_along_axis(window, at[:, :, None], axis=1)
            live = real > 0
            out = None
            if kernel:  # both selects are the kernel's
                leaf, out = s1(state_var.value, fresh=fresh, live=live)
            else:
                s1 = jnp.where(live[:, None, None, None], s1, held_s)
            c1 = jnp.where(live[:, None, None], c1.astype(held_c.dtype),
                           held_c)
            if paged:
                state_var.value = (leaf if kernel
                                   else state_var.value.at[rows].set(s1))
                conv_var.value = conv_var.value.at[rows].set(c1)
            else:
                state_var.value, conv_var.value = s1, c1
            return out

        return s0, c0, real, keep

    @nn.nowrap
    def _convolved(self, c0, pre, conv_w, bias=None):
        """(``c0`` in front of ``pre``, the SiLU of the depthwise causal
        convolution of ``TAPS`` taps over it, plus ``bias`` a channel where
        the layer has one, float32 [B, L, channels])."""
        l, f32 = pre.shape[1], jnp.float32
        window = jnp.concatenate([c0, pre], axis=1)
        conv = sum(conv_w[j].astype(f32) * window[:, j:j + l].astype(f32)
                   for j in range(self.TAPS))
        return window, nn.silu(conv if bias is None
                               else conv + bias.astype(f32))


def _unit(t):
    """``t`` L2-normed over its last axis (epsilon 1e-6 under the root)."""
    return t * jax.lax.rsqrt(jnp.sum(jnp.square(t), -1, keepdims=True) + 1e-6)


def _delta_rule_token(s0, keep, window, *token):
    """One token of the delta rule from what ``_held`` gave, the state kept:
    ``token`` is ``delta_rule_update``'s operands behind the state. Returns
    ``o`` [B, 1, H, D]. ``s0`` None is a paged decode tick on the kernel:
    the update runs on the cache leaf inside ``keep``."""
    if s0 is None:
        from pytorch_distributed_tpu.ops.state_update import delta_rule_tick

        return keep(window, lambda leaf, **flags: delta_rule_tick(
            leaf, *token, **flags))[:, None]
    s1, o = delta_rule_update(s0, *token)
    o = o[:, None]
    keep(window, s1)
    return o


class KDAttention(_SlotStateAttention):
    """Delta-rule linear attention with a gate a channel (``attn_kind=
    "kda"``; Kimi Delta Attention, arXiv:2510.26692, as
    ``perfbench/references/ling.py`` writes it down).

    One fused projection takes the normed state to q~, k~ and v~ (``H * D``
    channels each); each channel runs through a depthwise causal
    convolution of ``T`` taps and a SiLU; q and k are L2-normed a head (q
    scaled by ``D ** -0.5``). A head keeps a ``D x D`` float32 STATE ``S``
    and a token updates it once::

        S <- Diag(alpha_t) S;  S <- S + beta_t k_t (v_t - k_t^T S)^T
        o_t = S^T q_t

    with ``alpha_t = exp(L * sigmoid(exp(A_log_h) * (x_t W_f + dt_bias)))``
    in ``(e^L, 1)`` a channel (``L`` = ``LOWER_BOUND``) and ``beta_t =
    sigmoid(x_t W_b)`` a head. The output is ``o_t`` RMS-normed a head,
    gated by ``sigmoid(x_t W_g)`` and projected back. The state's products
    are elementwise float32 multiplies and sums, never a matrix unit's
    rounded passes. The state and the convolutions' last inputs are a
    request's (``_SlotStateAttention``); ``TAPS`` is the published
    ``short_conv_kernel_size``.
    """

    #: the gate's lower bound (the published ``kda_lower_bound``): a
    #: channel's decay a token lies in ``(exp(LOWER_BOUND), 1)``
    LOWER_BOUND = -5.0
    KIND = "kda"

    @nn.compact
    def __call__(self, x, position_offset, block_tables=None, slots=None,
                 lengths=None):
        cfg = self.config
        b, l, e = x.shape
        f32 = jnp.float32
        h, d = cfg.num_heads, cfg.head_width
        inner = h * d

        def dense(width, name):
            return nn.Dense(width, use_bias=False, dtype=cfg.dtype,
                            name=name)(x)

        pre = dense(3 * inner, "qkv")
        conv_w = self.param("conv_kernel", nn.initializers.normal(0.02),
                            (self.TAPS, 3 * inner))
        gate_f = dense(inner, "gate_f")
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (inner,))
        a_log = self.param("A_log", nn.initializers.zeros, (h,))
        beta = jax.nn.sigmoid(dense(h, "beta").astype(f32))  # [B, L, H]
        gate_o = dense(inner, "gate_o")

        s0, c0, real, keep = self._held(
            b, l, position_offset, block_tables, slots, lengths, (h, d, d),
            3 * inner)
        window, qkv = self._convolved(c0, pre, conv_w)
        q, k, v = (qkv[..., i * inner:(i + 1) * inner].reshape(b, l, h, d)
                   for i in range(3))
        q, k = _unit(q) * d ** -0.5, _unit(k)
        rate = jnp.exp(a_log.astype(f32))[:, None]
        g = self.LOWER_BOUND * jax.nn.sigmoid(  # log alpha, in (L, 0)
            rate * (gate_f.astype(f32) + dt_bias.astype(f32)).reshape(
                b, l, h, d))

        if l == 1:
            o = _delta_rule_token(s0, keep, window, q[:, 0], k[:, 0],
                                  v[:, 0], jnp.exp(g[:, 0]), beta[:, 0])
        else:
            # a padding position neither decays the state nor writes to it
            valid = jnp.arange(l)[None, :] < real[:, None]
            s1, o = delta_rule_blocks(
                s0, q, k, v, jnp.where(valid[..., None, None], g, 0.0),
                jnp.where(valid[..., None], beta, 0.0), self.BLOCK)
            keep(window, s1)

        o = nn.RMSNorm(epsilon=cfg.norm_eps, dtype=f32, name="o_norm")(o)
        o = (o.reshape(b, l, inner) * jax.nn.sigmoid(
            gate_o.astype(f32))).astype(cfg.dtype)
        out = nn.Dense(e, use_bias=False, dtype=cfg.dtype, name="proj")(o)
        if cfg.dropout:
            out = nn.Dropout(cfg.dropout,
                             deterministic=self.deterministic)(out)
        return out


class GatedDeltaNet(_SlotStateAttention):
    """The gated delta rule (``attn_kind="gdn"``; Gated DeltaNet,
    arXiv:2412.06464, as ``perfbench/references/qwen3_next.py`` writes it
    down): ``KDAttention``'s recurrence with ONE decay a head a token,
    unbounded below.

    One fused projection takes the normed state to ``[q~ | k~ | v~ | z]``
    (``H_k D``, ``H_k D``, ``H_v D`` and ``H_v D`` channels: ``H_v`` =
    ``linear_num_heads`` state heads, ``H_k`` = ``linear_num_key_heads``
    q/k heads, ``D`` = ``linear_head_dim``) and a second to ``[b | a]``
    (``H_v`` each). ONE depthwise causal convolution of ``TAPS`` taps and a
    SiLU runs over the concatenated q~, k~, v~ channels; q and k are
    L2-normed a head (q scaled by ``D ** -0.5``); key head ``j`` serves
    state heads ``j * H_v / H_k ...``. A state head keeps ``S`` ``[D, D]``
    float32::

        S <- alpha_t S;  S <- S + beta_t k_t (v_t - S^T k_t)^T
        o_t = S^T q_t

    with ``beta_t = sigmoid(b_t)`` and ``alpha_t = exp(-exp(A_log_h) *
    softplus(a_t + dt_bias_h))`` in ``(0, 1)``. The output is ``o_t``
    RMS-normed a head (one learned scale of ``D``), gated by ``SiLU(z_t)``
    and projected back. The tick updates the state with float32 multiplies
    and sums (``delta_rule_update``); a sequence runs ``BLOCK`` positions a
    step through ``KDAttention``'s block solve, of which a decay shared by
    a head's channels is the special case (``delta_rule_blocks``). The
    state and the convolution's last inputs are a request's
    (``_SlotStateAttention``); ``TAPS`` is the published
    ``linear_conv_kernel_dim``.
    """

    KIND = "gdn"

    @nn.compact
    def __call__(self, x, position_offset, block_tables=None, slots=None,
                 lengths=None):
        cfg = self.config
        b, l, e = x.shape
        f32 = jnp.float32
        hv, hk, d = (cfg.linear_heads, cfg.linear_key_heads,
                     cfg.linear_head_width)
        keys, values = hk * d, hv * d
        conv_width = 2 * keys + values

        def dense(width, name):
            return nn.Dense(width, use_bias=False, dtype=cfg.dtype,
                            name=name)(x)

        qkvz = dense(conv_width + values, "qkvz")
        pre, z = qkvz[..., :conv_width], qkvz[..., conv_width:]
        conv_w = self.param("conv_kernel", nn.initializers.normal(0.02),
                            (self.TAPS, conv_width))
        ba = dense(2 * hv, "ba").astype(f32)
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (hv,))
        a_log = self.param("A_log", nn.initializers.zeros, (hv,))
        beta = jax.nn.sigmoid(ba[..., :hv])  # [B, L, H_v]
        g = -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(  # log alpha < 0
            ba[..., hv:] + dt_bias.astype(f32))

        s0, c0, real, keep = self._held(
            b, l, position_offset, block_tables, slots, lengths, (hv, d, d),
            conv_width)
        window, qkv = self._convolved(c0, pre, conv_w)
        q = qkv[..., :keys].reshape(b, l, hk, d)
        k = qkv[..., keys:2 * keys].reshape(b, l, hk, d)
        v = qkv[..., 2 * keys:].reshape(b, l, hv, d)
        q, k = _unit(q) * d ** -0.5, _unit(k)
        if hv != hk:  # a q/k head is shared by its group of state heads
            q = jnp.repeat(q, hv // hk, axis=2)
            k = jnp.repeat(k, hv // hk, axis=2)

        if l == 1:
            o = _delta_rule_token(s0, keep, window, q[:, 0], k[:, 0],
                                  v[:, 0], jnp.exp(g[:, 0])[..., None],
                                  beta[:, 0])
        else:
            # a padding position neither decays the state nor writes to it
            valid = jnp.arange(l)[None, :] < real[:, None]
            s1, o = delta_rule_blocks(
                s0, q, k, v, jnp.where(valid[..., None], g, 0.0)[..., None],
                jnp.where(valid[..., None], beta, 0.0), self.BLOCK)
            keep(window, s1)

        o = nn.RMSNorm(epsilon=cfg.norm_eps, dtype=f32, name="o_norm")(o)
        o = (o.reshape(b, l, values) * nn.silu(z.astype(f32))).astype(
            cfg.dtype)
        out = nn.Dense(e, use_bias=False, dtype=cfg.dtype, name="proj")(o)
        if cfg.dropout:
            out = nn.Dropout(cfg.dropout,
                             deterministic=self.deterministic)(out)
        return out


def ssm_update(s, x_t, b_t, c_t, a_t, dt_t):
    """One token of the Mamba-2 recurrence: ``s`` [B, H, P, N], ``x_t``
    [B, H, P], ``b_t``, ``c_t`` [B, G, N] (a group of ``H / G`` heads
    shares them), the decay ``a_t`` and the step ``dt_t`` [B, H]. Returns (the new
    state ``a S + dt x B^T``, ``y_t = S' C``). Float32 multiplies and sums on
    the vector unit; both results read the OLD state and the new one is
    written once: ``S' C = a (S C) + dt x (B . C)``, so nothing reads the
    state just written (``delta_rule_update``'s form; XLA keeps the read
    and the update in two fusions, two passes over the old state)."""
    share = s.shape[1] // b_t.shape[1]
    b_t, c_t = (jnp.repeat(t, share, axis=1) for t in (b_t, c_t))
    write = dt_t[..., None] * x_t  # [B, H, P]
    read = jnp.sum(s * c_t[..., None, :], axis=-1)
    s = a_t[..., None, None] * s + write[..., None] * b_t[..., None, :]
    return s, a_t[..., None] * read + write * jnp.sum(
        b_t * c_t, -1, keepdims=True)


def ssm_blocks(s0, x, bm, cm, g, dt, block: int):
    """The Mamba-2 recurrence over a sequence, ``block`` positions a step,
    from state ``s0`` [B, H, P, N]: ``x`` [B, L, H, P], ``bm``, ``cm``
    [B, L, G, N] (a group of ``H / G`` heads shares them), the log decay
    ``g`` and the step ``dt`` [B, L, H], all float32. Returns (the state
    after position L - 1, every position's ``y`` [B, L, H, P]). The same
    function as a token at a time: nothing is subtracted from what the state
    holds, so inside a block, with ``l_t`` the running sum of ``g``,

        y_t = sum_{s<=t} e^{l_t - l_s} (C_t . B_s) dt_s x_s + e^{l_t} S_0 C_t
        S_end = e^{l_last} S_0 + sum_s e^{l_last - l_s} dt_s x_s B_s^T

    and no system is solved: the state is read and written once a block and
    the products run on the matrix unit at the highest precision. Every
    exponent is a later position's sum less an earlier one's, at most 0:
    nothing overflows however fast a head forgets. A position with ``g`` 0
    and ``dt`` 0 (padding) neither decays the state nor writes to it."""
    b, l, h, p = x.shape
    groups = bm.shape[2]
    c = min(block, l)
    n = -(-l // c)
    lower = jnp.tril(jnp.ones((c, c), bool))
    high = jax.lax.Precision.HIGHEST

    def heads(t, axis=2):  # a group's values for each of its heads
        return jnp.repeat(t, h // groups, axis=axis)

    def step(s, xs):
        x, bm, cm, g, dt = xs
        run = jnp.cumsum(g, axis=1)  # l_t [B, c, H]
        # e^{l_t - l_s} for s <= t, 0 above the diagonal [B, t, s, H]
        decay = jnp.exp(jnp.where(lower[None, :, :, None],
                                  run[:, :, None] - run[:, None, :],
                                  -jnp.inf))
        cb = jnp.einsum("btgn,bsgn->btsg", cm, bm, precision=high)
        write = dt[..., None] * x  # [B, c, H, P]
        y = jnp.einsum("btsh,bshp->bthp", heads(cb, 3) * decay, write,
                       precision=high) + jnp.exp(run)[..., None] * jnp.einsum(
            "bthn,bhpn->bthp", heads(cm), s, precision=high)
        left = jnp.exp(run[:, -1:] - run)  # e^{l_last - l_s} [B, c, H]
        s = jnp.exp(run[:, -1])[..., None, None] * s + jnp.einsum(
            "bshp,bshn->bhpn", left[..., None] * write, heads(bm),
            precision=high)
        return s, y

    s1, y = jax.lax.scan(step, s0, tuple(_in_blocks(t, n, c)
                                         for t in (x, bm, cm, g, dt)))
    return s1, jnp.moveaxis(y, 0, 1).reshape(b, n * c, h, p)[:, :l]


class Mamba2Mixer(_SlotStateAttention):
    """The Mamba-2 state-space mixer (the "M" layers of a
    ``layer_pattern``; arXiv:2405.21060, as
    ``perfbench/references/nemotron_h.py`` writes it down).

    One projection takes the normed state to ``[z | x~ B~ C~ | dt]``
    (``H P``, ``H P + 2 G N`` and ``H`` channels: ``H`` =
    ``mamba_num_heads`` heads of ``P`` = ``mamba_head_dim``, ``G`` =
    ``mamba_n_groups`` groups whose heads share ``B`` and ``C`` of ``N`` =
    ``mamba_state_size``). ONE depthwise causal convolution of ``TAPS``
    taps WITH a bias and a SiLU runs over the x~, B~, C~ channels. A head
    keeps ``S`` ``[P, N]`` float32::

        S <- a_t S + Delta_t x_t B_t^T;   y_t = S C_t + D_h x_t

    with ``Delta_t = softplus(dt_t + dt_bias_h)`` and ``a_t =
    exp(-exp(A_log_h) Delta_t)``: the step scales the decay and the write,
    and nothing is subtracted from what the state holds. The output is ``y *
    SiLU(z)`` RMS-normed over each group's ``H P / G`` channels (one learned
    scale a channel) and projected back. The tick updates the state with
    float32 multiplies and sums (``ssm_update``); a sequence runs ``BLOCK``
    positions a step (``ssm_blocks``). The state and the convolution's last
    inputs are a request's (``_SlotStateAttention``); ``TAPS`` is the
    published ``conv_kernel``.
    """

    #: no triangular solve bounds a block, so a step takes four times the
    #: delta rule's positions
    BLOCK = 64
    KIND = "mamba2"

    @nn.compact
    def __call__(self, x, position_offset, block_tables=None, slots=None,
                 lengths=None):
        cfg = self.config
        b, l, e = x.shape
        f32 = jnp.float32
        h, p, n, groups = (cfg.mamba_num_heads, cfg.mamba_head_dim,
                           cfg.mamba_state_size, cfg.mamba_n_groups)
        inner, bc = h * p, groups * n
        conv_width = inner + 2 * bc

        zxbcdt = nn.Dense(2 * inner + 2 * bc + h, use_bias=False,
                          dtype=cfg.dtype, name="in_proj")(x)
        z = zxbcdt[..., :inner]
        pre = zxbcdt[..., inner:inner + conv_width]
        conv_w = self.param("conv_kernel", nn.initializers.normal(0.02),
                            (self.TAPS, conv_width))
        conv_b = self.param("conv_bias", nn.initializers.zeros,
                            (conv_width,))
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (h,))
        a_log = self.param("A_log", nn.initializers.zeros, (h,))
        skip = self.param("D", nn.initializers.ones, (h,))
        dt = jax.nn.softplus(zxbcdt[..., inner + conv_width:].astype(f32)
                             + dt_bias.astype(f32))  # [B, L, H]
        g = -jnp.exp(a_log.astype(f32)) * dt  # log a < 0

        s0, c0, real, keep = self._held(
            b, l, position_offset, block_tables, slots, lengths, (h, p, n),
            conv_width)
        window, xbc = self._convolved(c0, pre, conv_w, conv_b)
        xs = xbc[..., :inner].reshape(b, l, h, p)
        bm = xbc[..., inner:inner + bc].reshape(b, l, groups, n)
        cm = xbc[..., inner + bc:].reshape(b, l, groups, n)

        if l == 1:
            s1, y = ssm_update(s0, xs[:, 0], bm[:, 0], cm[:, 0],
                               jnp.exp(g[:, 0]), dt[:, 0])
            y = y[:, None]
        else:
            # a padding position neither decays the state nor writes to it
            valid = (jnp.arange(l)[None, :] < real[:, None])[..., None]
            s1, y = ssm_blocks(s0, xs, bm, cm, jnp.where(valid, g, 0.0),
                               jnp.where(valid, dt, 0.0), self.BLOCK)
        keep(window, s1)

        y = y + skip.astype(f32)[:, None] * xs
        y = y.reshape(b, l, inner) * nn.silu(z.astype(f32))
        # an RMS norm a group of channels, one learned scale a channel
        y = nn.RMSNorm(epsilon=cfg.norm_eps, dtype=f32, name="o_norm",
                       feature_axes=(-2, -1), reduction_axes=-1)(
            y.reshape(b, l, groups, inner // groups))
        out = nn.Dense(e, use_bias=False, dtype=cfg.dtype, name="proj")(
            y.reshape(b, l, inner).astype(cfg.dtype))
        if cfg.dropout:
            out = nn.Dropout(cfg.dropout,
                             deterministic=self.deterministic)(out)
        return out


#: the layers that keep a float32 recurrent state a request, by
#: ``attn_kind``
SLOT_STATE_LAYERS = {
    c.KIND: c for c in (KDAttention, GatedDeltaNet, Mamba2Mixer)}


class MLAttention(nn.Module):
    """Multi-head latent attention (``attn_kind="mla"``; DeepSeek-V2,
    arXiv:2405.04434), in the two spellings the served configurations
    publish: ``perfbench/references/ling.py``'s (no query compression,
    values as wide as the unrotated keys, a gate a head) and
    ``perfbench/references/glm4_moe_lite.py``'s (a compressed query, values
    wider than the unrotated keys, no gate).

    ``H`` heads of ``D`` unrotated and ``R`` rotated query dims and ``V``
    value dims (``v_head_dim``; None: ``D``). The queries come straight
    from the token (``q``) or, with ``q_lora_rank``, from a compressed one:
    ``c_q = RMSNorm(x W_qa)``, ``[q_nope_h; q_rope_h] = c_q W_qb,h``. Keys
    and values come from ONE latent a token: ``[c~; k_r] = x W_kva``, ``c =
    RMSNorm(c~)`` (``kv_lora_rank`` values), ``[k_nope_h; v_h] = c
    W_kvb,h`` (``D`` key columns, then ``V`` value columns a head); RoPE
    turns each head's ``R`` query dims and the one shared ``k_r``; scores
    are scaled by ``(D + R) ** -0.5``; with ``mla_head_gate`` a scalar gate
    a head (``sigmoid(x W_gh)``) scales the output before the projection.

    Without a cache (and in ``generate``'s dense prefill) keys and values
    are EXPANDED for every position. With a cache a token keeps one row
    for all heads, ``[c; rotated k_r; zeros]`` of ``latent_row_width``
    values (``cache/latent``), and attention runs FOLDED: ``q'_h = W_UK,h^T
    q_nope_h`` (``W_UK`` the ``D`` key columns of ``kv_b``) scores against
    ``c``, the probabilities average ``c`` and ``o_h = W_UV,h`` (its ``V``
    value columns) of that average. The row is its own key and its own
    value, so the paged read (``ops.attention.paged_attention``) takes the
    one pool leaf as both pools, one narrow head of ``latent_row_width``
    read with ``H`` query rows a position; the first ``kv_lora_rank``
    lanes of its output are kept. One function either way.
    """

    config: TransformerConfig
    deterministic: bool = True
    decode: bool = False
    prefill: bool = False

    @nn.compact
    def __call__(self, x, position_offset, positions=None,
                 block_tables=None):
        cfg = self.config
        b, l, e = x.shape
        f32 = jnp.float32
        h, d, v = cfg.num_heads, cfg.head_width, cfg.value_head_width
        c, r, row = cfg.kv_lora_rank, cfg.qk_rope_head_dim, (
            cfg.latent_row_width)
        scale = (d + r) ** -0.5
        if positions is None:
            raise ValueError("MLAttention needs the resolved positions=")
        rpos = positions[None] if positions.ndim == 1 else positions
        cached = self.decode or self.prefill
        paged = block_tables is not None
        if paged and not cached:
            raise ValueError(
                "block_tables= is the paged SERVING cache layout; it "
                "requires decode or prefill mode")

        if cfg.q_lora_rank is not None:
            c_q = nn.RMSNorm(epsilon=cfg.norm_eps, dtype=f32, name="q_a_norm")(
                nn.Dense(cfg.q_lora_rank, use_bias=False, dtype=cfg.dtype,
                         name="q_a")(x)).astype(cfg.dtype)
            q = nn.DenseGeneral((h, d + r), use_bias=False, dtype=cfg.dtype,
                                name="q_b")(c_q)
        else:
            q = nn.DenseGeneral((h, d + r), use_bias=False, dtype=cfg.dtype,
                                name="q")(x)
        kva = nn.Dense(c + r, use_bias=False, dtype=cfg.dtype,
                       name="kv_a")(x)
        latent = nn.RMSNorm(epsilon=cfg.norm_eps, dtype=f32,
                            name="kv_a_norm")(kva[..., :c]).astype(cfg.dtype)
        # a head's D key columns (W_UK), then its V value columns (W_UV)
        w_kvb = self.param("kv_b", nn.initializers.normal(0.02),
                           (c, h, d + v)).astype(cfg.dtype)
        if cfg.mla_head_gate:
            gate = jax.nn.sigmoid(nn.Dense(
                h, use_bias=False, dtype=cfg.dtype,
                name="gate")(x).astype(f32))
        q_nope = q[..., :d]
        q_rope = _rope_rotate(q[..., d:], rpos, cfg.rope_theta)
        k_rope = _rope_rotate(kva[..., c:][:, :, None, :], rpos,
                              cfg.rope_theta)  # [B, L, 1, R]

        pos = jnp.asarray(position_offset, jnp.int32)
        fold = paged or self.decode
        if cached:
            pad = jnp.zeros((b, l, row - c - r), cfg.dtype)
            new_rows = jnp.concatenate([latent, k_rope[:, :, 0], pad], -1)
            if paged:
                var = self.variable("cache", "latent", _need_pool)
            else:
                var = self.variable(
                    "cache", "latent",
                    lambda: jnp.zeros((b, cfg.max_seq_len, 1, row),
                                      cfg.dtype))
        if fold:
            # the query in the latent's own coordinates
            q_lat = jnp.einsum("blhd,chd->blhc", q_nope, w_kvb[..., :d],
                               preferred_element_type=f32).astype(cfg.dtype)
            q_row = jnp.concatenate(
                [q_lat, q_rope,
                 jnp.zeros((b, l, h, row - c - r), cfg.dtype)], -1)
        if paged:
            from pytorch_distributed_tpu.ops.attention import paged_attention

            if pos.ndim != 1:
                raise ValueError(
                    "paged mode takes a [B] position_offset vector (each "
                    "request's write start), got a scalar")
            pool = var.value
            block_len = pool.shape[-2]
            gather_impl = attention_ops.default_gather_impl(
                l * h, attention_ops.dense_gather_bytes(
                    b, block_tables.shape[1] * block_len, row))
            p = pos[:, None] + jnp.arange(l)
            blk = jnp.take_along_axis(block_tables, p // block_len, axis=1)
            pool = pool.at[blk.reshape(-1), (p % block_len).reshape(-1)].set(
                new_rows.astype(pool.dtype).reshape(b * l, row))
            var.value = pool
            o_lat = paged_attention(q_row, pool, pool, block_tables, p,
                                    scale=scale, gather_impl=gather_impl)
        elif self.decode:
            assert l == 1, f"decode mode processes one token/step, got {l}"
            starts = pos if pos.ndim == 1 else jnp.full((b,), pos)
            var.value = var.value.at[jnp.arange(b), starts].set(
                new_rows[:, 0][:, None])
            rows = var.value[:, :, 0].astype(f32)  # [B, max_len, row]
            s = jnp.einsum("bhc,bkc->bhk", q_row[:, 0].astype(f32) * scale,
                           rows)
            seen = (jnp.arange(cfg.max_seq_len)[None, None, :]
                    <= starts[:, None, None])
            pr = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
            o_lat = jnp.einsum("bhk,bkc->bhc", pr, rows)[:, None].astype(
                cfg.dtype)
        else:
            if self.prefill:
                var.value = jax.lax.dynamic_update_slice(
                    var.value, new_rows[:, :, None], (0, pos, 0, 0))
            kv = jnp.einsum("blc,chd->blhd", latent, w_kvb,
                            preferred_element_type=f32).astype(cfg.dtype)
            keys = jnp.concatenate(
                [kv[..., :d], jnp.broadcast_to(k_rope, (b, l, h, r))], -1)
            out = dense_attention(
                jnp.concatenate([q_nope, q_rope], -1), keys, kv[..., d:],
                causal=True, scale=scale, q_offset=position_offset,
                k_offset=position_offset)
        if fold:
            out = jnp.einsum("blhc,chd->blhd", o_lat[..., :c],
                             w_kvb[..., d:],
                             preferred_element_type=f32).astype(cfg.dtype)
        if cfg.mla_head_gate:
            out = (out.astype(f32) * gate[..., None]).astype(cfg.dtype)
        out = nn.DenseGeneral(e, axis=(-2, -1), use_bias=False,
                              dtype=cfg.dtype, name="proj")(out)
        if cfg.dropout:
            out = nn.Dropout(cfg.dropout,
                             deterministic=self.deterministic)(out)
        return out


class ResidualScale(nn.Module):
    """``(s_x * x + b_x) + (s_f * f + b_f)``: how a sublayer's output
    ``f`` joins the stream ``x`` under ``residual_scaling``; four learned
    vectors, float32 arithmetic."""

    @nn.compact
    def __call__(self, x, f):
        width = (x.shape[-1],)
        ones, zeros = nn.initializers.ones, nn.initializers.zeros
        s_x, b_x = self.param("x_scale", ones, width), self.param(
            "x_bias", zeros, width)
        s_f, b_f = self.param("f_scale", ones, width), self.param(
            "f_bias", zeros, width)
        f32 = jnp.float32
        out = ((s_x.astype(f32) * x.astype(f32) + b_x.astype(f32))
               + (s_f.astype(f32) * f.astype(f32) + b_f.astype(f32)))
        return out.astype(x.dtype)


class Block(nn.Module):
    config: TransformerConfig
    use_moe: bool = False
    deterministic: bool = True
    decode: bool = False
    prefill: bool = False
    #: this layer's attention where the stack mixes kinds
    #: (``TransformerConfig.attn_kind_at``); None: the config's, or under a
    #: ``layer_pattern`` a layer that is no attention
    attn_kind: Optional[str] = None

    @nn.compact
    def __call__(self, x, position_offset, positions=None,
                 block_tables=None, pass_index=None, slots=None,
                 lengths=None, router_state=None):
        """``slots`` and ``lengths`` ([B] int32) are the paged cache's
        operands for state that belongs to a request (``CCAttention``'s
        tail, ``KDAttention``'s state) and for the rows an expert layer may
        route (``lengths`` real positions a row; None: all). A dropless
        expert block takes the previous block's ``router_state`` and
        returns ``(x, router_state)``; every other block returns ``x``.
        Under a ``layer_pattern`` the block is ``x + f(ln1(x))`` with ``f``
        ONE of the sublayers (``attn``, ``moe``, ``mlp_*``)."""
        cfg = self.config
        # under a ``layer_pattern`` the block is ONE sublayer behind ``ln1``
        # and ``attn_kind`` None says that it is not an attention
        single = cfg.layer_pattern is not None
        attn_kind = self.attn_kind or (None if single else cfg.attn_kind)

        def joins(x, out, sublayer: int):
            """The stream after a sublayer's output has joined it."""
            if cfg.residual_scaling:
                return ResidualScale(name=f"rs{sublayer}")(x, out)
            if cfg.post_norm:
                out = _norm(cfg, f"ln{sublayer}_post")(out).astype(cfg.dtype)
            return x + out

        def attend(h):
            mode = dict(deterministic=self.deterministic, decode=self.decode,
                        prefill=self.prefill, name="attn")
            if attn_kind == "cca":
                return CCAttention(cfg, **mode)(
                    h, position_offset, positions, block_tables, slots,
                    lengths)
            if attn_kind in SLOT_STATE_LAYERS:
                return SLOT_STATE_LAYERS[attn_kind](cfg, **mode)(
                    h, position_offset, block_tables, slots, lengths)
            if attn_kind == "mla":
                return MLAttention(cfg, **mode)(
                    h, position_offset, positions, block_tables)
            return Attention(cfg, **mode)(
                h, position_offset, positions, block_tables, pass_index)

        def dropless(h):
            """(the dropless expert layer's output, its router state)"""
            from pytorch_distributed_tpu.models.moe import DroplessMoE

            live = (None if lengths is None else
                    jnp.arange(x.shape[1])[None, :] < lengths[:, None])
            one_matrix = dict(
                router=cfg.moe_router, top_k=cfg.moe_top_k,
                n_group=cfg.moe_n_group, topk_group=cfg.moe_topk_group,
                routed_scale=cfg.moe_routed_scale,
                shared_dim=cfg.moe_shared_dim, held=cfg.experts_held,
                shared_gate=cfg.moe_shared_gate,
                relu2=cfg.mlp == "relu2",
            ) if cfg.moe_router != "mlp" else {}
            out, state = DroplessMoE(
                n_experts=cfg.n_experts, moe_dim=cfg.moe_dim,
                router_dim=cfg.router_dim, norm_eps=cfg.norm_eps,
                dtype=cfg.dtype, name="moe", **one_matrix,
            )(h, router_state, live)
            if cfg.dropout:
                out = nn.Dropout(cfg.dropout,
                                 deterministic=self.deterministic)(out)
            return out, state

        def dense_mlp(h):
            if cfg.model_axis:
                from pytorch_distributed_tpu.parallel.tensor import (
                    tp_copy,
                    tp_reduce,
                )

                h = tp_copy(h, cfg.model_axis)  # column-parallel mlp_up
            width = cfg.mlp_width // cfg.tp_size
            up = nn.Dense(width, use_bias=cfg.use_bias, dtype=cfg.dtype,
                          name="mlp_up")(h)
            if cfg.mlp == "swiglu":
                h = nn.silu(nn.Dense(width, use_bias=cfg.use_bias,
                                     dtype=cfg.dtype, name="mlp_gate")(h)) * up
            elif cfg.mlp == "relu2":
                h = jnp.square(nn.relu(up))
            else:
                h = nn.gelu(up)
            # Row-parallel mlp_down: bias-free (see Attention.proj).
            h = nn.Dense(cfg.embed_dim, use_bias=False, dtype=cfg.dtype,
                         name="mlp_down")(h)
            if cfg.model_axis:
                h = tp_reduce(h, cfg.model_axis)
            if cfg.dropout:  # after tp_reduce — see Attention
                h = nn.Dropout(cfg.dropout,
                               deterministic=self.deterministic)(h)
            return h

        h = _norm(cfg, "ln1")(x)
        if single:
            if attn_kind is not None:
                return joins(x, attend(h), 1)
            if self.use_moe:
                out, state = dropless(h)
                return joins(x, out, 1), state
            return joins(x, dense_mlp(h), 1)
        x = joins(x, attend(h), 1)
        h = _norm(cfg, "ln2")(x)
        if self.use_moe and cfg.moe_kind == "dropless":
            out, state = dropless(h)
            return joins(x, out, 2), state
        if self.use_moe:
            from pytorch_distributed_tpu.models.moe import MoEMLP

            out = MoEMLP(
                n_experts=cfg.n_experts,
                mlp_dim=cfg.embed_dim * cfg.mlp_ratio,
                capacity_factor=cfg.capacity_factor,
                aux_loss_weight=cfg.moe_aux_weight,
                top_k=cfg.moe_top_k,
                ep_size=cfg.ep_size,
                expert_axis=cfg.expert_axis,
                tp_size=cfg.tp_size,
                model_axis=cfg.model_axis,
                dtype=cfg.dtype,
                name="moe",
            )(h)
            if cfg.dropout:  # residual dropout, same placement as dense MLP
                out = nn.Dropout(cfg.dropout, deterministic=self.deterministic)(out)
            return joins(x, out, 2)
        return joins(x, dense_mlp(h), 2)


class TransformerLM(nn.Module):
    """Decoder-only LM over a (possibly sharded) token sequence.

    ``__call__(tokens [B, L_local], position_offset)`` → logits
    ``[B, L_local, vocab]`` (fp32). With attention="ring" this must run
    under shard_map on a mesh whose ``seq`` axis shards the length.
    """

    config: TransformerConfig

    @nn.compact
    def __call__(self, tokens, position_offset: jax.Array | int = 0,
                 train: bool = True, decode: bool = False,
                 prefill: bool = False, positions: jax.Array | None = None,
                 return_hidden: bool = False,
                 block_tables: jax.Array | None = None,
                 return_gates: bool = False,
                 slots: jax.Array | None = None,
                 lengths: jax.Array | None = None,
                 head_rows: jax.Array | None = None):
        """``slots`` and ``lengths`` ([B] int32, paged serving): each
        row's slot in the per-request cache leaves and its real length in
        this call (0: a padding job or an inactive lane, which an expert
        layer does not route). Configs whose cache is block chains only
        ignore both. ``head_rows`` ([B] int32): run the head at that one
        position of every row and return ``[B, 1, vocab]`` (a chunk
        program keeps one row a job; a head of 262k tokens over a whole
        chunk is most of the program's arithmetic)."""
        cfg = self.config
        if return_gates and cfg.ut_steps == 1:
            raise ValueError(
                "return_gates= is the looped stack's (ut_steps > 1): one "
                "exit gate a pass"
            )
        # Dropout is active only when train=True AND an rng is provided
        # (apply(..., rngs={"dropout": key}) — train/lm.py derives the key
        # from (seed, step, shard coords) so resumed runs are bit-identical).
        inference = decode or prefill
        deterministic = not (train and cfg.dropout > 0.0) or inference
        vp = cfg.uses_vocab_parallel()  # THE shared predicate — train/lm.py
        # and models/generate.py consult the same method, so the head/
        # embedding branch and the placement rules cannot diverge
        if vp:
            # Vocab-parallel embedding: each shard owns vocab rows
            # [r*V/tp, (r+1)*V/tp); out-of-range tokens look up a clipped
            # row, are zero-masked, and tp_reduce (psum forward, IDENTITY
            # backward — the Megatron g; a plain psum would transpose to
            # another psum and scale wte grads by tp) assembles the one
            # real row per token. The mask kills foreign rows'
            # cotangents, so each shard's wte grad lands only on the
            # rows it owns.
            from pytorch_distributed_tpu.parallel.tensor import tp_reduce

            v_loc = cfg.vocab_size // cfg.tp_size
            off = jax.lax.axis_index(cfg.model_axis) * v_loc
            loc = tokens - off
            ok = (loc >= 0) & (loc < v_loc)
            emb = nn.Embed(v_loc, cfg.embed_dim, dtype=cfg.dtype,
                           name="wte")(jnp.clip(loc, 0, v_loc - 1))
            x = tp_reduce(
                jnp.where(ok[..., None], emb, jnp.zeros((), emb.dtype)),
                cfg.model_axis,
            )
        else:
            wte = nn.Embed(
                cfg.vocab_size, cfg.embed_dim, dtype=cfg.dtype, name="wte"
            )
            x = wte(tokens)
        # ``positions`` ([L_local] i32) overrides the contiguous
        # offset+arange convention — required for the zigzag ring layout,
        # whose shards hold non-contiguous chunk pairs (train/lm.py
        # computes the chunk-map vector). Refuse silently-wrong math: a
        # zigzag config with no position vector would embed contiguous
        # wpe positions for non-contiguous tokens.
        if cfg.ring_layout == "zigzag" and positions is None:
            raise ValueError(
                "ring_layout='zigzag' requires the per-shard position "
                "vector (positions=): shards hold chunk pairs "
                "(r, 2s-1-r), so offset+arange wpe positions are wrong. "
                "Use the LM train/eval steps (train/lm.py), which compute "
                "it, and shard batches with shard_lm_batch(..., "
                "layout='zigzag')."
            )
        off = jnp.asarray(position_offset, jnp.int32)
        if off.ndim == 1 and not (
            (decode and tokens.shape[1] == 1) or block_tables is not None
        ):
            raise ValueError(
                "a [B] position_offset vector is the ragged DECODE "
                "convention (one token per request) or the paged serving "
                "convention (block_tables= set); prefill/training use a "
                "scalar offset or positions="
            )
        # ONE resolution of per-token absolute positions, feeding BOTH
        # the learned wpe lookup and (passed down to every block) the
        # rope rotation — the two can never disagree. Shapes: [L] shared,
        # [B, L] per-request, [B, 1] ragged decode, or [B, chunk] paged
        # chunk prefill (each request's chunk at its own start).
        if positions is not None:
            pos = positions
        elif off.ndim == 1:
            # per-request start positions [B] (ragged/paged serving)
            pos = off[:, None] + jnp.arange(tokens.shape[1])
        else:
            pos = off + jnp.arange(tokens.shape[1])
        if cfg.pos_embedding == "learned":
            x = x + nn.Embed(
                cfg.max_seq_len, cfg.embed_dim, dtype=cfg.dtype, name="wpe"
            )(pos)
        # rope: no wpe table — Attention rotates q/k from the same pos;
        # none: neither (the stack's recurrent layers carry the order)
        if cfg.dropout and not inference:
            x = nn.Dropout(cfg.dropout, deterministic=deterministic)(x)

        def stack():
            """The layer stack's modules, made once where they run."""
            return [
                Block(
                    cfg, deterministic=deterministic, decode=decode,
                    prefill=prefill, name=f"block{i}",
                    use_moe=cfg.moe_at(i),
                    attn_kind=cfg.attn_kind_at(i),
                )
                for i in range(cfg.num_layers)
            ], _norm(cfg, "ln_f")

        def one_pass(modules, x, t):
            blocks, ln_f = modules
            router_state = None  # the expert layers' second stream
            for block in blocks:
                x = block(x, position_offset, pos, block_tables, t, slots,
                          lengths, router_state)
                if isinstance(x, tuple):  # a dropless expert block
                    x, router_state = x
            return ln_f(x)

        gates = None
        if cfg.ut_steps == 1:
            x = one_pass(stack(), x, None)
        else:
            # The stack runs ut_steps times over the same parameters;
            # ln_f closes every pass and the normed state goes on.
            init = self.is_initializing()
            want_gates = return_gates or init

            def gate():
                return nn.Dense(1, dtype=jnp.float32, name="exit_gate")

            def looped_pass(modules, gate, x, t):
                z = one_pass(modules, x, t)
                lam = (jax.nn.sigmoid(gate(z)[..., 0]) if want_gates
                       else None)
                return z.astype(cfg.dtype), lam

            inference_cache = (decode or prefill) and not self.variables.get(
                "cache")
            if init or inference_cache:
                # parameters, or a dense cache, are made on this call: a
                # scan cannot make what it carries or broadcasts, so the
                # passes run unrolled (an init or an eval_shape, once)
                modules, g = stack(), gate()
                lams = []
                for t in range(cfg.ut_steps):
                    x, lam = looped_pass(modules, g, x, jnp.int32(t))
                    lams.append(lam)
                gates = jnp.stack(lams) if want_gates else None
            else:
                # ONE body of num_layers blocks under a ut_steps-trip
                # loop: the parameters are broadcast into it, the cache
                # is carried whole and indexed by the pass in place
                # (Attention), never sliced per pass.
                x, gates = nn.scan(
                    lambda _, x, t: looped_pass(stack(), gate(), x, t),
                    variable_broadcast="params", variable_carry="cache",
                    split_rngs={"params": False, "dropout": True},
                )(self, x, jnp.arange(cfg.ut_steps, dtype=jnp.int32))
        if head_rows is not None:
            x = jnp.take_along_axis(x, head_rows[:, None, None], axis=1)
        if cfg.tie_embeddings:
            # the head is wte's transpose: the tree has no lm_head leaf
            if return_hidden:
                raise ValueError(
                    "return_hidden= hands the caller lm_head's kernel for "
                    "the fused loss; a tied head (tie_embeddings) has none")
            return wte.attend(x.astype(cfg.dtype)).astype(jnp.float32)
        head = nn.Dense(
            cfg.vocab_size // cfg.tp_size if vp else cfg.vocab_size,
            use_bias=False, dtype=cfg.dtype, name="lm_head",
        )
        if return_hidden:
            # Fused-CE path (ops/fused_ce.py): the caller streams the
            # lm_head matmul into a blockwise logsumexp using
            # params["lm_head"]["kernel"] directly — the full [B, L, V]
            # fp32 logits never materialize. CAUTION: flax creates params
            # only for CALLED submodules, so init must always take the
            # logits path below (it does: create_lm_state applies with the
            # default return_hidden=False); apply-time skipping merely
            # leaves the existing lm_head params unused, which flax
            # tolerates — checkpoint layout identical either way.
            return (x, gates) if return_gates else x
        if vp:
            # column-parallel head: replicated input, vocab-sharded
            # output — the f-operator (identity fwd, psum bwd) collects
            # each shard's dx contribution, exactly like qkv/mlp_up
            from pytorch_distributed_tpu.parallel.tensor import tp_copy

            x = tp_copy(x, cfg.model_axis)
        logits = head(x).astype(jnp.float32)
        if vp:
            # full logits for sampling/eval callers: concatenate the
            # vocab shards in axis order (matches the shard offsets).
            # tp_all_gather, not lax.all_gather: downstream losses are
            # replicated over the model axis, and the raw gather's
            # psum_scatter transpose would scale grads by tp.
            from pytorch_distributed_tpu.parallel.tensor import tp_all_gather

            logits = tp_all_gather(logits, cfg.model_axis, dim=-1)
        # gates: [ut_steps, B, L] float32, the exit gate of every pass
        return (logits, gates) if return_gates else logits


def tiny_config(**overrides) -> TransformerConfig:
    """Small config for tests/CI."""
    defaults = dict(
        vocab_size=128, num_layers=2, num_heads=2, embed_dim=32,
        max_seq_len=256, dtype=jnp.float32,
    )
    defaults.update(overrides)
    return TransformerConfig(**defaults)
