"""What a decode tick of a hybrid decoder needs: delta-rule linear-attention
layers (``attn_kind="kda"``) with every ``layer_group_size``-th layer latent
attention ("mla"), dense SwiGLU MLPs in the first ``first_k_dense_replace``
layers and a sigmoid-routed expert layer with a shared expert in the rest,
of whose experts this chip holds ``experts_held``; an untied head over a
slice of the vocabulary. (``opcount.decode_tick_need`` counts a GPT-2 tick,
``opcount_looped`` a looped dense one, ``opcount_cca_moe`` a CCA + top-1
one.)

Read once a tick: every layer's attention, router, shared-expert, dense-MLP
and norm weights; the three matrices of every HELD expert that took a pair
(``experts_hit`` of them an expert layer: a grouped product need not touch
the others); the head slice once and one embedding row a live lane; the
live lanes' recurrent state read and written once in float32 and their
convolution inputs read and written; the live context's latent rows once a
latent layer and one new row a live lane. Operations: a lane's matrices
(its pairs that landed here, one expert each), the state's update, latent
attention over the live context. Checked against a hand count in
``perfbench/tests``.
"""

from __future__ import annotations

#: taps of a linear-attention layer's convolutions (``KDAttention.TAPS``)
CONV_TAPS = 4


def layer_kinds(program: dict) -> tuple:
    """(linear-attention layers, latent layers, dense-MLP layers, expert
    layers) of the stack."""
    n, g = program["num_layers"], program.get("layer_group_size", 0)
    latent = sum(1 for i in range(n) if g and (i + 1) % g == 0)
    dense = min(program.get("first_k_dense_replace", 0), n)
    return n - latent, latent, dense, n - dense


def latent_row(program: dict) -> int:
    """Values of a token's one cache row: the latent and the rotated key,
    padded to whole 128-lane tiles (``TransformerConfig.latent_row_width``)."""
    return -(-(program["kv_lora_rank"] + program["qk_rope_head_dim"])
             // 128) * 128


def sublayer_params(program: dict) -> dict:
    """Parameters of one sublayer by kind: ``kda`` (the fused q/k/v
    projection, the convolutions' taps, the decay gate and its bias and
    rates, beta, the output gate, norm and projection), ``mla`` (q, the
    latent's down-projection and norm, the up-projection, the head gate,
    the output projection), ``dense`` (three E x mlp_dim matrices),
    ``routing`` (the router's matrix and bias and the shared expert), ONE
    ``expert`` (three E x F matrices) and a layer's two ``norms``."""
    e, h, d = program["embed_dim"], program["num_heads"], program["head_dim"]
    inner, taps = h * d, CONV_TAPS
    c, r = program["kv_lora_rank"], program["qk_rope_head_dim"]
    f, x = program["moe_dim"], program["n_experts"]
    return {
        "kda": (e * 3 * inner + taps * 3 * inner + e * inner + inner + h
                + e * h + e * inner + d + inner * e),
        "mla": (e * h * (d + r) + e * (c + r) + c + c * h * 2 * d + e * h
                + h * d * e),
        "dense": 3 * e * program["mlp_dim"],
        "routing": e * x + x + 3 * e * (program.get("moe_shared_dim") or 0),
        "expert": 3 * e * f,
        "norms": 2 * e,
    }


def slot_state_bytes(program: dict, kv_bytes: int = 2) -> tuple:
    """(float32 recurrent state, convolution inputs) one slot holds, in
    bytes, over all linear-attention layers."""
    h, d = program["num_heads"], program["head_dim"]
    kda, _, _, _ = layer_kinds(program)
    return kda * h * d * d * 4, kda * (CONV_TAPS - 1) * 3 * h * d * kv_bytes


def hybrid_decode_tick_need(program: dict, live_slots: float,
                            live_context: float, experts_hit: float,
                            pairs_here: float, weight_bytes: int = 2,
                            kv_bytes: int = 2) -> dict:
    """``{"flops", "bytes", "state_bytes"}`` one decode tick needs.
    ``live_context`` is the SUM of the live slots' context lengths,
    ``experts_hit`` the mean over the expert layers of held experts with at
    least one pair, ``pairs_here`` the (lane, expert) pairs that landed on
    a held expert in a layer. ``state_bytes`` is the part of ``bytes`` that
    is the recurrent state read and written."""
    e, h, d = program["embed_dim"], program["num_heads"], program["head_dim"]
    kda, mla, dense, moe = layer_kinds(program)
    p = sublayer_params(program)
    row = latent_row(program)
    always = (kda * p["kda"] + mla * p["mla"] + dense * p["dense"]
              + moe * p["routing"] + (kda + mla) * p["norms"] + e)
    head = e * program["vocab_size"]
    state, conv = slot_state_bytes(program, kv_bytes)
    state_bytes = live_slots * 2 * state
    bytes_ = ((always + moe * experts_hit * p["expert"] + head
               + live_slots * e) * weight_bytes
              + state_bytes + live_slots * 2 * conv
              + (live_context + live_slots) * mla * row * kv_bytes)
    flops = (2.0 * (live_slots * (always + head)
                    + moe * pairs_here * p["expert"])
             + live_slots * kda * 8 * h * d * d
             + live_context * mla * 2 * 2 * h * row)
    return {"flops": flops, "bytes": bytes_, "state_bytes": state_bytes}
