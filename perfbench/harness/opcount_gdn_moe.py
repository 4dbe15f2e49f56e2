"""What a decode tick of a gated-delta-rule decoder needs: gated delta-rule
linear-attention layers (``attn_kind="gdn"``: ``linear_num_heads`` state
heads served by ``linear_num_key_heads`` q/k heads of ``linear_head_dim``)
with every ``layer_group_size``-th layer softmax attention over REAL keys and
values (``full_attn_kind="mha"``: ``num_heads`` gated query heads over
``num_kv_heads`` K/V heads of ``head_dim``), and in every layer a
softmax-routed expert layer with a gated shared expert, of whose experts this
chip holds ``experts_held``; an untied head over a slice of the vocabulary.
(``opcount_hybrid`` counts the sibling whose decay is a channel's and whose
full layer is latent.)

Read once a tick: every layer's attention, router, shared-expert and norm
weights; the three matrices of every HELD expert that took a pair
(``experts_hit`` of them a layer: a grouped product need not touch the
others); the head slice once and one embedding row a live lane; the live
lanes' recurrent state read and written once in float32 and their
convolution inputs read and written; the live context's key and value rows
once a full layer and one new row a live lane. Operations: a lane's matrices
(its pairs that landed here, one expert each), the state's update, attention
over the live context. Checked against a hand count in ``perfbench/tests``.
"""

from __future__ import annotations

#: taps of a delta-rule layer's convolution (``GatedDeltaNet.TAPS``)
CONV_TAPS = 4


def layer_kinds(program: dict) -> tuple:
    """(delta-rule layers, full-attention layers) of the stack."""
    n, g = program["num_layers"], program.get("layer_group_size", 0)
    full = sum(1 for i in range(n) if g and (i + 1) % g == 0)
    return n - full, full


def linear_widths(program: dict) -> tuple:
    """(key channels, value channels, channels under the convolution) of a
    delta-rule layer."""
    d = program["linear_head_dim"]
    keys = program["linear_num_key_heads"] * d
    values = program["linear_num_heads"] * d
    return keys, values, 2 * keys + values


def kv_row_values(program: dict) -> int:
    """Values of a token's key and value rows in a full layer."""
    return 2 * program["num_kv_heads"] * program["head_dim"]


def sublayer_params(program: dict) -> dict:
    """Parameters of one sublayer by kind: ``gdn`` (the fused q/k/v/z
    projection, the b/a projection, the convolution's taps, a rate and a
    bias a state head, the output norm and projection), ``full`` (the
    doubled q projection, k and v, the two norms a head, the output
    projection), ``routing`` (the router's matrix, the shared expert and
    its gate), ONE ``expert`` (three E x F matrices) and a layer's two
    ``norms``."""
    e, f = program["embed_dim"], program["moe_dim"]
    h, h_kv, a = (program["num_heads"], program["num_kv_heads"],
                  program["head_dim"])
    hv, d = program["linear_num_heads"], program["linear_head_dim"]
    keys, values, conv = linear_widths(program)
    shared = program.get("moe_shared_dim") or 0
    return {
        "gdn": (e * (conv + values) + e * 2 * hv + CONV_TAPS * conv + 2 * hv
                + d + values * e),
        "full": (e * h * a * (2 if program.get("attn_gate") else 1)
                 + e * 2 * h_kv * a
                 + (2 * a if program.get("qk_norm") else 0) + h * a * e),
        "routing": (e * program["n_experts"] + 3 * e * shared
                    + (e if program.get("moe_shared_gate") else 0)),
        "expert": 3 * e * f,
        "norms": 2 * e,
    }


def slot_state_bytes(program: dict, kv_bytes: int = 2) -> tuple:
    """(float32 recurrent state, convolution inputs) one slot holds, in
    bytes, over all delta-rule layers."""
    gdn, _ = layer_kinds(program)
    hv, d = program["linear_num_heads"], program["linear_head_dim"]
    _, _, conv = linear_widths(program)
    return gdn * hv * d * d * 4, gdn * (CONV_TAPS - 1) * conv * kv_bytes


def gdn_moe_decode_tick_need(program: dict, live_slots: float,
                             live_context: float, experts_hit: float,
                             pairs_here: float, weight_bytes: int = 2,
                             kv_bytes: int = 2) -> dict:
    """``{"flops", "bytes", "state_bytes", "kv_bytes"}`` one decode tick
    needs. ``live_context`` is the SUM of the live slots' context lengths,
    ``experts_hit`` the mean over the layers of held experts with at least
    one pair, ``pairs_here`` the (lane, expert) pairs that landed on a held
    expert in a layer. ``state_bytes`` is the part of ``bytes`` that is the
    recurrent state read and written, ``kv_bytes`` the part that is the
    live context's key and value rows read and each live lane's written."""
    e = program["embed_dim"]
    h, a = program["num_heads"], program["head_dim"]
    hv, d = program["linear_num_heads"], program["linear_head_dim"]
    gdn, full = layer_kinds(program)
    layers = gdn + full
    p = sublayer_params(program)
    always = (gdn * p["gdn"] + full * p["full"]
              + layers * (p["routing"] + p["norms"]) + e)
    head = e * program["vocab_size"]
    state, conv = slot_state_bytes(program, kv_bytes)
    state_bytes = live_slots * 2 * state
    rows_bytes = ((live_context + live_slots) * full
                  * kv_row_values(program) * kv_bytes)
    bytes_ = ((always + layers * experts_hit * p["expert"] + head
               + live_slots * e) * weight_bytes
              + state_bytes + live_slots * 2 * conv + rows_bytes)
    flops = (2.0 * (live_slots * (always + head)
                    + layers * pairs_here * p["expert"])
             + live_slots * gdn * 8 * hv * d * d
             + live_context * full * 2 * 2 * h * a)
    return {"flops": flops, "bytes": bytes_, "state_bytes": state_bytes,
            "kv_bytes": rows_bytes}
