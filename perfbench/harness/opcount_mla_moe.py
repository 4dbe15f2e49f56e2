"""What a decode tick of a latent-attention expert decoder needs: EVERY layer
multi-head latent attention (``attn_kind="mla"`` with no ``layer_group_size``:
``num_heads`` heads of ``head_dim`` unrotated and ``qk_rope_head_dim`` rotated
query dims and ``v_head_dim`` value dims over a latent of ``kv_lora_rank``,
the query compressed to ``q_lora_rank`` where the key is given) over a pool
of ONE row a token a layer that is the only cache, the first
``first_k_dense_replace`` layers with a dense SwiGLU MLP and every later one
with sigmoid-routed experts and a shared one, ALL of them held; an untied
head over the whole vocabulary. (``opcount_hybrid`` counts the sibling with
one latent layer in a period of delta-rule layers and a part of its experts.)

Read once a tick: every layer's attention and norm weights, the dense MLPs,
the routers and the shared experts; the three matrices of every expert that
took a pair (``experts_hit`` of them a layer: a grouped product need not
touch the others); the head once and one embedding row a live lane; the live
context's latent rows once a layer and one new row a live lane written; the
live lanes' float32 logits written. Operations: a lane's matrices (the fold's
two products are ``kv_b``'s columns, so its parameters count them; a pair
takes one expert), and attention over the live context in the latent's own
coordinates: a score and an average over the row's lanes for every head.
Checked against a hand count in ``perfbench/tests``.
"""

from __future__ import annotations


def layer_kinds(program: dict) -> tuple:
    """(layers with a dense MLP, layers with experts) of the stack."""
    n = program["num_layers"]
    dense = min(program.get("first_k_dense_replace", 0), n)
    return dense, n - dense


def latent_row(program: dict) -> int:
    """Values of a token's one cache row in a layer: the latent and the
    rotated key, padded to whole 128-lane tiles
    (``TransformerConfig.latent_row_width``)."""
    width = program["kv_lora_rank"] + program["qk_rope_head_dim"]
    return -(-width // 128) * 128


def sublayer_params(program: dict) -> dict:
    """Parameters of one sublayer by kind: ``mla`` (the query's
    down-projection, norm and up-projection, or its one matrix; the latent's
    down-projection and norm; ``kv_b``; the gate a head where the layer has
    one; the output projection), ``dense`` (a dense SwiGLU MLP), ``routing``
    (the router's matrix and bias, the shared expert), ONE ``expert`` (three
    E x F matrices) and a layer's two ``norms``."""
    e, f = program["embed_dim"], program["moe_dim"]
    h, d, r = (program["num_heads"], program["head_dim"],
               program["qk_rope_head_dim"])
    c = program["kv_lora_rank"]
    v = program.get("v_head_dim") or d
    q = program.get("q_lora_rank")
    shared = program.get("moe_shared_dim") or 0
    query = e * q + q + q * h * (d + r) if q else e * h * (d + r)
    gate = e * h if program.get("mla_head_gate", True) else 0
    return {
        "mla": (query + e * (c + r) + c + c * h * (d + v) + gate
                + h * v * e),
        "dense": 3 * e * program["mlp_dim"],
        "routing": (e * program["n_experts"] + program["n_experts"]
                    + 3 * e * shared),
        "expert": 3 * e * f,
        "norms": 2 * e,
    }


def mla_moe_decode_tick_need(program: dict, live_slots: float,
                             live_context: float, experts_hit: float,
                             pairs_here: float, weight_bytes: int = 2,
                             kv_bytes: int = 2) -> dict:
    """``{"flops", "bytes", "latent_bytes"}`` one decode tick needs.
    ``live_context`` is the SUM of the live slots' context lengths,
    ``experts_hit`` the mean over the expert layers of experts with at
    least one pair, ``pairs_here`` the (lane, expert) pairs a layer routed.
    ``latent_bytes`` is the part of ``bytes`` that is the live context's
    latent rows read and each live lane's row written."""
    e, h = program["embed_dim"], program["num_heads"]
    dense, expert = layer_kinds(program)
    layers = dense + expert
    p = sublayer_params(program)
    row = latent_row(program)
    always = (layers * (p["mla"] + p["norms"]) + dense * p["dense"]
              + expert * p["routing"] + e)
    head = e * program["vocab_size"]
    latent_bytes = (live_context + live_slots) * layers * row * kv_bytes
    bytes_ = ((always + expert * experts_hit * p["expert"] + head
               + live_slots * e) * weight_bytes
              + latent_bytes + live_slots * program["vocab_size"] * 4)
    flops = (2.0 * (live_slots * (always + head)
                    + expert * pairs_here * p["expert"])
             + live_context * layers * 2 * 2 * h * row)
    return {"flops": flops, "bytes": bytes_, "latent_bytes": latent_bytes}
