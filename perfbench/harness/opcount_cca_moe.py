"""What a decode tick of a CCA + top-1-expert decoder needs
(``opcount.decode_tick_need`` counts a GPT-2 tick, ``opcount_looped`` a
looped dense one). A layer is attention inside a compressed latent
(``attn_kind="cca"``: ``H`` query and ``H_kv`` narrow heads of ``D``) and
an expert layer behind an MLP router; the head is the embedding's
transpose.

Read once a tick: every layer's attention, convolution, router, norm and
residual-scale weights; the three matrices of every expert THAT TOOK A
TOKEN (``experts_hit`` of them a layer: a grouped product need not touch
the others); the tied embedding once (the head; the live slots' embedding
rows are rows of it); the live context's K and V once a layer; the live
slots' tails read and written, and one new K and V row a live slot a
layer. Operations: a token's matrices (one expert each), attention over
the live context. Checked against a hand count in ``perfbench/tests``.
"""

from __future__ import annotations


def layer_params(program: dict) -> dict:
    """Parameters of one layer by part: ``attention`` (the fused
    projection, the output projection, both convolutions, the key
    temperature), ``router`` (the down projection, the MLP, its norm, mix
    and bias), ``scales`` (two norms, eight residual vectors), and ONE
    ``expert`` (three E x F matrices)."""
    e, d = program["embed_dim"], program["head_dim"]
    h, h_kv = program["num_heads"], program["num_kv_heads"]
    r, x, f = program["router_dim"], program["n_experts"], program["moe_dim"]
    latent = (h + h_kv) * d
    return {
        "attention": (e * (latent + h_kv * d) + h * d * e
                      + 2 * latent + latent  # first convolution, its bias
                      + (h + h_kv) * 2 * d * d + latent  # second, its bias
                      + h_kv),
        "router": e * r + 2 * r * r + r * x + r + 1 + x,
        "scales": 2 * e + 8 * e,
        "expert": 3 * e * f,
    }


def tail_width(program: dict) -> int:
    """Values a request holds a layer beside its K/V blocks."""
    h, h_kv, d = (program["num_heads"], program["num_kv_heads"],
                  program["head_dim"])
    return 2 * (h + h_kv) * d + h_kv * d // 2


def cca_moe_decode_tick_need(program: dict, live_slots: float,
                             live_context: float, experts_hit: float,
                             weight_bytes: int = 2,
                             kv_bytes: int = 2) -> tuple:
    """(operations, bytes) one decode tick needs. ``live_context`` is the
    SUM of the live slots' context lengths; ``experts_hit`` the mean over
    the layers of experts with at least one token."""
    e, n = program["embed_dim"], program["num_layers"]
    h, h_kv, d = (program["num_heads"], program["num_kv_heads"],
                  program["head_dim"])
    p = layer_params(program)
    always = p["attention"] + p["router"] + p["scales"]
    head = e * program["vocab_size"]
    bytes_ = (
        (n * (always + experts_hit * p["expert"]) + head + e) * weight_bytes
        + (live_context + live_slots) * n * 2 * h_kv * d * kv_bytes
        + live_slots * n * 2 * tail_width(program) * kv_bytes)
    flops = (live_slots * 2.0 * (n * (always + p["expert"]) + head)
             + live_context * n * 2 * 2 * h * d)
    return flops, bytes_
