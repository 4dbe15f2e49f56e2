"""What a LOOPED decoder's decode tick needs (``opcount.decode_tick_need``
counts a GPT-2 tick: two MLP matrices, every weight once). A looped stack
of N layers runs U passes a token over the same parameters; pass t reads
and writes keys and values of its own, so the cache has U x N layers.

Weights are counted U times, not once: one pass's layer weights (4.93 GB
in bfloat16 for ouro-2.6b) are forty times the chip's 128 MiB of on-chip
memory, so nothing of pass t is left there when pass t + 1 comes for it,
and every pass reads them from HBM again. An algorithm that read them once
a tick would have to hold all four passes' activations of a layer at once,
which the data dependence (pass t + 1 of layer 0 needs pass t of layer N)
forbids for one token; the head and the embedding rows are read once.
Checked against a hand count in ``perfbench/tests``.
"""

from __future__ import annotations


def layer_matmul_params(program: dict) -> int:
    """Parameters of one layer that sit in matrix multiplications: q, k,
    v and o (4 E^2) and the MLP's matrices (three of E x F when gated,
    two otherwise). ``program`` is ``TransformerConfig``'s fields."""
    e = program["embed_dim"]
    f = program.get("mlp_dim") or e * program.get("mlp_ratio", 4)
    return 4 * e * e + (3 if program.get("mlp") == "swiglu" else 2) * e * f


def looped_decode_tick_need(program: dict, live_slots: float,
                            live_context: float, weight_bytes: int = 2,
                            kv_bytes: int = 2) -> tuple:
    """(operations, bytes) one decode tick of a looped decoder needs: the
    layers' matmul weights read once a PASS, the head once; the K and V
    rows of the live context read once in each of the U x N cache layers;
    one new K and V row a live slot a cache layer written; one embedding
    row a live slot read. Operations: the layers' matmuls U times and the
    head once for the live slots, attention over the live context in
    every cache layer. ``live_context`` is the SUM of the live slots'
    context lengths."""
    e, n = program["embed_dim"], program["num_layers"]
    u = program.get("ut_steps", 1)
    layers, head = n * layer_matmul_params(program), e * program["vocab_size"]
    cache_layers = u * n
    bytes_ = ((u * layers + head) * weight_bytes
              + live_slots * e * weight_bytes
              + (live_context + live_slots) * cache_layers * 2 * e * kv_bytes)
    flops = (live_slots * 2.0 * (u * layers + head)
             + live_context * cache_layers * 2 * 2 * e)
    return flops, bytes_
