"""What a decode tick of a state-space hybrid needs: a stack whose blocks are
ONE sublayer each, by a pattern string (``layer_pattern``): "M" a Mamba-2
mixer (``mamba_num_heads`` heads of ``mamba_head_dim`` with a float32 state of
``mamba_state_size`` a head, B and C shared by the heads of each of
``mamba_n_groups`` groups), "*" position-free softmax attention over REAL keys
and values (``num_heads`` query heads over ``num_kv_heads`` K/V heads of
``head_dim``), "E" a sigmoid-routed expert layer whose experts are TWO
matrices (squared ReLU) beside a shared one, of whose experts this chip holds
``experts_held``, "-" a dense MLP of two matrices; an untied head over a
slice of the vocabulary. (``opcount_gdn_moe`` and ``opcount_hybrid`` count the
siblings whose recurrent layer is a delta rule and whose block is attention
then MLP.)

Read once a tick: every block's non-expert weights (projections, router,
shared expert, norm); both matrices of every HELD expert that took a pair
(``experts_hit`` of them an expert block: a grouped product need not touch
the others); the head slice once and one embedding row a live lane; the live
lanes' Mamba-2 state read and written once in float32 and their convolution
inputs read and written; the live context's key and value rows once an
attention block and one new row a live lane. Operations: a lane's matrices
(its pairs that landed here, one expert each), the state's update and read,
attention over the live context. Checked against a hand count in
``perfbench/tests``.
"""

from __future__ import annotations

#: taps of a Mamba-2 block's convolution (``Mamba2Mixer.TAPS``)
CONV_TAPS = 4


def layer_kinds(program: dict) -> dict:
    """{letter: blocks of that kind} of the stack."""
    pattern = program["layer_pattern"]
    return {letter: pattern.count(letter) for letter in "ME*-"}


def mamba_widths(program: dict) -> tuple:
    """(inner channels H P, channels under the convolution H P + 2 G N) of
    a Mamba-2 block."""
    inner = program["mamba_num_heads"] * program["mamba_head_dim"]
    return inner, inner + 2 * program["mamba_n_groups"] * program[
        "mamba_state_size"]


def kv_row_values(program: dict) -> int:
    """Values of a token's key and value rows in an attention block."""
    return 2 * program["num_kv_heads"] * program["head_dim"]


def sublayer_params(program: dict) -> dict:
    """Parameters of one block by kind, its one norm included: ``M`` (the
    input projection to z, x, B, C and dt, the convolution's taps and bias,
    a rate, a bias and a skip a head, the group norm's weight, the output
    projection), ``*`` (q, k and v, the output projection), ``E`` WITHOUT
    its routed experts (the router's matrix and bias, the shared expert),
    ``-`` (two matrices) and ONE routed ``expert`` (two E x F matrices)."""
    e, f = program["embed_dim"], program["moe_dim"]
    h, h_kv, a = (program["num_heads"], program["num_kv_heads"],
                  program["head_dim"])
    heads = program["mamba_num_heads"]
    inner, conv = mamba_widths(program)
    shared = program.get("moe_shared_dim") or 0
    mlp = program.get("mlp_dim") or 4 * e
    return {
        "M": (e * (inner + conv + heads) + (CONV_TAPS + 1) * conv + 3 * heads
              + inner + inner * e + e),
        "*": e * h * a + e * 2 * h_kv * a + h * a * e + e,
        "E": e * program["n_experts"] + program["n_experts"]
             + 2 * e * shared + e,
        "-": 2 * e * mlp + e,
        "expert": 2 * e * f,
    }


def slot_state_bytes(program: dict, kv_bytes: int = 2) -> tuple:
    """(float32 Mamba-2 state, convolution inputs) one slot holds, in bytes,
    over all Mamba-2 blocks."""
    blocks = layer_kinds(program)["M"]
    _, conv = mamba_widths(program)
    state = (program["mamba_num_heads"] * program["mamba_head_dim"]
             * program["mamba_state_size"])
    return blocks * state * 4, blocks * (CONV_TAPS - 1) * conv * kv_bytes


def mamba_moe_decode_tick_need(program: dict, live_slots: float,
                               live_context: float, experts_hit: float,
                               pairs_here: float, weight_bytes: int = 2,
                               kv_bytes: int = 2) -> dict:
    """``{"flops", "bytes", "state_bytes", "kv_bytes"}`` one decode tick
    needs. ``live_context`` is the SUM of the live slots' context lengths,
    ``experts_hit`` the mean over the expert blocks of held experts with at
    least one pair, ``pairs_here`` the (lane, expert) pairs that landed on a
    held expert in an expert block. ``state_bytes`` is the part of ``bytes``
    that is the Mamba-2 state read and written, ``kv_bytes`` the part that is
    the live context's key and value rows read and each live lane's
    written."""
    e = program["embed_dim"]
    h, a = program["num_heads"], program["head_dim"]
    kinds = layer_kinds(program)
    p = sublayer_params(program)
    always = sum(kinds[k] * p[k] for k in "ME*-") + e  # and the final norm
    head = e * program["vocab_size"]
    state, conv = slot_state_bytes(program, kv_bytes)
    state_bytes = live_slots * 2 * state
    rows_bytes = ((live_context + live_slots) * kinds["*"]
                  * kv_row_values(program) * kv_bytes)
    bytes_ = ((always + kinds["E"] * experts_hit * p["expert"] + head
               + live_slots * e) * weight_bytes
              + state_bytes + live_slots * 2 * conv + rows_bytes)
    # a head's state: decayed and written (3 a value), read by C (2)
    state_values = state // 4
    flops = (2.0 * (live_slots * (always + head)
                    + kinds["E"] * pairs_here * p["expert"])
             + live_slots * 5 * state_values
             + live_context * kinds["*"] * 2 * 2 * h * a)
    return {"flops": flops, "bytes": bytes_, "state_bytes": state_bytes,
            "kv_bytes": rows_bytes}
