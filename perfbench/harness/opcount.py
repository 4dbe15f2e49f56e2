"""What the ALGORITHM needs: operations and bytes of a training step and
of a decode tick, from shapes alone. Recomputation and whatever the
compiled program moves beyond this are the inefficiency a share exposes,
never part of the count. Each function is checked against a hand count
in ``perfbench/tests``.
"""

from __future__ import annotations


def lm_matmul_params(cfg: dict) -> int:
    """Parameters that sit in matrix multiplications: per layer qkv
    (3E^2), attention output (E^2), MLP up and down (2 x 4E^2); the head
    (E x V). Embedding rows are looked up, not multiplied."""
    e, v, n = cfg["n_embd"], cfg["vocab_size"], cfg["n_layer"]
    mlp = cfg.get("n_inner") or 4 * e
    return n * (4 * e * e + 2 * e * mlp) + e * v


def lm_train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward of a causal LM, per token: 3 x (2 per matmul
    parameter + causal attention's QK^T and PV, 2 x 2 x (L/2) x E a
    layer). No recomputation."""
    attn = cfg["n_layer"] * 2 * 2 * (seq_len / 2) * cfg["n_embd"]
    return 3.0 * (2.0 * lm_matmul_params(cfg) + attn)


def resnet_forward_macs(cfg: dict) -> int:
    """Multiply-accumulates of one forward pass of a bottleneck ResNet
    (torchvision v1.5: the stride on the 3x3) at ``image_size``."""
    size = cfg["image_size"]
    width = cfg["width"]
    macs = 0
    hw = size // 2  # 7x7 stem, stride 2
    macs += hw * hw * 7 * 7 * 3 * width
    hw //= 2  # 3x3 max pool, stride 2
    cin = width
    for i, blocks in enumerate(cfg["stage_sizes"]):
        f = width * 2 ** i
        for j in range(blocks):
            stride = 2 if (i > 0 and j == 0) else 1
            out_hw = hw // stride
            macs += hw * hw * cin * f  # 1x1 reduce, at the input size
            macs += out_hw * out_hw * 9 * f * f  # 3x3, strided
            macs += out_hw * out_hw * f * 4 * f  # 1x1 expand
            if cin != 4 * f or stride != 1:
                macs += out_hw * out_hw * cin * 4 * f  # downsample
            cin, hw = 4 * f, out_hw
    macs += cin * cfg["num_classes"]
    return macs


def resnet_train_flops_per_image(cfg: dict) -> float:
    """Forward + backward: 3 x 2 x the forward's multiply-accumulates."""
    return 6.0 * resnet_forward_macs(cfg)


def decode_tick_need(cfg: dict, live_slots: float, live_context: float,
                     weight_bytes: int = 2, kv_bytes: int = 2) -> tuple:
    """(operations, bytes) one decode tick needs: every matmul weight read
    once, the K and V rows of the live context of the live slots read
    once, one new K and V row per live slot written, one embedding row
    per live slot read; operations of the matmuls for the live slots and
    of attention over the live context. ``live_context`` is the SUM of the
    live slots' context lengths."""
    e, n = cfg["n_embd"], cfg["n_layer"]
    params = lm_matmul_params(cfg)
    bytes_ = (params * weight_bytes
              + live_slots * e * weight_bytes
              + live_context * n * 2 * e * kv_bytes
              + live_slots * n * 2 * e * kv_bytes)
    flops = live_slots * 2.0 * params + live_context * n * 2 * 2 * e
    return flops, bytes_


def least_time_s(flops: float, bytes_: float, peak: dict) -> tuple:
    """(seconds, which bound) of the roofline's floor."""
    tf, tb = flops / peak["flops_per_s"], bytes_ / peak["hbm_bytes_per_s"]
    return (tf, "compute") if tf >= tb else (tb, "memory")


def share_percent(least_s: float, measured_s: float, what: str) -> float:
    """A share of a roofline or a peak, in percent. Above 100 the count
    of operations or bytes is too high, or the time leaves out part of
    the work: that is a fault of the benchmark, raised, never printed."""
    share = 100.0 * least_s / measured_s
    if share > 100.0:
        raise ArithmeticError(
            f"{what}: {share:.2f}% of the peak is impossible "
            f"(least {least_s:.6g} s against measured {measured_s:.6g} s)")
    return share
