import json
import os

import pytest

from perfbench.harness import device, opcount

GPT2M = {"n_embd": 1024, "n_layer": 24, "vocab_size": 50257, "n_inner": None}


def test_lm_step_against_a_hand_count():
    # per layer 4 E^2 (qkv + proj) + 8 E^2 (MLP) = 12 x 1024^2 = 12,582,912
    # 24 layers: 301,989,888; head 1024 x 50257 = 51,463,168
    assert opcount.lm_matmul_params(GPT2M) == 301_989_888 + 51_463_168
    # forward: 2 per parameter = 706,906,112; causal attention per token
    # 24 layers x 2 x 2 x 512 x 1024 = 50,331,648; x3 for the backward
    assert opcount.lm_train_flops_per_token(GPT2M, 1024) == pytest.approx(
        3 * (706_906_112 + 50_331_648))
    toy = {"n_embd": 4, "n_layer": 1, "vocab_size": 10, "n_inner": None}
    # 12 x 16 + 40 = 232 parameters; attention 1 x 2 x 2 x 4 x 4 = 64
    assert opcount.lm_train_flops_per_token(toy, 8) == 3 * (2 * 232 + 64)


def test_resnet50_against_a_hand_count(root):
    cfg = json.load(open(os.path.join(
        root, "perfbench", "unshipped", "resnet50.ddp4", "configs",
        "resnet50.json")))
    macs = opcount.resnet_forward_macs(cfg)
    # the published count for torchvision's resnet50 at 224: 4.09 GMACs
    assert 4.05e9 < macs < 4.15e9, macs
    toy = {"image_size": 8, "width": 2, "stage_sizes": [1], "num_classes": 3}
    # stem 4x4x147x2 = 4704; pool -> 2x2; block: 1x1 2x2x2x2 = 16,
    # 3x3 2x2x9x2x2 = 144, expand 2x2x2x8 = 64, downsample 2x2x2x8 = 64;
    # classifier 8 x 3 = 24
    assert opcount.resnet_forward_macs(toy) == 4704 + 16 + 144 + 64 + 64 + 24
    assert opcount.resnet_train_flops_per_image(toy) == 6 * 5016


def test_decode_tick_against_a_hand_count():
    toy = {"n_embd": 4, "n_layer": 2, "vocab_size": 10, "n_inner": None}
    params = 2 * 12 * 16 + 40  # 424
    flops, bytes_ = opcount.decode_tick_need(toy, live_slots=3,
                                             live_context=50)
    assert flops == 3 * 2 * params + 50 * 2 * 2 * 2 * 4
    # weights 848 B, embedding rows 3 x 4 x 2, K/V read 50 x 2 x 2 x 4 x 2,
    # K/V written 3 x 2 x 2 x 4 x 2
    assert bytes_ == 848 + 24 + 1600 + 96
    peak = {"flops_per_s": 1000.0, "hbm_bytes_per_s": 100.0}
    least, bound = opcount.least_time_s(flops, bytes_, peak)
    assert bound == "memory" and least == pytest.approx(bytes_ / 100.0)
    assert opcount.least_time_s(1e6, 10, peak) == (1000.0, "compute")


def test_gpt2_medium_tick_is_a_few_milliseconds():
    # 64 live slots at 120 tokens of context each: weights 0.71 GB, K/V
    # 7,680 x 98,304 B = 0.75 GB: about 1.8 ms at 819 GB/s
    flops, bytes_ = opcount.decode_tick_need(GPT2M, 64, 64 * 120)
    peak = device.peaks("TPU v5 lite")
    least, bound = opcount.least_time_s(flops, bytes_, peak)
    assert bound == "memory" and 1.5e-3 < least < 2.2e-3
    assert opcount.share_percent(least, 0.175, "x") == pytest.approx(
        100 * least / 0.175)


def test_a_share_above_100_raises():
    with pytest.raises(ArithmeticError):
        opcount.share_percent(1.01, 1.0, "decode_tick_roofline")
    assert opcount.share_percent(1.0, 1.0, "x") == 100.0


def test_peaks_table():
    v5e = device.peaks("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in v5e["source"]
    with pytest.raises(KeyError):
        device.peaks("TPU v9 imaginary")
