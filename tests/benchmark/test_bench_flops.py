"""Required operations and bytes against hand counts."""

import json
import os

import pytest

from benchmark import flops, weights
from benchmark.kernels import splash_attention as splash

HERE = os.path.dirname(flops.__file__)


def config(name: str, n_layer: int) -> dict:
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        cfg = dict(json.load(f)["pretrained_config"])
    cfg["n_layer"] = n_layer
    return cfg


def test_parameter_counts_granite_3b():
    counts = weights.count_parameters(config("granite-3b-code", 32))
    # a layer: c_attn 2560 x 7680 + c_proj 2560 x 2560 + c_fc 2560 x 20480 + c_proj 10240 x 2560
    assert counts["per_layer_matmul"] == 2560 * 7680 + 2560 * 2560 + 3 * 2560 * 10240 == 104_857_600
    assert counts["table"] == 49152 * 2560 == 125_829_120
    assert counts["total"] == 32 * (104_857_600 + 2 * 2560) + 125_829_120 + 2560 == 3_481_438_720


def test_parameter_counts_granite_8b_gqa():
    counts = weights.count_parameters(config("granite-8b-code", 36))
    # GQA: 32 query heads and 2 x 8 K/V heads of 128 -> the fused projection is 4096 x 6144
    assert counts["per_layer_matmul"] == 4096 * 6144 + 4096 * 4096 + 3 * 4096 * 14336
    assert round(counts["total"] / 1e9, 2) == 8.05


def test_train_flops_per_token_3b_at_4_layers():
    cfg = config("granite-3b-code", 4)
    matmul = 2 * (4 * 104_857_600 + 125_829_120)  # the tied head is a matmul
    attention = 4 * 4 * 32 * 80 * (4096 + 1) / 2  # causal: half the square
    assert flops.forward_flops_per_token(cfg, 4096) == pytest.approx(matmul + attention)
    assert flops.train_flops_per_token(cfg, 4096) == pytest.approx(3 * (matmul + attention))
    assert flops.train_flops_per_token(cfg, 4096) == pytest.approx(3.52e9, rel=0.01)


def test_train_flops_per_token_8b_at_2_layers():
    assert flops.train_flops_per_token(config("granite-8b-code", 2), 4096) == pytest.approx(4.01e9, rel=0.01)


def test_splash_flops_and_bytes():
    # one layer, one head of 128, 1024 tokens, one row: forward 2 matmuls x 2 flops x 128 x
    # 1024 * 1025 / 2 keys, backward 2.5 x that
    forward = 2 * 2 * 128 * 1024 * 1025 / 2
    assert splash.train_flops(1, 1, 128, 1024, 1) == pytest.approx(3.5 * forward)
    assert splash.train_flops(4, 32, 80, 4096, 2) == pytest.approx(2 * 4 * 32 * 3.5 * 2 * 2 * 80 * 4096 * 4097 / 2)
    q = 32 * 128 * 4096 * 2
    kv = 8 * 128 * 4096 * 2
    assert splash.train_bytes(1, 32, 8, 128, 4096, 1) == (2 * q + 2 * kv) + (4 * q + 4 * kv)
    assert splash.SCOPE_PREFIX == "splash_mha"


def test_roofline_says_which_bound():
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert splash.roofline_seconds(197e12, 1.0, peak) == (1.0, "compute")
    assert splash.roofline_seconds(1.0, 819e9, peak) == (1.0, "memory")
