"""Peaks, required operations and bytes, percentiles: hand-worked values."""

import json
import os

import pytest

from benchmark import yardstick as y

HERE = os.path.dirname(os.path.abspath(__file__))


def _config(name):
    with open(os.path.join(HERE, "..", "configs", f"{name}.json")) as f:
        return json.load(f)


def test_peak_table_is_keyed_by_device_kind_and_refuses_unknown():
    assert y.peak("TPU v5 lite", "bf16_flops") == 197e12
    assert y.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(ValueError, match="no 'bf16_flops' peak"):
        y.peak("cpu", "bf16_flops")


def test_percentile_interpolates_between_ranks():
    assert y.percentile([1, 2, 3, 4, 5], 50) == 3
    assert y.percentile([10, 20], 50) == 15
    assert y.percentile(list(range(1, 101)), 95) == pytest.approx(95.05)
    with pytest.raises(ValueError):
        y.percentile([], 50)


def test_gpt2_medium_required_operations_by_hand():
    cfg = _config("gpt2-medium")
    # 24 blocks x 12 x 1024^2 = 301,989,888; head 50,257 x 1,024 = 51,463,168
    assert y.gpt2_matmul_params(cfg) == 301_989_888 + 51_463_168
    # 6 x 353,453,056 = 2,120,718,336; attention 6 x 1024 x 1024 x 24
    # = 150,994,944 (the causal half, forward and backward)
    assert y.gpt2_train_flops_per_token(cfg, 1024) == \
        2_120_718_336 + 150_994_944


def test_tiny_gpt2_operations_by_hand():
    cfg = {"n_layer": 1, "n_embd": 2, "vocab_size": 3}
    assert y.gpt2_matmul_params(cfg) == 12 * 4 + 6
    assert y.gpt2_train_flops_per_token(cfg, 4) == 6 * 54 + 3 * 2 * 4 * 2


def test_mistral_20_layers_by_hand():
    cfg = _config("mistral-7b-l20")
    # a layer: qkv 4096 x 6144, out 4096 x 4096, mlp 3 x 4096 x 14336
    layer = 4096 * 6144 + 4096 * 4096 + 3 * 4096 * 14336
    assert layer == 218_103_808
    assert y.mistral_matmul_params(cfg) == 20 * layer + 32000 * 4096
    # one token of prefill: 2 x body, attention 2 x 1 x 1 x 4096 x 20, and
    # the head once
    assert y.mistral_prefill_flops(cfg, 1) == \
        2 * 20 * layer + 2 * 4096 * 20 + 2 * 32000 * 4096
    # decode reads every weight once and 81,920 bytes of K and V a token
    assert y.mistral_decode_bytes_per_step(cfg, 1000) == \
        2 * (20 * layer + 32000 * 4096) + 81_920 * 1000


def test_mfu_is_flops_rate_over_chips_times_peak():
    assert y.mfu_pct(1e9, 98_500, 1, "TPU v5e") == pytest.approx(50.0)
    assert y.mfu_pct(1e9, 98_500, 4, "TPU v5e") == pytest.approx(12.5)
