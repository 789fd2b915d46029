"""The yardstick's arithmetic: peaks, required operations and bytes,
percentiles. Kept with the benchmark so that no later PR that claims a gain
can move it. Pure Python: the driver process imports it and never jax.
"""

from __future__ import annotations

import math
from typing import Dict, Sequence

# Peaks of one chip, keyed by `jax.devices()[0].device_kind`. Source: Google
# Cloud documentation, "TPU v5e" system architecture page (197 TFLOP/s bf16,
# 16 GB of HBM2e at 819 GB/s, per chip). A kind that is not listed is an
# error, never a default: a utilisation against the wrong peak means nothing.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peak(device_kind: str, what: str) -> float:
    try:
        return PEAKS[device_kind][what]
    except KeyError:
        raise ValueError(
            f"no {what!r} peak for device kind {device_kind!r} in "
            f"benchmark/yardstick.py (known: {sorted(PEAKS)}); add it with "
            f"its source") from None


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    two nearest ranks, as `statistics.quantiles(method="inclusive")`."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


# -- required operations ------------------------------------------------------
#
# What the forward and backward passes need, not what the program happens to
# execute: the masked half of causal attention and recomputed activations do
# not count. A matrix multiplication of [m, k] by [k, n] is 2*m*k*n.

def gpt2_matmul_params(model: dict) -> int:
    """Parameters that multiply activations: per block qkv (3 d^2), attention
    output (d^2) and the MLP (8 d^2), and the tied head over the PUBLISHED
    vocabulary (padding rows are not required work)."""
    d = model["n_embd"]
    return model["n_layer"] * 12 * d * d + model["vocab_size"] * d


def gpt2_train_flops_per_token(model: dict, seq_len: int) -> float:
    """6 per matmul parameter (forward 2, backward 4) plus causal attention:
    QK^T and PV are each 2*T*d a token over the full square, half of it under
    the causal mask, times 3 for forward and backward."""
    attention = 3 * 2 * seq_len * model["n_embd"] * model["n_layer"]
    return 6.0 * gpt2_matmul_params(model) + attention


def mistral_matmul_params(model: dict) -> int:
    d = model["hidden_size"]
    hd = model["head_dim"]
    qkv = d * (model["num_attention_heads"]
               + 2 * model["num_key_value_heads"]) * hd
    out = model["num_attention_heads"] * hd * d
    mlp = 3 * d * model["intermediate_size"]
    return model["num_hidden_layers"] * (qkv + out + mlp) \
        + model["vocab_size"] * d


def mistral_prefill_flops(model: dict, n_tokens: int) -> float:
    """Forward pass over a prompt of n tokens, causal half of attention; the
    head is applied to the last position only (one next-token row)."""
    d_attn = model["num_attention_heads"] * model["head_dim"]
    body = mistral_matmul_params(model) - model["vocab_size"] \
        * model["hidden_size"]
    attention = 2 * n_tokens * n_tokens * d_attn * model["num_hidden_layers"]
    return 2.0 * body * n_tokens + attention \
        + 2.0 * model["vocab_size"] * model["hidden_size"]


def mistral_decode_bytes_per_step(model: dict, context_tokens: int,
                                  bytes_per_value: int = 2) -> float:
    """Bytes one decode step has to read from device memory: every weight
    once, and K and V of every cached position of the batch
    (`context_tokens` summed over the running sequences)."""
    kv_per_token = 2 * model["num_hidden_layers"] \
        * model["num_key_value_heads"] * model["head_dim"]
    return float(bytes_per_value) * (
        mistral_matmul_params(model) + kv_per_token * context_tokens)


def mfu_pct(flops_per_token: float, tokens_per_s: float, chips: int,
            device_kind: str) -> float:
    return 100.0 * flops_per_token * tokens_per_s / (
        chips * peak(device_kind, "bf16_flops"))
