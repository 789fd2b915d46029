"""The yardstick's arithmetic for the SDAR-MoE configuration: its
parameters, what a pass over the running set has to read from device
memory, and the reader of the per-layer metric that needs more than a
ratio. Pure Python, as `yardstick.py`. A reader returns None where the
program has no such counter (the parent of the PR that brought it)."""

from __future__ import annotations

import json
import os
from typing import Optional

from benchmark import yardstick
from benchmark.readers import lookup

HERE = os.path.dirname(os.path.abspath(__file__))


def attention_params(model: dict) -> int:
    """A layer's attention: the fused [q | k | v] projection, W_o, and the
    learned scales of the query and key heads' norms."""
    d, hd = model["hidden_size"], model["head_dim"]
    h, kvh = model["num_attention_heads"], model["num_key_value_heads"]
    return d * (h + 2 * kvh) * hd + h * hd * d + 2 * hd


def expert_params(model: dict) -> int:
    """One routed expert: a SwiGLU of the expert width."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def router_params(model: dict) -> int:
    return model["hidden_size"] * model["num_experts"]


def count_parameters(model: dict) -> dict:
    """The file's `parameters`: what this chip holds, by part."""
    d = model["hidden_size"]
    experts = model["num_experts"] * expert_params(model)
    layer = attention_params(model) + router_params(model) + experts + 2 * d
    vocabulary = 2 * d * model["vocab_size"] + d
    total = model["num_hidden_layers"] * layer + vocabulary
    return {"attention_a_layer": attention_params(model),
            "router_a_layer": router_params(model),
            "experts_a_layer": experts, "a_layer": layer,
            "embedding_head_and_final_norm": vocabulary,
            "total": total, "bf16_gb": round(2 * total / 1e9, 2)}


def kv_bytes_per_token_layer(model: dict, bytes_per_value: int = 2) -> int:
    """K and V of one cached position in one layer."""
    return 2 * model["num_key_value_heads"] * model["head_dim"] \
        * bytes_per_value


def pass_weight_params_outside_experts(model: dict) -> int:
    """Matrices every pass reads whole, whatever the routing: every layer's
    attention and router, and the head over the whole vocabulary. The
    embedding is read a row a token and is not counted."""
    return model["num_hidden_layers"] * (attention_params(model)
                                         + router_params(model)) \
        + model["hidden_size"] * model["vocab_size"]


def pass_required_bytes(model: dict, experts_touched: float,
                        context_tokens: float,
                        bytes_per_value: int = 2) -> float:
    """Bytes one pass over the running set has to read: the weights outside
    the experts once, every expert that got a token (`experts_touched`,
    summed over the layers), and K and V of every cached position of the
    live lanes in every layer (`context_tokens` summed over the lanes)."""
    return float(bytes_per_value) * (
        pass_weight_params_outside_experts(model)
        + experts_touched * expert_params(model)) \
        + context_tokens * model["num_hidden_layers"] \
        * kv_bytes_per_token_layer(model, bytes_per_value)


def decode_hbm_roofline_pct(obs: dict, args: dict) -> Optional[float]:
    """Required bytes of the window's mean pass over what the chip's memory
    could have moved while the pass held the device (`decode_dispatch` +
    `decode_device_wait`, as Kimi's and Ling's shares). The share of the
    WHOLE pass: it bounds any later claim on this cell. None where the
    program has no expert, context or lane counter."""
    delta = lookup(obs, "engine_delta") or {}
    steps = delta.get("decode_steps")
    calls = delta.get("decode_moe_expert_calls")
    context = delta.get("decode_context_tokens")
    if not steps or calls is None or context is None \
            or delta.get("decode_lane_passes") is None:
        return None
    held_ms = (delta.get("ph_decode_dispatch_ms", 0.0)
               + delta.get("ph_decode_device_wait_ms", 0.0)) / steps
    if not held_ms:
        return None
    with open(os.path.join(HERE, args["config"])) as f:
        model = json.load(f)
    need = pass_required_bytes(model, calls / steps, context / steps)
    return 100.0 * need / (
        yardstick.peak(obs["device_kind"], "hbm_bytes_per_s")
        * held_ms / 1e3)
