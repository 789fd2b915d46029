"""The yardstick's arithmetic for the Kimi-K2 configuration: what a decode
step has to read from device memory, and the readers of the per-layer
metrics that need more than a ratio. Pure Python, as `yardstick.py`.
"""

from __future__ import annotations

import json
import os
from typing import Optional

from benchmark import yardstick
from benchmark.readers import lookup

HERE = os.path.dirname(os.path.abspath(__file__))


def attention_params(model: dict) -> int:
    """A layer's attention matrices: W_dq, W_uq, W_dkv, W_ukv, W_o."""
    d, h = model["hidden_size"], model["num_attention_heads"]
    qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    return d * model["q_lora_rank"] + model["q_lora_rank"] * h * qk \
        + d * (model["kv_lora_rank"] + model["qk_rope_head_dim"]) \
        + model["kv_lora_rank"] * h * (model["qk_nope_head_dim"]
                                       + model["v_head_dim"]) \
        + h * model["v_head_dim"] * d


def expert_params(model: dict) -> int:
    """One routed (or shared) expert: a SwiGLU of the expert width."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def decode_weight_params_outside_experts(model: dict) -> int:
    """Matrices every decode step reads whole, whatever the routing: the
    attention of every layer, the dense layers' SwiGLU, the shared experts
    and routers of the expert layers (the router at its published width),
    and the head over the vocabulary held here. The embedding is read a
    row a token and is not counted."""
    layers, dense = model["num_hidden_layers"], model["first_k_dense_replace"]
    d = model["hidden_size"]
    router = d * model["published"]["n_routed_experts"]
    return layers * attention_params(model) \
        + dense * 3 * d * model["intermediate_size"] \
        + (layers - dense) * (model["n_shared_experts"]
                              * expert_params(model) + router) \
        + d * model["vocab_size"]


def decode_required_bytes(model: dict, experts_touched: float,
                          context_tokens: float,
                          bytes_per_value: int = 2) -> float:
    """Bytes one decode step has to read: the weights outside the routed
    experts once, every routed expert that got a token (`experts_touched`,
    summed over the expert layers), and the latent of every cached position
    of the batch in every layer (`context_tokens` summed over the running
    sequences; the latent's own kv_lora_rank + rope values, not the padded
    row the arena stores)."""
    latent = model["kv_lora_rank"] + model["qk_rope_head_dim"]
    return float(bytes_per_value) * (
        decode_weight_params_outside_experts(model)
        + experts_touched * expert_params(model)
        + context_tokens * model["num_hidden_layers"] * latent)


def decode_hbm_roofline_pct(obs: dict, args: dict) -> Optional[float]:
    """Required bytes of the window's mean decode step over the bytes the
    chip's memory could have moved in the time the step held the device:
    from the call into the compiled step to its results being ready
    (`decode_dispatch` + `decode_device_wait`; the device starts inside the
    call). None where the program has no expert or context counter."""
    delta = lookup(obs, "engine_delta") or {}
    steps = delta.get("decode_steps")
    calls = delta.get("decode_moe_expert_calls")
    context = delta.get("decode_context_tokens")
    if not steps or calls is None or context is None:
        return None
    held_ms = (delta["ph_decode_dispatch_ms"]
               + delta["ph_decode_device_wait_ms"]) / steps
    if not held_ms:
        return None
    with open(os.path.join(HERE, args["config"])) as f:
        model = json.load(f)
    need = decode_required_bytes(model, calls / steps, context / steps)
    could = yardstick.peak(obs["device_kind"], "hbm_bytes_per_s") \
        * held_ms / 1e3
    return 100.0 * need / could


def device_share_pct(obs: dict, args: dict) -> Optional[float]:
    """Device self time of the operation kinds matching `pattern`, as a
    share of the traced slice's busy time. None without a trace or where
    nothing matches (a program without these operations)."""
    from benchmark import trace_reduce

    trace = lookup(obs, "trace")
    if not trace or not trace.get("busy_s"):
        return None
    seconds = trace_reduce.kernel_seconds(trace, args["pattern"])
    if not seconds:
        return None
    return 100.0 * seconds / trace["busy_s"]
