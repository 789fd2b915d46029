"""The yardstick's arithmetic for the Trinity (AFMoE) configuration: its
parameters, what a decode step has to read from device memory, and the
readers of the per-layer metrics that need more than a ratio. Pure Python,
as `yardstick.py`. A reader returns None where the program has no such
counter (the parent of the PR that brought it)."""

from __future__ import annotations

import json
import os
from typing import Optional

from benchmark import yardstick
# one routed (or shared) expert, a SwiGLU of the expert width; K and V of one
# cached position in one layer: the same keys mean the same here
from benchmark.kimi_yardstick import expert_params
from benchmark.readers import lookup
from benchmark.sdar_yardstick import kv_bytes_per_token_layer

HERE = os.path.dirname(os.path.abspath(__file__))


def attention_params(model: dict) -> int:
    """A layer's attention: the fused [q | k | v | gate] projection, W_o,
    and the gains of the query and key heads' norms."""
    d, hd = model["hidden_size"], model["head_dim"]
    h, kvh = model["num_attention_heads"], model["num_key_value_heads"]
    return d * (2 * h + 2 * kvh) * hd + h * hd * d + 2 * hd


def router_params(model: dict) -> int:
    """The router at its published width, and its selection bias."""
    n = model["published"]["num_experts"]
    return model["hidden_size"] * n + n


def count_parameters(model: dict) -> dict:
    """The file's `parameters`: what this chip holds, by part."""
    d = model["hidden_size"]
    norms = 4 * d
    attention = attention_params(model)
    dense = attention + norms + 3 * d * model["intermediate_size"]
    expert_layer = attention + norms + router_params(model) \
        + (model["num_shared_experts"] + model["num_experts"]) \
        * expert_params(model)
    vocabulary = 2 * d * model["vocab_size"] + d
    n_dense = model["num_dense_layers"]
    total = n_dense * dense \
        + (model["num_hidden_layers"] - n_dense) * expert_layer + vocabulary
    return {"attention_a_layer": attention, "dense_layer": dense,
            "expert_layer_here": expert_layer,
            "vocabulary_slice_and_final_norm": vocabulary,
            "total": total, "bf16_gb": round(2 * total / 1e9, 2)}


def layers_by_kind(model: dict) -> dict:
    types = model["layer_types"]
    return {"window": types.count("sliding_attention"),
            "full": types.count("full_attention")}


def decode_weight_params_outside_experts(model: dict) -> int:
    """Matrices every decode step reads whole, whatever the routing: every
    layer's attention, the dense layers' SwiGLU, the shared experts and
    routers of the expert layers, and the head over the vocabulary held
    here. The embedding is read a row a token and is not counted."""
    layers, dense = model["num_hidden_layers"], model["num_dense_layers"]
    d = model["hidden_size"]
    return layers * attention_params(model) \
        + dense * 3 * d * model["intermediate_size"] \
        + (layers - dense) * (model["num_shared_experts"]
                              * expert_params(model) + router_params(model)) \
        + d * model["vocab_size"]


def decode_required_bytes(model: dict, experts_touched: float,
                          context_tokens: float, window_tokens: float,
                          bytes_per_value: int = 2) -> float:
    """Bytes one decode step has to read: the weights outside the routed
    experts once, every routed expert that got a token (`experts_touched`,
    summed over the expert layers), and K and V of the cached positions a
    layer's queries see: all of them in a full layer (`context_tokens`,
    summed over the running sequences), no more than the window's in a
    sliding layer (`window_tokens`: min(context, window - 1), summed)."""
    kinds = layers_by_kind(model)
    return float(bytes_per_value) * (
        decode_weight_params_outside_experts(model)
        + experts_touched * expert_params(model)) \
        + kv_bytes_per_token_layer(model, bytes_per_value) * (
            context_tokens * kinds["full"] + window_tokens * kinds["window"])


def _model(args: dict) -> dict:
    with open(os.path.join(HERE, args["config"])) as f:
        return json.load(f)


def decode_hbm_roofline_pct(obs: dict, args: dict) -> Optional[float]:
    """Required bytes of the window's mean decode step over what the chip's
    memory could have moved while the step held the device
    (`decode_dispatch` + `decode_device_wait`, as Kimi's share). The share
    of the WHOLE step: it bounds any later claim on this cell. None where
    the program has no expert, context or window counter."""
    delta = lookup(obs, "engine_delta") or {}
    steps = delta.get("decode_steps")
    calls = delta.get("decode_moe_expert_calls")
    context = delta.get("decode_context_tokens")
    window = delta.get("decode_context_tokens_window")
    if not steps or calls is None or context is None or window is None:
        return None
    held_ms = (delta.get("ph_decode_dispatch_ms", 0.0)
               + delta.get("ph_decode_device_wait_ms", 0.0)) / steps
    if not held_ms:
        return None
    need = decode_required_bytes(_model(args), calls / steps,
                                 context / steps, window / steps)
    return 100.0 * need / (
        yardstick.peak(obs["device_kind"], "hbm_bytes_per_s")
        * held_ms / 1e3)


def kv_bytes_vs_uniform_pct(obs: dict, args: dict) -> Optional[float]:
    """Page-layers the cache holds for its sequences, mean over the decode
    steps, over what an allocator with one kind of page would hold for the
    same sequences: a full-kind page for every page of every layer. None
    where the program has no counter a kind."""
    delta = lookup(obs, "engine_delta") or {}
    kinds = layers_by_kind(_model(args))
    held = {kind: delta.get(f"decode_kv_pages_{kind}") for kind in kinds}
    if None in held.values() or not held["full"]:
        return None
    return 100.0 * sum(held[kind] * n for kind, n in kinds.items()) \
        / (held["full"] * sum(kinds.values()))
