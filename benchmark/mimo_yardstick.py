"""The yardstick's arithmetic for the MiMo-V2 configuration: its parameters,
what a decode step has to read from device memory with K and V rows that
differ by layer kind, and the readers of the per-layer metrics that need
more than a ratio. Pure Python, as `yardstick.py`. A reader returns None
where the program has no such counter (the parent of the PR that brought
it) or the run no trace."""

from __future__ import annotations

import json
import os
from typing import Optional

from benchmark import yardstick
# one routed expert, a SwiGLU of the expert width: the same keys mean the
# same here
from benchmark.kimi_yardstick import expert_params
from benchmark.readers import lookup

HERE = os.path.dirname(os.path.abspath(__file__))
KINDS = ("window", "full")


def kv_heads(model: dict, kind: str) -> int:
    return model["swa_num_key_value_heads" if kind == "window"
                 else "num_key_value_heads"]


def attention_params(model: dict, kind: str) -> int:
    """A layer's attention: the fused [q | k | v] projection at the kind's
    K/V head count, W_o over the values' width, and a window layer's sinks
    (one a query head)."""
    d, h = model["hidden_size"], model["num_attention_heads"]
    hd, vd, kvh = model["head_dim"], model["v_head_dim"], kv_heads(model, kind)
    sink = model["add_swa_attention_sink_bias" if kind == "window"
                 else "add_full_attention_sink_bias"]
    return d * ((h + kvh) * hd + kvh * vd) + h * vd * d + (h if sink else 0)


def router_params(model: dict) -> int:
    """The router at its published width, and its selection bias."""
    n = model["published"]["n_routed_experts"]
    return model["hidden_size"] * n + n


def layers_by_kind(model: dict) -> dict:
    pattern = model["hybrid_layer_pattern"]
    return {"window": pattern.count(1), "full": pattern.count(0)}


def kv_bytes_per_token_layer(model: dict, kind: str,
                             bytes_per_value: int = 2) -> int:
    """K and V of one cached position in one layer of the kind: K rows of
    head_dim, V rows of v_head_dim, the kind's own K/V heads."""
    return kv_heads(model, kind) * (model["head_dim"] + model["v_head_dim"]) \
        * bytes_per_value


def count_parameters(model: dict) -> dict:
    """The file's `parameters`: what this chip holds, by part. There is no
    shared expert: an expert layer is its attention, two norms, the router
    and the experts held."""
    d, norms = model["hidden_size"], 2 * model["hidden_size"]
    attention = {kind: attention_params(model, kind) for kind in KINDS}
    dense = 3 * d * model["intermediate_size"]
    experts = router_params(model) \
        + model["n_routed_experts"] * expert_params(model)
    vocabulary = 2 * d * model["vocab_size"] + d
    total = vocabulary
    for window, moe in zip(model["hybrid_layer_pattern"],
                           model["moe_layer_freq"]):
        total += attention["window" if window else "full"] + norms \
            + (experts if moe else dense)
    return {"attention_a_window_layer": attention["window"],
            "attention_a_full_layer": attention["full"],
            "dense_feed_forward": dense,
            "experts_and_router_here": experts,
            "vocabulary_slice_and_final_norm": vocabulary,
            "total": total, "bf16_gb": round(2 * total / 1e9, 2)}


def decode_weight_params_outside_experts(model: dict) -> int:
    """Matrices every decode step reads whole, whatever the routing: every
    layer's attention, the dense layers' SwiGLU, the routers of the expert
    layers, and the head over the vocabulary held here. The embedding is
    read a row a token and is not counted."""
    kinds, d = layers_by_kind(model), model["hidden_size"]
    moe = sum(model["moe_layer_freq"])
    return sum(kinds[kind] * attention_params(model, kind)
               for kind in KINDS) \
        + (model["num_hidden_layers"] - moe) * 3 * d \
        * model["intermediate_size"] \
        + moe * router_params(model) + d * model["vocab_size"]


def decode_required_bytes(model: dict, experts_touched: float,
                          context_tokens: float, window_tokens: float,
                          bytes_per_value: int = 2) -> float:
    """Bytes one decode step has to read: the weights outside the routed
    experts once, every routed expert that got a token (`experts_touched`,
    summed over the expert layers), and K and V of the cached positions a
    layer's queries see, at the layer's kind's own row: all of them in a
    full layer (`context_tokens`, summed over the running sequences), no
    more than the window's in a window layer (`window_tokens`: min(context,
    window - 1), summed)."""
    kinds = layers_by_kind(model)
    return float(bytes_per_value) * (
        decode_weight_params_outside_experts(model)
        + experts_touched * expert_params(model)) \
        + context_tokens * kinds["full"] * kv_bytes_per_token_layer(
            model, "full", bytes_per_value) \
        + window_tokens * kinds["window"] * kv_bytes_per_token_layer(
            model, "window", bytes_per_value)


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


def attn_full_hbm_roofline_pct(obs: dict, args: dict) -> Optional[float]:
    """K and V bytes that the full layers' walks had to read in the traced
    slice, over what the chip's memory could have moved in the device time
    of the operations `pattern` matches (`attn_full_device_pct`'s). The
    bytes: every cached position a decode step's lanes hold
    (`decode_context_tokens`) and every cached position a chunk's sequence
    held before it (`chunk_context_tokens`), in each full layer at the full
    kind's row. The reduced trace keeps seconds by operation kind and no
    counts, so the slice's bytes are the window's, by time: the window's
    bytes x slice / window (the pump is never idle in a closed loop of 32).
    A chunk's scores are bound by the matrix unit, not by memory, so the
    share reads low where chunks fill the slice: it is the memory's share
    alone."""
    from benchmark import trace_reduce

    trace = lookup(obs, "trace")
    delta = lookup(obs, "engine_delta") or {}
    context = delta.get("decode_context_tokens")
    window_s = lookup(obs, "window_s")
    if not trace or not trace.get("window_s") or context is None \
            or not window_s:
        return None
    seconds = trace_reduce.kernel_seconds(trace, args["pattern"])
    if not seconds:
        return None
    model = _model(args)
    need = (context + delta.get("chunk_context_tokens", 0)) \
        * layers_by_kind(model)["full"] \
        * kv_bytes_per_token_layer(model, "full") \
        * trace["window_s"] / window_s
    return 100.0 * need / (
        yardstick.peak(obs["device_kind"], "hbm_bytes_per_s") * seconds)


def kv_bytes_vs_uniform_pct(obs: dict, args: dict) -> Optional[float]:
    """Bytes the cache holds for its sequences, a kind's pages at the
    kind's own row width and layer count, mean over the decode steps, over
    what one kind of page for every layer would hold for the same
    sequences: every position kept in every layer, each layer at its own
    row. None where the program has no counter a kind."""
    delta = lookup(obs, "engine_delta") or {}
    model = _model(args)
    kinds = layers_by_kind(model)
    held = {kind: delta.get(f"decode_kv_pages_{kind}") for kind in kinds}
    if None in held.values() or not held["full"]:
        return None
    row = {kind: kinds[kind] * kv_bytes_per_token_layer(model, kind)
           for kind in kinds}
    return 100.0 * sum(held[kind] * row[kind] for kind in kinds) \
        / (held["full"] * sum(row.values()))
