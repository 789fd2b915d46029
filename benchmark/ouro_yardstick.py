"""The yardstick's arithmetic for the Ouro configuration: its parameters,
what a decode step has to move through device memory when the stack runs
`total_ut_steps` times a token, and the readers of the per-layer metrics that
need more than a ratio. Pure Python, as `yardstick.py`. A reader returns None
where the program has no such counter (the parent of the PR that brought
it)."""

from __future__ import annotations

import json
import os
from typing import Optional

from benchmark import yardstick
from benchmark.readers import lookup

HERE = os.path.dirname(os.path.abspath(__file__))


def layer_params(model: dict) -> int:
    """A layer: the fused [q | k | v] projection, W_o, the SwiGLU's three
    matrices and the gains of its four norms."""
    d, hd = model["hidden_size"], model["head_dim"]
    h, kvh = model["num_attention_heads"], model["num_key_value_heads"]
    return d * (h + 2 * kvh) * hd + h * hd * d \
        + 3 * d * model["intermediate_size"] + 4 * d


def count_parameters(model: dict) -> dict:
    """The file's `parameters`: what this chip holds, by part."""
    d = model["hidden_size"]
    layer = layer_params(model)
    stack = model["num_hidden_layers"] * layer
    table = model["vocab_size"] * d
    total = stack + 2 * table + d + (d + 1)
    return {"a_layer": layer, "the_stack": stack, "embedding": table,
            "head": table, "final_norm": d, "exit_gate": d + 1,
            "total": total, "bf16_gb": round(2 * total / 1e9, 2)}


def page_layers(model: dict) -> int:
    """Layers of K/V rows a token leaves: one a pass a weight layer."""
    return model["total_ut_steps"] * model["num_hidden_layers"]


def kv_bytes_per_token(model: dict, bytes_per_value: int = 2) -> int:
    """K and V of one cached position in every page layer."""
    return page_layers(model) * 2 * model["num_key_value_heads"] \
        * model["head_dim"] * bytes_per_value


def decode_weight_bytes(model: dict, bytes_per_value: int = 2) -> float:
    """Weights one decode step has to read: the stack once a pass (the
    passes run one after the other, each through every layer, and no chip
    memory but HBM holds 4.9 GB between them), the exit gate a pass, the
    head once. The embedding is read a row a token and is not counted."""
    d = model["hidden_size"]
    return float(bytes_per_value) * (
        model["total_ut_steps"] * (
            model["num_hidden_layers"] * layer_params(model) + 2 * d + 1)
        + d * model["vocab_size"])


def decode_required_bytes(model: dict, context_tokens: float,
                          lanes: float, bytes_per_value: int = 2) -> float:
    """Bytes one decode step has to move: `decode_weight_bytes`, K and V of
    every cached position of the batch in every page layer
    (`context_tokens` summed over the running sequences), and the rows the
    step writes (`lanes` new positions in every page layer)."""
    return decode_weight_bytes(model, bytes_per_value) \
        + kv_bytes_per_token(model, bytes_per_value) * (
            context_tokens + lanes)


def _model(args: dict) -> dict:
    with open(os.path.join(HERE, args["config"])) as f:
        return json.load(f)


def _decoded_tokens(delta: dict) -> Optional[float]:
    """Tokens the window's decode steps yielded (a prefill yields the
    first), as `decode_batch_mean.generate` counts them."""
    if "tokens_generated" not in delta:
        return None
    return delta["tokens_generated"] - delta.get("prefill_steps", 0)


def _step(obs: dict):
    """(the model-free means of the window's decode step: cached positions
    read, lanes, the milliseconds it held the device) or None where the
    program counts no layer passes (it has no looped family)."""
    delta = lookup(obs, "engine_delta") or {}
    steps = delta.get("decode_steps")
    context = delta.get("decode_context_tokens")
    tokens = _decoded_tokens(delta)
    if not steps or context is None or tokens is None \
            or "decode_layer_passes" not in delta:
        return None
    held_ms = (delta.get("ph_decode_dispatch_ms", 0.0)
               + delta.get("ph_decode_device_wait_ms", 0.0)) / steps
    return context / steps, tokens / steps, held_ms


def decode_hbm_roofline_pct(obs: dict, args: dict) -> Optional[float]:
    """Required bytes of the window's mean decode step over what the chip's
    memory could have moved while the step held the device
    (`decode_dispatch` + `decode_device_wait`, as Kimi's share). The share
    of the WHOLE step: it bounds any later claim on this cell."""
    step = _step(obs)
    if step is None or not step[2]:
        return None
    context, lanes, held_ms = step
    need = decode_required_bytes(_model(args), context, lanes)
    return 100.0 * need / (
        yardstick.peak(obs["device_kind"], "hbm_bytes_per_s")
        * held_ms / 1e3)


def loop_weight_bytes_share_pct(obs: dict, args: dict) -> Optional[float]:
    """The weights' part of the mean decode step's required bytes: over a
    half, the stack streamed once a pass sets the pace; under it, the rows
    of the page layers do."""
    step = _step(obs)
    if step is None:
        return None
    model = _model(args)
    return 100.0 * decode_weight_bytes(model) \
        / decode_required_bytes(model, step[0], step[1])


def per_decoded_token(obs: dict, args: dict) -> Optional[float]:
    """`num` (a counter of the decode steps, summed over their live lanes)
    over the tokens those steps yielded, times `scale`."""
    delta = lookup(obs, "engine_delta") or {}
    num, tokens = delta.get(args["num"]), _decoded_tokens(delta)
    if num is None or not tokens:
        return None
    return float(num) / tokens * args.get("scale", 1.0)
