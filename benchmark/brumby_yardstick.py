"""The yardstick's arithmetic for the Brumby configuration: its parameters,
what a sequence's retention state weighs, what a decode step has to move
through device memory when every layer reads and writes a state a lane, and
the readers of the per-layer metrics that need more than a ratio. Counts come
from the file's keys, never from the program. Pure Python, as `yardstick.py`.
A reader returns None where the program has no such counter (the parent of
the PR that brought it)."""

from __future__ import annotations

import json
import os
from typing import Optional

from benchmark import yardstick
from benchmark.readers import lookup

HERE = os.path.dirname(os.path.abspath(__file__))


def layer_params(model: dict) -> int:
    """A layer: W_q and W_o, W_k and W_v, the gate's projection and bias (a
    scalar a key head), the q and k norms' gains, the SwiGLU's three matrices
    and the gains of its two norms."""
    d, hd = model["hidden_size"], model["head_dim"]
    h, kvh = model["num_attention_heads"], model["num_key_value_heads"]
    return 2 * d * h * hd + 2 * d * kvh * hd + (d + 1) * kvh + 2 * hd \
        + 3 * d * model["intermediate_size"] + 2 * d


def count_parameters(model: dict) -> dict:
    """The file's `parameters`: what this chip holds, by part."""
    d = model["hidden_size"]
    layer = layer_params(model)
    stack = model["num_hidden_layers"] * layer
    table = model["vocab_size"] * d
    total = stack + 2 * table + d
    return {"a_layer": layer, "the_stack": stack, "embedding": table,
            "head": table, "final_norm": d, "total": total,
            "bf16_gb": round(2 * total / 1e9, 2)}


def state_bytes_a_layer(model: dict) -> int:
    """One sequence's state in one layer: S [key heads, D, head_dim] and z
    [key heads, D], float32, D the file's `assumed.state_dim`."""
    kvh, big = model["num_key_value_heads"], model["assumed"]["state_dim"]
    return 4 * kvh * big * (model["head_dim"] + 1)


def state_bytes_a_sequence(model: dict) -> int:
    return model["num_hidden_layers"] * state_bytes_a_layer(model)


def decode_weight_bytes(model: dict, bytes_per_value: int = 2) -> float:
    """Weights one decode step has to read: every layer, the final norm and
    the head. The embedding is read a row a token and is not counted."""
    d = model["hidden_size"]
    return float(bytes_per_value) * (
        model["num_hidden_layers"] * layer_params(model) + d
        + d * model["vocab_size"])


def retention_step_required_bytes(model: dict, state_rows: float) -> float:
    """What the retention layers' decode updates have to move a step: every
    live state (`state_rows`: sequences x layers) read and written. (The
    projections' weights are matrix products of other operations.)"""
    return 2.0 * state_rows * state_bytes_a_layer(model)


def decode_required_bytes(model: dict, state_rows: float) -> float:
    """Bytes one decode step has to move: the weights once and every live
    state read and written. Nothing is cached a position: the state is all a
    step reads of a sequence, whatever its length."""
    return decode_weight_bytes(model) \
        + retention_step_required_bytes(model, state_rows)


def _model(args: dict) -> dict:
    with open(os.path.join(HERE, args["config"])) as f:
        return json.load(f)


def _rows_a_step(delta: dict) -> Optional[float]:
    steps, rows = delta.get("decode_steps"), \
        delta.get("decode_retention_state_rows")
    return None if not steps or rows is None else rows / steps


def decode_hbm_roofline_pct(obs: dict, args: dict) -> Optional[float]:
    """Required bytes of the window's mean decode step over what the chip's
    memory could have moved while the step held the device
    (`decode_dispatch` + `decode_device_wait`, as Ouro's share). The share of
    the WHOLE step: it bounds any later claim on this cell."""
    delta = lookup(obs, "engine_delta") or {}
    rows = _rows_a_step(delta)
    if rows is None:
        return None
    held_ms = (delta.get("ph_decode_dispatch_ms", 0.0)
               + delta.get("ph_decode_device_wait_ms", 0.0)) \
        / delta["decode_steps"]
    if not held_ms:
        return None
    return 100.0 * decode_required_bytes(_model(args), rows) / (
        yardstick.peak(obs["device_kind"], "hbm_bytes_per_s")
        * held_ms / 1e3)


def retention_step_hbm_roofline_pct(obs: dict, args: dict) -> Optional[float]:
    """State bytes the traced slice's decode steps had to move over what the
    chip's memory could have moved in the device self time of the decode
    step's state operations (`pattern`). The reduced trace keeps seconds by
    operation kind and no counts, so the slice's decode steps come from the
    host's clock, as `ling_yardstick:kda_step_hbm_roofline_pct`: the slice
    over a decode iteration's period where no prefill unit rides in it,
    (`pump_wall_ms` - `prefill_ms`) / `decode_steps`."""
    from benchmark import trace_reduce

    trace = lookup(obs, "trace")
    delta = lookup(obs, "engine_delta") or {}
    rows, wall = _rows_a_step(delta), delta.get("pump_wall_ms")
    if not trace or not trace.get("busy_s") or rows is None or not wall:
        return None
    seconds = trace_reduce.kernel_seconds(trace, args["pattern"])
    period_ms = (wall - delta.get("prefill_ms", 0.0)) / delta["decode_steps"]
    if not seconds or period_ms <= 0:
        return None
    steps_in_slice = trace["window_s"] * 1e3 / period_ms
    need = retention_step_required_bytes(_model(args), rows) * steps_in_slice
    return 100.0 * need / (
        yardstick.peak(obs["device_kind"], "hbm_bytes_per_s") * seconds)
