"""The yardstick's arithmetic for the Ling hybrid configuration: its
parameters, what a decode step has to read from device memory, what a chunk
of the blocked Kimi-Delta-Attention scan has to compute, and the readers of
the per-layer metrics that need more than a ratio. Pure Python, as
`yardstick.py`. A reader returns None where the program has no such counter
or the trace no such operation (the parent of the PR that brought it)."""

from __future__ import annotations

import json
import os
from typing import Optional

from benchmark import yardstick
from benchmark.readers import lookup

HERE = os.path.dirname(os.path.abspath(__file__))


def _model(args: dict) -> dict:
    with open(os.path.join(HERE, args["config"])) as f:
        return json.load(f)


def is_mla(model: dict, i: int) -> bool:
    return (i + 1) % model["layer_group_size"] == 0


def layer_kinds(model: dict):
    """(KDA layers, MLA layers) among the layers held."""
    n = model["num_hidden_layers"]
    mla = sum(1 for i in range(n) if is_mla(model, i))
    return n - mla, mla


# -- parameters ---------------------------------------------------------------

def kda_params(model: dict) -> int:
    """A KDA layer's attention: W_q, W_k, W_v, the decay and output-gate
    projections (full rank), W_o, beta, the convolution's taps, and the
    three small vectors (dt_bias, A_log, the output norm)."""
    d, h, dk = model["hidden_size"], model["num_attention_heads"], \
        model["head_dim"]
    return 3 * d * h * dk + 2 * d * h * dk + h * dk * d + d * h \
        + 3 * h * dk * model["short_conv_kernel_size"] + h * dk + h + dk


def mla_params(model: dict) -> int:
    """An MLA layer's attention: W_q with its head norm, W_dkv with the
    latent's norm, W_ukv, the heads' gate, W_o."""
    d, h = model["hidden_size"], model["num_attention_heads"]
    qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    return d * h * qk + qk \
        + d * (model["kv_lora_rank"] + model["qk_rope_head_dim"]) \
        + model["kv_lora_rank"] \
        + model["kv_lora_rank"] * h * (model["qk_nope_head_dim"]
                                       + model["v_head_dim"]) \
        + d * h + h * model["v_head_dim"] * d


def expert_params(model: dict) -> int:
    """One routed (or shared) expert: a SwiGLU of the expert width."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def router_params(model: dict) -> int:
    """The router at its published width, and its selection bias."""
    n = model["published"]["num_experts"]
    return model["hidden_size"] * n + n


def count_parameters(model: dict) -> dict:
    """The file's `parameters`: what this chip holds, by part."""
    d = model["hidden_size"]
    n, dense = model["num_hidden_layers"], model["first_k_dense_replace"]
    kda, mla = layer_kinds(model)
    experts_here = model["num_experts"] * expert_params(model) \
        + model["num_shared_experts"] * expert_params(model) \
        + router_params(model)
    vocabulary = 2 * d * model["vocab_size"] + d
    total = kda * kda_params(model) + mla * mla_params(model) + n * 2 * d \
        + dense * 3 * d * model["intermediate_size"] \
        + (n - dense) * experts_here + vocabulary
    return {"kda_attention_a_layer": kda_params(model),
            "mla_attention_a_layer": mla_params(model),
            "dense_feed_forward": 3 * d * model["intermediate_size"],
            "expert_layer_here": experts_here,
            "vocabulary_slice_and_final_norm": vocabulary,
            "total": total, "bf16_gb": round(2 * total / 1e9, 2)}


# -- a decode step's bytes ----------------------------------------------------

def kda_state_bytes(model: dict) -> int:
    """One sequence's state in one KDA layer: float32 [H, dk, dv]."""
    return 4 * model["num_attention_heads"] * model["head_dim"] ** 2


def seq_state_bytes(model: dict) -> int:
    """What one sequence keeps beside its pages: every KDA layer's state
    and its convolution's tail (W - 1 inputs of 3 H dk channels, bf16)."""
    kda, _ = layer_kinds(model)
    tail = 2 * (model["short_conv_kernel_size"] - 1) * 3 \
        * model["num_attention_heads"] * model["head_dim"]
    return kda * (kda_state_bytes(model) + tail)


def decode_weight_params_outside_experts(model: dict) -> int:
    """Matrices every decode step reads whole, whatever the routing: every
    layer's attention, the dense layers' SwiGLU, the shared experts and
    routers of the expert layers, the head over the vocabulary held here.
    The embedding is read a row a token and is not counted."""
    n, dense = model["num_hidden_layers"], model["first_k_dense_replace"]
    d = model["hidden_size"]
    kda, mla = layer_kinds(model)
    return kda * kda_params(model) + mla * mla_params(model) \
        + dense * 3 * d * model["intermediate_size"] \
        + (n - dense) * (model["num_shared_experts"] * expert_params(model)
                         + router_params(model)) \
        + d * model["vocab_size"]


def decode_required_bytes(model: dict, experts_touched: float,
                          state_rows: float, context_tokens: float,
                          bytes_per_value: int = 2) -> float:
    """Bytes one decode step has to move: the weights outside the routed
    experts once, every routed expert that got a token (`experts_touched`,
    summed over the expert layers), every live KDA state read and written
    (`state_rows`: sequences x KDA layers), and the latent of every cached
    position of the batch in every MLA layer (`context_tokens` summed over
    the running sequences; the latent's own values, not the padded row)."""
    _, mla = layer_kinds(model)
    latent = model["kv_lora_rank"] + model["qk_rope_head_dim"]
    return float(bytes_per_value) * (
        decode_weight_params_outside_experts(model)
        + experts_touched * expert_params(model)
        + context_tokens * mla * latent) \
        + 2.0 * state_rows * kda_state_bytes(model)


def _held_s(delta: dict) -> Optional[float]:
    steps = delta.get("decode_steps")
    if not steps:
        return None
    ms = delta.get("ph_decode_dispatch_ms", 0.0) \
        + delta.get("ph_decode_device_wait_ms", 0.0)
    return ms / steps / 1e3 or None


def decode_hbm_roofline_pct(obs: dict, args: dict) -> Optional[float]:
    """Required bytes of the window's mean decode step over what the chip's
    memory could have moved while the step held the device
    (`decode_dispatch` + `decode_device_wait`, as Kimi's share)."""
    delta = lookup(obs, "engine_delta") or {}
    calls = delta.get("decode_moe_expert_calls")
    rows = delta.get("decode_kda_state_rows")
    context = delta.get("decode_context_tokens")
    held = _held_s(delta)
    if held is None or calls is None or rows is None or context is None:
        return None
    steps = delta["decode_steps"]
    need = decode_required_bytes(_model(args), calls / steps, rows / steps,
                                 context / steps)
    return 100.0 * need / (
        yardstick.peak(obs["device_kind"], "hbm_bytes_per_s") * held)


# -- the KDA kernels ----------------------------------------------------------

def kda_step_required_bytes(model: dict, lanes: float) -> float:
    """What the KDA layers' decode updates have to move a step: each live
    sequence's state read and written in every KDA layer. (Their
    projections' weights are matrix products of other operations.)"""
    kda, _ = layer_kinds(model)
    return 2.0 * lanes * kda * kda_state_bytes(model)


def kda_chunk_required_flops(model: dict, tokens: int, block: int) -> float:
    """Required operations of the blocked scan over `tokens` tokens in
    every KDA layer, a multiply-add as 2: a block's A and B against its
    keys under the causal mask (half of 2 C^2 dk each), the solve's two
    products (C^2 / 2 rows against dk + dv columns), the three products
    with the carried state (C dk dv each) and B U under the mask. The
    projections are other operations' and are not counted. No metric reads
    it yet: the traced slice of the configuration's one cell seldom holds
    a chunk (PERF.md, Open questions)."""
    kda, _ = layer_kinds(model)
    h, dk = model["num_attention_heads"], model["head_dim"]
    dv, c = dk, block
    a_block = 2 * (c * c * dk) + c * c * (dk + dv) \
        + 3 * 2 * c * dk * dv + c * c * dv
    return float(kda) * h * (tokens / c) * a_block


def _trace_seconds(obs: dict, pattern: str):
    from benchmark import trace_reduce

    trace = lookup(obs, "trace")
    if not trace or not trace.get("busy_s"):
        return None, None
    return trace_reduce.kernel_seconds(trace, pattern) or None, trace


def kda_step_hbm_roofline_pct(obs: dict, args: dict) -> Optional[float]:
    """State bytes the traced slice's decode steps had to move over what
    the chip's memory could have moved in the device time of the `kda_step`
    operations (`pattern`). The reduced trace keeps seconds by operation
    kind and no counts, so the slice's decode steps come from the host's
    clock: the slice over a decode step's period where no prefill unit
    rides in the iteration, (`pump_wall_ms` - `prefill_ms`) /
    `decode_steps` (a steady slice seldom holds a prefill; one that does
    holds fewer decode steps than counted, and the share reads high by the
    prefill's part of the slice). Lanes a step from `decode_kda_state_rows`."""
    seconds, trace = _trace_seconds(obs, args["pattern"])
    delta = lookup(obs, "engine_delta") or {}
    steps, rows = delta.get("decode_steps"), delta.get("decode_kda_state_rows")
    wall = delta.get("pump_wall_ms")
    if seconds is None or not steps or rows is None or not wall:
        return None
    period_ms = (wall - delta.get("prefill_ms", 0.0)) / steps
    if period_ms <= 0:
        return None
    model = _model(args)
    kda, _ = layer_kinds(model)
    steps_in_slice = trace["window_s"] * 1e3 / period_ms
    need = kda_step_required_bytes(model, rows / steps / kda) * steps_in_slice
    return 100.0 * need / (
        yardstick.peak(obs["device_kind"], "hbm_bytes_per_s") * seconds)
