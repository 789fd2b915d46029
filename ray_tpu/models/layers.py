"""The layer math the model families share, once and under public names.

A family file (`llama.py`, `gpt.py`, `kimi_k2.py`, `ling_hybrid.py`,
`sdar_moe.py`, `afmoe.py`, `ouro.py`, `brumby.py`, `mimo_v2.py`,
`moe_gpt.py`) composes these and keeps
what is its own: its config, its attention, its step functions, and the
names the serving engine reads from a family's module
(`serve/llm/engine.py`, `_family_cache`). This module imports no family, and
no family imports an underscore name from another.

Every function here is plain Python around `jax.numpy`: it leaves no name in
a lowered program, so a family that calls one lowers to the text it had when
the ops stood in its own file, as long as their ORDER is kept. The tables of
program hashes in `tests/test_serve_llm.py` (`NEIGHBOUR_PROGRAMS`,
`AFMOE_PROGRAMS`, `STATEFUL_AND_LOOP_PROGRAMS`) hold every family to that,
and `SEEDED_WEIGHTS` holds `Weights` to the arrays each family drew before.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.parallel.moe import MOE_COUNTS, expert_shard_layer

# a masked score: once a row's maximum is a real score, a masked key weighs
# exp(NEG_INF - m) = 0 exactly
NEG_INF = -1e30
# `rope`'s `at` for cos/sin rows gathered a position ([..., D/2]) against
# heads [..., H, D]: one axis for the heads
A_HEAD = np.s_[..., None, :]


def unboxed_params(variables):
    """Strip the {"params": ...} wrapper and the nn.Partitioned boxes; a
    `top` group (`Weights` under `name="top"`: the embedding, the final norm,
    the head) is folded into the level above it."""
    p = nn.meta.unbox(variables)
    p = p.get("params", p)
    if "top" in p:
        p = {**{k: v for k, v in p.items() if k != "top"}, **p["top"]}
    return p


def rms(x, scale, eps, dtype):
    """RMSNorm over the last axis, float32 inside (`llama.RMSNorm` is this
    with the scale as its parameter, so prefill and decode agree op for
    op)."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(dtype)


def rope(x, cos, sin, at=None):
    """Rotate the halves of the last axis; cos/sin broadcast against
    x[..., :D/2] as they are, or, with `at`, as `cos[at]` and `sin[at]`:
    indexed here, after x is cast and split, which is where the llama forms
    (`apply_rope`, one position a sequence, a window of positions) always
    did it and where their programs' text has it."""
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    if at is not None:
        cos, sin = cos[at], sin[at]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def swiglu(x, gate_up, down, dtype):
    """[gate | up] fused into one product (MXU-friendlier than two small
    ones), split on the last axis."""
    gate, up = jnp.split(x @ gate_up.astype(dtype), 2, axis=-1)
    return (nn.silu(gate) * up) @ down.astype(dtype)


# -- the weights of the serving families --------------------------------------

def _uniform(lo: float, hi: float, log: bool = False):
    def init(key, shape, dtype):
        x = jax.random.uniform(key, shape, jnp.float32, lo, hi)
        return (jnp.log(x) if log else x).astype(dtype)
    return init


# kind of parameter -> (initializer, dtype; None: the model's `param_dtype`)
INITS = {
    "w": (nn.initializers.normal(0.02), None),
    "ones": (nn.initializers.ones, None),
    # the selection bias is float32 in the checkpoint. A trained one
    # balances the experts' load; under a random router a deviation of 0.01
    # already changes three in ten tokens' sets of experts and leaves the
    # load's spread as it is, and 0.1 sends most pairs to the dozen experts
    # with the largest bias (the top sigmoid scores lie within 0.03 of each
    # other)
    "bias": (nn.initializers.normal(0.01), jnp.float32),
    # four taps a channel, the depth-wise convolution's usual
    # uniform(+-1/sqrt(width)): activations of order one
    "conv": (_uniform(-0.5, 0.5), None),
    # trained tensors that place the KDA decays; drawn so that the channels
    # spread over both ends of (exp(lower), 1): exp(A_log) in (1, 16) as
    # Kimi Linear initialises it, the gate's bias in (-4, 1) around
    # projections of deviation one
    "a_log": (_uniform(1.0, 16.0, log=True), jnp.float32),
    "dt_bias": (_uniform(-4.0, 1.0), jnp.float32),
    # a window layer's learned sinks, one score a query head, drawn so that
    # a check can see them: with weights of deviation 0.02 every score is
    # near 0 and a full window's 128 keys weigh about 128, so a sink around
    # ln 128 with deviation 1 holds a fifth to four fifths of a row's
    # softmax mass; drawn at zero it would hold under 1%
    "sink": (lambda key, shape, dtype: (
        np.log(128.0) + jax.random.normal(key, shape, jnp.float32)
    ).astype(dtype), jnp.float32),
}


class Weights(nn.Module):
    """Declares one group of parameters and returns them as a dict. `shapes`:
    name -> (shape, how it is drawn): a kind of `INITS`, which brings its
    initializer and its dtype, or an initializer, drawn in `param_dtype`.
    Flax draws a parameter from its path and its place among the group's, so
    the order of `shapes` is part of a seed's weights."""
    shapes: Any
    param_dtype: Any

    @nn.compact
    def __call__(self):
        params = {}
        for name, (shape, how) in self.shapes.items():
            init, dtype = INITS[how] if isinstance(how, str) else (how, None)
            params[name] = self.param(name, init, shape,
                                      dtype or self.param_dtype)
        return params


def top_shapes(cfg) -> dict:
    """The `top` group of a family with an untied head."""
    return {"wte": ((cfg.vocab_size, cfg.d_model), "w"),
            "final_norm": ((cfg.d_model,), "ones"),
            "lm_head": ((cfg.d_model, cfg.vocab_size), "w")}


def declare_weights(top: dict, layers, param_dtype) -> dict:
    """Called from a flax module's compact `__call__`: the `top` group, then
    a group `layer<i>` for each of `layers` (its `shapes`), as one dict with
    `top`'s names at its top level (what `unboxed_params` makes of the
    variables `init` returns)."""
    p = Weights(top, param_dtype, name="top")()
    for i, shapes in enumerate(layers):
        p[f"layer{i}"] = Weights(shapes, param_dtype, name=f"layer{i}")()
    return p


# -- parts of a layer ---------------------------------------------------------

def routed_feed_forward(lp, cfg, i: int, h, valid):
    """Layer i's feed-forward of h [N, d]: the dense SwiGLU below
    `cfg.n_dense_layer`, else this chip's experts' part of the sigmoid-routed
    sum (`expert_shard_layer`; group-limited where the config has `n_group`
    and `topk_group`, one group where not), plus the shared expert where the
    config has one (`n_shared`; a model without has no such weights, and the
    term is left out, not computed at width 0). Returns (result [N, d],
    counts int32[len(MOE_COUNTS)])."""
    if i < cfg.n_dense_layer:
        with jax.named_scope("dense_mlp"):
            return swiglu(h, lp["mlp_gate_up"], lp["mlp_down"],
                          cfg.dtype), jnp.zeros(len(MOE_COUNTS), jnp.int32)
    routed, counts = expert_shard_layer(
        h, lp["router"], lp["router_bias"],
        {"gate_up": lp["experts_gate_up"], "down": lp["experts_down"]},
        cfg.first_expert, cfg.n_experts, cfg.top_k, cfg.routed_scale,
        valid=valid, n_group=getattr(cfg, "n_group", 1),
        topk_group=getattr(cfg, "topk_group", 1))
    if not cfg.n_shared:
        return routed, counts
    with jax.named_scope("moe_shared"):
        shared = swiglu(h, lp["shared_gate_up"], lp["shared_down"],
                        cfg.dtype)
    return routed + shared, counts


def head(p, cfg, x):
    """The final norm and the untied head."""
    with jax.named_scope("lm_head"):
        x = rms(x, p["final_norm"], cfg.norm_eps, cfg.dtype)
        return x @ p["lm_head"].astype(cfg.dtype)


def slot_state(arena, slot, layer: int):
    """Layer `layer` of slot `slot` (traced) of a sequence-state arena array
    [slots, L, ...], as a float32 batch of one: a slice, not a gather."""
    at = (slot, layer) + (0,) * (arena.ndim - 2)
    return jax.lax.dynamic_slice(
        arena, at, (1, 1) + arena.shape[2:])[0].astype(jnp.float32)


def put_slot_state(arena, new, slot, layer: int):
    """`new` ([1, ...], as `slot_state` gives it) written where it was read:
    an update of the donated arena in place."""
    at = (slot, layer) + (0,) * (arena.ndim - 2)
    return jax.lax.dynamic_update_slice(arena, new[None].astype(arena.dtype),
                                        at)


def last_row(rows, true_len):
    """rows [B, S, ...] of a padded prompt batch from position 0 on -> each
    sequence's last real row [B, ...] (row 0 of an empty one)."""
    idx = jnp.maximum(true_len - 1, 0)
    return jnp.take_along_axis(rows, idx[:, None, None], axis=1)[:, 0]
