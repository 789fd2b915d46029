"""Mistral-7B's forward pass in plain float32 `jax.numpy`: no cache, no
batching, no kernel.

Follows Jiang et al. 2023 and `Mistral-7B-v0.1/config.json`: RMSNorm,
rotary position embeddings on halves of each head (the `rotate_half` form),
grouped-query attention (every 4 query heads share one key/value head),
SwiGLU. Contexts here end below the 4,096 sliding window, so the window never
masks a key and full causal attention is the published mathematics.
Departure: the head is tied to the embedding, because the program's model has
one matrix (see the configuration file's `assumed`).

Parameters are read in the program's layout (`wte`, `layer<i>/{attn_norm,
attn_qkv,attn_out,mlp_norm,mlp_gate_up,mlp_down}`, `final_norm`; `attn_qkv`
is [q | k | v] and `mlp_gate_up` is [gate | up] along the last axis). The
weights stay in the type they are served in and are cast to float32 one layer
at a time, so that the reference fits beside them on the chip: the values are
the same, only the arithmetic is float32. Call under
`jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale


def _rope(x, theta):
    """x [T, H, D]: rotate (x[..., :D/2], x[..., D/2:]) by position."""
    t, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(x, p, n_head, n_kv, hd, eps, theta):
    """x [T, d] float32; p: one layer's parameters in any float type."""
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    t = x.shape[0]
    h = _rms(x, p["attn_norm"]["scale"], eps)
    fused = h @ p["attn_qkv"]["kernel"]
    q = fused[:, :n_head * hd].reshape(t, n_head, hd)
    k = fused[:, n_head * hd:(n_head + n_kv) * hd].reshape(t, n_kv, hd)
    v = fused[:, (n_head + n_kv) * hd:].reshape(t, n_kv, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, n_head // n_kv, axis=1)
    v = jnp.repeat(v, n_head // n_kv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(float(hd))
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], scores,
                       -jnp.inf)
    att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + att.reshape(t, n_head * hd) @ p["attn_out"]["kernel"]
    h = _rms(x, p["mlp_norm"]["scale"], eps)
    gate, up = jnp.split(h @ p["mlp_gate_up"]["kernel"], 2, axis=-1)
    return x + (jax.nn.silu(gate) * up) @ p["mlp_down"]["kernel"]


_layer = jax.jit(layer, static_argnums=(2, 3, 4, 5, 6))


@jax.jit
def _embed(wte, tokens):
    return wte[tokens].astype(jnp.float32)


@jax.jit
def _head(x, scale, wte, eps):
    x = _rms(x, scale.astype(jnp.float32), eps)
    return x @ wte.astype(jnp.float32).T


def logits(params, config: dict, tokens):
    """tokens [T] int -> logits [T, vocab] float32: the whole sequence in one
    pass, every position attending to all before it."""
    x = _embed(params["wte"], tokens)
    for i in range(config["num_hidden_layers"]):
        x = _layer(x, params[f"layer{i}"], config["num_attention_heads"],
                   config["num_key_value_heads"], config["head_dim"],
                   config["rms_norm_eps"], config["rope_theta"])
    return _head(x, params["final_norm"]["scale"], params["wte"],
                 config["rms_norm_eps"])
