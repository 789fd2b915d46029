"""MiMo-V2's forward pass (Xiaomi's MiMo-V2-Flash / V2.5, the language model)
in plain float32 `jax.numpy`: no cache, no pages, no batching, no grouped
product.

Follows `XiaomiMiMo/MiMo-V2.5/config.json` (the catalog's row); what the
config does not settle is listed under `assumed` in the configuration file.
Per token x of layer l, RMSNorm with a learned scale (`layernorm_epsilon`):

- h_0 = E[token] (no scale). Layer l is a full layer where
  `hybrid_layer_pattern[l]` is 0 and a window layer where 1; H =
  `num_attention_heads` query heads, KVH = `num_key_value_heads` (full) or
  `swa_num_key_value_heads` (window).
- Attention: a = norm(x); [q | k | v] = a W with q [H, head_dim], k [KVH,
  head_dim], v [KVH, v_head_dim]; rope turns the first
  floor(`partial_rotary_factor` x head_dim) channels of every q and k head
  (their halves rotated; base `rope_theta` in a full layer, `swa_rope_theta`
  in a window layer), the others pass; scores s_ij = q_i . k_j /
  sqrt(head_dim) for j <= i, and in a window layer only i - j <
  `sliding_window`; H / KVH query heads share a K/V head. In a window layer
  (`add_swa_attention_sink_bias`) a learned scalar b_h a query head joins
  the denominator and brings no value: p_ij = exp(s_ij) / (exp(b_h) +
  sum_j' exp(s_ij')); a full layer's p is the plain softmax.
  o_i = `attention_value_scale` x sum_j p_ij v_j; x += o W_o.
- Feed-forward: m = norm(x); where `moe_layer_freq[l]` is 0 a SwiGLU of
  `intermediate_size`; else p = sigmoid(m W_r) in float32, the experts of a
  token the `num_experts_per_tok` largest of p + b, their weights p
  (without b) over their sum (`norm_topk_prob`) times
  `routed_scaling_factor` (null: 1), f = sum_k w_k E_k(m); no shared
  expert; x += f.
- A final norm, an untied head.

The chip's share: only experts [first_expert, first_expert +
n_routed_experts) are held, and a token's routed sum runs over those of its
experts that are held; what the absent experts would add is left out, as in
the program. The vocabulary is a slice: a smaller vocabulary.

Parameters are read in the program's layout (`top/{wte, final_norm,
lm_head}`, `layer<i>/{attn_norm, attn_qkv, attn_out, mlp_norm}`, `sink` in
a window layer, and `mlp_gate_up, mlp_down` or `router, router_bias,
experts_gate_up, experts_down`). It imports nothing from `ray_tpu.models`.
The weights stay in the type they are served in and are cast to float32 a
layer's attention, a feed-forward or one expert at a time, and attention
runs `QUERY_ROWS` query rows at a time against all the keys, so that 9,003
positions fit beside the served model on the chip. Call under
`jax.default_matmul_precision("highest")`.

`logits` is what the harness's `bench_check` calls, and where the cell's own
limit is applied (see there).
"""

from __future__ import annotations

import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

QUERY_ROWS = 128
# how far under the top a refused token's logit is put, in the row's rms:
# past any limit the harness has
REFUSED = 100.0


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale


def _rope(x, positions, theta, n):
    """x [T, H, D]: the first n channels of every head, as (x[..., :n/2],
    x[..., n/2:n]), rotated by position; the others as they are."""
    inv = theta ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ang = positions.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2, rest = x[..., :n // 2], x[..., n // 2:n], x[..., n:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _numbers(config: dict, window: bool):
    """The configuration's numbers a layer's attention needs, hashable:
    (query heads, K/V heads, head_dim, v_head_dim, channels rope turns, eps,
    rope's base, the value scale, the window or None)."""
    hd = config["head_dim"]
    return (config["num_attention_heads"],
            config["swa_num_key_value_heads" if window
                   else "num_key_value_heads"],
            hd, config["v_head_dim"],
            int(config["partial_rotary_factor"] * hd),
            config["layernorm_epsilon"],
            float(config["swa_rope_theta" if window else "rope_theta"]),
            config["attention_value_scale"],
            config["sliding_window"] if window else None)


@partial(jax.jit, static_argnums=(2,))
def attention(x, p, numbers):
    """x [T, d] float32 -> x + attention W_o; p: the layer's attention
    parameters in any float type (with `sink` [H] in a window layer);
    `numbers[-1]` the window, None for a full layer."""
    n_head, n_kv, hd, vd, n_rope, eps, theta, value_scale, window = numbers
    p = _f32(p)
    t = x.shape[0]
    pos = jnp.arange(t)
    a = _rms(x, p["attn_norm"], eps)
    n_q, n_k = n_head * hd, n_kv * hd
    q, k, v = jnp.split(a @ p["attn_qkv"], [n_q, n_q + n_k], axis=-1)
    q = _rope(q.reshape(t, n_head, hd), pos, theta, n_rope)
    k = _rope(k.reshape(t, n_kv, hd), pos, theta, n_rope)
    v = v.reshape(t, n_kv, vd)
    k = jnp.repeat(k, n_head // n_kv, axis=1)
    v = jnp.repeat(v, n_head // n_kv, axis=1)
    pad = -t % QUERY_ROWS
    blocks = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, QUERY_ROWS, n_head, hd)
    first = jnp.arange(blocks.shape[0]) * QUERY_ROWS

    def rows(args):
        q_blk, i0 = args
        i = i0 + jnp.arange(QUERY_ROWS)
        seen = pos[None, :] <= i[:, None]
        if window is not None:
            seen &= i[:, None] - pos[None, :] < window
        s = jnp.einsum("qhd,khd->hqk", q_blk, k) * hd ** -0.5
        e = jnp.where(seen[None], s, -jnp.inf)
        # a padded row past T sees every key: its output is cut below
        top = jnp.max(e, axis=-1, keepdims=True)
        if "sink" in p:
            top = jnp.maximum(top, p["sink"][:, None, None])
        e = jnp.exp(e - top)
        den = jnp.sum(e, axis=-1, keepdims=True)
        if "sink" in p:
            den = den + jnp.exp(p["sink"][:, None, None] - top)
        return jnp.einsum("hqk,khd->qhd", e / den, v)

    o = jax.lax.map(rows, (blocks, first)).reshape(-1, n_head * vd)[:t]
    return x + (value_scale * o) @ p["attn_out"]


@jax.jit
def swiglu(h, gate_up, down):
    gate, up = jnp.split(h @ gate_up.astype(jnp.float32), 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ down.astype(jnp.float32)


@partial(jax.jit, static_argnums=(3, 4))
def route(h, router, bias, top_k, scale):
    """h [T, d] -> (expert ids [T, top_k], weights [T, top_k])."""
    g = jax.nn.sigmoid(h @ router.astype(jnp.float32))
    _, expert = jax.lax.top_k(g + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(g, expert, axis=-1)
    return expert, w / jnp.sum(w, axis=-1, keepdims=True) * scale


@jax.jit
def _norm(x, scale, eps):
    return _rms(x, scale.astype(jnp.float32), eps)


@jax.jit
def _embed(wte, tokens):
    return wte[tokens].astype(jnp.float32)


@jax.jit
def _head(x, scale, lm_head, eps):
    return _rms(x, scale.astype(jnp.float32), eps) \
        @ lm_head.astype(jnp.float32)


ATTENTION_KEYS = ("attn_norm", "attn_qkv", "attn_out", "sink")


def feed_forward(h, p, config: dict):
    """The layer's feed-forward of h [T, d] (already normed)."""
    if "router" not in p:
        return swiglu(h, p["mlp_gate_up"], p["mlp_down"])
    if config["scoring_func"] != "sigmoid" or not config["norm_topk_prob"] \
            or config["n_group"] != 1 or config["n_shared_experts"]:
        raise ValueError("the reference routes by sigmoid scores, normalised "
                         "over the chosen, in one group, and adds no shared "
                         "expert")
    scale = config["routed_scaling_factor"]
    expert, weight = route(h, p["router"], p["router_bias"],
                           config["num_experts_per_tok"],
                           1.0 if scale is None else scale)
    out = jnp.zeros_like(h)
    first = config.get("deployment_share", {}).get("first_expert", 0)
    for e in range(p["experts_down"].shape[0]):     # the experts held here
        w_e = jnp.sum(jnp.where(expert == first + e, weight, 0.0), axis=-1)
        out = out + w_e[:, None] * swiglu(h, p["experts_gate_up"][e],
                                          p["experts_down"][e])
    return out


def hidden(params, config: dict, tokens):
    """tokens [T] int -> the last layer's output [T, d] float32: the whole
    sequence in one pass."""
    top = params.get("top", params)
    eps = config["layernorm_epsilon"]
    x = _embed(top["wte"], tokens)
    for i in range(config["num_hidden_layers"]):
        p = params[f"layer{i}"]
        window = config["hybrid_layer_pattern"][i] == 1
        if ("sink" in p) != (window and config["add_swa_attention_sink_bias"]
                             or not window
                             and config["add_full_attention_sink_bias"]):
            raise ValueError(f"layer {i}: the weights and the file disagree "
                             f"on the sink")
        if ("router" in p) != (config["moe_layer_freq"][i] == 1):
            raise ValueError(f"layer {i}: the weights and the file disagree "
                             f"on the experts")
        x = attention(x, {k: p[k] for k in ATTENTION_KEYS if k in p},
                      _numbers(config, window))
        x = x + feed_forward(_norm(x, p["mlp_norm"], eps), p, config)
    return x


def full_logits(params, config: dict, tokens, rows=None):
    """[T, V] float32 (or the given `rows` of it): one forward of `tokens`
    as they stand."""
    top = params.get("top", params)
    x = hidden(params, config, jnp.asarray(tokens, jnp.int32))
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return _head(x, top["final_norm"], top["lm_head"],
                 config["layernorm_epsilon"])


def shortfall(row, token) -> float:
    """The harness's measure: how far the token's logit lies under the
    row's largest, in the row's rms."""
    return float(row.max() - row[token]) / float(np.sqrt(np.mean(row ** 2)))


def logits(params, config: dict, ids):
    """What `bench_check` reads: `ids` is a check prompt and all but the
    last of its streamed answer; row r holds the logits from which the token
    at position r + 1 was chosen, for the answer's positions (the last
    `check.new_tokens` rows; the head over 9,003 rows would be 0.7 GB), the
    other rows are zeros.

    The harness holds every serving cell to one limit, a shortfall of 0.5
    of a row's rms. The cell's own limit is `check.shortfall_limit`, set
    between its readings (`check.shortfall_limit_why` in the configuration
    file); the harness has no place for it, so it is applied here, as
    `references/afmoe.py` applies Trinity's: a row whose streamed token (the
    harness passes all but the last) falls short by more than the limit gets
    that token's logit put `REFUSED` rms under the top, which the harness
    then reads as not correct. Every other row is the logits as computed."""
    check = config["check"]
    ids = np.asarray(ids)
    n = len(ids)
    rows = list(range(max(0, n - check["new_tokens"]), n))
    got = np.asarray(full_logits(params, config, ids, rows))
    out = np.zeros((n, got.shape[-1]), np.float32)
    out[rows] = got
    limit = check.get("shortfall_limit")
    for r in rows[:-1] if limit is not None else ():
        short = shortfall(out[r], ids[r + 1])
        if short > limit:
            print(f"references/mimo_v2.py: the token at position {r + 1} "
                  f"falls short by {short:.4g} of its row's rms, over the "
                  f"cell's limit of {limit}", file=sys.stderr, flush=True)
            out[r, ids[r + 1]] = out[r].max() \
                - REFUSED * np.sqrt(np.mean(out[r] ** 2))
    return out
