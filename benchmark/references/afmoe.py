"""AFMoE's forward pass (Arcee's Trinity) in plain float32 `jax.numpy`: no
cache, no pages, no batching, no grouped product.

Follows `arcee-ai/Trinity-Large-Preview/config.json` (the catalog's row) and,
where the config has no key, the family's published modelling code as ISSUE
46 recalls it (each such reading is under `assumed` in the configuration
file). Per token x of layer l, RMSNorm with a learned scale throughout:

- h_0 = E[token] * sqrt(hidden_size) (`mup_enabled`).
- Attention: a = norm(x); [q | k | v | g] = a W; q and k as heads of 128,
  each normed over its 128 with one gain for q and one for k; on a
  `sliding_attention` layer q and k are rotated (rope, the halves, theta
  10,000, no scaling), on a `full_attention` layer they are not; scores
  q_i . k_j / sqrt(128) for j <= i, and on a sliding layer only i - j <
  `sliding_window`; 6 query heads share a K/V head; o = softmax(s) v;
  o = o * sigmoid(g); x += norm(o W_o).
- Feed-forward: m = norm(x); the leading `num_dense_layers` layers a SwiGLU
  of `intermediate_size`; the others p = sigmoid(m W_r) in float32, the
  experts of a token the `num_experts_per_tok` largest of p + b, their
  weights p (without b) over their sum (`route_norm`) times `route_scale`,
  f = sum_k w_k E_k(m) + E_shared(m); x += norm(f).
- A final norm, an untied head.

The chip's share: only experts [first_expert, first_expert + num_experts)
are held, and a token's routed sum runs over those of its experts that are
held; what the absent experts would add is left out, as in the program. The
vocabulary is a slice: a smaller vocabulary.

Parameters are read in the program's layout (`top/{wte, final_norm,
lm_head}`, `layer<i>/{attn_norm, attn_qkvg, q_norm, k_norm, attn_out,
post_attn_norm, mlp_norm, post_mlp_norm}` and `mlp_gate_up, mlp_down` or
`router, router_bias, experts_gate_up, experts_down, shared_gate_up,
shared_down`). The weights stay in the type they are served in and are cast
to float32 a layer's attention, a feed-forward or one expert at a time, and
attention runs `QUERY_ROWS` query rows at a time against all the keys, so
that 9,003 positions fit beside the served model on the chip. Call under
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


def _rope(x, positions, theta):
    """x [T, H, D]: rotate (x[..., :D/2], x[..., D/2:]) by position."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _numbers(config: dict, sliding: bool):
    """The configuration's numbers a layer's attention needs, hashable."""
    return (config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["rms_norm_eps"],
            float(config["rope_theta"]),
            config["sliding_window"] if sliding else None)


@partial(jax.jit, static_argnums=(2,))
def attention(x, p, numbers):
    """x [T, d] float32 -> x + norm(gated attention W_o); p: the layer's
    attention parameters in any float type; `numbers[-1]` the window of a
    sliding layer (which also turns rope on), None for a full layer."""
    n_head, n_kv, hd, eps, theta, window = numbers
    p = _f32(p)
    t = x.shape[0]
    pos = jnp.arange(t)
    a = _rms(x, p["attn_norm"], eps)
    n_q, n_k = n_head * hd, n_kv * hd
    q, k, v, g = jnp.split(a @ p["attn_qkvg"], [n_q, n_q + n_k, n_q + 2 * n_k],
                           axis=-1)
    q = _rms(q.reshape(t, n_head, hd), p["q_norm"], eps)
    k = _rms(k.reshape(t, n_kv, hd), p["k_norm"], eps)
    v = v.reshape(t, n_kv, hd)
    if window is not None:
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
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
        s = jnp.where(seen[None], s, -jnp.inf)
        # a padded row past T sees every key: its output is cut below
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(rows, (blocks, first)).reshape(-1, n_q)[:t]
    o = o * jax.nn.sigmoid(g)
    return x + _rms(o @ p["attn_out"], p["post_attn_norm"], eps)


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
def _embed(wte, tokens, scale):
    return wte[tokens].astype(jnp.float32) * scale


@jax.jit
def _head(x, scale, lm_head, eps):
    return _rms(x, scale.astype(jnp.float32), eps) \
        @ lm_head.astype(jnp.float32)


ATTENTION_KEYS = ("attn_norm", "attn_qkvg", "q_norm", "k_norm", "attn_out",
                  "post_attn_norm")


def feed_forward(h, p, config: dict):
    """The layer's feed-forward of h [T, d] (already normed)."""
    if "router" not in p:
        return swiglu(h, p["mlp_gate_up"], p["mlp_down"])
    if config["score_func"] != "sigmoid" or not config["route_norm"] \
            or config["n_group"] != 1:
        raise ValueError("the reference routes by sigmoid scores, normalised "
                         "over the chosen, in one group")
    expert, weight = route(h, p["router"], p["router_bias"],
                           config["num_experts_per_tok"],
                           config["route_scale"])
    out = swiglu(h, p["shared_gate_up"], p["shared_down"])
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
    eps = config["rms_norm_eps"]
    scale = config["hidden_size"] ** 0.5 if config["mup_enabled"] else 1.0
    x = _embed(top["wte"], tokens, scale)
    for i in range(config["num_hidden_layers"]):
        p = params[f"layer{i}"]
        sliding = config["layer_types"][i] == "sliding_attention"
        x = attention(x, {k: p[k] for k in ATTENTION_KEYS},
                      _numbers(config, sliding))
        f = feed_forward(_norm(x, p["mlp_norm"], eps), p, config)
        x = x + _norm(f, p["post_mlp_norm"], eps)
    return x


def full_logits(params, config: dict, tokens, rows=None):
    """[T, V] float32 (or the given `rows` of it): one forward of `tokens`
    as they stand."""
    top = params.get("top", params)
    x = hidden(params, config, jnp.asarray(tokens, jnp.int32))
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return _head(x, top["final_norm"], top["lm_head"],
                 config["rms_norm_eps"])


def shortfall(row, token) -> float:
    """The harness's measure: how far the token's logit lies under the
    row's largest, in the row's rms."""
    return float(row.max() - row[token]) / float(np.sqrt(np.mean(row ** 2)))


def logits(params, config: dict, ids):
    """What `bench_check` reads: `ids` is a check prompt and all but the
    last of its streamed answer; row r holds the logits from which the token
    at position r + 1 was chosen, for the answer's positions (the last
    `check.new_tokens` rows; the head over 9,003 rows would be 0.9 GB), the
    other rows are zeros.

    The harness holds every serving cell to one limit, a shortfall of 0.5
    of a row's rms, which an 8-bit control passes (`check.shortfall_limit_why`
    in the configuration file). The cell's own limit is
    `check.shortfall_limit`, set between its two readings; the harness has
    no place for it, so it is applied here, as `references/sdar_moe.py`
    does: a row whose streamed token (the harness passes all but the last)
    falls short by more than the limit gets that token's logit put
    `REFUSED` rms under the top, which the harness then reads as not
    correct. Every other row is the logits as computed."""
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
            print(f"references/afmoe.py: the token at position {r + 1} falls "
                  f"short by {short:.4g} of its row's rms, over the cell's "
                  f"limit of {limit}", file=sys.stderr, flush=True)
            out[r, ids[r + 1]] = out[r].max() \
                - REFUSED * np.sqrt(np.mean(out[r] ** 2))
    return out
