"""Ouro's forward pass (ByteDance's looped language model) in plain float32
`jax.numpy`: no cache, no pages, no batching, the passes and the layers two
plain Python loops.

Follows `ByteDance/Ouro-2.6B/config.json` (the catalog's row) and, where the
config has no key, the family's published modelling code as ISSUE 48's author
recalls it (each such reading is under `assumed` in the configuration file).
Per token, RMSNorm with a learned gain throughout:

- h = E[token] (no scale).
- For pass t = 0 .. `total_ut_steps` - 1, for layer l = 0 .. L - 1, the SAME
  weights in every pass:
  a = norm1_l(h); [q | k | v] = a W (no biases); heads of `head_dim`; q and k
  rotated (rope, the halves, theta `rope_theta`, no scaling, at the token's
  position, the same in every pass); scores q_i . k_j / sqrt(head_dim) for
  j <= i over the keys of THIS pass and layer; o = softmax(s) v;
  h += norm2_l(o W_o); m = norm3_l(h); h += norm4_l((silu(m W_g) * m W_u) W_d).
- After the last layer of every pass: h = norm_f(h), the one final norm, and
  the normed h is what the next pass starts from; g_t = sigmoid(h w_e + b_e).
- Exit: p_t = g_t * prod_{j<t} (1 - g_j) for t < T - 1, p_{T-1} what is left.
  The model answers from the first pass whose cumulative p reaches
  `early_exit_threshold`; at the published 1.0 that is the last pass for
  every token: logits = h_{T-1} W_head (untied). The gate does not reach the
  logits; its p is returned beside them.

Departures from the published description: none known; a threshold other
than 1.0 is refused and never computed as something else.

Parameters are read in the program's layout (`top/{wte, final_norm,
exit_gate, exit_bias, lm_head}`, `layer<i>/{attn_norm, attn_qkv, attn_out,
post_attn_norm, mlp_norm, mlp_gate_up, mlp_down, post_mlp_norm}`; `attn_qkv`
is [q | k | v] and `mlp_gate_up` [gate | up] along the last axis). The
weights stay in the type they are served in and are cast to float32 one layer
at a time, so that the reference fits beside the served model on the chip.
Call under `jax.default_matmul_precision("highest")`.

`logits` is what the harness's `bench_check` calls, and where the cell's own
limit is applied (see there).
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

# how far under the top a refused token's logit is put, in the row's rms:
# past any limit the harness has
REFUSED = 100.0


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _rms(x, gain, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * gain


def _rope(x, theta):
    """x [T, H, D]: rotate (x[..., :D/2], x[..., D/2:]) by position."""
    t, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(x, p, n_head, n_kv, hd, eps, theta):
    """x [T, d] float32; p: one layer's parameters in any float type. The
    keys and values are this call's own: a pass never sees another's."""
    p = _f32(p)
    t = x.shape[0]
    a = _rms(x, p["attn_norm"], eps)
    fused = a @ p["attn_qkv"]
    q = fused[:, :n_head * hd].reshape(t, n_head, hd)
    k = fused[:, n_head * hd:(n_head + n_kv) * hd].reshape(t, n_kv, hd)
    v = fused[:, (n_head + n_kv) * hd:].reshape(t, n_kv, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, n_head // n_kv, axis=1)
    v = jnp.repeat(v, n_head // n_kv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(float(hd))
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], scores,
                       -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + _rms(o.reshape(t, n_head * hd) @ p["attn_out"],
                 p["post_attn_norm"], eps)
    m = _rms(x, p["mlp_norm"], eps)
    gate, up = jnp.split(m @ p["mlp_gate_up"], 2, axis=-1)
    return x + _rms((jax.nn.silu(gate) * up) @ p["mlp_down"],
                    p["post_mlp_norm"], eps)


_layer = jax.jit(layer, static_argnums=(2, 3, 4, 5, 6))


@jax.jit
def _embed(wte, tokens):
    return wte[tokens].astype(jnp.float32)


@jax.jit
def _close_pass(x, gain, gate_w, gate_b, eps):
    """The final norm after a pass, and the exit gate on its result."""
    x = _rms(x, gain.astype(jnp.float32), eps)
    return x, jax.nn.sigmoid(x @ gate_w.astype(jnp.float32)[:, 0]
                             + gate_b.astype(jnp.float32)[0])


@jax.jit
def _head(x, lm_head):
    return x @ lm_head.astype(jnp.float32)


def exit_distribution(gates):
    """gates: g_t [T] of every pass, one after the other -> [p_0 .. p_{T-1}]:
    a plain loop over the passes."""
    left, out = 1.0, []
    for g in gates[:-1]:
        out.append(g * left)
        left = left * (1.0 - g)
    return out + [left + 0.0 * gates[-1]]


def full_logits(params, config: dict, tokens, rows=None):
    """tokens [T] int -> (logits [T or len(rows), vocab] float32, the exit
    distribution p [n_pass, T]): the whole sequence through every pass, every
    position attending to all before it in that pass and layer."""
    if config["early_exit_threshold"] != 1:
        raise ValueError(
            f"early_exit_threshold {config['early_exit_threshold']}: the "
            f"reference answers from the last pass, which is the published 1")
    top = params.get("top", params)
    x = _embed(top["wte"], jnp.asarray(tokens, jnp.int32))
    gates = []
    for _ in range(config["total_ut_steps"]):
        for i in range(config["num_hidden_layers"]):
            x = _layer(x, params[f"layer{i}"], config["num_attention_heads"],
                       config["num_key_value_heads"], config["head_dim"],
                       config["rms_norm_eps"], float(config["rope_theta"]))
        x, g = _close_pass(x, top["final_norm"], top["exit_gate"],
                           top["exit_bias"], config["rms_norm_eps"])
        gates.append(g)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return _head(x, top["lm_head"]), jnp.stack(exit_distribution(gates))


def shortfall(row, token) -> float:
    """The harness's measure: how far the token's logit lies under the
    row's largest, in the row's rms."""
    return float(row.max() - row[token]) / float(np.sqrt(np.mean(row ** 2)))


def logits(params, config: dict, ids):
    """What `bench_check` reads: `ids` is a check prompt and all but the
    last of its streamed answer; row r holds the logits from which the token
    at position r + 1 was chosen, for the answer's positions (the last
    `check.new_tokens` rows), the other rows are zeros.

    The harness holds every serving cell to one limit, a shortfall of 0.5 of
    a row's rms. The cell's own limit is `check.shortfall_limit`, set between
    its two readings on the chip (`check.shortfall_limit_why` in the
    configuration file); the harness has no place for it, so it is applied
    here, as `references/afmoe.py` does: a row whose streamed token (the
    harness passes all but the last) falls short by more than the limit gets
    that token's logit put `REFUSED` rms under the top, which the harness
    then reads as not correct. Every other row is the logits as computed."""
    check = config["check"]
    ids = np.asarray(ids)
    n = len(ids)
    rows = list(range(max(0, n - check["new_tokens"]), n))
    got = np.asarray(full_logits(params, config, ids, rows)[0])
    out = np.zeros((n, got.shape[-1]), np.float32)
    out[rows] = got
    limit = check.get("shortfall_limit")
    for r in rows[:-1] if limit is not None else ():
        short = shortfall(out[r], ids[r + 1])
        if short > limit:
            print(f"references/ouro.py: the token at position {r + 1} falls "
                  f"short by {short:.4g} of its row's rms, over the cell's "
                  f"limit of {limit}", file=sys.stderr, flush=True)
            out[r, ids[r + 1]] = out[r].max() \
                - REFUSED * np.sqrt(np.mean(out[r] ** 2))
    return out
