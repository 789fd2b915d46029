"""Brumby's forward pass (Manifest AI's power-retention language model) in
plain float32 `jax.numpy`: no state, no cache, no batching, no embedding of
the keys, the layers and the heads two plain Python loops.

Follows `manifestai/Brumby-14B-Base/config.json` (the catalog's row) and,
where the config has no key, the published paper ("Scaling Context Requires
Rethinking Attention", arXiv:2507.04239) and modelling code as ISSUE 53's
author recalls them (each such reading is under `assumed` in the
configuration file). Per token, RMSNorm with a learned gain throughout:

- h = E[token].
- For each layer: a = norm1(h); [q | k | v] = a W (no biases); heads of
  `head_dim`, `num_attention_heads` of q and `num_key_value_heads` of k and v;
  q and k RMS-normed over a head (one gain of `head_dim` each); q and k
  rotated (rope, the halves, theta `rope_theta`, no scaling, at the token's
  position); q scaled by head_dim^-1/2; log g = logsigmoid(a W_g + b_g), one
  scalar a key head a token.
- POWER RETENTION of degree 2, in its ATTENTION FORM over the whole sequence:
  query head h reads key head h // (heads a key head); with c_i the running
  sum of log g,

      w_ij = exp(c_i - c_j) (q_i . k_j)^2     for j <= i
      o_i  = sum_j w_ij v_j / (sum_j w_ij + eps)

  a head at a time, the [T, T] weights formed whole. The program under test
  never forms them: it keeps the state of the recurrence the same numbers
  make (`models/brumby.py`), so the embedding, the recurrence and its chunk
  form are all checked against something that shares none of them.
- h += concat(o) W_o; m = norm2(h); h += (silu(m W_gate) * m W_up) W_down.
- After the last layer: logits = norm_f(h) W_head (untied).

Departures from the published description: the published inference keeps
keys and values for a short sequence and changes to the state past a
switch-over length; both are these numbers, and this file has no state at
all. None other known.

Parameters are read in the program's layout (`top/{wte, final_norm,
lm_head}`, `layer<i>/{attn_norm, attn_qkv, q_norm, k_norm, gate_w, gate_b,
attn_out, mlp_norm, mlp_gate_up, mlp_down}`; `attn_qkv` is [q | k | v] and
`mlp_gate_up` [gate | up] along the last axis). The weights stay in the type
they are served in and are cast to float32 a matrix at a time, the head in
blocks of the vocabulary and for the rows asked alone, so that the reference
fits beside a resident engine of 13 GB on the chip. Call under
`jax.default_matmul_precision("highest")`.

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
# columns of the head cast to float32 at a time (5,120 x 16,384 x 4 B)
HEAD_BLOCK = 16384


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, gain, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * _f32(gain)


def _rope(x, theta):
    """x [T, H, D]: rotate (x[..., :D/2], x[..., D/2:]) by position."""
    t, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def retention(q, k, v, log_g, eps):
    """The attention form, a head at a time. q [T, H, D] (scaled); k, v
    [T, KV, D]; log_g [T, KV]. Returns o [T, H, D]."""
    t, n_head, _ = q.shape
    group = n_head // k.shape[1]
    c = jnp.cumsum(log_g, axis=0)
    seen = jnp.tril(jnp.ones((t, t), bool))
    heads = []
    for h in range(n_head):
        kv = h // group
        # the difference first: every exponent taken is <= 0
        since = jnp.where(seen, c[:, None, kv] - c[None, :, kv], 0.0)
        w = jnp.where(seen, jnp.exp(since) * jnp.square(q[:, h] @ k[:, kv].T),
                      0.0)
        heads.append((w @ v[:, kv])
                     / (jnp.sum(w, axis=-1, keepdims=True) + eps))
    return jnp.stack(heads, axis=1)


def layer(x, p, n_head, n_kv, hd, eps, theta, retention_eps):
    """x [T, d] float32; p: one layer's parameters in any float type."""
    t = x.shape[0]
    a = _rms(x, p["attn_norm"], eps)
    fused = a @ _f32(p["attn_qkv"])
    q = fused[:, :n_head * hd].reshape(t, n_head, hd)
    k = fused[:, n_head * hd:(n_head + n_kv) * hd].reshape(t, n_kv, hd)
    v = fused[:, (n_head + n_kv) * hd:].reshape(t, n_kv, hd)
    q = _rope(_rms(q, p["q_norm"], eps), theta) / jnp.sqrt(float(hd))
    k = _rope(_rms(k, p["k_norm"], eps), theta)
    log_g = jax.nn.log_sigmoid(a @ _f32(p["gate_w"]) + _f32(p["gate_b"]))
    o = retention(q, k, v, log_g, retention_eps)
    x = x + o.reshape(t, n_head * hd) @ _f32(p["attn_out"])
    m = _rms(x, p["mlp_norm"], eps)
    gate, up = jnp.split(m @ _f32(p["mlp_gate_up"]), 2, axis=-1)
    return x + (jax.nn.silu(gate) * up) @ _f32(p["mlp_down"])


_layer = jax.jit(layer, static_argnums=(2, 3, 4, 5, 6, 7))


@jax.jit
def _embed(wte, tokens):
    return _f32(wte[tokens])


@jax.jit
def _head_block(x, gain, eps, block):
    return _rms(x, gain, eps) @ _f32(block)


def full_logits(params, config: dict, tokens, rows=None):
    """tokens [T] int -> logits [T or len(rows), vocab] float32: the whole
    sequence through every layer, every position reading all before it."""
    top = params.get("top", params)
    x = _embed(top["wte"], jnp.asarray(tokens, jnp.int32))
    assumed = config["assumed"]
    if assumed["retention_degree"] != 2:
        raise ValueError("the reference squares the scores: degree 2")
    for i in range(config["num_hidden_layers"]):
        x = _layer(x, params[f"layer{i}"], config["num_attention_heads"],
                   config["num_key_value_heads"], config["head_dim"],
                   config["rms_norm_eps"], float(config["rope_theta"]),
                   float(assumed["retention_eps"]))
    if rows is not None:
        x = x[jnp.asarray(rows)]
    head = top["lm_head"]
    return jnp.concatenate([
        _head_block(x, top["final_norm"], config["rms_norm_eps"],
                    head[:, at:at + HEAD_BLOCK])
        for at in range(0, head.shape[1], HEAD_BLOCK)], axis=-1)


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
    here, as `references/ouro.py` does: a row whose streamed token (the
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
            print(f"references/brumby.py: the token at position {r + 1} "
                  f"falls short by {short:.4g} of its row's rms, over the "
                  f"cell's limit of {limit}", file=sys.stderr, flush=True)
            out[r, ids[r + 1]] = out[r].max() \
                - REFUSED * np.sqrt(np.mean(out[r] ** 2))
    return out
