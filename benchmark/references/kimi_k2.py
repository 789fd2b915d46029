"""Kimi-K2's forward pass (the language model) in plain float32 `jax.numpy`:
no cache, no batching, no grouped product, no absorbed attention.

Follows `moonshotai/Kimi-K2.6/config.json` and DeepSeek-V3's modelling code,
whose block it is. Per token x, RMSNorm with a learned scale throughout:

- Attention, every layer: h = norm(x); c_q = norm(h W_dq); q = c_q W_uq as
  heads of [q_nope | q_rope]; [c_kv | k_rope] = h W_dkv; c_kv = norm(c_kv);
  k_rope is one vector for all heads; rope (rotating the halves) on q_rope
  and k_rope with YaRN frequencies; [k_nope | v] a head = c_kv W_ukv; scores
  (q_nope . k_nope + q_rope . k_rope) * (nope + rope)^-0.5 * mscale^2 with
  mscale = 0.1 * mscale_all_dim * ln(factor) + 1; causal softmax; o = P v;
  x += o W_o.
- Feed-forward, the leading `first_k_dense_replace` layers: SwiGLU.
- Feed-forward, the others: g = sigmoid(h W_r); the experts of a token are
  the `num_experts_per_tok` largest of g + b (one group: the group step of
  `noaux_tc` is the identity), their weights g (without b) over their sum
  times `routed_scaling_factor`; x += sum_k w_k E_k(h) + E_shared(h).
- Final norm, an untied head.

Departures, each also under `assumed` in the configuration file:
- The chip's share: only experts [first_expert, first_expert +
  n_routed_experts) are held, and a token's result is the weighted sum over
  those of its experts that are held (a loop over them); what the absent
  experts would add is left out, as in the program. The vocabulary is a
  slice: a smaller vocabulary.
- Rope rotates the halves of the rope channels; the checkpoint interleaves
  the pairs, a fixed permutation of columns that random weights do not see.
- No vision tower: the catalog's config has the language model alone.

Parameters are read in the program's layout (`top/{wte, final_norm,
lm_head}`, `layer<i>/{attn_norm, q_a, q_a_norm, q_b, kv_a, kv_a_norm, kv_b,
attn_out, mlp_norm}` and `mlp_gate_up, mlp_down` or `router, router_bias,
experts_gate_up, experts_down, shared_gate_up, shared_down`; [gate | up]
along the last axis). The weights stay in the type they are served in and
are cast to float32 a layer's attention, a feed-forward or one expert at a
time, and attention is computed eight heads at a time, so that the reference
fits beside the served model on the chip: the values are the same, only the
arithmetic is float32. Call under `jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

HEADS_AT_A_TIME = 8


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim, base, scaling):
    """The `dim / 2` rotation frequencies: extrapolated where a channel
    turns more than `beta_fast` times over the original context,
    interpolated (over `factor`) where fewer than `beta_slow`, a linear
    blend between."""
    extra = base ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    inter = extra / scaling["factor"]
    original = scaling["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    m = 1.0 - ramp
    return inter * (1 - m) + extra * m


def _rope(x, positions, inv_freq, scale):
    """x [T, ..., D]: rotate (x[..., :D/2], x[..., D/2:]) by position."""
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _numbers(config):
    """The configuration's numbers the layers need, hashable."""
    s = config["rope_scaling"]
    return (config["num_attention_heads"], config["kv_lora_rank"],
            config["qk_nope_head_dim"], config["qk_rope_head_dim"],
            config["v_head_dim"], config["rms_norm_eps"],
            float(config["rope_theta"]),
            tuple(sorted((k, v) for k, v in s.items() if k != "type")))


@partial(jax.jit, static_argnums=(2,))
def attention(x, p, numbers):
    """x [T, d] float32 -> x + attention; p: the layer's attention
    parameters in any float type."""
    n_head, kv_rank, nope, rope, v_dim, eps, theta, scaling = numbers
    scaling = dict(scaling)
    p = _f32(p)
    t = x.shape[0]
    pos = jnp.arange(t)
    inv = yarn_inv_freq(rope, theta, scaling)
    rope_scale = _mscale(scaling["factor"], scaling["mscale"]) \
        / _mscale(scaling["factor"], scaling["mscale_all_dim"])
    m_all = _mscale(scaling["factor"], scaling["mscale_all_dim"])
    softmax_scale = (nope + rope) ** -0.5 * m_all * m_all

    h = _rms(x, p["attn_norm"], eps)
    q = (_rms(h @ p["q_a"], p["q_a_norm"], eps) @ p["q_b"]).reshape(
        t, n_head, nope + rope)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], pos, inv,
                                          rope_scale)
    kv = h @ p["kv_a"]
    c_kv = _rms(kv[:, :kv_rank], p["kv_a_norm"], eps)
    k_rope = _rope(kv[:, kv_rank:], pos, inv, rope_scale)       # [T, rope]
    kv_b = p["kv_b"].reshape(kv_rank, n_head, nope + v_dim)
    causal = jnp.tril(jnp.ones((t, t), bool))
    outs = []
    for h0 in range(0, n_head, HEADS_AT_A_TIME):
        heads = slice(h0, h0 + HEADS_AT_A_TIME)
        expanded = jnp.einsum("tc,chn->thn", c_kv, kv_b[:, heads])
        k_nope, v = expanded[..., :nope], expanded[..., nope:]
        scores = (jnp.einsum("qhn,khn->hqk", q_nope[:, heads], k_nope)
                  + jnp.einsum("qhr,kr->hqk", q_rope[:, heads], k_rope)) \
            * softmax_scale
        scores = jnp.where(causal[None], scores, -jnp.inf)
        outs.append(jnp.einsum("hqk,khv->qhv",
                               jax.nn.softmax(scores, axis=-1), v))
    o = jnp.concatenate(outs, axis=1).reshape(t, n_head * v_dim)
    return x + o @ p["attn_out"]


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


ATTENTION_KEYS = ("attn_norm", "q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm",
                  "kv_b", "attn_out")


def feed_forward(h, p, config: dict):
    """The layer's feed-forward of h [T, d] (already normed)."""
    if "router" not in p:
        return swiglu(h, p["mlp_gate_up"], p["mlp_down"])
    expert, weight = route(h, p["router"], p["router_bias"],
                           config["num_experts_per_tok"],
                           config["routed_scaling_factor"])
    out = swiglu(h, p["shared_gate_up"], p["shared_down"])
    first = config.get("deployment_share", {}).get("first_expert", 0)
    for e in range(p["experts_down"].shape[0]):     # the experts held here
        w_e = jnp.sum(jnp.where(expert == first + e, weight, 0.0), axis=-1)
        out = out + w_e[:, None] * swiglu(h, p["experts_gate_up"][e],
                                          p["experts_down"][e])
    return out


def logits(params, config: dict, tokens):
    """tokens [T] int -> logits [T, vocab] float32: the whole sequence in
    one pass, every position attending to all before it."""
    top = params.get("top", params)
    eps = config["rms_norm_eps"]
    x = _embed(top["wte"], tokens)
    for i in range(config["num_hidden_layers"]):
        p = params[f"layer{i}"]
        x = attention(x, {k: p[k] for k in ATTENTION_KEYS}, _numbers(config))
        x = x + feed_forward(_norm(x, p["mlp_norm"], eps), p, config)
    return _head(x, top["final_norm"], top["lm_head"], eps)
