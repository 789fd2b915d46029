"""Ling-3.0-flash's language model (`bailing_hybrid`) in plain float32
`jax.numpy`: no cache, no blocks, no batching, no grouped product, no
absorbed attention. The Kimi Delta Attention recurrence is computed token by
token.

Per token x, RMSNorm with a learned scale throughout, pre-norm residual
blocks, an untied head. Layer i is latent attention (MLA) where
`(i + 1) % layer_group_size == 0` and Kimi Delta Attention (KDA) otherwise;
the leading `first_k_dense_replace` layers have a dense SwiGLU, the others
routed experts beside a shared one.

- KDA, h = norm(x): [q~ | k~ | v~] = h W_qkv; a causal depth-wise
  convolution of width `short_conv_kernel_size` over time on every channel
  (zeros before the first token), then SiLU; a head's q = l2norm(q') /
  sqrt(d_k), k = l2norm(k'), v = v'; g = kda_lower_bound * sigmoid(exp(A_log)
  * (h W_f + dt_bias)) a channel of a head, alpha = exp(g); beta = sigmoid(h
  W_beta) a head; a head's state S [d_k, d_v] from zero: S' = diag(alpha) S;
  S = S' + beta k (v - S'^T k)^T; o = S^T q; y = rmsnorm(o) * sigmoid(h W_og)
  a head (one norm scale for all heads); x += concat(y) W_o.
- MLA: q = h W_q as heads of [nope | rope], RMSNorm over each head; [c_kv |
  k_rope] = h W_dkv, c_kv = norm(c_kv); rope (rotating the halves, theta
  `rope_theta`, no scaling) on the rope channels; [k_nope | v] a head = c_kv
  W_ukv; scores over nope + rope channels times (nope + rope)^-0.5, causal
  softmax; each head's output times sigmoid(h w_gate)[head]; x += o W_o.
- Experts: s = sigmoid(h W_r); c = s + b; `n_group` groups of consecutive
  experts, a group's score the sum of its two largest c, the `topk_group`
  best groups stay, the `num_experts_per_tok` largest c among their experts
  are chosen; weights s[chosen] / sum * `routed_scaling_factor`; x += sum_k
  w_k E_k(h) + E_shared(h).

Departures, each also under `assumed` in the configuration file: only
experts [first_expert, first_expert + num_experts) are held and a token's
result is the weighted sum over those of its experts that are held; the
vocabulary is a slice; rope rotates halves (the checkpoint interleaves
pairs: a fixed permutation of columns); no vision tower, no multi-token
prediction layer.

Reads the program's parameter tree (`top/{wte, final_norm, lm_head}`,
`layer<i>/...` as `ray_tpu/models/ling_hybrid.py` names them) and nothing
else of the program. Weights stay in the type they are served in and are
cast to float32 a part at a time. Call under
`jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HEADS_AT_A_TIME = 8


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale


def _rope(x, positions, theta):
    """x [T, ..., D]: rotate (x[..., :D/2], x[..., D/2:]) by position."""
    dim = x.shape[-1]
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnums=(2,))
def kda(x, p, numbers):
    """x [T, d] float32 -> x + the KDA layer's output."""
    n_head, dk, width, lower, eps = numbers
    p = _f32(p)
    t = x.shape[0]
    h = _rms(x, p["attn_norm"], eps)
    u = h @ p["kda_qkv"]                                     # [T, 3 H dk]
    padded = jnp.concatenate(
        [jnp.zeros((width - 1, u.shape[1]), jnp.float32), u])
    conv = sum(padded[j:j + t] * p["kda_conv"][:, j] for j in range(width))
    q, k, v = (jax.nn.silu(conv).reshape(t, 3, n_head, dk)[:, i]
               for i in range(3))

    def l2(z):
        return z / jnp.sqrt(jnp.sum(z * z, axis=-1, keepdims=True) + 1e-6)

    q, k = l2(q) / jnp.sqrt(float(dk)), l2(k)
    a = (h @ p["kda_f"] + p["kda_dt_bias"]).reshape(t, n_head, dk)
    alpha = jnp.exp(lower * jax.nn.sigmoid(
        jnp.exp(p["kda_a_log"])[None, :, None] * a))
    beta = jax.nn.sigmoid(h @ p["kda_beta"])                 # [T, H]

    def token(s, xs):
        q_t, k_t, v_t, alpha_t, beta_t = xs
        s = alpha_t[:, :, None] * s
        seen = jnp.einsum("hkv,hk->hv", s, k_t)
        s = s + (beta_t[:, None] * k_t)[:, :, None] \
            * (v_t - seen)[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((n_head, dk, dk), jnp.float32),
                        (q, k, v, alpha, beta))
    gate = jax.nn.sigmoid(h @ p["kda_og"]).reshape(t, n_head, dk)
    y = _rms(o, p["kda_o_norm"], eps) * gate
    return x + y.reshape(t, n_head * dk) @ p["attn_out"]


@partial(jax.jit, static_argnums=(2,))
def mla(x, p, numbers):
    """x [T, d] float32 -> x + the MLA layer's output."""
    n_head, kv_rank, nope, rope, v_dim, eps, theta = numbers
    p = _f32(p)
    t = x.shape[0]
    pos = jnp.arange(t)
    h = _rms(x, p["attn_norm"], eps)
    q = _rms((h @ p["q"]).reshape(t, n_head, nope + rope), p["q_norm"], eps)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], pos, theta)
    kv = h @ p["kv_a"]
    c_kv = _rms(kv[:, :kv_rank], p["kv_a_norm"], eps)
    k_rope = _rope(kv[:, kv_rank:], pos, theta)                 # [T, rope]
    kv_b = p["kv_b"].reshape(kv_rank, n_head, nope + v_dim)
    causal = jnp.tril(jnp.ones((t, t), bool))
    outs = []
    for h0 in range(0, n_head, HEADS_AT_A_TIME):
        heads = slice(h0, h0 + HEADS_AT_A_TIME)
        expanded = jnp.einsum("tc,chn->thn", c_kv, kv_b[:, heads])
        k_nope, v = expanded[..., :nope], expanded[..., nope:]
        scores = (jnp.einsum("qhn,khn->hqk", q_nope[:, heads], k_nope)
                  + jnp.einsum("qhr,kr->hqk", q_rope[:, heads], k_rope)) \
            * (nope + rope) ** -0.5
        scores = jnp.where(causal[None], scores, -jnp.inf)
        outs.append(jnp.einsum("hqk,khv->qhv",
                               jax.nn.softmax(scores, axis=-1), v))
    o = jnp.concatenate(outs, axis=1)                           # [T, H, v]
    o = o * jax.nn.sigmoid(h @ p["attn_gate"])[:, :, None]
    return x + o.reshape(t, n_head * v_dim) @ p["attn_out"]


@jax.jit
def swiglu(h, gate_up, down):
    gate, up = jnp.split(h @ gate_up.astype(jnp.float32), 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ down.astype(jnp.float32)


@partial(jax.jit, static_argnums=(3, 4, 5, 6))
def route(h, router, bias, top_k, scale, n_group, topk_group):
    """h [T, d] -> (expert ids [T, top_k], weights [T, top_k])."""
    s = jax.nn.sigmoid(h @ router.astype(jnp.float32))
    c = s + bias.astype(jnp.float32)
    groups = c.reshape(c.shape[0], n_group, -1)
    group_score = jnp.sum(jax.lax.top_k(groups, 2)[0], axis=-1)
    kept = jax.lax.top_k(group_score, topk_group)[1]            # [T, kept]
    keep = jnp.zeros(group_score.shape, bool).at[
        jnp.arange(c.shape[0])[:, None], kept].set(True)
    c = jnp.where(keep[:, :, None], groups, -jnp.inf).reshape(c.shape)
    _, expert = jax.lax.top_k(c, top_k)
    w = jnp.take_along_axis(s, expert, axis=-1)
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


FEED_FORWARD_KEYS = ("mlp_norm", "mlp_gate_up", "mlp_down", "router",
                     "router_bias", "experts_gate_up", "experts_down",
                     "shared_gate_up", "shared_down")


def feed_forward(h, p, config: dict):
    """The layer's feed-forward of h [T, d] (already normed): the dense
    SwiGLU, or the held experts' part of the routed sum plus the shared
    expert."""
    if "router" not in p:
        return swiglu(h, p["mlp_gate_up"], p["mlp_down"])
    published = config.get("published", {}).get("num_experts",
                                                config["num_experts"])
    if p["router"].shape[1] != published:
        raise ValueError("the router's width is not the published count")
    expert, weight = route(h, p["router"], p["router_bias"],
                           config["num_experts_per_tok"],
                           config["routed_scaling_factor"],
                           config["n_group"], config["topk_group"])
    out = swiglu(h, p["shared_gate_up"], p["shared_down"])
    first = config.get("deployment_share", {}).get("first_expert", 0)
    for e in range(p["experts_down"].shape[0]):     # the experts held here
        w_e = jnp.sum(jnp.where(expert == first + e, weight, 0.0), axis=-1)
        out = out + w_e[:, None] * swiglu(h, p["experts_gate_up"][e],
                                          p["experts_down"][e])
    return out


def logits(params, config: dict, tokens):
    """tokens [T] int -> logits [T, vocab] float32: the whole sequence in
    one pass, every position attending to (or having folded in) all before
    it."""
    top = params.get("top", params)
    eps = config["rms_norm_eps"]
    n_head = config["num_attention_heads"]
    kda_numbers = (n_head, config["head_dim"],
                   config["short_conv_kernel_size"],
                   float(config["kda_lower_bound"]), eps)
    mla_numbers = (n_head, config["kv_lora_rank"],
                   config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                   config["v_head_dim"], eps, float(config["rope_theta"]))
    x = _embed(top["wte"], tokens)
    for i in range(config["num_hidden_layers"]):
        p = params[f"layer{i}"]
        attention = {k: v for k, v in p.items()
                     if k not in FEED_FORWARD_KEYS}
        if (i + 1) % config["layer_group_size"] == 0:
            x = mla(x, attention, mla_numbers)
        else:
            x = kda(x, attention, kda_numbers)
        x = x + feed_forward(_norm(x, p["mlp_norm"], eps), p, config)
    return _head(x, top["final_norm"], top["lm_head"], eps)
