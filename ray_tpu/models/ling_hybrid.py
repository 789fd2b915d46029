"""Ling hybrid family (`bailing_hybrid`) decoder: periods of Kimi Delta
Attention (KDA) layers closed by one latent-attention (MLA) layer, a leading
dense SwiGLU layer, then layers of group-limited sigmoid-routed experts
beside a shared expert. Serving only, as `kimi_k2.py`: the three step
functions the paged engine calls, and a flax module that makes the weights.

What it asks of the system that `kimi_k2.py` does not:

- Two kinds of state side by side. An MLA layer leaves a latent row a token
  in the paged arena (`cache_rows`, Kimi's row; `paged_layers(cfg)` of the
  layers page). A KDA layer leaves ONE state a sequence, overwritten every
  step: a float32 `[n_head, d_k, d_v]` matrix a head and the last
  `conv_width - 1` inputs of its short convolution. `seq_state(cfg)`
  declares those arrays; the cache manager keeps them a slot a sequence,
  and every step, the prefill too, takes the donated arena's arrays and the
  lanes' slots (`seq_state=`, `slots=`) and returns THE ARENA'S ARRAYS after
  the cache rows (`STATE_IN_PLACE`, the contract `brumby.py` runs): a KDA
  layer reads its lanes' states from the arena and writes their successors
  back where they lay. A decode step moves 2 MB a lane a layer, so nothing
  of [lanes, layers, ...] or [lanes, ...] stands beside the arena there: no
  gather of the lanes' states, no stack of new ones, no scatter
  (`decode_step`). A chunk and a prefill return one row of logits a
  sequence, its last token's.
- A KDA layer in two forms with the same numbers: `kda_chunk`, a blocked
  evaluation of the recurrence for prefill and chunks, and `kda_step`, the
  recurrence itself for one token.
- Group-limited routing (`parallel.moe.sigmoid_topk_route`, `n_group`).

The recurrence, a head, state S [d_k, d_v] (zero before the first token),
decay alpha_t = exp(g_t) a channel of d_k, beta_t a scalar:

    S' = diag(alpha_t) S_{t-1}
    u_t = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t u_t^T
    o_t = S_t^T q_t = S'^T q_t + (k_t . q_t) u_t

(the last form needs nothing of S_t: a step that reads S' once has both
S'^T k_t and S'^T q_t, and writes S_t over it: `kda_step`.)

The MLA layer is DeepSeek's without the query bottleneck, with RMSNorm over
each query head and a per-head sigmoid gate on the output; it calls
`kimi_k2.attend_absorbed` (over `listed_walk`'s work list of live (lane, key
block) pairs) / `attend_expanded` with its own shapes (`cfg.mla` is their
`KimiK2Config`). Rope: the half-rotation form on the rope channels, no scaling.

Parameters: `top/{wte, final_norm, lm_head}`; `layer<i>/{attn_norm,
attn_out, mlp_norm}` with, in a KDA layer, `kda_qkv` ([q | k | v]),
`kda_conv` ([channels, width]), `kda_f`, `kda_dt_bias`, `kda_a_log`,
`kda_beta`, `kda_og`, `kda_o_norm`, and in an MLA layer `q`, `q_norm`,
`kv_a`, `kv_a_norm`, `kv_b`, `attn_gate`; the feed-forward as Kimi's
(`mlp_gate_up`, `mlp_down` or `router`, `router_bias`, `experts_gate_up`,
`experts_down`, `shared_gate_up`, `shared_down`).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.kimi_k2 import (KimiK2Config, attend_absorbed,
                                    attend_expanded, listed_walk, yarn_tables)
from ray_tpu.models.layers import (declare_weights, head, last_row,
                                   put_slot_state, rms, rope,
                                   routed_feed_forward, slot_state,
                                   top_shapes, unboxed_params)
from ray_tpu.parallel.moe import MOE_COUNTS

# what each step returns last, an int32 vector summed over the layers:
# Kimi's expert counts and key slots (the MLA layers), then the KDA states
# the step had to read and write (live sequences x KDA layers, padded lanes
# not counted), then the (slot, KDA layer) states it did read and write,
# idle slots and padded lanes included: a decode step in slot order walks
# every slot of the arena, one in lane order its bucket's lanes
STEP_COUNTS = tuple(f"moe_{name}" for name in MOE_COUNTS) \
    + ("attn_key_slots", "kda_state_rows", "kda_slot_rows")
# the steps take the state arena (`seq_state=`, `slots=`; the prefill too)
# and return the arena's arrays (`engine._family_cache`)
STATE_IN_PLACE = True
# tokens the blocked scan folds into the state at a time, and the
# sub-blocks within which decays are taken against one reference point
KDA_BLOCK = 64
KDA_SUB = 16
# the largest exponent the blocked scan forms: a sub-block's worth of the
# strongest decay. exp(80) is a float32; exp(89) is not
KDA_MAX_EXPONENT = 80.0
HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class LingHybridConfig:
    vocab_size: int = 157184
    n_layer: int = 42
    n_dense_layer: int = 2          # first_k_dense_replace
    layer_group_size: int = 6       # the last layer of a period is MLA
    n_head: int = 32
    d_model: int = 2560
    head_dim: int = 128             # KDA's d_k = d_v
    conv_width: int = 4             # short_conv_kernel_size
    kda_lower_bound: float = -5.0   # g = lower * sigmoid(.): kda_safe_gate
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    ffn_dim: int = 6144             # the dense layers' width
    moe_ffn_dim: int = 768          # an expert's width
    n_experts: int = 512            # the router's outputs
    experts_held: int = 512         # experts whose weights live here ...
    first_expert: int = 0           # ... from this one on
    top_k: int = 8
    n_group: int = 8
    topk_group: int = 4
    n_shared: int = 1
    routed_scale: float = 2.5
    max_seq_len: int = 131072
    rope_theta: float = 6000000.0
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if -self.kda_lower_bound * KDA_SUB > KDA_MAX_EXPONENT:
            raise ValueError(
                f"kda_lower_bound {self.kda_lower_bound} over a sub-block of "
                f"{KDA_SUB} tokens leaves float32's range")

    def is_mla(self, i: int) -> bool:
        return (i + 1) % self.layer_group_size == 0

    @property
    def mla_layers(self) -> Tuple[int, ...]:
        return tuple(i for i in range(self.n_layer) if self.is_mla(i))

    @property
    def kda_layers(self) -> Tuple[int, ...]:
        return tuple(i for i in range(self.n_layer) if not self.is_mla(i))

    @property
    def conv_channels(self) -> int:
        return 3 * self.n_head * self.head_dim

    @property
    def mla(self) -> KimiK2Config:
        """The MLA layer's shapes as the config `kimi_k2`'s attention
        functions read; a rope factor of 1 is no scaling."""
        return KimiK2Config(
            n_head=self.n_head, d_model=self.d_model,
            kv_lora_rank=self.kv_lora_rank, qk_nope_dim=self.qk_nope_dim,
            qk_rope_dim=self.qk_rope_dim, v_head_dim=self.v_head_dim,
            max_seq_len=self.max_seq_len, rope_theta=self.rope_theta,
            rope_factor=1.0, norm_eps=self.norm_eps, dtype=self.dtype,
            param_dtype=self.param_dtype)

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=512, n_layer=7, n_dense_layer=1, n_head=4,
                    d_model=64, head_dim=16, kv_lora_rank=16, qk_nope_dim=8,
                    qk_rope_dim=8, v_head_dim=8, ffn_dim=128, moe_ffn_dim=32,
                    n_experts=16, experts_held=16, top_k=4, n_group=4,
                    topk_group=2, max_seq_len=128)
        base.update(kw)
        return cls(**base)


def cache_rows(cfg: LingHybridConfig) -> Tuple[Tuple[int, ...], ...]:
    """What a token leaves in the paged arena, an MLA layer: Kimi's latent
    row, padded to whole lane tiles."""
    return ((cfg.mla.row_dim,),)


def paged_layers(cfg: LingHybridConfig) -> int:
    """Layers that leave rows in the paged arena: the MLA ones."""
    return len(cfg.mla_layers)


def seq_state(cfg: LingHybridConfig):
    """What a SEQUENCE keeps beside its pages, one (shape, dtype) an array,
    the KDA layers leading: every head's state, and the convolution's
    tail (the last `conv_width - 1` inputs of the q, k and v channels,
    one after the other in a row of whole lane tiles: with an axis of 3
    next to last the TPU compiler re-lays the array out around every
    scatter; compiled for a described v5e, PR 33)."""
    n = len(cfg.kda_layers)
    return (((n, cfg.n_head, cfg.head_dim, cfg.head_dim), jnp.float32),
            ((n, (cfg.conv_width - 1) * cfg.conv_channels), cfg.dtype))


def _tail_rows(cfg: LingHybridConfig, tail):
    """A layer's stored tail [B, (W - 1) * ch] as rows [B, W - 1, ch]."""
    return tail.reshape(tail.shape[0], cfg.conv_width - 1, cfg.conv_channels)


# -- the weights --------------------------------------------------------------

def layer_shapes(cfg: LingHybridConfig, i: int) -> dict:
    """name -> (shape, kind of `layers.INITS`) of layer i's parameters."""
    d, h, dk = cfg.d_model, cfg.n_head, cfg.head_dim
    shapes = {"attn_norm": ((d,), "ones"), "mlp_norm": ((d,), "ones")}
    if cfg.is_mla(i):
        shapes.update({
            "q": ((d, h * (cfg.qk_nope_dim + cfg.qk_rope_dim)), "w"),
            "q_norm": ((cfg.qk_nope_dim + cfg.qk_rope_dim,), "ones"),
            "kv_a": ((d, cfg.kv_lora_rank + cfg.qk_rope_dim), "w"),
            "kv_a_norm": ((cfg.kv_lora_rank,), "ones"),
            "kv_b": ((cfg.kv_lora_rank,
                      h * (cfg.qk_nope_dim + cfg.v_head_dim)), "w"),
            "attn_gate": ((d, h), "w"),
            "attn_out": ((h * cfg.v_head_dim, d), "w"),
        })
    else:
        shapes.update({
            "kda_qkv": ((d, cfg.conv_channels), "w"),
            "kda_conv": ((cfg.conv_channels, cfg.conv_width), "conv"),
            "kda_f": ((d, h * dk), "w"),
            "kda_dt_bias": ((h * dk,), "dt_bias"),
            "kda_a_log": ((h,), "a_log"),
            "kda_beta": ((d, h), "w"),
            "kda_og": ((d, h * dk), "w"),
            "kda_o_norm": ((dk,), "ones"),
            "attn_out": ((h * dk, d), "w"),
        })
    if i < cfg.n_dense_layer:
        shapes["mlp_gate_up"] = ((d, 2 * cfg.ffn_dim), "w")
        shapes["mlp_down"] = ((cfg.ffn_dim, d), "w")
        return shapes
    f = cfg.moe_ffn_dim
    shapes.update({
        "router": ((d, cfg.n_experts), "w"),
        "router_bias": ((cfg.n_experts,), "bias"),
        "experts_gate_up": ((cfg.experts_held, d, 2 * f), "w"),
        "experts_down": ((cfg.experts_held, f, d), "w"),
        "shared_gate_up": ((d, 2 * f * cfg.n_shared), "w"),
        "shared_down": ((f * cfg.n_shared, d), "w"),
    })
    return shapes


class LingHybrid(nn.Module):
    """`net.init` makes the weights; `apply` is the full causal forward
    (no cache, every state from zero), tokens [B, T] -> logits [B, T, V]."""
    config: LingHybridConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.config
        p = declare_weights(top_shapes(cfg), (
            layer_shapes(cfg, i) for i in range(cfg.n_layer)), cfg.param_dtype)
        x, *_ = _window_forward(
            p, cfg, tokens, jnp.zeros(tokens.shape[:1], jnp.int32), None,
            None, None, None)
        return head(p, cfg, x)


# -- Kimi Delta Attention -----------------------------------------------------

def _lower_solve(a):
    """(I + a)^-1 for strictly lower triangular a [..., C, C], by forward
    substitution a row at a time (row i of the inverse is e_i minus row i
    of `a` times the rows above it). Float32 throughout: the powers of `a`
    a product form would take grow before they vanish."""
    c = a.shape[-1]
    eye = jnp.broadcast_to(jnp.eye(c, dtype=a.dtype), a.shape)

    def row(i, inv):
        a_i = jax.lax.dynamic_slice_in_dim(a, i, 1, axis=-2)
        new = jax.lax.dynamic_slice_in_dim(eye, i, 1, axis=-2) \
            - jnp.einsum("...ij,...jk->...ik", a_i, inv, precision=HIGHEST)
        return jax.lax.dynamic_update_slice_in_dim(inv, new, i, axis=-2)

    # rows from i on are still the identity's when row i is made, and `a`
    # is zero there
    return jax.lax.fori_loop(1, c, row, eye)


def kda_chunk(q, k, v, g, beta, state):
    """T tokens a sequence folded into the state `KDA_BLOCK` at a time: the
    recurrence of the module's docstring in its blocked (WY) form. q, k
    [B, T, H, dk] (normalised, q scaled), v [B, T, H, dv], g [B, T, H, dk]
    (log decays, <= 0), beta [B, T, H], state [B, H, dk, dv]; float32. A row
    with g = 0 and beta = 0 leaves the state as it was (a padded row).
    Returns (o [B, T, H, dv], the state after the last token).

    Within a block, with G the running sum of g, U the rows `u_t = beta_t
    (v_t - S'^T k_t)`, S0 the state before the block:

        A_tj = sum_c k_t[c] k_j[c] exp(G_t[c] - G_j[c])     (j < t)
        B_tj = sum_c q_t[c] k_j[c] exp(G_t[c] - G_j[c])     (j <= t)
        (I + diag(beta) A) U = diag(beta) (V - (K exp(G)) S0)
        O = (Q exp(G)) S0 + B U
        S = diag(exp(G_last)) S0 + (K exp(G_last - G))^T U

    Decays enter only as exp of a difference that is <= 0, or, inside one
    sub-block of `KDA_SUB` tokens, of one that is at most `KDA_SUB` steps
    of the strongest decay (`KDA_MAX_EXPONENT`): row t of sub-block i
    carries exp(G_t - R_i), column j carries exp(R_i - G_j), R_i the sum
    before the sub-block. Nothing is ever divided by a cumulative decay."""
    with jax.named_scope("kda_chunk"):
        b, t, h, dk = k.shape
        c, s = KDA_BLOCK, KDA_SUB
        pad = -t % c
        if pad:
            q, k, v, g = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                          for x in (q, k, v, g))
            beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
        n = (t + pad) // c

        def blocks(x):                  # [B, T, H, d] -> [B, H, n, C, d]
            return x.reshape(b, n, c, h, -1).transpose(0, 3, 1, 2, 4)

        q, k, v, g = blocks(q), blocks(k), blocks(v), blocks(g)
        beta = blocks(beta[..., None])[..., 0]              # [B, H, n, C]
        big = jnp.cumsum(g, axis=-2)
        sub = (b, h, n, c // s, s, dk)
        big_s = big.reshape(sub)
        ref = big_s[..., 0, :] - g.reshape(sub)[..., 0, :]  # [B,H,n,C/s,dk]
        row = jnp.exp(big_s - ref[..., None, :])            # <= 1
        col = jnp.exp(jnp.minimum(
            ref[..., None, :] - big[..., None, :, :], KDA_MAX_EXPONENT))
        k_col = k[..., None, :, :] * col                    # [B,H,n,C/s,C,dk]

        def against_keys(x):
            return jnp.einsum("...isc,...ijc->...isj", x.reshape(sub) * row,
                              k_col, precision=HIGHEST).reshape(b, h, n, c, c)

        idx = jnp.arange(c)
        a = jnp.where(idx[:, None] > idx[None, :], against_keys(k), 0.0) \
            * beta[..., None]
        bq = jnp.where(idx[:, None] >= idx[None, :], against_keys(q), 0.0)
        decay = jnp.exp(big)
        rhs = jnp.concatenate([k * decay, v], axis=-1) * beta[..., None]
        w_u = jnp.einsum("...ij,...jd->...id", _lower_solve(a), rhs,
                         precision=HIGHEST)
        w, u0 = jnp.split(w_u, [dk], axis=-1)
        last = big[..., -1:, :]
        xs = (w, u0, q * decay, bq, k * jnp.exp(last - big),
              jnp.exp(last[..., 0, :]))

        def fold(state, x):
            w, u0, q_dec, bq, k_end, end = x
            u = u0 - jnp.einsum("bhck,bhkv->bhcv", w, state,
                                precision=HIGHEST)
            o = jnp.einsum("bhck,bhkv->bhcv", q_dec, state,
                           precision=HIGHEST) \
                + jnp.einsum("bhcj,bhjv->bhcv", bq, u, precision=HIGHEST)
            state = end[..., None] * state + jnp.einsum(
                "bhck,bhcv->bhkv", k_end, u, precision=HIGHEST)
            return state, o

        state, o = jax.lax.scan(
            fold, state, tuple(jnp.moveaxis(x, 2, 0) for x in xs))
        o = o.transpose(1, 0, 3, 2, 4).reshape(b, n * c, h, -1)
    return o[:, :t], state


def kda_step(q, k, v, g, beta, state):
    """One token a sequence: the recurrence itself. q, k, g [B, H, dk];
    v [B, H, dv]; beta [B, H]; state [B, H, dk, dv]; float32. Returns
    (o [B, H, dv], the new state). The old state is read once: both
    reductions S'^T k and S'^T q come off one product, and the output by
    `o = S'^T q + (k . q) u` needs nothing of the new state, so a caller
    may write the new state over the old (`kda_slots`, `kda_lanes`) and
    nothing tempts the compiler to keep a copy."""
    decayed = jnp.exp(g)[..., None] * state
    both = jnp.einsum("bhkv,bhnk->bhnv", decayed, jnp.stack([k, q], 2),
                      precision=HIGHEST)
    u = beta[..., None] * (v - both[:, :, 0])
    o = both[:, :, 1] + jnp.sum(k * q, -1, keepdims=True) * u
    return o, decayed + k[..., None] * u[..., None, :]


def slot_lanes(slots, n: int):
    """The lane that names each of the arena's n slots, -1 where none does:
    a decode step's `slots` [B] (lane i's slot; a padded lane names the
    scratch slot, n) turned about, once a step for all its KDA layers."""
    return jnp.full((n,), -1, jnp.int32).at[slots].set(
        jnp.arange(slots.shape[0], dtype=jnp.int32), mode="drop")


def kda_slots(s_arena, j: int, slots, q, k, v, g, beta, *, lanes):
    """`kda_step` of layer j for every lane on the state arena where it
    lies, in SLOT ORDER: for a bucket that covers the arena. s_arena
    [n + 1, n_kda, H, dk, dv], its last slot the padded lanes' scratch;
    slots [B], lane i's slot; `lanes` [n] is `slot_lanes(slots, n)`; the
    rest as `kda_step` takes them. Each slot's row of the small inputs is
    its lane's, and `kda_step` runs over the static slice `s_arena[:n, j]`:
    one read of the old states, one read and write for the update, which
    lands where the old state lay. A slot that no lane names (idle, or held
    by a sequence that is mid-prefill) keeps its state bit for bit; a padded
    lane reads some slot's output, and nobody reads the lane's. Returns
    (o [B, H, dv], the arena)."""
    n = s_arena.shape[0] - 1
    with jax.named_scope("kda_step"):
        old, at = s_arena[:n, j], jnp.maximum(lanes, 0)
        o, new = kda_step(q[at], k[at], v[at], g[at], beta[at], old)
        new = jnp.where((lanes >= 0)[:, None, None, None], new, old)
        s_arena = jax.lax.dynamic_update_slice(s_arena, new[:, None],
                                               (0, j, 0, 0, 0))
        return o[jnp.minimum(slots, n - 1)], s_arena


def kda_lanes(s_arena, j: int, slots, q, k, v, g, beta):
    """`kda_step` of layer j on the state arena in LANE ORDER, for a bucket
    smaller than the arena: a loop over the lanes that slices a lane's state
    out of its slot (a slice, not a gather) and writes its successor back.
    A padded lane names the scratch slot, and what lands there is nobody's.
    Arguments and results as `kda_slots`. (Both walks lie whole under the
    scope `kda_step`: what a profile reads the path by.)"""
    def lane(i, carry):
        s_arena, out = carry
        o, new = kda_step(q[i][None], k[i][None], v[i][None], g[i][None],
                          beta[i][None], slot_state(s_arena, slots[i], j))
        return put_slot_state(s_arena, new, slots[i], j), out.at[i].set(o[0])

    with jax.named_scope("kda_step"):
        s_arena, o = jax.lax.fori_loop(0, q.shape[0], lane,
                                       (s_arena, jnp.zeros_like(v)))
    return o, s_arena


def _short_conv(x, weight):
    """Causal depth-wise convolution, then SiLU. x [B, W - 1 + T, ch]: the
    tail before the window, then the window; weight [ch, W]. Returns
    [B, T, ch] in x's type: row t is silu(sum_j w[:, j] x[t + j])."""
    width = weight.shape[1]
    t = x.shape[1] - (width - 1)
    w = weight.astype(jnp.float32)
    y = sum(x[:, j:j + t].astype(jnp.float32) * w[:, j]
            for j in range(width))
    return nn.silu(y).astype(x.dtype)


def _kda_project(lp, cfg: LingHybridConfig, h):
    """h [..., d] -> (u [..., 3 H dk] before the convolution, g [..., H, dk]
    log decays, beta [..., H], output gate [..., H, dk]); the decays and
    beta float32 from a float32 accumulation: `exp(A_log)` multiplies what
    rounding the projection to the model's type would leave."""
    dtype, f32 = cfg.dtype, jnp.float32
    heads = h.shape[:-1] + (cfg.n_head, cfg.head_dim)
    u = h @ lp["kda_qkv"].astype(dtype)
    a = jnp.dot(h, lp["kda_f"].astype(dtype), preferred_element_type=f32) \
        + lp["kda_dt_bias"].astype(f32)
    g = cfg.kda_lower_bound * jax.nn.sigmoid(
        jnp.exp(lp["kda_a_log"].astype(f32))[:, None] * a.reshape(heads))
    beta = jax.nn.sigmoid(jnp.dot(h, lp["kda_beta"].astype(dtype),
                                  preferred_element_type=f32))
    gate = jax.nn.sigmoid(jnp.dot(h, lp["kda_og"].astype(dtype),
                                  preferred_element_type=f32)).reshape(heads)
    return u, g, beta, gate


def _kda_heads(cfg: LingHybridConfig, y):
    """The convolved channels [..., 3 H dk] as float32 heads: q (unit
    length, over sqrt(dk)), k (unit length), v."""
    y = y.astype(jnp.float32).reshape(
        y.shape[:-1] + (3, cfg.n_head, cfg.head_dim))
    q, k, v = y[..., 0, :, :], y[..., 1, :, :], y[..., 2, :, :]

    def unit(x):
        return x * jax.lax.rsqrt(
            jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)

    return unit(q) * cfg.head_dim ** -0.5, unit(k), v


def _kda_out(lp, cfg: LingHybridConfig, o, gate):
    """Heads' outputs o [..., H, dv] float32 -> [..., d]: RMSNorm a head
    (one scale for all heads), the sigmoid gate, the output matrix."""
    y = rms(o, lp["kda_o_norm"], cfg.norm_eps, jnp.float32) * gate
    y = y.astype(cfg.dtype).reshape(o.shape[:-2] + (-1,))
    return y @ lp["attn_out"].astype(cfg.dtype)


# -- latent attention ---------------------------------------------------------

def _mla_project(lp, cfg: LingHybridConfig, h, cos, sin):
    """h [..., d] -> q_nope [..., H, nope], q_rope [..., H, rope] (normed a
    head, rotated), the cache row [..., row_dim], the heads' output gates
    [..., H, 1]."""
    m, dtype = cfg.mla, cfg.dtype
    q = (h @ lp["q"].astype(dtype)).reshape(
        h.shape[:-1] + (m.n_head, m.qk_nope_dim + m.qk_rope_dim))
    q = rms(q, lp["q_norm"], cfg.norm_eps, dtype)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_dim], axis=-1)
    q_rope = rope(q_rope, cos[..., None, :], sin[..., None, :])
    kv = h @ lp["kv_a"].astype(dtype)
    c_kv, k_rope = jnp.split(kv, [m.kv_lora_rank], axis=-1)
    c_kv = rms(c_kv, lp["kv_a_norm"], cfg.norm_eps, dtype)
    latent = jnp.concatenate(
        [c_kv, rope(k_rope, cos, sin),
         jnp.zeros(c_kv.shape[:-1] + (m.row_dim - m.latent_dim,), dtype)],
        axis=-1)
    gate = jax.nn.sigmoid(jnp.dot(h, lp["attn_gate"].astype(dtype),
                                  preferred_element_type=jnp.float32))
    return q_nope, q_rope, latent, gate[..., None]


def _mla_out(lp, cfg: LingHybridConfig, att, gate):
    """att [..., H * v] from Kimi's attention, gated a head."""
    heads = att.reshape(att.shape[:-1] + (cfg.n_head, cfg.v_head_dim))
    y = (heads.astype(jnp.float32) * gate).astype(cfg.dtype)
    return y.reshape(att.shape) @ lp["attn_out"].astype(cfg.dtype)


def _rope_tables(cfg: LingHybridConfig, positions):
    cos, sin = yarn_tables(cfg.mla)
    return jnp.asarray(cos)[positions], jnp.asarray(sin)[positions]


# -- the three steps ----------------------------------------------------------

def _step_counts(moe_counts, key_slots, state_rows, slot_rows):
    return jnp.concatenate([moe_counts, jnp.stack([
        jnp.asarray(n, jnp.int32)
        for n in (key_slots, state_rows, slot_rows)])])


def _window_forward(p, cfg: LingHybridConfig, tokens, start, pages,
                    page_table, valid_rows, state):
    """C tokens a sequence from position `start` on: the MLA layers
    against the cached latents of its pages (none when `pages` is None),
    the KDA layers from `state` = (states [B, n_kda, H, dk, dv], tails
    [B, n_kda, (W - 1) * ch]), or from zero when None. The tokens are the
    leading rows of the window; `valid_rows` [B, C] marks them (None:
    all), and the rows after them change no state. Returns (the stream
    [B, C, d_model] after the last layer, latents [B, C, n_mla, row],
    (states, tails), counts)."""
    dtype = cfg.dtype
    b, c = tokens.shape
    x = p["wte"].astype(dtype)[tokens]
    positions = jnp.minimum(start[:, None] + jnp.arange(c)[None, :],
                            cfg.max_seq_len - 1)
    cos, sin = _rope_tables(cfg, positions)
    if valid_rows is None:
        valid_rows = jnp.ones((b, c), bool)
    n_valid = jnp.sum(valid_rows.astype(jnp.int32), axis=1)
    flat_valid = valid_rows.reshape(-1)
    if state is None:
        state = tuple(jnp.zeros((b,) + shape, dt)
                      for shape, dt in seq_state(cfg))
    tail_rows = n_valid[:, None] + jnp.arange(cfg.conv_width - 1)[None, :]
    latents, states, tails = [], [], []
    counts, key_slots = jnp.zeros(len(MOE_COUNTS), jnp.int32), jnp.int32(0)
    for i in range(cfg.n_layer):
        lp = p[f"layer{i}"]
        h = rms(x, lp["attn_norm"], cfg.norm_eps, dtype)
        if cfg.is_mla(i):
            q_nope, q_rope, lat, gate = _mla_project(lp, cfg, h, cos, sin)
            with jax.named_scope("mla_expanded"):
                att, slots = attend_expanded(
                    lp, cfg.mla, q_nope, q_rope, lat, start, pages,
                    page_table, len(latents))
            x = x + _mla_out(lp, cfg, att, gate)
            key_slots = key_slots + b * slots
            latents.append(lat)
        else:
            j = len(states)
            u, g, beta, gate = _kda_project(lp, cfg, h)
            seen = jnp.concatenate([_tail_rows(cfg, state[1][:, j]), u],
                                   axis=1)
            q, k, v = _kda_heads(cfg, _short_conv(seen, lp["kda_conv"]))
            o, new = kda_chunk(
                q, k, v, jnp.where(valid_rows[..., None, None], g, 0.0),
                jnp.where(valid_rows[..., None], beta, 0.0), state[0][:, j])
            x = x + _kda_out(lp, cfg, o, gate)
            states.append(new)
            # the inputs of the last W - 1 tokens, some of them the old
            # tail's where the window holds fewer
            tails.append(jnp.take_along_axis(
                seen, tail_rows[..., None], axis=1).reshape(b, -1))
        h = rms(x, lp["mlp_norm"], cfg.norm_eps, dtype)
        y, n = routed_feed_forward(lp, cfg, i, h.reshape(b * c, -1),
                                   flat_valid)
        x = x + y.reshape(b, c, -1)
        counts = counts + n
    return x, jnp.stack(latents, axis=2), \
        (jnp.stack(states, axis=1), jnp.stack(tails, axis=1)), \
        _step_counts(counts, key_slots, b * len(states), b * len(states))


def _put_window_state(seq_state, slots, new):
    """The window's sequences' new states (`new`: [B, n_kda, ...] an array
    of the arena) written to their slots of the donated arena: a window is
    one sequence or a few, so what stands beside the arena is theirs."""
    return tuple(arr.at[slots].set(x.astype(arr.dtype))
                 for arr, x in zip(seq_state, new))


def prefill_step(variables, cfg: LingHybridConfig, tokens, true_len,
                 seq_state=None, slots=None, valid=None):
    """Full forward over a padded prompt batch, every state from zero,
    whatever the sequences' slots held. tokens [B, S]; true_len [B];
    `seq_state` the arena's arrays ([slots + 1, n_kda, ...]), `slots` [B];
    `valid` [B, S] marks the rows that are tokens (None: the first
    `true_len`). Returns (next_logits [B, V], latents [B, S, n_mla, row],
    the arena's arrays with the states after the last token in the slots,
    counts); latent rows past true_len are garbage the caller must not
    cache."""
    p = unboxed_params(variables)
    b, s = tokens.shape
    if valid is None:
        valid = jnp.arange(s)[None, :] < true_len[:, None]
    x, latents, state, counts = _window_forward(
        p, cfg, tokens, jnp.zeros((b,), jnp.int32), None, None, valid, None)
    return (head(p, cfg, last_row(x, true_len)), latents) \
        + _put_window_state(seq_state, slots, state) + (counts,)


def chunk_step(variables, cfg: LingHybridConfig, tokens, start, pages,
               page_table, seq_state=None, slots=None, valid=None):
    """C tokens a sequence against a paged cache that holds its first
    `start` positions and the state arena's slot that holds its KDA state
    after them. `seq_state` the arena's arrays ([slots + 1, n_kda, ...]),
    `slots` [B]. A sequence's first window (`start` 0) starts from zero
    whatever its slot held. Returns (the logits of each sequence's last
    token [B, V], latents, the arena's arrays, counts)."""
    p = unboxed_params(variables)
    if valid is None:
        valid = jnp.ones(tokens.shape, bool)
    first = start == 0
    state = tuple(jnp.where(first.reshape((-1,) + (1,) * (a.ndim - 1)),
                            jnp.zeros((), a.dtype), a[slots])
                  for a in seq_state)
    x, latents, state, counts = _window_forward(
        p, cfg, tokens, start, pages, page_table, valid, state)
    n_valid = jnp.sum(valid.astype(jnp.int32), axis=1)
    return (head(p, cfg, last_row(x, n_valid)), latents) \
        + _put_window_state(seq_state, slots, state) + (counts,)


def decode_step(variables, cfg: LingHybridConfig, tokens, positions, pages,
                page_table, seq_state=None, slots=None, valid=None):
    """One token a sequence: the MLA layers' absorbed path over the paged
    cache (each lane's latents as far as its own last key block: Kimi's
    `listed_walk`, made once a step), the KDA layers' recurrence ON the state
    arena (`seq_state`, the donated arrays [slots + 1, n_kda, ...]; `slots`
    [B] names lane i's): each layer reads its lanes' states from the arena
    and writes their successors back where they lay. One recurrence
    (`kda_step`) walked two ways, by a shape the program sees: a bucket that
    covers the arena (lanes >= slots, the scratch slot apart) walks it in slot
    order, densely (`kda_slots`, the slots' lanes found once a step:
    `slot_lanes`); a smaller one walks its lanes (`kda_lanes`: slot order
    would cost the bucket of one a walk of every slot, lane order the bucket
    of 64 a few hundred trips of a loop). tokens [B]; positions [B]; `valid`
    [B] marks the lanes that hold a sequence (the others name the scratch
    slot). Returns (logits [B, V], latents [B, n_mla, row], the arena's
    arrays, counts)."""
    p = unboxed_params(variables)
    dtype = cfg.dtype
    b = tokens.shape[0]
    x = p["wte"].astype(dtype)[tokens]
    cos, sin = _rope_tables(cfg, positions)
    key_walk = listed_walk(positions, page_table, pages.shape[2])
    s_arena, tail_arena = seq_state
    n_slots = s_arena.shape[0] - 1
    if b >= n_slots:
        walk, walked = functools.partial(
            kda_slots, lanes=slot_lanes(slots, n_slots)), n_slots
    else:
        walk, walked = kda_lanes, b
    latents, tails = [], []
    counts = jnp.zeros(len(MOE_COUNTS), jnp.int32)
    for i in range(cfg.n_layer):
        lp = p[f"layer{i}"]
        h = rms(x, lp["attn_norm"], cfg.norm_eps, dtype)
        if cfg.is_mla(i):
            q_nope, q_rope, lat, gate = _mla_project(lp, cfg, h, cos, sin)
            with jax.named_scope("mla_absorbed"):
                att = attend_absorbed(lp, cfg.mla, q_nope, q_rope, lat,
                                      pages, len(latents), key_walk)
            x = x + _mla_out(lp, cfg, att, gate)
            latents.append(lat)
        else:
            j = len(tails)
            u, g, beta, gate = _kda_project(lp, cfg, h)
            seen = jnp.concatenate(
                [_tail_rows(cfg, tail_arena[slots, j]), u[:, None]], axis=1)
            q, k, v = _kda_heads(cfg, _short_conv(seen, lp["kda_conv"])[:, 0])
            o, s_arena = walk(s_arena, j, slots, q, k, v, g, beta)
            x = x + _kda_out(lp, cfg, o, gate)
            tails.append(seen[:, 1:].reshape(b, -1))
        h = rms(x, lp["mlp_norm"], cfg.norm_eps, dtype)
        y, n = routed_feed_forward(lp, cfg, i, h, valid)
        x = x + y
        counts = counts + n
    # a tail is 72 KB a lane a layer: the lanes' rows, written at their slots
    tail_arena = tail_arena.at[slots].set(
        jnp.stack(tails, axis=1).astype(tail_arena.dtype))
    lanes = b if valid is None else jnp.sum(valid.astype(jnp.int32))
    # what an MLA layer scored: every lane's own latent, then whole trips of
    # the list, dead pairs and the last blocks' padding included
    trips, width, keys, _ = key_walk
    return head(p, cfg, x), jnp.stack(latents, axis=1), s_arena, tail_arena, \
        _step_counts(counts, len(latents) * (b + trips * width * keys),
                     lanes * len(tails), walked * len(tails))
