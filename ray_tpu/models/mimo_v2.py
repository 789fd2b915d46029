"""MiMo-V2 family decoder (Xiaomi's MiMo-V2-Flash / V2.5, the language
model): sliding-window and full attention layers mixed 5:1, the two kinds
with K/V head counts of their own (8 in a window layer, 4 in a full one),
key rows wider than value rows (192 / 128), a learned sink a query head in
the window layers' softmax, rope on the first third of every head with a
base a kind, a scale on the attention's values; pre-norm residual blocks, a
leading dense SwiGLU layer, then layers of sigmoid-routed experts with NO
shared expert, an untied head. Serving only: the three step functions the
paged engine calls, and a flax module that exists to make the weights.

What it asks of the system that no other family does:

- Two KINDS of paged layer (`page_kinds`) whose arrays differ in SHAPE and
  not only in how long they keep a row: a `window` layer leaves K rows of
  8 x 192 and V rows of 8 x 128, a `full` layer 4 x 192 and 4 x 128 (stored
  flat: `cache_row`). The cache manager keeps arrays a kind at the kind's
  own rows
  (`serve/llm/kv_cache.py`), and the window kind's table is a ring of
  window / block + 1 pages: 9 at a window of 128 in pages of 16.
- The sink: one learned scalar b_h a query head of a window layer joins
  every row's denominator and brings no value,
  p_ij = exp(s_ij) / (exp(b_h) + sum_j' exp(s_ij')). In the paged walk that
  is the running softmax's state before any key, not a key
  (`afmoe.window_attend`, the one walk of both families with window and
  full kinds: it takes V's width from `v`, the K/V heads from the kind's
  arrays, and the sink and the value scale where a family has them).
- No shared expert: `layers.routed_feed_forward` leaves the term out.

From `layers.py`: `rms`, the rotation, the head, the weights' declaration
and `routed_feed_forward` (`parallel.moe.expert_shard_layer` under
`sigmoid_topk_route`, the chip's share of an expert-parallel layer:
`experts_held` of `n_experts` from `first_expert` on, the router at its
whole width; `MOE_COUNTS`). From `afmoe.py`: `window_attend`,
`batch_key_slots` and `rope_angles`.

Parameters: `wte`, `layer<i>/{attn_norm, attn_qkv, attn_out, mlp_norm,
...}`, `final_norm`, `lm_head` ([q | k | v] and [gate | up] along the last
axis); a window layer has `sink` ([n_head] float32); a dense layer has
`mlp_gate_up`, `mlp_down`, an expert layer `router`, `router_bias`,
`experts_gate_up`, `experts_down`.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax.numpy as jnp

from ray_tpu.models.afmoe import (batch_key_slots, rope_angles,
                                  window_attend)
from ray_tpu.models.layers import (A_HEAD, declare_weights, head, last_row,
                                   rms, rope, routed_feed_forward,
                                   top_shapes, unboxed_params)
from ray_tpu.parallel.moe import MOE_COUNTS

WINDOW, FULL = "window", "full"
# what each step returns after the cache rows, an int32 vector summed over
# the layers: the engine adds it to `decode_<name>` / `prefill_<name>`.
# `key_slots_<kind>` as `afmoe.STEP_COUNTS` has them. `sink_mass_milli`: the
# share of a row's softmax mass that the sink took, per mille, the step's
# mean over its live rows (a decode step's live lanes), the query heads and
# the window layers, so that the engine's total over its steps' count is a
# mean over steps of a mean over (lane, head, layer)
STEP_COUNTS = tuple(f"moe_{name}" for name in MOE_COUNTS) \
    + ("key_slots_window", "key_slots_full", "sink_mass_milli")


@dataclasses.dataclass(frozen=True)
class MimoV2Config:
    vocab_size: int = 152576
    n_layer: int = 48
    # a layer's attention, `WINDOW` or `FULL`; empty: layer 0 full, then
    # every `global_every`-th full (5, 11, 17, ...), the others window
    layer_types: Tuple[str, ...] = ()
    global_every: int = 6
    n_dense_layer: int = 1          # leading layers with a dense SwiGLU
    window: int = 128               # a window layer sees i - j < window
    n_head: int = 64
    n_kv_head: int = 4              # a full layer's K/V heads ...
    n_kv_head_window: int = 8       # ... and a window layer's
    d_model: int = 4096
    head_dim: int = 192             # a query's and a key's width
    v_head_dim: int = 128           # a value's
    rope_dim: int = 64              # the leading channels rope turns
    rope_theta: float = 1e7         # a full layer's base ...
    rope_theta_window: float = 1e4  # ... and a window layer's
    value_scale: float = 0.707
    ffn_dim: int = 16384            # a dense layer's width
    moe_ffn_dim: int = 2048         # an expert's width
    n_experts: int = 256            # the router's outputs
    experts_held: int = 256         # experts whose weights live here ...
    first_expert: int = 0           # ... from this one on
    top_k: int = 8
    n_shared: int = 0               # the model has no shared expert
    routed_scale: float = 1.0
    max_seq_len: int = 1048576
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    @property
    def types(self) -> Tuple[str, ...]:
        if self.layer_types:
            if len(self.layer_types) != self.n_layer or \
                    set(self.layer_types) - {WINDOW, FULL}:
                raise ValueError(f"layer_types {self.layer_types} for "
                                 f"{self.n_layer} layers")
            return tuple(self.layer_types)
        return tuple(FULL if i == 0 or (i + 1) % self.global_every == 0
                     else WINDOW for i in range(self.n_layer))

    def kv_heads(self, kind: str) -> int:
        return self.n_kv_head_window if kind == WINDOW else self.n_kv_head

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=512, n_layer=4, global_every=3, window=8,
                    n_head=8, n_kv_head=2, n_kv_head_window=4, d_model=64,
                    head_dim=24, v_head_dim=16, rope_dim=8, ffn_dim=128,
                    moe_ffn_dim=32, n_experts=16, experts_held=16, top_k=4,
                    max_seq_len=128)
        base.update(kw)
        return cls(**base)


def page_kinds(cfg: MimoV2Config) -> Tuple[tuple, ...]:
    """The kinds of paged layer, as `kv_cache.PageKind`'s fields (name,
    layers, rows, window): a kind's rows are its own, K of its K/V heads x
    head_dim and V of its K/V heads x v_head_dim, each FLAT, one axis a row
    (`cache_row`). A kind no layer is of is left out."""
    types = cfg.types
    kinds = tuple(
        (name, types.count(name),
         ((cfg.kv_heads(name) * cfg.head_dim,),
          (cfg.kv_heads(name) * cfg.v_head_dim,)), window)
        for name, window in ((WINDOW, cfg.window), (FULL, None)))
    return tuple(kind for kind in kinds if kind[1])


def cache_row(x):
    """[B, C, KVH, D] -> [B, C, KVH * D]: a position's K (or V) as the arena
    stores it. A row of [4, 192] is not one the TPU's tiles of (8, 128) hold:
    compiled for a v5e, every program re-laid the full kind's whole K array
    out (heads before the page's positions) and back, three copies of 1.6 GB
    a decode step. A row of 768 (1,536, 512, 1,024) is whole tiles as it
    stands; the walk gives the gathered block its heads back, which costs
    the block and not the arena."""
    return x.reshape(x.shape[:2] + (-1,))


def layer_slots(cfg: MimoV2Config) -> Tuple[Tuple[int, int], ...]:
    """Layer i -> (its kind's index in `page_kinds`, its index among the
    layers of that kind)."""
    names = [kind[0] for kind in page_kinds(cfg)]
    seen = [0] * len(names)
    slots = []
    for t in cfg.types:
        kind = names.index(t)
        slots.append((kind, seen[kind]))
        seen[kind] += 1
    return tuple(slots)


# -- the weights --------------------------------------------------------------

def layer_shapes(cfg: MimoV2Config, i: int) -> dict:
    """name -> (shape, kind of `layers.INITS`) of layer i's parameters."""
    d, h, kind = cfg.d_model, cfg.n_head, cfg.types[i]
    kvh = cfg.kv_heads(kind)
    shapes = {
        "attn_norm": ((d,), "ones"),
        "attn_qkv": ((d, (h + kvh) * cfg.head_dim + kvh * cfg.v_head_dim),
                     "w"),
        "attn_out": ((h * cfg.v_head_dim, d), "w"),
        "mlp_norm": ((d,), "ones"),
    }
    if kind == WINDOW:
        shapes["sink"] = ((h,), "sink")
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
    })
    return shapes


class MimoV2(nn.Module):
    """`net.init` makes the weights; `apply` is the full causal forward (no
    cache), tokens [B, T] -> logits [B, T, V]."""
    config: MimoV2Config

    @nn.compact
    def __call__(self, tokens):
        cfg = self.config
        p = declare_weights(top_shapes(cfg), (
            layer_shapes(cfg, i) for i in range(cfg.n_layer)), cfg.param_dtype)
        logits, _, _ = _forward(
            p, cfg, tokens, jnp.zeros(tokens.shape[:1], jnp.int32), None,
            None)
        return logits


# -- the three steps ----------------------------------------------------------

def _partial_rope(x, cos, sin, n: int):
    """Rope on the first `n` channels of every head (their halves rotated);
    the others pass."""
    return jnp.concatenate(
        [rope(x[..., :n], cos, sin, A_HEAD), x[..., n:]], axis=-1)


def _forward(p, cfg: MimoV2Config, tokens, start, cache, valid_rows):
    """C tokens a sequence from position `start` on, against the cached K
    and V of its pages (`cache`: every kind's K and V arrays, then every
    kind's page table, in `page_kinds`' order; None for no cache). Returns
    (logits [B, C, V]; the new rows in the arrays' order, k then v a kind,
    [B, C, layers of the kind, the kind's K/V heads x head_dim or
    v_head_dim]; counts as `STEP_COUNTS`)."""
    dtype, hd, vd = cfg.dtype, cfg.head_dim, cfg.v_head_dim
    b, c = tokens.shape
    kinds = page_kinds(cfg)
    x = p["wte"].astype(dtype)[tokens]
    positions = start[:, None] + jnp.arange(c)[None, :]
    angles = {FULL: rope_angles(positions, cfg.rope_dim, cfg.rope_theta),
              WINDOW: rope_angles(positions, cfg.rope_dim,
                                  cfg.rope_theta_window)}
    flat_valid = None if valid_rows is None else valid_rows.reshape(-1)
    rows = [([], []) for _ in kinds]
    counts = jnp.zeros(len(MOE_COUNTS), jnp.int32)
    key_slots = {WINDOW: jnp.int32(0), FULL: jnp.int32(0)}
    sink_mass = jnp.float32(0.0)
    n_q = cfg.n_head * hd
    for i, (kind, at) in enumerate(layer_slots(cfg)):
        lp = p[f"layer{i}"]
        name, window = kinds[kind][0], kinds[kind][3]
        kvh = cfg.kv_heads(name)
        h = rms(x, lp["attn_norm"], cfg.norm_eps, dtype)
        q, k, v = jnp.split(h @ lp["attn_qkv"].astype(dtype),
                            [n_q, n_q + kvh * hd], axis=-1)
        cos, sin = angles[name]
        q = _partial_rope(q.reshape(b, c, cfg.n_head, hd), cos, sin,
                          cfg.rope_dim)
        k = _partial_rope(k.reshape(b, c, kvh, hd), cos, sin, cfg.rope_dim)
        v = v.reshape(b, c, kvh, vd)
        pages = table = None
        if cache is not None:
            pages = cache[2 * kind:2 * kind + 2]
            table = cache[2 * len(kinds) + kind]
        att, slots, mass = window_attend(
            q, k, v, pages, at, table, start, window=window,
            scale=hd ** -0.5, sink=lp.get("sink"),
            value_scale=cfg.value_scale)
        x = x + att @ lp["attn_out"].astype(dtype)
        h = rms(x, lp["mlp_norm"], cfg.norm_eps, dtype)
        y, n = routed_feed_forward(lp, cfg, i, h.reshape(b * c, -1),
                                   flat_valid)
        x = x + y.reshape(b, c, -1)
        counts = counts + n
        key_slots[name] = key_slots[name] + batch_key_slots(slots, b,
                                                            valid_rows)
        if mass is not None:
            mass = jnp.mean(mass, axis=-1)              # over the heads
            if valid_rows is not None:
                mass = jnp.where(valid_rows, mass, 0.0)
            sink_mass = sink_mass + jnp.sum(mass)
        rows[kind][0].append(cache_row(k))
        rows[kind][1].append(cache_row(v))
    live = b * c if valid_rows is None else jnp.sum(valid_rows)
    sink_mass = sink_mass / (max(1, cfg.types.count(WINDOW))
                             * jnp.maximum(live, 1))
    counts = jnp.concatenate([counts, jnp.stack(
        [key_slots[WINDOW], key_slots[FULL],
         jnp.round(1000.0 * sink_mass).astype(jnp.int32)]
    ).astype(jnp.int32)])
    return head(p, cfg, x), \
        [jnp.stack(r, axis=2) for pair in rows for r in pair], counts


def prefill_step(variables, cfg: MimoV2Config, tokens, true_len, valid=None):
    """Full forward over a padded prompt batch. tokens [B, S]; true_len
    [B]; `valid` [B, S] marks the rows that are tokens (for the counters;
    None counts every row). Returns (next_logits [B, V], the new rows a kind
    (k then v, [B, S, layers of the kind, ...]), counts); rows past true_len
    are garbage the caller must not cache."""
    b = tokens.shape[0]
    logits, rows, counts = _forward(
        unboxed_params(variables), cfg, tokens, jnp.zeros((b,), jnp.int32),
        None, valid)
    return (last_row(logits, true_len), *rows, counts)


def chunk_step(variables, cfg: MimoV2Config, tokens, start, *cache,
               valid=None):
    """C tokens a sequence against a paged cache that holds its first
    `start` positions. tokens [B, C]; `cache`: every kind's k_pages
    [P, L, block, KVH * head_dim] and v_pages [P, L, block, KVH *
    v_head_dim], then every kind's page_table [B, n_pages]. Returns (logits [B, C, V],
    the new rows a kind, counts)."""
    logits, rows, counts = _forward(
        unboxed_params(variables), cfg, tokens, start, cache, valid)
    return (logits, *rows, counts)


def decode_step(variables, cfg: MimoV2Config, tokens, positions, *cache,
                valid=None):
    """One token a sequence on a paged cache: the chunk of one. tokens [B];
    positions [B] (= tokens already cached); `valid` [B] marks the lanes
    that hold a sequence. Returns (logits [B, V], the new rows a kind
    [B, layers of the kind, ...], counts)."""
    rows_valid = None if valid is None else valid[:, None]
    logits, rows, counts = _forward(
        unboxed_params(variables), cfg, tokens[:, None], positions, cache,
        rows_valid)
    return (logits[:, 0], *[r[:, 0] for r in rows], counts)
