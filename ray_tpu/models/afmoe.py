"""AFMoE family decoder (Arcee's Trinity): sliding-window and full
attention layers mixed, grouped-query attention with a learned norm on
every query and key head and a sigmoid gate on the attention's output, four
norms a layer (before and after the attention, before and after the
feed-forward), leading dense SwiGLU layers, then layers of sigmoid-routed
experts beside a shared expert, an embedding scaled by sqrt(d_model), an
untied head. Serving only: the three step functions the paged engine calls,
and a flax module that exists to make the weights.

What it asks of the system that no other family does:

- Its layers are of two KINDS of paged layer (`page_kinds`): a `window`
  layer's query reads the last `window - 1` cached positions and itself, a
  `full` layer's all of them. The cache manager keeps pages a kind
  (`serve/llm/kv_cache.py`): K and V arrays of the window layers alone and
  of the full layers alone, a page table a kind, and the window kind's
  table is a RING: position p of a sequence lies in its page (p // block)
  mod the ring, so a window layer holds a window's pages at any context
  length. The steps take every kind's arrays, then every kind's table, and
  return the new rows in the arrays' order.
- `window_attend` reads a ring: which position a slot of the table holds
  follows from how many the sequence has cached; the mask is exact at the
  window's far edge (`i - j < window`) and at the near one (`j < start`,
  what the ring still holds of an older lap is never seen).
- Rope turns the query and key of the window layers only; a full layer's
  scores carry no position.

From `layers.py`: `rms`, the rotation, the head, the weights' declaration
and `routed_feed_forward` (Kimi's too: `parallel.moe.expert_shard_layer`
under `sigmoid_topk_route`, the chip's share of an expert-parallel layer:
`experts_held` of `n_experts` from `first_expert` on, the router at its
whole width; `MOE_COUNTS`). Shared with `llama.py`: the walk over cached key
blocks of `KEY_BLOCK` slots: a batch of lanes walks a work list of its live
(lane, block) pairs, each lane its own blocks and no lane another's
(`key_block_pairs`); one lane alone (a chunk, the bucket of one) walks as
far as its own last (`key_block_trips`).

Parameters: `wte`, `layer<i>/{attn_norm, attn_qkvg, q_norm, k_norm,
attn_out, post_attn_norm, mlp_norm, post_mlp_norm, ...}`, `final_norm`,
`lm_head` ([q | k | v | gate] and [gate | up] along the last axis); a dense
layer has `mlp_gate_up`, `mlp_down`, an expert layer `router`,
`router_bias`, `experts_gate_up`, `experts_down`, `shared_gate_up`,
`shared_down`.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import llama as _llama
from ray_tpu.models.layers import (A_HEAD, NEG_INF, declare_weights, head,
                                   last_row, rms, rope, routed_feed_forward,
                                   top_shapes, unboxed_params)
from ray_tpu.parallel.moe import MOE_COUNTS

SLIDING, FULL = "sliding", "full"
# what each step returns after the cache rows, an int32 vector summed over
# the layers: the engine adds it to `decode_<name>` / `prefill_<name>`.
# `key_slots_<kind>` is the key slots a query row of each sequence was
# scored against in the layers of the kind: the step's own and the cached
# blocks the walk visited, whole blocks, so a lane's last one counts to its
# end. In a batch of lanes a pad lane of the bucket counts nothing and no
# lane counts another's blocks
STEP_COUNTS = tuple(f"moe_{name}" for name in MOE_COUNTS) \
    + ("key_slots_window", "key_slots_full")


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 200192
    n_layer: int = 60
    n_dense_layer: int = 6          # leading layers with a dense SwiGLU
    # a layer's attention, `SLIDING` or `FULL`; empty: every
    # `global_every`-th layer full, the others sliding
    layer_types: Tuple[str, ...] = ()
    global_every: int = 4
    window: int = 4096              # a sliding layer sees i - j < window
    n_head: int = 48
    n_kv_head: int = 8
    d_model: int = 3072
    head_dim: int = 128
    ffn_dim: int = 12288            # a dense layer's width
    moe_ffn_dim: int = 3072         # an expert's width
    n_experts: int = 256            # the router's outputs
    experts_held: int = 256         # experts whose weights live here ...
    first_expert: int = 0           # ... from this one on
    top_k: int = 4
    n_shared: int = 1
    routed_scale: float = 2.448
    mup: bool = True                # the embedding times sqrt(d_model)
    max_seq_len: int = 262144
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    @property
    def types(self) -> Tuple[str, ...]:
        if self.layer_types:
            if len(self.layer_types) != self.n_layer or \
                    set(self.layer_types) - {SLIDING, FULL}:
                raise ValueError(f"layer_types {self.layer_types} for "
                                 f"{self.n_layer} layers")
            return tuple(self.layer_types)
        return tuple(FULL if (i + 1) % self.global_every == 0 else SLIDING
                     for i in range(self.n_layer))

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=512, n_layer=4, n_dense_layer=1, window=8,
                    n_head=4, n_kv_head=2, d_model=64, head_dim=16,
                    ffn_dim=128, moe_ffn_dim=32, n_experts=16,
                    experts_held=16, top_k=4, max_seq_len=128)
        base.update(kw)
        return cls(**base)


def page_kinds(cfg: AfmoeConfig) -> Tuple[tuple, ...]:
    """The kinds of paged layer, as `kv_cache.PageKind`'s fields (name,
    layers, rows, window): the sliding layers keep a window of K and V, the
    full layers every position's. A kind no layer is of is left out."""
    rows = ((cfg.n_kv_head, cfg.head_dim),) * 2
    types = cfg.types
    kinds = (("window", types.count(SLIDING), rows, cfg.window),
             ("full", types.count(FULL), rows, None))
    return tuple(kind for kind in kinds if kind[1])


def layer_slots(cfg: AfmoeConfig) -> Tuple[Tuple[int, int], ...]:
    """Layer i -> (its kind's index in `page_kinds`, its index among the
    layers of that kind)."""
    names = [kind[0] for kind in page_kinds(cfg)]
    seen = [0] * len(names)
    slots = []
    for t in cfg.types:
        kind = names.index("window" if t == SLIDING else "full")
        slots.append((kind, seen[kind]))
        seen[kind] += 1
    return tuple(slots)


# -- the weights --------------------------------------------------------------

def layer_shapes(cfg: AfmoeConfig, i: int) -> dict:
    """name -> (shape, kind of `layers.INITS`) of layer i's parameters."""
    d, hd, h = cfg.d_model, cfg.head_dim, cfg.n_head
    shapes = {
        "attn_norm": ((d,), "ones"),
        "attn_qkvg": ((d, (2 * h + 2 * cfg.n_kv_head) * hd), "w"),
        "q_norm": ((hd,), "ones"),
        "k_norm": ((hd,), "ones"),
        "attn_out": ((h * hd, d), "w"),
        "post_attn_norm": ((d,), "ones"),
        "mlp_norm": ((d,), "ones"),
        "post_mlp_norm": ((d,), "ones"),
    }
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


class Afmoe(nn.Module):
    """`net.init` makes the weights; `apply` is the full causal forward (no
    cache), tokens [B, T] -> logits [B, T, V]."""
    config: AfmoeConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.config
        p = declare_weights(top_shapes(cfg), (
            layer_shapes(cfg, i) for i in range(cfg.n_layer)), cfg.param_dtype)
        logits, _, _ = _window_forward(
            p, cfg, tokens, jnp.zeros(tokens.shape[:1], jnp.int32), None,
            None)
        return logits


# -- the layer's parts --------------------------------------------------------

def rope_angles(positions, head_dim: int, theta: float):
    """(cos, sin) float32 [..., head_dim / 2] at `positions`, computed where
    they are used: a table over 32,768 positions would be 16 MB of constants
    in every program."""
    inv = 1.0 / theta ** (np.arange(0, head_dim, 2, dtype=np.float32)
                          / head_dim)
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def _fold(state, s, v, dtype):
    """One block of scores s [B, G, R, C, K] (masked keys at NEG_INF) and
    its values v [B, K, G, D] folded into the running softmax state = (m, l
    [B, G, R, C], acc [B, G, R, C, D]), float32. Once a row's maximum is a
    real score, a masked key weighs exp(NEG_INF - m) = 0 exactly."""
    m, l, acc = state
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(m - m_new)
    return m_new, l * alpha + jnp.sum(p, axis=-1), \
        acc * alpha[..., None] + jnp.einsum(
            "bgrck,bkgd->bgrcd", p.astype(dtype), v.astype(dtype),
            preferred_element_type=jnp.float32)


@partial(jax.jit, static_argnames=("window", "scale", "value_scale"))
def window_attend(q, k_new, v_new, pages, layer, page_table, start,
                  window: Optional[int], scale: float, sink=None,
                  value_scale: Optional[float] = None):
    """C tokens a sequence, positions `start` to `start + C - 1`, attending
    causally over themselves and over the sequence's cached positions; with
    a `window`, query i sees key j only where i - j < window. The walk of
    every family whose paged layers are of a window kind and a full kind
    (`afmoe`, `mimo_v2`); what one of them does not have is None and adds no
    operation.

    q [B, C, H, D]; k_new [B, C, KVH, D] and v_new [B, C, KVH, DV] (the
    step's own; V's width is its own); pages (k_pages [P, L, block, KVH, D],
    v_pages [P, L, block, KVH, DV], or each with its row flat, [P, L, block,
    KVH * D]: a gathered block is given the step's own shape) of the layer's
    kind, whose K/V head count is the kind's own, or None for no cache;
    `layer` the layer's index among its kind's; page_table [B, n_pages] of
    that kind; start [B]. The query's H heads are KVH groups of H // KVH
    scored against K and V as they lie (no repeat). `sink` [H] float32: a learned score a query head that joins
    every row's denominator and brings no value. It is no key: it is the
    running softmax's state before any key (m = sink, l = 1, acc = 0),
    which the step's own keys are folded into, exactly. `value_scale`
    multiplies the weighted sum of the values.
    One running softmax (`_fold`): the step's own keys first, where a row's
    own key gives it a real maximum, then blocks of the table's slots,
    gathered from the arena by (page, layer). One lane (a chunk, the bucket
    of one) walks `llama.key_block_trips` blocks. B lanes walk
    `llama.key_block_pairs`' list of their live (lane, block) pairs, B
    pairs a trip whatever their lanes, so that a short lane beside a long
    one is gathered and scored as far as its own last block and a lane that
    holds nothing not at all; every array of a trip has the shape it would
    have with lane t in the place of pair t, and `llama.fold_pairs` adds the
    pairs of one lane together before they meet the lane's state: the same
    sums in another order, no key left out.

    Without a window the table's slot s is the sequence's page s. With one
    the table is a ring of n_pages slots: the sequence's page p was written
    to slot p mod n_pages, so slot s holds the newest page p <= (start - 1)
    // block with p = s (mod n_pages): `last - (last - s) mod n_pages`,
    negative where the sequence has not reached the slot. The page's
    positions j = p * block + offset are seen where 0 <= j < start (the rest
    of its rows are an older lap's, or nobody's) and i - j < window. The
    ring has one page more than a window's, so every position a query may
    see is still there (`kv_cache.py`).

    Returns ([B, C, H * DV] in q's dtype; the key slots a query row was
    scored against: int32, C + the walk's blocks x the block; one number
    where there is one walk, [B] where each lane has its own; and, with a
    sink, the share of each row's softmax mass that the sink took, float32
    [B, C, H], else None)."""
    with jax.named_scope("attn_full" if window is None else "attn_window"):
        b, c, h, d = q.shape
        kvh, dv = k_new.shape[2], v_new.shape[3]
        f32 = jnp.float32
        qg = q.reshape(b, c, kvh, h // kvh, d)
        # the step's own keys: causal, and inside the window
        i = jnp.arange(c)
        seen = i[None, :] <= i[:, None]
        if window is not None:
            seen &= i[:, None] - i[None, :] < window
        s = jnp.einsum("bcgrd,bkgd->bgrck", qg, k_new,
                       preferred_element_type=f32) * scale
        s = jnp.where(seen, s, NEG_INF)
        m = jnp.max(s, axis=-1)
        if sink is not None:
            sink = sink.astype(f32).reshape(1, kvh, h // kvh, 1)
            m = jnp.maximum(m, sink)
        p = jnp.exp(s - m[..., None])
        l = jnp.sum(p, axis=-1)
        if sink is not None:
            l = l + jnp.exp(sink - m)
        state = (m, l, jnp.einsum(
            "bgrck,bkgd->bgrcd", p.astype(q.dtype), v_new,
            preferred_element_type=f32))
        slots = jnp.int32(c)
        if pages is not None:
            k_pages, v_pages = pages
            n_pages, page = page_table.shape[1], k_pages.shape[2]
            trips, keys = _llama.key_block_trips(start, n_pages, page)
            per_block = keys // page
            table = jnp.pad(page_table, ((0, 0), (0, -n_pages % per_block)))
            last = (start - 1) // page                          # [B]
            q_pos = start[:, None] + i[None, :]                 # [B, C]

            def cached(j, state):
                ids = jax.lax.dynamic_slice_in_dim(
                    table, j * per_block, per_block, axis=1)
                k = k_pages[ids, layer].reshape(b, keys, kvh, d)
                v = v_pages[ids, layer].reshape(b, keys, kvh, dv)
                s = jnp.einsum("bcgrd,bkgd->bgrck", qg, k.astype(q.dtype),
                               preferred_element_type=f32) * scale
                slot = j * per_block + jnp.arange(keys) // page  # [K]
                held = jnp.broadcast_to(slot[None, :], (b, keys))
                if window is not None:
                    held = last[:, None] - (last[:, None] - held) % n_pages
                pos = held * page + jnp.arange(keys) % page      # [B, K]
                seen = (slot < n_pages)[None, :] & (pos >= 0) \
                    & (pos < start[:, None])
                seen = jnp.broadcast_to(seen[:, None, :], (b, c, keys))
                if window is not None:
                    seen &= q_pos[:, :, None] - pos[:, None, :] < window
                s = jnp.where(seen[:, None, None], s, NEG_INF)
                return _fold(state, s, v, q.dtype)

            def paired(j, state):
                # `cached` with pair j * B + t in the place of lane t's
                # block j: the pair's lane's pages, query, start and last
                # (a body of its own, for `cached` lowers to the text the
                # chunk and the bucket of one had: the machine's cache)
                lane, at, live, begun, newest = (
                    jax.lax.dynamic_slice_in_dim(a, j * b, b) for a in pairs)
                ids = table[lane[:, None], at[:, None] * per_block
                            + jnp.arange(per_block)[None, :]]
                k = k_pages[ids, layer].reshape(b, keys, kvh, d)
                v = v_pages[ids, layer].reshape(b, keys, kvh, dv)
                s = jnp.einsum("bcgrd,bkgd->bgrck", qg[lane],
                               k.astype(q.dtype),
                               preferred_element_type=f32) * scale
                slot = at[:, None] * per_block \
                    + (jnp.arange(keys) // page)[None, :]        # [T, K]
                held = slot
                if window is not None:
                    held = newest[:, None] \
                        - (newest[:, None] - held) % n_pages
                pos = held * page + jnp.arange(keys) % page      # [T, K]
                seen = live[:, None] & (slot < n_pages) & (pos >= 0) \
                    & (pos < begun[:, None])
                seen = jnp.broadcast_to(seen[:, None, :], (b, c, keys))
                if window is not None:
                    seen &= (begun[:, None] + i[None, :])[:, :, None] \
                        - pos[:, None, :] < window
                s = jnp.where(seen[:, None, None], s, NEG_INF)
                return _llama.fold_pairs(state, s, v, lane, live, q.dtype)

            if b == 1:      # a chunk, the bucket of one: nothing to pair
                state = jax.lax.fori_loop(0, trips, cached, state)
                slots = slots + trips * keys
            else:
                blocks, lane, at, live, _ = _llama.key_block_pairs(
                    start, n_pages, page)
                pairs = (lane, at, live, start[lane], last[lane])
                state = jax.lax.fori_loop(0, -(-jnp.sum(blocks) // b), paired,
                                          state)
                slots = slots + blocks * keys                   # [B]
        m, l, acc = state
        out = acc / jnp.maximum(l, 1e-20)[..., None]   # [B, KVH, R, C, DV]
        if value_scale is not None:
            out = out * value_scale
        mass = None
        if sink is not None:
            mass = (jnp.exp(sink - m) / l).transpose(0, 3, 1, 2).reshape(
                b, c, h)
        return out.transpose(0, 3, 1, 2, 4).reshape(b, c, h * dv).astype(
            q.dtype), slots, mass


def batch_key_slots(slots, b: int, valid_rows):
    """`window_attend`'s key slots, summed over a batch of `b` lanes: the
    one walk's count a lane, or the work list's own a lane, where a pad lane
    of the bucket (no row of `valid_rows` [B, C] a token) counts nothing."""
    if not slots.ndim:
        return b * slots
    if valid_rows is not None:
        slots = jnp.where(valid_rows.any(axis=1), slots, 0)
    return jnp.sum(slots)


# -- the three steps ----------------------------------------------------------

def _window_forward(p, cfg: AfmoeConfig, tokens, start, cache, valid_rows):
    """C tokens a sequence from position `start` on, against the cached K
    and V of its pages (`cache`: every kind's K and V arrays, then every
    kind's page table, in `page_kinds`' order; None for no cache). Returns
    (logits [B, C, V]; the new rows in the arrays' order, k then v a kind,
    each [B, C, layers of the kind, KVH, D]; counts as `STEP_COUNTS`)."""
    dtype, hd = cfg.dtype, cfg.head_dim
    b, c = tokens.shape
    kinds = page_kinds(cfg)
    x = p["wte"].astype(dtype)[tokens]
    if cfg.mup:
        x = x * jnp.asarray(cfg.d_model ** 0.5, dtype)
    cos, sin = rope_angles(start[:, None] + jnp.arange(c)[None, :], hd,
                           cfg.rope_theta)
    flat_valid = None if valid_rows is None else valid_rows.reshape(-1)
    rows = [([], []) for _ in kinds]
    counts = jnp.zeros(len(MOE_COUNTS), jnp.int32)
    key_slots = {"window": jnp.int32(0), "full": jnp.int32(0)}
    n_q, n_kv = cfg.n_head * hd, cfg.n_kv_head * hd
    for i, (kind, at) in enumerate(layer_slots(cfg)):
        lp = p[f"layer{i}"]
        name, window = kinds[kind][0], kinds[kind][3]
        h = rms(x, lp["attn_norm"], cfg.norm_eps, dtype)
        q, k, v, gate = jnp.split(
            h @ lp["attn_qkvg"].astype(dtype),
            [n_q, n_q + n_kv, n_q + 2 * n_kv], axis=-1)
        q = rms(q.reshape(b, c, cfg.n_head, hd), lp["q_norm"],
                 cfg.norm_eps, dtype)
        k = rms(k.reshape(b, c, cfg.n_kv_head, hd), lp["k_norm"],
                 cfg.norm_eps, dtype)
        if window is not None:      # a full layer's scores carry no position
            q, k = rope(q, cos, sin, A_HEAD), rope(k, cos, sin, A_HEAD)
        v = v.reshape(b, c, cfg.n_kv_head, hd)
        pages = table = None
        if cache is not None:
            pages = cache[2 * kind:2 * kind + 2]
            table = cache[2 * len(kinds) + kind]
        att, slots, _ = window_attend(q, k, v, pages, at, table, start,
                                      window=window, scale=hd ** -0.5)
        att = att * jax.nn.sigmoid(gate)
        x = x + rms(att @ lp["attn_out"].astype(dtype),
                     lp["post_attn_norm"], cfg.norm_eps, dtype)
        h = rms(x, lp["mlp_norm"], cfg.norm_eps, dtype)
        y, n = routed_feed_forward(lp, cfg, i, h.reshape(b * c, -1),
                                   flat_valid)
        x = x + rms(y.reshape(b, c, -1), lp["post_mlp_norm"], cfg.norm_eps,
                     dtype)
        counts = counts + n
        key_slots[name] = key_slots[name] + batch_key_slots(slots, b,
                                                            valid_rows)
        rows[kind][0].append(k)
        rows[kind][1].append(v)
    counts = jnp.concatenate([counts, jnp.stack(
        [key_slots["window"], key_slots["full"]]).astype(jnp.int32)])
    return head(p, cfg, x), \
        [jnp.stack(r, axis=2) for pair in rows for r in pair], counts


def prefill_step(variables, cfg: AfmoeConfig, tokens, true_len, valid=None):
    """Full forward over a padded prompt batch. tokens [B, S]; true_len
    [B]; `valid` [B, S] marks the rows that are tokens (for the expert
    counters; None counts every row). Returns (next_logits [B, V], the new
    rows a kind (k then v, [B, S, layers of the kind, KVH, D]), counts);
    rows past true_len are garbage the caller must not cache."""
    b = tokens.shape[0]
    logits, rows, counts = _window_forward(
        unboxed_params(variables), cfg, tokens, jnp.zeros((b,), jnp.int32),
        None, valid)
    return (last_row(logits, true_len), *rows, counts)


def chunk_step(variables, cfg: AfmoeConfig, tokens, start, *cache,
               valid=None):
    """C tokens a sequence against a paged cache that holds its first
    `start` positions. tokens [B, C]; `cache`: every kind's k_pages and
    v_pages [P, L, block, KVH, D], then every kind's page_table
    [B, n_pages]. Returns (logits [B, C, V], the new rows a kind, counts)."""
    logits, rows, counts = _window_forward(
        unboxed_params(variables), cfg, tokens, start, cache, valid)
    return (logits, *rows, counts)


def decode_step(variables, cfg: AfmoeConfig, tokens, positions, *cache,
                valid=None):
    """One token a sequence on a paged cache: the chunk of one. tokens [B];
    positions [B] (= tokens already cached); `valid` [B] marks the lanes
    that hold a sequence. Returns (logits [B, V], the new rows a kind
    [B, layers of the kind, KVH, D], counts)."""
    rows_valid = None if valid is None else valid[:, None]
    logits, rows, counts = _window_forward(
        unboxed_params(variables), cfg, tokens[:, None], positions, cache,
        rows_valid)
    return (logits[:, 0], *[r[:, 0] for r in rows], counts)
