"""Kimi-K2 family decoder (DeepSeek-V3's block): multi-head latent
attention, one leading dense SwiGLU layer, then layers of sigmoid-routed
experts beside a shared expert. Serving only: the three step functions the
paged engine calls, and a flax module that exists to make the weights.

What it asks of the system that `llama.py` does not:

- The cache row is ONE latent a token a layer, `kv_lora_rank +
  qk_rope_dim` values (the normed compressed key/value and the shared rope
  key) padded to whole lane tiles, not K and V of `[n_kv_head, head_dim]`:
  `cache_rows(cfg)` says so and the engine builds its arena from it.
- Two attention paths for one layer, the same numbers. `decode_step` scores
  the query against the cached latents themselves (absorbed: `q_nope W_uk^T`
  against `c_kv`, the output `(P c_kv) W_uv`; nothing of width heads x 256 a
  cached position), each lane's as far as its own last key block, on a work
  list of live (lane, key block) pairs. `prefill_step` and `chunk_step` expand
  latents to per-head keys and values, a group of heads and a block of keys at
  a time: the cached pages as far as `start` reaches, then the window.
- The expert layers hold `experts_held` of `n_experts` experts, starting at
  `first_expert`: one chip's share of an expert-parallel deployment
  (`parallel.moe.expert_shard_layer`). The router keeps all its outputs.

Rope is the half-rotation form on the rope parts only, with YaRN
frequencies (`yarn_tables`). Parameters: `wte`, `layer<i>/{attn_norm, q_a,
q_a_norm, q_b, kv_a, kv_a_norm, kv_b, attn_out, mlp_norm, ...}`,
`final_norm`, `lm_head` (untied); a dense layer has `mlp_gate_up`,
`mlp_down`, an expert layer `router`, `router_bias`, `experts_gate_up`,
`experts_down`, `shared_gate_up`, `shared_down` ([gate | up] along the last
axis, as in `llama.py`).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.layers import (NEG_INF, declare_weights, head, last_row,
                                   rms, rope, routed_feed_forward, top_shapes,
                                   unboxed_params)
from ray_tpu.models.llama import fold_pairs, key_block_pairs
from ray_tpu.parallel.moe import MOE_COUNTS

# what each step returns after the cache rows, an int32 vector summed over
# the layers: the engine adds it to `decode_<name>` / `prefill_<name>`.
# `attn_key_slots` is the key slots a query row of each sequence was scored
# against (cached slots visited, padding among them, and the step's own)
STEP_COUNTS = tuple(f"moe_{name}" for name in MOE_COUNTS) \
    + ("attn_key_slots",)
# heads expanded together in the expanded path: a chunk of 1,024 queries
# against a block of 1,024 keys is 268 MB of float32 scores over 64 heads
HEAD_GROUP = 8
# cached key slots the expanded path visits at a time (whole pages of the
# sequence's table): a chunk of 1,024 is then one block of the same shape
KEY_BLOCK = 1024
# the TPU tiles an array's last axis by this; the cache row is the latent
# padded with zeros to a multiple of it (see `cache_rows`)
LANE_TILE = 128


@dataclasses.dataclass(frozen=True)
class KimiK2Config:
    vocab_size: int = 163840
    n_layer: int = 61
    n_dense_layer: int = 1          # first_k_dense_replace
    n_head: int = 64
    d_model: int = 7168
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    ffn_dim: int = 18432            # the dense layer's width
    moe_ffn_dim: int = 2048         # an expert's width
    n_experts: int = 384            # the router's outputs
    experts_held: int = 384         # experts whose weights live here ...
    first_expert: int = 0           # ... from this one on
    top_k: int = 8
    n_shared: int = 1
    routed_scale: float = 2.827
    max_seq_len: int = 262144
    rope_theta: float = 50000.0
    rope_factor: float = 64.0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    rope_original_max: int = 4096
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def row_dim(self) -> int:
        return -(-self.latent_dim // LANE_TILE) * LANE_TILE

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=512, n_layer=3, n_head=4, d_model=64,
                    q_lora_rank=32, kv_lora_rank=16, qk_nope_dim=8,
                    qk_rope_dim=8, v_head_dim=8, ffn_dim=128,
                    moe_ffn_dim=32, n_experts=16, experts_held=16,
                    top_k=4, max_seq_len=128, rope_factor=4.0,
                    rope_original_max=32)
        base.update(kw)
        return cls(**base)


def cache_rows(cfg: KimiK2Config) -> Tuple[Tuple[int, ...], ...]:
    """What a token leaves in the cache, a layer: one latent row, padded
    to whole lane tiles. The TPU tiles an array's last axis by 128: a row
    of 576 is four and a half tiles, and the compiler then lays the arena
    out with the pages innermost and copies the whole of it to a
    rows-innermost layout and back around every program's scatter (two
    copies of 1.06 GB a call; compiled for a described v5e, PR 27). A row
    of 640 is five tiles, what the 576 would take up in a tiled layout
    anyway, and is updated in place."""
    return ((cfg.row_dim,),)


# -- rope ---------------------------------------------------------------------

def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_tables(cfg: KimiK2Config):
    """(cos, sin) float32 [max_seq_len, qk_rope_dim / 2], YaRN (Peng et al.
    2023) as DeepSeek-V3's modelling code computes it: the extrapolated
    frequencies `theta^(-2i/d)` and the interpolated ones (those over
    `factor`) blended by a linear ramp between the correction dims of
    `beta_fast` and `beta_slow` over the original context; both scaled by
    `mscale(factor, mscale) / mscale(factor, mscale_all_dim)`."""
    dim, base = cfg.qk_rope_dim, cfg.rope_theta
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / cfg.rope_factor

    def correction_dim(rotations):
        return dim * math.log(cfg.rope_original_max
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / ((high - low) or 0.001), 0, 1)
    mask = 1.0 - ramp
    inv = inter * (1 - mask) + extra * mask
    ang = np.outer(np.arange(cfg.max_seq_len, dtype=np.float64), inv)
    m = _yarn_mscale(cfg.rope_factor, cfg.rope_mscale) \
        / _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    return (np.cos(ang) * m).astype(np.float32), \
        (np.sin(ang) * m).astype(np.float32)


def softmax_scale(cfg: KimiK2Config) -> float:
    """(nope + rope)^-0.5 times the square of `mscale_all_dim`'s YaRN
    attention factor."""
    m = _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    return (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5 * m * m


# -- the weights --------------------------------------------------------------

def layer_shapes(cfg: KimiK2Config, i: int) -> dict:
    """name -> (shape, kind of `layers.INITS`) of layer i's parameters."""
    d, h = cfg.d_model, cfg.n_head
    shapes = {
        "attn_norm": ((d,), "ones"),
        "q_a": ((d, cfg.q_lora_rank), "w"),
        "q_a_norm": ((cfg.q_lora_rank,), "ones"),
        "q_b": ((cfg.q_lora_rank,
                 h * (cfg.qk_nope_dim + cfg.qk_rope_dim)), "w"),
        "kv_a": ((d, cfg.latent_dim), "w"),
        "kv_a_norm": ((cfg.kv_lora_rank,), "ones"),
        "kv_b": ((cfg.kv_lora_rank,
                  h * (cfg.qk_nope_dim + cfg.v_head_dim)), "w"),
        "attn_out": ((h * cfg.v_head_dim, d), "w"),
        "mlp_norm": ((d,), "ones"),
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


class KimiK2(nn.Module):
    """`net.init` makes the weights; `apply` is the full causal forward
    (no cache), tokens [B, T] -> logits [B, T, V]."""
    config: KimiK2Config

    @nn.compact
    def __call__(self, tokens):
        cfg = self.config
        p = declare_weights(top_shapes(cfg), (
            layer_shapes(cfg, i) for i in range(cfg.n_layer)), cfg.param_dtype)
        logits, _, _ = _window_forward(
            p, cfg, tokens, jnp.zeros(tokens.shape[:1], jnp.int32), None,
            None, None)
        return logits


# -- the layer's parts --------------------------------------------------------

def _project(lp, cfg: KimiK2Config, h, cos, sin):
    """h [..., d] -> q_nope [..., H, nope], q_rope [..., H, rope] (rotated),
    cache row [..., row_dim]: the normed compressed key/value, the one
    rotated rope key all heads share, zeros up to the row's width.
    cos/sin [..., rope/2]."""
    dtype = cfg.dtype
    with jax.named_scope("mla_project"):
        c_q = rms(h @ lp["q_a"].astype(dtype), lp["q_a_norm"],
                   cfg.norm_eps, dtype)
        q = (c_q @ lp["q_b"].astype(dtype)).reshape(
            h.shape[:-1] + (cfg.n_head, cfg.qk_nope_dim + cfg.qk_rope_dim))
        q_nope, q_rope = jnp.split(q, [cfg.qk_nope_dim], axis=-1)
        q_rope = rope(q_rope, cos[..., None, :], sin[..., None, :])
        kv = h @ lp["kv_a"].astype(dtype)
        c_kv, k_rope = jnp.split(kv, [cfg.kv_lora_rank], axis=-1)
        c_kv = rms(c_kv, lp["kv_a_norm"], cfg.norm_eps, dtype)
        parts = [c_kv, rope(k_rope, cos, sin)]
        if cfg.row_dim > cfg.latent_dim:
            parts.append(jnp.zeros(
                c_kv.shape[:-1] + (cfg.row_dim - cfg.latent_dim,), dtype))
        latent = jnp.concatenate(parts, axis=-1)
    return q_nope, q_rope, latent


def _kv_b(lp, cfg: KimiK2Config):
    """`kv_b` as [kv_lora, H, nope + v]."""
    return lp["kv_b"].astype(cfg.dtype).reshape(
        cfg.kv_lora_rank, cfg.n_head, cfg.qk_nope_dim + cfg.v_head_dim)


# the absorbed path's work list (`absorbed_walk`, at this file's end): cached
# keys a (lane, block) pair, and pairs a lane of the bucket a trip. One latent
# feeds all the heads, so a wide block pays; a trip wider than the lanes have
# pairs to fill costs Ling half as much again. Settled on the chip with the
# attention alone at both cells' shapes (16 lanes x 64 heads, 64 x 32): the
# quickest at both of 128-1,024 keys x 1, 2, 4 pairs (PERF.md section 6, PR 61)
LATENT_BLOCK = 640
PAIRS_A_LANE = 1


def attend_absorbed(lp, cfg: KimiK2Config, q_nope, q_rope, lat_new, pages,
                    layer, walk):
    """One token a sequence against its cached latents and itself.
    q_nope [B, H, nope], q_rope [B, H, rope]; lat_new [B, row] (the token's
    own); pages [P, L, block, row] (the whole arena) and `layer`, the page
    layer to read; `walk` what `listed_walk` made of the step's positions and
    page table, once for all the step's layers. The query is carried into the
    latent space once (`q_nope W_uk^T`, beside `q_rope`, padded to the row)
    and scored against the latents as they lie in the cache: the one latent
    "head" is the K/V head and the H query heads its group, so `walk_latents`
    (at this file's end) reads every live (lane, key block) pair once under
    one running softmax, and no [B, table slots, row] array is made. The
    weighted latent is cut to `kv_lora_rank` and expanded once a head
    (`W_uv`). Returns [B, H * v]."""
    with jax.named_scope("mla_attend"):
        w = _kv_b(lp, cfg)
        w_uk, w_uv = jnp.split(w, [cfg.qk_nope_dim], axis=-1)
        q_lat = jnp.einsum("bhn,chn->bhc", q_nope, w_uk)
        q_cat = jnp.concatenate([q_lat, q_rope], axis=-1)
        # the row's padding scores nothing: [B, H, row]
        q_cat = jnp.pad(q_cat, ((0, 0), (0, 0),
                                (0, cfg.row_dim - cfg.latent_dim)))
        trips, width, _, rows = walk
        # over the whole latent and cut afterwards: a slice of a gathered
        # block would be another copy of it
        o_lat = walk_latents(q_cat, lat_new, pages, layer, trips, rows,
                             width=width, scale=softmax_scale(cfg))
        o_lat = o_lat[..., :cfg.kv_lora_rank].astype(cfg.dtype)
        out = jnp.einsum("bhc,chv->bhv", o_lat, w_uv)
    return out.reshape(out.shape[0], cfg.n_head * cfg.v_head_dim)


def _fold_block(cfg: KimiK2Config, state, w, q, lat_blk, valid):
    """One block of K latents folded into the running softmax of every
    head group (the flash kernel's recurrence, in `jax.numpy`): the
    latents are expanded to keys and values (`c_kv W_ukv`) for `HEAD_GROUP`
    heads at a time, so no scores wider than the block are ever held.
    state = (m, l [G, B, g, C], acc [G, B, g, C, v]), float32: the running
    maximum, sum and weighted values; w [G, kv_lora, g, nope + v];
    q [G, B, g, C, nope + rope]; lat_blk [B, K, row]; valid [B, C | 1, K].
    Once a row's maximum is a real score, a masked key weighs
    exp(NEG_INF - m) = 0 exactly."""
    c_blk, k_rope, _ = jnp.split(
        lat_blk, [cfg.kv_lora_rank, cfg.latent_dim], axis=-1)
    scale = softmax_scale(cfg)
    valid = valid[:, None]
    f32 = jnp.float32

    def group(args):
        w_g, q_g, m, l, acc = args
        kv = jnp.einsum("bkc,cgn->bgkn", c_blk, w_g)
        k_nope, v = jnp.split(kv, [cfg.qk_nope_dim], axis=-1)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_rope[:, None], k_nope.shape[:-1] + k_rope.shape[-1:])], axis=-1)
        s = jnp.einsum("bgqn,bgkn->bgqk", q_g, k,
                       preferred_element_type=f32) * scale
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bgqk,bgkv->bgqv", p.astype(cfg.dtype), v,
            preferred_element_type=f32)
        return m_new, l, acc

    return jax.lax.map(group, (w, q) + state)


def attend_expanded(lp, cfg: KimiK2Config, q_nope, q_rope, lat, start,
                    pages, page_table, layer: int):
    """A window of C tokens causally against its own latents and against
    its sequence's first `start` cached ones. q_nope [B, C, H, nope],
    q_rope [B, C, H, rope]; lat [B, C, row] (the window's); start [B];
    pages [P, L, block, row] and page_table [B, n_pages], or None for no
    cache. One running softmax (`_fold_block`) over blocks of keys: the
    window first (a row's own key gives it a real maximum from the start),
    then the cached slots in blocks of `KEY_BLOCK`, whole pages of the
    table gathered a block at a time, as far as the block that holds the
    batch's largest `start` and no further (the mask is each row's own).
    Returns ([B, C, H * v], the key slots a query row was scored against:
    int32, C + the loop's trips x the block)."""
    with jax.named_scope("mla_attend"):
        b, c, h, _ = q_nope.shape
        g = HEAD_GROUP if h % HEAD_GROUP == 0 else h
        w = jnp.moveaxis(_kv_b(lp, cfg).reshape(
            cfg.kv_lora_rank, h // g, g, -1), 1, 0)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        q = q.reshape(b, c, h // g, g, -1).transpose(2, 0, 3, 1, 4)
        stat = jnp.zeros((h // g, b, g, c), jnp.float32)
        state = _fold_block(
            cfg, (stat + NEG_INF, stat,
                  jnp.zeros(stat.shape + (cfg.v_head_dim,), jnp.float32)),
            w, q, lat, jnp.tril(jnp.ones((c, c), bool))[None])
        slots = jnp.int32(c)
        if pages is not None:
            n_pages, page = page_table.shape[1], pages.shape[2]
            per_block = max(1, min(KEY_BLOCK // page, n_pages))
            k_blk = per_block * page
            n_blocks = -(-n_pages // per_block)
            table = jnp.pad(page_table,
                            ((0, 0), (0, n_blocks * per_block - n_pages)))

            def cached(j, carry):
                trips, state = carry
                ids = jax.lax.dynamic_slice_in_dim(
                    table, j * per_block, per_block, axis=1)
                blk = pages[ids, layer].reshape(b, k_blk, -1)
                seen = (j * k_blk + jnp.arange(k_blk))[None, :] \
                    < start[:, None]
                return trips + 1, _fold_block(
                    cfg, state, w, q, blk.astype(cfg.dtype), seen[:, None])

            trips, state = jax.lax.fori_loop(
                0, jnp.minimum(-(-jnp.max(start) // k_blk), n_blocks),
                cached, (jnp.int32(0), state))
            slots = slots + trips * k_blk
        _, l, acc = state
        out = (acc / jnp.maximum(l, 1e-20)[..., None]).astype(cfg.dtype)
        out = out.transpose(1, 3, 0, 2, 4)          # [B, C, H/g, g, v]
    return out.reshape(b, c, h * cfg.v_head_dim), slots


# -- the three steps ----------------------------------------------------------

def _step_counts(moe_counts, key_slots):
    """The `STEP_COUNTS` vector: the expert layers' counts, then the key
    slots the sequences' query rows were scored against, over the layers."""
    return jnp.concatenate(
        [moe_counts, jnp.asarray(key_slots, jnp.int32)[None]])


def _window_forward(p, cfg: KimiK2Config, tokens, start, pages, page_table,
                    valid_rows):
    """C tokens a sequence from position `start` on, against the cached
    latents of its pages (none when `pages` is None): the expanded path.
    Returns (logits [B, C, V], latents [B, C, L, row], counts)."""
    dtype = cfg.dtype
    b, c = tokens.shape
    x = p["wte"].astype(dtype)[tokens]
    positions = jnp.minimum(start[:, None] + jnp.arange(c)[None, :],
                            cfg.max_seq_len - 1)
    cos_t, sin_t = yarn_tables(cfg)
    cos, sin = jnp.asarray(cos_t)[positions], jnp.asarray(sin_t)[positions]
    flat_valid = None if valid_rows is None else valid_rows.reshape(-1)
    latents, counts = [], jnp.zeros(len(MOE_COUNTS), jnp.int32)
    key_slots = jnp.int32(0)
    for i in range(cfg.n_layer):
        lp = p[f"layer{i}"]
        h = rms(x, lp["attn_norm"], cfg.norm_eps, dtype)
        q_nope, q_rope, lat = _project(lp, cfg, h, cos, sin)
        att, slots = attend_expanded(lp, cfg, q_nope, q_rope, lat, start,
                                     pages, page_table, i)
        x = x + att @ lp["attn_out"].astype(dtype)
        h = rms(x, lp["mlp_norm"], cfg.norm_eps, dtype)
        y, n = routed_feed_forward(lp, cfg, i, h.reshape(b * c, -1),
                                   flat_valid)
        x = x + y.reshape(b, c, -1)
        counts = counts + n
        key_slots = key_slots + b * slots
        latents.append(lat)
    return head(p, cfg, x), jnp.stack(latents, axis=2), \
        _step_counts(counts, key_slots)


def prefill_step(variables, cfg: KimiK2Config, tokens, true_len,
                 valid=None):
    """Full forward over a padded prompt batch. tokens [B, S]; true_len
    [B]; `valid` [B, S] marks the rows that are tokens (for the expert
    counters; None counts every row). Returns (next_logits [B, V], latents
    [B, S, L, row], counts); rows past true_len are garbage the caller
    must not cache."""
    p = unboxed_params(variables)
    b = tokens.shape[0]
    logits, latents, counts = _window_forward(
        p, cfg, tokens, jnp.zeros((b,), jnp.int32), None, None, valid)
    return last_row(logits, true_len), latents, counts


def chunk_step(variables, cfg: KimiK2Config, tokens, start, pages,
               page_table, valid=None):
    """C tokens a sequence against a paged cache that holds its first
    `start` positions. tokens [B, C]; pages [P, L, block, row];
    page_table [B, n_pages]. Returns (logits [B, C, V], latents
    [B, C, L, row], counts)."""
    return _window_forward(unboxed_params(variables), cfg, tokens, start,
                           pages, page_table, valid)


def decode_step(variables, cfg: KimiK2Config, tokens, positions, pages,
                page_table, valid=None):
    """One token a sequence on a paged cache: the absorbed path, each lane's
    cached latents read as far as its own last key block (`listed_walk`, made
    once for all the layers). tokens [B]; positions [B] (= tokens already
    cached); `valid` [B] marks the lanes that hold a sequence. Returns
    (logits [B, V], latents [B, L, row], counts)."""
    p = unboxed_params(variables)
    dtype = cfg.dtype
    x = p["wte"].astype(dtype)[tokens]
    cos_t, sin_t = yarn_tables(cfg)
    cos, sin = jnp.asarray(cos_t)[positions], jnp.asarray(sin_t)[positions]
    walk = listed_walk(positions, page_table, pages.shape[2])
    latents, counts = [], jnp.zeros(len(MOE_COUNTS), jnp.int32)
    for i in range(cfg.n_layer):
        lp = p[f"layer{i}"]
        h = rms(x, lp["attn_norm"], cfg.norm_eps, dtype)
        q_nope, q_rope, lat = _project(lp, cfg, h, cos, sin)
        att = attend_absorbed(lp, cfg, q_nope, q_rope, lat, pages, i, walk)
        x = x + att @ lp["attn_out"].astype(dtype)
        h = rms(x, lp["mlp_norm"], cfg.norm_eps, dtype)
        y, n = routed_feed_forward(lp, cfg, i, h, valid)
        x = x + y
        counts = counts + n
        latents.append(lat)
    # what the program scored: every lane's own latent, then whole trips,
    # dead pairs and the last blocks' padding included
    trips, width, keys, _ = walk
    return head(p, cfg, x), jnp.stack(latents, axis=1), _step_counts(
        counts, cfg.n_layer * (x.shape[0] + trips * width * keys))


# -- the absorbed path's walk over the cached latents -------------------------
# (below the steps: the chunk and prefill programs hold a Pallas kernel whose
# compile-cache key carries the lines of `_window_forward`'s frames, which
# stand where they stood before the walk came, PR 61)

def absorbed_walk(positions, n_pages: int, page: int, xp=jnp):
    """What a decode step walks of the cached latents, for `positions` [B]:
    (trips, pairs a trip, keys a block, the work list), as
    `llama.key_block_walk` lays a K/V step's out, for every bucket, the bucket
    of one too. The list is `llama.key_block_pairs`' at `LATENT_BLOCK` keys a
    block (lane, block, live, each a whole number of trips long):
    `PAIRS_A_LANE` x B pairs a trip whatever their lanes, `ceil(pairs / that)`
    trips; the last trip's pairs past the list's end are dead and are scored
    all the same. So a layer scores the lanes' own latents + trips x pairs a
    trip x keys a block slots, which the steps count (`attn_key_slots`).
    `xp=np` on the host, where a test counts with the program's function."""
    lanes = positions.shape[0]
    blocks, *pairs, keys = key_block_pairs(positions, n_pages, page, xp,
                                           LATENT_BLOCK)
    width = PAIRS_A_LANE * lanes
    short = -len(pairs[0]) % width
    if short:
        pairs = [xp.pad(a, (0, short)) for a in pairs]
    return -(-xp.sum(blocks) // width), width, keys, tuple(pairs)


def listed_walk(positions, page_table, page: int):
    """`absorbed_walk` of a step, with a row a pair in the place of the list
    (made once a step: it is the same in every layer, and a trip's body, one a
    layer in the program, stays small): (trips, pairs a trip, keys a block,
    rows int32 [pairs, 2 + pages a block]), a row its pair's lane, the keys
    its lane holds from the block's first slot on (0 for a dead pair), its
    page ids. positions [B]; page_table [B, n_pages]."""
    n_pages = page_table.shape[1]
    trips, width, keys, (lane, at, live) = absorbed_walk(positions, n_pages,
                                                         page)
    per_block = keys // page
    table = jnp.pad(page_table, ((0, 0), (0, -n_pages % per_block)))
    return trips, width, keys, jnp.concatenate([
        lane[:, None],
        jnp.where(live, positions[lane] - at * keys, 0)[:, None],
        table[lane[:, None], at[:, None] * per_block
              + jnp.arange(per_block)[None, :]]], axis=1)


def _walk_latents(q_cat, lat_new, pages, layer, trips, rows, width: int,
                  scale: float):
    """The running softmax of `attend_absorbed` in the latent space. q_cat
    [B, H, row] (the query in the latent space); lat_new [B, row]; pages
    [P, L, block, row]; `trips`, `rows`, `width` as `listed_walk` gives them.
    State = (maximum, sum [B, 1, H], accumulator [B, 1, H, row]) in float32.
    The token's own latent first, which gives every row a real maximum, so a
    masked key weighs exp(NEG_INF - m) = 0 exactly; then a trip of the list
    at a time: its pairs' pages gathered from the arena by (page, layer),
    scored against their own lanes' queries (the lane's H heads the rows of
    the product), masked by their own lanes' positions and folded
    (`llama.fold_pairs`, which adds the pairs of one lane together before
    they meet the lane's state; the gathered block is key and value both).
    A short lane beside a long one is read as far as its own last block and a
    lane that holds nothing not at all: the same sums as one softmax over
    each lane's latents, in another order, no key left out. Returns the
    weighted latent [B, H, row] float32.

    Jitted on its own with the layer as an operand, so that a step traces it
    once for all its layers, as `llama.paged_attend` is."""
    b, h, row = q_cat.shape
    f32 = jnp.float32
    own = jnp.einsum("bhl,bl->bh", q_cat, lat_new,
                     preferred_element_type=f32)[:, None] * scale
    state = (own, jnp.ones_like(own), jnp.broadcast_to(
        lat_new.astype(f32)[:, None, None], (b, 1, h, row)))

    def paired(j, state):
        pairs = jax.lax.dynamic_slice_in_dim(rows, j * width, width)
        lane, held, ids = pairs[:, 0], pairs[:, 1], pairs[:, 2:]
        blk = pages[ids, layer].reshape(width, -1, row).astype(q_cat.dtype)
        s = jnp.einsum("thl,tkl->thk", q_cat[lane], blk,
                       preferred_element_type=f32) * scale
        seen = jnp.arange(blk.shape[1])[None, :] < held[:, None]
        s = jnp.where(seen[:, None, :], s, NEG_INF)
        return fold_pairs(state, s[:, None], blk[:, :, None], lane, held > 0,
                          q_cat.dtype)

    _, l, acc = jax.lax.fori_loop(0, trips, paired, state)
    return (acc / jnp.maximum(l, 1e-20)[..., None])[:, 0]


walk_latents = jax.jit(_walk_latents, static_argnames=("width", "scale"))
