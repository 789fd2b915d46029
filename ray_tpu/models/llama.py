"""Llama-family decoder-only transformer, TPU-first.

Modern LM architecture (RMSNorm, rotary embeddings, SwiGLU MLP, grouped
-query attention) complementing the GPT-2 family in `gpt.py`. The
reference framework ships no model zoo of its own (models arrive via
torch/HF integrations, e.g. `python/ray/train/huggingface/`); here the
zoo is native Flax with the same logical-axis annotations as `gpt.py`,
so every `parallel/` sharding strategy (DP/FSDP/TP/SP) applies to this
family unchanged.

Design notes:
- GQA: `n_kv_head <= n_head`; the query heads are groups of
  `n_head // n_kv_head` over one K/V head each (the serving decode step
  scores a group against its K/V head in place; the chunk path still
  repeats K and V). KV projections shard over the same "heads" logical
  axis.
- RoPE is computed in float32 and applied per-head (precision matters
  for long sequences); cos/sin tables are closed-over constants folded
  by XLA, not params.
- SwiGLU: gate/up projections fused into one matmul (MXU-friendlier
  than two small ones), split on the last axis.
- `attention_fn` pluggable exactly like GPT: ring/Ulysses attention for
  sequence parallelism binds here.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.gpt import dense, embedding_table
from ray_tpu.models.layers import (A_HEAD, NEG_INF, last_row, rms, rope,
                                   swiglu, unboxed_params)
from ray_tpu.parallel.ring_attention import full_attention
from ray_tpu.parallel.sharding import logical_constraint


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    n_layer: int = 12
    n_head: int = 12
    n_kv_head: int = 4          # GQA group count (== n_head -> MHA)
    d_model: int = 768
    ffn_mult: float = 8 / 3     # SwiGLU hidden = ffn_mult * d_model
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head

    @property
    def ffn_dim(self) -> int:
        # round to a multiple of 128 so the MXU tiles cleanly
        d = int(self.ffn_mult * self.d_model)
        return ((d + 127) // 128) * 128

    @classmethod
    def llama_125m(cls, **kw):
        return cls(n_layer=12, n_head=12, n_kv_head=4, d_model=768, **kw)

    @classmethod
    def tiny(cls, **kw):
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq_len", 128)
        kw.setdefault("n_kv_head", 2)
        return cls(n_layer=2, n_head=4, d_model=64, **kw)


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param(
            "scale",
            nn.with_partitioning(nn.initializers.ones, ("norm",)),
            (x.shape[-1],), self.param_dtype)
        return rms(x, scale, self.eps, self.dtype)


def rope_tables(seq_len: int, head_dim: int, theta: float):
    """(cos, sin) float32 tables [T, head_dim/2]."""
    freqs = 1.0 / (theta ** (
        np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))
    t = np.arange(seq_len, dtype=np.float32)
    ang = np.outer(t, freqs)
    return jnp.asarray(np.cos(ang)), jnp.asarray(np.sin(ang))


def apply_rope(x, cos, sin):
    """Rotate pairs of channels; x: [B, T, H, D] with D even; cos/sin the
    tables' first T rows."""
    return rope(x, cos, sin, np.s_[None, :x.shape[1], None, :])


def _dense(features, logical_axes, name, cfg):
    # Llama uses bias-free projections throughout
    return dense(features, logical_axes, name, cfg, use_bias=False)


class LlamaBlock(nn.Module):
    config: LlamaConfig
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        cfg = self.config
        hd = cfg.head_dim

        h = RMSNorm(cfg.norm_eps, cfg.dtype, cfg.param_dtype,
                    name="attn_norm")(x)
        b, t = h.shape[0], h.shape[1]
        # fused QKV: n_head q-heads + 2 * n_kv_head kv-heads in one matmul
        fused = _dense((cfg.n_head + 2 * cfg.n_kv_head) * hd,
                       ("embed", "qkv"), "attn_qkv", cfg)(h)
        q, k, v = jnp.split(
            fused, [cfg.n_head * hd, (cfg.n_head + cfg.n_kv_head) * hd],
            axis=-1)
        q = q.reshape(b, t, cfg.n_head, hd)
        k = k.reshape(b, t, cfg.n_kv_head, hd)
        v = v.reshape(b, t, cfg.n_kv_head, hd)
        cos, sin = rope_tables(cfg.max_seq_len, hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        # GQA: KV keeps its n_kv_head heads here — every attention_fn
        # (dense/ring/Ulysses via expand_kv_heads, flash via its KV
        # index map) handles the grouping itself, so the expansion is a
        # broadcast (or nothing at all), never an HBM copy
        q = logical_constraint(q, ("batch", "seq", "heads", None))
        k = logical_constraint(k, ("batch", "seq", "heads", None))
        v = logical_constraint(v, ("batch", "seq", "heads", None))
        # post-RoPE K/V are exactly what a decode cache needs; sow is a
        # no-op unless the caller asks for mutable=["intermediates"]
        # (serve.llm prefill), so the training path is unchanged
        self.sow("intermediates", "kv_cache", (k, v))
        attend = self.attention_fn or partial(full_attention, causal=True)
        att = attend(q, k, v).reshape(b, t, cfg.d_model)
        x = x + _dense(cfg.d_model, ("heads", "embed"),
                       "attn_out", cfg)(att)

        h = RMSNorm(cfg.norm_eps, cfg.dtype, cfg.param_dtype,
                    name="mlp_norm")(x)
        # SwiGLU with fused gate+up matmul
        gu = _dense(2 * cfg.ffn_dim, ("embed", "mlp"), "mlp_gate_up",
                    cfg)(h)
        gate, up = jnp.split(gu, 2, axis=-1)
        h = nn.silu(gate) * up
        x = x + _dense(cfg.d_model, ("mlp", "embed"), "mlp_down", cfg)(h)
        return logical_constraint(x, ("batch", "seq", "embed"))


class Llama(nn.Module):
    config: LlamaConfig
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, tokens, deterministic: bool = True,
                 return_hidden: bool = False):
        cfg = self.config
        wte = self.param(
            "wte",
            nn.with_partitioning(nn.initializers.normal(0.02),
                                 ("vocab", "embed")),
            (cfg.vocab_size, cfg.d_model), cfg.param_dtype)
        x = embedding_table(wte, cfg.dtype)[tokens]
        x = logical_constraint(x, ("batch", "seq", "embed"))

        block = LlamaBlock
        if cfg.remat:
            block = nn.remat(LlamaBlock, prevent_cse=False,
                             static_argnums=(1,))
        for i in range(cfg.n_layer):
            x = block(cfg, self.attention_fn,
                      name=f"layer{i}")(x, deterministic)

        x = RMSNorm(cfg.norm_eps, cfg.dtype, cfg.param_dtype,
                    name="final_norm")(x)
        if return_hidden:
            # for ops.fused_cross_entropy: the [B, T, vocab] logits are
            # never materialized in HBM (same hook as GPT.return_hidden)
            return x, wte.astype(cfg.dtype)
        # tied LM head
        return jnp.einsum("btd,vd->btv", x, embedding_table(wte, cfg.dtype))


# -- decode path (serve.llm) ----------------------------------------------
#
# Inference splits the forward into two pure functions the engine can
# AOT-compile per (batch, seq) bucket via `parallel.compiled_step`:
#   prefill_step — full-sequence forward (the flax module itself, so the
#     math is bit-identical to training) that also returns per-position
#     K/V slabs for cache seeding, via the kv_cache sow above;
#   decode_step — single-token forward over a paged KV cache: every layer's
#     attention (`paged_attend`) takes the whole page arena, the layer's
#     index and the per-sequence page-table rows, and reads the K and V of
#     the live pages once: the token's own key, then the cached slots a key
#     block at a time, each lane as far as its own last block (a work list
#     of (lane, block) pairs; one lane alone walks in a loop). No layer of
#     the arena is sliced out, no contiguous or repeated KV copy is made.

# cached keys a trip of `paged_attend`'s loop, whole pages. Settled on the
# chip at Mistral's widths (PERF.md §6, PR 31): 256 is the quickest of
# 128 / 256 / 512 / 1,024, by a layer's attention alone and by a whole step
KEY_BLOCK = 256


def key_block_trips(positions, n_pages: int, page: int, xp=jnp):
    """(trips, keys a block) of `paged_attend`'s walk over the cached keys:
    blocks of `KEY_BLOCK` keys (whole pages; the whole table where it is
    shorter), as far as the block that holds the batch's largest position
    and no further. `positions` [B] with `xp=jnp` inside the program,
    with `xp=np` on the host, where the engine counts what the program
    scored (`decode_attn_key_slots`): one function, so the two cannot
    drift."""
    per_block = max(1, min(KEY_BLOCK // page, n_pages))
    keys = per_block * page
    trips = xp.minimum(-(-xp.max(positions) // keys),
                       -(-n_pages // per_block))
    return trips, keys


def key_block_pairs(positions, n_pages: int, page: int, xp=jnp,
                    key_block: int = KEY_BLOCK):
    """The walk of `key_block_trips` as a work list, each lane its own
    blocks and no lane another's: (blocks [B], lane [W], block [W], live
    [W], keys a block). Lane b holds `blocks[b]` = `min(ceil(positions[b] /
    keys), the table's)` blocks of cached keys, none where it holds nothing
    (a pad lane of the bucket); the live (lane, block) pairs are numbered
    lane by lane and block by block from 0, `blocks.sum()` of them in a
    list of W = B x the table's blocks, the most there can be; what lies
    past them reads lane 0's block 0 and is not live. Pair w is of the
    first lane whose blocks end past w (`searchsorted(ends, w, "right")`,
    as W x B compares). `key_block`: the list's block, `KEY_BLOCK` unless
    the caller settled another for its trips (`paged_attend`'s
    `pair_block`). `xp` as in `key_block_trips`: the host counts with the
    program's function."""
    per_block = max(1, min(key_block // page, n_pages))
    keys = per_block * page
    most = -(-n_pages // per_block)
    blocks = xp.minimum(-(-positions // keys), most)
    ends = xp.cumsum(blocks)
    w = xp.arange(positions.shape[0] * most)
    live = w < ends[-1]
    lane = xp.where(live, xp.sum(w[:, None] >= ends[None, :], axis=1), 0)
    block = xp.where(live, w - (ends[lane] - blocks[lane]), 0)
    return blocks, lane, block, live, keys


# `paged_attend`'s work list: a trip holds `PAIRS_A_LANE` (live or dead) pairs
# a lane of the bucket, of `pair_block` cached keys each. A list bounds a
# lane's walk only where a trip is no wider than the lanes have pairs to fill
# it: B pairs of `KEY_BLOCK` are, for sixteen lanes of one or two hundred
# keys, the walk to the batch's longest over again. Settled on the chip with
# `paged_attend` alone at Ouro's and at Mistral's widths, 16 lanes of their
# cells' decks (PERF.md §6, PR 49): the time goes with the slots scored, a
# trip's own cost is 5-10 us
PAIRS_A_LANE = 4


def pair_block(group: int) -> int:
    """Cached keys a block of the list, by the query heads a K/V head.
    Ungrouped heads (`group` 1: Ouro, GPT-2) are scored without the MXU, at
    the same cost a slot whatever the block, so the block is a page of 16
    and a lane's last block wastes 8 slots on average; a group's product
    wants 64 keys and more a block (Mistral's 4 x 128 by 128 x keys: blocks
    of 16 cost what the walk to the longest did, 64 and 128 two thirds)."""
    return 16 if group == 1 else 64


def key_block_walk(positions, n_pages: int, page: int, group: int, xp=jnp):
    """What `paged_attend` walks of the cached keys, for `positions` [B] and
    `group` query heads a K/V head: (trips, blocks a trip, keys a block, the
    work list or None). One lane walks `key_block_trips` blocks of
    `KEY_BLOCK`, one a trip, and has no list. Two lanes and more walk the
    list of their live (lane, block) pairs (`key_block_pairs` at
    `pair_block(group)`: lane, block, live, each a whole number of trips
    long), `PAIRS_A_LANE` x B of them a trip whatever their lanes, `ceil(
    pairs / that)` trips: the last trip's pairs past the list's end are dead
    and are scored all the same. So a layer scores the lanes' own keys +
    trips x blocks a trip x keys a block slots, which is what the engine
    counts on the host through `decode_key_walk` (`decode_attn_key_slots`)."""
    lanes = positions.shape[0]
    if lanes == 1:
        trips, keys = key_block_trips(positions, n_pages, page, xp)
        return trips, 1, keys, None
    blocks, *pairs, keys = key_block_pairs(positions, n_pages, page, xp,
                                           pair_block(group))
    width = PAIRS_A_LANE * lanes
    short = -len(pairs[0]) % width
    if short:
        pairs = [xp.pad(a, (0, short)) for a in pairs]
    return -(-xp.sum(blocks) // width), width, keys, tuple(pairs)


def decode_key_walk(cfg, positions, n_pages: int, page: int, xp=jnp):
    """`key_block_walk` of this family's decode step (`ouro`'s too), by the
    name under which the engine asks a family's module for it."""
    return key_block_walk(positions, n_pages, page,
                          cfg.n_head // cfg.n_kv_head, xp)


def fold_pairs(state, s, v, lane, live, dtype):
    """A trip of a work list folded into the lanes' running softmax state =
    (m, l [B, G, R, ...], acc [B, G, R, ..., D]), float32: pair t's scores
    s[t] ([T, G, R, ..., K], masked keys at NEG_INF) and values v[t]
    ([T, K, G, D]) are of lane `lane[t]` where `live[t]`, and of nobody
    where not. A trip may hold several blocks of one lane and none of
    another, so the pairs are combined a lane: the lanes' new maxima first
    (a lane with no pair here keeps its own), every pair's exponentials
    against its lane's, then the sums and accumulators added a lane through
    the one-hot [B, T] of `lane` (at `highest`: 1.0 x a float32 must come
    out that float32). Once a row's maximum is a real score, a masked key
    weighs exp(NEG_INF - m) = 0 exactly. (`...`: the chunk's positions in
    `afmoe.window_attend`, nothing in `paged_attend`.)"""
    m, l, acc = state
    hot = live[None, :] & (lane[None, :] == jnp.arange(m.shape[0])[:, None])
    m_new = jnp.maximum(m, jnp.max(jnp.where(
        hot[(...,) + (None,) * (m.ndim - 1)], jnp.max(s, axis=-1)[None],
        NEG_INF), axis=1))
    p = jnp.exp(s - m_new[lane][..., None])
    alpha = jnp.exp(m - m_new)
    hot = hot.astype(jnp.float32)
    highest = jax.lax.Precision.HIGHEST
    return m_new, l * alpha + jnp.einsum(
        "bt,t...->b...", hot, jnp.sum(p, axis=-1), precision=highest), \
        acc * alpha[..., None] + jnp.einsum(
            "bt,t...->b...", hot, jnp.einsum(
                "tgr...k,tkgd->tgr...d", p.astype(dtype), v.astype(dtype),
                preferred_element_type=jnp.float32), precision=highest)


@partial(jax.jit, static_argnames="scale")
def paged_attend(q, k_new, v_new, k_pages, v_pages, layer, page_table,
                 positions, scale):
    """One decode token attending over its paged KV history + itself,
    reading the K and V of the live pages once.

    q: [B, H, D]; k_new/v_new: [B, KVH, D] (this token, post-RoPE);
    k_pages/v_pages: [P, L, block, KVH, D] (the whole arena); page_table:
    [B, n_pages] page ids; positions: [B] keys each sequence has cached
    (0 for a lane that holds none). The query's H heads are viewed as
    KVH groups of H // KVH and scored against K and V as they lie in the
    pages (no repeat). One running softmax (maximum, sum, accumulator in
    float32) over blocks of keys: the token's own key first, which gives
    every row a real maximum, so a masked key weighs exp(NEG_INF - m) = 0
    exactly; then the trips of `key_block_walk`, each block gathered from
    the arena by (page, layer) and masked by its own lane's position. One
    lane (the bucket of one) walks its blocks of `KEY_BLOCK` in a loop. B
    lanes walk the work list of their live (lane, block) pairs, a trip's
    pairs in the place of the lanes, so that a short lane beside a long one
    is gathered and scored as far as its own last block and a lane that
    holds nothing not at all; `fold_pairs` adds the pairs of one lane
    together before they meet the lane's state: the same sums in another
    order, no key left out. Same mathematics as `full_attention` (NEG_INF
    mask, maximum subtracted, 1e-20 sum floor), so decode logits track the
    full forward to float tolerance. Returns [B, H, D] in q's dtype.

    Jitted on its own, with the layer index as an operand, so that a
    decode step traces it once for all its layers: XLA inlines the calls
    and the program is the same, but a serving cell's five decode programs
    trace and lower in 1.3 s on the build box where they took 3.3
    (PERF.md §6, PR 31).
    """
    b, h, d = q.shape
    kvh = k_new.shape[1]
    n_pages, page = page_table.shape[1], k_pages.shape[2]
    f32 = jnp.float32
    qg = q.reshape(b, kvh, h // kvh, d)
    own = jnp.einsum("bgrd,bgd->bgr", qg, k_new,
                     preferred_element_type=f32) * scale
    state = (own, jnp.ones_like(own), jnp.broadcast_to(
        v_new.astype(f32)[:, :, None], qg.shape))
    trips, width, keys, pairs = key_block_walk(positions, n_pages, page,
                                               h // kvh)
    per_block = keys // page
    table = jnp.pad(page_table, ((0, 0), (0, -n_pages % per_block)))

    def cached(j, state):
        m, l, acc = state
        ids = jax.lax.dynamic_slice_in_dim(
            table, j * per_block, per_block, axis=1)
        k = k_pages[ids, layer].reshape(b, keys, kvh, d).astype(q.dtype)
        v = v_pages[ids, layer].reshape(b, keys, kvh, d).astype(q.dtype)
        s = jnp.einsum("bgrd,bkgd->bgrk", qg, k,
                       preferred_element_type=f32) * scale
        seen = (j * keys + jnp.arange(keys))[None, :] < positions[:, None]
        s = jnp.where(seen[:, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bgrk,bkgd->bgrd", p.astype(q.dtype), v,
            preferred_element_type=f32)
        return m_new, l, acc

    if pairs is not None:
        # a row a pair, made here and not a trip (it is the same in every
        # layer of a step, and a trip's body, one a layer in the program,
        # stays small): its lane, the keys its lane holds from the block's
        # first slot on (0 for a dead pair), its page ids
        lane, at, live = pairs
        listed = jnp.concatenate([
            lane[:, None],
            jnp.where(live, positions[lane] - at * keys, 0)[:, None],
            table[lane[:, None], at[:, None] * per_block
                  + jnp.arange(per_block)[None, :]]], axis=1)

    def paired(j, state):
        # `cached` with pair j * width + t in the place of lane t's block j:
        # the pair's lane's query, pages and mask
        rows = jax.lax.dynamic_slice_in_dim(listed, j * width, width)
        lane, held, ids = rows[:, 0], rows[:, 1], rows[:, 2:]
        k = k_pages[ids, layer].reshape(width, keys, kvh, d).astype(q.dtype)
        v = v_pages[ids, layer].reshape(width, keys, kvh, d).astype(q.dtype)
        s = jnp.einsum("tgrd,tkgd->tgrk", qg[lane], k,
                       preferred_element_type=f32) * scale
        seen = jnp.arange(keys)[None, :] < held[:, None]
        s = jnp.where(seen[:, None, None, :], s, NEG_INF)
        return fold_pairs(state, s, v, lane, held > 0, q.dtype)

    _, l, acc = jax.lax.fori_loop(
        0, trips, cached if pairs is None else paired, state)
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    return out.reshape(b, h, d).astype(q.dtype)


def prefill_step(variables, cfg: LlamaConfig, tokens, true_len):
    """Prefill: full forward over a padded prompt batch.

    tokens: [B, S_bucket] (entries at positions >= true_len are padding —
    causal masking keeps them out of every valid position's receptive
    field); true_len: [B] int32. Returns (next_logits [B, V],
    k [B, S, L, KVH, D], v [B, S, L, KVH, D]) where k/v rows past
    true_len are garbage the caller must not cache.
    """
    model = Llama(dataclasses.replace(cfg, remat=False))
    logits, state = model.apply(variables, tokens,
                                mutable=["intermediates"])
    inter = state["intermediates"]
    ks = [inter[f"layer{i}"]["kv_cache"][0][0]
          for i in range(cfg.n_layer)]
    vs = [inter[f"layer{i}"]["kv_cache"][0][1]
          for i in range(cfg.n_layer)]
    k = jnp.stack(ks, axis=2)  # [B, S, L, KVH, D]
    v = jnp.stack(vs, axis=2)
    return last_row(logits, true_len), k, v


def paged_attend_chunk(q, k_new, v_new, k_pages_l, v_pages_l, page_table,
                       valid, scale):
    """A window of C tokens attending over paged KV history + the
    window itself (causally).

    q: [B, C, H, D]; k_new/v_new: [B, C, KVH, D] (this window,
    post-RoPE); k_pages_l/v_pages_l: [P, block, KVH, D]; page_table:
    [B, n_pages]; valid: [B, C, T+C] key mask per query position
    (cached positions < that query's global position, plus the causal
    triangle inside the window). Same math as `paged_attend` — C=1
    reduces to it exactly, which is what makes chunked prefill
    logit-identical to the one-shot path.
    """
    b, c, h, d = q.shape
    kvh = k_new.shape[2]
    kc = k_pages_l[page_table].reshape(b, -1, kvh, d).astype(q.dtype)
    vc = v_pages_l[page_table].reshape(b, -1, kvh, d).astype(q.dtype)
    k_all = jnp.concatenate([kc, k_new], axis=1)  # [B, T+C, KVH, D]
    v_all = jnp.concatenate([vc, v_new], axis=1)
    if kvh != h:  # GQA: repeat KV query-side (expand_kv_heads)
        k_all = jnp.repeat(k_all, h // kvh, axis=2)
        v_all = jnp.repeat(v_all, h // kvh, axis=2)
    logits = jnp.einsum("bchd,bkhd->bhck", q, k_all) * scale
    logits = jnp.where(valid[:, None, :, :], logits, NEG_INF)
    row_max = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - row_max)
    row_sum = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhck,bkhd->bchd", p / jnp.maximum(row_sum, 1e-20),
                     v_all)
    return out


def chunk_valid_mask(start, positions, c: int, t_max: int):
    """[B, C, T+C] key mask for `paged_attend_chunk`: query j (global
    position start+j) sees cached keys < start plus window keys <= j.
    Padding rows (start+j >= true length) still compute but their
    output is discarded by the caller — causality keeps them out of
    every real position's receptive field."""
    key_idx = jnp.arange(t_max)
    cache_valid = key_idx[None, None, :] < start[:, None, None]
    b = start.shape[0]
    causal = jnp.tril(jnp.ones((c, c), dtype=bool))[None]
    return jnp.concatenate(
        [jnp.broadcast_to(cache_valid, (b, c, t_max)),
         jnp.broadcast_to(causal, (b, c, c))], axis=-1)


def chunk_step(variables, cfg: LlamaConfig, tokens, start,
               k_pages, v_pages, page_table):
    """Forward C tokens per sequence against a paged cache holding each
    sequence's first `start` positions. The engine calls it at B=1 for
    chunked prefill (the prompt arrives in fixed-size windows
    interleaved with decode steps) and for the suffix of a prompt whose
    prefix the cache held; the caller reads the logit row of the
    prompt's last token.

    tokens: [B, C]; start: [B] tokens already cached per sequence;
    k_pages/v_pages: [P, L, block, KVH, D]; page_table: [B, n_pages].
    Returns (logits [B, C, V], new_k [B, C, L, KVH, D], new_v
    [B, C, L, KVH, D]); the caller writes rows [0, true_len-start) into
    each sequence's pages and ignores the padding tail.
    """
    p = unboxed_params(variables)
    dtype = cfg.dtype
    hd = cfg.head_dim
    b, c = tokens.shape
    block = k_pages.shape[2]
    t_max = page_table.shape[1] * block
    wte = p["wte"].astype(dtype)
    x = wte[tokens]  # [B, C, D]
    # clamp pad positions into the rope table (their output is garbage
    # by contract; the clamp only keeps the gather in-bounds)
    positions = jnp.minimum(start[:, None] + jnp.arange(c)[None, :],
                            cfg.max_seq_len - 1)
    cos_t, sin_t = rope_tables(cfg.max_seq_len, hd, cfg.rope_theta)
    cos_p, sin_p = cos_t[positions], sin_t[positions]  # [B, C, D/2]
    scale = hd ** -0.5
    valid = chunk_valid_mask(start, positions, c, t_max)
    new_ks, new_vs = [], []
    for i in range(cfg.n_layer):
        lp = p[f"layer{i}"]
        h = rms(x, lp["attn_norm"]["scale"], cfg.norm_eps, dtype)
        fused = h @ lp["attn_qkv"]["kernel"].astype(dtype)
        q, k, v = jnp.split(
            fused, [cfg.n_head * hd, (cfg.n_head + cfg.n_kv_head) * hd],
            axis=-1)
        q = rope(q.reshape(b, c, cfg.n_head, hd), cos_p, sin_p, A_HEAD)
        k = rope(k.reshape(b, c, cfg.n_kv_head, hd), cos_p, sin_p, A_HEAD)
        v = v.reshape(b, c, cfg.n_kv_head, hd)
        att = paged_attend_chunk(q, k, v, k_pages[:, i], v_pages[:, i],
                                 page_table, valid, scale)
        x = x + att.reshape(b, c, cfg.d_model) @ \
            lp["attn_out"]["kernel"].astype(dtype)
        h = rms(x, lp["mlp_norm"]["scale"], cfg.norm_eps, dtype)
        x = x + swiglu(h, lp["mlp_gate_up"]["kernel"],
                       lp["mlp_down"]["kernel"], dtype)
        new_ks.append(k)
        new_vs.append(v)
    x = rms(x, p["final_norm"]["scale"], cfg.norm_eps, dtype)
    logits = jnp.einsum("bcd,vd->bcv", x, wte)
    return logits, jnp.stack(new_ks, axis=2), jnp.stack(new_vs, axis=2)


def decode_step(variables, cfg: LlamaConfig, tokens, positions,
                k_pages, v_pages, page_table):
    """One decode iteration for a batch of sequences on a paged cache.

    tokens: [B] current token ids; positions: [B] their 0-based
    positions (== tokens already cached per sequence; 0 for a lane that
    holds no sequence); k_pages/v_pages: [P, L, block, KVH, D] arena views;
    page_table: [B, n_pages] page ids per logical block (rows padded with
    any valid page id — masked). What a layer reads of the cache, in order
    (`paged_attend`): nothing for the token's own key and value, which are
    scored first; then the trips of `key_block_walk(positions)`, the (page,
    layer) rows of each lane's own key blocks alone, whatever the table's
    length. Returns (logits [B, V], new_k [B, L, KVH, D], new_v
    [B, L, KVH, D]); the caller appends new_k/new_v into each sequence's
    tail page.
    """
    p = unboxed_params(variables)
    dtype = cfg.dtype
    hd = cfg.head_dim
    b = tokens.shape[0]
    wte = p["wte"].astype(dtype)
    x = wte[tokens]  # [B, D]
    cos_t, sin_t = rope_tables(cfg.max_seq_len, hd, cfg.rope_theta)
    cos_p, sin_p = cos_t[positions], sin_t[positions]
    scale = hd ** -0.5
    new_ks, new_vs = [], []
    for i in range(cfg.n_layer):
        lp = p[f"layer{i}"]
        h = rms(x, lp["attn_norm"]["scale"], cfg.norm_eps, dtype)
        fused = h @ lp["attn_qkv"]["kernel"].astype(dtype)
        q, k, v = jnp.split(
            fused, [cfg.n_head * hd, (cfg.n_head + cfg.n_kv_head) * hd],
            axis=-1)
        q = rope(q.reshape(b, cfg.n_head, hd), cos_p, sin_p, A_HEAD)
        k = rope(k.reshape(b, cfg.n_kv_head, hd), cos_p, sin_p, A_HEAD)
        v = v.reshape(b, cfg.n_kv_head, hd)
        att = paged_attend(q, k, v, k_pages, v_pages, i, page_table,
                           positions, scale)
        x = x + att.reshape(b, cfg.d_model) @ \
            lp["attn_out"]["kernel"].astype(dtype)
        h = rms(x, lp["mlp_norm"]["scale"], cfg.norm_eps, dtype)
        x = x + swiglu(h, lp["mlp_gate_up"]["kernel"],
                       lp["mlp_down"]["kernel"], dtype)
        new_ks.append(k)
        new_vs.append(v)
    x = rms(x, p["final_norm"]["scale"], cfg.norm_eps, dtype)
    logits = jnp.einsum("bd,vd->bv", x, wte)
    return logits, jnp.stack(new_ks, axis=1), jnp.stack(new_vs, axis=1)


def flops_per_token(cfg: LlamaConfig, seq_len: int | None = None) -> float:
    t = seq_len or cfg.max_seq_len
    hd = cfg.head_dim
    per_layer = (
        2 * cfg.d_model * (cfg.n_head + 2 * cfg.n_kv_head) * hd  # qkv
        + 2 * cfg.d_model * cfg.d_model                          # attn out
        + 3 * 2 * cfg.d_model * cfg.ffn_dim                      # swiglu
    )
    n_flops = cfg.n_layer * per_layer + 2 * cfg.vocab_size * cfg.d_model
    return 3.0 * n_flops + 12.0 * cfg.n_layer * cfg.d_model * t
