"""SDAR-MoE family decoder (Qwen3-MoE's block, generated by diffusion over
blocks): grouped-query attention with a learned norm on every query and key
head, a routed feed-forward in every layer (softmax top-k, weights
renormalised over the chosen, no shared expert), an untied head. Serving
only: the three step functions the paged engine calls, and a flax module
that exists to make the weights.

What it asks of the system that `llama.py` does not:

- The mask is BLOCK-CAUSAL: with blocks of `block_length` positions, key j
  is visible to query i iff j // block_length <= i // block_length: a token
  sees every earlier block and its own block in both directions.
- A decode pass carries one whole block a lane (`decode_step`: tokens
  [lanes, block_length], the block's first position a lane), scores it
  against the lane's pages and the block's own in-flight K and V
  (`block_attend`), and chooses in the program: at every position the
  largest logit's token (never the mask token) and its softmax
  probability (`block_choose`), two [lanes, block_length] arrays for the
  host, which keeps what is revealed (`block_schedule`: how long a block
  is, how many positions a pass reveals, the mask token). The pass returns
  the block's K and V rows; the engine writes them only for a lane whose
  block is whole.
- A prefill yields no token: `prefill_step` and `chunk_step` return None
  where the other families return logits, and compute no head.

From `layers.py`: `rms`, the rotation, the weights' declaration. Shared with
`llama.py`: `rope_tables` and the walk over cached key blocks
(`key_walk` below: one lane walks `key_block_trips` blocks of `KEY_BLOCK`
slots in a loop, two lanes and more `key_block_pairs`' work list of their
live (lane, block) pairs, each lane as far as its own last block, folded by
`fold_pairs`). Shared with `kimi_k2.py` / `ling_hybrid.py`:
`parallel.moe.expert_shard_layer` (here told to route by
`softmax_topk_route`) and `MOE_COUNTS`.

Parameters: `wte`, `layer<i>/{attn_norm, attn_qkv, q_norm, k_norm,
attn_out, mlp_norm, router, experts_gate_up, experts_down}`, `final_norm`,
`lm_head` ([q | k | v] and [gate | up] along the last axis).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models import llama as _llama
from ray_tpu.models.layers import (A_HEAD, NEG_INF, declare_weights, rms,
                                   rope, top_shapes, unboxed_params)
from ray_tpu.models.llama import rope_tables
from ray_tpu.parallel.moe import (MOE_COUNTS, expert_shard_layer,
                                  softmax_topk_route)

# what each step returns after the cache rows, an int32 vector summed over
# the layers: the engine adds it to `decode_<name>` / `prefill_<name>`
STEP_COUNTS = tuple(f"moe_{name}" for name in MOE_COUNTS)


@dataclasses.dataclass(frozen=True)
class SdarMoeConfig:
    vocab_size: int = 151936
    n_layer: int = 48
    n_head: int = 32
    n_kv_head: int = 4
    d_model: int = 2048
    head_dim: int = 128
    moe_ffn_dim: int = 768          # an expert's width
    n_experts: int = 128            # the router's outputs
    experts_held: int = 128         # experts whose weights live here ...
    first_expert: int = 0           # ... from this one on
    top_k: int = 8
    max_seq_len: int = 32768
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-6
    # generation: positions a block, denoising passes a whole block (a pass
    # reveals block_length // denoise_steps positions), the id an
    # unrevealed position holds
    block_length: int = 4
    denoise_steps: int = 2
    mask_token: int = 151669
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=512, n_layer=2, n_head=4, n_kv_head=2,
                    d_model=64, head_dim=32, moe_ffn_dim=32, n_experts=16,
                    experts_held=16, top_k=4, max_seq_len=128,
                    mask_token=511)
        base.update(kw)
        return cls(**base)


def cache_rows(cfg: SdarMoeConfig) -> Tuple[Tuple[int, ...], ...]:
    """K and V of [n_kv_head, head_dim]: the head is wider than
    d_model // n_head, so the engine cannot derive it."""
    return ((cfg.n_kv_head, cfg.head_dim),) * 2


def block_schedule(cfg: SdarMoeConfig) -> Tuple[int, int, int]:
    """(positions a block, positions a denoising pass reveals, the mask
    token): what the engine needs to run a block's passes."""
    if cfg.block_length % cfg.denoise_steps:
        raise ValueError(f"{cfg.denoise_steps} passes do not divide a block "
                         f"of {cfg.block_length}")
    return (cfg.block_length, cfg.block_length // cfg.denoise_steps,
            cfg.mask_token)


# -- the weights --------------------------------------------------------------

def layer_shapes(cfg: SdarMoeConfig) -> dict:
    """name -> (shape, kind of `layers.INITS`) of a layer's parameters."""
    d, hd, f = cfg.d_model, cfg.head_dim, cfg.moe_ffn_dim
    return {
        "attn_norm": ((d,), "ones"),
        "attn_qkv": ((d, (cfg.n_head + 2 * cfg.n_kv_head) * hd), "w"),
        "q_norm": ((hd,), "ones"),
        "k_norm": ((hd,), "ones"),
        "attn_out": ((cfg.n_head * hd, d), "w"),
        "mlp_norm": ((d,), "ones"),
        "router": ((d, cfg.n_experts), "w"),
        "experts_gate_up": ((cfg.experts_held, d, 2 * f), "w"),
        "experts_down": ((cfg.experts_held, f, d), "w"),
    }


class SdarMoe(nn.Module):
    """`net.init` makes the weights; `apply` is the full forward under the
    block-causal mask (no cache), tokens [B, T] -> logits [B, T, V]
    float32."""
    config: SdarMoeConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.config
        p = declare_weights(top_shapes(cfg),
                            [layer_shapes(cfg)] * cfg.n_layer,
                            cfg.param_dtype)
        x, _, _, _ = _window_forward(
            p, cfg, tokens, jnp.zeros(tokens.shape[:1], jnp.int32), None,
            None, None)
        return head_logits(p, cfg, x)


# -- the walk over the cached keys --------------------------------------------

# cached keys a (lane, block) pair of `block_attend`'s work list, one pair a
# lane of the bucket a trip. A pair here brings its lane's C = 4 query
# positions: four times `llama.paged_attend`'s gathered queries and float32
# partial sums beside the same K and V, so a pair's own cost asks for a wider
# block than a token step's 64 and for fewer pairs a trip than its 4 a lane.
# Settled on the chip with `block_attend` alone at the cell's shapes (64
# lanes, 7 layers, the deck's lengths; PERF.md §6, PR 59)
PAIR_BLOCK = 192


def key_walk(start, n_pages: int, page: int, xp=jnp, listed: bool = True):
    """What `block_attend` walks of the cached keys, for `start` [B]: (trips,
    blocks a trip, keys a block, the work list or None), as
    `llama.key_block_walk` lays a token step's out. One lane walks
    `llama.key_block_trips` blocks of `KEY_BLOCK`, one a trip, and has no
    list. Two lanes and more walk `llama.key_block_pairs`' list of their live
    (lane, block) pairs at `PAIR_BLOCK` keys a block (lane, block, live, each
    B x the table's blocks long, so whole trips), B of them a trip whatever
    their lanes, `ceil(pairs / B)` trips: the last trip's pairs past the
    list's end are dead and are scored all the same. Not `listed`: the same
    trips, width and keys from the lanes' blocks alone (`key_block_pairs`'
    first lines) and None for the list, for the host, which counts a pass of
    64 lanes and reads no pair (`tests/test_sdar_moe.py` holds the two to
    each other)."""
    lanes = start.shape[0]
    if lanes == 1:
        trips, keys = _llama.key_block_trips(start, n_pages, page, xp)
        return trips, 1, keys, None
    if listed:
        blocks, *pairs, keys = _llama.key_block_pairs(start, n_pages, page,
                                                      xp, PAIR_BLOCK)
        return -(-xp.sum(blocks) // lanes), lanes, keys, tuple(pairs)
    per_block = max(1, min(PAIR_BLOCK // page, n_pages))
    keys = per_block * page
    blocks = xp.minimum(-(-start // keys), -(-n_pages // per_block))
    return -(-xp.sum(blocks) // lanes), lanes, keys, None


def decode_key_walk(cfg: SdarMoeConfig, positions, n_pages: int, page: int,
                    xp=jnp):
    """`key_walk` of a decode pass, by the name under which the engine asks
    a family's module for it: the host (`xp=np`) counts what the program
    scored (`decode_attn_key_slots`) and makes no list."""
    return key_walk(positions, n_pages, page, xp, listed=xp is jnp)


# -- the layer's parts --------------------------------------------------------

@partial(jax.jit, static_argnames=("block_length", "scale"))
def block_attend(q, k_new, v_new, pages, layer, page_table, start,
                 block_length: int, scale: float):
    """A window of C tokens a sequence, whole blocks from position `start`
    (a multiple of `block_length`) on, attending block-causally over the
    window itself and over the sequence's first `start` cached positions.

    q [B, C, H, D]; k_new/v_new [B, C, KVH, D] (the window's, post-rope);
    pages (k_pages, v_pages), each [P, L, block, KVH, D] (the whole arena),
    or None for no cache; page_table [B, n_pages]; start [B]. The query's H
    heads are KVH groups of H // KVH scored against K and V as they lie (no
    repeat). One running softmax (maximum, sum, accumulator in float32): the
    window first, where a row's own key gives it a real maximum, so a masked
    key weighs exp(NEG_INF - m) = 0 exactly; then the trips of `key_walk`,
    each block gathered from the arena by (page, layer) and masked by its
    own lane's `start`. One lane (a chunk, the bucket of one) walks its
    blocks of `KEY_BLOCK` in a loop. B lanes walk the work list of their
    live (lane, block) pairs, a trip's pairs in the place of the lanes,
    each pair with its lane's C query rows, so that a short lane beside a
    long one is gathered and scored as far as its own last block and a lane
    that holds nothing not at all; `llama.fold_pairs` adds the pairs of one
    lane together before they meet the lane's state: the same sums in
    another order, no key left out. A decode pass is the window of one block
    (every key of it visible); `llama.paged_attend` is this at one query a
    lane. Returns [B, C, H * D] in q's dtype.

    Jitted on its own with the layer as an operand, so that a step traces
    it once for all its layers, as `llama.paged_attend` is."""
    with jax.named_scope("block_attend"):
        b, c, h, d = q.shape
        kvh = k_new.shape[2]
        f32 = jnp.float32
        qg = q.reshape(b, c, kvh, h // kvh, d)
        blk = jnp.arange(c) // block_length
        s = jnp.einsum("bcgrd,bkgd->bgrck", qg, k_new,
                       preferred_element_type=f32) * scale
        s = jnp.where(blk[None, :] <= blk[:, None], s, NEG_INF)
        m = jnp.max(s, axis=-1)
        p = jnp.exp(s - m[..., None])
        state = (m, jnp.sum(p, axis=-1), jnp.einsum(
            "bgrck,bkgd->bgrcd", p.astype(q.dtype), v_new,
            preferred_element_type=f32))
        if pages is not None:
            k_pages, v_pages = pages
            n_pages, page = page_table.shape[1], k_pages.shape[2]
            trips, width, keys, pairs = key_walk(start, n_pages, page)
            per_block = keys // page
            table = jnp.pad(page_table, ((0, 0), (0, -n_pages % per_block)))

            def cached(j, state):
                m, l, acc = state
                ids = jax.lax.dynamic_slice_in_dim(
                    table, j * per_block, per_block, axis=1)
                k = k_pages[ids, layer].reshape(b, keys, kvh, d)
                v = v_pages[ids, layer].reshape(b, keys, kvh, d)
                s = jnp.einsum("bcgrd,bkgd->bgrck", qg, k.astype(q.dtype),
                               preferred_element_type=f32) * scale
                seen = (j * keys + jnp.arange(keys))[None, :] \
                    < start[:, None]
                s = jnp.where(seen[:, None, None, None, :], s, NEG_INF)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                p = jnp.exp(s - m_new[..., None])
                alpha = jnp.exp(m - m_new)
                l = l * alpha + jnp.sum(p, axis=-1)
                acc = acc * alpha[..., None] + jnp.einsum(
                    "bgrck,bkgd->bgrcd", p.astype(q.dtype),
                    v.astype(q.dtype), preferred_element_type=f32)
                return m_new, l, acc

            if pairs is not None:
                # a row a pair, as `llama.paged_attend` lays it out (made
                # here and not a trip: it is the same in every layer): its
                # lane, the keys its lane holds from the block's first slot
                # on (0 for a dead pair), its pages' first rows in the
                # arena seen as below
                lane, at, live = pairs
                layers = k_pages.shape[1]
                listed = jnp.concatenate([
                    lane[:, None],
                    jnp.where(live, start[lane] - at * keys, 0)[:, None],
                    layers * table[lane[:, None], at[:, None] * per_block
                                   + jnp.arange(per_block)[None, :]]], axis=1)
                # the arena as [pages x layers, block x KVH, D]: the same
                # bytes, a (page, layer) one row of whole (8, 128) tiles,
                # which the chip gathers quicker than a [block, KVH, D] of
                # (4, 128) tiles (PERF.md §6, PR 59)
                k_rows = k_pages.reshape(-1, page * kvh, d)
                v_rows = v_pages.reshape(-1, page * kvh, d)

            def paired(j, state):
                # `cached` with pair j * width + t in the place of lane t's
                # block j: the pair's lane's C queries, pages and mask
                rows = jax.lax.dynamic_slice_in_dim(listed, j * width, width)
                lane, held, ids = rows[:, 0], rows[:, 1], rows[:, 2:] + layer
                k = k_rows[ids].reshape(width, keys, kvh, d)
                v = v_rows[ids].reshape(width, keys, kvh, d)
                s = jnp.einsum("tcgrd,tkgd->tgrck", qg[lane],
                               k.astype(q.dtype),
                               preferred_element_type=f32) * scale
                seen = jnp.arange(keys)[None, :] < held[:, None]
                s = jnp.where(seen[:, None, None, None, :], s, NEG_INF)
                return _llama.fold_pairs(state, s, v, lane, held > 0, q.dtype)

            state = jax.lax.fori_loop(
                0, trips, cached if pairs is None else paired, state)
        _, l, acc = state
        out = acc / jnp.maximum(l, 1e-20)[..., None]   # [B, KVH, R, C, D]
        return out.transpose(0, 3, 1, 2, 4).reshape(b, c, h * d).astype(
            q.dtype)


def feed_forward(lp, cfg: SdarMoeConfig, h, valid):
    """The routed feed-forward of h [N, d]: this chip's experts' part of
    the sum over each token's `top_k` of `n_experts` (all of it where every
    expert is held). Returns (result [N, d], counts int32 as `MOE_COUNTS`)."""
    return expert_shard_layer(
        h, lp["router"], None,
        {"gate_up": lp["experts_gate_up"], "down": lp["experts_down"]},
        cfg.first_expert, cfg.n_experts, cfg.top_k, 1.0, valid=valid,
        route=softmax_topk_route)


def head_logits(p, cfg: SdarMoeConfig, x):
    """x [..., d] (the last layer's output) -> logits [..., V] float32."""
    h = rms(x, p["final_norm"], cfg.norm_eps, cfg.dtype)
    return jnp.dot(h, p["lm_head"].astype(cfg.dtype),
                   preferred_element_type=jnp.float32)


def choose(logits, mask_token: int):
    """At every position the token of the largest logit, the mask token
    apart, and its probability under the softmax over the same tokens.
    logits [..., V] float32 -> (token int32 [...], probability float32)."""
    logits = jnp.where(jnp.arange(logits.shape[-1]) == mask_token, -jnp.inf,
                       logits)
    top = jnp.max(logits, axis=-1)
    token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return token, 1.0 / jnp.sum(jnp.exp(logits - top[..., None]), axis=-1)


def block_choose(p, cfg: SdarMoeConfig, x):
    with jax.named_scope("block_choose"):
        return choose(head_logits(p, cfg, x), cfg.mask_token)


# -- the three steps ----------------------------------------------------------

def _window_forward(p, cfg: SdarMoeConfig, tokens, start, pages, page_table,
                    valid_rows):
    """C tokens a sequence from position `start` on, against the K and V of
    its pages (none when `pages` is None). Returns (the last layer's output
    [B, C, d], k [B, C, L, KVH, D], v, counts)."""
    dtype, hd = cfg.dtype, cfg.head_dim
    b, c = tokens.shape
    x = p["wte"].astype(dtype)[tokens]
    # a request's last block may reach past `max_seq_len` by a part of a
    # block: the table has room for it
    cos_t, sin_t = rope_tables(cfg.max_seq_len + cfg.block_length, hd,
                               cfg.rope_theta)
    positions = jnp.minimum(start[:, None] + jnp.arange(c)[None, :],
                            cos_t.shape[0] - 1)
    cos, sin = cos_t[positions], sin_t[positions]
    flat_valid = None if valid_rows is None else valid_rows.reshape(-1)
    ks, vs = [], []
    counts = jnp.zeros(len(MOE_COUNTS), jnp.int32)
    n_q, n_kv = cfg.n_head * hd, cfg.n_kv_head * hd
    for i in range(cfg.n_layer):
        lp = p[f"layer{i}"]
        h = rms(x, lp["attn_norm"], cfg.norm_eps, dtype)
        q, k, v = jnp.split(h @ lp["attn_qkv"].astype(dtype),
                            [n_q, n_q + n_kv], axis=-1)
        q = rms(q.reshape(b, c, cfg.n_head, hd), lp["q_norm"],
                 cfg.norm_eps, dtype)
        k = rms(k.reshape(b, c, cfg.n_kv_head, hd), lp["k_norm"],
                 cfg.norm_eps, dtype)
        q, k = rope(q, cos, sin, A_HEAD), rope(k, cos, sin, A_HEAD)
        v = v.reshape(b, c, cfg.n_kv_head, hd)
        att = block_attend(q, k, v, pages, i, page_table, start,
                           block_length=cfg.block_length, scale=hd ** -0.5)
        x = x + att @ lp["attn_out"].astype(dtype)
        h = rms(x, lp["mlp_norm"], cfg.norm_eps, dtype)
        y, n = feed_forward(lp, cfg, h.reshape(b * c, -1), flat_valid)
        x = x + y.reshape(b, c, -1)
        counts = counts + n
        ks.append(k)
        vs.append(v)
    return x, jnp.stack(ks, axis=2), jnp.stack(vs, axis=2), counts


def prefill_step(variables, cfg: SdarMoeConfig, tokens, true_len,
                 valid=None):
    """The whole blocks of a padded prompt batch. tokens [B, S]; `true_len`
    [B] is a multiple of the block length, so no real row sees a padded
    one; `valid` [B, S] marks the rows that are tokens (for the expert
    counters). A prefill yields no token: returns (None, k [B, S, L, KVH,
    D], v, counts); rows past true_len are garbage the caller must not
    cache."""
    b = tokens.shape[0]
    _, k, v, counts = _window_forward(
        unboxed_params(variables), cfg, tokens, jnp.zeros((b,), jnp.int32),
        None, None, valid)
    return None, k, v, counts


def chunk_step(variables, cfg: SdarMoeConfig, tokens, start, k_pages,
               v_pages, page_table, valid=None):
    """C tokens a sequence, whole blocks, against a paged cache that holds
    its first `start` positions (a multiple of the block length). Returns
    (None, k [B, C, L, KVH, D], v, counts)."""
    _, k, v, counts = _window_forward(
        unboxed_params(variables), cfg, tokens, start, (k_pages, v_pages),
        page_table, valid)
    return None, k, v, counts


def decode_step(variables, cfg: SdarMoeConfig, tokens, positions, k_pages,
                v_pages, page_table, valid=None):
    """One pass over one block a lane. tokens [B, block_length]: the
    block as it stands, the mask token where nothing is revealed yet;
    positions [B]: the block's first position (= positions the lane has
    cached); `valid` [B] marks the lanes that hold a sequence. Returns
    ((token, probability) of `choose` at every position, each [B,
    block_length]; k [B, block_length, L, KVH, D]; v; counts)."""
    p = unboxed_params(variables)
    rows = None if valid is None else jnp.broadcast_to(
        valid[:, None], tokens.shape)
    x, k, v, counts = _window_forward(
        p, cfg, tokens, positions, (k_pages, v_pages), page_table, rows)
    return block_choose(p, cfg, x), k, v, counts
