"""Ouro family decoder (ByteDance's looped language model): one stack of
layers run `n_pass` times a token, the same weights in every pass. A layer is
attention with rotary embeddings and a SwiGLU, each between two RMSNorms
(before it, and on its output before the residual add); the one final norm
closes every pass, and what it gives is what the next pass starts from; an
exit gate reads each pass's result; an untied head. Serving only: the three
step functions the paged engine calls, and a flax module that exists to make
the weights.

What it asks of the system that no other family does:

- A token leaves K and V in `n_pass * n_layer` PAGE LAYERS while the weights
  have `n_layer` (`paged_layers`): pass t of weight layer l reads and writes
  page layer `t * n_layer + l` and no other. The cache manager, the admission
  arithmetic and the counters see the page layers; the weights are read
  `n_pass` times a step.
- The passes are a ROLLED loop (`lax.scan` over the pass index, the page
  layer computed from it; `llama.paged_attend` takes the layer as an
  operand): a step's program holds one pass's layers, not `n_pass` copies of
  them. Unrolled, the nine programs of the benchmark's cell compile in four
  times the seconds, hold half as much again in temporaries and decode no
  faster (PERF.md §6, PR 48).
- The exit gate: after pass t, `g_t = sigmoid(h w_e + b_e)`; a token would
  answer from pass t with `p_t = g_t * prod_{j<t}(1 - g_j)`, the last pass
  taking the rest. The steps answer from the last pass for every token, which
  is `exit_threshold` 1.0 (the published value); the gate is computed and
  counted and does not reach the logits. Any other threshold is refused: a
  pass count a lane is the scheduler's work (ROADMAP R26).

From `layers.py`: `rms`, the rotation, `swiglu`, the weights' declaration.
Shared with `llama.py`: `paged_attend` (the decode step's walk over cached
key blocks) and `paged_attend_chunk`.

Parameters: `wte`, `layer<i>/{attn_norm, attn_qkv, attn_out, post_attn_norm,
mlp_norm, mlp_gate_up, mlp_down, post_mlp_norm}`, `final_norm`, `exit_gate`,
`exit_bias`, `lm_head` ([q | k | v] and [gate | up] along the last axis).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.linen.initializers import constant, ones

from ray_tpu.models.layers import (A_HEAD, declare_weights, last_row, rms,
                                   rope, swiglu, unboxed_params)
# `decode_key_walk` by its own name: the engine asks a family's module for it
# and counts `decode_attn_key_slots` on the host with the program's function
from ray_tpu.models.llama import (chunk_valid_mask, decode_key_walk,
                                  paged_attend, paged_attend_chunk,
                                  rope_tables)
from ray_tpu.parallel.ring_attention import full_attention

# what each step returns after the cache rows, an int32 vector: the engine
# adds it to `decode_<name>` / `prefill_<name>`. Over the rows that are
# tokens: `layer_passes` the layer applications (`n_pass * n_layer` a token),
# `exit_pass_milli` 1,000 x the gate's expected exit pass `sum_t (t+1) p_t`.
# (The key slots a decode step scores are the host's `decode_attn_key_slots`,
# counted over the arena's layers with `decode_key_walk`.)
STEP_COUNTS = ("layer_passes", "exit_pass_milli")


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    vocab_size: int = 49152
    n_layer: int = 48
    n_pass: int = 4                 # `total_ut_steps`
    exit_threshold: float = 1.0     # `early_exit_threshold`
    n_head: int = 16
    n_kv_head: int = 16
    d_model: int = 2048
    ffn_dim: int = 5632
    max_seq_len: int = 65536
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.exit_threshold != 1.0:
            raise ValueError(
                f"exit_threshold={self.exit_threshold}: the steps answer "
                f"from the last pass for every token, which is the published "
                f"1.0; an exit before it makes a lane's pass count data, "
                f"which the scheduler does not handle (ROADMAP R26)")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=512, n_layer=3, n_pass=2, n_head=4,
                    n_kv_head=4, d_model=64, ffn_dim=128, max_seq_len=128)
        base.update(kw)
        return cls(**base)


def paged_layers(cfg: OuroConfig) -> int:
    """Layers of the paged arena: one a pass a weight layer."""
    return cfg.n_pass * cfg.n_layer


# -- the weights --------------------------------------------------------------
#
# Seeded weights stand in for trained ones, and the check that compares the
# served tokens with the reference is only as good as the function they make:
# it has to depend on the context and on the pass (or a program that reads
# another pass's rows, or runs a pass too few, answers as the sound one does)
# without amplifying bf16 rounding past what separates the two (PERF.md §6,
# PR 48). Two things decide that here:
#
# - Every value is DRAWN IN FLOAT32 and then rounded to the parameter's type.
#   `jax.random.normal` in bfloat16 takes 128 distinct values with a mean of
#   -0.0117 deviations: every matrix then maps the all-ones direction onto
#   itself, 384 sub-layers a token add that one direction up, and the stream
#   of every token of every prompt ends as the same vector (the reference's
#   logit rows of two unrelated prompts equal to three digits, on the chip).
# - The stream starts at rms 1 (the embedding's deviation) and the gains of
#   the norms on a sub-layer's OUTPUT are `OUTPUT_GAINS` x `(2 * n_layer) **
#   -0.5`, the attention's and the feed-forward's: at 1 and 1 a pass's 2 *
#   n_layer updates together weigh what the stream does. The two pull
#   opposite ways. A soft attention hands every row of a sequence nearly the
#   same average, which damps whatever differs between rows (rounding too)
#   and ends with the rows all but equal: greedy decoding then repeats one
#   token at one margin, and a check of several tokens is one trial. The
#   feed-forward works on a row alone and amplifies differences. The gains
#   are set where the rows stay apart (their cosine about 0.5), 8-bit weights
#   and a loop fault move the logits by a third of their rms and more, and
#   bf16 rounding by a few hundredths (PERF.md §6, PR 48: the readings of the
#   pairs tried). (Gains of 1 throughout with an embedding of 0.02 had each
#   sub-layer add a vector of unit rms to a stream of 0.02, and amplified the
#   rounding a hundredfold.) The other matrices are normal with deviation
#   0.02: scores of deviation 0.8 at d 2,048 (sharper scores amplify the
#   rounding of q and k faster than they add dependence on the context).

OUTPUT_GAINS = (2.5, 4.0)       # (after the attention, after the feed-forward)


def _normal(dev):
    def init(key, shape, dtype):
        return (dev * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    return init


def layer_shapes(cfg: OuroConfig) -> dict:
    """name -> (shape, initializer) of a layer's parameters."""
    d, hd = cfg.d_model, cfg.head_dim
    after_attn, after_mlp = (constant(g * (2 * cfg.n_layer) ** -0.5)
                             for g in OUTPUT_GAINS)
    return {
        "attn_norm": ((d,), ones),
        "attn_qkv": ((d, (cfg.n_head + 2 * cfg.n_kv_head) * hd),
                     _normal(0.02)),
        "attn_out": ((cfg.n_head * hd, d), _normal(0.02)),
        "post_attn_norm": ((d,), after_attn),
        "mlp_norm": ((d,), ones),
        "mlp_gate_up": ((d, 2 * cfg.ffn_dim), _normal(0.02)),
        "mlp_down": ((cfg.ffn_dim, d), _normal(0.02)),
        "post_mlp_norm": ((d,), after_mlp),
    }


def top_shapes(cfg: OuroConfig) -> dict:
    d = cfg.d_model
    return {"wte": ((cfg.vocab_size, d), _normal(1.0)),
            "final_norm": ((d,), ones),
            "exit_gate": ((d, 1), _normal(0.02)),
            "exit_bias": ((1,), _normal(0.02)),
            "lm_head": ((d, cfg.vocab_size), _normal(0.02))}


class Ouro(nn.Module):
    """`net.init` makes the weights; `apply` is the full causal forward (no
    cache), tokens [B, T] -> logits [B, T, V]."""
    config: OuroConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.config
        p = declare_weights(top_shapes(cfg),
                            [layer_shapes(cfg)] * cfg.n_layer,
                            cfg.param_dtype)
        b, t = tokens.shape
        x, _, _ = _loop_forward(
            p, cfg, tokens, jnp.broadcast_to(jnp.arange(t), (b, t)),
            _attend_prefill, None)
        return _head(p, cfg, x)


# -- the loop -----------------------------------------------------------------

def exit_distribution(gates):
    """gates [T, ...] (g_t of every pass; the last one's is not read) ->
    p [T, ...], float32: `p_t = g_t * prod_{j<t}(1 - g_j)` for t < T - 1 and
    what is left for the last pass, so that p sums to 1 over the passes."""
    stay = jnp.cumprod(1.0 - gates[:-1], axis=0)
    before = jnp.concatenate([jnp.ones_like(gates[:1]), stay])  # not out yet
    return jnp.concatenate([gates[:-1] * before[:-1], before[-1:]])


def _attend_prefill(q, k, v, layer):
    return full_attention(q, k, v, causal=True)


def _loop_forward(p, cfg: OuroConfig, tokens, positions, attend, valid_rows):
    """C tokens a sequence at `positions` [B, C] through `n_pass` passes of
    the stack. `attend(q [B, C, H, D], k, v [B, C, KVH, D], page_layer)` is
    the step's attention of one page layer over what the sequence has
    cached and the step's own keys. Returns (the last pass's normed
    result [B, C, d]; the step's K and V rows, each [B, C, n_pass * n_layer,
    KVH, D] in page-layer order; counts as `STEP_COUNTS`)."""
    dtype, hd, eps = cfg.dtype, cfg.head_dim, cfg.norm_eps
    b, c = tokens.shape
    n_q, n_kv = cfg.n_head * hd, cfg.n_kv_head * hd
    # the residual stream is float32 from the embedding to the head: every
    # sub-layer adds a vector of unit rms (its norm's output) to a sum that
    # reaches rms 10 by a pass's end, and 192 bf16 roundings of that sum were
    # amplified, on one seed in fifty, to a third of the logits' rms (PERF.md
    # §6, PR 48). The matrix products read and write `dtype`
    x = p["wte"][tokens].astype(jnp.float32)
    cos_t, sin_t = rope_tables(cfg.max_seq_len, hd, cfg.rope_theta)
    # a pad row's position may lie past the table: its output is garbage by
    # contract, the clamp keeps the gather in bounds
    at = jnp.minimum(positions, cfg.max_seq_len - 1)
    cos, sin = cos_t[at], sin_t[at]
    gate_w = p["exit_gate"].astype(jnp.float32)[:, 0]
    gate_b = p["exit_bias"].astype(jnp.float32)[0]

    def one_pass(x, t):
        ks, vs = [], []
        with jax.named_scope("loop_pass"):
            for i in range(cfg.n_layer):
                lp = p[f"layer{i}"]
                h = rms(x, lp["attn_norm"], eps, dtype)
                q, k, v = jnp.split(h @ lp["attn_qkv"].astype(dtype),
                                    [n_q, n_q + n_kv], axis=-1)
                q = rope(q.reshape(b, c, cfg.n_head, hd), cos, sin, A_HEAD)
                k = rope(k.reshape(b, c, cfg.n_kv_head, hd), cos, sin,
                         A_HEAD)
                v = v.reshape(b, c, cfg.n_kv_head, hd)
                with jax.named_scope("attn_full"):
                    att = attend(q, k, v, t * cfg.n_layer + i)
                att = att.reshape(b, c, n_q).astype(dtype)
                x = x + rms(att @ lp["attn_out"].astype(dtype),
                             lp["post_attn_norm"], eps, jnp.float32)
                h = rms(x, lp["mlp_norm"], eps, dtype)
                x = x + rms(swiglu(h, lp["mlp_gate_up"], lp["mlp_down"],
                                     dtype), lp["post_mlp_norm"], eps,
                             jnp.float32)
                ks.append(k)
                vs.append(v)
            x = rms(x, p["final_norm"], eps, jnp.float32)
            with jax.named_scope("exit_gate"):
                gate = jax.nn.sigmoid(x @ gate_w + gate_b)
        return x, (gate, jnp.stack(ks, axis=2), jnp.stack(vs, axis=2))

    # a pass's rows [B, C, n_layer, KVH, D], stacked over the passes: on the
    # TPU the compiler lays the stack out pass-inside-position, so the move
    # to page-layer order below is no copy (compiled for a described v5e)
    x, (gates, k_rows, v_rows) = jax.lax.scan(
        one_pass, x, jnp.arange(cfg.n_pass))
    k_rows, v_rows = (
        jnp.moveaxis(r, 0, 2).reshape(b, c, -1, cfg.n_kv_head, hd)
        for r in (k_rows, v_rows))
    passes = jnp.arange(1, cfg.n_pass + 1, dtype=jnp.float32)
    expected = jnp.tensordot(passes, exit_distribution(gates), axes=1)
    if valid_rows is None:
        valid_rows = jnp.ones((b, c), bool)
    n_valid = jnp.sum(valid_rows.astype(jnp.int32))
    counts = jnp.stack([
        n_valid * paged_layers(cfg),
        jnp.round(1000.0 * jnp.sum(jnp.where(valid_rows, expected, 0.0))
                  ).astype(jnp.int32)]).astype(jnp.int32)
    return x, (k_rows, v_rows), counts


def _head(p, cfg: OuroConfig, x):
    """The final norm closed the last pass: the head reads its result."""
    with jax.named_scope("lm_head"):
        return x.astype(cfg.dtype) @ p["lm_head"].astype(cfg.dtype)


# -- the three steps ----------------------------------------------------------

def prefill_step(variables, cfg: OuroConfig, tokens, true_len, valid=None):
    """Full forward over a padded prompt batch. tokens [B, S]; true_len
    [B]; `valid` [B, S] marks the rows that are tokens (for the counters;
    None counts every row). Returns (next_logits [B, V], k and v [B, S,
    n_pass * n_layer, KVH, D], counts); rows past true_len are garbage the
    caller must not cache."""
    p = unboxed_params(variables)
    b, s = tokens.shape
    x, rows, counts = _loop_forward(
        p, cfg, tokens, jnp.broadcast_to(jnp.arange(s), (b, s)),
        _attend_prefill, valid)
    return (_head(p, cfg, last_row(x, true_len)), *rows, counts)


def chunk_step(variables, cfg: OuroConfig, tokens, start, k_pages, v_pages,
               page_table, valid=None):
    """C tokens a sequence against a paged cache that holds its first
    `start` positions in every page layer. tokens [B, C]; start [B];
    k_pages/v_pages [P, n_pass * n_layer, block, KVH, D]; page_table
    [B, n_pages]. Returns (logits [B, C, V], k and v [B, C, n_pass *
    n_layer, KVH, D], counts)."""
    p = unboxed_params(variables)
    c = tokens.shape[1]
    t_max = page_table.shape[1] * k_pages.shape[2]
    positions = start[:, None] + jnp.arange(c)[None, :]
    seen = chunk_valid_mask(start, positions, c, t_max)
    scale = cfg.head_dim ** -0.5

    # the sequences' own pages of one page layer, gathered by (page, layer)
    # as `paged_attend` gathers a key block: a slice of the arena by a
    # traced layer (`k_pages[:, layer]`) has the TPU compiler copy the whole
    # arena to a layer-major layout (two copies of 3.9 GB at the cell's
    # widths, compiled for a described v5e, PR 48)
    b, n_pages = page_table.shape
    own = jnp.arange(b * n_pages).reshape(b, n_pages)

    def attend(q, k, v, layer):
        k_own, v_own = (pages[page_table, layer].reshape(
            (b * n_pages,) + pages.shape[2:]) for pages in (k_pages, v_pages))
        return paged_attend_chunk(q, k, v, k_own, v_own, own, seen, scale)

    x, rows, counts = _loop_forward(p, cfg, tokens, positions, attend, valid)
    return (_head(p, cfg, x), *rows, counts)


def decode_step(variables, cfg: OuroConfig, tokens, positions, k_pages,
                v_pages, page_table, valid=None):
    """One token a sequence on a paged cache. tokens [B]; positions [B] (=
    tokens already cached); `valid` [B] marks the lanes that hold a
    sequence. What pass t of layer l reads of the cache is page layer `t *
    n_layer + l`'s rows of the key blocks `llama.paged_attend` walks.
    Returns (logits [B, V], k and v [B, n_pass * n_layer, KVH, D], counts)."""
    p = unboxed_params(variables)
    scale = cfg.head_dim ** -0.5

    def attend(q, k, v, layer):
        return paged_attend(q[:, 0], k[:, 0], v[:, 0], k_pages, v_pages,
                            layer, page_table, positions, scale)

    x, rows, counts = _loop_forward(
        p, cfg, tokens[:, None], positions[:, None], attend,
        None if valid is None else valid[:, None])
    return (_head(p, cfg, x[:, 0]), *[r[:, 0] for r in rows], counts)
