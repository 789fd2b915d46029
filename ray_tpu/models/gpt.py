"""GPT-2-family decoder-only transformer, TPU-first.

The north-star model (BASELINE.json: "JaxTrainer GPT-2-125M data-parallel").
Design notes:
- bfloat16 activations/params-compute, float32 master params via optimizer.
- Every parameter is annotated with logical axes (`nn.with_partitioning`),
  so DP/FSDP/TP shardings are a rules change, not a model change
  (ray_tpu/parallel/sharding.py maps them onto the mesh).
- Attention is pluggable: dense (XLA fuses to MXU-friendly blocks), ring
  (sequence sharded over `sp`, KV blocks rotating over ICI), or Ulysses.
- `remat` wraps each block so long-sequence training trades FLOPs for HBM.
- No data-dependent Python control flow: one jit-traced program.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.parallel.ring_attention import full_attention
from ray_tpu.parallel.sharding import logical_constraint


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304  # GPT-2 vocab padded to a multiple of 128 (MXU)
    n_layer: int = 12
    n_head: int = 12
    d_model: int = 768
    max_seq_len: int = 1024
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True

    @classmethod
    def gpt2_125m(cls, **kw):
        return cls(n_layer=12, n_head=12, d_model=768, **kw)

    @classmethod
    def gpt2_350m(cls, **kw):
        return cls(n_layer=24, n_head=16, d_model=1024, **kw)

    @classmethod
    def tiny(cls, **kw):
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq_len", 128)
        return cls(n_layer=2, n_head=2, d_model=64, **kw)


def dense(features, logical_axes, name, config, use_bias=True):
    return nn.Dense(
        features,
        use_bias=use_bias,
        dtype=config.dtype,
        param_dtype=config.param_dtype,
        kernel_init=nn.with_partitioning(
            nn.initializers.normal(stddev=0.02), logical_axes
        ),
        bias_init=nn.with_partitioning(
            nn.initializers.zeros, (logical_axes[-1],)
        ),
        name=name,
    )


def embedding_table(wte, dtype):
    """The tied table as the lookup and the head use it: cast, and under a
    mesh gathered over `embed` (the weight's own ZeRO gather, one for both
    uses), so the batch stays split through the lookup: a table split both
    ways is looked up for every sequence on every chip."""
    return logical_constraint(wte.astype(dtype), ("vocab", None))


class Block(nn.Module):
    """Pre-LN transformer block."""

    config: GPTConfig
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        cfg = self.config
        head_dim = cfg.d_model // cfg.n_head

        h = nn.LayerNorm(dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                         scale_init=nn.with_partitioning(
                             nn.initializers.ones, ("norm",)),
                         bias_init=nn.with_partitioning(
                             nn.initializers.zeros, ("norm",)),
                         name="ln_1")(x)
        qkv = dense(3 * cfg.d_model, ("embed", "qkv"), "attn_qkv", cfg)(h)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        b, t = q.shape[0], q.shape[1]
        q = q.reshape(b, t, cfg.n_head, head_dim)
        k = k.reshape(b, t, cfg.n_head, head_dim)
        v = v.reshape(b, t, cfg.n_head, head_dim)
        q = logical_constraint(q, ("batch", "seq", "heads", None))
        k = logical_constraint(k, ("batch", "seq", "heads", None))
        v = logical_constraint(v, ("batch", "seq", "heads", None))
        # decode-cache tap (serve.llm prefill); no-op unless the caller
        # passes mutable=["intermediates"]
        self.sow("intermediates", "kv_cache", (k, v))
        attend = self.attention_fn or partial(full_attention, causal=True)
        att = attend(q, k, v).reshape(b, t, cfg.d_model)
        att = dense(cfg.d_model, ("heads", "embed"), "attn_out", cfg)(att)
        x = x + att

        h = nn.LayerNorm(dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                         scale_init=nn.with_partitioning(
                             nn.initializers.ones, ("norm",)),
                         bias_init=nn.with_partitioning(
                             nn.initializers.zeros, ("norm",)),
                         name="ln_2")(x)
        h = dense(4 * cfg.d_model, ("embed", "mlp"), "mlp_up", cfg)(h)
        h = nn.gelu(h)
        h = dense(cfg.d_model, ("mlp", "embed"), "mlp_down", cfg)(h)
        if cfg.dropout > 0:
            h = nn.Dropout(cfg.dropout)(h, deterministic=deterministic)
        x = x + h
        return logical_constraint(x, ("batch", "seq", "embed"))


class GPT(nn.Module):
    """Decoder-only LM. `attention_fn` lets the trainer swap in ring/Ulysses
    attention bound to its mesh for sequence parallelism.

    `return_hidden=True` skips the LM head and returns
    `(hidden [B,T,D], wte [V,D])` for the memory-efficient chunked loss
    (`chunked_cross_entropy`) — the full [B,T,V] logits tensor
    (f32: 6 GiB at batch 32, seq 1024) never exists in HBM."""

    config: GPTConfig
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, tokens, deterministic: bool = True,
                 return_hidden: bool = False):
        cfg = self.config
        b, t = tokens.shape
        wte = self.param(
            "wte",
            nn.with_partitioning(nn.initializers.normal(0.02),
                                 ("vocab", "embed")),
            (cfg.vocab_size, cfg.d_model),
            cfg.param_dtype,
        )
        wpe = self.param(
            "wpe",
            nn.with_partitioning(nn.initializers.normal(0.01),
                                 (None, "embed")),
            (cfg.max_seq_len, cfg.d_model),
            cfg.param_dtype,
        )
        x = embedding_table(wte, cfg.dtype)[tokens] + \
            wpe.astype(cfg.dtype)[None, :t]
        x = logical_constraint(x, ("batch", "seq", "embed"))

        block = Block
        if cfg.remat:
            block = nn.remat(
                Block,
                prevent_cse=False,
                static_argnums=(1,),
            )
        for i in range(cfg.n_layer):
            x = block(cfg, self.attention_fn, name=f"h{i}")(x, deterministic)

        x = nn.LayerNorm(dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                         scale_init=nn.with_partitioning(
                             nn.initializers.ones, ("norm",)),
                         bias_init=nn.with_partitioning(
                             nn.initializers.zeros, ("norm",)),
                         name="ln_f")(x)
        if return_hidden:
            return x, wte
        # Tied LM head: logits = x @ wte^T (the vocab axis shards over tp).
        logits = jnp.einsum("btd,vd->btv", x,
                            embedding_table(wte, cfg.dtype))
        return logits


def cross_entropy_loss(logits, targets, ignore_index: int = -1):
    """Mean token NLL in float32 (stable softmax on bf16 logits)."""
    logits = logits.astype(jnp.float32)
    mask = (targets != ignore_index).astype(jnp.float32)
    targets = jnp.maximum(targets, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def chunked_cross_entropy(hidden, wte, targets, ignore_index: int = -1,
                          chunk_size: int = 128):
    """LM-head + token NLL computed blockwise over the sequence.

    A lax.scan keeps exactly one [B, chunk, V] logits block live (f32)
    instead of the whole [B, T, V] tensor — the dominant HBM temp of LM
    training (6N-param GPT-2 at batch 32 would need 6 GiB for it). Same
    math as `cross_entropy_loss(model.apply(...), targets)` on the full
    logits; backward rematerializes per chunk inside the scan.
    """
    B, T, D = hidden.shape
    n = T // chunk_size
    rem = T - n * chunk_size
    dtype = hidden.dtype
    wte_c = wte.astype(dtype)

    def block_nll(h_blk, t_blk):
        logits = jnp.einsum("bcd,vd->bcv", h_blk, wte_c)
        logits = logits.astype(jnp.float32)
        mask = (t_blk != ignore_index).astype(jnp.float32)
        tt = jnp.maximum(t_blk, 0)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, tt[..., None], axis=-1)[..., 0]
        return (nll * mask).sum(), mask.sum()

    total, count = jnp.asarray(0.0), jnp.asarray(0.0)
    if n:
        h = hidden[:, :n * chunk_size].reshape(B, n, chunk_size, D)
        t = targets[:, :n * chunk_size].reshape(B, n, chunk_size)

        def body(carry, xt):
            s, c = block_nll(*xt)
            return (carry[0] + s, carry[1] + c), None

        (total, count), _ = jax.lax.scan(
            body, (total, count),
            (h.transpose(1, 0, 2, 3), t.transpose(1, 0, 2)))
    if rem:  # sequence not divisible by chunk_size: one tail block
        s, c = block_nll(hidden[:, n * chunk_size:],
                         targets[:, n * chunk_size:])
        total, count = total + s, count + c
    return total / jnp.maximum(count, 1.0)


# -- decode path (serve.llm) ----------------------------------------------
# Same two-function split as `llama.py` (see the note there): prefill is
# the flax module itself (kv sown per block), decode is a pure paged
# single-token forward sharing `paged_attend` with Llama.
#
# `layers` is imported here, below the training forward, and `dense` stayed in
# this file (public, where `_dense` stood, line for line): a Pallas kernel's
# serialized body carries the file and LINE of every Python frame that reaches
# it (`Block.__call__`, `GPT.__call__`), that body is part of the training
# step's text and so of its compile-cache key, and a line more or fewer above
# those call sites gives `gpt2-medium.pretrain` (the cell with the flash
# kernel) a new step to compile (PERF.md §7, PR 52).

from ray_tpu.models.layers import last_row, unboxed_params  # noqa: E402

def _ln(x, scale, bias, dtype, eps=1e-6):
    # mirrors flax LayerNorm (f32 stats, fast-variance, eps 1e-6)
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    mean2 = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    var = jnp.maximum(0.0, mean2 - jnp.square(mean))
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    y = y * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return y.astype(dtype)


def prefill_step(variables, cfg: GPTConfig, tokens, true_len):
    """Full forward over a padded prompt batch; returns
    (next_logits [B, V], k [B, S, L, H, D], v [B, S, L, H, D])."""
    model = GPT(dataclasses.replace(cfg, remat=False))
    logits, state = model.apply(variables, tokens,
                                mutable=["intermediates"])
    inter = state["intermediates"]
    k = jnp.stack([inter[f"h{i}"]["kv_cache"][0][0]
                   for i in range(cfg.n_layer)], axis=2)
    v = jnp.stack([inter[f"h{i}"]["kv_cache"][0][1]
                   for i in range(cfg.n_layer)], axis=2)
    return last_row(logits, true_len), k, v


def decode_key_walk(cfg, positions, n_pages: int, page: int, xp=jnp):
    """`llama.key_block_walk` of a group of one: this family's decode step
    walks the cached keys with `llama.paged_attend` too."""
    from ray_tpu.models.llama import key_block_walk  # import cycle

    return key_block_walk(positions, n_pages, page, 1, xp)


def decode_step(variables, cfg: GPTConfig, tokens, positions,
                k_pages, v_pages, page_table):
    """Single-token decode over a paged KV cache (MHA: kv heads ==
    query heads, a group of one). Shapes as in `llama.decode_step`: the
    token's own key, then the cached pages a key block at a time."""
    from ray_tpu.models.llama import paged_attend  # avoids import cycle

    p = unboxed_params(variables)
    dtype = cfg.dtype
    hd = cfg.d_model // cfg.n_head
    b = tokens.shape[0]
    wte = p["wte"].astype(dtype)
    x = wte[tokens] + p["wpe"].astype(dtype)[positions]
    scale = hd ** -0.5
    new_ks, new_vs = [], []
    for i in range(cfg.n_layer):
        lp = p[f"h{i}"]
        h = _ln(x, lp["ln_1"]["scale"], lp["ln_1"]["bias"], dtype)
        qkv = h @ lp["attn_qkv"]["kernel"].astype(dtype) + \
            lp["attn_qkv"]["bias"].astype(dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, cfg.n_head, hd)
        k = k.reshape(b, cfg.n_head, hd)
        v = v.reshape(b, cfg.n_head, hd)
        att = paged_attend(q, k, v, k_pages, v_pages, i, page_table,
                           positions, scale)
        att = att.reshape(b, cfg.d_model) @ \
            lp["attn_out"]["kernel"].astype(dtype) + \
            lp["attn_out"]["bias"].astype(dtype)
        x = x + att
        h = _ln(x, lp["ln_2"]["scale"], lp["ln_2"]["bias"], dtype)
        h = h @ lp["mlp_up"]["kernel"].astype(dtype) + \
            lp["mlp_up"]["bias"].astype(dtype)
        h = nn.gelu(h)
        h = h @ lp["mlp_down"]["kernel"].astype(dtype) + \
            lp["mlp_down"]["bias"].astype(dtype)
        x = x + h
        new_ks.append(k)
        new_vs.append(v)
    x = _ln(x, p["ln_f"]["scale"], p["ln_f"]["bias"], dtype)
    logits = jnp.einsum("bd,vd->bv", x, wte)
    return logits, jnp.stack(new_ks, axis=1), jnp.stack(new_vs, axis=1)


def chunk_step(variables, cfg: GPTConfig, tokens, start,
               k_pages, v_pages, page_table):
    """Forward C tokens per sequence against a paged cache (chunked
    prefill, a prefix-cache suffix). Shapes as in `llama.chunk_step`."""
    from ray_tpu.models.llama import (  # avoids import cycle
        chunk_valid_mask, paged_attend_chunk)

    p = unboxed_params(variables)
    dtype = cfg.dtype
    hd = cfg.d_model // cfg.n_head
    b, c = tokens.shape
    block = k_pages.shape[2]
    t_max = page_table.shape[1] * block
    wte = p["wte"].astype(dtype)
    positions = jnp.minimum(start[:, None] + jnp.arange(c)[None, :],
                            cfg.max_seq_len - 1)
    x = wte[tokens] + p["wpe"].astype(dtype)[positions]
    scale = hd ** -0.5
    valid = chunk_valid_mask(start, positions, c, t_max)
    new_ks, new_vs = [], []
    for i in range(cfg.n_layer):
        lp = p[f"h{i}"]
        h = _ln(x, lp["ln_1"]["scale"], lp["ln_1"]["bias"], dtype)
        qkv = h @ lp["attn_qkv"]["kernel"].astype(dtype) + \
            lp["attn_qkv"]["bias"].astype(dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, c, cfg.n_head, hd)
        k = k.reshape(b, c, cfg.n_head, hd)
        v = v.reshape(b, c, cfg.n_head, hd)
        att = paged_attend_chunk(q, k, v, k_pages[:, i], v_pages[:, i],
                                 page_table, valid, scale)
        att = att.reshape(b, c, cfg.d_model) @ \
            lp["attn_out"]["kernel"].astype(dtype) + \
            lp["attn_out"]["bias"].astype(dtype)
        x = x + att
        h = _ln(x, lp["ln_2"]["scale"], lp["ln_2"]["bias"], dtype)
        h = h @ lp["mlp_up"]["kernel"].astype(dtype) + \
            lp["mlp_up"]["bias"].astype(dtype)
        h = nn.gelu(h)
        h = h @ lp["mlp_down"]["kernel"].astype(dtype) + \
            lp["mlp_down"]["bias"].astype(dtype)
        x = x + h
        new_ks.append(k)
        new_vs.append(v)
    x = _ln(x, p["ln_f"]["scale"], p["ln_f"]["bias"], dtype)
    logits = jnp.einsum("bcd,vd->bcv", x, wte)
    return logits, jnp.stack(new_ks, axis=2), jnp.stack(new_vs, axis=2)


def count_params(params) -> int:
    return sum(int(np.prod(p.shape))
               for p in jax.tree_util.tree_leaves(params))


def flops_per_token(cfg: GPTConfig, seq_len: int | None = None) -> float:
    """Approximate training FLOPs per token (6N + attention term)."""
    t = seq_len or cfg.max_seq_len
    n_params = (
        cfg.vocab_size * cfg.d_model
        + cfg.max_seq_len * cfg.d_model
        + cfg.n_layer * (12 * cfg.d_model**2 + 13 * cfg.d_model)
        + 2 * cfg.d_model
    )
    return 6.0 * n_params + 12.0 * cfg.n_layer * cfg.d_model * t
