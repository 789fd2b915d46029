"""BERT-family bidirectional encoder, TPU-first.

Masked-LM pretraining complement to the causal decoders in gpt.py /
llama.py. Same conventions: bf16 activations over f32 params, logical-
axis annotations so every `parallel/` sharding strategy applies
unchanged, pluggable attention (dense by default; the pallas flash
kernel with causal=False on TPU). The MLM loss IS the fused LM-head
cross-entropy: non-masked positions carry `ignore_index` targets, so
`fused_cross_entropy(hidden, wte, mlm_targets)` scores exactly the
masked positions without a gather.

Reference parity: the reference ships no model zoo (encoders arrive via
its HF integrations, `python/ray/train/huggingface/`); this is the
native-Flax equivalent surface.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.gpt import Block
from ray_tpu.parallel.ring_attention import full_attention
from ray_tpu.parallel.sharding import logical_constraint


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30528       # padded to a multiple of 64
    n_layer: int = 12
    n_head: int = 12
    d_model: int = 768
    max_seq_len: int = 512
    type_vocab_size: int = 2      # segment A/B embeddings
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True

    @classmethod
    def bert_base(cls, **kw):
        return cls(n_layer=12, n_head=12, d_model=768, **kw)

    @classmethod
    def tiny(cls, **kw):
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq_len", 128)
        return cls(n_layer=2, n_head=2, d_model=64, **kw)

    def _gpt_view(self):
        """Blocks are shared with the decoder family — only the
        attention mask differs (supplied via attention_fn)."""
        from ray_tpu.models.gpt import GPTConfig

        return GPTConfig(
            vocab_size=self.vocab_size, n_layer=self.n_layer,
            n_head=self.n_head, d_model=self.d_model,
            max_seq_len=self.max_seq_len, dropout=self.dropout,
            dtype=self.dtype, param_dtype=self.param_dtype,
            remat=self.remat)


class BertEncoder(nn.Module):
    """Bidirectional encoder. `__call__` returns the final hidden states
    and the tied word embedding, ready for `fused_cross_entropy`
    (MLM) or downstream heads."""

    config: BertConfig
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, tokens, token_types=None,
                 deterministic: bool = True):
        cfg = self.config
        b, t = tokens.shape
        wte = self.param(
            "wte",
            nn.with_partitioning(nn.initializers.normal(0.02),
                                 ("vocab", "embed")),
            (cfg.vocab_size, cfg.d_model), cfg.param_dtype)
        wpe = self.param(
            "wpe",
            nn.with_partitioning(nn.initializers.normal(0.01),
                                 (None, "embed")),
            (cfg.max_seq_len, cfg.d_model), cfg.param_dtype)
        wtt = self.param(
            "wtt",
            nn.with_partitioning(nn.initializers.normal(0.01),
                                 (None, "embed")),
            (cfg.type_vocab_size, cfg.d_model), cfg.param_dtype)
        x = wte.astype(cfg.dtype)[tokens] + wpe.astype(cfg.dtype)[None, :t]
        if token_types is not None:
            x = x + wtt.astype(cfg.dtype)[token_types]
        x = logical_constraint(x, ("batch", "seq", "embed"))

        attend = self.attention_fn or partial(full_attention,
                                              causal=False)
        gcfg = cfg._gpt_view()
        block = Block
        if cfg.remat:
            block = nn.remat(Block, prevent_cse=False, static_argnums=(1,))
        for i in range(cfg.n_layer):
            x = block(gcfg, attend, name=f"h{i}")(x, deterministic)

        x = nn.LayerNorm(dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                         scale_init=nn.with_partitioning(
                             nn.initializers.ones, ("norm",)),
                         bias_init=nn.with_partitioning(
                             nn.initializers.zeros, ("norm",)),
                         name="ln_f")(x)
        return x, wte


def mlm_loss(encoder: BertEncoder, params, tokens, mlm_targets,
             token_types=None, ignore_index: int = -1,
             deterministic: bool = True, rngs=None):
    """Masked-LM objective: `mlm_targets` holds the original token at
    masked positions and `ignore_index` everywhere else — the fused
    cross-entropy scores only the masked positions. For dropout > 0
    training pass deterministic=False and rngs={"dropout": key}."""
    from ray_tpu.ops import fused_cross_entropy

    hidden, wte = encoder.apply(params, tokens, token_types,
                                deterministic=deterministic, rngs=rngs)
    return fused_cross_entropy(hidden, wte, mlm_targets,
                               ignore_index)


def mask_tokens(tokens, rng, *, mask_token_id: int,
                vocab_size: int, mask_prob: float = 0.15,
                ignore_index: int = -1):
    """BERT's 80/10/10 corruption: returns (corrupted, mlm_targets).
    Pure-jnp so it jits into the input pipeline or train step."""
    r_select, r_kind, r_rand = jax.random.split(rng, 3)
    selected = jax.random.uniform(r_select, tokens.shape) < mask_prob
    kind = jax.random.uniform(r_kind, tokens.shape)
    random_toks = jax.random.randint(r_rand, tokens.shape, 0, vocab_size)
    corrupted = jnp.where(
        selected & (kind < 0.8), mask_token_id,
        jnp.where(selected & (kind >= 0.9), random_toks, tokens))
    targets = jnp.where(selected, tokens, ignore_index)
    return corrupted, targets
