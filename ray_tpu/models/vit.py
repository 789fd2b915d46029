"""Vision Transformer (ViT) classifier, TPU-first.

Third model family of the native zoo (with `gpt.py` decoders and
`resnet.py` convnets). Patchify is a single strided conv (one big MXU
matmul per image), encoder blocks are pre-LN transformers with the same
logical-axis annotations as the LM families, so DP/FSDP/TP rules from
`parallel/sharding.py` apply unchanged.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax.numpy as jnp

from ray_tpu.parallel.sharding import logical_constraint


@dataclasses.dataclass(frozen=True)
class ViTConfig:
    image_size: int = 224
    patch_size: int = 16
    num_classes: int = 1000
    n_layer: int = 12
    n_head: int = 12
    d_model: int = 768
    mlp_mult: int = 4
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True

    @property
    def num_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    @classmethod
    def base_16(cls, **kw):  # ViT-B/16
        return cls(n_layer=12, n_head=12, d_model=768, **kw)

    @classmethod
    def tiny(cls, **kw):
        kw.setdefault("image_size", 32)
        kw.setdefault("patch_size", 8)
        kw.setdefault("num_classes", 10)
        return cls(n_layer=2, n_head=4, d_model=64, **kw)


def _dense(features, logical_axes, name, cfg):
    return nn.Dense(
        features, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
        kernel_init=nn.with_partitioning(
            nn.initializers.xavier_uniform(), logical_axes),
        bias_init=nn.with_partitioning(
            nn.initializers.zeros, (logical_axes[-1],)),
        name=name)


def _ln(cfg, name):
    return nn.LayerNorm(
        dtype=cfg.dtype, param_dtype=cfg.param_dtype,
        scale_init=nn.with_partitioning(nn.initializers.ones, ("norm",)),
        bias_init=nn.with_partitioning(nn.initializers.zeros, ("norm",)),
        name=name)


class EncoderBlock(nn.Module):
    config: ViTConfig

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        cfg = self.config
        hd = cfg.d_model // cfg.n_head
        h = _ln(cfg, "ln_1")(x)
        qkv = _dense(3 * cfg.d_model, ("embed", "qkv"), "attn_qkv",
                     cfg)(h)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        b, t = q.shape[0], q.shape[1]
        q = q.reshape(b, t, cfg.n_head, hd)
        k = k.reshape(b, t, cfg.n_head, hd)
        v = v.reshape(b, t, cfg.n_head, hd)
        # bidirectional attention (no mask) — straight MXU einsums
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
            jnp.asarray(hd, cfg.dtype))
        att = jnp.einsum(
            "bhqk,bkhd->bqhd",
            nn.softmax(scores.astype(jnp.float32)).astype(cfg.dtype),
            v).reshape(b, t, cfg.d_model)
        x = x + _dense(cfg.d_model, ("heads", "embed"), "attn_out",
                       cfg)(att)

        h = _ln(cfg, "ln_2")(x)
        h = _dense(cfg.mlp_mult * cfg.d_model, ("embed", "mlp"),
                   "mlp_up", cfg)(h)
        h = nn.gelu(h)
        h = _dense(cfg.d_model, ("mlp", "embed"), "mlp_down", cfg)(h)
        if cfg.dropout > 0:
            h = nn.Dropout(cfg.dropout)(h, deterministic=deterministic)
        x = x + h
        return logical_constraint(x, ("batch", "seq", "embed"))


class ViT(nn.Module):
    """images [B, H, W, C] -> class logits [B, num_classes]."""

    config: ViTConfig

    @nn.compact
    def __call__(self, images, deterministic: bool = True):
        cfg = self.config
        x = nn.Conv(
            cfg.d_model,
            kernel_size=(cfg.patch_size, cfg.patch_size),
            strides=(cfg.patch_size, cfg.patch_size),
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            kernel_init=nn.with_partitioning(
                nn.initializers.xavier_uniform(),
                (None, None, None, "embed")),
            name="patchify")(images.astype(cfg.dtype))
        b = x.shape[0]
        x = x.reshape(b, -1, cfg.d_model)  # [B, patches, D]

        cls_tok = self.param(
            "cls",
            nn.with_partitioning(nn.initializers.zeros, (None, "embed")),
            (1, cfg.d_model), cfg.param_dtype)
        x = jnp.concatenate(
            [jnp.broadcast_to(cls_tok.astype(cfg.dtype),
                              (b, 1, cfg.d_model)), x], axis=1)
        pos = self.param(
            "pos_embed",
            nn.with_partitioning(nn.initializers.normal(0.02),
                                 (None, "embed")),
            (cfg.num_patches + 1, cfg.d_model), cfg.param_dtype)
        x = x + pos.astype(cfg.dtype)[None]
        x = logical_constraint(x, ("batch", "seq", "embed"))

        block = EncoderBlock
        if cfg.remat:
            block = nn.remat(EncoderBlock, prevent_cse=False,
                             static_argnums=(1,))
        for i in range(cfg.n_layer):
            x = block(cfg, name=f"encoder{i}")(x, deterministic)

        x = _ln(cfg, "ln_f")(x)
        return _dense(cfg.num_classes, ("embed", "vocab"), "head",
                      cfg)(x[:, 0]).astype(jnp.float32)
