"""Mixture-of-Experts decoder LM (Switch/Mixtral-style), TPU-first.

Fourth model family of the native zoo: the GPT decoder with the dense
MLP replaced by a top-1-routed expert layer. Unlike
`parallel/moe.py` (explicit shard_map + all_to_all, for when you want
manual control), this model expresses MoE the GSPMD way: experts are a
leading parameter axis annotated with the "expert" logical axis, routing
is static-shape einsum dispatch, and pjit's sharding rules place experts
over the `ep` mesh axis — XLA inserts the all_to_alls.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.gpt import GPTConfig, dense
from ray_tpu.parallel.ring_attention import full_attention
from ray_tpu.parallel.sharding import logical_constraint


@dataclasses.dataclass(frozen=True)
class MoEGPTConfig(GPTConfig):
    num_experts: int = 8
    capacity_factor: float = 1.25
    router_aux_coeff: float = 0.01

    @classmethod
    def tiny(cls, **kw):
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq_len", 128)
        kw.setdefault("num_experts", 4)
        return cls(n_layer=2, n_head=2, d_model=64, **kw)


class MoEMLP(nn.Module):
    """Top-1 routed expert MLP over flattened [tokens, d] activations.

    Static shapes throughout: per-expert capacity buffers of
    C = ceil(capacity_factor * T / E) tokens; overflow tokens pass
    through the residual untouched (Switch Transformer semantics).
    Router aux loss lands in the "moe_aux_loss" collection — pull it via
    `mutable=["moe_aux_loss"]` and add to the task loss.
    """

    config: MoEGPTConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        b, t, d = x.shape
        tokens = b * t
        E = cfg.num_experts
        C = max(1, int(cfg.capacity_factor * tokens / E))
        flat = x.reshape(tokens, d)

        router_w = self.param(
            "router",
            nn.with_partitioning(nn.initializers.normal(0.02),
                                 ("embed", None)),
            (d, E), cfg.param_dtype)
        # route in float32 — bf16 softmax ties break routing determinism
        logits = (flat.astype(jnp.float32)
                  @ router_w.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        expert = jnp.argmax(probs, axis=-1)
        gate = jnp.take_along_axis(probs, expert[:, None], axis=-1)[:, 0]

        onehot = jax.nn.one_hot(expert, E, dtype=jnp.int32)
        position = jnp.cumsum(onehot, axis=0) * onehot - 1
        keep = (position >= 0) & (position < C)
        pos_c = jnp.clip(position, 0, C - 1)
        dispatch = (jax.nn.one_hot(pos_c, C, dtype=cfg.dtype)
                    * keep.astype(cfg.dtype)[..., None])  # [T, E, C]
        combine = dispatch * gate.astype(cfg.dtype)[:, None, None]

        # expert params: leading E axis sharded over the ep mesh axis
        w_up = self.param(
            "experts_up",
            nn.with_partitioning(nn.initializers.normal(0.02),
                                 ("expert", "embed", "mlp")),
            (E, d, 4 * d), cfg.param_dtype)
        w_down = self.param(
            "experts_down",
            nn.with_partitioning(nn.initializers.normal(0.02),
                                 ("expert", "mlp", "embed")),
            (E, 4 * d, d), cfg.param_dtype)

        # dispatch -> [E, C, d] buffers; GSPMD turns the einsum over the
        # sharded E axis into an all_to_all over ep
        buf = jnp.einsum("td,tec->ecd", flat, dispatch)
        h = jnp.einsum("ecd,edf->ecf", buf, w_up.astype(cfg.dtype))
        h = nn.gelu(h)
        h = jnp.einsum("ecf,efd->ecd", h, w_down.astype(cfg.dtype))
        out = jnp.einsum("ecd,tec->td", h, combine)

        # Switch load-balancing loss
        density = onehot.astype(jnp.float32).mean(axis=0)
        density_proxy = probs.mean(axis=0)
        aux = jnp.sum(density * density_proxy) * E
        self.sow("moe_aux_loss", "aux", cfg.router_aux_coeff * aux)
        return out.reshape(b, t, d)


class MoEBlock(nn.Module):
    config: MoEGPTConfig
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        cfg = self.config
        head_dim = cfg.d_model // cfg.n_head
        h = nn.LayerNorm(dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                         name="ln_1")(x)
        qkv = dense(3 * cfg.d_model, ("embed", "qkv"), "attn_qkv",
                     cfg)(h)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        b, t = q.shape[0], q.shape[1]
        q = q.reshape(b, t, cfg.n_head, head_dim)
        k = k.reshape(b, t, cfg.n_head, head_dim)
        v = v.reshape(b, t, cfg.n_head, head_dim)
        attend = self.attention_fn or partial(full_attention, causal=True)
        att = attend(q, k, v).reshape(b, t, cfg.d_model)
        x = x + dense(cfg.d_model, ("heads", "embed"), "attn_out",
                       cfg)(att)
        h = nn.LayerNorm(dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                         name="ln_2")(x)
        x = x + MoEMLP(cfg, name="moe")(h)
        return logical_constraint(x, ("batch", "seq", "embed"))


class MoEGPT(nn.Module):
    config: MoEGPTConfig
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, tokens, deterministic: bool = True):
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
        x = wte.astype(cfg.dtype)[tokens] + wpe.astype(cfg.dtype)[None, :t]

        block = MoEBlock
        if cfg.remat:
            block = nn.remat(MoEBlock, prevent_cse=False,
                             static_argnums=(1,))
        for i in range(cfg.n_layer):
            x = block(cfg, self.attention_fn, name=f"h{i}")(
                x, deterministic)

        x = nn.LayerNorm(dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                         name="ln_f")(x)
        return jnp.einsum("btd,vd->btv", x, wte.astype(cfg.dtype))


def total_aux_loss(aux_vars) -> jax.Array:
    """Sum the per-layer router losses sown into `moe_aux_loss`."""
    leaves = jax.tree_util.tree_leaves(aux_vars.get("moe_aux_loss", {}))
    if not leaves:
        return jnp.asarray(0.0)
    return sum(jnp.sum(l) for l in leaves)
