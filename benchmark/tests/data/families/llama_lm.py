"""A third family, added as files only: a language-model training job on
`models/llama.py`, which the shipped cells only serve. The configuration
`configs/tiny-llama-lm.json` names this file's builder, required-operations
function and reference; no file that was there knows of it."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

from benchmark.references import mistral
from benchmark.serve_cell import llama_engine
from ray_tpu.models.gpt import cross_entropy_loss


def job(config: dict, traffic: dict, devices) -> dict:
    model = llama_engine(config)["net"]
    batch, seq = traffic["batch"], traffic["seq_len"]
    tx = optax.adamw(3e-4)

    def loss_of(params, inputs, targets):
        return cross_entropy_loss(model.apply(params, inputs), targets)

    def make_carry(key):
        params = model.init(key, jnp.zeros((batch, seq), jnp.int32))
        return params, tx.init(params)

    def step(carry, data):
        params, opt_state = carry
        loss, grads = jax.value_and_grad(loss_of)(params, *data)
        updates, opt_state = tx.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state), loss

    return {"loss_of": loss_of, "step": step, "make_carry": make_carry,
            "block0_of": lambda tree: tree["params"]["layer0"],
            "mesh": None, "rules": None, "shardings": None,
            "place": lambda batch_np: batch_np, "batch": batch, "seq": seq}


def train_flops_per_token(config: dict, seq_len: int) -> float:
    d, layers = config["hidden_size"], config["num_hidden_layers"]
    heads, kv, hd = (config["num_attention_heads"],
                     config["num_key_value_heads"], config["head_dim"])
    matmul = layers * (d * (heads + 2 * kv) * hd + heads * hd * d
                       + 3 * d * config["intermediate_size"]) \
        + config["vocab_size"] * d
    return 6.0 * matmul + 3 * 2 * seq_len * heads * hd * layers


def loss_and_block0_grad_norm(params, config: dict, tokens, targets):
    """The plain reference's loss, and the norm of its gradient with respect
    to the first layer, by `jax.grad` of the reference itself."""
    def loss(layer0):
        p = {**params, "layer0": layer0}
        rows = jnp.stack([mistral.logits(p, config, t) for t in tokens])
        return cross_entropy_loss(rows, targets)

    value, grads = jax.value_and_grad(loss)(params["layer0"])
    return value, jnp.sqrt(sum(jnp.sum(jnp.square(g))
                               for g in jax.tree_util.tree_leaves(grads)))
