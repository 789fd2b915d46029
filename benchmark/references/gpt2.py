"""GPT-2 in plain float32 `jax.numpy`: no kernel, no fused loss, no sharding.

Follows Radford et al. 2019 and the `gpt2-*` `config.json` files: learned
position embeddings, pre-LayerNorm blocks, full causal softmax attention,
a 4x MLP with the tanh GELU (`gelu_new`), a final LayerNorm, the head tied to
the embedding. Departures, each because the program has no field for the
published value (see the configuration file's `assumed`): the LayerNorm
epsilon comes from the configuration file as run, and the softmax runs over
every embedding row the program keeps (`vocab_rows`), padding rows included.

Parameters are read in the program's own layout (a plain dict: `wte`, `wpe`,
`h<i>/{ln_1,attn_qkv,attn_out,ln_2,mlp_up,mlp_down}`, `ln_f`). Call under
`jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _dense(x, p):
    return x @ p["kernel"] + p["bias"]


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def block(x, p, n_head, eps):
    b, t, d = x.shape
    hd = d // n_head
    h = _layer_norm(x, p["ln_1"], eps)
    q, k, v = jnp.split(_dense(h, p["attn_qkv"]), 3, axis=-1)
    q, k, v = (a.reshape(b, t, n_head, hd) for a in (q, k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(hd))
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + _dense(att.reshape(b, t, d), p["attn_out"])
    h = _layer_norm(x, p["ln_2"], eps)
    return x + _dense(_gelu_new(_dense(h, p["mlp_up"])), p["mlp_down"])


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


# One small program per piece, every block through the same one, so that the
# persistent compile cache holds a few megabytes and not one unrolled model.
_block = jax.jit(block, static_argnums=(2, 3))


@jax.jit
def _embed(wte, wpe, tokens):
    return wte[tokens] + wpe[None, :tokens.shape[1]]


def _head_logits(x, ln_f, wte, eps):
    return _layer_norm(x, ln_f, eps) @ wte.T


def _head_loss(x, ln_f, wte, eps, targets):
    logp = jax.nn.log_softmax(_head_logits(x, ln_f, wte, eps), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


_block_vjp_x = jax.jit(
    lambda x, p, g, n_head, eps: jax.vjp(
        lambda a: block(a, p, n_head, eps), x)[1](g)[0],
    static_argnums=(3, 4))
_block_vjp_p = jax.jit(
    lambda x, p, g, n_head, eps: jax.vjp(
        lambda q: block(x, q, n_head, eps), p)[1](g)[0],
    static_argnums=(3, 4))
_head_loss_and_grad = jax.jit(jax.value_and_grad(_head_loss),
                              static_argnums=(3,))
_head_logits_jit = jax.jit(_head_logits, static_argnums=(3,))


def _hidden_states(p, config, tokens):
    """The input of every block, and the output of the last."""
    xs = [_embed(p["wte"], p["wpe"], tokens)]
    for i in range(config["n_layer"]):
        xs.append(_block(xs[-1], p[f"h{i}"], config["n_head"],
                         config["layer_norm_epsilon"]))
    return xs


def logits(params, config: dict, tokens):
    """tokens [B, T] int -> logits [B, T, rows] float32."""
    p = _f32(params)
    return _head_logits_jit(_hidden_states(p, config, tokens)[-1],
                            p["ln_f"], p["wte"],
                            config["layer_norm_epsilon"])


def loss_and_block0_grad_norm(params, config: dict, tokens, targets):
    """The mean token loss, and the norm of its gradient with respect to the
    first block's parameters: the gradient that has passed through every
    other block on its way down. Backpropagation is written out block by
    block (the chain rule, nothing else), so only one block is ever
    differentiated at a time."""
    p = _f32(params)
    eps, n_head = config["layer_norm_epsilon"], config["n_head"]
    xs = _hidden_states(p, config, tokens)
    value, g = _head_loss_and_grad(xs[-1], p["ln_f"], p["wte"], eps, targets)
    for i in range(config["n_layer"] - 1, 0, -1):
        g = _block_vjp_x(xs[i], p[f"h{i}"], g, n_head, eps)
    grads = _block_vjp_p(xs[0], p["h0"], g, n_head, eps)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(a))
                        for a in jax.tree_util.tree_leaves(grads)))
    return value, norm
