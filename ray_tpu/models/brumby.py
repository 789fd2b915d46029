"""Brumby family decoder (`model_type` `brumby`): a dense stack of layers
whose attention is POWER RETENTION of degree 2 ("Scaling Context Requires
Rethinking Attention", arXiv:2507.04239), rotary embeddings, grouped heads, a
SwiGLU, an untied head. Serving only, as `ling_hybrid.py`: the three step
functions the engine calls, and a flax module that makes the weights.

What it asks of the system that no other family does:

- NO PAGED LAYER. A token leaves nothing in a page: every layer keeps one
  state a sequence, whatever the sequence's length (`page_kinds` is empty,
  `seq_state` declares the arrays). The cache manager's arena is the state
  arena alone and admission takes a slot and nothing else.
- The state is LARGE: a key head keeps `S` [D, head_dim] and `z` [D] in
  float32, D = head_dim (head_dim + 1) / 2 (8,256 at 128: 34 MB a layer a
  sequence). So the steps take the donated state arena and return THE ARENA
  (`STATE_IN_PLACE`): each layer reads and writes its lanes' states at their
  slots, a decode step one lane at a time, and no array of [lanes, layers,
  ...] ever stands beside the arena. A chunk and a prefill return one row of
  logits a sequence, its last token's: the head over 1,024 rows of a whole
  vocabulary is a quarter again of a chunk's work, for the one row read.

The layer, a token at position i, a = RMSNorm(h): q = a W_q (n_head heads),
k = a W_k, v = a W_v (n_kv_head heads), no biases; q and k RMS-normed over a
head with one learned scale each, rotated (rope, the halves), q scaled by
head_dim^-1/2; log g = logsigmoid(a W_g + b_g) in float32, one scalar a KEY
head. Query head h reads key head h // (n_head / n_kv_head). With c_i the
running sum of log g:

    w_ij = exp(c_i - c_j) (q_i . k_j)^2          (j <= i)
    o_i  = sum_j w_ij v_j / (sum_j w_ij + eps)

The same numbers as a recurrence, `phi` the symmetric degree-2 embedding
(`phi(q) . phi(k) = (q . k)^2`), S and z zero before the first token:

    S_i = g_i S_{i-1} + phi(k_i) v_i^T      z_i = g_i z_{i-1} + phi(k_i)
    o_i = S_i^T phi(q_i) / (z_i . phi(q_i) + eps)

`retention_step` is the recurrence for one token; `retention_chunk` folds a
window `RETENTION_BLOCK` tokens at a time: within a block the first form,
against what came before the block the state. Decays are one scalar a key
head a token, so every exponent either forms is <= 0.

Parameters: `top/{wte, final_norm, lm_head}`; `layer<i>/{attn_norm,
attn_qkv ([q | k | v]), q_norm, k_norm, gate_w, gate_b, attn_out, mlp_norm,
mlp_gate_up ([gate | up]), mlp_down}`.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from flax.linen.initializers import ones

from ray_tpu.models.afmoe import rope_angles
from ray_tpu.models.layers import (A_HEAD, declare_weights, head, last_row,
                                   put_slot_state, rms, rope, slot_state,
                                   swiglu, unboxed_params)

# what each step returns last, an int32 vector: the states the step read and
# wrote (live sequences x layers, padded lanes not counted) and the tokens it
# folded into them (a sequence's tokens once, not once a layer)
STEP_COUNTS = ("retention_state_rows", "retention_tokens")
# the steps take the state arena (`seq_state=`, `slots=`; the prefill too)
# and return the arena's arrays where other families return the sequences'
# new states (`engine._family_cache`)
STATE_IN_PLACE = True
# tokens `retention_chunk` folds into the state at a time (a chunk of the
# benchmark's cell whole): within a block the [C, C] weights of the attention
# form, 168 MB for 40 heads in float32
RETENTION_BLOCK = 1024
HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class BrumbyConfig:
    vocab_size: int = 151936
    n_layer: int = 40
    n_head: int = 40
    n_kv_head: int = 8
    d_model: int = 5120
    head_dim: int = 128
    ffn_dim: int = 17408
    max_seq_len: int = 32768
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    retention_eps: float = 1e-6     # beside the normaliser
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    @property
    def state_dim(self) -> int:
        """D: the entries x_a x_b, a <= b, of a head's channels."""
        return self.head_dim * (self.head_dim + 1) // 2

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=512, n_layer=3, n_head=4, n_kv_head=2,
                    d_model=64, head_dim=8, ffn_dim=128, max_seq_len=128)
        base.update(kw)
        return cls(**base)


def page_kinds(cfg: BrumbyConfig) -> tuple:
    """No layer leaves rows in a paged arena."""
    return ()


def seq_state(cfg: BrumbyConfig):
    """What a SEQUENCE keeps, one (shape, dtype) an array: every layer's
    `S` and `z`, a key head each, float32."""
    heads = (cfg.n_layer, cfg.n_kv_head, cfg.state_dim)
    return ((heads + (cfg.head_dim,), jnp.float32), (heads, jnp.float32))


# -- the weights --------------------------------------------------------------

def _normal(dev: float):
    """Every value drawn in float32 and then rounded to the parameter's type
    (`jax.random.normal` in bfloat16 takes 128 distinct values: `ouro.py`)."""
    def init(key, shape, dtype):
        return (dev * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    return init


# the gate's biases of a layer's key heads, evenly over this range: a head's
# decay sigmoid(.) then lies between 0.73 and 0.9997
GATE_BIAS = (1.0, 8.0)
# a key head's values are drawn wider by (memory / 3) to this power
VALUE_GAIN = 0.3


def head_memory(cfg: BrumbyConfig):
    """Tokens a key head's state keeps, 1 / (1 - g) at its gate's bias: 3.7
    for the first head to 2,982 for the last, the same in every layer."""
    bias = np.linspace(*GATE_BIAS, cfg.n_kv_head)
    return bias, 1.0 + np.exp(bias)


def layer_shapes(cfg: BrumbyConfig) -> dict:
    """name -> (shape, initializer) of a layer's parameters. Seeded weights
    stand in for trained ones, and the check that compares the served tokens
    with the reference is only as good as the function they make (PERF.md §6,
    PR 53): it has to depend on the gate AND on the carried state. So the
    gate's bias (float32) is spread evenly over `GATE_BIAS`, heads that
    forget in a few tokens beside heads that keep thousands (a state that
    forgets in two tokens would hide a wrong carry), and a key head's VALUES
    are drawn wider by `(memory / 3) ** VALUE_GAIN`, 1.07 to 7.9. Why: o is
    a weighted mean of values, and the weights (q . k)^2 of n remembered
    tokens leave n / 3 of them effective, so at equal values a head that
    keeps 3,000 tokens adds a thirtieth of what a head that keeps 4 does
    and a chunk that starts from a ZERO STATE answers as the sound one
    (shortfalls 0.008-0.46 on the chip); at the full sqrt the slow heads
    carry the layer and the gate LEFT OUT is what goes unseen (0.69); at 0.3
    both read 2.8 and more on every seed (the readings: PERF.md §6)."""
    d, hd, h, kv = cfg.d_model, cfg.head_dim, cfg.n_head, cfg.n_kv_head
    w = _normal(0.02)
    bias, memory = head_memory(cfg)
    gain = np.ones((h + 2 * kv) * hd, np.float32)
    gain[(h + kv) * hd:] = np.repeat((memory / 3.0) ** VALUE_GAIN, hd)

    def qkv(key, shape, dtype):
        return (w(key, shape, jnp.float32) * gain).astype(dtype)

    def gate_bias(key, shape, dtype):
        return jnp.asarray(bias, jnp.float32)

    return {
        "attn_norm": ((d,), ones),
        "attn_qkv": ((d, (h + 2 * kv) * hd), qkv),
        "q_norm": ((hd,), ones),
        "k_norm": ((hd,), ones),
        "gate_w": ((d, kv), w),
        "gate_b": ((kv,), gate_bias),
        "attn_out": ((h * hd, d), w),
        "mlp_norm": ((d,), ones),
        "mlp_gate_up": ((d, 2 * cfg.ffn_dim), w),
        "mlp_down": ((cfg.ffn_dim, d), w),
    }


def top_shapes(cfg: BrumbyConfig) -> dict:
    w = _normal(0.02)
    return {"wte": ((cfg.vocab_size, cfg.d_model), w),
            "final_norm": ((cfg.d_model,), ones),
            "lm_head": ((cfg.d_model, cfg.vocab_size), w)}


class Brumby(nn.Module):
    """`net.init` makes the weights; `apply` is the full causal forward,
    every state from zero, tokens [B, T] -> logits [B, T, V]."""
    config: BrumbyConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.config
        p = declare_weights(top_shapes(cfg),
                            [layer_shapes(cfg)] * cfg.n_layer,
                            cfg.param_dtype)
        b = tokens.shape[0]
        arena = tuple(jnp.zeros((b,) + shape, dt)
                      for shape, dt in seq_state(cfg))
        x, _, _ = _window_forward(
            p, cfg, tokens, jnp.zeros((b,), jnp.int32), None, arena,
            jnp.arange(b), carried=jnp.zeros((b,), bool))
        return head(p, cfg, x)


# -- power retention ----------------------------------------------------------

def phi_turn(x, turned, t):
    """Entries (t, 0 .. d-1) of `phi(x)`: x_a x_{(a + t) mod d}, `turned`
    being x rotated by t (t may be traced); times sqrt 2 but for t = 0,
    where the pair is (a, a)."""
    return x * turned * jnp.where(t == 0, 1.0, np.float32(np.sqrt(2.0)))


def phi(x):
    """The symmetric degree-2 embedding of x [..., d] (d even): the
    d (d + 1) / 2 products x_a x_b of unordered pairs, those with a != b
    times sqrt 2, so that `phi(x) . phi(y) = (x . y)^2`. Ordered by circular
    distance: entry (t, a) is x_a x_{(a + t) mod d} for the turns t = 0 ..
    d/2 - 1, then the d/2 pairs at distance d/2 once each. Every turn's d
    entries are x against a rotation of itself: no gather, whole lane rows."""
    with jax.named_scope("retention_embed"):
        d = x.shape[-1]
        half = d // 2
        around = jnp.concatenate([x, x[..., :half]], axis=-1)
        pairs = jnp.stack([phi_turn(x, around[..., t:t + d], t)
                           for t in range(half + 1)], axis=-2)
        return pairs.reshape(x.shape[:-1] + (-1,))[..., :d * (d + 1) // 2]


def power(scores):
    """The kernel on a score, degree 2: `phi(q) . phi(k) = power(q . k)`.
    Even, so every weight is non-negative."""
    return jnp.square(scores)


def _normalised(num, den, eps):
    """o = num / (den + eps): the weights' sum takes softmax's place."""
    return num / (den[..., None] + eps)


def retention_update(phi_q, phi_k, v, log_g, s, z, eps):
    """The recurrence on embedded queries and keys. phi_q [B, H, D]; phi_k
    [B, KV, D]; v [B, KV, d]; log_g [B, KV] (<= 0); s [B, KV, D, d]; z
    [B, KV, D]; float32. Returns (o [B, H, d], the new s, the new z)."""
    with jax.named_scope("retention_step"):
        b, h, _ = phi_q.shape
        kv = phi_k.shape[1]
        g = jnp.exp(log_g)
        s = g[..., None, None] * s + phi_k[..., None] * v[..., None, :]
        z = g[..., None] * z + phi_k
        phi_q = phi_q.reshape(b, kv, h // kv, -1)
        num = jnp.einsum("bhdv,bhrd->bhrv", s, phi_q, precision=HIGHEST)
        den = jnp.einsum("bhd,bhrd->bhr", z, phi_q, precision=HIGHEST)
        o = _normalised(num, den, eps)
    return o.reshape(b, h, -1), s, z


def retention_step(q, k, v, log_g, s, z, eps):
    """One token a sequence: the recurrence itself. q [B, H, d] (scaled);
    k, v [B, KV, d]; log_g [B, KV] (<= 0); s [B, KV, D, d]; z [B, KV, D];
    float32. Returns (o [B, H, d], the new s, the new z)."""
    return retention_update(phi(q), phi(k), v, log_g, s, z, eps)


def retention_chunk(q, k, v, log_g, s, z, eps, block: int = RETENTION_BLOCK):
    """T tokens a sequence after the state (s, z), `block` at a time. q
    [B, T, H, d] (scaled); k, v [B, T, KV, d]; log_g [B, T, KV] (<= 0);
    s [B, KV, D, d]; z [B, KV, D]; float32. A row with k = 0 and log_g = 0
    leaves the state as it was (a padded row). Returns (o [B, T, H, d], the
    state after the last token).

    A block's rows 1..C after (S, z), b_i the running sum of log g inside it:

        num_i = exp(b_i) S^T phi(q_i) + sum_{j<=i} exp(b_i - b_j) (q_i.k_j)^2 v_j
        den_i likewise with z and without v;  o_i = num_i / (den_i + eps)
        S <- exp(b_C) S + sum_j exp(b_C - b_j) phi(k_j) v_j^T;  z likewise

    NEITHER phi(q) NOR phi(k) IS EVER FORMED (1.35 GB for the 40 heads of a
    chunk of 1,024): the sums over D go a TURN at a time (`phi_turn`: the d
    entries x_a x_{(a + t) mod d}), each against its [d, d] slab of S, which
    is read, used and overwritten where it lies; d/2 whole turns and the half
    turn of the pairs at distance d/2. (Formed a block of 128 at a time, the
    embedding, its re-layouts and the sum over D by multiply-and-reduce were
    two thirds of a chunk's 200 ms on the chip: PERF.md §6, PR 53.)"""
    with jax.named_scope("retention_chunk"):
        b, t, h, d = q.shape
        kv, half = k.shape[2], d // 2
        c = min(block, t)
        pad = -t % c
        if pad:
            q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                       for x in (q, k, v))
            log_g = jnp.pad(log_g, ((0, 0), (0, pad), (0, 0)))
        n = (t + pad) // c

        def blocks(x):          # [B, T, ...] -> [n, B, C, ...]
            return jnp.moveaxis(x.reshape((b, n, c) + x.shape[2:]), 1, 0)

        causal = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]

        def fold(state, x):
            s, z = state
            q, k, v, log_g = x
            q = q.reshape(b, c, kv, h // kv, d)
            run = jnp.cumsum(log_g, axis=1)                 # [B, C, KV]
            end = run[:, -1]                                # [B, KV]
            g_end = jnp.exp(end)
            k_end = k * jnp.exp(end[:, None] - run)[..., None]

            def against(s_t, z_t, phi_q, phi_k):
                """A slab of the state: what the queries read of it, and
                its successor."""
                num = jnp.einsum("bthra,bhav->bthrv", phi_q, s_t,
                                 precision=HIGHEST)
                den = jnp.einsum("bthra,bha->bthr", phi_q, z_t,
                                 precision=HIGHEST)
                s_t = g_end[..., None, None] * s_t + jnp.einsum(
                    "btha,bthv->bhav", phi_k, v, precision=HIGHEST)
                z_t = g_end[..., None] * z_t + jnp.sum(phi_k, axis=1)
                return num, den, s_t, z_t

            def turn(i, carry):
                s, z, num, den, q_i, k_i = carry    # q and k rotated by i
                at = (0, 0, i * d)
                more, less, s_i, z_i = against(
                    jax.lax.dynamic_slice(s, at + (0,), (b, kv, d, d)),
                    jax.lax.dynamic_slice(z, at, (b, kv, d)),
                    phi_turn(q, q_i, i), phi_turn(k_end, k_i, i))
                return (jax.lax.dynamic_update_slice(s, s_i, at + (0,)),
                        jax.lax.dynamic_update_slice(z, z_i, at),
                        num + more, den + less,
                        jnp.roll(q_i, -1, axis=-1), jnp.roll(k_i, -1, axis=-1))

            s, z, num, den, q_i, k_i = jax.lax.fori_loop(0, half, turn, (
                s, z, jnp.zeros(q.shape, jnp.float32),
                jnp.zeros(q.shape[:-1], jnp.float32), q, k))
            # the pairs at distance d/2, once each: the half turn
            more, less, s_i, z_i = against(
                s[:, :, half * d:], z[:, :, half * d:],
                phi_turn(q, q_i, half)[..., :half],
                phi_turn(k_end, k_i, half)[..., :half])
            s = s.at[:, :, half * d:].set(s_i)
            z = z.at[:, :, half * d:].set(z_i)
            seen = jnp.exp(run)
            num = seen[..., None, None] * (num + more)
            den = seen[..., None] * (den + less)
            # exp(b_i - b_j) for j <= i, the difference taken first
            since = jnp.moveaxis(run, 1, 2)                 # [B, KV, C]
            since = since[..., :, None] - since[..., None, :]
            decay = jnp.where(causal, jnp.exp(jnp.where(causal, since, 0.0)),
                              0.0)
            w = power(jnp.einsum("bihrd,bjhd->bhrij", q, k,
                                 precision=HIGHEST)) * decay[:, :, None]
            num = num + jnp.einsum("bhrij,bjhv->bihrv", w, v,
                                   precision=HIGHEST)
            den = den + jnp.moveaxis(jnp.sum(w, axis=-1), 3, 1)
            return (s, z), _normalised(num, den, eps).reshape(b, c, h, d)

        (s, z), o = jax.lax.scan(
            fold, (s, z), tuple(blocks(x) for x in (q, k, v, log_g)))
        o = jnp.moveaxis(o, 0, 1).reshape(b, n * c, h, d)
    return o[:, :t], s, z


# -- the layer's parts --------------------------------------------------------

def _project(lp, cfg: BrumbyConfig, a, cos, sin):
    """a [..., d_model] (normed) -> q [..., H, d] (normed a head, rotated,
    scaled), k [..., KV, d] (normed, rotated), v [..., KV, d], float32, and
    log g [..., KV] float32 from a float32 accumulation (the gate's bias is
    float32, and exp(log g) multiplies a state that lives thousands of
    tokens)."""
    dtype, hd, f32 = cfg.dtype, cfg.head_dim, jnp.float32
    n_q, n_kv = cfg.n_head * hd, cfg.n_kv_head * hd
    q, k, v = jnp.split(a @ lp["attn_qkv"].astype(dtype), [n_q, n_q + n_kv],
                        axis=-1)
    q = rms(q.reshape(q.shape[:-1] + (cfg.n_head, hd)), lp["q_norm"],
            cfg.norm_eps, dtype)
    k = rms(k.reshape(k.shape[:-1] + (cfg.n_kv_head, hd)), lp["k_norm"],
            cfg.norm_eps, dtype)
    q, k = rope(q, cos, sin, A_HEAD), rope(k, cos, sin, A_HEAD)
    v = v.reshape(v.shape[:-1] + (cfg.n_kv_head, hd))
    log_g = jax.nn.log_sigmoid(
        jnp.dot(a, lp["gate_w"].astype(dtype), preferred_element_type=f32)
        + lp["gate_b"].astype(f32))
    return q.astype(f32) * hd ** -0.5, k.astype(f32), v.astype(f32), log_g


def _close_layer(lp, cfg: BrumbyConfig, x, o):
    """The heads' outputs o [..., H, d] float32 through W_o, then the
    SwiGLU, each added to the stream x."""
    dtype = cfg.dtype
    x = x + o.astype(dtype).reshape(o.shape[:-2] + (-1,)) \
        @ lp["attn_out"].astype(dtype)
    m = rms(x, lp["mlp_norm"], cfg.norm_eps, dtype)
    return x + swiglu(m, lp["mlp_gate_up"], lp["mlp_down"], dtype)


def _step_counts(cfg: BrumbyConfig, sequences, tokens):
    return jnp.stack([jnp.asarray(sequences, jnp.int32) * cfg.n_layer,
                      jnp.asarray(tokens, jnp.int32)])


# -- the three steps ----------------------------------------------------------

def _window_forward(p, cfg: BrumbyConfig, tokens, start, valid_rows, arena,
                    slots, carried):
    """C tokens a sequence from position `start` on, every layer's state
    read from the sequence's slot of `arena` = (S [slots, L, KV, D, d], z
    [slots, L, KV, D]) where `carried` (a [B] flag a sequence; zero where
    not) and written back to it. The tokens are the
    leading rows of the window; `valid_rows` [B, C] marks them (None: all),
    and the rows after them change no state. Returns (the stream [B, C,
    d_model] after the last layer, the arena, counts)."""
    dtype = cfg.dtype
    b, c = tokens.shape
    x = p["wte"].astype(dtype)[tokens]
    cos, sin = rope_angles(start[:, None] + jnp.arange(c)[None, :],
                           cfg.head_dim, cfg.rope_theta)
    if valid_rows is None:
        valid_rows = jnp.ones((b, c), bool)
    s_arena, z_arena = arena
    for i in range(cfg.n_layer):
        lp = p[f"layer{i}"]
        a = rms(x, lp["attn_norm"], cfg.norm_eps, dtype)
        q, k, v, log_g = _project(lp, cfg, a, cos, sin)
        k = jnp.where(valid_rows[..., None, None], k, 0.0)
        log_g = jnp.where(valid_rows[..., None], log_g, 0.0)
        s0 = jnp.where(carried[:, None, None, None],
                       s_arena[slots, i].astype(jnp.float32), 0.0)
        z0 = jnp.where(carried[:, None, None],
                       z_arena[slots, i].astype(jnp.float32), 0.0)
        o, s, z = retention_chunk(q, k, v, log_g, s0, z0, cfg.retention_eps)
        s_arena = s_arena.at[slots, i].set(s.astype(s_arena.dtype))
        z_arena = z_arena.at[slots, i].set(z.astype(z_arena.dtype))
        x = _close_layer(lp, cfg, x, o)
    n_valid = jnp.sum(valid_rows.astype(jnp.int32), axis=1)
    return x, (s_arena, z_arena), _step_counts(
        cfg, jnp.sum((n_valid > 0).astype(jnp.int32)), jnp.sum(n_valid))


def prefill_step(variables, cfg: BrumbyConfig, tokens, true_len,
                 seq_state=None, slots=None, valid=None):
    """Full forward over a padded prompt batch, every state from zero,
    whatever the sequences' slots held. tokens [B, S]; true_len [B];
    `seq_state` the arena's arrays, `slots` [B]; `valid` [B, S] marks the
    rows that are tokens (None: the first `true_len`). Returns (next_logits
    [B, V], the arena's arrays with the states after the last token in the
    slots, counts)."""
    p = unboxed_params(variables)
    b, s = tokens.shape
    if valid is None:
        valid = jnp.arange(s)[None, :] < true_len[:, None]
    x, arena, counts = _window_forward(
        p, cfg, tokens, jnp.zeros((b,), jnp.int32), valid, seq_state, slots,
        carried=jnp.zeros((b,), bool))
    return (head(p, cfg, last_row(x, true_len)),) + arena + (counts,)


def chunk_step(variables, cfg: BrumbyConfig, tokens, start, seq_state=None,
               slots=None, valid=None):
    """C tokens a sequence after its first `start` positions, whose state
    its slot of the arena holds; a sequence's first window (`start` 0)
    starts from zero whatever the slot held. `valid` [B, C] marks the rows
    that are tokens. Returns (the logits of each sequence's last token
    [B, V], the arena's arrays, counts)."""
    p = unboxed_params(variables)
    if valid is None:
        valid = jnp.ones(tokens.shape, bool)
    x, arena, counts = _window_forward(
        p, cfg, tokens, start, valid, seq_state, slots, carried=start > 0)
    n_valid = jnp.sum(valid.astype(jnp.int32), axis=1)
    return (head(p, cfg, last_row(x, n_valid)),) + arena + (counts,)


def decode_step(variables, cfg: BrumbyConfig, tokens, positions,
                seq_state=None, slots=None, valid=None):
    """One token a sequence: the recurrence on the lanes' slots of the
    arena, ONE LANE AT A TIME in every layer (a loop over the lanes that
    slices a slot's state out of the arena and writes its successor back),
    so that nothing of the arena's size stands beside it. tokens [B];
    positions [B]; `valid` [B] marks the lanes that hold a sequence (the
    others name the scratch slot, and what lands there is nobody's). Returns
    (logits [B, V], the arena's arrays, counts)."""
    p = unboxed_params(variables)
    dtype = cfg.dtype
    b = tokens.shape[0]
    x = p["wte"].astype(dtype)[tokens]
    cos, sin = rope_angles(positions, cfg.head_dim, cfg.rope_theta)
    s_arena, z_arena = seq_state
    for i in range(cfg.n_layer):
        lp = p[f"layer{i}"]
        a = rms(x, lp["attn_norm"], cfg.norm_eps, dtype)
        q, k, v, log_g = _project(lp, cfg, a, cos, sin)
        phi_q, phi_k = phi(q), phi(k)       # every lane's at once

        def lane(j, carry):
            s_arena, z_arena, out = carry
            o, s, z = retention_update(
                phi_q[j][None], phi_k[j][None], v[j][None], log_g[j][None],
                slot_state(s_arena, slots[j], i),
                slot_state(z_arena, slots[j], i), cfg.retention_eps)
            return (put_slot_state(s_arena, s, slots[j], i),
                    put_slot_state(z_arena, z, slots[j], i),
                    out.at[j].set(o[0]))

        s_arena, z_arena, o = jax.lax.fori_loop(
            0, b, lane, (s_arena, z_arena, jnp.zeros_like(q)))
        x = _close_layer(lp, cfg, x, o)
    lanes = b if valid is None else jnp.sum(valid.astype(jnp.int32))
    return head(p, cfg, x), s_arena, z_arena, _step_counts(cfg, lanes, lanes)
