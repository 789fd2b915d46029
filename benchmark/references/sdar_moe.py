"""Plain reference for the SDAR-MoE configuration: `jax.numpy`, float32, no
cache, no kernels, no batching, independent of `ray_tpu/models/sdar_moe.py`.

The block (Qwen3-MoE's, which `sdar_moe` follows): `h = x + Attn(norm1(x))`,
`y = h + MoE(norm2(h))`, RMSNorm throughout, a final norm and an untied head.
`Attn`: q of H heads, k and v of KVH heads of `head_dim`, an RMSNorm with a
learned scale on every q head and k head, rotate-half rope, scores over
sqrt(head_dim), query heads in groups of H / KVH a K/V head, and the
BLOCK-CAUSAL mask as an explicit [T, T] array: key j is visible to query i
iff j // B <= i // B. `MoE`: `p = softmax(x W_r)`, the `top_k` largest,
weights `p_e` over their sum, EVERY expert computed for every token and
weighted by the route (0 for an expert that was not chosen), a group of
experts at a time so that a layer's float32 experts never exist whole.

Generation (`replay`), as the configuration file's `generation` states it:
the prompt's whole blocks are context; what is left of it opens the first
block as revealed positions; an unrevealed position holds the mask token; a
denoising pass is one full forward of [everything before the block, the
block as it stands]; at each unrevealed position the largest logit's token
(the mask token apart) and its softmax probability; the `block_length /
denoise_steps` unrevealed positions of highest probability are revealed (all
that are left if fewer, the lowest index on a tie); a revealed token is
final; a whole block becomes context and the next opens, all masks.

`logits(params, config, ids)` is what the harness calls with `ids = prompt
+ answer[:-1]` and reads at row `len(prompt) - 1 + j` for answer token j. A
causal model's full forward gives that row; here the row from which the
token at position r + 1 was chosen depends on what its block held when it
was revealed, so this function REPLAYS the generation teacher-forced: it
makes its own float32 choice of which positions to reveal, puts the
streamed token there (its own choice where the harness gave none: the last
answer token and the positions past the answer's end), and records, in row
r, the logits of the pass that revealed position r + 1. The split of `ids`
into prompt and answer is not passed: the answer's length is
`generation.check_new_tokens` of the configuration file.
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
# experts computed together: 16 x 3 x 2,048 x 768 float32 values are 302 MB
EXPERT_GROUP = 16
# vocabulary rows of the head computed together (19k x 2,048 float32: 156 MB)
HEAD_SLICE = 19_000
# `replay` pads what it forwards to a multiple of this, so that the passes
# of one generation share their compiled operations; under the block-causal
# mask a later block changes nothing before it
PAD_TO = 64
# how far under a row's top `logits` puts a token that the cell's own limit
# refuses, in the row's rms: the harness then reads 100 / sqrt(1 + 100^2 / V),
# over 22 for any vocabulary of 512 or more, against its limit of 0.5
REFUSED = 100.0


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rope(x, positions, theta):
    """Rotate-half rope over the whole head; x [T, heads, D]."""
    d = x.shape[-1]
    freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions.astype(F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block_causal_mask(t: int, block: int):
    blk = jnp.arange(t) // block
    return blk[None, :] <= blk[:, None]


def attention(lp, config, x, mask):
    h, kvh, d = config["num_attention_heads"], \
        config["num_key_value_heads"], config["head_dim"]
    t = x.shape[0]
    q, k, v = jnp.split(x @ lp["attn_qkv"].astype(F32),
                        [h * d, (h + kvh) * d], axis=-1)
    eps = config["rms_norm_eps"]
    positions = jnp.arange(t)
    q = _rope(_rms(q.reshape(t, h, d), lp["q_norm"], eps), positions,
              config["rope_theta"])
    k = _rope(_rms(k.reshape(t, kvh, d), lp["k_norm"], eps), positions,
              config["rope_theta"])
    v = v.reshape(t, kvh, d)
    k, v = jnp.repeat(k, h // kvh, axis=1), jnp.repeat(v, h // kvh, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(d)
    scores = jnp.where(mask[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(t, h * d) @ lp["attn_out"].astype(F32)


def route(lp, config, x):
    """[T, n_experts] float32: an expert's weight for a token, 0 where it
    is not among the token's `top_k`."""
    if not config["norm_topk_prob"]:
        raise ValueError("the reference renormalises the chosen weights")
    p = jax.nn.softmax(x @ lp["router"].astype(F32), axis=-1)
    top, idx = jax.lax.top_k(p, config["num_experts_per_tok"])
    w = top / jnp.sum(top, axis=-1, keepdims=True)
    return jnp.zeros_like(p).at[jnp.arange(x.shape[0])[:, None], idx].set(w)


def experts(lp, config, x):
    """Every expert for every token, weighted by the route."""
    w = route(lp, config, x)
    n = lp["experts_down"].shape[0]
    if n != config["num_experts"]:
        raise ValueError(f"{n} experts' weights for a router of "
                         f"{config['num_experts']}")
    out = jnp.zeros_like(x)
    for e in range(0, n, EXPERT_GROUP):
        gate_up = lp["experts_gate_up"][e:e + EXPERT_GROUP].astype(F32)
        down = lp["experts_down"][e:e + EXPERT_GROUP].astype(F32)
        gate, up = jnp.split(jnp.einsum("td,edf->tef", x, gate_up), 2, -1)
        y = jnp.einsum("tef,efd->ted", jax.nn.silu(gate) * up, down)
        out = out + jnp.einsum("ted,te->td", y, w[:, e:e + EXPERT_GROUP])
    return out


def forward(params, config, ids):
    """ids [T] -> the last layer's output [T, d] float32, under the
    block-causal mask."""
    x = params["wte"][ids].astype(F32)
    mask = block_causal_mask(ids.shape[0],
                             config["generation"]["block_length"])
    eps = config["rms_norm_eps"]
    for i in range(config["num_hidden_layers"]):
        lp = params[f"layer{i}"]
        x = x + attention(lp, config, _rms(x, lp["attn_norm"], eps), mask)
        x = x + experts(lp, config, _rms(x, lp["mlp_norm"], eps))
    return x


def head(params, config, x):
    """x [n, d] -> logits [n, V] float32, the vocabulary a slice at a time."""
    h = _rms(x, params["final_norm"], config["rms_norm_eps"])
    v = params["lm_head"].shape[1]
    return jnp.concatenate(
        [h @ params["lm_head"][:, a:a + HEAD_SLICE].astype(F32)
         for a in range(0, v, HEAD_SLICE)], axis=-1)


def full_logits(params, config, ids):
    """[T, V]: one forward of `ids` as they stand."""
    params = _flat(params)
    with jax.default_matmul_precision("highest"):
        return head(params, config, forward(params, config, jnp.asarray(ids)))


def _flat(params):
    """The flax module keeps `wte`, `final_norm`, `lm_head` under `top`."""
    if "top" in params:
        params = {**{k: v for k, v in params.items() if k != "top"},
                  **params["top"]}
    return params


def replay(params, config, prompt, streamed, new_tokens: int):
    """The generation of `new_tokens` tokens after `prompt`, teacher-forced
    with `streamed` (the tokens at the answer's first positions, as many as
    are known). Returns {"rows": {position: logits [V] float32 from which
    that position's token was chosen}, "reveals": [(the block's first
    position, the positions revealed) a denoising pass], "tokens": the
    token at every answer position}."""
    params = _flat(params)
    gen = config["generation"]
    block, mask_id = gen["block_length"], gen["mask_token_id"]
    reveal = block // gen["denoise_steps"]
    s = len(prompt)
    given = list(prompt) + list(streamed)
    last = s + new_tokens - 1
    start = s - s % block
    context = list(prompt[:start])
    tokens = list(prompt[start:]) + [mask_id] * (block - s % block)
    shown = [True] * (s % block) + [False] * (block - s % block)
    rows, reveals = {}, []
    while True:
        with jax.default_matmul_precision("highest"):
            ids = context + tokens
            ids = jnp.asarray(ids + [0] * (-len(ids) % PAD_TO), jnp.int32)
            got = np.asarray(head(params, config, forward(
                params, config, ids)[start:start + block]))
        scored = got.copy()
        scored[:, mask_id] = -np.inf
        top = scored.max(axis=-1)
        prob = 1.0 / np.exp(scored - top[:, None]).sum(axis=-1)
        hidden = [j for j in range(block) if not shown[j]]
        hidden.sort(key=lambda j: (-prob[j], j))
        now = sorted(hidden[:reveal])
        reveals.append((start, [start + j for j in now]))
        for j in now:
            at = start + j
            tokens[j] = given[at] if at < len(given) \
                else int(scored[j].argmax())
            shown[j] = True
            rows[at] = got[j]
        if all(shown[:min(block, last - start + 1)]) and \
                last < start + block:
            break
        if all(shown):
            context += tokens
            start += block
            tokens, shown = [mask_id] * block, [False] * block
    answer = (context + tokens)[s:last + 1]
    return {"rows": rows, "reveals": reveals, "tokens": answer}


def shortfall(row, token) -> float:
    """The harness's measure: how far the token's logit lies under the
    row's largest, in the row's rms."""
    return float(row.max() - row[token]) / float(np.sqrt(np.mean(row ** 2)))


def logits(params, config, ids):
    """What `bench_check` reads: row r holds the logits from which the
    token at position r + 1 was chosen, for the answer's positions; the
    other rows are zeros.

    The harness holds every serving cell to one limit, a shortfall of 0.5
    of a row's rms, which this configuration's 8-bit control passes (over
    151,936 near-normal logits the top two lie 0.2 apart). The cell's own
    limit is `generation.check_shortfall_limit`, set between its two
    readings; the harness has no place for it, so it is applied here: a
    row whose streamed token (the harness passes all but the last) falls
    short by more than the limit gets that token's logit put `REFUSED`
    rms under the top, which the harness then reads as not correct. Every
    other row is the logits as computed."""
    gen = config["generation"]
    new = gen["check_new_tokens"]
    ids = [int(t) for t in np.asarray(ids)]
    s = len(ids) + 1 - new
    got = replay(params, config, ids[:s], ids[s:], new)
    out = np.zeros((len(ids), got["rows"][s].shape[0]), np.float32)
    for at, row in got["rows"].items():
        if s <= at <= len(ids):
            out[at - 1] = row
    limit = gen.get("check_shortfall_limit")
    for at in range(s, len(ids)) if limit is not None else ():
        row = out[at - 1]
        short = shortfall(row, ids[at])
        if short > limit:
            print(f"references/sdar_moe.py: the token at position {at} falls "
                  f"short by {short:.4g} of its row's rms, over the cell's "
                  f"limit of {limit}", file=sys.stderr, flush=True)
            row[ids[at]] = row.max() - REFUSED * np.sqrt(np.mean(row ** 2))
    return out
