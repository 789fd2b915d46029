"""Ring attention: exact attention over a sequence sharded across devices.

Absent from the reference (SURVEY.md §2.6 — no CP/SP anywhere); here it is a
first-class primitive. The sequence axis is sharded over the `sp` mesh axis;
each device holds a Q/K/V block, and K/V blocks rotate around the ICI ring
via `lax.ppermute` while a numerically-stable online softmax accumulates the
output (blockwise attention, the standard ring-attention recipe). Peak
memory is O(seq/devices) and the KV exchange overlaps compute on TPU because
ppermute is async on ICI.

Causal masking uses global positions derived from each block's ring index,
and blocks strictly in the future are skipped via `lax.cond` (their compute
is still traced once — static shapes — but XLA's branch executes cheaply).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _block_attend(q, k, v, q_offset, kv_offset, causal: bool,
                  scale: float):
    """Attend q-block to one kv-block, returning unnormalized partials.

    q: [B, Tq, H, D], k/v: [B, Tkv, H, D] ->
    (out [B, Tq, H, D], row_max [B, H, Tq], row_sum [B, H, Tq])
    """
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        q_pos = q_offset + jnp.arange(tq)[:, None]
        k_pos = kv_offset + jnp.arange(tk)[None, :]
        mask = q_pos >= k_pos
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    row_max = jnp.max(logits, axis=-1)
    p = jnp.exp(logits - row_max[..., None])
    row_sum = jnp.sum(p, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return out, row_max, row_sum


def expand_kv_heads(q, k, v):
    """GQA inputs (fewer KV heads than Q heads) -> repeat KV query-side.
    XLA folds the repeat into the attention einsum as a broadcast; the
    pallas flash kernel instead handles grouping natively and never
    calls this."""
    if k.shape[2] != q.shape[2]:
        groups = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, groups, axis=2)
        v = jnp.repeat(v, groups, axis=2)
    return k, v


def full_attention(q, k, v, *, causal: bool = True,
                   scale: Optional[float] = None):
    """Dense (unsharded) softmax attention — the single-device reference
    all sharded variants must match."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    k, v = expand_kv_heads(q, k, v)
    out, _, row_sum = _block_attend(q, k, v, 0, 0, causal, scale)
    return out / jnp.maximum(row_sum, 1e-20).transpose(0, 2, 1)[..., None]


def _ring_attention_local(q, k, v, *, axis_name: str, causal: bool,
                          scale: float):
    """Per-shard body: rotate KV blocks around the ring with an online
    softmax accumulator."""
    n = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    tq = q.shape[1]
    b, _, h, d = q.shape

    acc = jnp.zeros((b, tq, h, d), dtype=jnp.float32)
    row_max = jnp.full((b, h, tq), NEG_INF, dtype=jnp.float32)
    row_sum = jnp.zeros((b, h, tq), dtype=jnp.float32)
    q_offset = my_idx * tq

    def body(step, carry):
        acc, row_max, row_sum, k_blk, v_blk = carry
        kv_idx = (my_idx - step) % n  # whose block we hold this round
        kv_offset = kv_idx * k_blk.shape[1]

        def attend(operands):
            acc, row_max, row_sum = operands
            # GQA KV rotates the ring at its narrow h_kv width; the
            # expansion here feeds straight into the block einsum, so
            # XLA lowers it to a broadcast, not an HBM copy
            k_e, v_e = expand_kv_heads(q, k_blk, v_blk)
            out, blk_max, blk_sum = _block_attend(
                q, k_e, v_e, q_offset, kv_offset, causal, scale
            )
            new_max = jnp.maximum(row_max, blk_max)
            old_scale = jnp.exp(row_max - new_max)
            blk_scale = jnp.exp(blk_max - new_max)
            acc = acc * old_scale.transpose(0, 2, 1)[..., None] + \
                out.astype(jnp.float32) * blk_scale.transpose(0, 2, 1)[..., None]
            row_sum = row_sum * old_scale + blk_sum * blk_scale
            return acc, new_max, row_sum

        if causal:
            # A block entirely in the future contributes nothing; skip its
            # FLOPs (q_offset+tq-1 < kv_offset means no valid pair).
            needed = q_offset + tq - 1 >= kv_offset
            acc, row_max, row_sum = lax.cond(
                needed, attend, lambda ops: ops, (acc, row_max, row_sum)
            )
        else:
            acc, row_max, row_sum = attend((acc, row_max, row_sum))

        # Rotate KV to the next device; last round's rotate is wasted but
        # keeps the loop uniform (XLA overlaps it with the final attend).
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return acc, row_max, row_sum, k_blk, v_blk

    acc, row_max, row_sum, _, _ = lax.fori_loop(
        0, n, body, (acc, row_max, row_sum, k, v)
    )
    out = acc / jnp.maximum(row_sum, 1e-20).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    axis_name: str = "sp",
    causal: bool = True,
    scale: Optional[float] = None,
    batch_axes=("dp", "fsdp"),
    head_axis: Optional[str] = "tp",
) -> jax.Array:
    """Exact attention with the sequence sharded over `axis_name`.

    Inputs are [batch, seq, heads, head_dim] global arrays (sharded or not);
    output has the same sharding as q.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    batch = tuple(a for a in batch_axes if a in mesh.axis_names)
    bspec = batch if len(batch) > 1 else (batch[0] if batch else None)
    hspec = head_axis if head_axis in mesh.axis_names else None
    spec = P(bspec, axis_name if axis_name in mesh.axis_names else None,
             hspec, None)
    if axis_name not in mesh.axis_names or mesh.shape[axis_name] == 1:
        # No sequence sharding: plain attention.
        return full_attention(q, k, v, causal=causal, scale=scale)

    fn = shard_map(
        functools.partial(_ring_attention_local, axis_name=axis_name,
                          causal=causal, scale=scale),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    axis_name: str = "sp",
    causal: bool = True,
    scale: Optional[float] = None,
    batch_axes=("dp", "fsdp"),
) -> jax.Array:
    """Ulysses/DeepSpeed-style sequence parallelism: all_to_all swaps the
    sharded dimension from sequence to heads, attention runs with full
    sequence per device on a head subset, then all_to_all swaps back.
    Requires heads % sp == 0. Cheaper than ring for moderate sequence
    lengths (two all_to_alls instead of n-1 permutes)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if axis_name not in mesh.axis_names or mesh.shape[axis_name] == 1:
        return full_attention(q, k, v, causal=causal, scale=scale)
    if k.shape[2] % mesh.shape[axis_name]:
        # GQA with kv heads not divisible by sp: the head all_to_all
        # can't split h_kv evenly — expand first (full-width comm)
        k, v = expand_kv_heads(q, k, v)
    batch = tuple(a for a in batch_axes if a in mesh.axis_names)
    bspec = batch if len(batch) > 1 else (batch[0] if batch else None)
    spec = P(bspec, axis_name, None, None)

    def local(q, k, v):
        # [B, T/sp, H, D] -> all_to_all -> [B, T, H/sp, D]
        qh = lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1,
                            tiled=True)
        kh = lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1,
                            tiled=True)
        vh = lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1,
                            tiled=True)
        out = full_attention(qh, kh, vh, causal=causal, scale=scale)
        return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    fn = shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec, check_vma=False)
    return fn(q, k, v)
