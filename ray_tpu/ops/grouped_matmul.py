"""Grouped matrix product as one Pallas TPU kernel that walks only what is live.

`lhs` [rows, k] holds rows sorted by group (the rows past the last group
belong to none) and `rhs` [groups, k, n] holds a matrix a group: row r of
group g gives `lhs[r] @ rhs[g]`, what `lax.ragged_dot` computes. The kernel's
grid runs over a walk its caller made from the group sizes
(`parallel.moe.tile_walk`, handed over by scalar prefetch): the (row tile,
group) pairs that hold at least one row. A group with no row is never loaded
and a row tile past the last group is never computed, so the worst case
(every row live) is the buffer's size and not the work. A visit streams its
group's matrix in `[tk, n]` blocks through the BlockSpec pipeline, so the
first block of the next visit, whichever group it belongs to, is in flight
while this one multiplies; the row tile and the result tile stay in VMEM over
the consecutive visits that share them. Operands in their own type, float32
accumulation, the result in `lhs`'s type. Rows that belong to no group are
zero in a visited tile and unwritten in a tile no visit reaches.

The row tile is the walk's; the contraction tile comes from the static shapes
alone (`_k_tile`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# bytes of one `[tk, n]` block of a group's matrix (two are in VMEM): a whole
# matrix of 3 to 8 MB is one block and needs no accumulator
WEIGHT_BLOCK_BYTES = 8 * 2**20


def _k_tile(k: int, n: int, itemsize: int) -> int:
    """The contraction tile: the largest divisor of `k` in whole lane tiles
    whose `[tk, n]` block stays under `WEIGHT_BLOCK_BYTES`; all of `k` where
    it is no multiple of 128 (a block may span a whole dimension)."""
    if k % 128:
        return k
    fits = [tk for tk in range(128, k + 1, 128)
            if k % tk == 0 and tk * n * itemsize <= WEIGHT_BLOCK_BYTES]
    return max(fits, default=128)


def _kernel(group_ref, tile_ref, start_ref, end_ref, lhs_ref, rhs_ref,
            out_ref, *acc, tm: int, tk: int, tiles_k: int):
    v, k = pl.program_id(0), pl.program_id(1)

    def store(product):
        g, tile = group_ref[v], tile_ref[v]
        row = tile * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        mine = (row >= start_ref[g]) & (row < end_ref[g])
        # the first visit of a row tile finds whatever the buffer held
        revisit = (v > 0) & (tile_ref[jnp.maximum(v - 1, 0)] == tile)
        kept = jnp.where(revisit, out_ref[...], jnp.zeros_like(out_ref))
        out_ref[...] = jnp.where(mine, product.astype(out_ref.dtype), kept)

    if tiles_k == 1:
        store(jnp.dot(lhs_ref[...], rhs_ref[...],
                      preferred_element_type=jnp.float32))
        return
    (acc_ref,) = acc

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        lhs_ref[:, pl.ds(pl.multiple_of(k * tk, tk), tk)], rhs_ref[...],
        preferred_element_type=jnp.float32)

    @pl.when(k == tiles_k - 1)
    def _():
        store(acc_ref[...])


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def grouped_matmul(lhs, rhs, walk, *, tm: int, interpret: bool = False):
    """`lax.ragged_dot(lhs, rhs, group_sizes)` on the rows that belong to a
    group, in `lhs`'s type with float32 accumulation. lhs [rows, k]; rhs
    [groups, k, n]; `walk` the (group, tile, starts, ends, visits) that
    `parallel.moe.tile_walk` makes of the group sizes for row tiles of `tm`
    rows: one walk serves every product over the same rows. Jitted, so that a
    program whose layers call it at one shape traces and lowers the kernel
    once: lowered a layer, seven layers cost a second of every start-up,
    compile cache hit or not."""
    rows, k = lhs.shape
    groups, _, n = rhs.shape
    group, tile, starts, ends, visits = walk
    padded = -(-rows // tm) * tm
    if group.shape != (padded // tm + groups - 1,):
        raise ValueError(f"a walk of {group.shape[0]} visits for {rows} rows "
                         f"in tiles of {tm} over {groups} groups")
    if padded != rows:
        lhs = jnp.pad(lhs, ((0, padded - rows), (0, 0)))
    itemsize = jnp.dtype(rhs.dtype).itemsize
    tk = _k_tile(k, n, itemsize)
    tiles_k = k // tk
    lhs_size = jnp.dtype(lhs.dtype).itemsize
    vmem = (2 * tk * n * itemsize + 2 * tm * k * lhs_size
            + 2 * tm * n * lhs_size + 3 * tm * n * 4)
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm, tk=tk, tiles_k=tiles_k),
        out_shape=jax.ShapeDtypeStruct((padded, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            # with no row in any group, one visit of an empty group
            grid=(jnp.maximum(visits, 1), tiles_k),
            in_specs=[
                pl.BlockSpec((tm, k), lambda v, kk, g, t, s, e: (t[v], 0)),
                pl.BlockSpec((None, tk, n),
                             lambda v, kk, g, t, s, e: (g[v], kk, 0)),
            ],
            out_specs=pl.BlockSpec((tm, n),
                                   lambda v, kk, g, t, s, e: (t[v], 0)),
            scratch_shapes=[pltpu.VMEM((tm, n), jnp.float32)]
            if tiles_k > 1 else [],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem + 8 * 2**20),
        interpret=interpret,
    )(group, tile, starts, ends, lhs, rhs)
    return out[:rows]
