"""Expert parallelism: sharded mixture-of-experts dispatch.

Absent from the reference (SURVEY.md §2.6). Experts are sharded over the
`ep` mesh axis; tokens are routed top-k, dispatched to expert shards with an
`all_to_all` inside `shard_map`, processed, and combined back weighted by the
router probabilities. Capacity-factor truncation keeps shapes static for XLA.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


def top1_routing(router_logits: jax.Array, num_experts: int,
                 capacity: int):
    """Top-1 routing with static capacity. Returns (dispatch [T, E, C]
    one-hot, combine [T, E, C] weights, aux_loss)."""
    probs = jax.nn.softmax(router_logits, axis=-1)  # [T, E]
    expert = jnp.argmax(probs, axis=-1)  # [T]
    gate = jnp.take_along_axis(probs, expert[:, None], axis=-1)[:, 0]

    # Position of each token within its expert's capacity buffer.
    onehot = jax.nn.one_hot(expert, num_experts, dtype=jnp.int32)  # [T, E]
    position = jnp.cumsum(onehot, axis=0) * onehot - 1  # [T, E]
    in_capacity = (position < capacity) & (position >= 0)
    pos_clipped = jnp.clip(position, 0, capacity - 1)
    dispatch = (
        jax.nn.one_hot(pos_clipped, capacity, dtype=jnp.float32)
        * in_capacity[..., None]
    )  # [T, E, C]
    combine = dispatch * gate[:, None, None]

    # Load-balancing auxiliary loss (Switch Transformer).
    density = onehot.mean(axis=0)
    density_proxy = probs.mean(axis=0)
    aux_loss = jnp.sum(density * density_proxy) * num_experts
    return dispatch, combine, aux_loss


def moe_layer(
    x: jax.Array,              # [tokens, d_model] (shard-local)
    router_w: jax.Array,       # [d_model, num_experts] (replicated)
    expert_params,             # pytree with leading [experts_local, ...]
    expert_fn: Callable,       # (params_e, tokens[C, d]) -> [C, d]
    *,
    axis_name: str = "ep",
    capacity_factor: float = 1.25,
):
    """Shard-local MoE body — call inside shard_map with experts sharded
    over `axis_name` and tokens sharded over the data axes."""
    n_shards = lax.axis_size(axis_name)
    tokens, d_model = x.shape
    experts_local = jax.tree_util.tree_leaves(expert_params)[0].shape[0]
    num_experts = experts_local * n_shards
    capacity = max(1, int(capacity_factor * tokens / num_experts))

    logits = x @ router_w
    dispatch, combine, aux = top1_routing(logits, num_experts, capacity)

    # Dispatch: [E, C, d]; shard j hosts experts [j*E_local, (j+1)*E_local).
    # all_to_all(tiled=False) removes the size-n split axis and stacks the
    # n received pieces at concat_axis.
    expert_in = jnp.einsum("tec,td->ecd", dispatch, x)  # [E, C, d]
    expert_in = expert_in.reshape(n_shards, experts_local, capacity, d_model)
    expert_in = lax.all_to_all(expert_in, axis_name, split_axis=0,
                               concat_axis=2, tiled=False)
    # [E_local, C, n_src, d] -> [E_local, n_src * C, d]
    expert_in = expert_in.transpose(0, 2, 1, 3).reshape(
        experts_local, n_shards * capacity, d_model
    )

    expert_out = jax.vmap(expert_fn)(expert_params, expert_in)

    # Route back: the exact inverse layout walk.
    expert_out = expert_out.reshape(experts_local, n_shards, capacity, d_model)
    expert_out = expert_out.transpose(0, 2, 1, 3)  # [E_local, C, n_src, d]
    expert_out = lax.all_to_all(expert_out, axis_name, split_axis=2,
                                concat_axis=0, tiled=False)
    # [n_host, E_local, C, d] -> [E, C, d] on every shard's own token set.
    expert_out = expert_out.reshape(num_experts, capacity, d_model)
    y = jnp.einsum("tec,ecd->td", combine, expert_out)
    return y.astype(x.dtype), aux


def apply_moe(
    x: jax.Array,              # [batch, seq, d_model] global
    router_w: jax.Array,
    expert_params,             # [num_experts, ...] pytree, sharded over ep
    expert_fn: Callable,
    mesh: Mesh,
    *,
    axis_name: str = "ep",
    batch_axes=("dp", "fsdp"),
    capacity_factor: float = 1.25,
):
    if axis_name not in mesh.axis_names or mesh.shape[axis_name] == 1:
        # Single shard: dense dispatch without collectives.
        b, s, d = x.shape
        flat = x.reshape(b * s, d)
        num_experts = jax.tree_util.tree_leaves(expert_params)[0].shape[0]
        capacity = max(1, int(capacity_factor * flat.shape[0] / num_experts))
        logits = flat @ router_w
        dispatch, combine, aux = top1_routing(logits, num_experts, capacity)
        expert_in = jnp.einsum("tec,td->ecd", dispatch, flat)
        expert_out = jax.vmap(expert_fn)(expert_params, expert_in)
        y = jnp.einsum("tec,ecd->td", combine, expert_out)
        return y.reshape(b, s, d).astype(x.dtype), aux

    batch = tuple(a for a in batch_axes if a in mesh.axis_names)
    bspec = batch if len(batch) > 1 else (batch[0] if batch else None)
    xspec = P(bspec, None, None)
    pspec = jax.tree_util.tree_map(
        lambda p: P(axis_name, *([None] * (p.ndim - 1))), expert_params
    )

    def local(x, router_w, expert_params):
        b, s, d = x.shape
        y, aux = moe_layer(
            x.reshape(b * s, d), router_w, expert_params, expert_fn,
            axis_name=axis_name, capacity_factor=capacity_factor,
        )
        return y.reshape(b, s, d), lax.pmean(aux, axis_name)

    fn = shard_map(
        local, mesh=mesh,
        in_specs=(xspec, P(None, None), pspec),
        out_specs=(xspec, P()),
        check_vma=False,
    )
    return fn(x, router_w, expert_params)


# -- one chip's share of an expert-parallel layer -----------------------------

MOE_COUNTS = ("pairs_routed", "pairs_local", "pairs_computed",
              "expert_calls", "tile_visits")


# rows of one visit of the grouped product: the MXU's width, so a visit's
# multiply costs what loading its weights into the array costs and no more.
# A product of fewer rows is XLA's: such a bucket is met on a ramp and
# seldom after, and each program that holds the kernel costs its start
# half a second, compile cache hit or not (PERF.md §6, PR 54)
ROW_TILE = 128


def group_tiles(group_sizes, rows: int, tm: int = ROW_TILE):
    """Each group's first row, end row and how many row tiles of `tm` rows it
    touches, of `rows` rows sorted by group. The sum of the last is the (row
    tile, group) pairs that hold a row: `MOE_COUNTS`' `tile_visits`, at most
    `ceil(rows / tm) + groups - 1`."""
    ends = jnp.minimum(jnp.cumsum(group_sizes.astype(jnp.int32)), rows)
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32), ends[:-1]])
    tiles = jnp.where(ends > starts, (ends - 1) // tm - starts // tm + 1, 0)
    return starts, ends, tiles


def tile_walk(starts, ends, tiles, rows: int, tm: int = ROW_TILE):
    """The walk `ops.grouped_matmul` runs, from `group_tiles`: visit v is
    (group[v], tile[v]), groups in order and each group's tiles in order, so
    the visits of one row tile are consecutive; the worst case's length, of
    which the first `visits` count. A compare and a sum, not a search: a
    handful of operations to lower and to run, once a layer."""
    max_visits = -(-rows // tm) + tiles.shape[0] - 1
    visit_ends = jnp.cumsum(tiles)
    v = jnp.arange(max_visits, dtype=jnp.int32)
    group = jnp.minimum(
        jnp.sum(v[:, None] >= visit_ends[None, :], axis=1, dtype=jnp.int32),
        tiles.shape[0] - 1)
    # a group's first tile less its first visit: add the visit for the tile
    tile = (starts // tm - (visit_ends - tiles))[group] + v
    tile = jnp.clip(tile, 0, -(-rows // tm) - 1).astype(jnp.int32)
    return group, tile, starts, ends, visit_ends[-1]


def sigmoid_topk_route(x, router_w, bias, top_k: int, scale: float,
                       n_group: int = 1, topk_group: int = 1):
    """Sigmoid-scored top-k routing with a selection bias (DeepSeek-V3's
    `noaux_tc`; one group is Kimi-K2's). x [N, d]; router_w
    [d, n_experts]; bias [n_experts]. The experts are the top-k of
    `sigmoid(x W) + bias`; their weights are the scores WITHOUT the bias,
    over their sum, times `scale`. With `n_group` > 1 the selection is
    group-limited: the experts are `n_group` groups of consecutive ones, a
    group's score is the sum of its two largest biased scores, the
    `topk_group` best groups stay and the top-k is over their experts
    alone. Float32 at the highest matmul precision: a rounded score
    changes which expert a token goes to. Returns (expert ids [N, top_k]
    int32, weights [N, top_k] float32)."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=lax.Precision.HIGHEST))
    choice = scores + bias.astype(jnp.float32)
    if n_group > 1:
        grouped = choice.reshape(choice.shape[0], n_group, -1)
        group_score = jnp.sum(lax.top_k(grouped, 2)[0], axis=-1)
        _, kept = lax.top_k(group_score, topk_group)
        keep = jnp.any(kept[:, :, None] == jnp.arange(n_group)[None, None],
                       axis=1)
        choice = jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(
            choice.shape)
    _, expert = lax.top_k(choice, top_k)
    weight = jnp.take_along_axis(scores, expert, axis=-1)
    weight = weight / jnp.sum(weight, axis=-1, keepdims=True) * scale
    return expert.astype(jnp.int32), weight


def softmax_topk_route(x, router_w, top_k: int):
    """Softmax-scored top-k routing with the weights renormalised over the
    chosen (`norm_topk_prob`; Qwen3-MoE's router, no bias, no scale). x
    [N, d]; router_w [d, n_experts]. The experts are the top-k of
    `softmax(x W)`; their weights are those probabilities over their sum.
    Float32 at the highest matmul precision, as `sigmoid_topk_route`.
    Returns (expert ids [N, top_k] int32, weights [N, top_k] float32)."""
    probs = jax.nn.softmax(jnp.dot(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=lax.Precision.HIGHEST), axis=-1)
    weight, expert = lax.top_k(probs, top_k)
    weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    return expert.astype(jnp.int32), weight


def expert_shard_layer(x, router_w, bias, experts_held, first_expert: int,
                       n_experts: int, top_k: int, scale: float,
                       valid=None, n_group: int = 1, topk_group: int = 1,
                       route=None):
    """What the chip that holds experts [first_expert, first_expert + held)
    of `n_experts` adds to a routed expert layer: every token is routed
    over ALL the experts (`n_group`, `topk_group`: group-limited, as
    `sigmoid_topk_route` says; or by `route`, a function of (x, router_w,
    top_k) to expert ids and weights such as `softmax_topk_route`, which
    then is the layer's router and leaves `bias`, `scale` and the groups
    unread), the (token, expert) pairs whose expert lives here
    are kept, and the result is the weighted sum of the held experts'
    outputs alone. No capacity: a pair is never dropped. The kept pairs are
    sorted by expert and each projection is one grouped product over
    `N * min(top_k, held)` rows, the most that can be local: that is the
    buffer's size, and on the TPU, from `ROW_TILE` rows up, not the work
    (`ops.grouped_matmul` walks the (row tile, expert) pairs that hold a
    row, `tile_visits` of them, by one `tile_walk` for both products;
    elsewhere `lax.ragged_dot`). On one chip this is the whole layer's local
    half; under expert parallelism the partial results of the shards add up
    to the layer (the caller adds what every chip computes alike, a shared
    expert, once).

    x [N, d]; router_w [d, n_experts]; bias [n_experts]; `experts_held`
    {"gate_up" [held, d, 2f], "down" [held, f, d]} (SwiGLU, gate | up along
    the last axis); `valid` [N] bool marks the rows that are tokens (a
    padded lane routes nowhere and is not counted). Returns (partial
    result [N, d] in x's type, counts int32[5] as `MOE_COUNTS`)."""
    if router_w.shape[1] != n_experts:
        raise ValueError(f"the router has {router_w.shape[1]} outputs for "
                         f"{n_experts} experts")
    n, d = x.shape
    held = experts_held["down"].shape[0]
    with jax.named_scope("moe_route"):
        if route is None:
            expert, weight = sigmoid_topk_route(
                x, router_w, bias, top_k, scale, n_group, topk_group)
        else:
            expert, weight = route(x, router_w, top_k)
        local = (expert >= first_expert) & (expert < first_expert + held)
        if valid is not None:
            local = local & valid[:, None]
        n_valid = n if valid is None else jnp.sum(valid.astype(jnp.int32))
        # pairs in token order, then sorted by held expert; a pair that is not
        # local gets the id `held` and sorts behind every group
        pair_expert = jnp.where(local, expert - first_expert, held).reshape(-1)
        order = jnp.argsort(pair_expert, stable=True)
        rows = n * min(top_k, held)
        take = order[:rows]
        group_sizes = jnp.sum(
            pair_expert[:, None] == jnp.arange(held)[None, :], axis=0,
            dtype=jnp.int32)
        n_local = jnp.sum(group_sizes)
    with jax.named_scope("moe_experts"):
        starts, ends, tiles = group_tiles(group_sizes, rows)
        if jax.default_backend() == "tpu" and rows >= ROW_TILE:
            # here and not at the top: Pallas is a second of imports, which
            # a process that runs no expert layer on a chip never pays
            from ray_tpu.ops.grouped_matmul import grouped_matmul
            product = functools.partial(
                grouped_matmul, walk=tile_walk(starts, ends, tiles, rows),
                tm=ROW_TILE)
        else:
            product = functools.partial(lax.ragged_dot,
                                        group_sizes=group_sizes)
        xs = x[take // top_k]                                    # [rows, d]
        gu = product(xs, experts_held["gate_up"].astype(x.dtype))
        gate, up = jnp.split(gu, 2, axis=-1)
        ys = product(jax.nn.silu(gate) * up,
                     experts_held["down"].astype(x.dtype))
        # back to token order: pair j lies at row `where[j]` of the sorted
        # rows; a row past the last group belongs to no expert, and whatever
        # the grouped product left there is not a result
        where = jnp.argsort(order)
        n_computed = jnp.minimum(n_local, rows)
        mine = jnp.where((where < n_computed)[:, None],
                         ys[jnp.minimum(where, rows - 1)].astype(jnp.float32),
                         0.0)
        out = jnp.sum((mine * weight.reshape(-1, 1)).reshape(n, top_k, d),
                      axis=1)
    counts = jnp.stack([
        n_valid * top_k, jnp.sum(local.astype(jnp.int32)), n_computed,
        jnp.sum((group_sizes > 0).astype(jnp.int32)),
        jnp.sum(tiles)]).astype(jnp.int32)
    return out.astype(x.dtype), counts
