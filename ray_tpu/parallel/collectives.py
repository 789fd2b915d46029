"""Collective operations over mesh axes.

Replaces the reference's `ray.util.collective` NCCL/GLOO groups
(`python/ray/util/collective/collective.py:258-594`): on TPU there is no
NCCL — collectives are XLA ops over ICI, expressed inside `shard_map` (or
inserted automatically by GSPMD). This module provides the same operation
vocabulary (allreduce / allgather / reducescatter / broadcast / barrier /
send-recv ring) as thin, mesh-axis-named wrappers, plus host-level (CPU)
collectives over the object store for control-plane coordination.
"""

from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


# --- in-program collectives (use inside shard_map) ---------------------

def allreduce(x, axis: str | Sequence[str]):
    return lax.psum(x, axis)


def allreduce_mean(x, axis: str | Sequence[str]):
    return lax.pmean(x, axis)


def allgather(x, axis: str, *, gather_dim: int = 0, tiled: bool = True):
    return lax.all_gather(x, axis, axis=gather_dim, tiled=tiled)


def reducescatter(x, axis: str, *, scatter_dim: int = 0):
    return lax.psum_scatter(x, axis, scatter_dimension=scatter_dim, tiled=True)


def all_to_all(x, axis: str, *, split_dim: int, concat_dim: int):
    return lax.all_to_all(x, axis, split_axis=split_dim,
                          concat_axis=concat_dim, tiled=True)


def ring_permute(x, axis: str, *, shift: int = 1):
    """Rotate shards around the mesh axis ring (ICI neighbor exchange)."""
    n = lax.axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis, perm)


def broadcast(x, axis: str, *, root: int = 0):
    """Every member gets the root's value."""
    idx = lax.axis_index(axis)
    masked = jnp.where(idx == root, x, jnp.zeros_like(x))
    return lax.psum(masked, axis)


def axis_index(axis: str):
    return lax.axis_index(axis)


# --- jit-level helpers --------------------------------------------------

def device_allreduce(mesh: Mesh, xs, axis: str = "dp"):
    """One-shot allreduce of a pytree across a mesh axis (the NCCL-group
    `allreduce` equivalent of ray.util.collective, but compiled)."""
    spec = P(axis)

    @partial(
        shard_map, mesh=mesh,
        in_specs=(spec,), out_specs=P(),
        check_vma=False,
    )
    def _reduce(x):
        return lax.psum(x, axis)

    return jax.tree_util.tree_map(_reduce, xs)


# --- host-level collectives (CPU control plane) -------------------------
# The reference's GLOO group covers host-only coordination; here the object
# store + named actors provide the rendezvous.

class HostGroup:
    """Barrier/broadcast/allreduce among N ray_tpu actors or drivers,
    coordinated through a named rendezvous actor."""

    def __init__(self, group_name: str, world_size: int, rank: int,
                 timeout_s: float = 300.0):
        import collections

        import ray_tpu

        self.world_size = world_size
        self.rank = rank
        # every collective's completion deadline: a dead/absent rank
        # surfaces as GetTimeoutError here instead of a silent hang
        self.timeout_s = timeout_s
        # Per-tag round counters: every rank calls collectives in the same
        # order (SPMD), so suffixing the round number lets tags be reused.
        self._rounds = collections.defaultdict(int)
        # self-send FIFOs, one per tag (send/recv to own rank never
        # touches the rendezvous actor)
        self._loopback = collections.defaultdict(collections.deque)
        if rank == 0:
            # Barrier semantics need all members' calls in flight at once.
            self._actor = _Rendezvous.options(
                name=f"collective:{group_name}", lifetime="detached",
                max_concurrency=max(16, world_size * 4),
            ).remote(world_size)
        else:
            import time

            deadline = time.time() + 60
            while True:
                try:
                    self._actor = ray_tpu.get_actor(f"collective:{group_name}")
                    break
                except ValueError:
                    if time.time() > deadline:
                        raise
                    time.sleep(0.1)

    def _round_tag(self, tag: str) -> str:
        n = self._rounds[tag]
        self._rounds[tag] += 1
        return f"{tag}#{n}"

    def _timed_get(self, ref):
        """Collective completion wait, charged to the flight recorder's
        collective_ms phase (folds into this thread's next StepStats)."""
        import time

        import ray_tpu
        from ray_tpu.util import step_profiler

        t0 = time.perf_counter()
        try:
            return ray_tpu.get(ref, timeout=self.timeout_s)
        finally:
            step_profiler.add_phase_ms(
                "collective_ms", (time.perf_counter() - t0) * 1e3)

    def barrier(self, tag: str = "barrier"):
        self._timed_get(
            self._actor.barrier.remote(self._round_tag(tag), self.rank))

    def broadcast(self, value=None, root: int = 0, tag: str = "bcast"):
        tag = self._round_tag(tag)
        if self.rank == root:
            self._timed_get(self._actor.put.remote(tag, value))
            return value
        return self._timed_get(self._actor.take.remote(tag))

    def allreduce_sum(self, value, tag: str = "sum"):
        return self._timed_get(
            self._actor.reduce.remote(self._round_tag(tag), self.rank,
                                      value))

    def allgather(self, value, tag: str = "gather"):
        """Every rank receives [value_0, ..., value_{world-1}] in rank
        order (reference `collective.allgather`, GLOO host path)."""
        return self._timed_get(
            self._actor.gather.remote(self._round_tag(tag), self.rank,
                                      value))

    def reducescatter_sum(self, value, tag: str = "rs"):
        """Sum across ranks, then each rank keeps its 1/world_size shard
        along axis 0 (reference `collective.reducescatter`). `value` must
        be an array with leading dim divisible by world_size."""
        import numpy as np

        value = np.asarray(value)
        if value.shape[0] % self.world_size:
            raise ValueError(
                f"reducescatter: leading dim {value.shape[0]} not "
                f"divisible by world_size {self.world_size}")
        total = self.allreduce_sum(value, tag=tag)
        return np.array_split(total, self.world_size, axis=0)[self.rank]

    # -- point-to-point (reference `collective.send/recv`) -----------------

    def _p2p_tag(self, src: int, dst: int, tag: str) -> str:
        key = (src, dst, tag)
        n = self._rounds[key]
        self._rounds[key] += 1
        return f"p2p:{src}->{dst}:{tag}#{n}"

    def send(self, value, dst: int, tag: str = "p2p"):
        """Deliver `value` to rank `dst` (non-blocking handoff through
        the rendezvous actor; pairs with exactly one recv). A self-send
        (dst == rank) short-circuits through a local FIFO — both sides
        of the pair live in this process, so the round counters would
        otherwise never match."""
        if dst == self.rank:
            self._loopback[tag].append(value)
            return
        import ray_tpu

        ray_tpu.get(
            self._actor.put.remote(self._p2p_tag(self.rank, dst, tag),
                                   value),
            timeout=self.timeout_s)

    def recv(self, src: int, tag: str = "p2p"):
        """Block until the matching send from rank `src` arrives."""
        if src == self.rank:
            # both ends live on this thread: a recv with no prior send
            # could only deadlock, so fail loudly instead
            if not self._loopback[tag]:
                raise ValueError(
                    f"recv(src=rank) with no prior send(dst=rank) for "
                    f"tag {tag!r} — a self-recv cannot block")
            return self._loopback[tag].popleft()
        import ray_tpu

        return ray_tpu.get(
            self._actor.take_pop.remote(self._p2p_tag(src, self.rank, tag)),
            timeout=self.timeout_s)


try:
    import ray_tpu as _ray_tpu

    @_ray_tpu.remote
    class _Rendezvous:
        def __init__(self, world_size: int):
            import asyncio

            self.world = world_size
            self.values = {}
            self.events = {}
            self.counts = {}
            self.reduced = {}
            self.consumed = {}
            self._asyncio = asyncio

        def _event(self, tag):
            if tag not in self.events:
                self.events[tag] = self._asyncio.Event()
            return self.events[tag]

        def _release(self, key, readers: int):
            """Free a round's state once every expected reader has
            taken its result — long-lived groups must not accumulate
            one entry per collective round."""
            self.consumed[key] = self.consumed.get(key, 0) + 1
            if self.consumed[key] >= readers:
                self.consumed.pop(key, None)
                self.counts.pop(key, None)
                self.events.pop(key, None)
                self.values.pop(key, None)
                self.reduced.pop(key, None)

        async def barrier(self, tag, rank):
            key = ("b", tag)
            self.counts[key] = self.counts.get(key, 0) + 1
            if self.counts[key] >= self.world:
                self._event(key).set()
            await self._event(key).wait()
            self._release(key, self.world)
            return True

        async def put(self, tag, value):
            if self.world == 1:
                return True  # no takers would ever free the slot
            self.values[tag] = value
            self._event(("v", tag)).set()
            return True

        async def take(self, tag):
            """Multi-consumer take (broadcast: world-1 non-root readers)."""
            await self._event(("v", tag)).wait()
            value = self.values[tag]
            self.consumed[tag] = self.consumed.get(tag, 0) + 1
            if self.consumed[tag] >= self.world - 1:
                self.consumed.pop(tag, None)
                self.events.pop(("v", tag), None)
                self.values.pop(tag, None)
            return value

        async def take_pop(self, tag):
            """Single-consumer take: frees the slot (p2p recv)."""
            await self._event(("v", tag)).wait()
            self.events.pop(("v", tag), None)
            return self.values.pop(tag)

        async def gather(self, tag, rank, value):
            key = ("g", tag)
            self.values.setdefault(key, {})[rank] = value
            if len(self.values[key]) >= self.world:
                self._event(key).set()
            await self._event(key).wait()
            vals = self.values[key]
            out = [vals[r] for r in range(self.world)]
            self._release(key, self.world)
            return out

        async def reduce(self, tag, rank, value):
            key = ("r", tag)
            if key not in self.reduced:
                self.reduced[key] = value
            else:
                self.reduced[key] = jax.tree_util.tree_map(
                    lambda a, b: a + b, self.reduced[key], value
                )
            self.counts[key] = self.counts.get(key, 0) + 1
            if self.counts[key] >= self.world:
                self._event(key).set()
            await self._event(key).wait()
            out = self.reduced[key]
            self._release(key, self.world)
            return out
except Exception:  # pragma: no cover - import-order edge in workers
    _Rendezvous = None
