"""Sharding strategies over named mesh axes (GSPMD recipe).

The reference has no native model parallelism (SURVEY.md §2.6: TP/PP/SP/EP
all absent, delegated to DeepSpeed/FSDP). Here every strategy is a set of
logical-axis rules mapped onto the mesh:

- DP:   batch -> dp         (gradients allreduced by XLA over ICI)
- FSDP: batch -> fsdp, params' largest axis -> fsdp (ZeRO-3 gather/scatter
        inserted by GSPMD)
- TP:   heads/mlp/vocab -> tp (Megatron-style column/row splits)
- SP/CP: sequence -> sp     (activations sharded along sequence; ring
        attention exchanges KV blocks over ICI)
- EP:   experts -> ep       (all_to_all dispatch)

Models annotate parameters with logical axis names through
`flax.linen.Partitioned` metadata (`nn.with_partitioning`), which
`param_shardings` resolves into the carry's `NamedSharding`s, and
activations through `logical_constraint` below, which resolves the names
when a step is traced for a mesh: inside `tracing_for(mesh)` (a
`compiled_step` given `mesh=` enters it around its lowering) every call
becomes a `lax.with_sharding_constraint`; with no mesh it returns its
argument, so a one-chip program holds no trace of it.
`nn.with_logical_constraint` is not used: under the pinned flax a
`with mesh:` is not what it calls a global mesh, and it returned its input
on every path this repo has (PR 34).
"""

from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, fields
from typing import Dict, List, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class ShardingStrategy:
    """Declarative parallelism config (the ScalingConfig extension promised
    in SURVEY.md §7.1).

    `dcn_dp` is the multislice knob: the number of ICI slices ganged over
    the inter-slice (DCN) network, used as an extra OUTER data-parallel
    axis. The per-slice axes (dp/fsdp/tp/sp/pp/ep) describe one slice's
    mesh; the full mesh is dcn x per-slice (mesh.build_hybrid_mesh)."""

    dp: int = 1
    fsdp: int = 1
    tp: int = 1
    sp: int = 1
    pp: int = 1
    ep: int = 1
    dcn_dp: int = 1

    def mesh_axes(self, n_devices: int) -> Dict[str, int]:
        """Per-slice (ICI) axes for `n_devices` devices in ONE slice."""
        from ray_tpu.parallel.mesh import mesh_shape_for

        return mesh_shape_for(n_devices, dp=self.dp, fsdp=self.fsdp,
                              tp=self.tp, sp=self.sp, pp=self.pp, ep=self.ep)

    def build_mesh(self, devices=None) -> Mesh:
        from ray_tpu.parallel.mesh import (MeshConfig, build_hybrid_mesh,
                                           build_mesh)

        devices = list(devices if devices is not None else jax.devices())
        if self.dcn_dp > 1:
            if len(devices) % self.dcn_dp != 0:
                raise ValueError(
                    f"{len(devices)} devices not divisible into "
                    f"{self.dcn_dp} slices")
            per_slice = len(devices) // self.dcn_dp
            return build_hybrid_mesh(
                self.mesh_axes(per_slice), {"dcn": self.dcn_dp}, devices)
        return build_mesh(MeshConfig(self.mesh_axes(len(devices))), devices)

    @property
    def data_axes(self) -> Tuple[str, ...]:
        """Mesh axes the global batch is split over (dcn outermost)."""
        axes = tuple(a for a, n in (("dcn", self.dcn_dp), ("dp", self.dp),
                                    ("fsdp", self.fsdp)) if n > 1)
        return axes or ("dp",)


def logical_axis_rules(strategy: ShardingStrategy) -> List[Tuple[str, Optional[tuple]]]:
    """Logical-axis -> mesh-axis rules for `flax.linen.logical_axis_rules`."""
    batch_axes = tuple(a for a, n in (("dcn", strategy.dcn_dp),
                                      ("dp", strategy.dp),
                                      ("fsdp", strategy.fsdp)) if n > 1)
    rules: List[Tuple[str, Optional[tuple]]] = [
        ("batch", batch_axes or None),
        ("seq", ("sp",) if strategy.sp > 1 else None),
        # Parameter axes.
        ("embed", ("fsdp",) if strategy.fsdp > 1 else None),
        ("mlp", ("tp",) if strategy.tp > 1 else None),
        ("heads", ("tp",) if strategy.tp > 1 else None),
        ("kv", None),
        ("qkv", ("tp",) if strategy.tp > 1 else None),
        ("vocab", ("tp",) if strategy.tp > 1 else None),
        ("expert", ("ep",) if strategy.ep > 1 else None),
        ("stage", ("pp",) if strategy.pp > 1 else None),
        ("norm", None),
    ]
    return [(name, axes[0] if axes and len(axes) == 1 else axes)
            for name, axes in rules]


def batch_spec(strategy: ShardingStrategy, extra_dims: int = 1) -> P:
    """PartitionSpec for a [batch, ...] array: batch split over data axes,
    sequence over sp if enabled."""
    axes: list = [strategy.data_axes if len(strategy.data_axes) > 1
                  else strategy.data_axes[0]]
    if strategy.sp > 1 and extra_dims >= 1:
        axes.append("sp")
        extra_dims -= 1
    axes.extend([None] * extra_dims)
    return P(*axes)


def shard_batch(batch, mesh: Mesh, strategy: ShardingStrategy):
    """Place a host-local batch pytree onto the mesh, sharded over the data
    (and sequence) axes."""

    def place(x):
        ndim = getattr(x, "ndim", 0)
        spec = batch_spec(strategy, extra_dims=max(0, ndim - 1))
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map(place, batch)


@dataclass
class ConstraintTally:
    """What `logical_constraint` did while one program was traced: the
    constraints it emitted and the calls it passed through unchanged."""

    emitted: int = 0
    skipped: int = 0


_TRACE = threading.local()  # .scope: (mesh, tally) of `tracing_for`


@contextlib.contextmanager
def tracing_for(mesh: Optional[Mesh]):
    """The scope in which a step is traced for `mesh`: `logical_constraint`
    emits against it on this thread, and the tally yielded counts what it
    did meanwhile. None keeps the mesh of an enclosing scope, if any.
    `compiled_step(..., mesh=mesh)` enters it around its lowering; a step
    jitted by hand is called (the first time) inside it. It enters
    `with mesh:` too: that is part of the key of jax's own cache of traces,
    so a function traced before with no mesh, or for another, is traced
    again and not taken from there without its constraints."""
    outer = getattr(_TRACE, "scope", (None, None))
    tally = ConstraintTally()
    _TRACE.scope = (mesh if mesh is not None else outer[0], tally)
    try:
        with mesh if mesh is not None else contextlib.nullcontext():
            yield tally
    finally:
        _TRACE.scope = outer


def _strategy_of(mesh: Mesh) -> ShardingStrategy:
    """The strategy a mesh's own axis sizes spell:
    `Mesh('fsdp': 2, 'tp': 2)` is `ShardingStrategy(fsdp=2, tp=2)`."""
    known = {f.name for f in fields(ShardingStrategy)}
    return ShardingStrategy(
        dcn_dp=mesh.shape.get("dcn", 1),
        **{axis: n for axis, n in mesh.shape.items() if axis in known})


def logical_constraint(x, names: Tuple[Optional[str], ...]):
    """Constrain the activation `x`, whose dimensions carry the logical
    `names`, to the layout they have on the mesh the step is traced for.

    The mesh is that of the enclosing `tracing_for`. The names resolve
    through the active `nn.logical_axis_rules` or, where none are active,
    through `logical_axis_rules` of the strategy the mesh's axis sizes
    spell, in flax's order of precedence (in `("batch", "seq", "embed")`
    under fsdp the batch takes `fsdp` and `embed` stays whole); a mesh axis
    the mesh lacks, like a name no rule knows, leaves its dimension whole.
    With no mesh, or outside a trace, it returns `x` itself."""
    import flax.linen as nn

    mesh, tally = getattr(_TRACE, "scope", (None, None))
    if mesh is None or not isinstance(x, jax.core.Tracer):
        if tally is not None:
            tally.skipped += 1
        return x
    rules = nn.get_logical_axis_rules() or logical_axis_rules(
        _strategy_of(mesh))

    def on_mesh(entry):
        axes = tuple(a for a in ((entry,) if isinstance(entry, str)
                                 else entry or ()) if a in mesh.shape)
        return axes[0] if len(axes) == 1 else axes or None

    spec = P(*map(on_mesh, nn.logical_to_mesh_axes(names, rules)))
    tally.emitted += 1
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def param_shardings(mesh: Mesh, abstract_params, rules) -> "jax.tree_util.PyTreeDef":
    """NamedShardings for a flax param tree annotated with
    `nn.with_partitioning` metadata; unannotated leaves replicate."""
    import flax.linen as nn

    logical = nn.get_partition_spec(abstract_params)

    def to_sharding(spec):
        with nn.logical_axis_rules(rules):
            mesh_spec = nn.logical_to_mesh(spec)
        return NamedSharding(mesh, mesh_spec if isinstance(mesh_spec, P) else P())

    return jax.tree_util.tree_map(
        to_sharding, logical,
        is_leaf=lambda x: isinstance(x, P),
    )
