"""TPU-native parallelism: meshes, sharding rules, collectives, ring
attention, pipeline and expert parallelism.

This package is what replaces the reference's delegated parallelism story
(NCCL process groups via `python/ray/util/collective/` and
`torch.distributed` bootstrap in `python/ray/train/torch/config.py`): every
strategy — DP / FSDP / TP / SP-CP / ring attention / PP / EP — is provided
natively on `jax.sharding.Mesh` + GSPMD + `shard_map`, with XLA collectives
riding ICI inside a slice and DCN across slices.
"""

from ray_tpu.parallel.compile_cache import (
    ExecutableCache,
    RetraceError,
    cache_stats,
    compiled_step,
    fold_steps,
    global_cache,
    stack_batches,
)
from ray_tpu.parallel.mesh import (MeshConfig, build_hybrid_mesh,
                                   build_mesh, mesh_shape_for)
from ray_tpu.parallel.sharding import (
    ShardingStrategy,
    logical_axis_rules,
    logical_constraint,
    shard_batch,
    tracing_for,
)

__all__ = [
    "ExecutableCache",
    "MeshConfig",
    "RetraceError",
    "ShardingStrategy",
    "build_hybrid_mesh",
    "build_mesh",
    "cache_stats",
    "compiled_step",
    "fold_steps",
    "global_cache",
    "logical_axis_rules",
    "logical_constraint",
    "mesh_shape_for",
    "shard_batch",
    "stack_batches",
    "tracing_for",
]
