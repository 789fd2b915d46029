"""Device-mesh construction for TPU slices.

The mesh is the foundation of every parallelism strategy: axes are named
(`dp`, `fsdp`, `tp`, `sp`, `pp`, `ep`) and strategies are expressed as
shardings over those names (scaling-book recipe: pick a mesh, annotate
shardings, let XLA insert the collectives).

On a real TPU slice, `jax.devices()` is already ordered so that contiguous
devices are ICI neighbors; `create_device_mesh` improves the assignment for
torus topologies where available.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh


@dataclass
class MeshConfig:
    """Named mesh axes. At most one axis may be -1 (inferred from the device
    count, like a reshape)."""

    axes: Dict[str, int] = field(default_factory=dict)

    def resolved(self, n_devices: int) -> Dict[str, int]:
        axes = {k: v for k, v in self.axes.items()}
        unknown = [k for k, v in axes.items() if v == -1]
        if len(unknown) > 1:
            raise ValueError("at most one mesh axis may be -1")
        known = int(np.prod([v for v in axes.values() if v != -1])) if axes else 1
        if unknown:
            if n_devices % known != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by {known}"
                )
            axes[unknown[0]] = n_devices // known
        total = int(np.prod(list(axes.values()))) if axes else 1
        if total != n_devices:
            raise ValueError(
                f"mesh axes {axes} use {total} devices but {n_devices} present"
            )
        return axes

    @classmethod
    def data_parallel(cls) -> "MeshConfig":
        return cls({"dp": -1})

    @classmethod
    def fsdp(cls) -> "MeshConfig":
        return cls({"fsdp": -1})


def mesh_shape_for(
    n_devices: int,
    dp: int = 1,
    fsdp: int = 1,
    tp: int = 1,
    sp: int = 1,
    pp: int = 1,
    ep: int = 1,
) -> Dict[str, int]:
    """Build an axes dict, inferring `dp` when it is left at 1 and devices
    remain (so `mesh_shape_for(8, tp=2)` -> dp=4, tp=2)."""
    fixed = fsdp * tp * sp * pp * ep * dp
    if fixed != n_devices:
        if dp == 1 and n_devices % (fsdp * tp * sp * pp * ep) == 0:
            dp = n_devices // (fsdp * tp * sp * pp * ep)
        else:
            raise ValueError(
                f"axes dp={dp} fsdp={fsdp} tp={tp} sp={sp} pp={pp} ep={ep} "
                f"do not factor {n_devices} devices"
            )
    axes = {"dp": dp, "fsdp": fsdp, "tp": tp, "sp": sp, "pp": pp, "ep": ep}
    return {k: v for k, v in axes.items() if v > 1} or {"dp": 1}


def build_mesh(
    config: MeshConfig | Dict[str, int],
    devices: Optional[Sequence] = None,
) -> Mesh:
    if isinstance(config, dict):
        config = MeshConfig(config)
    devices = list(devices if devices is not None else jax.devices())
    axes = config.resolved(len(devices))
    names = tuple(axes.keys())
    shape = tuple(axes.values())
    if devices[0].platform == "tpu":
        from jax.experimental import mesh_utils

        # Topology-aware assignment: contiguous mesh axes map to ICI
        # neighbors so the innermost (most communication-heavy) axes
        # get the fastest links. A shape the topology cannot carry
        # raises here; a silent reshape would hide slow links.
        device_array = mesh_utils.create_device_mesh(shape, devices)
    else:
        device_array = np.asarray(devices).reshape(shape)
    return Mesh(device_array, names)


def build_hybrid_mesh(
    ici_axes: Dict[str, int],
    dcn_axes: Dict[str, int],
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Hybrid ICI x DCN mesh for multislice (SURVEY §7.1: "in-slice =
    ICI ...; cross-slice = DCN (multislice)"; generalizes the reference's
    pod convention `python/ray/_private/accelerators/tpu.py:363-388`).

    The dcn axes are OUTERMOST so every collective over an ici axis stays
    inside one slice's fast fabric; only dcn-axis collectives (typically
    the data-parallel gradient reduction) cross the slower inter-slice
    network — the scaling-book layout.

    On real multislice TPU the devices carry `slice_index` and
    `mesh_utils.create_hybrid_device_mesh` assigns them; on CPU (tests,
    the driver's virtual dryrun) devices are partitioned into contiguous
    blocks, one block playing each slice.
    """
    devices = list(devices if devices is not None else jax.devices())
    ici_shape = tuple(ici_axes.values())
    dcn_shape = tuple(dcn_axes.values())
    n_slices = int(np.prod(dcn_shape)) if dcn_shape else 1
    per_slice = int(np.prod(ici_shape)) if ici_shape else 1
    if n_slices * per_slice != len(devices):
        raise ValueError(
            f"hybrid mesh {dcn_axes} x {ici_axes} needs "
            f"{n_slices * per_slice} devices, have {len(devices)}")
    names = tuple(dcn_axes.keys()) + tuple(ici_axes.keys())
    if devices and devices[0].platform == "tpu" \
            and getattr(devices[0], "slice_index", None) is not None:
        from jax.experimental import mesh_utils

        # same-rank shapes: each axis is parallel over exactly one
        # network (dcn axes are 1 in the ici shape and vice versa)
        mesh_shape = (1,) * len(dcn_shape) + ici_shape
        dcn_mesh_shape = dcn_shape + (1,) * len(ici_shape)
        device_array = mesh_utils.create_hybrid_device_mesh(
            mesh_shape, dcn_mesh_shape, devices)
    else:
        device_array = np.asarray(devices).reshape(dcn_shape + ici_shape)
    return Mesh(device_array, names)


def slice_info() -> dict:
    """Topology of the local TPU slice (host count, chips per host, ICI
    coords) — drives slice-aware gang scheduling (reference sketch:
    `python/ray/_private/accelerators/tpu.py` pod-type metadata)."""
    devices = jax.devices()
    d0 = devices[0]
    info = {
        "platform": d0.platform,
        "num_devices": len(devices),
        "num_hosts": max(d.process_index for d in devices) + 1,
        "device_kind": getattr(d0, "device_kind", "unknown"),
    }
    if hasattr(d0, "coords"):
        info["topology"] = sorted(tuple(d.coords) for d in devices)
    return info
