"""TPU accelerator abstraction: chip detection + pod-slice topology.

Reference: `python/ray/_private/accelerators/tpu.py:75` —
`TPUAcceleratorManager` detects chips via `/dev/accel*` (:104-120), reads
pod topology from instance metadata (:199), advertises `TPU-{version}`
accelerator resources (:312-315) and a one-per-slice
`TPU-{pod_type}-head` resource on worker 0 (:363-388).

TPU-first delta: the reference leaves the head-resource convention to
user code (fan out one task per host by hand, doc comment tpu.py:341-369).
Here the slice is promoted into the scheduler itself — raylets carry
slice labels, and the GCS places slice-topology placement groups
atomically (see `scheduling.place_slice_bundles`) — so gang scheduling a
pod slice is a first-class primitive, not a convention.

Slice metadata comes from env vars (set by the TPU-VM runtime or by the
test Cluster): `TPU_ACCELERATOR_TYPE` (e.g. "v4-16"), `TPU_WORKER_ID`
(host index in the slice), `TPU_SLICE_NAME` (unique slice identity;
falls back to the pod name), `TPU_WORKER_HOSTNAMES` (to count hosts).
"""

from __future__ import annotations

import glob
import os
import sys
from typing import Dict, Optional, Sequence

# label keys carried by every raylet in a slice
LABEL_SLICE_NAME = "ray_tpu.slice_name"
LABEL_SLICE_TYPE = "ray_tpu.slice_type"
LABEL_SLICE_HOST_ID = "ray_tpu.slice_host_id"
LABEL_SLICE_NUM_HOSTS = "ray_tpu.slice_num_hosts"


# Per-chip dense bf16 peak by `device_kind` (what jax reports for
# `jax.devices()[0].device_kind`). Source: Google Cloud TPU documentation,
# "System architecture" pages for v4, v5e, v5p and v6e ("Peak compute per
# chip, bf16"). MFU is only meaningful against the real peak, so a kind
# that is not listed is an error, never a default.
PEAK_BF16_FLOPS: Dict[str, float] = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
}


def peak_bf16_flops(device_kind: str) -> float:
    try:
        return PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no bf16 peak known for device kind {device_kind!r}; add it "
            f"to accelerators.PEAK_BF16_FLOPS with its source "
            f"(known: {sorted(PEAK_BF16_FLOPS)})") from None


def num_local_chips() -> int:
    """This host's TPU chip count: one device node per chip, `/dev/accel*`
    under the accel driver or a numbered IOMMU group under `/dev/vfio/`
    (reference tpu.py:104-120). The vfio numbers are group ids, not chip
    indices — a one-chip slice of a four-chip host shows `/dev/vfio/3`
    alone — so only their count is used."""
    return len(glob.glob("/dev/accel*")) or \
        len(glob.glob("/dev/vfio/[0-9]*"))


# libtpu carves a host's chips into per-process blocks by these bounds
# (x,y,z chips per process); same table as the reference's tpu.py.
_CHIP_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1", 8: "2,4,1"}


def visible_chip_env(chips: Sequence[int], node_chips: int) -> Dict[str, str]:
    """Env that makes libtpu in a worker open exactly `chips` (indices
    into this host's chips). A worker holding part of the host runs as a
    standalone process over its own block; one granted the whole host
    keeps the host's topology env, which on a multi-host slice carries
    the process grid."""
    env = {"TPU_VISIBLE_CHIPS": ",".join(str(c) for c in chips)}
    if len(chips) == node_chips:
        return env
    bounds = _CHIP_BOUNDS.get(len(chips))
    if bounds is None:
        raise ValueError(
            f"a TPU worker holds {sorted(_CHIP_BOUNDS)} chips or the whole "
            f"host ({node_chips}), not {len(chips)}")
    env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = bounds
    env["TPU_PROCESS_BOUNDS"] = "1,1,1"
    return env


# JAX's persistent compilation cache, when nothing outside places it: one
# fixed directory beside the native build outputs (gitignored like them).
# The path is part of the cache's key, so a directory that moves never hits.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "native", "jax_cache")


def configure_compile_cache() -> None:
    """Place JAX's persistent compilation cache for this process.

    `JAX_COMPILATION_CACHE_DIR` set: the cache was placed from outside
    and nothing is changed here. Otherwise the fixed `COMPILE_CACHE_DIR`
    is used, with the minimum compile time lowered so that the serving
    buckets (a second or so each) are kept. Works before jax is imported
    (env, which children inherit) and after (live config)."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    settings = {
        "jax_compilation_cache_dir": COMPILE_CACHE_DIR,
        "jax_persistent_cache_min_compile_time_secs": 0.5,
    }
    jax = sys.modules.get("jax")
    for name, value in settings.items():
        os.environ[name.upper()] = str(value)
        if jax is not None:
            jax.config.update(name, value)


def head_resource_name(slice_type: str) -> str:
    """`TPU-{pod_type}-head` (reference tpu.py:363)."""
    return f"TPU-{slice_type}-head"


def slice_env() -> Optional[Dict[str, str]]:
    """Slice membership labels for this host, or None when the host is
    not part of a TPU pod slice."""
    slice_type = os.environ.get("TPU_ACCELERATOR_TYPE")
    if not slice_type:
        return None
    host_id = int(os.environ.get("TPU_WORKER_ID", "0"))
    name = os.environ.get("TPU_SLICE_NAME") or \
        os.environ.get("TPU_NAME") or f"slice-{slice_type}"
    hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    num_hosts = len(hostnames.split(",")) if hostnames else 1
    return {
        LABEL_SLICE_NAME: name,
        LABEL_SLICE_TYPE: slice_type,
        LABEL_SLICE_HOST_ID: str(host_id),
        LABEL_SLICE_NUM_HOSTS: str(num_hosts),
    }


def slice_resources(labels: Dict[str, str]) -> Dict[str, float]:
    """Extra resources a raylet derives from its slice labels: host 0
    carries the one-per-slice head resource so a driver can target "one
    task per slice" exactly as in the reference convention."""
    if labels.get(LABEL_SLICE_TYPE) is None:
        return {}
    if int(labels.get(LABEL_SLICE_HOST_ID, "0")) != 0:
        return {}
    return {head_resource_name(labels[LABEL_SLICE_TYPE]): 1.0}
