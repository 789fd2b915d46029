"""Backend plugin ABC + the JAX backend.

Reference: `python/ray/train/backend.py:16,32` (`BackendConfig`/`Backend`)
and `python/ray/train/torch/config.py:150` (`_TorchBackend.on_start` — the
NCCL process-group rendezvous). The TPU-native equivalent initializes
`jax.distributed` instead: rank 0 picks a coordinator port, the executor
broadcasts `rank0_host:port` to every worker, and each worker calls
`jax.distributed.initialize(coordinator, num_processes, process_id)` so XLA
collectives ride ICI in-slice / DCN across slices. No NCCL anywhere.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, Optional, TYPE_CHECKING

if TYPE_CHECKING:
    from ray_tpu.train._internal.worker_group import WorkerGroup


@dataclasses.dataclass
class BackendConfig:
    """Base backend config; subclass per framework."""

    @property
    def backend_cls(self):
        return Backend

    def worker_env(self) -> Dict[str, str]:
        """Env vars to set in worker processes before anything imports jax."""
        return {}


class Backend:
    """Hooks called by the BackendExecutor around training."""

    def on_start(self, worker_group: "WorkerGroup",
                 backend_config: BackendConfig) -> None:
        pass

    def on_training_start(self, worker_group: "WorkerGroup",
                          backend_config: BackendConfig) -> None:
        pass

    def on_shutdown(self, worker_group: "WorkerGroup",
                    backend_config: BackendConfig) -> None:
        pass


# ---------------------------------------------------------------------------
# JAX
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class JaxConfig(BackendConfig):
    """Config for the JAX backend.

    distributed: "auto" initializes jax.distributed only when there is more
        than one worker AND the platform is TPU (single-host CPU tests run
        each worker as an independent jax process); "on"/"off" force it.
    coordinator_port: fixed port for rank 0's coordinator (0 = pick free).
    platform: override JAX_PLATFORMS in workers (e.g. "cpu" for tests).
    """

    distributed: str = "auto"
    coordinator_port: int = 0
    platform: Optional[str] = None
    xla_flags: Optional[str] = None

    @property
    def backend_cls(self):
        return _JaxBackend

    def worker_env(self) -> Dict[str, str]:
        env = {}
        if self.platform:
            env["JAX_PLATFORMS"] = self.platform
        if self.xla_flags:
            env["XLA_FLAGS"] = self.xla_flags
        return env


def _worker_jax_platform() -> str:
    """The platform jax in this worker WILL use, read from the env the
    raylet spawned it with (chips granted: the machine's own setting or
    none; no chips: "cpu") plus the backend config's override. Asking
    jax itself would start the backend, which on TPU must not happen
    before `jax.distributed.initialize`."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms:
        return platforms.split(",")[0]
    return "tpu" if os.environ.get("TPU_VISIBLE_CHIPS") else "cpu"


def _init_jax_distributed(coordinator: str, num_processes: int,
                          process_id: int) -> str:
    import jax
    if jax.distributed.is_initialized():
        # a reused process whose client points at a dead coordinator:
        # leave that rendezvous before joining the new one
        jax.distributed.shutdown()
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )
    return f"{jax.process_index()}/{jax.process_count()}"


class _JaxBackend(Backend):
    def on_start(self, worker_group: "WorkerGroup",
                 backend_config: JaxConfig) -> None:
        cfg = backend_config
        n = len(worker_group)
        want = cfg.distributed
        if want == "off" or (want == "auto" and n == 1):
            return
        if want == "auto" and worker_group.execute_single(
                0, _worker_jax_platform) != "tpu":
            return
        # Rendezvous: rank 0 picks the coordinator port, everyone joins.
        port = cfg.coordinator_port or worker_group.execute_single(
            0, _free_port_fn)
        host = worker_group.execute_single(0, _hostname_fn)
        coordinator = f"{host}:{port}"
        import ray_tpu
        refs = [
            w.execute.remote(_init_jax_distributed, coordinator, n, rank)
            for rank, w in enumerate(worker_group.workers)
        ]
        ray_tpu.get(refs, timeout=300)


def _free_port_fn() -> int:
    import socket
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.bind(("0.0.0.0", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _hostname_fn() -> str:
    import socket
    return socket.gethostname()
