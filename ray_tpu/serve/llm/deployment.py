"""serve.llm.LLMDeployment — an LLMEngine behind the Serve stack.

Each replica hosts one engine (pump thread + paged KV arena on its
device) and streams tokens over the existing `handle_request_streaming` path:

    app = serve.llm.build_app(name="llm", num_replicas=2)
    handle = serve.run(app)
    for tok in handle.generate.options(stream=True).remote([1, 2, 3], 8):
        ...

The replica exports `get_autoscaling_metrics` so the controller's poll
sees queue depth + KV-page occupancy (autoscaling pressure); the
engine's own counters join the node's /metrics scrape via the registry
callback it registers.
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, List, Optional


class LLMDeployment:
    """Deployment callable: one continuous-batching engine per replica.

    `model` is the family (a key of `engine.MODEL_FAMILIES`);
    `model_config` /
    `engine_config` are plain dicts so deployments stay picklable
    (resolved into the real config dataclasses replica-side). `seed`
    fixes the weight init — replicas of one deployment must agree so
    greedy streams are replayable across a replica death.
    """

    def __init__(self, model: str = "llama",
                 model_config: Optional[Dict[str, Any]] = None,
                 engine_config: Optional[Dict[str, Any]] = None,
                 seed: int = 0):
        from ray_tpu.serve.llm.engine import (EngineConfig, LLMEngine,
                                              model_family)

        model_cfg = None
        if model_config:
            family, mod = model_family(model)
            model_cfg = getattr(mod, family.config)(**model_config)
        from ray_tpu._private.object_ref import get_core_worker

        cw = get_core_worker()
        self._tpu_chips = []
        if cw is not None:
            self._tpu_chips = list(cw.tpu_chips)
            self._refuse_host_compute_beside_a_chip(cw)
        self.engine = LLMEngine(
            model=model, model_cfg=model_cfg,
            engine_config=EngineConfig(**(engine_config or {})),
            seed=seed)
        t0 = time.perf_counter()
        self.engine.warmup()
        self._warmup_s = time.perf_counter() - t0
        self.engine.start()

    @staticmethod
    def _refuse_host_compute_beside_a_chip(cw) -> None:
        """A replica on a node that advertises chips computes on one:
        landing on the host there (deployed without `build_app`'s chip
        request, or jax held to the CPU) would be a slow success that
        nothing reports."""
        import jax

        import ray_tpu

        node_tpus = next(
            (n["Resources"].get("TPU", 0.0) for n in ray_tpu.nodes()
             if n["NodeID"] == cw.node_id_hex), 0.0)
        if node_tpus and jax.default_backend() != "tpu":
            raise RuntimeError(
                f"LLM replica on node {cw.node_id_hex[:8]} (TPU: "
                f"{node_tpus:g}) would compute on {jax.default_backend()!r}: "
                f"chips granted to this worker: {list(cw.tpu_chips)}, "
                f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}")

    # -- request path -----------------------------------------------------

    def generate(self, prompt: List[int], max_new_tokens: int = 16,
                 timeout_s: Optional[float] = None):
        """Generator: yields {"index", "token"} per generated token.
        Streamed to the caller chunk-by-chunk via
        `handle.generate.options(stream=True)`."""
        req = self.engine.submit([int(t) for t in prompt],
                                 int(max_new_tokens),
                                 timeout_s=timeout_s)
        emitted = 0
        while True:
            kind, *rest = req.out_q.get(timeout=120.0)
            if kind == "token":
                yield {"index": rest[0], "token": rest[1]}
                emitted += 1
            elif kind == "done":
                return
            else:
                raise RuntimeError(f"generation failed: {rest[0]}")

    def generate_once(self, prompt: List[int],
                      max_new_tokens: int = 16) -> List[int]:
        """Unary variant: the full generated token list in one reply."""
        req = self.engine.submit([int(t) for t in prompt],
                                 int(max_new_tokens))
        return req.result(timeout=120.0)

    # -- control plane ----------------------------------------------------

    def get_autoscaling_metrics(self) -> Dict[str, Any]:
        m = self.engine.metrics()
        out = {
            "queue_depth": float(m["queue_depth"]),
            "llm_running": float(m["running"]),
            "kv_pages_live": float(m["kv_pages_live"]),
            "kv_pages_cached": float(m.get("kv_pages_cached", 0)),
            "kv_pages_total": float(m["kv_pages_total"]),
        }
        # the roll-up for the dashboard's /api/serve_llm panel: the
        # prefix cache's hit rate
        hit = m.get("prefix_cache_hit_tokens")
        if hit is not None:
            total = hit + m.get("prefix_cache_miss_tokens", 0)
            out["prefix_cache_hit_rate"] = hit / total if total else 0.0
            out["prefix_cache_entries"] = float(
                m.get("prefix_cache_entries", 0))
        return out

    def engine_metrics(self) -> Dict[str, Any]:
        return self.engine.metrics()

    def replica_info(self) -> Dict[str, Any]:
        """Where this replica computes, seen from inside its process:
        jax's device, the chips the raylet granted, the cold-start cost,
        the size of the KV arena (resident on the device) and the
        executable-cache counters (retraces must stay 0)."""
        import jax

        from ray_tpu import parallel
        from ray_tpu.util import tracing

        dev = jax.devices()[0]
        m = self.engine.metrics()
        return {
            "pid": os.getpid(),
            "platform": dev.platform,
            "device_kind": dev.device_kind,
            "device_count": jax.device_count(),
            "tpu_chips": self._tpu_chips,
            "warmup_s": self._warmup_s,
            "kv_arena_bytes": self.engine.kv.arena_nbytes,
            "kv_pages_live": m["kv_pages_live"],
            "requests_completed": m["requests_completed"],
            "cache_stats": parallel.cache_stats(),
            # what this process did before it served: the start-up
            # ledger's rows (`tracing.collect_startup` has every process's)
            "startup": tracing.startup_rows(),
        }

    def device_trace(self, seconds: float,
                     log_dir: Optional[str] = None) -> str:
        """Profile this replica's process for `seconds`
        (`handle.device_trace.remote(1.0).result()`; a second of a busy
        device is what stopping the session affords, see
        `tracing.device_trace`): only the process that holds a chip can
        trace it. Returns the directory, on this replica's node, under
        which `jax.profiler` wrote the `.xplane.pb`; the engine's `rt/`
        phases are host events of the same file, on the clock of the
        device's."""
        from ray_tpu.util import tracing

        with tracing.device_trace(log_dir) as path:
            time.sleep(float(seconds))
        return path

    def check_health(self) -> bool:
        return self.engine._thread is not None and \
            self.engine._thread.is_alive()

    def __del__(self):
        try:
            self.engine.shutdown()
        except Exception:
            pass


def build_app(name: str = "llm", num_replicas: int = 1,
              autoscaling_config: Optional[Dict[str, Any]] = None,
              **init_kwargs):
    """Bind LLMDeployment into a deployable app:
    `serve.run(serve.llm.build_app(...))`. Call after `ray_tpu.init()`:
    where the cluster advertises TPU chips every replica is granted
    one, and on a cluster without any the replicas compute on the
    host."""
    import ray_tpu
    from ray_tpu import serve

    on_chip = ray_tpu.cluster_resources().get("TPU", 0.0) > 0
    deco = serve.deployment(
        name=name,
        num_replicas=None if autoscaling_config else num_replicas,
        autoscaling_config=autoscaling_config,
        ray_actor_options={"num_tpus": 1} if on_chip else None)
    return deco(LLMDeployment).bind(**init_kwargs)
