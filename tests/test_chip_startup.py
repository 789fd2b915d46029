"""The start-up and device-selection rules, as unit tests (no cluster).

What decides where a process computes is read before jax starts: chip
discovery, the env a TPU worker is spawned with, the compile cache's place,
the peak table, the LLM app's chip request. `chip_smoke.py` proves them on
the chip; these hold them on the CPU.
"""

import glob
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu._private import accelerators, worker_api
from ray_tpu.ops.flash_attention import flash_attention, path_calls
from ray_tpu.train import backend

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def live_cache_config():
    """Put jax's compilation-cache settings back after a test moved them."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    before = {n: getattr(jax.config, n) for n in names}
    yield
    for name, value in before.items():
        jax.config.update(name, value)


def test_compile_cache_placed_from_outside_is_left_alone(
        monkeypatch, live_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/elsewhere")
    # an earlier test of this worker may have placed the cache itself
    monkeypatch.delenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                       raising=False)
    before = jax.config.jax_compilation_cache_dir
    accelerators.configure_compile_cache()
    assert jax.config.jax_compilation_cache_dir == before
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == "/placed/elsewhere"
    assert "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ


def test_compile_cache_defaults_to_one_fixed_dir_in_the_checkout(
        monkeypatch, live_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                       raising=False)
    accelerators.configure_compile_cache()
    fixed = os.path.join(REPO, "ray_tpu", "native", "jax_cache")
    assert accelerators.COMPILE_CACHE_DIR == fixed
    # children inherit the env; this process already imported jax
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == fixed
    assert jax.config.jax_compilation_cache_dir == fixed
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.5
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert "ray_tpu/native/jax_cache/" in f.read().split()


def test_peak_table_knows_the_v5e_and_refuses_the_unknown():
    assert accelerators.peak_bf16_flops("TPU v5 lite") == 197e12
    with pytest.raises(ValueError, match="no bf16 peak known"):
        accelerators.peak_bf16_flops("TPU v9 imaginary")
    with pytest.raises(ValueError, match="no bf16 peak known"):
        accelerators.peak_bf16_flops("cpu")


@pytest.mark.parametrize("nodes,want", [
    (["/dev/accel0", "/dev/accel1", "/dev/accel2", "/dev/accel3"], 4),
    # one chip of a four-chip host: the number is an IOMMU group, and
    # /dev/vfio/vfio is the container device, not a chip
    (["/dev/vfio/3", "/dev/vfio/vfio"], 1),
    (["/dev/vfio/0", "/dev/vfio/1", "/dev/vfio/2", "/dev/vfio/3",
      "/dev/vfio/vfio"], 4),
    ([], 0),
], ids=["accel", "one_vfio_group", "four_vfio_groups", "none"])
def test_chip_discovery_counts_device_nodes(monkeypatch, nodes, want):
    import fnmatch

    monkeypatch.setattr(
        glob, "glob", lambda pat: [n for n in nodes if fnmatch.fnmatch(n, pat)])
    assert accelerators.num_local_chips() == want


def test_tpu_worker_env_opens_exactly_its_chips():
    one = accelerators.visible_chip_env((2,), node_chips=4)
    assert one == {"TPU_VISIBLE_CHIPS": "2",
                   "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
                   "TPU_PROCESS_BOUNDS": "1,1,1"}
    pair = accelerators.visible_chip_env((0, 1), node_chips=4)
    assert pair["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,2,1"
    # the whole host keeps the host's own topology env
    assert accelerators.visible_chip_env((0, 1, 2, 3), node_chips=4) == \
        {"TPU_VISIBLE_CHIPS": "0,1,2,3"}
    with pytest.raises(ValueError, match="not 3"):
        accelerators.visible_chip_env((0, 1, 2), node_chips=4)


@pytest.mark.parametrize("env,want", [
    ({"JAX_PLATFORMS": "cpu", "TPU_VISIBLE_CHIPS": "0"}, "cpu"),
    ({"JAX_PLATFORMS": "tpu,cpu", "TPU_VISIBLE_CHIPS": "0"}, "tpu"),
    ({"TPU_VISIBLE_CHIPS": "0"}, "tpu"),
    ({}, "cpu"),
], ids=["held_to_cpu", "machine_says_tpu", "chips_granted", "no_chips"])
def test_train_backend_reads_the_platform_without_starting_jax(
        monkeypatch, env, want):
    for name in ("JAX_PLATFORMS", "TPU_VISIBLE_CHIPS"):
        monkeypatch.delenv(name, raising=False)
    for name, value in env.items():
        monkeypatch.setenv(name, value)
    assert backend._worker_jax_platform() == want


@pytest.mark.parametrize("cluster_tpus,want", [(1.0, {"num_tpus": 1}),
                                               (0.0, None)],
                         ids=["node_with_a_chip", "node_without"])
def test_llm_app_requests_the_chip_where_there_is_one(
        monkeypatch, cluster_tpus, want):
    monkeypatch.setattr(ray_tpu, "cluster_resources",
                        lambda: {"CPU": 8.0, "TPU": cluster_tpus})
    app = serve.llm.build_app(name="llm", num_replicas=2)
    assert app.deployment.config.ray_actor_options == want
    assert app.deployment.config.num_replicas == 2


def test_llm_replica_refuses_the_host_of_a_node_with_a_chip(monkeypatch):
    """Here jax is held to the CPU: on a node that advertises a chip that
    is an error, on a node without one it is the way to serve."""
    import types

    from ray_tpu.serve.llm import LLMDeployment

    cw = types.SimpleNamespace(node_id_hex="ab" * 16, tpu_chips=(0,))
    monkeypatch.setattr(ray_tpu, "nodes", lambda: [
        {"NodeID": cw.node_id_hex, "Resources": {"CPU": 8.0, "TPU": 1.0}}])
    with pytest.raises(RuntimeError, match="would compute on 'cpu'"):
        LLMDeployment._refuse_host_compute_beside_a_chip(cw)
    monkeypatch.setattr(ray_tpu, "nodes", lambda: [
        {"NodeID": cw.node_id_hex, "Resources": {"CPU": 8.0, "TPU": 0.0}}])
    LLMDeployment._refuse_host_compute_beside_a_chip(cw)


def test_driver_is_told_when_its_node_has_no_chip(monkeypatch):
    """A cluster that `init()` started is one fixed node: asking it for a
    chip it does not advertise raises instead of queueing forever."""
    state = worker_api.GlobalState(None, None, owns_cluster=True,
                                   node_tpus=0.0)
    monkeypatch.setattr(worker_api, "_global_state", state)
    with pytest.raises(ValueError, match="advertises TPU: 0"):
        worker_api._resource_dict({"num_tpus": 1}, default_cpu=1.0)
    assert worker_api._resource_dict({"num_cpus": 2}, 1.0) == {"CPU": 2.0}
    # a cluster this driver only joined may still grow (autoscaler)
    state.node_tpus = None
    assert worker_api._resource_dict({"num_tpus": 1}, 1.0)["TPU"] == 1.0


def test_flash_attention_says_which_path_ran():
    """Off a TPU the wrapper takes the dense path — and counts it, so a
    caller that must be on the kernel can tell."""
    before = path_calls()
    q = jnp.ones((1, 128, 2, 64), jnp.float32)
    flash_attention(q, q, q, causal=True)
    after = path_calls()
    assert after["dense"] == before["dense"] + 1
    assert after["pallas"] == before["pallas"]


def test_chip_smoke_refuses_a_cpu_within_seconds():
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert time.monotonic() - t0 < 30
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"


def test_gcs_does_not_bury_a_node_for_its_own_stall():
    """A TPU runtime starting up freezes every process of the sandboxed
    chip machine for seconds. Frozen together, a raylet's heartbeats and
    the GCS's check both stop: that is no silence of the node. A node that
    does go silent while the GCS runs is still found dead."""
    import asyncio

    from ray_tpu._private.gcs import GcsServer

    async def main():
        gcs = GcsServer()
        gcs.config.raylet_heartbeat_period_s = 0.05
        gcs.config.health_check_failure_threshold = 4  # dead after 0.2 s
        node = b"n" * 16
        gcs.nodes[node] = {"node_id": node, "alive": True,
                           "raylet_addr": "127.0.0.1:1"}
        beating = True

        async def raylet():
            while beating:
                gcs._last_heartbeat[node] = time.monotonic()
                await asyncio.sleep(0.05)

        tasks = [asyncio.ensure_future(raylet()),
                 asyncio.ensure_future(gcs._health_check_loop())]
        await asyncio.sleep(0.2)
        time.sleep(0.6)  # the whole process stalls, three thresholds long
        await asyncio.sleep(0.2)
        assert gcs.nodes[node]["alive"]
        beating = False  # now only the node falls silent
        await asyncio.sleep(0.6)
        assert not gcs.nodes[node]["alive"]
        for t in tasks:
            t.cancel()

    asyncio.run(main())


def test_bench_exit_code_tells_of_a_failed_phase(monkeypatch, capsys):
    """No phase of bench.py can fail while the exit code is 0, and the
    headline never changes its metric when the model phase has no TPU."""
    sys.path.insert(0, REPO)
    import bench

    def run_phase(name, arg=None):
        if name == "gpt2_125m_train":
            raise RuntimeError("model phases need a TPU; jax found 'cpu'")
        return {"rows": 1.0}

    monkeypatch.setattr(bench, "_run_phase", run_phase)
    assert bench.main() == 1
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["metric"] == "gpt2_125m_tokens_per_sec_per_chip"
    assert line["value"] is None
    assert line["failed_phases"] == ["gpt2_125m_train"]
    assert "error" in line["suite"]["gpt2_125m_train"]

    monkeypatch.setattr(bench, "_run_phase", lambda name, arg=None: {
        "tokens_per_sec_per_chip": 1.0} if name == "gpt2_125m_train"
        else {"rows": 1.0})
    assert bench.main() == 0
