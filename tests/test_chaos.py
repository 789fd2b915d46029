"""Chaos plane: deterministic fault injection + gang-durable commit.

The seeded `FaultPlan` (`ray_tpu/_private/fault_injection.py`) replaces
ad-hoc SIGKILLs with named, replayable injection points. This matrix
drives the plan through RPC loss/duplication/delay, delayed heartbeat
handling, worker-spawn failure (including the crash-loop breaker), node
kill during a live Tune run, and a kill landed *between* one train rank's
shard persist and the gang checkpoint commit — proving walk-back to the
last gang-durable checkpoint.

Activation is per-process via the RAY_TPU_CHAOS env var: daemons spawned
while the var is set parse their own plan, so a fault can be scoped to one
node by setting the var only around that node's spawn (the driver process
keeps no plan — it was imported before the var existed).

Reference ground: `python/ray/tests/test_chaos.py` and
`python/ray/_private/test_utils.py` (WorkerKillerActor / NodeKillerActor),
made seeded and deterministic.
"""

import asyncio
import os
import threading
import time
from contextlib import contextmanager

import numpy as np
import pytest

import ray_tpu
from ray_tpu._private import fault_injection as fi
from ray_tpu._private.node import Cluster

pytestmark = pytest.mark.chaos


@contextmanager
def chaos_env(spec: str):
    """Export RAY_TPU_CHAOS so daemons spawned inside the block parse the
    plan; the test process itself stays plan-free."""
    os.environ[fi.ENV_VAR] = spec
    try:
        yield
    finally:
        os.environ.pop(fi.ENV_VAR, None)


# ---------------------------------------------------------------------------
# plan: parsing + determinism (no cluster)
# ---------------------------------------------------------------------------


def test_fault_plan_parsing():
    p = fi.FaultPlan(
        "seed=3;rpc_drop=0.1;rpc_delay=0.5:0.02;rpc_dup=0.05;"
        "rpc_recv_drop=0.2;rpc_recv_delay=0.004;"
        "rpc_match=heartbeat|pull;heartbeat_delay=0.25;heartbeat_drop=0.1;"
        "health_delay=0.05;spawn_fail=3;lease_delay=0.01;"
        "pull_delay=1.0:0.002;kill_node=heartbeats:4;commit_kill=1:2")
    assert p.seed == 3
    assert p.rpc_drop == 0.1 and p.rpc_dup == 0.05
    assert p.rpc_delay == (0.5, 0.02)
    assert p.rpc_recv_drop == 0.2
    assert p.rpc_recv_delay == (1.0, 0.004)  # bare seconds -> p=1
    assert p.rpc_match == ("heartbeat", "pull")
    assert p.heartbeat_delay == 0.25 and p.heartbeat_drop == 0.1
    assert p.health_delay == 0.05
    assert p.spawn_fail == 3
    assert p.lease_delay == (1.0, 0.01)
    assert p.pull_delay == (1.0, 0.002)
    assert p.kill_node == ("heartbeats", 4)
    assert p.commit_kill == (1, 2)

    # method scoping
    assert p.rpc_send("other_method") is None
    # an empty plan injects nothing
    empty = fi.FaultPlan("")
    assert empty.rpc_send("heartbeat") is None
    assert empty.rpc_recv("heartbeat") is None

    with pytest.raises(ValueError, match="probability"):
        fi.FaultPlan("rpc_drop=1.5")
    with pytest.raises(ValueError, match="unknown chaos key"):
        fi.FaultPlan("frobnicate=1")
    with pytest.raises(ValueError, match="kill_node"):
        fi.FaultPlan("kill_node=tasks:3")
    with pytest.raises(ValueError, match="key=value"):
        fi.FaultPlan("rpc_drop")


def test_fault_plan_env_activation():
    # no env var -> no plan, and the injection-point guard is a single
    # module-global None check
    assert fi._PLAN is None
    assert fi.init_from_env() is None
    try:
        os.environ[fi.ENV_VAR] = "seed=2;rpc_drop=0.5"
        p = fi.init_from_env()
        assert p is not None and fi._PLAN is p and p.seed == 2
    finally:
        os.environ.pop(fi.ENV_VAR, None)
        fi.init_from_env()
    assert fi._PLAN is None


def test_fault_plan_determinism():
    """The same seed replays the identical fault schedule: decisions are
    per-site RNG streams, a pure function of (seed, site, draw index)."""
    spec = ("seed=41;rpc_drop=0.3;rpc_dup=0.2;rpc_delay=0.4:0.01;"
            "rpc_recv_drop=0.25;heartbeat_drop=0.5;spawn_fail=2;"
            "pull_delay=0.5:0.003;lease_delay=0.5:0.001")

    def drive(plan: fi.FaultPlan):
        decisions = []
        for i in range(300):
            decisions.append(plan.rpc_send(f"method_{i % 7}"))
            decisions.append(plan.rpc_recv(f"method_{i % 5}"))

        async def drive_async():
            # zero-delay async sites still draw from their streams
            for _ in range(50):
                decisions.append(await plan.gcs_heartbeat())
                await plan.object_pull()
                await plan.lease_request()

        asyncio.run(drive_async())
        for _ in range(4):
            try:
                plan.spawn_attempt()
                decisions.append("spawn_ok")
            except fi.ChaosError:
                decisions.append("spawn_fail")
        return decisions

    a, b = fi.FaultPlan(spec), fi.FaultPlan(spec)
    da, db = drive(a), drive(b)
    assert da == db
    assert a.schedule == b.schedule and len(a.schedule) > 0
    # draws landed on both faulting and non-faulting outcomes
    assert any(d is not None for d in da if not isinstance(d, (str, bool)))
    # a different seed produces a different schedule
    c = fi.FaultPlan(spec.replace("seed=41", "seed=42"))
    assert drive(c) != da


# ---------------------------------------------------------------------------
# gang-durable commit barrier (unit, no cluster)
# ---------------------------------------------------------------------------


def test_gang_commit_barrier_unit(tmp_path):
    """report(checkpoint=) must not return until the controller acks; an
    abort releases the reporter with an error instead of wedging it."""
    from ray_tpu.air.checkpoint import Checkpoint
    from ray_tpu.train._internal.session import SessionConfig, _TrainSession

    sess = _TrainSession(SessionConfig(
        experiment_name="t", storage_path=str(tmp_path), world_rank=0,
        world_size=2, local_rank=0, local_world_size=2, node_rank=0,
        trial_dir=str(tmp_path / "trial"), gang_commit=True))
    state = {"returned": False, "error": None}

    def reporter():
        try:
            sess.report({"step": 1},
                        checkpoint=Checkpoint.from_dict({"x": 1}))
            state["returned"] = True
        except BaseException as e:  # noqa: BLE001
            state["error"] = e

    t = threading.Thread(target=reporter, daemon=True)
    t.start()
    item = sess.result_queue.get(timeout=10)
    assert item["gang_commit"] is True and item["report_index"] == 0
    # the shard is durable and the report drained — but with no ack the
    # barrier must hold
    time.sleep(0.3)
    assert not state["returned"] and state["error"] is None
    sess.ack_commit(0)
    t.join(timeout=10)
    assert state["returned"] and state["error"] is None

    # metrics-only reports never arm the barrier
    t2 = threading.Thread(
        target=lambda: sess.report({"step": 2}), daemon=True)
    t2.start()
    assert sess.result_queue.get(timeout=10).get("gang_commit") is None
    t2.join(timeout=10)
    assert not t2.is_alive()

    # abort releases a blocked reporter with an error
    state2 = {"error": None}

    def reporter2():
        try:
            sess.report({"step": 3},
                        checkpoint=Checkpoint.from_dict({"x": 3}))
        except BaseException as e:  # noqa: BLE001
            state2["error"] = e

    t3 = threading.Thread(target=reporter2, daemon=True)
    t3.start()
    sess.result_queue.get(timeout=10)
    sess.abort_commit("gang teardown")
    t3.join(timeout=10)
    assert isinstance(state2["error"], RuntimeError)
    assert "gang teardown" in str(state2["error"])


def test_incomplete_checkpoint_rejected(tmp_path):
    """The controller's commit gate refuses to register a sharded
    checkpoint that is missing shard contributions."""
    import json

    import jax.numpy as jnp

    from ray_tpu.air.checkpoint import Checkpoint
    from ray_tpu.train import array_checkpoint as ac
    from ray_tpu.train._internal.checkpoint_manager import (
        CheckpointManager,
        IncompleteCheckpointError,
    )

    d = str(tmp_path / "ck")
    ac.save_sharded(d, {"a": jnp.ones((4,))})
    ipath = os.path.join(
        d, [f for f in os.listdir(d) if f.startswith("asv_index")][0])
    with open(ipath) as f:
        rec = json.load(f)
    rec["num_processes"] = 2  # a second writer that never finished
    with open(ipath, "w") as f:
        json.dump(rec, f)

    mgr = CheckpointManager()
    with pytest.raises(IncompleteCheckpointError):
        mgr.register_checkpoint(Checkpoint(d), {"step": 1},
                                require_usable=True)
    assert mgr.latest_checkpoint is None
    # without the gate (non-gang callers) registration still works
    mgr.register_checkpoint(Checkpoint(d), {"step": 1})
    assert mgr.latest_checkpoint is not None


# ---------------------------------------------------------------------------
# satellite hardening (unit, no cluster)
# ---------------------------------------------------------------------------


def test_merge_wire_rejects_pip_plus_conda():
    """ADVICE #1: a job-level conda merged with a per-actor pip (or vice
    versa) must raise, not silently prefer pip at spawn time."""
    from ray_tpu._private import runtime_env as re_mod

    base = {"conda": {"name": "base-env"}, "_hash": "a"}
    override = {"pip": {"packages": ["x"]}, "_hash": "b"}
    with pytest.raises(ValueError, match="pip and conda"):
        re_mod.merge_wire(base, override)
    with pytest.raises(ValueError, match="pip and conda"):
        re_mod.merge_wire(override, base)
    # either alone merges fine
    merged = re_mod.merge_wire({"env_vars": {"A": "1"}, "_hash": "c"},
                               override)
    assert merged["pip"] == {"packages": ["x"]} and "_hash" in merged


def test_conda_empty_stdout_is_setup_error(monkeypatch):
    """ADVICE #2: `conda run` exiting 0 with empty stdout must be a
    deterministic RuntimeEnvSetupError (IndexError would read as
    transient and respawn forever while leases hang)."""
    import subprocess

    from ray_tpu._private import runtime_env as re_mod

    monkeypatch.setattr(re_mod, "_conda_exe", lambda: "/bin/conda-stub")
    monkeypatch.setattr(
        re_mod.subprocess, "run",
        lambda *a, **k: subprocess.CompletedProcess(a, 0, stdout="",
                                                    stderr="boom"))
    re_mod._conda_named_cache.pop("ghost-env", None)
    with pytest.raises(re_mod.RuntimeEnvSetupError,
                       match="no interpreter path"):
        re_mod.ensure_conda_env({"name": "ghost-env"})


def test_store_client_merges_legacy_table_dir(tmp_path):
    """ADVICE #4: when both the legacy and canonical table dirs exist,
    legacy key files merge into the canonical dir (existing keys win)
    instead of being orphaned on restore."""
    import pickle

    from ray_tpu._private.store_client import FileStoreClient
    from urllib.parse import quote

    root = tmp_path / "store"
    legacy = root / "kv:default"          # pre-quote encoding
    canon = root / quote("kv:default", safe="")
    legacy.mkdir(parents=True)
    canon.mkdir(parents=True)
    k_old, k_both, k_new = b"\x01".hex(), b"\x02".hex(), b"\x03".hex()
    (legacy / k_old).write_bytes(pickle.dumps("legacy-only"))
    (legacy / k_both).write_bytes(pickle.dumps("legacy-version"))
    (canon / k_both).write_bytes(pickle.dumps("canonical-version"))
    (canon / k_new).write_bytes(pickle.dumps("canonical-only"))

    store = FileStoreClient(str(root))
    table = store.get_all("kv:default")
    assert table[b"\x01"] == "legacy-only"          # recovered
    assert table[b"\x02"] == "canonical-version"    # newer write kept
    assert table[b"\x03"] == "canonical-only"
    assert not legacy.exists()                       # merged away


# ---------------------------------------------------------------------------
# chaos matrix: live cluster runs under an active plan
# ---------------------------------------------------------------------------


def _simple_task_workload(n: int = 60) -> None:
    @ray_tpu.remote
    def double(x):
        return 2 * x

    got = ray_tpu.get([double.remote(i) for i in range(n)], timeout=120)
    assert got == [2 * i for i in range(n)]


def _session_logs_contain(pattern: str) -> bool:
    """Grep the live init() cluster's daemon/worker logs for evidence the
    chaos plan actually fired in the target process."""
    import glob

    from ray_tpu._private import worker_api

    state = worker_api._global_state
    if state is None or state.cluster is None:
        return False
    for path in glob.glob(
            os.path.join(state.cluster.session_dir, "logs", "*")):
        try:
            with open(path, errors="replace") as f:
                if pattern in f.read():
                    return True
        except OSError:
            continue
    return False


def test_chaos_rpc_faults_during_train(tmp_path):
    """RPC loss/duplication/delay scoped to the heartbeat plane while a
    2-worker Train run reports checkpoints: the run must complete and
    the node must stay alive (drops are i.i.d. at p=0.3 — nowhere near
    the 10-consecutive-miss death threshold)."""
    from ray_tpu import train
    from ray_tpu.air import RunConfig, ScalingConfig
    from ray_tpu.train.backend import JaxConfig

    with chaos_env("seed=5;rpc_drop=0.3;rpc_dup=0.2;rpc_delay=0.3:0.01;"
                   "rpc_match=heartbeat"):
        ray_tpu.init(num_cpus=8, object_store_memory=128 * 1024 * 1024)
    try:
        def loop(config):
            from ray_tpu import train as train_mod
            from ray_tpu.air.checkpoint import Checkpoint

            for i in range(3):
                train_mod.report(
                    {"step": i + 1},
                    checkpoint=Checkpoint.from_dict({"step": i + 1}))

        trainer = train.JaxTrainer(
            loop,
            backend_config=JaxConfig(distributed="off", platform="cpu"),
            scaling_config=ScalingConfig(num_workers=2),
            run_config=RunConfig(storage_path=str(tmp_path / "results"),
                                 name="rpc_chaos"),
        )
        result = trainer.fit()
        assert result.metrics["step"] == 3
        assert all(n["Alive"] for n in ray_tpu.nodes())
    finally:
        ray_tpu.shutdown()


def test_chaos_heartbeat_delay(tmp_path):
    """Delayed heartbeat HANDLING at the GCS (0.6s per beat, under the
    5s death threshold): liveness bookkeeping lags but nothing dies and
    the task plane stays correct."""
    with chaos_env("seed=6;heartbeat_delay=0.6"):
        ray_tpu.init(num_cpus=4, object_store_memory=128 * 1024 * 1024)
    try:
        _simple_task_workload()
        assert all(n["Alive"] for n in ray_tpu.nodes())
    finally:
        ray_tpu.shutdown()


def test_chaos_spawn_fail_recovers():
    """First two worker spawns fail (non-RuntimeEnvSetupError): the
    raylet must count them in the crash-loop breaker AND immediately
    re-drive dispatch, so the third spawn serves the lease — without the
    re-dispatch (ADVICE #5) this hangs until an unrelated event."""
    with chaos_env("seed=8;spawn_fail=2"):
        ray_tpu.init(num_cpus=4, object_store_memory=128 * 1024 * 1024)
    try:
        start = time.monotonic()
        _simple_task_workload(n=8)
        assert time.monotonic() - start < 60
        # the plan really fired in the raylet (not a silently inactive env)
        assert _session_logs_contain("injected worker spawn failure")
    finally:
        ray_tpu.shutdown()


def test_chaos_spawn_fail_breaker_trips():
    """Persistent spawn failure must trip the crash-loop breaker and
    fail the waiting leases with a diagnosable error instead of hanging
    them forever (ADVICE #5's second half)."""
    with chaos_env("seed=9;spawn_fail=1000"):
        ray_tpu.init(num_cpus=4, object_store_memory=128 * 1024 * 1024)
    try:
        @ray_tpu.remote
        def probe():
            return 1

        with pytest.raises(Exception, match="crash-loop|spawn"):
            ray_tpu.get(probe.remote(), timeout=90)
    finally:
        ray_tpu.shutdown()


def test_chaos_node_kill_during_tune():
    """Abrupt node death (plan-driven os._exit after 6 heartbeats on the
    victim raylet only) during a live Tune run: FailureConfig retries
    must carry every trial to completion on the surviving node, and the
    GCS must have marked the victim dead."""
    from ray_tpu import tune
    from ray_tpu.air.config import FailureConfig, RunConfig

    cluster = Cluster(head_resources={"CPU": 2.0})
    with chaos_env("seed=12;kill_node=heartbeats:6"):
        victim = cluster.add_node({"CPU": 4.0})
    ray_tpu.init(address=cluster.gcs_addr)
    try:
        def trainable(config):
            for i in range(8):
                time.sleep(0.25)
                tune.report({"step": i, "value": config["x"] * i})

        tuner = tune.Tuner(
            trainable,
            param_space={"x": tune.grid_search([1, 2, 3, 4])},
            tune_config=tune.TuneConfig(metric="value", mode="max"),
            run_config=RunConfig(
                storage_path="/tmp/ray_tpu_chaos_nodekill",
                name=f"nodekill_{int(time.time())}",
                failure_config=FailureConfig(max_failures=8),
            ),
        )
        grid = tuner.fit()
        assert len(grid) == 4
        for res in grid:
            assert res.error is None, f"trial failed: {res.error}"
            assert res.metrics["step"] == 7
        # the plan actually fired: the victim raylet process is gone and
        # the GCS noticed
        assert victim.process.proc.poll() is not None
        dead = [n for n in ray_tpu.nodes() if not n["Alive"]]
        assert dead, "GCS never marked the chaos-killed node dead"
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()


# ---------------------------------------------------------------------------
# the gang-commit kill window (integration)
# ---------------------------------------------------------------------------


def _make_commit_kill_loop():
    # factory so cloudpickle serializes by value (workers can't import
    # this test module)
    def _loop(config):
        import jax
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from ray_tpu import train as train_mod
        from ray_tpu.train import array_checkpoint as ac_mod

        devs = jax.devices()
        mesh = Mesh(np.array(devs).reshape(len(devs)), ("dp",))
        w0 = np.arange(32, dtype=np.float32).reshape(8, 4)
        state = {
            "w": jax.make_array_from_callback(
                (8, 4), NamedSharding(mesh, P("dp")), lambda idx: w0[idx]),
            "step": jax.make_array_from_callback(
                (), NamedSharding(mesh, P()),
                lambda idx: np.zeros((), np.int32)),
        }
        start = 0
        ckpt = train_mod.get_checkpoint()
        if ckpt is not None and ac_mod.is_sharded_checkpoint(ckpt):
            state = ac_mod.restore_sharded(ckpt, state)
            start = int(np.asarray(state["step"].addressable_shards[0].data))

        @jax.jit
        def update(s):
            return {"w": s["w"] * 2.0 + 1.0, "step": s["step"] + 1}

        for i in range(start, 3):
            state = update(state)
            fp = float(sum(np.asarray(s.data).sum()
                           for s in state["w"].addressable_shards
                           if s.replica_id == 0))
            # On the fresh attempt the chaos plan kills rank 1 inside
            # report(): after its step-2 shard persist, before the gang
            # commit (commit_kill=1:1 -> report_index 1).
            train_mod.report(
                {"step": i + 1, "fp": fp, "resumed_from": start},
                checkpoint=ac_mod.save_to_checkpoint(state))

    return _loop


def test_commit_kill_walks_back_to_gang_durable(tmp_path):
    """THE gang-durability proof: a rank killed between its own shard
    persist and the gang commit leaves a checkpoint that is durable on
    disk but never registered — walk-back must land on the previous
    (gang-committed) checkpoint, never on the half-committed one, and
    never below the last commit."""
    from ray_tpu import train
    from ray_tpu.air import FailureConfig, RunConfig, ScalingConfig
    from ray_tpu.train.backend import JaxConfig

    with chaos_env("seed=11;commit_kill=1:1"):
        ray_tpu.init(num_cpus=8, object_store_memory=256 * 1024 * 1024)
    try:
        trainer = train.JaxTrainer(
            _make_commit_kill_loop(),
            backend_config=JaxConfig(
                distributed="on", platform="cpu",
                xla_flags="--xla_force_host_platform_device_count=2"),
            scaling_config=ScalingConfig(num_workers=2),
            run_config=RunConfig(
                storage_path=str(tmp_path / "results"), name="commitkill",
                failure_config=FailureConfig(max_failures=1)),
        )
        result = trainer.fit()
        assert result.metrics["step"] == 3
        # Walk-back landed exactly on the last gang-COMMITTED checkpoint
        # (step 1). The step-2 checkpoint was fully durable (both ranks
        # persisted before the kill) but the controller never registered
        # it — resuming from it would have made report()'s return a lie.
        assert result.metrics["resumed_from"] == 1
        # bit-identical math across the restore
        w = np.arange(32, dtype=np.float32).reshape(8, 4)
        for _ in range(3):
            w = w * 2.0 + 1.0
        assert result.metrics["fp"] == pytest.approx(float(w[:4].sum()),
                                                     abs=0.0)
    finally:
        ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# serve.llm: replica kill mid-stream (seeded, deterministic)
# ---------------------------------------------------------------------------


def test_chaos_llm_replica_kill_midstream():
    """Kill the replica serving a token stream mid-generation. The
    handle must fail over to the surviving replica and replay-skip the
    already-delivered chunks (greedy decode is deterministic and both
    replicas share a seed, so the resumed stream is the SAME stream) —
    no accepted request is lost. (The dead replica's KV arena was its
    own device memory and went with the process.)"""
    from ray_tpu import serve
    from ray_tpu.serve.llm import LLMDeployment

    ray_tpu.init(num_cpus=8, num_tpus=0,
                 object_store_memory=256 * 1024 * 1024)
    try:
        class SlowLLM(LLMDeployment):
            """Per-chunk delay so the kill reliably lands mid-stream."""

            def generate(self, prompt, max_new_tokens=16,
                         timeout_s=None):
                for chunk in LLMDeployment.generate(
                        self, prompt, max_new_tokens, timeout_s):
                    time.sleep(0.05)
                    yield chunk

        app = serve.deployment(name="llm", num_replicas=2)(
            SlowLLM).bind(seed=0)
        handle = serve.run(app)
        ctrl = ray_tpu.get_actor("SERVE_CONTROLLER")
        # prime the controller's metrics cache pre-kill
        ray_tpu.get(ctrl.reconcile_now.remote(), timeout=60)

        n_tokens = 24
        gen = handle.generate.options(stream=True).remote(
            [5, 9, 3], n_tokens)
        tokens = [next(gen)["token"] for _ in range(4)]

        # find the replica carrying the stream (ongoing >= 1), then
        # murder it
        info = ray_tpu.get(ctrl.get_replicas.remote("llm"), timeout=30)
        serving = None
        for r in info["replicas"]:
            m = ray_tpu.get(r.get_metrics.remote(), timeout=30)
            if m["ongoing"] >= 1 and serving is None:
                serving = r
        assert serving is not None
        ray_tpu.kill(serving)

        # the stream completes on the survivor via replay
        for chunk in gen:
            tokens.append(chunk["token"])
        assert len(tokens) == n_tokens

        # ground truth: a fresh request (now served by the survivor)
        rerun = handle.generate_once.remote([5, 9, 3], n_tokens).result(
            timeout=120)
        assert tokens == rerun  # the failed-over stream lost nothing

        # flight-recorder regression (ISSUE 12): the failed-over stream
        # produced exactly ONE client record (the resubmit's temporary
        # response is neutered), the survivor-replayed chunks are
        # counted but never timed, and TPOT is averaged over delivered-
        # token gaps only — the recovery gap is excluded, so every
        # timed gap carries the 50 ms per-chunk delay.
        from ray_tpu.util import request_recorder as rr

        fo = [r for r in rr.ring().recent()
              if r.role == "client" and r.outcome == "failed_over"]
        assert len(fo) == 1
        crec = fo[0]
        assert crec.tokens_out == n_tokens
        assert crec.replayed_tokens >= 4  # >= chunks delivered pre-kill
        # one untimed first chunk per stream half: pre-kill k chunks
        # give k-1 gaps, post-failover (n-k) chunks give n-k-1 gaps
        assert crec.attrs["timed_gaps"] == n_tokens - 2
        assert crec.tpot_ms is not None and crec.tpot_ms >= 40.0

    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def test_chaos_llm_replica_kill_midstream_prefix_chunked():
    """Mid-stream replica kill with the shared-prefix cache and chunked
    prefill both on. The failover replay contract must survive both:
    replicas share a seed and greedy decode is deterministic, so the
    survivor's resumed stream is the SAME stream even though its prefill
    rides aliased prefix-cache pages and the chunk program."""
    from ray_tpu import serve
    from ray_tpu.serve.llm import LLMDeployment

    ray_tpu.init(num_cpus=8, num_tpus=0,
                 object_store_memory=256 * 1024 * 1024)
    try:
        class SlowLLM(LLMDeployment):
            def generate(self, prompt, max_new_tokens=16,
                         timeout_s=None):
                for chunk in LLMDeployment.generate(
                        self, prompt, max_new_tokens, timeout_s):
                    time.sleep(0.05)
                    yield chunk

        # small buckets keep warmup well under the controller's 10 s
        # liveness-poll timeout; the chunked-prefill window lets the
        # 36-token prompt through the 16-token top bucket
        app = serve.deployment(name="llm", num_replicas=2)(
            SlowLLM).bind(
                seed=0,
                engine_config={"prefix_cache": 1,
                               "prefill_chunk": 8, "block_size": 4,
                               "batch_buckets": (1, 2),
                               "prefill_buckets": (8, 16)})
        handle = serve.run(app)
        ctrl = ray_tpu.get_actor("SERVE_CONTROLLER")
        ray_tpu.get(ctrl.reconcile_now.remote(), timeout=60)

        # a 36-token prompt spans 8 full KV pages (block 4): prime
        # BOTH replicas' prefix caches so wherever the failed-over
        # stream replays, its prefill aliases cached pages
        rng = np.random.RandomState(18)
        prompt = [int(t) for t in rng.randint(1, 500, size=36)]
        n_tokens = 24
        for _ in range(4):
            handle.generate_once.remote(prompt, 4).result(timeout=120)

        gen = handle.generate.options(stream=True).remote(
            prompt, n_tokens)
        tokens = [next(gen)["token"] for _ in range(4)]

        info = ray_tpu.get(ctrl.get_replicas.remote("llm"), timeout=30)
        serving = None
        for r in info["replicas"]:
            m = ray_tpu.get(r.get_metrics.remote(), timeout=30)
            if m["ongoing"] >= 1 and serving is None:
                serving = r
        assert serving is not None
        ray_tpu.kill(serving)

        for chunk in gen:                  # survivor replays + resumes
            tokens.append(chunk["token"])
        assert len(tokens) == n_tokens

        rerun = handle.generate_once.remote(prompt, n_tokens).result(
            timeout=120)
        assert tokens == rerun             # failed-over stream lost nothing

        # the survivor's prefill aliased the primed prefix pages: a hit
        # rate above 0 is hit tokens above 0 (the controller may not have
        # reconciled the death yet, so polls can still hit the corpse:
        # skip it)
        info = ray_tpu.get(ctrl.get_replicas.remote("llm"), timeout=30)
        live = []
        for r in info["replicas"]:
            try:
                live.append(ray_tpu.get(r.get_metrics.remote(),
                                        timeout=30))
            except Exception:
                pass
        assert any(m.get("prefix_cache_hit_rate", 0) > 0 for m in live)
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def test_chaos_llm_replica_kill_midstream_window_family():
    """Mid-stream replica kill with pages of two kinds (the AFMoE family:
    window layers keep a ring of pages a sequence, full layers the whole
    length). The dead replica's arena went with its process; the survivor
    replays the stream from the prompt, through chunked prefill and a ring
    that wraps (36 + 24 tokens against a window of 8), to the SAME stream,
    and ends with no page of either kind live."""
    from ray_tpu import serve
    from ray_tpu.serve.llm import LLMDeployment

    ray_tpu.init(num_cpus=8, num_tpus=0,
                 object_store_memory=256 * 1024 * 1024)
    try:
        class SlowLLM(LLMDeployment):
            def generate(self, prompt, max_new_tokens=16,
                         timeout_s=None):
                for chunk in LLMDeployment.generate(
                        self, prompt, max_new_tokens, timeout_s):
                    time.sleep(0.05)
                    yield chunk

        app = serve.deployment(name="llm", num_replicas=2)(
            SlowLLM).bind(
                model="afmoe", seed=0,
                engine_config={"prefix_cache": 0,
                               "prefill_chunk": 8, "block_size": 4,
                               "batch_buckets": (1, 2),
                               "prefill_buckets": (8, 16)})
        handle = serve.run(app)
        ctrl = ray_tpu.get_actor("SERVE_CONTROLLER")
        ray_tpu.get(ctrl.reconcile_now.remote(), timeout=60)

        rng = np.random.RandomState(18)
        prompt = [int(t) for t in rng.randint(1, 500, size=36)]
        n_tokens = 24
        gen = handle.generate.options(stream=True).remote(
            prompt, n_tokens)
        tokens = [next(gen)["token"] for _ in range(4)]

        info = ray_tpu.get(ctrl.get_replicas.remote("llm"), timeout=30)
        serving = None
        for r in info["replicas"]:
            m = ray_tpu.get(r.get_metrics.remote(), timeout=30)
            if m["ongoing"] >= 1 and serving is None:
                serving = r
        assert serving is not None
        ray_tpu.kill(serving)

        for chunk in gen:                  # survivor replays + resumes
            tokens.append(chunk["token"])
        assert len(tokens) == n_tokens

        rerun = handle.generate_once.remote(prompt, n_tokens).result(
            timeout=120)
        assert tokens == rerun             # failed-over stream lost nothing

        # the leak gate, both kinds: whoever answers now is alive, has
        # served a whole stream through a ring that wrapped, holds nothing
        m = handle.engine_metrics.remote().result(timeout=60)
        assert m["model"] == "afmoe" and m["kv_pages_window_seq_max"] == 3
        assert (m["kv_pages_live_window"], m["kv_pages_live_full"],
                m["kv_pages_live"]) == (0, 0, 0)
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# timed wall-clock fault schedules (`at=` grammar) + post-mortem replay
# ---------------------------------------------------------------------------


def test_timed_schedule_parsing():
    p = fi.FaultPlan(
        "seed=4;at=5:kill@train|3.5:data_stall:2.5@worker|7:ckpt_fail:2"
        "|9:hb_brownout:1.5@gcs|11:crash_loop:3@raylet")
    assert p.timed == [
        fi.TimedFault(5.0, "kill", 0.0, "train"),
        fi.TimedFault(3.5, "data_stall", 2.5, "worker"),
        fi.TimedFault(7.0, "ckpt_fail", 2.0, None),
        fi.TimedFault(9.0, "hb_brownout", 1.5, "gcs"),
        fi.TimedFault(11.0, "crash_loop", 3.0, "raylet"),
    ]
    # bare ckpt_fail defaults to one persist; repeated at= keys accumulate
    q = fi.FaultPlan("at=1:ckpt_fail;at=2:kill@train")
    assert q.timed == [fi.TimedFault(1.0, "ckpt_fail", 1.0, None),
                       fi.TimedFault(2.0, "kill", 0.0, "train")]

    # drop_objects: bare form sweeps half the sealed set; the fraction
    # must stay inside (0, 1]
    r = fi.FaultPlan("at=4:drop_objects@raylet|6:drop_objects:0.25")
    assert r.timed == [fi.TimedFault(4.0, "drop_objects", 0.5, "raylet"),
                       fi.TimedFault(6.0, "drop_objects", 0.25, None)]
    with pytest.raises(ValueError, match="outside"):
        fi.FaultPlan("at=1:drop_objects:1.5")
    with pytest.raises(ValueError, match="outside"):
        fi.FaultPlan("at=1:drop_objects:0")

    with pytest.raises(ValueError, match="unknown role"):
        fi.FaultPlan("at=1:kill@mainframe")
    with pytest.raises(ValueError, match="unknown fault"):
        fi.FaultPlan("at=1:meteor")
    with pytest.raises(ValueError, match="kill takes no argument"):
        fi.FaultPlan("at=1:kill:2")
    with pytest.raises(ValueError, match="requires an argument"):
        fi.FaultPlan("at=1:data_stall")
    with pytest.raises(ValueError, match="not <offset>"):
        fi.FaultPlan("at=5")


def test_timed_fire_once_and_replay(tmp_path, monkeypatch):
    """Timed entries fire at their offsets, flip the injection state the
    fault sites consume, are gated to ONE fire per soak run by the
    once-sentinels, and the post-mortem artifact rebuilds the identical
    plan via `from_artifact`."""
    monkeypatch.setenv(fi.LOG_ENV, str(tmp_path))
    spec = "seed=2;at=0.05:ckpt_fail:2|0.1:data_stall:0.2|0.1:hb_brownout:30"
    p = fi.FaultPlan(spec)
    p.arm_timed("worker")   # unroled entries arm in any process
    deadline = time.monotonic() + 5
    while len(p.timed_fired) < 3 and time.monotonic() < deadline:
        time.sleep(0.02)
    assert sorted(f["fault"] for f in p.timed_fired) == \
        ["ckpt_fail", "data_stall", "hb_brownout"]

    # state the fault sites consume: two persist failures, then clean
    with pytest.raises(fi.ChaosError, match="chaos"):
        p.checkpoint_persist()
    with pytest.raises(fi.ChaosError, match="chaos"):
        p.checkpoint_persist()
    p.checkpoint_persist()   # pending exhausted
    # brownout window active: the GCS handler drops the heartbeat
    assert asyncio.run(p.gcs_heartbeat()) is True

    # once-sentinels: a second plan (a restarted attempt re-reading the
    # same env spec) re-arms but never re-fires
    q = fi.FaultPlan(spec)
    q.arm_timed("worker")
    time.sleep(0.4)
    assert q.timed_fired == []
    q._timed_stop.set()

    # post-mortem artifact -> exact replay
    path = p.export_artifact(str(tmp_path / "chaos-test.json"))
    r = fi.FaultPlan.from_artifact(path)
    assert r.spec == spec and r.seed == p.seed and r.timed == p.timed
    p._timed_stop.set()


def test_timed_epoch_anchor_expiry(tmp_path, monkeypatch):
    """With RAY_TPU_CHAOS_EPOCH set, offsets are wall-clock soak time:
    a process arming AFTER an entry's fire time (a restarted attempt)
    records it as expired instead of firing it into the fresh attempt;
    a still-future entry fires at its original wall-clock slot."""
    monkeypatch.setenv(fi.LOG_ENV, str(tmp_path))
    monkeypatch.setenv(fi.EPOCH_ENV, repr(time.time() - 10.0))
    p = fi.FaultPlan("seed=3;at=5:data_stall:1|10.3:ckpt_fail")
    p.arm_timed("train")
    time.sleep(0.7)
    # offset 5 was 5 s in the past at arm -> expired, never fired
    assert [f["fault"] for f in p.timed_fired] == ["ckpt_fail"]
    assert any(site == "timed.data_stall" and "expired" in decision
               for site, _, decision in p.schedule)
    # and the anchored entry fired ~0.3 s after arm, not 10.3 s after
    p._timed_stop.set()


def test_timed_stop_event_cancels():
    p = fi.FaultPlan("seed=1;at=0.3:ckpt_fail")
    p.arm_timed("worker")
    p._timed_stop.set()      # uninstall()/install() path
    time.sleep(0.5)
    assert p.timed_fired == []


def test_timed_two_fault_smoke(tmp_path):
    """Seeded two-fault timed schedule against a live 2-worker train
    run: the stall fires first (harmless), the persist failure fails the
    attempt and FailureConfig walks training back to the last
    gang-committed checkpoint. Both firings are exported as replayable
    post-mortem artifacts. Gated N-of-N by tools/flake_gate.py."""
    from ray_tpu import train
    from ray_tpu.air import FailureConfig, RunConfig, ScalingConfig
    from ray_tpu.train.backend import JaxConfig

    log_dir = tmp_path / "chaos"
    spec = "seed=12;at=1.0:data_stall:0.5@train|2.5:ckpt_fail@train"
    os.environ[fi.LOG_ENV] = str(log_dir)
    with chaos_env(spec):
        ray_tpu.init(num_cpus=8, object_store_memory=256 * 1024 * 1024)
    try:
        def loop(config):
            from ray_tpu import train as train_mod
            from ray_tpu.air.checkpoint import Checkpoint

            start, resumed = 0, None
            ckpt = train_mod.get_checkpoint()
            if ckpt is not None:
                start = resumed = ckpt.to_dict()["step"]
            for i in range(start, 25):
                time.sleep(0.2)
                train_mod.report(
                    {"step": i + 1, "resumed_from": resumed},
                    checkpoint=Checkpoint.from_dict({"step": i + 1}))

        trainer = train.JaxTrainer(
            loop,
            backend_config=JaxConfig(distributed="off", platform="cpu"),
            scaling_config=ScalingConfig(num_workers=2),
            run_config=RunConfig(
                storage_path=str(tmp_path / "results"), name="timed",
                failure_config=FailureConfig(max_failures=2)),
        )
        result = trainer.fit()
        # the run completed across the injected walk-back
        assert result.metrics["step"] == 25
        assert result.metrics["resumed_from"] is not None
        assert result.metrics["resumed_from"] >= 1

        # both entries fired exactly once (once-sentinels), and every
        # faulted process exported an artifact that replays the plan
        import glob as glob_mod
        fired = []
        for path in glob_mod.glob(str(log_dir / "chaos-*.json")):
            import json
            art = json.loads(open(path).read())
            fired += [f["fault"] for f in art["timed_fired"]]
            replay = fi.FaultPlan.from_artifact(path)
            assert replay.spec == spec
            assert replay.timed == fi.FaultPlan(spec).timed
        assert sorted(fired) == ["ckpt_fail", "data_stall"]
        assert (log_dir / "once-ckpt_fail-2.5-train").exists()
        assert (log_dir / "once-data_stall-1-train").exists()
    finally:
        os.environ.pop(fi.LOG_ENV, None)
        ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# object-loss matrix rows: lineage recovery under timed faults
# ---------------------------------------------------------------------------


def _cluster_logs_contain(cluster, pattern: str) -> bool:
    import glob as glob_mod

    for path in glob_mod.glob(
            os.path.join(cluster.session_dir, "logs", "*")):
        try:
            with open(path, errors="replace") as f:
                if pattern in f.read():
                    return True
        except OSError:
            continue
    return False


def test_timed_kill_raylet_mid_pipeline_reconstructs(tmp_path):
    """Matrix row: `kill@raylet` lands mid-pipeline on the node holding
    stage-1's plasma outputs. Downstream consumers submitted AFTER the
    node death must still complete — the owner re-executes the lost
    producers from lineage on the surviving node — and the recovered
    arrays are bit-identical to a local recompute. Gated 5/5 by
    tools/flake_gate.py."""
    log_dir = tmp_path / "chaos"
    os.environ[fi.LOG_ENV] = str(log_dir)
    cluster = Cluster(head_resources={"CPU": 2.0},
                      object_store_memory=64 * 1024 * 1024)
    # arm the plan only around the victim's spawn: the kill is scoped to
    # that one raylet process
    with chaos_env("seed=7;at=3:kill@raylet"):
        victim = cluster.add_node({"CPU": 2.0, "scratch": 1.0})
    ray_tpu.init(address=cluster.gcs_addr)
    try:
        affinity = ray_tpu.NodeAffinitySchedulingStrategy(
            victim.node_id_hex, soft=True)

        @ray_tpu.remote(scheduling_strategy=affinity)
        def stage1(i):
            return (np.arange(250_000, dtype=np.uint32) * (i + 1)) \
                .astype(np.uint8)

        @ray_tpu.remote
        def stage2(x):
            return int(x.astype(np.uint64).sum())

        refs = [stage1.remote(i) for i in range(4)]
        ready, _ = ray_tpu.wait(refs, num_returns=len(refs), timeout=60)
        assert len(ready) == len(refs)

        # the plan fires ~3s after the victim raylet armed; wait for the
        # process to actually die so the consumers race nothing
        deadline = time.monotonic() + 60
        while victim.process.proc.poll() is None \
                and time.monotonic() < deadline:
            time.sleep(0.2)
        assert victim.process.proc.poll() is not None, \
            "chaos kill@raylet never fired"
        time.sleep(1.0)

        expect = [
            int((np.arange(250_000, dtype=np.uint32) * (i + 1))
                .astype(np.uint8).astype(np.uint64).sum())
            for i in range(4)
        ]
        outs = ray_tpu.get([stage2.remote(r) for r in refs],
                           timeout=240)
        assert outs == expect, "re-executed stage-1 outputs differ"
        # and the raw arrays really are bit-identical post-recovery
        arr0 = ray_tpu.get(refs[0], timeout=240)
        assert np.array_equal(
            arr0, (np.arange(250_000, dtype=np.uint32) * 1)
            .astype(np.uint8))
    finally:
        os.environ.pop(fi.LOG_ENV, None)
        ray_tpu.shutdown()
        cluster.shutdown()


def test_timed_drop_objects_sweep_recovers(tmp_path):
    """Matrix row: `drop_objects@raylet` force-deletes every sealed
    object on one node WITHOUT killing the process (silent-loss fault —
    the raylet keeps heartbeating, so only the pull path notices).
    Owned task returns must recover via lineage re-execution."""
    log_dir = tmp_path / "chaos"
    os.environ[fi.LOG_ENV] = str(log_dir)
    cluster = Cluster(object_store_memory=64 * 1024 * 1024)
    with chaos_env("seed=5;at=2:drop_objects:1.0@raylet"):
        cluster.add_node({"CPU": 2.0})
    ray_tpu.init(address=cluster.gcs_addr)
    try:
        @ray_tpu.remote
        def produce(i):
            return np.full(300_000, i + 1, np.uint8)

        refs = [produce.remote(i) for i in range(3)]
        ready, _ = ray_tpu.wait(refs, num_returns=len(refs), timeout=60)
        assert len(ready) == len(refs)

        # the sweep fires ~2s after the raylet armed and logs its kill
        # count — wait for the evidence before poking the store
        deadline = time.monotonic() + 60
        while not _cluster_logs_contain(
                cluster, "drop_objects force-deleted") \
                and time.monotonic() < deadline:
            time.sleep(0.3)
        assert _cluster_logs_contain(
            cluster, "drop_objects force-deleted"), \
            "drop_objects sweep never fired"

        outs = ray_tpu.get(refs, timeout=240)
        for i, out in enumerate(outs):
            assert out[0] == i + 1 and out.shape == (300_000,), \
                "post-sweep get returned wrong bytes"
    finally:
        os.environ.pop(fi.LOG_ENV, None)
        ray_tpu.shutdown()
        cluster.shutdown()
