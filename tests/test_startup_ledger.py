"""The start-up ledger: what a serving app and a training job leave in their
session's shards, what a steady window adds (nothing), and the benchmark's
readers over a recorded ledger and over hand-made rows."""

import json
import os

import pytest

from ray_tpu.util import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _names(rows, pid=None):
    return [r["name"] for r in rows if pid is None or r["pid"] == pid]


def _first(rows, name, pid=None):
    return next(r for r in rows
                if r["name"] == name and (pid is None or r["pid"] == pid))


def _assert_a_start_in_order(rows, user_pid):
    """Driver, raylet and worker stamps of one start, on one clock."""
    up, call = _first(rows, "cluster_up"), _first(rows, "deploy_call")
    spawn = _first(rows, "worker_spawn", user_pid)
    boot = _first(rows, "worker_boot", user_pid)
    entered = _first(rows, "user_entered", user_pid)
    assert up["pid"] == call["pid"] == os.getpid() != user_pid
    assert up["attrs"]["owns_cluster"] is True
    assert call["begin_ns"] == call["end_ns"]           # a mark
    assert entered["begin_ns"] == entered["end_ns"]
    # the spawn began in the raylet and ended at the worker's first line,
    # where its boot begins
    assert spawn["end_ns"] == boot["begin_ns"]
    assert spawn["attrs"]["tpu_chips"] == boot["attrs"]["tpu_chips"] == []
    order = [up["begin_ns"], up["end_ns"], call["begin_ns"],
             spawn["begin_ns"], spawn["end_ns"], boot["end_ns"],
             entered["begin_ns"]]
    assert order == sorted(order), order
    # a second of imports and RPCs at most between any two, on the CPU
    assert entered["begin_ns"] - up["begin_ns"] < 120e9
    for r in rows:      # the wall stamps lie as the ledger's clock has them
        assert abs((r["end"] - r["start"])
                   - (r["end_ns"] - r["begin_ns"]) / 1e9) < 1e-3
    assert rows == sorted(rows, key=lambda r: r["begin_ns"])


def test_a_serving_app_leaves_its_start_in_order_on_one_clock():
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu._private import worker_api

    class _TinyLLM:
        """A deployment that builds an engine and serves one request before it
        is up, as `LLMDeployment` does."""

        def __init__(self):
            from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine

            self.engine = LLMEngine(model="llama", engine_config=EngineConfig(
                num_pages=32, batch_buckets=(2,), prefill_buckets=(16,)))
            self.engine.start()
            self.engine.submit([1] * 8, 2).result(timeout=120)

        def rows(self):
            return {"pid": os.getpid(), "startup": tracing.startup_rows()}

    ray_tpu.init(num_cpus=2, num_tpus=0,
                 object_store_memory=64 * 1024 * 1024)
    try:
        session = worker_api._global_state.cluster.session_dir
        handle = serve.run(serve.deployment(name="tiny")(_TinyLLM).bind())
        seen = handle.rows.remote().result(timeout=120)
        serve.shutdown()
    finally:
        ray_tpu.shutdown()
    rows = tracing.collect_startup(session)
    pid = seen["pid"]
    _assert_a_start_in_order(rows, pid)
    assert _first(rows, "deploy_call")["attrs"] == {"entry": "serve.run"}
    assert _first(rows, "user_entered")["attrs"] == {"deployment": "_TinyLLM"}
    built = _first(rows, "engine_build", pid)
    assert built["begin_ns"] > _first(rows, "user_entered")["begin_ns"]
    assert built["attrs"]["model"] == "llama"
    assert built["attrs"]["kv_arena_bytes"] > 0
    # the engine's two programs, named as `compiled_step_calls` names them,
    # after the engine was built; the weights' eager programs inside it
    steps = [r for r in rows if r["name"] == "program" and r["pid"] == pid
             and r["attrs"]["door"] == "compiled_step"]
    assert [r["attrs"]["fn"] for r in steps] == ["prefill:16", "decode:2"]
    assert all(r["begin_ns"] > built["end_ns"] for r in steps)
    assert any(r["name"] == "program" and r["attrs"]["door"] == "jit"
               and built["begin_ns"] < r["begin_ns"] < built["end_ns"]
               for r in rows)
    # the replica's own list is its shard
    mine = [r for r in rows if r["pid"] == pid]
    assert sorted(seen["startup"], key=lambda r: r["begin_ns"]) == mine


def test_a_training_job_leaves_its_start_in_order_on_one_clock(tmp_path):
    import ray_tpu
    from ray_tpu import train
    from ray_tpu._private import worker_api
    from ray_tpu.air import RunConfig, ScalingConfig

    def loop(config):
        import jax.numpy as jnp

        runner = train.TrainStepRunner(lambda w, x: (w + x.sum(), w.sum()))
        w, loss = runner.run(jnp.zeros(4), jnp.ones(4))
        train.report({"loss": float(loss), "pid": os.getpid()})

    ray_tpu.init(num_cpus=4, num_tpus=0,
                 object_store_memory=64 * 1024 * 1024)
    try:
        session = worker_api._global_state.cluster.session_dir
        result = train.JaxTrainer(
            loop, scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(storage_path=str(tmp_path), name="tiny"),
        ).fit()
    finally:
        ray_tpu.shutdown()
    rows = tracing.collect_startup(session)
    pid = result.metrics["pid"]
    _assert_a_start_in_order(rows, pid)
    assert _first(rows, "deploy_call")["attrs"] == {"entry": "JaxTrainer.fit"}
    assert _first(rows, "user_entered")["attrs"] == {"world_rank": 0}
    (step,) = [r for r in rows if r["name"] == "program" and r["pid"] == pid
               and r["attrs"]["door"] == "compiled_step"]
    assert step["begin_ns"] > _first(rows, "user_entered")["begin_ns"]
    assert "engine_build" not in _names(rows)


def test_the_first_report_of_a_train_session_carries_the_rows(tmp_path):
    from ray_tpu.train._internal import session as session_mod

    tracing.startup_mark("before_training")
    sess = session_mod._TrainSession(session_mod.SessionConfig(
        experiment_name="e", storage_path=str(tmp_path), world_rank=0,
        world_size=1, local_rank=0, local_world_size=1, node_rank=0,
        trial_dir=str(tmp_path / "t")))
    sess.report({"a": 1})
    first = sess.result_queue.get(timeout=5)
    assert "before_training" in _names(first["startup"])
    sess.report({"a": 2})
    assert "startup" not in sess.result_queue.get(timeout=5)


def _ledger_state():
    from ray_tpu.parallel import compile_cache

    return compile_cache.listener_calls, len(tracing.startup_rows()), \
        compile_cache.cache_stats()["programs"]


def test_a_hundred_steady_engine_steps_add_no_row_and_call_no_listener():
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine

    engine = LLMEngine(model="llama", engine_config=EngineConfig(
        num_pages=64, batch_buckets=(2,), prefill_buckets=(16,)))
    tracing.clear_startup()     # an earlier test's rows may fill the list
    engine.start()
    try:
        for _ in range(2):      # both programs, and whatever jax compiles
            engine.submit([1] * 8, 4).result(timeout=120)   # eagerly, once
        before, steps = _ledger_state(), engine.metrics()["decode_steps"]
        for i in range(4):
            engine.submit([2 + i] * 8, 30).result(timeout=120)
        assert engine.metrics()["decode_steps"] - steps >= 100
        assert _ledger_state() == before
    finally:
        engine.stop()


def test_a_hundred_steady_train_steps_add_no_row_and_call_no_listener():
    import jax
    import jax.numpy as jnp

    from ray_tpu import train

    tracing.clear_startup()
    runner = train.TrainStepRunner(lambda w, x: (w + x.mean(), w.sum()),
                                   on_retrace="error")
    carry, batch = jnp.zeros(8), jnp.ones(8)
    for _ in range(2):
        carry, loss = runner.run(carry, batch)
    jax.block_until_ready(loss)
    assert tracing.startup_rows()[-1]["attrs"]["door"] == "compiled_step"
    before = _ledger_state()
    for _ in range(100):
        carry, loss = runner.run(carry, batch)
    jax.block_until_ready(loss)
    assert _ledger_state() == before


# -- the benchmark's readers ---------------------------------------------

DRIVER, CONTROLLER, CHIP = 100, 200, 300


def _row(name, pid, begin, end=None, **attrs):
    """A recorded row at `begin`..`end` seconds of the ledger's clock; the
    wall runs 1,000 s ahead of it."""
    end = begin if end is None else end
    return {"name": name, "pid": pid, "attrs": attrs,
            "begin_ns": int(begin * 1e9), "end_ns": int(end * 1e9),
            "start": 1000.0 + begin, "end": 1000.0 + end}


def _program(begin, end, door="compiled_step", hit=True, pid=CHIP, **times):
    times = {"trace_s": 0.0, "lower_s": 0.0, "load_s": 0.0,
             "compile_s": 0.0, **times}
    return _row("program", pid, begin, end, fn="decode:4", door=door,
                persistent_hit=hit, backend_s=times["load_s"]
                + times["compile_s"], **times)


# A serving start as a chip run records it, shortened: the benchmark's
# process began at 10.0 and the window opened at 40.0 and closed at 91.0.
RECORDED = [
    _row("cluster_up", DRIVER, 10.5, 12.5, owns_cluster=True),
    _row("deploy_call", DRIVER, 12.6, entry="serve.run"),
    _row("worker_spawn", CONTROLLER, 12.7, 13.2, tpu_chips=[]),
    _row("worker_boot", CONTROLLER, 13.2, 13.3, tpu_chips=[]),
    _row("worker_spawn", CHIP, 13.5, 14.5, tpu_chips=[0]),
    _row("worker_boot", CHIP, 14.5, 14.75, tpu_chips=[0]),
    _row("user_entered", CHIP, 15.0, deployment="BenchLLMDeployment"),
    _program(20.0, 21.0, door="jit", trace_s=0.25, lower_s=0.25,
             load_s=0.25),
    _row("engine_build", CHIP, 22.0, 24.0, model="llama",
         kv_arena_bytes=1 << 30),
    # an eager program inside the engine's build
    _program(22.5, 23.0, door="jit", hit=None, compile_s=0.25),
    _program(25.0, 28.0, trace_s=1.0, lower_s=0.5, load_s=1.0),
    # one the persistent cache did not hold
    _program(30.0, 36.0, hit=False, trace_s=1.0, lower_s=0.5,
             compile_s=4.0),
    # inside the window, in another process: counted there only
    _program(50.0, 50.5, door="jit", pid=CONTROLLER, compile_s=0.25),
    # after the window: in no metric
    _program(95.0, 96.0, door="jit", compile_s=0.5),
]
RECORDED_OBS = {"setup_s": 30.0, "t_open": 40.0, "t_close": 91.0}
EXPECTED = {
    "setup_cluster_up_s": 2.0,
    "setup_worker_boot_s": 1.25,
    "setup_deploy_other_s": 2.4 - 1.25,
    "setup_user_start_s": 25.0,
    "setup_engine_build_s": 2.0,
    "setup_programs": 4,
    "setup_program_trace_s": 2.25,
    "setup_program_lower_s": 1.25,
    "setup_program_load_s": 1.25,
    "setup_program_compile_s": 4.25,
    "setup_persistent_misses": 1,
    "setup_user_other_s": 25.0 - (1.0 + 2.0 + 3.0 + 6.0),
    "programs_in_window": 1,
}


def _write_session(tmp_path, rows):
    logs = tmp_path / "logs"
    logs.mkdir(parents=True)
    for pid in {r["pid"] for r in rows}:
        with open(logs / f"startup-{pid}.jsonl", "w") as f:
            for r in rows:
                if r["pid"] == pid:
                    f.write(json.dumps(r) + "\n")
    return str(tmp_path)


def _new_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return [m for m in bench["per_layer"]
            if m["name"] in EXPECTED]


def test_the_ledgers_metrics_end_benchmark_json_and_move_setup_s():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert len(bench["per_layer"]) <= 128       # the contract's limit
    last = bench["per_layer"][-len(EXPECTED):]
    assert [m["name"] for m in last] == list(EXPECTED)
    serving = [w["name"] for w in bench["workloads"]
               if not w["traffic"].startswith("pretrain")]
    for m in last:
        assert (m["better"], m["source"], m["moves"]) == \
            ("lower", "program_span", "setup_s")
        assert m["unit"] == ("s" if m["name"].endswith("_s") else "count")
        assert m.get("workloads") == (
            serving if m["name"] == "setup_engine_build_s" else None)


@pytest.mark.parametrize("clock", ["ledger", "wall"])
@pytest.mark.parametrize("name", list(EXPECTED))
def test_a_metric_file_reads_a_recorded_ledger(tmp_path, name, clock):
    from benchmark import readers, startup_ledger

    obs = dict(RECORDED_OBS)
    if clock == "wall":     # a training cell's window is on the wall
        obs = {"setup_s": 30.0, "window_open_wall": 1040.0, "window_s": 51.0}
    startup_ledger.load(obs, _write_session(tmp_path, RECORDED))
    got = readers.read_metric("layer_metrics", name, obs)
    assert got == pytest.approx(EXPECTED[name], abs=1e-6)


@pytest.mark.parametrize("name", list(EXPECTED))
def test_a_metric_file_reads_none_without_a_ledger(tmp_path, name,
                                                   monkeypatch):
    from benchmark import readers, startup_ledger

    # this process made no cluster (an earlier test's may lie in /tmp)
    monkeypatch.setattr(startup_ledger, "session_dir", lambda: None)
    (tmp_path / "logs").mkdir()
    obs = dict(RECORDED_OBS)
    assert startup_ledger.load(obs, str(tmp_path)) is None
    assert readers.read_metric("layer_metrics", name, obs) is None
    # nor where the run made no cluster, or has no window
    assert startup_ledger.load(dict(RECORDED_OBS), None) is None
    assert startup_ledger.load(
        {"setup_s": 1.0}, _write_session(tmp_path / "s", RECORDED)) is None


def test_the_parents_tracing_reads_as_no_ledger(tmp_path, monkeypatch):
    """This PR's readers over a program from before the ledger."""
    from benchmark import startup_ledger

    session = _write_session(tmp_path, RECORDED)
    monkeypatch.delattr(tracing, "collect_startup")
    obs = dict(RECORDED_OBS)
    assert startup_ledger.load(obs, session) is None
    assert startup_ledger.rows(obs, {"name": "program"}) is None


@pytest.fixture
def handmade(tmp_path):
    """Window 10..20, origin 0; rows a..e of process 1 (no chips, no
    `user_entered`: no chip process) and one row of process 2."""
    from benchmark import startup_ledger

    rows = [_row("a", 1, 1.0, 3.0, kind="x"), _row("a", 1, 2.0, 5.0),
            _row("b", 1, 4.0, 6.0, kind="x"), _row("c", 2, 7.0, 12.0),
            _row("mark", 1, 8.0), _row("a", 1, 10.0, 11.0, kind="x"),
            _row("a", 1, 19.5, 25.0), _row("a", 1, 20.0, 21.0)]
    obs = {"setup_s": 10.0, "t_open": 10.0, "t_close": 20.0}
    startup_ledger.load(obs, _write_session(tmp_path, rows))
    return obs


def test_stage_s_sums_the_named_rows_that_begin_before_the_window(handmade):
    from benchmark.startup_ledger import stage_s

    assert stage_s(handmade, {"names": ["a"]}) == pytest.approx(5.0)
    assert stage_s(handmade, {"names": ["a", "b"]}) == pytest.approx(7.0)
    # a row that begins before the opening counts whole
    assert stage_s(handmade, {"names": ["c"]}) == pytest.approx(5.0)
    assert stage_s(handmade, {"names": ["nothing"]}) is None
    # no process holds a chip or has entered: nothing is the chip's
    assert stage_s(handmade, {"names": ["a"], "chip_process": True}) is None


def test_between_s_runs_from_an_edge_to_an_edge(handmade):
    from benchmark.startup_ledger import between_s

    assert between_s(handmade, {"from": "origin", "to": "open"}) == 10.0
    assert between_s(handmade, {"from": {"row": "a"}, "to": {
        "row": "b", "edge": "end"}}) == pytest.approx(5.0)
    assert between_s(handmade, {"from": {"row": "mark"},
                                "to": "open"}) == pytest.approx(2.0)
    assert between_s(handmade, {"from": {"row": "nothing"},
                                "to": "open"}) is None


def test_rows_counts_sums_and_keeps_to_its_side_of_the_opening(handmade):
    from benchmark.startup_ledger import rows

    assert rows(handmade, {"name": "a"}) == 2       # 10.0 is the window's
    assert rows(handmade, {"name": "a", "where": {"kind": "x"}}) == 1
    assert rows(handmade, {"name": "a", "in_window": True}) == 2
    assert rows(handmade, {"name": "a", "in_window": True,
                           "where": {"kind": "x"}}) == 1
    assert rows(handmade, {"name": "nothing"}) == 0
    assert rows(handmade, {"name": "a", "sum": "missing"}) == 0.0


def test_remainder_s_takes_the_union_of_overlapping_rows_out(handmade):
    from benchmark.startup_ledger import remainder_s

    # a: 1..3 and 2..5, b: 4..6: their union is 1..6
    assert remainder_s(handmade, {"from": "origin", "to": "open",
                                  "less": ["a", "b"]}) == pytest.approx(5.0)
    assert remainder_s(handmade, {"from": "origin", "to": "open",
                                  "less": ["a"]}) == pytest.approx(6.0)
    # c runs past the opening: only what lies in the span is taken out
    assert remainder_s(handmade, {"from": {"row": "mark"}, "to": "open",
                                  "less": ["c"]}) == pytest.approx(0.0)
    assert remainder_s(handmade, {"from": {"row": "b", "edge": "end"},
                                  "to": "open", "less": ["nothing"]}) \
        == pytest.approx(4.0)
    assert remainder_s(handmade, {"from": {"row": "nothing"}, "to": "open",
                                  "less": ["a"]}) is None


def test_the_chip_process_is_the_one_whose_boot_holds_chips(tmp_path):
    from benchmark import startup_ledger

    obs = dict(RECORDED_OBS)
    ledger = startup_ledger.load(obs, _write_session(tmp_path, RECORDED))
    assert ledger["chip_pid"] == CHIP
    assert ledger["origin"] == 10.0
    # a rehearsal on the CPU: the process that entered the user's code
    rows = [dict(r, attrs=dict(r["attrs"], tpu_chips=[]))
            if r["name"] == "worker_boot" else r for r in RECORDED]
    obs = dict(RECORDED_OBS)
    ledger = startup_ledger.load(obs, _write_session(tmp_path / "cpu", rows))
    assert ledger["chip_pid"] == CHIP
