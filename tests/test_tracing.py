"""Tracing tests: spans around submit/execute stitch into one trace.

Reference ground: `python/ray/tests/test_tracing.py` — remote task and
actor-method calls produce `.remote` (producer) and `.execute`
(consumer) spans that share a trace id across processes.
"""

import json
import os

import pytest

from ray_tpu.util import tracing as _tracing


def test_task_and_actor_spans(tmp_path):
    trace_dir = str(tmp_path / "traces")
    os.environ["RAY_TPU_TRACE"] = "1"
    _tracing.refresh()  # read once at import
    os.environ["RAY_TPU_TRACE_DIR"] = trace_dir
    # a shard that an earlier test of this process left open would take
    # the driver's spans (as the other tracing tests do)
    _tracing._reset_writer()
    import ray_tpu
    from ray_tpu.util import tracing

    ray_tpu.init(num_cpus=2, object_store_memory=64 * 1024 * 1024)
    try:
        @ray_tpu.remote
        def traced_fn(x):
            return x * 2

        assert ray_tpu.get(traced_fn.remote(21)) == 42

        @ray_tpu.remote
        class TracedActor:
            def method(self, x):
                return x + 1

        a = TracedActor.remote()
        assert ray_tpu.get(a.method.remote(1)) == 2
        ray_tpu.kill(a)
        import time

        time.sleep(1.5)  # the shards are flushed a second after a line
    finally:
        ray_tpu.shutdown()
        os.environ.pop("RAY_TPU_TRACE", None)
        _tracing.refresh()  # read once at import
        os.environ.pop("RAY_TPU_TRACE_DIR", None)
        _tracing._reset_writer()

    spans = tracing.collect(trace_dir)
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)

    # producer span on the driver, consumer span in the worker process,
    # linked by trace_id + parent_id
    assert "traced_fn.remote" in by_name
    assert "traced_fn.execute" in by_name
    sub = by_name["traced_fn.remote"][0]
    ex = by_name["traced_fn.execute"][0]
    assert ex["trace_id"] == sub["trace_id"]
    assert ex["parent_id"] == sub["span_id"]
    assert ex["pid"] != sub["pid"]  # crossed a process boundary
    assert ex["attrs"]["task_type"] == "normal"

    # actor method call traced the same way
    assert "method.remote" in by_name and "method.execute" in by_name
    m_sub = by_name["method.remote"][0]
    m_ex = by_name["method.execute"][0]
    assert m_ex["trace_id"] == m_sub["trace_id"]
    assert m_ex["attrs"]["task_type"] == "actor"

    # chrome export is well-formed
    events = tracing.to_chrome(spans)
    assert any(e["ph"] == "X" for e in events)
    assert any(e["ph"] == "s" for e in events)  # flow arrows


def test_tracing_disabled_is_free(tmp_path):
    """With tracing off, no shard files appear and spans are no-ops."""
    from ray_tpu.util import tracing

    os.environ.pop("RAY_TPU_TRACE", None)
    _tracing.refresh()  # read once at import
    os.environ["RAY_TPU_TRACE_DIR"] = str(tmp_path / "none")
    try:
        with tracing.span("x") as s:
            assert s == {}
        assert tracing.current_context() is None
        assert not os.path.exists(str(tmp_path / "none"))
    finally:
        os.environ.pop("RAY_TPU_TRACE_DIR", None)


# ---------------------------------------------------------------------------
# phases: the self-time ledger, the profiler annotation, the JSONL span
# ---------------------------------------------------------------------------

def test_nested_phases_self_times_sum_to_the_outer_duration():
    import time

    from ray_tpu.util import tracing

    table = tracing.PhaseTable(("outer", "inner", "leaf", "never"))
    with table.phase("outer") as outer:
        time.sleep(0.01)
        with table.phase("inner") as inner:
            time.sleep(0.01)
            with table.phase("leaf") as leaf:
                time.sleep(0.01)
        with table.phase("inner"):
            time.sleep(0.005)
    ns = table.snapshot_ns()
    # every nanosecond of the outer phase is one name's self time
    assert sum(ns.values()) == outer.ns
    assert ns["never"] == 0 and table.count("never") == 0
    assert ns["leaf"] == leaf.ns >= 10_000_000
    assert inner.ns >= leaf.ns + 10_000_000    # a phase's `ns` is whole
    assert 10_000_000 <= ns["outer"] < outer.ns - inner.ns
    assert table.count("inner") == 2 and table.count("outer") == 1
    assert table.ms("leaf") == ns["leaf"] / 1e6
    # a phase's edges are stamps on the ledger's clock, at no read of
    # their own: whoever owns the phase marks a transition with them
    for ph in (outer, inner, leaf):
        assert ph.end_ns - ph.begin_ns == ph.ns
    assert outer.begin_ns < inner.begin_ns < leaf.begin_ns \
        < leaf.end_ns <= inner.end_ns < outer.end_ns
    with table.phase("outer") as still_open:
        assert still_open.end_ns == still_open.begin_ns
    assert not table.in_phase()
    table.clear()
    assert sum(table.snapshot_ns().values()) == 0


def test_total_ns_is_the_wall_time_of_a_stretch_and_reads_are_live():
    import time

    from ray_tpu.util import tracing

    table = tracing.PhaseTable(("base", "work"))
    with table.phase("base"):
        mark = table.total_ns()
        t0 = time.perf_counter_ns()
        with table.phase("work"):
            time.sleep(0.01)
        time.sleep(0.005)
        stretch = table.total_ns() - mark
        wall = time.perf_counter_ns() - t0
        assert abs(stretch - wall) < 1_000_000
        # another thread's reading includes the open phase's remainder
        seen = {}
        import threading

        time.sleep(0.01)
        t = threading.Thread(
            target=lambda: seen.update(table.snapshot_ns()))
        t.start()
        t.join(timeout=10)
        assert seen["base"] >= 14_000_000
        assert seen["base"] > table.ms("base") * 1e6  # charged + live


def test_timed_lock_charges_only_contended_waits_of_phase_threads():
    import threading
    import time

    from ray_tpu.util import tracing

    table = tracing.PhaseTable(("run", "lock_wait"))
    lock = tracing.TimedLock(table, threading.Lock())
    with table.phase("run"):
        for _ in range(100):
            with lock:
                pass
    assert table.count("lock_wait") == 0       # uncontended: no clock
    # a thread with no phase open is never charged
    holder_has_it = threading.Event()
    release = threading.Event()

    def hold():
        with lock:
            holder_has_it.set()
            release.wait(10)

    t = threading.Thread(target=hold)
    t.start()
    assert holder_has_it.wait(10)
    assert not lock.acquire(False)
    threading.Timer(0.05, release.set).start()
    with table.phase("run"):
        with lock:
            pass
    t.join(timeout=10)
    assert not t.is_alive()
    assert table.count("lock_wait") == 1
    assert 30 <= table.ms("lock_wait") < 2000
    assert not lock.locked()


def test_phase_writes_the_jsonl_span_only_when_tracing_is_on(tmp_path):
    from ray_tpu.util import tracing

    table = tracing.PhaseTable()
    trace_dir = str(tmp_path / "traces")
    os.environ["RAY_TPU_TRACE_DIR"] = trace_dir
    tracing._reset_writer()
    try:
        with table.phase("quiet"):
            pass
        assert not os.path.exists(trace_dir)
        os.environ["RAY_TPU_TRACE"] = "1"
        assert not tracing.enabled()           # cached until refresh()
        tracing.refresh()
        assert tracing.enabled()
        with table.phase("llm.outer", req_id="r-1", kind="consumer",
                         attrs={"flow_id": "req:r-1", "bucket": 8}):
            with table.phase("inner"):
                pass
    finally:
        os.environ.pop("RAY_TPU_TRACE", None)
        os.environ.pop("RAY_TPU_TRACE_DIR", None)
        tracing.refresh()
        tracing._reset_writer()
    spans = {s["name"]: s for s in tracing.collect(trace_dir)}
    assert set(spans) == {"llm.outer", "inner"}
    outer, inner = spans["llm.outer"], spans["inner"]
    assert outer["kind"] == "consumer"
    assert outer["attrs"] == {"flow_id": "req:r-1", "bucket": 8,
                              "req_id": "r-1"}
    # nested in it, and carrying the request's id
    assert inner["parent_id"] == outer["span_id"]
    assert inner["attrs"]["req_id"] == "r-1"
    assert table.count("quiet") == 1 and table.count("inner") == 1


def test_tracing_and_a_phase_leave_jax_out_of_the_process():
    """The benchmark's driver and the daemons import this module and must
    not take the chip: jax is used only where it is already imported."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from ray_tpu.util import tracing\n"
        "t = tracing.PhaseTable(('a',))\n"
        "with t.phase('a', step=1, req_id='r'):\n"
        "    pass\n"
        "assert t.count('a') == 1\n"
        "assert 'jax' not in sys.modules, 'jax imported'\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=root)
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=root,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr


def _host_events(log_dir):
    """{event name: [its stats as a dict]} of a profiler session's host
    planes."""
    import glob

    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert paths, f"no .xplane.pb under {log_dir}"
    events = {}
    for plane in ProfileData.from_file(paths[-1]).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("rt/"):
                    events.setdefault(e.name, []).append(dict(e.stats))
    return events


def test_device_trace_holds_the_phases_and_the_train_step(tmp_path):
    """Under a profiler session the phases are host events of the
    `.xplane.pb`: `rt/train_step` as a numbered step with its three phases
    and the executable cache's lookup inside."""
    import jax.numpy as jnp

    from ray_tpu.train import TrainStepRunner
    from ray_tpu.util import step_profiler, tracing

    def step(w, batch):
        return w + batch.sum(), w.sum()

    runner = TrainStepRunner(step, donate_carry=False)
    w = jnp.zeros(4)
    w, _ = runner.run(w, jnp.ones(4))           # compiles outside the trace
    n0 = step_profiler.recent()[-1]["step"]
    with tracing.device_trace(str(tmp_path / "trace")) as log_dir:
        for _ in range(3):
            w, _ = runner.run(w, jnp.ones(4))
    events = _host_events(log_dir)
    assert len(events["rt/train_step"]) == 3
    assert sorted(e["step_num"] for e in events["rt/train_step"]) == \
        [n0 + 1, n0 + 2, n0 + 3]
    for name in ("rt/train_data_wait", "rt/train_dispatch",
                 "rt/train_device_wait", "rt/cache_lookup"):
        assert len(events[name]) == 3, (name, sorted(events))
    # what went to the flight recorder is what the phases measured
    row = step_profiler.recent()[-1]
    assert row["host_dispatch_ms"] > 0 and row["device_execute_ms"] >= 0
    assert runner.phases.count("train_step") == 4
    total = sum(runner.phases.snapshot_ns().values()) / 1e6
    rows = step_profiler.recent()[-4:]
    assert abs(total - sum(r["total_ms"] for r in rows)) < 0.05 * total + 0.5


# -- the start-up ledger's rows ---------------------------------------------

@pytest.fixture
def fresh_ledger():
    """The process's ledger, empty and with no session, then as it was."""
    from ray_tpu.util import tracing

    was = tracing._startup_dir
    tracing.set_startup_dir(None)
    tracing.clear_startup()
    yield tracing
    tracing.set_startup_dir(was)
    tracing.clear_startup()


def test_rows_and_marks_round_trip_through_the_shard(tmp_path, fresh_ledger):
    import time

    tracing = fresh_ledger
    session = str(tmp_path / "session")
    t0 = time.perf_counter_ns()
    with tracing.startup_stage("boot", {"chips": [0]}) as attrs:
        time.sleep(0.01)
        attrs["bytes"] = 7          # known only at the end
    mark = tracing.startup_mark("entered", {"who": "me"})
    # a row that began in another process of the host, on the one clock
    far = tracing.startup_row("spawn", t0 - 5_000_000, t0, {"n": 1})
    assert not os.path.exists(session)      # no session yet: the rows wait
    tracing.set_startup_dir(session)
    last = tracing.startup_mark("late", flush=True)
    shard = os.path.join(session, "logs", f"startup-{os.getpid()}.jsonl")
    with open(shard) as f:                  # written through, all four
        assert len(f.read().splitlines()) == 4
    rows = tracing.collect_startup(session)
    assert [r["name"] for r in rows] == ["spawn", "boot", "entered", "late"]
    assert rows == sorted(tracing.startup_rows(),
                          key=lambda r: r["begin_ns"])
    boot = rows[1]
    assert boot["attrs"] == {"chips": [0], "bytes": 7}
    assert boot["pid"] == os.getpid()
    assert boot["end_ns"] - boot["begin_ns"] >= 10_000_000
    assert boot["end"] - boot["start"] == pytest.approx(
        (boot["end_ns"] - boot["begin_ns"]) / 1e9, abs=1e-5)
    assert rows[0] == far and far["end_ns"] - far["begin_ns"] == 5_000_000
    assert rows[2] == mark and mark["begin_ns"] == mark["end_ns"]
    assert mark["start"] == mark["end"]
    assert rows[3] == last
    # wall and ledger clocks tell one story
    assert boot["start"] - far["start"] == pytest.approx(
        (boot["begin_ns"] - far["begin_ns"]) / 1e9, abs=1e-3)


def test_a_failed_stage_is_a_row_that_names_the_error(fresh_ledger):
    tracing = fresh_ledger
    with pytest.raises(KeyError):
        with tracing.startup_stage("build"):
            raise KeyError("x")
    (row,) = tracing.startup_rows()
    assert row["name"] == "build" and row["attrs"] == {"error": "KeyError"}


def test_the_ledger_keeps_a_bounded_list_and_counts_the_rest(
        fresh_ledger, monkeypatch, tmp_path):
    tracing = fresh_ledger
    monkeypatch.setattr(tracing, "STARTUP_CAP", 3)
    tracing.set_startup_dir(str(tmp_path))
    for i in range(5):
        tracing.startup_mark(f"m{i}")
    assert [r["name"] for r in tracing.startup_rows()] == ["m0", "m1", "m2"]
    assert tracing.startup_dropped() == 2
    assert len(tracing.collect_startup(str(tmp_path))) == 3
    # a new session: the rows written to the last one are that one's
    tracing.set_startup_dir(str(tmp_path / "next"))
    assert tracing.startup_rows() == [] and tracing.startup_dropped() == 0
    tracing.startup_mark("again")
    assert [r["name"] for r in tracing.collect_startup(
        str(tmp_path / "next"))] == ["again"]
    assert len(tracing.collect_startup(str(tmp_path))) == 3


def test_a_stage_is_an_annotation_where_jax_is_imported(fresh_ledger):
    import jax  # noqa: F401

    tracing = fresh_ledger
    seen = []

    class Spy:
        def __init__(self, name, **kw):
            seen.append(name)

        def __enter__(self):
            seen.append("in")

        def __exit__(self, *exc):
            seen.append("out")

    was = tracing._ANNOTATIONS
    tracing._ANNOTATIONS = (Spy, Spy)
    try:
        with tracing.startup_stage("engine_build"):
            pass
    finally:
        tracing._ANNOTATIONS = was
    assert seen == ["rt/engine_build", "in", "out"]


def test_the_shard_writes_in_blocks_and_loses_a_second_at_most(tmp_path):
    import time

    from ray_tpu.util import tracing

    path = str(tmp_path / "deep" / "shard.jsonl")
    def names():
        with open(path) as f:
            return [json.loads(line)["n"] for line in f]

    shard = tracing._Shard(lambda: path)
    shard.write({"n": "a"})
    assert not os.path.exists(path)             # buffered, not serialized
    deadline = time.time() + 5
    while not os.path.exists(path) and time.time() < deadline:
        time.sleep(0.05)                        # the timer's flush
    assert names() == ["a"]
    shard.write({"n": "b"}, flush=True)         # written through
    assert names() == ["a", "b"]
    for i in range(tracing._FLUSH_ROWS):        # a full block goes at once
        shard.write({"n": i})
    assert len(names()) == 2 + tracing._FLUSH_ROWS
    shard.write({"n": "c"})
    shard.forget()                              # a forked child's view
    shard.close()
    assert names()[-1] != "c"


def test_span_lines_wait_in_the_buffer_until_collect(tmp_path):
    from ray_tpu.util import tracing

    trace_dir = str(tmp_path / "traces")
    os.environ["RAY_TPU_TRACE"] = "1"
    os.environ["RAY_TPU_TRACE_DIR"] = trace_dir
    tracing.refresh()
    tracing._reset_writer()
    try:
        with tracing.span("one"):
            pass
        assert not os.path.exists(trace_dir)    # no write inside the span
        assert [s["name"] for s in tracing.collect(trace_dir)] == ["one"]
    finally:
        os.environ.pop("RAY_TPU_TRACE", None)
        os.environ.pop("RAY_TPU_TRACE_DIR", None)
        tracing.refresh()
        tracing._reset_writer()
