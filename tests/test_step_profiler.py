"""Flight-recorder tests: StepStats ring, dispatch sampling, metrics
export, cross-process unified timeline, fork-safe shard writers.

Reference ground: the reference exports task state + OpenCensus metrics
+ `ray timeline` as a first-class observability layer; this suite pins
the reproduction's equivalents (ISSUE 5).
"""

import json
import os
import subprocess
import sys
import time

import pytest

from ray_tpu.util import metrics as metrics_mod
from ray_tpu.util import step_profiler as sp
from ray_tpu.util import tracing as _tracing



@pytest.fixture(autouse=True)
def _clean_recorder():
    sp.refresh()
    sp.clear()
    yield
    sp.clear()


# ---------------------------------------------------------------------------
# ring semantics
# ---------------------------------------------------------------------------

def test_ring_bounds_and_eviction_under_sustained_stepping(monkeypatch):
    """Sustained stepping must hold steady memory: the ring keeps the
    newest `capacity` records and the total counter keeps counting."""
    monkeypatch.setenv("RAY_TPU_STEP_RING", "32")
    sp.refresh()
    try:
        for i in range(3 * 32 + 5):
            sp.record_step(i, 1.0)
        assert len(sp.ring()) == 32
        assert sp.ring().total_recorded == 3 * 32 + 5
        steps = [r["step"] for r in sp.recent()]
        # oldest evicted, newest kept, order preserved
        assert steps == list(range(69, 101))
        assert sp.recent(5)[-1]["step"] == 100
    finally:
        monkeypatch.delenv("RAY_TPU_STEP_RING")
        sp.refresh()


def test_record_step_computes_mfu_from_tokens_flops():
    rec = sp.record_step(1, 100.0, tokens=1000, flops=5e10, peak=1e12)
    # 5e10 flops in 0.1 s against a 1e12 flop/s peak -> 0.5 MFU
    assert rec.mfu == pytest.approx(0.5)
    # no peak (CPU) and none supplied -> no MFU claim
    rec2 = sp.record_step(2, 100.0, tokens=1000, flops=5e10)
    assert rec2.mfu is None


def test_disabled_recorder_is_inert():
    sp.set_enabled(False)
    try:
        assert sp.record_step(1, 1.0) is None
        sp.add_phase_ms("checkpoint_ms", 5.0)
        assert len(sp.ring()) == 0
    finally:
        sp.set_enabled(True)


def test_pending_phase_accumulators_fold_into_next_step():
    sp.add_phase_ms("checkpoint_ms", 7.0)
    sp.add_phase_ms("collective_ms", 3.0)
    sp.add_phase_ms("collective_ms", 2.0)
    rec = sp.record_step(1, 50.0)
    assert rec.checkpoint_ms == pytest.approx(7.0)
    assert rec.collective_ms == pytest.approx(5.0)
    # consumed: the next step starts clean
    rec2 = sp.record_step(2, 50.0)
    assert rec2.checkpoint_ms == 0.0


def test_attribution_sums_to_one():
    sp.record_step(1, 100.0, host_dispatch_ms=10.0,
                   device_execute_ms=60.0, data_wait_ms=20.0)
    attr = sp.attribution()
    assert attr["host_dispatch"] == pytest.approx(0.10)
    assert attr["device_execute"] == pytest.approx(0.60)
    assert attr["other"] == pytest.approx(0.10)
    assert sum(attr.values()) == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# compiled_step dispatch sampling + TrainStepRunner integration
# ---------------------------------------------------------------------------

def test_compiled_step_samples_dispatch(monkeypatch):
    import jax.numpy as jnp

    from ray_tpu.parallel.compile_cache import (ExecutableCache,
                                                compiled_step)

    monkeypatch.setenv("RAY_TPU_DISPATCH_SAMPLE", "4")
    sp.refresh()
    sp.clear()
    try:
        tick = compiled_step(lambda x: x + 1, cache=ExecutableCache())
        x = jnp.zeros(())
        for _ in range(16):
            x = tick(x)
        stats = sp.dispatch_stats()
        assert stats["calls"] == 16
        assert stats["sampled"] == 4  # 1 in 4
        assert stats["p50_ms"] >= 0
    finally:
        monkeypatch.delenv("RAY_TPU_DISPATCH_SAMPLE")
        sp.refresh()


def test_train_step_runner_records_step_stats():
    import jax.numpy as jnp

    from ray_tpu import train

    def step(carry, batch):
        return carry + jnp.sum(batch), carry

    runner = train.TrainStepRunner(step, steps_per_call=2,
                                   donate_carry=False,
                                   tokens_per_step=128,
                                   flops_per_step=1e6, peak_flops=1e12)
    carry = jnp.zeros(())
    batches = iter([jnp.ones(4)] * 8)
    carry, _aux = runner.run(carry, batches)
    carry, _aux = runner.run(carry, batches)
    recs = runner.step_stats()
    assert len(recs) == 2
    assert recs[-1]["step"] == 4                # 2 dispatches x K=2
    assert recs[-1]["steps_per_call"] == 2
    assert recs[-1]["tokens"] == 256
    assert recs[-1]["total_ms"] > 0
    assert recs[-1]["host_dispatch_ms"] > 0
    assert recs[-1]["mfu"] is not None          # peak supplied
    # the lowering/compile time is accounted by the cache, not the step
    assert runner.cache_stats()["misses"] >= 1


def test_compile_cache_tracks_lowering_ms():
    import jax.numpy as jnp

    from ray_tpu.parallel.compile_cache import (ExecutableCache,
                                                compiled_step)

    cache = ExecutableCache()
    tick = compiled_step(lambda x: x * 2, cache=cache)
    tick(jnp.zeros(3))
    assert cache.phases.ms("compiled_step.lower") > 0
    # as_dict stays counter-only (bench/test equality contracts)
    assert set(cache.stats.as_dict()) == {"hits", "misses", "retraces"}


# ---------------------------------------------------------------------------
# metrics export
# ---------------------------------------------------------------------------

def test_registry_callback_exposes_flight_recorder():
    # importing a plane registers its scrape callback — a process that
    # exercises the compile cache / channels exposes them automatically
    import ray_tpu.experimental.channel  # noqa: F401
    import ray_tpu.parallel.compile_cache  # noqa: F401

    sp.record_step(3, 20.0, host_dispatch_ms=2.0, tokens=64,
                   flops=1e9, peak=1e12)
    text = metrics_mod.DEFAULT_REGISTRY.prometheus_text()
    assert "train_steps_recorded_total 1" in text
    assert 'train_step_time_ms{phase="total"} 20.0' in text
    assert "train_step_mfu" in text
    assert "compile_cache_hits_total" in text       # compile cache rides
    assert "channel_frames_total" in text           # channel plane rides


def test_registry_callback_errors_do_not_break_scrape():
    reg = metrics_mod._Registry()
    metrics_mod.Counter("ok_total", "fine", registry=reg).inc()
    reg.register_callback("bad", lambda: 1 / 0)
    reg.register_callback("good", lambda: "extra_metric 1\n")
    text = reg.prometheus_text()
    assert "ok_total 1.0" in text
    assert "extra_metric 1" in text


def test_label_values_escaped_per_text_format():
    reg = metrics_mod._Registry()
    c = metrics_mod.Counter("named_total", "names", ("name",),
                            registry=reg)
    c.inc(tags={"name": 'quo"te'})
    c.inc(tags={"name": "back\\slash"})
    c.inc(tags={"name": "new\nline"})
    text = reg.prometheus_text()
    assert 'named_total{name="quo\\"te"} 1.0' in text
    assert 'named_total{name="back\\\\slash"} 1.0' in text
    assert 'named_total{name="new\\nline"} 1.0' in text


def test_serve_metrics_body_ends_with_eof():
    import asyncio
    import urllib.request

    async def scrape():
        reg = metrics_mod._Registry()
        metrics_mod.Gauge("g", "gauge", registry=reg).set(1)
        server, port = await metrics_mod.serve_metrics(registry=reg)
        try:
            body = await asyncio.get_event_loop().run_in_executor(
                None,
                lambda: urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics",
                    timeout=10).read().decode())
        finally:
            server.close()
        return body

    body = asyncio.run(scrape())
    assert body.endswith("# EOF\n")
    assert "g 1.0" in body


# ---------------------------------------------------------------------------
# unified timeline: shards + flow arrows across processes
# ---------------------------------------------------------------------------

_CHILD_SPANS = """
import os
import jax
jax.config.update("jax_platforms", "cpu")
from ray_tpu.util import tracing, step_profiler
with tracing.span("channel.read", kind="consumer",
                  attrs={"channel": "ch0", "seq": 7,
                         "flow_id": "ch0:7"}):
    pass
step_profiler.record_step(11, 4.5, host_dispatch_ms=1.0)
"""


def test_flow_arrows_survive_merge_across_processes(tmp_path):
    """Producer span in THIS process, consumer span + step record in a
    CHILD process: collect()+to_chrome() must stitch one s->f arrow
    pair sharing the flow id, and the unified timeline must carry the
    child's step record — all across pid boundaries."""
    trace_dir = str(tmp_path / "traces")
    os.environ["RAY_TPU_TRACE"] = "1"
    _tracing.refresh()  # read once at import
    os.environ["RAY_TPU_TRACE_DIR"] = trace_dir
    from ray_tpu.util import tracing
    from ray_tpu.util.timeline import unified_timeline

    tracing._reset_writer()
    sp._reset_shard_writer()
    try:
        with tracing.span("channel.write", kind="producer",
                          attrs={"channel": "ch0", "seq": 7,
                                 "flow_id": "ch0:7"}):
            pass
        sp.record_step(10, 2.5, host_dispatch_ms=0.5)
        env = dict(os.environ)
        r = subprocess.run([sys.executable, "-c", _CHILD_SPANS],
                           capture_output=True, text=True, env=env,
                           timeout=120)
        assert r.returncode == 0, r.stderr

        spans = tracing.collect(trace_dir)
        pids = {s["pid"] for s in spans}
        assert len(pids) == 2, spans  # two processes contributed
        events = tracing.to_chrome(spans)
        starts = [e for e in events
                  if e.get("ph") == "s" and e.get("id") == "ch0:7"]
        finishes = [e for e in events
                    if e.get("ph") == "f" and e.get("id") == "ch0:7"]
        assert len(starts) == 1 and len(finishes) == 1
        assert starts[0]["pid"] != finishes[0]["pid"]  # crossed procs

        # the unified merge carries spans AND both processes' steps
        out = str(tmp_path / "unified.json")
        merged = unified_timeline(out, trace_dir=trace_dir,
                                  include_tasks=False)
        assert any(e.get("cat") == "train_step" and
                   e["name"] == "step 10" for e in merged)
        assert any(e.get("cat") == "train_step" and
                   e["name"] == "step 11" for e in merged)
        assert any(e.get("id") == "ch0:7" and e["ph"] == "s"
                   for e in merged)
        assert any(e.get("id") == "ch0:7" and e["ph"] == "f"
                   for e in merged)
        with open(out) as f:
            assert json.load(f) == merged
    finally:
        os.environ.pop("RAY_TPU_TRACE", None)
        _tracing.refresh()  # read once at import
        os.environ.pop("RAY_TPU_TRACE_DIR", None)
        tracing._reset_writer()
        sp._reset_shard_writer()


def test_fork_resets_shard_writers(tmp_path):
    """After a fork, the child must write to ITS OWN pid-named shards
    (the inherited parent handles are dropped by the at-fork hooks)."""
    trace_dir = str(tmp_path / "traces")
    os.environ["RAY_TPU_TRACE"] = "1"
    _tracing.refresh()  # read once at import
    os.environ["RAY_TPU_TRACE_DIR"] = trace_dir
    from ray_tpu.util import tracing

    tracing._reset_writer()
    sp._reset_shard_writer()
    try:
        with tracing.span("parent.span"):
            pass
        sp.record_step(1, 1.0)
        pid = os.fork()
        if pid == 0:
            # child: write one span + one step record, then hard-exit
            # (no pytest teardown in the child)
            try:
                with tracing.span("child.span"):
                    pass
                sp.record_step(2, 1.0)
                tracing._reset_writer()     # `os._exit` runs no atexit
            finally:
                os._exit(0)
        _, status = os.waitpid(pid, 0)
        assert status == 0
        tracing._reset_writer()             # the spans are written in blocks
        shards = sorted(os.listdir(trace_dir))
        trace_shards = [s for s in shards if s.startswith("trace-")]
        step_shards = [s for s in shards if s.startswith("steps-")]
        assert len(trace_shards) == 2, shards  # parent + child pids
        assert len(step_shards) == 2, shards
        # the parent's shards contain ONLY the parent's records
        with open(os.path.join(trace_dir,
                               f"trace-{os.getpid()}.jsonl")) as f:
            names = [json.loads(ln)["name"] for ln in f if ln.strip()]
        assert names == ["parent.span"]
    finally:
        os.environ.pop("RAY_TPU_TRACE", None)
        _tracing.refresh()  # read once at import
        os.environ.pop("RAY_TPU_TRACE_DIR", None)
        tracing._reset_writer()
        sp._reset_shard_writer()


def test_fork_resets_event_writers(tmp_path):
    from ray_tpu.util import events as ev

    os.environ["RAY_TPU_EVENT_DIR"] = str(tmp_path / "ev")
    ev._files.clear()
    try:
        ev.report("GCS", "INFO", "PARENT", "parent event")
        pid = os.fork()
        if pid == 0:
            try:
                ev.report("GCS", "INFO", "CHILD", "child event")
            finally:
                os._exit(0)
        _, status = os.waitpid(pid, 0)
        assert status == 0
        shards = os.listdir(str(tmp_path / "ev"))
        assert len(shards) == 2, shards  # one shard per pid
        labels = {e["label"]: e["pid"] for e in ev.list_events()}
        assert labels["PARENT"] == os.getpid()
        assert labels["CHILD"] != os.getpid()
    finally:
        os.environ.pop("RAY_TPU_EVENT_DIR", None)
        ev._files.clear()


# ---------------------------------------------------------------------------
# CLI surfaces
# ---------------------------------------------------------------------------

def test_cli_profile_prints_step_table(tmp_path, capsys):
    """`ray_tpu profile` renders the last-N table + attribution from
    the step shards, offline (no cluster)."""
    trace_dir = str(tmp_path / "traces")
    os.environ["RAY_TPU_TRACE"] = "1"
    _tracing.refresh()  # read once at import
    os.environ["RAY_TPU_TRACE_DIR"] = trace_dir
    sp._reset_shard_writer()
    try:
        for i in range(5):
            sp.record_step(i + 1, 10.0 + i, host_dispatch_ms=1.0,
                           device_execute_ms=7.0, tokens=32,
                           flops=1e9, peak=1e12)
    finally:
        os.environ.pop("RAY_TPU_TRACE", None)
        _tracing.refresh()  # read once at import
        os.environ.pop("RAY_TPU_TRACE_DIR", None)
        sp._reset_shard_writer()

    from ray_tpu.scripts.cli import main

    main(["profile", "--trace-dir", trace_dir, "--last", "3"])
    out = capsys.readouterr().out
    assert "MFU" in out and "time attribution" in out
    assert f"{'5':>8}" in out  # newest step present
    # --json emits raw records
    main(["profile", "--trace-dir", trace_dir, "--json", "--last", "2"])
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln]
    assert len(lines) == 2
    assert json.loads(lines[-1])["step"] == 5


def test_cli_timeline_unified_offline(tmp_path, capsys):
    trace_dir = str(tmp_path / "traces")
    os.environ["RAY_TPU_TRACE"] = "1"
    _tracing.refresh()  # read once at import
    os.environ["RAY_TPU_TRACE_DIR"] = trace_dir
    os.environ.pop("RAY_TPU_ADDRESS", None)
    # point the CLI at an empty state file: a stale machine-global
    # /tmp/ray_tpu/cli_node.json must not make --unified try a dead GCS
    os.environ["RAY_TPU_CLI_STATE_FILE"] = str(tmp_path / "none.json")
    from ray_tpu.util import tracing

    tracing._reset_writer()
    sp._reset_shard_writer()
    try:
        with tracing.span("work"):
            pass
        sp.record_step(1, 3.0)
        os.environ.pop("RAY_TPU_TRACE", None)
        _tracing.refresh()  # read once at import
        os.environ.pop("RAY_TPU_TRACE_DIR", None)

        from ray_tpu.scripts.cli import main

        out_file = str(tmp_path / "unified.json")
        main(["timeline", "--unified", "--trace-dir", trace_dir,
              "--output", out_file])
        assert "step records" in capsys.readouterr().out
        events = json.load(open(out_file))
        assert any(e.get("cat") == "train_step" for e in events)
        assert any(e["name"] == "work" for e in events)
    finally:
        os.environ.pop("RAY_TPU_TRACE", None)
        _tracing.refresh()  # read once at import
        os.environ.pop("RAY_TPU_TRACE_DIR", None)
        os.environ.pop("RAY_TPU_CLI_STATE_FILE", None)
        tracing._reset_writer()
        sp._reset_shard_writer()
