"""A traced run ends in a whole line or says why not (PR 41): the child
reducer, the slice that is judged and taken once more, the refusals, the
gaps' names, and the last line of a failed run. CPU, no profiler: a slice is
a fake `start`/`stop`, a check a fake child."""

import argparse
import json
import os
import subprocess
import sys

import pytest

from benchmark import run, stage
from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
RECORDED = os.path.join(HERE, "data", "trace_recorded.json")
MS = 1e6  # ns
OP = "%fusion.1 = f32[4]{0} fusion("


def _planes(device_events, host_events=()):
    return [{"name": "/device:TPU:0", "lines": [
        {"name": tr.OPS_LINE, "events": [list(e) for e in device_events]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [list(e) for e in host_events]}]}]


WHOLE = {"devices": 1, "window_s": 0.98, "device_events": 40_000,
         "host_events": 3_000, "span_events": 2_500}


class FakeChild:
    """What `SliceTaker` sees of a check's child: done at once."""

    def __init__(self, got, rc=0, err=""):
        self.out, self.returncode, self.err = json.dumps(got), rc, err

    def poll(self):
        return self.returncode

    def communicate(self, timeout=None):
        return self.out, self.err


def _taker(checks, seconds=51.0, serving=True):
    """A taker over fake slices; `checks` is what each slice's check says."""
    log, answers = [], list(checks)

    def start():
        log.append("start")

    def stop():
        log.append("stop")
        return f"/nowhere/slice{log.count('stop')}"

    taker = tr.SliceTaker(start, stop, seconds, serving,
                          check=lambda d: FakeChild(answers.pop(0)))
    return taker, log


def _drive(taker, until=51.0, step=0.05):
    t = 0.0
    while t < until and not taker.idle:
        taker.poll(t)
        t += step
    return taker.close(until)["dir"]


# -- the child ---------------------------------------------------------------

def test_the_child_reduces_the_recorded_trace_and_the_rows_add_up():
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.trace_reduce", RECORDED],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    with open(RECORDED.replace(".json", ".expected.json")) as f:
        want = json.load(f)
    for key in ("devices", "window_s", "busy_s", "collective_s",
                "collective_exposed_s", "idle_s"):
        assert got[key] == pytest.approx(want[key]), key
    assert [k for k, _ in got["device_ops"]] == \
        [k for k, _ in want["device_ops"]]
    assert got["idle_gaps"] == [[k, pytest.approx(v)]
                                for k, v in want["idle_gaps"]]
    # what was there before, then the two remainders, and they add up
    assert [k for k, _ in got["idle_gaps"][-2:]] == [tr.HOLES, tr.UNCOVERED]
    assert sum(v for _, v in got["idle_gaps"]) == \
        pytest.approx(got["window_s"] - got["busy_s"], rel=1e-9)
    assert got["device_events"] == 1258 and got["span_events"] == 0
    assert len(got["idle_gaps"]) <= 10 and len(got["device_ops"]) <= 10


def test_a_child_that_fails_or_says_nothing_is_an_error_not_a_trace(tmp_path):
    got = tr.reduce_in_child(str(tmp_path))     # no .xplane.pb under it
    assert "FileNotFoundError" in got["error"] or "xplane" in got["error"]
    assert tr.refused(got, 1.0, serving=True) == got["error"]
    assert "ended 0" in tr.child_result(0, "not json\n", "")["error"]
    assert tr.child_result(0, 'noise\n{"devices": 1}\n', "") == {"devices": 1}


# -- judged, and tried once more ---------------------------------------------

def test_a_lost_slice_is_taken_once_more_and_the_second_accepted():
    taker, log = _taker([{}, WHOLE])
    assert _drive(taker) == "/nowhere/slice2"
    assert log == ["start", "stop", "start", "stop"]
    assert [t["refused"] for t in taker.tries] == [
        "no device plane with operation events", None]
    # the first slice opens 3 s into the window and is 1 s long
    assert taker.slice_s == tr.SLICE_S == 1.0 and tr.SLICE_AT_S == 3.0


def test_a_whole_first_slice_is_the_only_one():
    taker, log = _taker([WHOLE])
    assert _drive(taker) == "/nowhere/slice1"
    assert log == ["start", "stop"] and taker.idle
    assert taker.tries == [{"dir": "/nowhere/slice1", "refused": None,
                            "stop_s": pytest.approx(0, abs=1)}]


def test_two_losses_fail_the_run_with_both_reasons():
    taker, log = _taker([{}, dict(WHOLE, window_s=0.012, device_events=47)])
    assert _drive(taker) is None
    assert log == ["start", "stop", "start", "stop"]        # and no third
    said = taker.lost()
    assert said.startswith("device trace lost twice: try 1: no device plane")
    assert "try 2: the device events span 12.0 ms of the 1 s slice" in said
    # through the cell's own hand-over: a failure, and no `trace` to read
    obs = {"failures": [], "platform": "tpu"}
    taken = taker.close(51.0)
    assert taken["dir"] is None and taken["lost"] == said
    json.dumps(taken)       # plain data: a worker reports it to its driver
    tr.reduce_taken(obs, taken, serving=True)
    assert obs["failures"] == [said] and "trace" not in obs
    line = run.result_line(
        {"per_layer": []}, {"name": "c"},
        dict(obs, device_kind="TPU v5 lite", count=1, memory_peak_bytes=1,
             attempted=3, failed=0), trace=True)
    assert line["correct"] is False and line["failures"] == [said]
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert [t["refused"] for t in line["notes"]["trace_tries"]] == \
        [t["refused"] for t in taker.tries]


def test_a_window_that_closes_on_an_open_slice_or_check_still_ends():
    taker, log = _taker([WHOLE], seconds=8.0)
    taker.poll(2.0)                     # opens (a quarter of 8 s in)
    assert taker.tracing and taker.slice_s == 1.0
    assert taker.close(8.0)["dir"] == "/nowhere/slice1"
    assert log == ["start", "stop"]
    taker, log = _taker([{}])
    taker.poll(3.0), taker.poll(4.0)
    assert taker.close(50.0)["dir"] is None
    assert "closed before a second slice" in taker.lost()
    assert tr.SliceTaker(None, None, 51.0, True).lost().startswith(
        "device trace never taken")


def test_what_is_refused_as_no_trace():
    assert tr.refused(WHOLE, 1.0, serving=True) is None
    assert tr.refused({}, 1.0, serving=True) == \
        "no device plane with operation events"
    # 12 ms of a 4 s slice read `device_idle_pct.generate` 89.27 (PR 35)
    short = dict(WHOLE, window_s=0.012, device_events=47)
    assert "12.0 ms of the 4 s slice" in tr.refused(short, 4.0, True)
    assert tr.refused(dict(WHOLE, window_s=0.26), 1.0, True) is None
    assert tr.refused(dict(WHOLE, window_s=0.24), 1.0, True) is not None
    # a serving cell's trace has to hold the program's phases
    no_spans = dict(WHOLE, span_events=0)
    assert "no rt/ host event among 3000" in tr.refused(no_spans, 1.0, True)
    assert tr.refused(no_spans, 1.0, serving=False) is None
    assert tr.refused({"error": "the child ended 1"}, 1.0, True) == \
        "the child ended 1"


def test_the_check_counts_what_refused_reads():
    ops = [(OP, 0, 1 * MS), (OP, 5 * MS, 1 * MS)]
    host = [("rt/admit", 1 * MS, 3 * MS), ("$engine.py:1 step", 0, 9 * MS),
            ("instant", 2 * MS, 0)]
    assert tr.check_trace(_planes(ops, host)) == {
        "devices": 1, "window_s": pytest.approx(0.006), "device_events": 2,
        "host_events": 2, "span_events": 1}
    assert tr.check_trace(_planes([], host)) == {}
    full = tr.reduce_trace(_planes(ops, host))
    assert {k: full[k] for k in ("device_events", "host_events",
                                 "span_events")} == {
        "device_events": 2, "host_events": 2, "span_events": 1}


# -- gaps named by the program's spans ---------------------------------------

def test_a_gap_is_named_by_the_rt_span_not_the_python_frame_over_it():
    ops = [(OP, 0, 1 * MS), (OP, 5 * MS, 1 * MS), (OP, 8 * MS, 1 * MS),
           (OP, 9.02 * MS, 0.98 * MS)]
    host = [("$engine.py:563 step", 1.1 * MS, 3.8 * MS),   # tighter, a frame
            ("rt/engine_step", 0, 9 * MS),
            ("rt/admit", 0.9 * MS, 4.2 * MS),
            ("$threading.py:1 run", -500 * MS, 1000 * MS)]
    r = tr.reduce_trace(_planes(ops, host))
    gaps = dict(r["idle_gaps"])
    assert gaps["rt/admit"] == pytest.approx(0.004)
    # [6,8]: only the step's span reaches it, however much longer it is
    assert gaps["rt/engine_step"] == pytest.approx(0.002)
    assert not any(k.startswith(("engine.py", "threading.py", "_unknown"))
                   for k in gaps)
    assert gaps[tr.HOLES] == pytest.approx(20e-6)
    assert gaps[tr.UNCOVERED] == 0.0
    assert sum(gaps.values()) == pytest.approx(r["idle_s"]) \
        == pytest.approx(r["window_s"] - r["busy_s"])


def test_a_gap_is_shared_among_the_spans_open_in_it_by_self_time():
    """A decode pass's frame (`rt/decode_assemble`) holds its phases: the
    hole between two programs is the fetch, the sample, what the frame
    does itself, the next pass's dispatch, each for the time it was the
    innermost span open, as the program's own ledger counts them."""
    ops = [(OP, 0, 10 * MS), (OP, 14 * MS, 10 * MS)]
    host = [("rt/engine_step", 0.5 * MS, 11.6 * MS),
            ("rt/decode_assemble", 1 * MS, 11 * MS),        # pass k
            ("rt/decode_device_wait", 2 * MS, 8.1 * MS),
            ("rt/decode_fetch", 10.1 * MS, 1 * MS),
            ("rt/decode_sample", 11.2 * MS, 0.7 * MS),
            ("rt/engine_step", 12.3 * MS, 20 * MS),
            ("rt/admit", 12.3 * MS, 0.2 * MS),              # same start
            ("rt/decode_assemble", 12.6 * MS, 19 * MS),     # pass k + 1
            ("rt/decode_dispatch", 13 * MS, 1.5 * MS)]
    r = tr.reduce_trace(_planes(ops, host))
    gaps = dict(r["idle_gaps"])
    assert gaps == {
        "rt/decode_device_wait": pytest.approx(0.0001),
        "rt/decode_fetch": pytest.approx(0.001),
        "rt/decode_sample": pytest.approx(0.0007),
        "rt/decode_assemble": pytest.approx(0.0002 + 0.0004),
        "rt/engine_step": pytest.approx(0.0001 + 0.0001),
        "rt/admit": pytest.approx(0.0002),
        "rt/decode_dispatch": pytest.approx(0.001),
        # between the two steps no span of the pump is open
        tr.UNCOVERED: pytest.approx(0.0002), tr.HOLES: 0.0}
    assert sum(gaps.values()) == pytest.approx(r["idle_s"]) \
        == pytest.approx(0.004)


def test_a_looker_on_threads_span_does_not_name_the_feeders_gaps():
    """`rt/metrics` of a poll waits for the engine's lock across the pump's
    steps (0.87 of a 4 s slice's idle seconds went to it on the chip): the
    thread with most spans feeds the device and names the gaps."""
    ops = [(OP, 0, 1 * MS), (OP, 5 * MS, 1 * MS), (OP, 20 * MS, 1 * MS)]
    planes = _planes(ops, [("rt/engine_step", 0, 6 * MS),
                           ("rt/decode_assemble", 0.5 * MS, 4.7 * MS)])
    planes.append({"name": "/host:CPU", "lines": [{
        "name": "python3", "events": [["rt/metrics", 0.9 * MS, 4.2 * MS],
                                      ["rt/metrics", 8 * MS, 10 * MS]]}]})
    gaps = dict(tr.reduce_trace(planes)["idle_gaps"])
    assert gaps["rt/decode_assemble"] == pytest.approx(0.004)
    # [6,20]: no span of the feeder reaches it, so another thread's may
    assert gaps["rt/metrics"] == pytest.approx(0.014)


def test_more_names_than_rows_fold_into_one_and_still_add_up():
    ops, host = [], []
    for i in range(12):
        ops.append((OP, i * 10 * MS, 1 * MS))
        if i < 11:
            host.append((f"rt/phase_{chr(97 + i)}", (i * 10 + 1) * MS,
                         (9 - 0.1 * i) * MS))
    r = tr.reduce_trace(_planes(ops, host))
    rows = r["idle_gaps"]
    assert len(rows) == 10
    assert [k for k, _ in rows[-3:]] == [tr.OTHER, tr.HOLES, tr.UNCOVERED]
    assert sum(v for _, v in rows) == pytest.approx(r["idle_s"])


# -- a failed run says why ---------------------------------------------------

def test_a_raising_cell_ends_stderr_with_the_stage_and_exits_1(
        monkeypatch, capsys):
    def raising(args):
        stage.enter("setup")
        stage.enter("window")
        stage.enter("trace_stop")
        raise RuntimeError("ActorDiedError: the replica died\nsecond line")

    monkeypatch.setattr(run, "run", raising)
    monkeypatch.setattr(run, "keep_logs", lambda w: None)
    monkeypatch.setattr(run, "kill_leftovers", lambda: [])
    rc = run.main(["--workload", "kimi-k2.6-ep32-l7.generate-long",
                   "--seed", "1", "--seconds", "1", "--trace", "1"])
    out, err = capsys.readouterr()
    assert rc == 1 and out == ""
    assert "Traceback" in err
    assert err.strip().splitlines()[-1] == (
        "benchmark failed: workload=kimi-k2.6-ep32-l7.generate-long trace=1 "
        "stage=trace_stop RuntimeError: ActorDiedError: the replica died")


def test_a_leftover_is_named_in_the_last_line(monkeypatch, capsys):
    monkeypatch.setattr(run, "run", lambda args: {"correct": True})
    monkeypatch.setattr(run, "kill_leftovers",
                        lambda: ["python -m ray_tpu._private.raylet x"])
    rc = run.main(["--workload", "w", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc == 1 and out == ""
    assert err.strip().splitlines()[-1] == (
        "benchmark failed: workload=w trace=0 stage=leftovers LeftRunning: "
        "after shutdown, killed: ['python -m ray_tpu._private.raylet x']")


def test_a_workers_stage_reaches_the_drivers_line(tmp_path):
    path = str(tmp_path / "stage")
    stage.enter("window")
    stage.read_from(path)
    try:
        assert stage.current() == "window"          # no file yet
        with open(path, "w") as f:
            f.write("check")                        # what the worker wrote
        assert stage.current() == "window/check"
    finally:
        stage.read_from(None)
    stage.write_to(path)
    try:
        stage.enter("trace_stop")
        with open(path) as f:
            assert f.read() == "trace_stop"
    finally:
        stage.write_to(None)
    assert set(stage.STAGES) >= {"setup", "ramp", "window", "trace_stop",
                                 "reduce", "check", "shutdown", "leftovers"}


def test_the_real_command_fails_with_the_line_where_no_chip_is():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "gpt2-medium.pretrain", "--seed", "1", "--seconds",
         "1", "--trace", "1"], capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1 and proc.stdout.strip() == ""
    assert proc.stderr.strip().splitlines()[-1].startswith(
        "benchmark failed: workload=gpt2-medium.pretrain trace=1 "
        "stage=setup SystemExit: gpt2-medium.pretrain needs 1 TPU chip(s)")


def test_only_the_session_and_the_child_import_jax():
    """`run.py`'s process must not: whoever imports jax there holds the
    chip its worker needs."""
    code = ("import sys; from benchmark import trace_reduce, stage, run; "
            "trace_reduce.reduce_trace([]); print('jax' in sys.modules)")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=120)
    assert proc.stdout.strip() == "False", proc.stderr


def test_an_untraced_tiny_run_never_meets_the_taker(monkeypatch):
    """Every edit of the cells sits behind `trace`: an untraced run builds
    no taker, starts no child and reports no tries."""
    def never(*a, **k):
        raise AssertionError("an untraced run reached the trace's code")

    monkeypatch.setattr(tr, "SliceTaker", never)
    monkeypatch.setattr(tr, "start_child", never)
    args = argparse.Namespace(workload="tiny.generate", seed=11, seconds=2.0,
                              trace=0)
    try:
        line = run.run(args, require_tpu=False,
                       bench_file=os.path.join(HERE, "data",
                                               "BENCHMARK.tiny.json"),
                       traffic_folder=os.path.join(HERE, "data", "traffic"))
    finally:
        assert run.kill_leftovers() == []
    assert line["correct"] is True
    # the line's keys as they were before PR 41
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "notes"]
    assert list(line["device"]) == ["platform", "kind", "count",
                                    "memory_peak_bytes"]
    assert list(line["notes"]) == ["check", "warmup_s",
                                   "compiled_step_calls", "memory",
                                   "tokens_in_window", "window_s"]
