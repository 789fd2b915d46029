"""The trace reduction, on hand-made events (where every number can be
worked out by hand) and on a small trace recorded on the chip."""

import json
import os

import pytest

from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1e6  # ns


def _planes(device_events, host_events=(), second_device=None):
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_step(1)", 0, 10 * MS]]},
        {"name": tr.OPS_LINE, "events": [list(e) for e in device_events]}]}]
    if second_device is not None:
        planes.append({"name": "/device:TPU:1", "lines": [
            {"name": tr.OPS_LINE,
             "events": [list(e) for e in second_device]}]})
    planes.append({"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [list(e) for e in host_events]}]})
    return planes


def test_busy_is_the_union_and_idle_is_the_rest():
    r = tr.reduce_trace(_planes([
        ("%fusion.1 = bf16[8,128]{1,0:T(8,128)} fusion(", 0, 2 * MS),
        ("%fusion.2 = bf16[8,128]{1,0:T(8,128)} fusion(", 1 * MS, 2 * MS),
        ("%copy.3 = f32[4]{0} copy(", 8 * MS, 2 * MS)]))
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.005)       # [0,3] and [8,10]
    assert r["devices"] == 1


def test_operation_kinds_merge_numbers_and_keep_shapes():
    assert tr.op_name("%fusion.12 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion("
                      ) == "fusion bf16[8,128]"
    assert tr.op_name("%fusion.7 = bf16[8,128]{1,0} fusion(%a, %b)"
                      ) == "fusion bf16[8,128]"
    # a Pallas kernel is a custom-call whatever scope named it
    assert tr.op_name("%h13.3 = (bf16[8,16,1024,64]{3,2,1,0}, f32[8,16,"
                      "1024,1]{3,2,1,0}) custom-call(bf16[8] %x)"
                      ) == "custom-call (bf16[8,16,1024,64], f32[8,16,1024,1])"
    assert tr.op_name("$kv_cache.py:392 insert") == "kv_cache.py:392 insert"
    assert tr.op_name("XlaDelinearize") == "XlaDelinearize"


def test_a_loop_is_not_charged_its_body_twice():
    r = tr.reduce_trace(_planes([
        ("%while.1 = (s32[]) while(", 0, 10 * MS),
        ("%fusion.2 = f32[4]{0} fusion(", 1 * MS, 3 * MS),
        ("%fusion.3 = f32[4]{0} fusion(", 5 * MS, 3 * MS)]))
    assert r["busy_s"] == pytest.approx(0.010)
    assert r["op_seconds"]["fusion f32[4]"] == pytest.approx(0.006)
    assert r["op_seconds"]["while (s32[])"] == pytest.approx(0.004)
    assert tr.kernel_seconds(r, "^fusion") == pytest.approx(0.006)


def test_collective_time_counts_as_exposed_only_without_compute():
    ops = [("%fusion.1 = f32[4]{0} fusion(", 0, 4 * MS),
           ("%all-reduce.1 = f32[4]{0} all-reduce(", 4 * MS, 2 * MS),
           ("%fusion.2 = f32[4]{0} fusion(", 6 * MS, 4 * MS)]
    hidden = [("%fusion.1 = f32[4]{0} fusion(", 0, 10 * MS),
              ("%all-gather-start.1 = f32[8]{0} all-gather-start(", 2 * MS,
               3 * MS)]
    r = tr.reduce_trace(_planes(ops, second_device=hidden))
    assert r["devices"] == 2
    assert r["collective_s"] == pytest.approx((0.002 + 0.003) / 2)
    assert r["collective_exposed_s"] == pytest.approx(0.002 / 2)
    assert r["busy_s"] == pytest.approx(0.010)


def test_an_idle_gap_is_named_by_the_innermost_host_span_over_it():
    ops = [("%fusion.1 = f32[4]{0} fusion(", 0, 1 * MS),
           ("%fusion.2 = f32[4]{0} fusion(", 5 * MS, 1 * MS),
           ("%fusion.3 = f32[4]{0} fusion(", 8 * MS, 1 * MS)]
    host = [("$engine.py:563 step", 0, 9 * MS),
            ("XlaDelinearize", 1.2 * MS, 3.5 * MS),
            ("$threading.py:1 run", -500 * MS, 1000 * MS)]
    r = tr.reduce_trace(_planes(ops, host))
    gaps = dict(r["idle_gaps"])
    assert gaps["XlaDelinearize"] == pytest.approx(0.004)
    # [6,8] has only the step's span over it; the thread's outermost span
    # covers every gap and names none
    assert gaps["engine.py:563 step"] == pytest.approx(0.002)
    assert gaps[tr.HOLES] == 0.0 and gaps[tr.UNCOVERED] == 0.0
    r = tr.reduce_trace(_planes(ops))
    assert dict(r["idle_gaps"]) == {tr.HOLES: 0.0,
                                    tr.UNCOVERED: pytest.approx(0.006)}
    assert r["idle_s"] == pytest.approx(0.006)


def test_the_programs_span_names_a_gap_before_any_other_host_event():
    """With the Python tracer off (PR 41) the host events are the `rt/`
    phases and the runtime's own; a gap's time goes to the innermost phase
    open at each instant of it, however long the phase, and to a runtime
    event only where no phase reaches."""
    ops = [("%fusion.1 = f32[4]{0} fusion(", 0, 1 * MS),
           ("%fusion.2 = f32[4]{0} fusion(", 1.1 * MS, 0.9 * MS),
           ("%fusion.3 = f32[4]{0} fusion(", 5 * MS, 1 * MS),
           ("%fusion.4 = f32[4]{0} fusion(", 8 * MS, 1 * MS),
           ("%fusion.5 = f32[4]{0} fusion(", 9.01 * MS, 0.99 * MS)]
    host = [("rt/decode_device_wait", 0, 2.5 * MS),    # 25 x the hole in it
            ("rt/decode_sample", 2.5 * MS, 2.6 * MS),
            ("XlaDelinearize", 2.2 * MS, 2.7 * MS),
            ("TpuExecute", 6.1 * MS, 1.8 * MS)]
    r = tr.reduce_trace(_planes(ops, host))
    gaps = dict(r["idle_gaps"])
    # [1, 1.1] and the first half millisecond of [2, 5], then the sample's
    assert gaps["rt/decode_device_wait"] == pytest.approx(0.0006)
    assert gaps["rt/decode_sample"] == pytest.approx(0.0025)
    assert gaps["TpuExecute"] == pytest.approx(0.002)
    assert gaps[tr.HOLES] == pytest.approx(0.00001)    # [9, 9.01]
    assert "XlaDelinearize" not in gaps
    assert sum(gaps.values()) == pytest.approx(r["idle_s"])
    assert r["span_events"] == 2 and r["host_events"] == 4
    assert r["device_events"] == 5


def test_load_keeps_the_lines_the_reduction_reads(monkeypatch, tmp_path):
    """`load_xplane` lists the `XLA Ops` line of a device plane and the
    timed host events, and builds nothing of the other lines."""
    import types

    def ev(name, start, dur):
        return types.SimpleNamespace(name=name, start_ns=start,
                                     duration_ns=dur)

    def line(name, events):
        return types.SimpleNamespace(name=name, events=events)

    data = types.SimpleNamespace(planes=[
        types.SimpleNamespace(name="/device:TPU:0", lines=[
            line("XLA Modules", [ev("jit_step(1)", 0, 10)]),
            line("XLA Ops", [ev("%fusion.1 = f32[4]{0} fusion(", 0, 5)]),
            line("Async XLA Ops", [ev("%copy-start", 0, 5)])]),
        types.SimpleNamespace(name="/host:CPU", lines=[
            line("python3", [ev("rt/admit", 1, 3), ev("instant", 2, 0)])]),
        types.SimpleNamespace(name="/host:metadata", lines=[])])
    import jax.profiler
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: data))
    folder = tmp_path / "plugins" / "profile" / "2026_01_01"
    folder.mkdir(parents=True)
    (folder / "host.xplane.pb").write_bytes(b"")
    assert tr.load_xplane(str(tmp_path)) == [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["%fusion.1 = f32[4]{0} fusion(", 0.0, 5.0]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["rt/admit", 1.0, 3.0]]}]}]
    with pytest.raises(FileNotFoundError):
        tr.load_xplane(str(tmp_path / "plugins"))


def test_no_device_plane_reduces_to_nothing():
    assert tr.reduce_trace([{"name": "/host:CPU", "lines": []}]) == {}


def test_interval_arithmetic():
    assert tr.union([(3, 5), (0, 2), (1, 4)]) == [(0, 5)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]


RECORDED = os.path.join(HERE, "data", "trace_recorded.json")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace beside the test")
def test_recorded_trace_from_the_chip():
    """Cut from a `--trace 1` run on the v5e (PR 23): the planes' events of
    a fraction of a second, as `load_xplane` returns them. The
    expected numbers are what the reduction read when the trace was cut,
    kept beside it, so a change to the reduction shows."""
    with open(RECORDED) as f:
        planes = json.load(f)
    with open(RECORDED.replace(".json", ".expected.json")) as f:
        want = json.load(f)
    got = tr.reduce_trace(planes)
    for key in ("devices", "window_s", "busy_s", "collective_s",
                "collective_exposed_s", "idle_s"):
        assert got[key] == pytest.approx(want[key]), key
    assert sum(v for _, v in got["idle_gaps"]) == pytest.approx(got["idle_s"])
    assert [k for k, _ in got["device_ops"]] == \
        [k for k, _ in want["device_ops"]]
    assert [k for k, _ in got["idle_gaps"]] == \
        [k for k, _ in want["idle_gaps"]]
    assert 0 < got["busy_s"] < got["window_s"]
