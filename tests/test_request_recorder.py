"""Request-path flight recorder tests (ISSUE 12): per-request ring,
context propagation handle->replica->engine, phase attribution,
histogram export, scrape hardening, the tsdb time-series plane, and
the `ray_tpu requests` CLI.

Reference ground: the step-profiler suite (ISSUE 5) pins the training
plane's flight recorder; this suite pins its inference twin.
"""

import json
import os
import time

import pytest

from ray_tpu.util import metrics as metrics_mod
from ray_tpu.util import request_recorder as rr
from ray_tpu.util import tsdb as tsdb_mod
from ray_tpu.util import tracing as _tracing



@pytest.fixture(autouse=True)
def _clean_recorder():
    rr.refresh()
    rr.clear()
    yield
    rr.refresh()
    rr.clear()


# ---------------------------------------------------------------------------
# ring semantics + knobs
# ---------------------------------------------------------------------------

def test_ring_bounds_and_eviction(monkeypatch):
    """Sustained serving must hold steady memory: the ring keeps the
    newest `RAY_TPU_REQ_RING` records and the total keeps counting."""
    monkeypatch.setenv("RAY_TPU_REQ_RING", "16")
    rr.refresh()
    for i in range(3 * 16 + 5):
        rr.record_engine(None, ts=float(i), total_ms=1.0 + i)
    assert len(rr.ring()) == 16
    assert rr.ring().total_recorded == 3 * 16 + 5
    totals = [r.total_ms for r in rr.ring().recent()]
    assert totals == [1.0 + i for i in range(37, 53)]  # newest kept


def test_sample_knob_records_one_in_n(monkeypatch):
    monkeypatch.setenv("RAY_TPU_REQ_SAMPLE", "4")
    rr.refresh()
    for i in range(16):
        rr.record_engine(None, ts=0.0, total_ms=1.0)
    assert len(rr.ring()) == 4  # 1 in 4
    # the sampled bit is minted ONCE at the handle: client and engine
    # agree on whether the request exists
    ctxs = [rr.new_context("d") for _ in range(16)]
    assert sum(1 for c in ctxs if c["sampled"]) == 4


def test_disabled_recorder_is_inert():
    rr.set_enabled(False)
    try:
        assert rr.record_engine(None, ts=0.0, total_ms=1.0) is None
        ctx = rr.new_context("d")
        assert ctx["sampled"] is False
        assert rr.record_client(ctx, ts=0.0, total_ms=1.0) is None
        assert len(rr.ring()) == 0
    finally:
        rr.set_enabled(True)


# ---------------------------------------------------------------------------
# context plane + record merge
# ---------------------------------------------------------------------------

def test_serving_region_carries_context_to_engine_role():
    ctx = rr.new_context("chat", job="tenant-a")
    assert rr.current() is None
    with rr.serving(ctx):
        assert rr.current() is ctx
        rec = rr.record_engine(rr.current(), ts=1.0, total_ms=10.0,
                               queue_ms=1.0, admission_ms=2.0,
                               prefill_span_ms=2.5, first_hold_ms=0.5,
                               prefill_ms=2.0, decode_ms=3.5,
                               finish_ms=0.5,
                               ttft_ms=6.0, tpot_ms=1.0,
                               tokens_in=8, tokens_out=5)
    assert rr.current() is None
    assert rec.req_id == ctx["req_id"]
    assert rec.deployment == "chat" and rec.job == "tenant-a"
    assert rec.phase_sum_ms() == pytest.approx(10.0)


def test_merge_by_request_joins_client_and_engine_rows():
    ctx = rr.new_context("chat", job="tenant-a")
    eng = rr.record_engine(ctx, ts=1.0, total_ms=9.0, queue_ms=1.0,
                           admission_ms=1.0, prefill_ms=3.0,
                           decode_ms=4.0, ttft_ms=5.0, tpot_ms=1.0,
                           tokens_out=5)
    cli = rr.record_client(ctx, ts=1.0, total_ms=11.0, queue_ms=0.5,
                           ttft_ms=6.0, tpot_ms=1.2, tokens_out=5,
                           replayed_tokens=2, outcome="failed_over")
    merged = rr.merge_by_request([eng.as_dict(), cli.as_dict()])
    assert len(merged) == 1
    m = merged[0]
    assert m["req_id"] == ctx["req_id"]
    # engine phases are authoritative; client total/TTFT/outcome win
    assert m["prefill_ms"] == pytest.approx(3.0)
    assert m["total_ms"] == pytest.approx(11.0)
    assert m["ttft_ms"] == pytest.approx(6.0)
    assert m["outcome"] == "failed_over"
    assert m["replayed_tokens"] == 2


def test_summary_and_slowest():
    for i in range(10):
        rr.record_engine(None, ts=float(i), total_ms=10.0 * (i + 1),
                         prefill_span_ms=6.0 * (i + 1),
                         prefill_ms=5.0 * (i + 1),
                         decode_ms=4.0 * (i + 1), ttft_ms=7.0,
                         tpot_ms=1.5)
    s = rr.summary()
    assert s["n"] == 10
    assert s["total_ms_p50"] == pytest.approx(50.0)
    assert s["ttft_ms_p50"] == pytest.approx(7.0)
    assert s["outcomes"] == {"ok": 10}
    # phases tile 100% of total in this synthetic set
    assert sum(s["attribution"].values()) == pytest.approx(1.0)
    worst = rr.slowest([r.as_dict() for r in rr.ring().recent()], 3)
    assert [w["total_ms"] for w in worst] == [100.0, 90.0, 80.0]


# ---------------------------------------------------------------------------
# live engine: phases tile the measured end-to-end latency
# ---------------------------------------------------------------------------

def test_engine_phase_sum_matches_e2e():
    """The ISSUE 12 attribution contract: queue + admission + prefill +
    decode reconstruct the engine-observed e2e latency (within 5%)."""
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    eng = LLMEngine(model="llama",
                    engine_config=EngineConfig(batch_buckets=(1, 2),
                                               prefill_buckets=(8,)),
                    seed=0)
    eng.warmup()
    eng.start()
    try:
        reqs = [eng.submit([3, 4, 5], 4) for _ in range(4)]
        for r in reqs:
            r.result(timeout=120)
    finally:
        eng.quiesce(timeout=60)
        assert eng.shutdown() == 0

    recs = [r for r in rr.ring().recent()
            if r.role == "engine" and r.outcome == "ok"]
    assert len(recs) == 4
    for rec in recs:
        assert rec.ttft_ms is not None and rec.ttft_ms > 0
        assert rec.tokens_out == 4
        assert rec.tpot_ms is not None  # 4 tokens -> 3 decode gaps
        ratio = rec.phase_sum_ms() / rec.total_ms
        assert 0.95 <= ratio <= 1.05, rec.as_dict()
        # built from one clock's stamps, the phases tile the total whole
        assert rec.phase_sum_ms() == pytest.approx(rec.total_ms, abs=1e-6)
        assert rec.tpot_ms == pytest.approx(rec.decode_ms / 3)
        # the request's own units: the span and the hold but for the
        # other streams' passes between its chunks (none here) and the
        # unit's tail past the hand-over
        assert 0 < rec.prefill_ms <= rec.total_ms
        assert rec.ttft_ms == pytest.approx(
            rec.queue_ms + rec.admission_ms + rec.prefill_span_ms
            + rec.first_hold_ms, abs=1e-6)
    # TTFT is taken where the first token is handed to its reader
    assert sorted(rec.ttft_ms for rec in recs) == pytest.approx(sorted(
        (r.first_handed_ns - r.submit_ns) / 1e6 for r in reqs))


def test_a_shed_requests_record_tiles_too():
    """A request that ends before its first token: its record's phases
    still tile its total (the stamps it never took read as its end), with
    no TTFT and no decode span."""
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    eng = LLMEngine(model="llama",
                    engine_config=EngineConfig(batch_buckets=(1,),
                                               prefill_buckets=(8,)),
                    seed=0)
    try:
        req = eng.submit([4, 4], 4, timeout_s=0.001)
        time.sleep(0.02)
        eng.run_until_idle()
        assert req.error and req.first_handed_ns is None
    finally:
        assert eng.shutdown() == 0
    (rec,) = [r for r in rr.ring().recent() if r.role == "engine"]
    assert rec.outcome == "timed_out" and rec.ttft_ms is None
    assert rec.total_ms == pytest.approx(
        (req.finish_ns - req.submit_ns) / 1e6)
    assert rec.queue_ms == pytest.approx(rec.total_ms)
    assert rec.phase_sum_ms() == pytest.approx(rec.total_ms, abs=1e-6)
    assert rec.decode_ms == rec.prefill_ms == rec.finish_ms == 0.0


def test_the_records_surfaces_show_the_span_the_hold_and_the_own_prefill():
    ctx = rr.new_context("chat", job="tenant-a")
    rec = rr.record_engine(ctx, ts=0.0, total_ms=20.0, queue_ms=1.0,
                           admission_ms=2.0, prefill_span_ms=8.0,
                           first_hold_ms=3.0, prefill_ms=6.0,
                           decode_ms=5.5, finish_ms=0.5, ttft_ms=14.0,
                           tpot_ms=1.8, tokens_out=4)
    assert rec.phase_sum_ms() == pytest.approx(20.0)
    d = rec.as_dict()
    assert (d["prefill_span_ms"], d["first_hold_ms"], d["prefill_ms"],
            d["finish_ms"]) == (8.0, 3.0, 6.0, 0.5)
    text = metrics_mod.DEFAULT_REGISTRY.prometheus_text()
    for phase, le in (("prefill_span", "10.0"), ("first_hold", "5.0"),
                      ("prefill", "10.0")):
        assert (f'serve_request_phase_ms_bucket{{phase="{phase}",'
                f'deployment="chat",job="tenant-a",le="{le}"}} 1') in text
    assert 'phase="finish"' not in text     # microseconds: no histogram
    # TTFT and TPOT come before the larger family: a capped reader keeps
    # what comes first
    assert text.index("serve_ttft_ms_bucket") \
        < text.index("serve_request_phase_ms_bucket")
    table = rr.format_table([d])
    header = table.splitlines()[0].split()
    assert header[3:10] == ["total", "queue", "admit", "span", "own",
                            "hold", "decode"]
    assert table.splitlines()[2].split()[3:10] == \
        ["20.00", "1.00", "2.00", "8.00", "6.00", "3.00", "5.50"]
    # the attribution is over what tiles the total: the own prefill, a
    # part of the span, is not counted a second time
    assert sum(rr.summary([d])["attribution"].values()) == \
        pytest.approx(1.0)
    merged = rr.merge_by_request([d])[0]
    assert merged["prefill_span_ms"] == 8.0 and merged["prefill_ms"] == 6.0


# ---------------------------------------------------------------------------
# metrics export
# ---------------------------------------------------------------------------

def test_histograms_carry_phase_deployment_job_labels():
    ctx = rr.new_context("chat", job="tenant-a")
    rr.record_engine(ctx, ts=0.0, total_ms=9.0, queue_ms=0.5,
                     admission_ms=0.5, prefill_ms=4.0, decode_ms=4.0,
                     ttft_ms=4.5, tpot_ms=1.3, tokens_out=4)
    text = metrics_mod.DEFAULT_REGISTRY.prometheus_text()
    # the module registers its callback at import: the family arrives
    # through the shared registry scrape, fully labelled
    assert ('serve_request_phase_ms_bucket{phase="queue",'
            'deployment="chat",job="tenant-a",le="1.0"} 1') in text
    assert ('serve_request_phase_ms_bucket{phase="decode",'
            'deployment="chat",job="tenant-a",le="5.0"} 1') in text
    assert 'serve_ttft_ms_bucket{deployment="chat",job="tenant-a"' \
        in text
    assert 'serve_tpot_ms_sum{deployment="chat",job="tenant-a"} 1.3' \
        in text
    assert 'serve_request_outcomes_total{outcome="ok"} 1' in text
    assert "serve_requests_recorded_total 1" in text


def test_raising_source_degrades_to_scrape_error_comment():
    """Satellite 2: scrape assembly is all-or-nothing PER SOURCE — a
    raising metric or callback must leave a `# scrape_error` comment,
    not a torn body (headers without samples), and must not take the
    other sources down with it."""
    reg = metrics_mod._Registry()
    metrics_mod.Counter("ok_total", "fine", registry=reg).inc()
    bad = metrics_mod.Counter("bad_total", "boom", registry=reg)

    def _boom():
        raise RuntimeError("mid-render")

    bad.samples = _boom
    reg.register_callback("bad_cb", lambda: 1 / 0)
    reg.register_callback("good_cb", lambda: "extra_metric 1\n")
    text = reg.prometheus_text()
    assert "ok_total 1.0" in text
    assert "extra_metric 1" in text
    assert '# scrape_error source="bad_total" error="RuntimeError"' \
        in text
    assert '# scrape_error source="bad_cb" error="ZeroDivisionError"' \
        in text
    # no torn chunk: the failed metric contributed NOTHING but the
    # comment (no dangling HELP/TYPE header)
    assert "# HELP bad_total" not in text
    assert "# TYPE bad_total" not in text


# ---------------------------------------------------------------------------
# two-process serve app: one req_id spans handle + replica
# ---------------------------------------------------------------------------

def test_request_spans_stitch_across_processes(tmp_path):
    """The handle's producer span (driver pid) and the replica's
    consumer span (worker pid) must share one `req:<id>` flow id, and
    collect()+to_chrome() must emit the s->f arrow pair across the
    process boundary."""
    trace_dir = str(tmp_path / "traces")
    os.environ["RAY_TPU_TRACE"] = "1"
    _tracing.refresh()  # read once at import
    os.environ["RAY_TPU_TRACE_DIR"] = trace_dir
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.util import tracing

    tracing._reset_writer()
    rr._reset_shard_writer()
    ray_tpu.init(num_cpus=2, object_store_memory=64 * 1024 * 1024)
    try:
        @serve.deployment
        def echo(x):
            return x

        handle = serve.run(echo.bind())
        assert handle.remote(7).result(timeout=60) == 7
        time.sleep(1.5)  # the shards are flushed a second after a line
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
        os.environ.pop("RAY_TPU_TRACE", None)
        _tracing.refresh()  # read once at import
        os.environ.pop("RAY_TPU_TRACE_DIR", None)
        tracing._reset_writer()
        rr._reset_shard_writer()

    spans = tracing.collect(trace_dir)
    prod = [s for s in spans if s["name"] == "serve.echo.request"]
    cons = [s for s in spans if s["name"] == "replica.handle_request"]
    assert prod and cons, [s["name"] for s in spans]
    flow = prod[0]["attrs"]["flow_id"]
    assert flow.startswith("req:")
    assert cons[0]["attrs"]["flow_id"] == flow
    assert cons[0]["attrs"]["req_id"] == prod[0]["attrs"]["req_id"]
    assert prod[0]["pid"] != cons[0]["pid"]  # crossed processes

    events = tracing.to_chrome(spans)
    starts = [e for e in events
              if e.get("ph") == "s" and e.get("id") == flow]
    finishes = [e for e in events
                if e.get("ph") == "f" and e.get("id") == flow]
    assert len(starts) == 1 and len(finishes) >= 1
    assert starts[0]["pid"] != finishes[0]["pid"]

    # the handle also shed a client record shard for the same request
    recs = rr.collect(trace_dir)
    mine = [r for r in recs
            if r["req_id"] == prod[0]["attrs"]["req_id"]]
    assert mine and mine[0]["role"] == "client"
    assert mine[0]["outcome"] == "ok"
    assert mine[0]["deployment"] == "echo"

    # and the unified timeline carries the serve-request row
    from ray_tpu.util.timeline import unified_timeline

    merged = unified_timeline(trace_dir=trace_dir, include_tasks=False)
    assert any(e.get("cat") == "serve_request" for e in merged)


# ---------------------------------------------------------------------------
# tsdb: the metrics time-series plane
# ---------------------------------------------------------------------------

def test_parse_prometheus_text_labels_and_escapes():
    text = (
        "# HELP x about\n"
        "# TYPE x counter\n"
        "serve_x_total 3\n"
        'serve_y{job="a,b",name="quo\\"te"} 1.5\n'
        "malformed line without value x\n"
    )
    samples = tsdb_mod.parse_prometheus_text(text)
    assert ("serve_x_total", {}, 3.0) in samples
    assert ("serve_y", {"job": "a,b", "name": 'quo"te'}, 1.5) in samples
    assert len(samples) == 2  # comments + malformed dropped


def test_tsdb_bounded_series_and_points():
    db = tsdb_mod.TSDB(max_series=2, max_points=3, prefixes=("serve_",))
    for i in range(5):
        db.ingest(f"serve_a 1\nserve_b 2\nserve_c 3\nother {i}\n",
                  source="t", ts=float(i))
    # third serve_ series dropped (bound), non-prefixed never admitted
    assert len(db.series()) == 2
    assert db.dropped_series == 5
    # per-series ring trimmed to max_points, newest kept
    assert [t for t, _ in db.points("serve_a", source="t")] == \
        [2.0, 3.0, 4.0]
    assert db.latest("serve_b") == 2.0


def test_rate_computes_per_second_and_clamps_resets():
    db = tsdb_mod.TSDB(max_series=4, max_points=16, prefixes=("serve_",))
    for i, v in enumerate((0, 10, 20, 30)):
        db.ingest(f"serve_reqs_total {v}\n", source="t", ts=float(i))
    assert db.rate("serve_reqs_total", window_s=10.0) == \
        pytest.approx(10.0)
    # counter reset (daemon restart) reads as quiet, never negative
    db.ingest("serve_reqs_total 0\n", source="t", ts=4.0)
    assert db.rate("serve_reqs_total", window_s=10.0) == 0.0


def test_histogram_quantile_interpolates():
    db = tsdb_mod.TSDB(max_series=8, max_points=4, prefixes=())
    db.ingest(
        'lat_bucket{le="1.0"} 0\n'
        'lat_bucket{le="2.0"} 5\n'
        'lat_bucket{le="+Inf"} 10\n',
        source="t", ts=1.0)
    # q=0.5 -> target 5 falls exactly at the le=2.0 bucket edge
    assert tsdb_mod.histogram_quantile(db, "lat", 0.5) == \
        pytest.approx(2.0)
    # mass beyond the last finite bound reports that bound
    assert tsdb_mod.histogram_quantile(db, "lat", 0.99) == \
        pytest.approx(2.0)
    # q=0.25 -> target 2.5, linear inside (1.0, 2.0]
    assert tsdb_mod.histogram_quantile(db, "lat", 0.25) == \
        pytest.approx(1.5)


def test_scrape_local_feeds_request_histograms():
    rr.record_engine(None, ts=0.0, total_ms=9.0, prefill_ms=5.0,
                     decode_ms=4.0, ttft_ms=5.5, tpot_ms=1.3)
    db = tsdb_mod.TSDB(max_series=128, max_points=8)
    assert tsdb_mod.scrape_local(db, ts=1.0) > 0
    q50 = tsdb_mod.histogram_quantile(db, "serve_ttft_ms", 0.5,
                                      source="local")
    assert q50 is not None and 0 < q50 <= 10.0
    snap = db.snapshot()
    assert snap["scrapes"] == 1
    assert any(s["name"].startswith("serve_") for s in snap["series"])


# ---------------------------------------------------------------------------
# CLI surfaces
# ---------------------------------------------------------------------------

def test_cli_requests_offline(tmp_path, capsys):
    trace_dir = str(tmp_path / "traces")
    os.environ["RAY_TPU_TRACE"] = "1"
    _tracing.refresh()  # read once at import
    os.environ["RAY_TPU_TRACE_DIR"] = trace_dir
    rr._reset_shard_writer()
    try:
        for i in range(5):
            ctx = rr.new_context("chat", job="tenant-a")
            rr.record_engine(ctx, ts=float(i),
                             total_ms=10.0 * (i + 1),
                             prefill_ms=6.0 * (i + 1),
                             decode_ms=4.0 * (i + 1),
                             ttft_ms=7.0, tpot_ms=1.5, tokens_out=4)
    finally:
        os.environ.pop("RAY_TPU_TRACE", None)
        _tracing.refresh()  # read once at import
        os.environ.pop("RAY_TPU_TRACE_DIR", None)
        rr._reset_shard_writer()

    from ray_tpu.scripts.cli import main

    main(["requests", "--trace-dir", trace_dir, "--last", "3"])
    out = capsys.readouterr().out
    assert "phase attribution" in out
    assert "chat" in out and "tenant-a" in out

    main(["requests", "--trace-dir", trace_dir, "--slow", "2",
          "--json"])
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln]
    assert len(lines) == 2
    assert json.loads(lines[0])["total_ms"] == 50.0  # worst first
