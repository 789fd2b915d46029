"""The per-layer metrics that read the program's phase counters (PR 24):
each is a file for the `ratio` reader, appended to `BENCHMARK.json` without
touching what was there."""

import json
import os

import pytest

from benchmark import readers

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# the window's differences of engine.metrics(), as serve_cell.py makes them
ENGINE_DELTA = {
    "decode_steps": 120, "prefill_steps": 400, "pump_wall_ms": 50_000.0,
    "ph_decode_dispatch_ms": 36_000.0, "ph_decode_device_wait_ms": 8_400.0,
    "ph_decode_fetch_ms": 600.0, "ph_decode_kv_append_ms": 240.0,
    "ph_decode_sample_ms": 120.0, "ph_prefill_device_wait_ms": 26_000.0,
    "ph_prefill_kv_fetch_ms": 6_000.0, "ph_prefill_kv_write_ms": 4_000.0,
    "ph_pump_idle_ms": 12_500.0, "ph_lock_wait_ms": 250.0,
    "metrics_ms": 5_000.0}
CACHE_STATS = {"lookup_ms": 3_300.0, "lookups": 300}

WANT = {
    "decode_dispatch_ms.generate": 300.0,
    "decode_device_wait_ms.generate": 70.0,
    "decode_fetch_ms.generate": 5.0,
    "decode_kv_append_ms.generate": 2.0,
    "decode_sample_ms.generate": 1.0,
    "prefill_device_wait_ms.score": 65.0,
    "prefill_kv_write_ms.score": 10.0,
    "pump_idle_pct.score": 25.0,
    "pump_lock_wait_pct.score": 0.5,
    "metrics_poll_pct.score": 10.0,
    "cache_lookup_ms.train": 11.0,
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_phase_metric_resolves_through_read_metric(name):
    obs = {"engine_delta": ENGINE_DELTA, "cache_stats": CACHE_STATS}
    assert readers.read_metric("layer_metrics", name, obs) == \
        pytest.approx(WANT[name])
    # a program without the counter (the parent commit): left out, no raise
    assert readers.read_metric(
        "layer_metrics", name,
        {"engine_delta": {"decode_steps": 120, "prefill_steps": 400},
         "cache_stats": {"hits": 1}}) is None
    assert readers.load_metric("layer_metrics", name)["reader"] == "ratio"


def test_benchmark_json_lists_them_after_the_entries_it_had():
    """By name, not by place: later PRs append entries and a benchmark PR
    may prune one (`prefill_kv_fetch_ms.score` went in PR 41: the arena
    has been on the device since PR 25 and the phase read 0.0 for good)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert "prefill_kv_fetch_ms.score" not in by_name
    assert not os.path.exists(os.path.join(
        ROOT, "benchmark", "layer_metrics", "prefill_kv_fetch_ms.score.json"))
    cells = {w["name"] for w in bench["workloads"]}
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in WANT}
    judged = {m["name"]: m for m in bench["end_to_end"]}
    for name in WANT:
        m = by_name[name]
        assert m["better"] == "lower" and m["source"] == "program_counter"
        assert m["layer"] in layers and set(m["workloads"]) <= cells
        # every cell it lists reports the end-to-end metric it moves
        assert set(m["workloads"]) <= set(judged[m["moves"]]["workloads"])
