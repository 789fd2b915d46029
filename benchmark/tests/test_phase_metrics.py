"""The per-layer metrics that read the program's phase counters (PR 24):
each is a file for the `ratio` reader, appended to `BENCHMARK.json` without
touching what was there."""

import hashlib
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
    "prefill_kv_fetch_ms.score": 15.0,
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
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    had, new = bench["per_layer"][:20], bench["per_layer"][20:]
    assert hashlib.sha256(json.dumps(had, sort_keys=True).encode()) \
        .hexdigest() == ("b09fe36fecd8689fdd5e36aca0dbd4253e99d1a7c12b3fb3"
                         "d300b00bd112d761")
    assert [m["name"] for m in new] == [
        "decode_dispatch_ms.generate", "decode_device_wait_ms.generate",
        "decode_fetch_ms.generate", "decode_kv_append_ms.generate",
        "decode_sample_ms.generate", "prefill_device_wait_ms.score",
        "prefill_kv_fetch_ms.score", "prefill_kv_write_ms.score",
        "pump_idle_pct.score", "pump_lock_wait_pct.score",
        "metrics_poll_pct.score", "cache_lookup_ms.train"]
    cells = {w["name"] for w in bench["workloads"]}
    layers = {m["layer"] for m in had}
    judged = {m["name"]: m for m in bench["end_to_end"]}
    for m in new:
        assert m["better"] == "lower" and m["source"] == "program_counter"
        assert m["layer"] in layers and set(m["workloads"]) <= cells
        # every cell it lists reports the end-to-end metric it moves
        assert set(m["workloads"]) <= set(judged[m["moves"]]["workloads"])
