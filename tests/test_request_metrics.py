"""The request's stages and the stream's gaps as the benchmark reads them
(PR 43): the histogram reader, the eleven metric files over a recorded
`engine_delta`, and their entries at the end of `BENCHMARK.json`. CPU, no
engine: the engine's side is in `test_serve_llm.py`."""

import json
import os

import pytest

from benchmark import engine_hist, readers

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SERVE = ["mistral-7b-l20.generate", "kimi-k2.6-ep32-l7.generate-long",
         "ling-3.0-flash-vl-ep4-l7.reason-wide",
         "sdar-30b-a3b-l7.block-denoise"]
SCORE = ["mistral-7b-l20.score-serial"]
# name -> (cells, what it reads of RECORDED)
NEW_METRICS = {
    "req_queue_ms.score": (SCORE, 30.0 / 10),
    "req_admission_ms.score": (SCORE, 20.0 / 10),
    "req_first_hold_ms.score": (SCORE, 40.0 / 10),
    "req_queue_ms.serve": (SERVE, 30.0 / 10),
    "req_admission_ms.serve": (SERVE, 20.0 / 10),
    "req_prefill_span_ms.serve": (SERVE, 350.0 / 10),
    "req_first_hold_ms.serve": (SERVE, 40.0 / 10),
    "stream_gap_mean_ms.serve": (SERVE, 6000.0 / 200),
    "stream_gap_p95_ms.serve": (SERVE, 75.0),    # rank 190: half of (50, 100]
    "stream_stall_prefill_ms.serve": (SERVE, 1600.0 / 200),
    "stream_stall_admit_ms.serve": (SERVE, 100.0 / 200),
}
# a window's differences of `engine.metrics()`, as `serve_cell` keeps them:
# 200 gaps, 180 of them of 20 ms and 20 in (50, 100]
RECORDED = {"engine_delta": {
    "decode_steps": 100, "req_first_tokens": 10, "req_queue_ms": 30.0,
    "req_admission_ms": 20.0, "req_prefill_span_ms": 350.0,
    "req_first_hold_ms": 40.0, "stream_gaps": 200, "stream_gap_ms": 6000.0,
    "stall_prefill_lane_ms": 1600.0, "stall_admit_lane_ms": 100.0,
    "stream_gap_le_10": 0, "stream_gap_le_20": 180, "stream_gap_le_50": 0,
    "stream_gap_le_100": 20, "stream_gap_le_inf": 0}}
# the program as it was before the counters came
OLDER = {"engine_delta": {"decode_steps": 100, "ph_decode_sample_ms": 96.0}}


def _hist(**buckets):
    return {"engine_delta": {f"gap_le_{edge}": n
                             for edge, n in buckets.items()}}


@pytest.mark.parametrize("obs, q, want", [
    ({"engine_delta": {}}, 95, None),                 # no such counters
    ({}, 95, None),                                   # no engine at all
    (_hist(**{"10": 0, "20": 0, "inf": 0}), 95, None),    # an empty window
    (_hist(**{"10": 0, "20": 8, "inf": 0}), 50, 15.0),    # one bucket
    (_hist(**{"10": 0, "20": 8, "inf": 0}), 100, 20.0),
    (_hist(**{"10": 4, "20": 4}), 25, 5.0),           # the first from 0
    (_hist(**{"10": 90, "20": 10, "inf": 0}), 95, 15.0),
    (_hist(**{"10": 5, "20": 0, "inf": 5}), 95, 20.0),    # `inf`: lower edge
    (_hist(**{"1.244": 10, "1.547": 10}), 75, 1.244 + 0.303 / 2),
])
def test_bucket_percentile_on_a_hand_made_histogram(obs, q, want):
    got = engine_hist.bucket_percentile(
        obs, {"prefix": "engine_delta.gap_le_", "q": q})
    assert got == (want if want is None else pytest.approx(want))


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_a_new_metric_file_reads_a_recorded_engine_delta(name):
    """A number where the program keeps the counters, nothing (and no
    error) where it does not: the parent's program under this benchmark."""
    got = readers.read_metric("layer_metrics", name, RECORDED)
    assert got == pytest.approx(NEW_METRICS[name][1])
    assert readers.read_metric("layer_metrics", name, OLDER) is None
    assert readers.read_metric("layer_metrics", name, {}) is None
    spec = readers.load_metric("layer_metrics", name)
    assert spec["reads"].startswith("engine.metrics()")


def test_the_new_metrics_are_in_the_benchmarks_per_layer_list():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = json.load(f)["per_layer"]
    # by name, not by place: a later PR appends after these, and a later
    # serving cell appends its name to the `.serve` lists
    tail = [m for m in listed if m["name"] in NEW_METRICS]
    assert sorted(m["name"] for m in tail) == sorted(NEW_METRICS)
    for m in tail:
        cells = NEW_METRICS[m["name"]][0]
        assert m["workloads"][:len(cells)] == cells
        assert m["layer"] == "engine"
        assert (m["source"], m["unit"], m["better"]) == \
            ("program_counter", "ms", "lower")
        assert m["moves"] == ("ttft_p95_ms" if cells is SCORE
                              else "out_tokens_per_s")


def test_the_engines_histogram_is_what_the_reader_parses():
    """The edges are a constant of the engine: 1 ms to 4 s, a ratio under
    1.25, names the reader turns back into the same numbers."""
    from ray_tpu.serve.llm import engine

    edges = engine.STREAM_GAP_EDGES_MS
    assert edges[0] == 1.0 and edges[-1] == 4000.0
    assert all(1.0 < b / a < 1.25 for a, b in zip(edges, edges[1:]))
    assert len(engine._GAP_KEYS) == len(edges) + 1
    assert [float(k[len("stream_gap_le_"):]) for k in engine._GAP_KEYS] \
        == list(edges) + [float("inf")]
    # 30 gaps of 2 ms in the bucket that holds 2 ms: its edges bound them
    delta = dict.fromkeys(engine._GAP_KEYS, 0)
    key = next(k for k, e in zip(engine._GAP_KEYS, edges) if e >= 2.0)
    delta[key] = 30
    p = engine_hist.bucket_percentile(
        {"engine_delta": delta},
        {"prefix": "engine_delta.stream_gap_le_", "q": 95})
    assert 2.0 / 1.25 < p <= float(key[len("stream_gap_le_"):])
