"""The Kimi-K2 configuration's benchmark files: its reference against the
program's model, the whole cell through the harness at toy widths on the
CPU, and the yardstick's count of a decode step's bytes."""

import argparse
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import kimi_yardstick, readers, run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data")
CELL = "kimi-k2.6-ep32-l7.generate-long"


def _config(name="configs/kimi-k2.6-ep32-l7.json", base=None):
    with open(os.path.join(base or os.path.join(ROOT, "benchmark"),
                           name)) as f:
        return json.load(f)


def test_kimi_reference_matches_the_model_forward():
    """The plain reference against `models/kimi_k2.py`'s full forward at the
    toy file's widths (float32 both, 4 of 16 experts held from expert 4
    on): 1e-4 absolute on logits of order 1, the order of the sums apart."""
    from benchmark.kimi_cell import kimi_engine
    from benchmark.references import kimi_k2 as ref

    config = _config("configs/tiny-kimi-k2.json", DATA)
    built = kimi_engine(config)
    assert built["model"] == "kimi_k2"
    assert built["model_cfg"].first_expert == 4
    assert built["model_cfg"].experts_held == 4
    params = built["net"].init(jax.random.PRNGKey(1),
                               jnp.ones((1, 8), jnp.int32))
    ids = np.random.default_rng(0).integers(0, config["vocab_size"], 50)
    with jax.default_matmul_precision("highest"):
        want = ref.logits(params["params"], config,
                          jnp.asarray(ids, jnp.int32))
        got = built["net"].apply(params, jnp.asarray(ids[None], jnp.int32))[0]
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    assert float(np.std(np.asarray(want))) > 0.05


def test_the_files_widths_are_the_published_ones():
    config = _config()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "Kimi-K2.6"]
    assert config["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items()
                     if config.get(k) != v)
    assert differs == sorted(config["reduced"])
    assert {k: row["config"][k] for k in differs} == config["published"]
    from benchmark.kimi_cell import kimi_engine

    cfg = kimi_engine(config)["model_cfg"]
    assert (cfg.n_experts, cfg.experts_held, cfg.first_expert, cfg.top_k) \
        == (384, 12, 0, 8)
    assert (cfg.d_model, cfg.n_head, cfg.latent_dim, cfg.row_dim) \
        == (7168, 64, 576, 640)


def test_the_cell_runs_through_the_harness_at_toy_widths():
    """`run.py`'s own path on the CPU: the builder, chunked prefill and
    decode through the latent cache, `correct` against the reference, and
    every per-layer metric the cell lists but the roofline (a CPU has no
    peak in the yardstick)."""
    args = argparse.Namespace(workload="tiny-kimi.generate-long", seed=7,
                              seconds=3.0, trace=1)
    try:
        line = run.run(args, require_tpu=False,
                       bench_file=os.path.join(DATA, "BENCHMARK.kimi.json"),
                       traffic_folder=os.path.join(DATA, "traffic"))
    finally:
        assert run.kill_leftovers() == []
    assert line["correct"] is True and line["failed"] == 0, line
    check = line["notes"]["check"]
    assert check["prompts"] == [20, 40] and check["tokens_checked"] == 8
    with open(os.path.join(DATA, "BENCHMARK.kimi.json")) as f:
        listed = json.load(f)["per_layer"]
    # the CPU's trace has no device plane: what reads one is left out
    missing = {m["name"] for m in listed if m["source"] != "device_trace"} \
        - set(line["metrics"])
    assert not missing, missing
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # 4 of 16 experts held: about a quarter of the pairs, never none
    assert 5 < m["moe_local_share_pct.generate-long"] < 60
    assert 0 < m["moe_experts_touched.generate-long"] <= 4 * 2 / 6
    assert m["chunks_per_decode_step.generate-long"] > 0
    assert m["prefill_chunk_ms.generate-long"] > 0
    assert m["latent_arena_gb.generate-long"] == pytest.approx(
        32 * 3 * 16 * 128 * 4 / 1e9)


def test_decode_bytes_of_the_cell_by_hand():
    """The issue's table: attention 101.1M a layer, the dense feed-forward
    396.4M, a shared expert and a routed one 44.04M each, the router 2.75M,
    the head 146.8M; 576 values a cached token a layer."""
    model = _config()
    assert kimi_yardstick.attention_params(model) == \
        7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 256 \
        + 64 * 128 * 7168
    assert kimi_yardstick.expert_params(model) == 3 * 7168 * 2048
    outside = kimi_yardstick.decode_weight_params_outside_experts(model)
    assert outside == 7 * kimi_yardstick.attention_params(model) \
        + 3 * 7168 * 18432 + 6 * (3 * 7168 * 2048 + 7168 * 384) \
        + 7168 * 20480
    need = kimi_yardstick.decode_required_bytes(model, 20.0, 60000.0)
    assert need == 2.0 * (outside + 20 * 3 * 7168 * 2048
                          + 60000 * 7 * 576)
    assert 4.5e9 < need < 5.5e9


def test_roofline_reader_and_the_parents_missing_counters():
    delta = {"decode_steps": 100, "decode_moe_expert_calls": 2000,
             "decode_context_tokens": 6_000_000,
             "ph_decode_dispatch_ms": 300.0,
             "ph_decode_device_wait_ms": 900.0}
    obs = {"engine_delta": delta, "device_kind": "TPU v5 lite"}
    got = readers.read_metric("layer_metrics",
                              "decode_hbm_roofline_pct.generate-long", obs)
    need = kimi_yardstick.decode_required_bytes(_config(), 20.0, 60000.0)
    assert got == pytest.approx(100 * need / (819e9 * 12e-3))
    assert 0 < got < 100
    # the parent's engine has neither counter: left out, no raise
    for name in ("decode_hbm_roofline_pct", "moe_local_share_pct",
                 "moe_experts_touched", "prefill_chunk_ms"):
        assert readers.read_metric(
            "layer_metrics", f"{name}.generate-long",
            {"engine_delta": {"decode_steps": 100, "chunk_steps": 5,
                              "ph_decode_dispatch_ms": 1.0,
                              "ph_decode_device_wait_ms": 1.0},
             "device_kind": "TPU v5 lite"}) is None
    assert kimi_yardstick.device_share_pct({}, {"pattern": "x"}) is None
    trace = {"busy_s": 2.0, "op_seconds": {"ragged-dot bf16[128,4096]": 0.5,
                                           "fusion f32[16]": 1.0}}
    assert kimi_yardstick.device_share_pct(
        {"trace": trace}, {"pattern": "^ragged-dot"}) == pytest.approx(25.0)
    assert kimi_yardstick.device_share_pct(
        {"trace": trace}, {"pattern": "^nothing"}) is None


def test_benchmark_json_gains_the_cell_by_additions_only():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # by name: the cells and configurations after it are later PRs'
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert config["file"] == "benchmark/configs/kimi-k2.6-ep32-l7.json"
    judged = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", []):
            assert CELL in judged[m["moves"]]["workloads"]
            readers.load_metric("layer_metrics", m["name"])
    assert all(len(w["why"]) <= 200 for w in bench["workloads"])
