"""The SDAR-MoE configuration's benchmark files: the whole cell through the
harness at toy widths on the CPU, the file against the catalog and against
the traffic file, the yardstick's counts by hand, and the readers on a
parent that lacks the counters."""

import argparse
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import readers, run, sdar_yardstick

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data")
CELL = "sdar-30b-a3b-l7.block-denoise"
FILE = "configs/sdar-30b-a3b-l7.json"


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _config():
    return _json(ROOT, "benchmark", FILE)


def test_the_files_widths_are_the_published_ones():
    config = _config()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "SDAR-30B-A3B-Chat"]
    assert config["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items()
                     if config.get(k, "absent") != v)
    assert differs == sorted(config["reduced"])
    assert {k: row["config"][k] for k in differs} == config["published"]
    from benchmark.sdar_cell import sdar_engine

    cfg = sdar_engine(config)["model_cfg"]
    assert (cfg.n_experts, cfg.experts_held, cfg.first_expert, cfg.top_k) \
        == (128, 128, 0, 8)
    assert (cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.head_dim,
            cfg.moe_ffn_dim, cfg.vocab_size) == (2048, 32, 4, 128, 768,
                                                 151936)
    assert (cfg.block_length, cfg.denoise_steps, cfg.mask_token) \
        == (4, 2, 151669)
    # the file's own count is the yardstick's, and the module's: 4.98B
    assert config["parameters"] == sdar_yardstick.count_parameters(config)
    from ray_tpu.models.sdar_moe import SdarMoe
    shapes = jax.eval_shape(SdarMoe(cfg).init, jax.random.PRNGKey(0),
                            jnp.ones((1, 8), jnp.int32))
    assert sum(int(np.prod(a.shape)) for a in
               jax.tree_util.tree_leaves(shapes)) \
        == config["parameters"]["total"] == 4984176384
    assert config["parameters"]["bf16_gb"] == 9.97


def test_the_check_answer_length_is_the_traffic_files():
    """`bench_check` passes a reference no prompt length: the file repeats
    the check's answer length, and here the two are tied."""
    for config, traffic in (
            (_config(), _json(ROOT, "benchmark", "traffic",
                              "block-denoise.json")),
            (_json(DATA, "configs", "tiny-sdar-moe.json"),
             _json(DATA, "traffic", "tiny-block-denoise.json"))):
        assert config["generation"]["check_new_tokens"] == \
            1 + traffic["check_decode_steps"]


def test_the_cells_own_limit_reaches_the_harness(monkeypatch):
    """The harness holds every serving cell to a shortfall of 0.5 of a
    row's rms; this file states 0.12 (between the sound program's 0.050
    and its 8-bit control's 0.236 on the chip) and the reference applies
    it: a streamed token under the cell's limit leaves its row as
    computed, one between the two limits makes the harness's own measure
    read over 0.5 (and a number), and without the key nothing is touched."""
    from benchmark.references import sdar_moe as ref
    from benchmark.serve_cell import SHORTFALL_TOLERANCE

    config = _config()
    gen = config["generation"]
    assert 0.050 * 2 < gen["check_shortfall_limit"] < 0.236 / 1.5
    rng = np.random.default_rng(5)
    s, new, vocab = 9, gen["check_new_tokens"], 4096
    rows = {at: rng.normal(size=vocab).astype(np.float32)
            for at in range(s, s + new)}

    def token_short_by(row, least, most):
        measure = (row.max() - row) / np.sqrt(np.mean(row ** 2))
        return int(np.flatnonzero((measure >= least) & (measure < most))[0])

    answer = [token_short_by(rows[at], 0.0, 0.1) for at in rows]
    answer[2] = token_short_by(rows[s + 2], 0.15, 0.45)
    monkeypatch.setattr(ref, "replay",
                        lambda *a: {"rows": dict(rows)})
    ids = list(range(1, s + 1)) + answer[:-1]
    got = ref.logits(None, config, ids)
    for j, token in enumerate(answer):
        if j == 2:
            read = ref.shortfall(got[s - 1 + j], token)
            assert np.isfinite(read) and read > 10 * SHORTFALL_TOLERANCE
        else:
            np.testing.assert_array_equal(got[s - 1 + j], rows[s + j])
    gen.pop("check_shortfall_limit")
    got = ref.logits(None, config, ids)
    np.testing.assert_array_equal(got[s + 1], rows[s + 2])


@pytest.mark.parametrize("key, value", [
    ("norm_topk_prob", False), ("decoder_sparse_step", 2),
    ("mlp_only_layers", [0]), ("use_sliding_window", True),
    ("tie_word_embeddings", True), ("attention_bias", True)])
def test_the_builder_refuses_what_the_program_does_not_compute(key, value):
    from benchmark.sdar_cell import sdar_engine

    config = _json(DATA, "configs", "tiny-sdar-moe.json")
    config[key] = value
    with pytest.raises(RuntimeError, match=key):
        sdar_engine(config)


@pytest.mark.parametrize("key, value", [
    ("remasking", "dynamic_threshold"), ("block_length", 3),
    ("mask_token_id", 512)])
def test_the_builder_refuses_a_schedule_it_does_not_run(key, value):
    from benchmark.sdar_cell import sdar_engine

    config = _json(DATA, "configs", "tiny-sdar-moe.json")
    config["generation"][key] = value
    with pytest.raises(RuntimeError, match=key):
        sdar_engine(config)


def test_the_cell_runs_through_the_harness_at_toy_widths():
    """`run.py`'s own path on the CPU: the builder, one-shot and chunked
    prefill of whole blocks, denoising and commit passes through the
    pages, `correct` against the replayed reference, and every per-layer
    metric the cell lists but those of a device trace and the roofline (a
    CPU has no peak in the yardstick)."""
    args = argparse.Namespace(workload="tiny-sdar.block-denoise", seed=7,
                              seconds=3.0, trace=1)
    try:
        line = run.run(args, require_tpu=False,
                       bench_file=os.path.join(DATA, "BENCHMARK.sdar.json"),
                       traffic_folder=os.path.join(DATA, "traffic"))
    finally:
        assert run.kill_leftovers() == []
    assert line["correct"] is True and line["failed"] == 0, line
    check = line["notes"]["check"]
    assert check["prompts"] == [21, 44] and check["tokens_checked"] == 16
    # float32 both, and the reference replays the program's own choices
    assert check["worst_shortfall"] < 1e-3
    listed = _json(DATA, "BENCHMARK.sdar.json")["per_layer"]
    missing = {m["name"] for m in listed
               if m["source"] != "device_trace"
               and "roofline" not in m["name"]} - set(line["metrics"])
    assert not missing, missing
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # 4 / 3 and a third but for a request's last block, which ends without
    # a commit pass (answers of 6 to 10 blocks here, 128 to 256 in the cell)
    assert 1.2 < m["tokens_per_pass.block-denoise"] < 1.45
    assert 25 < m["commit_pass_share_pct.block-denoise"] < 34
    assert 0 < m["moe_experts_touched.block-denoise"] <= 16
    assert 0 < m["prefill_share_pct.block-denoise"] < 100
    assert m["itl_p90_ms.block-denoise"] > 0


def test_pass_bytes_of_the_cell_by_hand():
    """The issue's figures: attention 18.87M a layer, the router 0.26M, an
    expert 4.72M (9.44 MB), a layer 623.1M, embedding and head 622.3M,
    4.98B in all; K and V 2 KB a token a layer; a page 229 KB."""
    model = _config()
    assert sdar_yardstick.attention_params(model) == \
        2048 * (4096 + 512 + 512) + 4096 * 2048 + 256
    assert sdar_yardstick.router_params(model) == 2048 * 128
    assert sdar_yardstick.expert_params(model) == 3 * 2048 * 768 == 4718592
    count = sdar_yardstick.count_parameters(model)
    assert count["a_layer"] == 623120640
    assert count["total"] == 7 * 623120640 + 2 * 151936 * 2048 + 2048
    assert sdar_yardstick.kv_bytes_per_token_layer(model) == 2048
    engine = model["engine"]
    assert engine["block_size"] * 7 * 2048 == 229376
    assert engine["num_pages"] >= 64 * (1024 + 1024) // 16
    assert engine["num_pages"] * 229376 < 1.95e9
    outside = sdar_yardstick.pass_weight_params_outside_experts(model)
    assert outside == 7 * (18874624 + 262144) + 2048 * 151936
    need = sdar_yardstick.pass_required_bytes(model, 7 * 128.0, 64 * 900.0)
    assert need == 2.0 * (outside + 896 * 4718592) + 57600 * 7 * 2048
    # the issue's count: about 10 GB a pass, 12.2 ms at 819 GB/s
    assert 9.9e9 < need < 10.4e9


def test_readers_and_the_parents_missing_counters():
    delta = {"decode_steps": 100, "decode_moe_expert_calls": 89600,
             "decode_context_tokens": 5_760_000,
             "decode_lane_passes": 6400, "decode_lane_commits": 2133,
             "decode_tokens_revealed": 8532,
             "ph_decode_dispatch_ms": 300.0,
             "ph_decode_device_wait_ms": 3200.0,
             "prefill_ms": 5000.0, "pump_wall_ms": 50000.0}
    obs = {"engine_delta": delta, "device_kind": "TPU v5 lite",
           "itl_ms": [0.01] * 80 + [30.0] * 20}
    got = readers.read_metric("layer_metrics",
                              "decode_hbm_roofline_pct.block-denoise", obs)
    need = sdar_yardstick.pass_required_bytes(_config(), 896.0, 57600.0)
    assert got == pytest.approx(100 * need / (819e9 * 35e-3))
    assert 0 < got < 100
    read = {name: readers.read_metric("layer_metrics", name, obs)
            for name in ("tokens_per_pass.block-denoise",
                         "commit_pass_share_pct.block-denoise",
                         "moe_experts_touched.block-denoise",
                         "prefill_share_pct.block-denoise",
                         "itl_p90_ms.block-denoise")}
    assert read == {
        "tokens_per_pass.block-denoise": pytest.approx(8532 / 6400),
        "commit_pass_share_pct.block-denoise":
            pytest.approx(100 * 2133 / 6400),
        "moe_experts_touched.block-denoise": pytest.approx(128.0),
        "prefill_share_pct.block-denoise": pytest.approx(10.0),
        "itl_p90_ms.block-denoise": pytest.approx(30.0)}
    obs["trace"] = {"busy_s": 2.0, "window_s": 4.0, "op_seconds": {
        "custom-call bf16[2048,1536]": 0.5, "custom-call bf16[2048,2048]": 0.3,
        "custom-call bf16[8192,1536]": 0.1, "custom-call bf16[2048,4096]": 9.0,
        "fusion f32[64,4,8,4,256]": 0.05, "fusion f32[64,4,8,4]": 0.01,
        "fusion bf16[1024,16,4,128]": 0.04, "fusion bf16[64,4,2048]": 9.0}}
    assert readers.read_metric(
        "layer_metrics", "moe_experts_device_pct.block-denoise", obs) == \
        pytest.approx(100 * 0.9 / 2.0)
    assert readers.read_metric(
        "layer_metrics", "block_attention_device_pct.block-denoise", obs) \
        == pytest.approx(100 * 0.1 / 2.0)
    # the parent's engine has none of the counters: left out, no raise
    mine = [m["name"] for m in _json(ROOT, "BENCHMARK.json")["per_layer"]
            if m.get("workloads") == [CELL]]
    assert len(mine) == 8
    parent = {"engine_delta": {"decode_steps": 100,
                               "ph_decode_dispatch_ms": 1.0,
                               "ph_decode_device_wait_ms": 1.0},
              "device_kind": "TPU v5 lite",
              "trace": {"busy_s": 2.0, "window_s": 4.0,
                        "op_seconds": {"fusion bf16[16,4096]": 1.0}}}
    for name in mine:
        assert readers.read_metric("layer_metrics", name, parent) is None


def test_benchmark_json_gains_the_cell_by_additions_only():
    bench = _json(ROOT, "BENCHMARK.json")
    # by name, not by place: a later PR appends after this one
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    (entry,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
    assert cell["chips"] == 1 and cell["traffic"] == "block-denoise"
    assert entry["file"] == "benchmark/" + FILE
    assert entry["reduced"] == _config()["reduced"] == \
        ["num_hidden_layers", "max_position_embeddings"]
    judged = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in judged["out_tokens_per_s"]["workloads"]
    assert CELL not in judged["itl_p50_ms"]["workloads"]
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["moves"] == "out_tokens_per_s"
            readers.load_metric("layer_metrics", m["name"])
    assert all(len(w["why"]) <= 200 for w in bench["workloads"])
    traffic = _json(ROOT, "benchmark", "traffic", "block-denoise.json")
    from benchmark.traffic import expand_deck
    deck = expand_deck(traffic)
    assert len(deck) == 32 and traffic["callers"] == 64
    assert max(p for p, _ in deck) <= 1024 and min(p for p, _ in deck) == 128
    assert max(p + n for p, n in deck) <= 2048
    assert 530 < sum(p for p, _ in deck) / 32 < 542
    assert sum(n for _, n in deck) / 32 == 768
    assert all(n % 4 == 0 for _, n in deck)
    assert sum(1 for p, _ in deck if p % 4) > 16
    assert traffic["check_prompts"] == [600, 301]
