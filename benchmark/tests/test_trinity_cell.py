"""The Trinity (AFMoE) configuration's benchmark files: the whole cell
through the harness at toy widths on the CPU, the file against the catalog
and against the traffic file, the yardstick's counts by hand, and the readers
on a parent that lacks the counters."""

import argparse
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import readers, run, trinity_yardstick

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data")
CELL = "trinity-large-ep32-l9.short-long"
FILE = "configs/trinity-large-ep32-l9.json"


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _config():
    return _json(ROOT, "benchmark", FILE)


def test_the_files_widths_are_the_published_ones():
    config = _config()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "Trinity-Large-Preview"]
    assert config["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items()
                     if config.get(k, "absent") != v)
    assert differs == sorted(config["reduced"])
    published = dict(config["published"])
    assert isinstance(published.pop("layer_types"), str)
    assert {k: row["config"][k] for k in differs if k != "layer_types"} \
        == published
    # every reading that is not a key of the catalog's config is stated
    for key in ("qk_norm", "rope_on_sliding_layers_only", "output_gate",
                "sandwich_norm", "embedding_scale", "expert_bias"):
        assert key in config["assumed"]
    from benchmark.trinity_cell import afmoe_engine

    cfg = afmoe_engine(config)["model_cfg"]
    assert (cfg.n_experts, cfg.experts_held, cfg.first_expert, cfg.top_k) \
        == (256, 8, 0, 4)
    assert (cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.head_dim,
            cfg.ffn_dim, cfg.moe_ffn_dim, cfg.vocab_size, cfg.window) == (
        3072, 48, 8, 128, 12288, 3072, 25024, 4096)
    assert cfg.types == ("sliding",) + ("sliding",) * 3 + ("full",) \
        + ("sliding",) * 3 + ("full",)
    # the file's own count is the yardstick's, and the module's: 2.88B
    assert config["parameters"] == trinity_yardstick.count_parameters(config)
    from ray_tpu.models.afmoe import Afmoe, page_kinds
    shapes = jax.eval_shape(Afmoe(cfg).init, jax.random.PRNGKey(0),
                            jnp.ones((1, 8), jnp.int32))
    assert sum(int(np.prod(a.shape)) for a in
               jax.tree_util.tree_leaves(shapes)) \
        == config["parameters"]["total"] == 2878065920
    assert config["parameters"]["bf16_gb"] == 5.76
    assert page_kinds(cfg) == (("window", 7, ((8, 128),) * 2, 4096),
                               ("full", 2, ((8, 128),) * 2, None))


def test_the_check_answer_length_is_the_traffic_files():
    """`bench_check` passes a reference no prompt length: the file repeats
    the check's answer length, and here the two are tied."""
    for config, traffic in (
            (_config(), _json(ROOT, "benchmark", "traffic",
                              "short-long.json")),
            (_json(DATA, "configs", "tiny-afmoe.json"),
             _json(DATA, "traffic", "tiny-short-long.json"))):
        assert config["check"]["new_tokens"] == \
            1 + traffic["check_decode_steps"]


def test_the_cells_own_limit_lies_between_its_two_readings():
    """The file states the cell's own limit with both readings (sound 0 to
    0.136, the 8-bit control 1.09 to 2.00 on the chip, PR 46); the reference
    applies it (`tests/test_afmoe.py`), and it is under the harness's."""
    from benchmark.serve_cell import SHORTFALL_TOLERANCE

    check = _config()["check"]
    assert 0.136 * 2 < check["shortfall_limit"] < 1.09 / 2
    assert check["shortfall_limit"] < SHORTFALL_TOLERANCE
    for reading in ("0.136", "1.09", "2.00"):
        assert reading in check["shortfall_limit_why"]


@pytest.mark.parametrize("key, value", [
    ("score_func", "softmax"), ("route_norm", False), ("n_group", 2),
    ("rope_scaling", {"type": "yarn"}), ("tie_word_embeddings", True),
    ("mup_enabled", False), ("layer_types", ["full_attention"])])
def test_the_builder_refuses_what_the_program_does_not_compute(key, value):
    from benchmark.trinity_cell import afmoe_engine

    config = _json(DATA, "configs", "tiny-afmoe.json")
    config[key] = value
    with pytest.raises(RuntimeError, match=key):
        afmoe_engine(config)


def test_the_cell_runs_through_the_harness_at_toy_widths():
    """`run.py`'s own path on the CPU: the builder, one-shot and chunked
    prefill into pages of two kinds, decode past the window through a ring
    that has wrapped, `correct` against the reference (a check prompt of 100
    tokens against a window of 16 and a chunk of 32), and every per-layer
    metric the cell lists but those of a device trace and the roofline (a
    CPU has no peak in the yardstick)."""
    args = argparse.Namespace(workload="tiny-trinity.short-long", seed=7,
                              seconds=3.0, trace=1)
    try:
        line = run.run(args, require_tpu=False,
                       bench_file=os.path.join(DATA,
                                               "BENCHMARK.trinity.json"),
                       traffic_folder=os.path.join(DATA, "traffic"))
    finally:
        assert run.kill_leftovers() == []
    assert line["correct"] is True and line["failed"] == 0, line
    check = line["notes"]["check"]
    assert check["prompts"] == [40, 100] and check["tokens_checked"] == 8
    assert check["worst_shortfall"] < 1e-3          # float32 both
    listed = _json(DATA, "BENCHMARK.trinity.json")["per_layer"]
    missing = {m["name"] for m in listed
               if m["source"] != "device_trace"
               and "roofline" not in m["name"]} - set(line["metrics"])
    assert not missing, missing
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # window 16 in pages of 8: a ring of 3; the long prompts always run
    assert m["window_pages_seq_max.short-long"] == 3
    # 3 window layers x at most 3 pages + 2 full layers x up to 18 pages,
    # over 5 layers x the full kind's pages
    assert 40 < m["kv_bytes_vs_uniform_pct.short-long"] < 85
    assert 0 < m["key_slots_window_per_step.short-long"] <= 4 * (1 + 24)
    assert m["key_slots_full_per_step.short-long"] \
        > m["key_slots_window_per_step.short-long"]
    assert 0 < m["moe_experts_touched.short-long"] <= 4
    assert 20 < m["moe_local_share_pct.short-long"] < 30   # 4 of 16 held
    assert m["chunks_per_decode_step.short-long"] > 0
    assert m["prefill_chunk_ms.short-long"] > 0
    assert m["decode_step_ms.short-long"] > 0


def test_step_bytes_of_the_cell_by_hand():
    """The issue's figures: attention 62.9M a layer, a dense layer 176.2M,
    an expert 28.3M, an expert layer with 8 held 318.5M, the vocabulary's
    slice 153.7M, 2.88B in all; K and V 4 KB a token a layer; a page of a
    layer 64 KB; the ring 257 pages a sequence."""
    model = _config()
    assert trinity_yardstick.attention_params(model) == \
        3072 * (6144 + 1024 + 1024 + 6144) + 6144 * 3072 + 256
    assert trinity_yardstick.expert_params(model) == 3 * 3072 * 3072 \
        == 28311552
    count = trinity_yardstick.count_parameters(model)
    assert count["dense_layer"] == 62914816 + 4 * 3072 + 3 * 3072 * 12288
    assert count["expert_layer_here"] == 62914816 + 4 * 3072 \
        + 3072 * 256 + 256 + 9 * 28311552
    assert count["total"] == count["dense_layer"] \
        + 8 * count["expert_layer_here"] + 2 * 25024 * 3072 + 3072
    assert trinity_yardstick.kv_bytes_per_token_layer(model) == 4096
    assert trinity_yardstick.layers_by_kind(model) == {"window": 7,
                                                       "full": 2}
    engine = model["engine"]
    assert engine["block_size"] * 4096 == 65536
    from benchmark.traffic import expand_deck
    deck = expand_deck(_json(ROOT, "benchmark", "traffic", "short-long.json"))
    # the deck is the running set (32 callers, 32 entries, fixed lanes):
    # its worst case of full-kind pages fits, with the check's and warm-up's
    worst = sum(-(-(p + n) // 16) for p, n in deck)
    assert worst + 600 <= engine["num_pages"]
    outside = trinity_yardstick.decode_weight_params_outside_experts(model)
    assert outside == 9 * 62914816 + 3 * 3072 * 12288 \
        + 8 * (28311552 + 3072 * 256 + 256) + 3072 * 25024
    # 32 lanes, 4 of 256: 0.5 pairs an expert, about 3.1 of 8 touched a
    # layer; the deck's contexts: 213k tokens, 69.6k inside the windows
    need = trinity_yardstick.decode_required_bytes(
        model, 8 * 3.1, 213000.0, 69600.0)
    assert need == 2.0 * (outside + 24.8 * 28311552) \
        + 4096 * (213000.0 * 2 + 69600.0 * 7)
    assert 6.5e9 < need < 7.5e9


def test_readers_and_the_parents_missing_counters():
    delta = {"decode_steps": 100, "decode_moe_expert_calls": 2480,
             "decode_moe_pairs_local": 400, "decode_moe_pairs_routed": 12800,
             "decode_context_tokens": 21_300_000,
             "decode_context_tokens_window": 6_960_000,
             "decode_kv_pages_window": 450_000,
             "decode_kv_pages_window_lane_max": 25_700,
             "decode_kv_pages_full": 1_340_000,
             "decode_key_slots_window": 100 * 7 * 32 * 4353,
             "decode_key_slots_full": 100 * 2 * 32 * 25089,
             "ph_decode_dispatch_ms": 300.0,
             "ph_decode_device_wait_ms": 2700.0,
             "decode_ms": 3300.0, "chunk_ms": 9000.0, "chunk_steps": 60}
    obs = {"engine_delta": delta, "device_kind": "TPU v5 lite"}

    def read(name):
        return readers.read_metric("layer_metrics", f"{name}.short-long", obs)

    need = trinity_yardstick.decode_required_bytes(
        _config(), 24.8, 213000.0, 69600.0)
    assert read("decode_hbm_roofline_pct") == pytest.approx(
        100 * need / (819e9 * 30e-3))
    assert 0 < read("decode_hbm_roofline_pct") < 100
    assert read("window_pages_seq_max") == pytest.approx(257.0)
    assert read("kv_bytes_vs_uniform_pct") == pytest.approx(
        100 * (450_000 * 7 + 1_340_000 * 2) / (1_340_000 * 9))
    assert read("key_slots_window_per_step") == pytest.approx(32 * 4353)
    assert read("key_slots_full_per_step") == pytest.approx(32 * 25089)
    assert read("moe_experts_touched") == pytest.approx(3.1)
    assert read("moe_local_share_pct") == pytest.approx(3.125)
    assert read("prefill_chunk_ms") == pytest.approx(150.0)
    assert read("chunks_per_decode_step") == pytest.approx(0.6)
    assert read("decode_step_ms") == pytest.approx(33.0)
    obs["trace"] = {"busy_s": 2.0, "window_s": 4.0, "op_seconds": {
        "fusion f32[32,8,6,1,256]": 0.3, "fusion bf16[512,16,8,128]": 0.2,
        "fusion f32[1,8,6,1024,256]": 0.1, "fusion f32[32,8,6,1]": 0.05,
        "fusion bf16[32,3072]": 9.0, "custom-call bf16[128,6144]": 9.0}}
    assert read("attention_device_pct") == pytest.approx(100 * 0.65 / 2.0)
    # the parent's engine has none of the counters: left out, no raise
    mine = [m["name"] for m in _json(ROOT, "BENCHMARK.json")["per_layer"]
            if m.get("workloads") == [CELL]]
    assert len(mine) == 11
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
    assert cell["chips"] == 1 and cell["traffic"] == "short-long"
    assert entry["file"] == "benchmark/" + FILE
    assert entry["reduced"] == _config()["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "layer_types", "num_experts",
        "vocab_size", "max_position_embeddings"]
    judged = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in judged["out_tokens_per_s"]["workloads"]
    assert CELL not in judged["itl_p50_ms"]["workloads"]
    listed = [m for m in bench["per_layer"] if CELL in m.get("workloads", [])]
    assert len(listed) == 11 + 3 + 8
    for m in listed:
        assert m["moves"] == "out_tokens_per_s"
        readers.load_metric("layer_metrics", m["name"])
    assert all(len(w["why"]) <= 200 for w in bench["workloads"])
    assert all(len(c["why"]) <= 200 for c in bench["configs"])
    traffic = _json(ROOT, "benchmark", "traffic", "short-long.json")
    from benchmark.traffic import expand_deck
    deck = expand_deck(traffic)
    assert len(deck) == 32 == traffic["callers"]
    assert traffic["order"] == "fixed_lanes"
    short = [p for p, _ in deck if p <= 2048]
    long = [p for p, _ in deck if p >= 8192]
    assert (len(short), len(long)) == (20, 12)
    assert (min(short), max(short), min(long), max(long)) == (
        256, 2048, 8192, 24576)
    assert 0.88 < sum(long) / sum(p for p, _ in deck) < 0.90
    assert {n for _, n in deck} == {128, 256, 384, 512}
    assert max(p + n for p, n in deck) <= 32768
    assert traffic["check_prompts"] == [1100, 9000]
    assert traffic["check_prompts"][1] > 4096 + 1024
