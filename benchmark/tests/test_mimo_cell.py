"""The MiMo-V2 configuration's benchmark files: the whole cell through the
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

from benchmark import mimo_yardstick, readers, run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data")
CELL = "mimo-v2.5-ep16-l7.long-agent"
FILE = "configs/mimo-v2.5-ep16-l7.json"
REDUCED = ["num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
           "n_routed_experts", "vocab_size", "max_position_embeddings"]
# the cell's own per-layer metrics, and of them the ones a device trace gives
MINE = ["kv_bytes_vs_uniform_pct", "key_slots_window_per_step",
        "key_slots_full_per_step", "decode_hbm_roofline_pct",
        "attn_full_hbm_roofline_pct", "attn_window_device_pct",
        "attn_full_device_pct", "sink_mass_pct", "moe_experts_touched",
        "moe_local_share_pct", "moe_tile_visits", "moe_experts_device_pct",
        "prefill_chunk_ms", "chunks_per_decode_step", "decode_step_ms"]
TRACED = {"attn_full_hbm_roofline_pct", "attn_window_device_pct",
          "attn_full_device_pct", "moe_experts_device_pct"}


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _config():
    return _json(ROOT, "benchmark", FILE)


def test_the_files_widths_are_the_published_ones():
    config = _config()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "MiMo-V2.5"]
    assert config["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items()
                     if config.get(k, "absent") != v)
    assert differs == sorted(config["reduced"]) == sorted(REDUCED)
    published = dict(config["published"])
    for key in ("hybrid_layer_pattern", "moe_layer_freq"):
        assert isinstance(published.pop(key), str)
        n = config["num_hidden_layers"]         # the first 7 as they stand
        assert config[key] == row["config"][key][:n]
    assert {k: row["config"][k] for k in published} == published
    # every reading that is not settled by the catalog's config is stated
    for key in ("residual_form", "partial_rotary", "rope_bases", "window",
                "attention_sink", "attention_value_scale",
                "attention_chunk_size_and_hybrid_block_size",
                "routed_scaling_factor", "towers_and_mtp", "sink_draw",
                "expert_bias", "weights"):
        assert key in config["assumed"]
    assert config["deployment_share"]["chips_sharing_a_layer"] == 16
    from benchmark.mimo_cell import mimo_engine

    cfg = mimo_engine(config)["model_cfg"]
    assert (cfg.n_experts, cfg.experts_held, cfg.first_expert, cfg.top_k,
            cfg.n_shared) == (256, 16, 0, 8, 0)
    assert (cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.n_kv_head_window,
            cfg.head_dim, cfg.v_head_dim, cfg.rope_dim, cfg.window,
            cfg.ffn_dim, cfg.moe_ffn_dim, cfg.vocab_size) == (
        4096, 64, 4, 8, 192, 128, 64, 128, 16384, 2048, 19072)
    assert (cfg.rope_theta, cfg.rope_theta_window, cfg.value_scale,
            cfg.routed_scale, cfg.norm_eps) == (1e7, 1e4, 0.707, 1.0, 1e-5)
    assert cfg.types == ("full",) + ("window",) * 4 + ("full", "window")
    assert cfg.n_dense_layer == 1
    # the file's own count is the yardstick's, and the module's: 3.43B
    assert config["parameters"] == mimo_yardstick.count_parameters(config)
    from ray_tpu.models.mimo_v2 import MimoV2, page_kinds
    shapes = jax.eval_shape(MimoV2(cfg).init, jax.random.PRNGKey(0),
                            jnp.ones((1, 8), jnp.int32))
    assert sum(int(np.prod(a.shape)) for a in
               jax.tree_util.tree_leaves(shapes)) \
        == config["parameters"]["total"] == 3429955392
    assert config["parameters"]["bf16_gb"] == 6.86
    assert page_kinds(cfg) == (("window", 5, ((8 * 192,), (8 * 128,)), 128),
                               ("full", 2, ((4 * 192,), (4 * 128,)), None))


def test_the_check_answer_length_is_the_traffic_files():
    """`bench_check` passes a reference no prompt length: the file repeats
    the check's answer length, and here the two are tied."""
    for config, traffic in (
            (_config(), _json(ROOT, "benchmark", "traffic",
                              "long-agent.json")),
            (_json(DATA, "configs", "tiny-mimo.json"),
             _json(DATA, "traffic", "tiny-long-agent.json"))):
        assert config["check"]["new_tokens"] == \
            1 + traffic["check_decode_steps"]


def test_the_cells_own_limit_lies_between_its_readings():
    """The file states the cell's own limit with its readings on the chip
    (PR 57: the sound program 0 to 0.0961 over 25 checks, the 8-bit control
    0.80 to 2.27); it lies between them with room on both sides and under
    the harness's; the reference applies it (`tests/test_mimo_v2.py`)."""
    from benchmark.serve_cell import SHORTFALL_TOLERANCE

    check = _config()["check"]
    assert 0.0961 * 2.5 < check["shortfall_limit"] < 0.80 / 2.5
    assert check["shortfall_limit"] < SHORTFALL_TOLERANCE
    for word in ("sound", "8-bit", "0.0961", "0.80", "2.27", "sink_left_out",
                 "v_at_the_full_kinds_head_count", "window_one_too_wide"):
        assert word in check["shortfall_limit_why"], word


@pytest.mark.parametrize("key, value", [
    ("scoring_func", "softmax"), ("norm_topk_prob", False), ("n_group", 2),
    ("n_shared_experts", 1), ("routed_scaling_factor", 2.5),
    ("rope_scaling", {"type": "yarn"}), ("tie_word_embeddings", True),
    ("add_swa_attention_sink_bias", False),
    ("add_full_attention_sink_bias", True), ("attention_chunk_size", 64),
    ("hybrid_block_size", 4), ("attention_projection_layout", "split"),
    ("swa_head_dim", 32), ("swa_v_head_dim", 8),
    ("swa_num_attention_heads", 4), ("sliding_window_size", 8),
    ("hybrid_layer_pattern", [0, 1]), ("moe_layer_freq", [0, 1, 0, 1, 1])])
def test_the_builder_refuses_what_the_program_does_not_compute(key, value):
    from benchmark.mimo_cell import mimo_engine

    config = _json(DATA, "configs", "tiny-mimo.json")
    mimo_engine(config)
    config[key] = value
    with pytest.raises(RuntimeError, match=key):
        mimo_engine(config)


def test_the_cell_runs_through_the_harness_at_toy_widths():
    """`run.py`'s own path on the CPU: the builder, one-shot and chunked
    prefill into pages of two kinds of different row shapes, decode past the
    window through a ring that has wrapped, `correct` against the reference
    (a check prompt of 100 tokens against a window of 16 and a chunk of 32),
    and every per-layer metric the cell lists but those of a device trace
    and the roofline (a CPU has no peak in the yardstick)."""
    args = argparse.Namespace(workload="tiny-mimo.long-agent", seed=7,
                              seconds=3.0, trace=1)
    try:
        line = run.run(args, require_tpu=False,
                       bench_file=os.path.join(DATA, "BENCHMARK.mimo.json"),
                       traffic_folder=os.path.join(DATA, "traffic"))
    finally:
        assert run.kill_leftovers() == []
    assert line["correct"] is True and line["failed"] == 0, line
    check = line["notes"]["check"]
    assert check["prompts"] == [40, 100] and check["tokens_checked"] == 8
    assert check["worst_shortfall"] < 1e-3          # float32 both
    listed = _json(DATA, "BENCHMARK.mimo.json")["per_layer"]
    assert {m["name"] for m in listed} >= {
        f"{n}.long-agent" for n in MINE if "roofline" not in n}
    missing = {m["name"] for m in listed
               if m["source"] != "device_trace"
               and "roofline" not in m["name"]} - set(line["metrics"])
    assert not missing, missing
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # window kind: 3 layers x a ring of 3 pages x rows of 4 x 40; full kind:
    # 2 layers x up to 18 pages x rows of 2 x 40: under every layer keeping
    # every position, and over the full kind's own share of it
    assert 100 * (2 * 80) / (2 * 80 + 3 * 160) \
        < m["kv_bytes_vs_uniform_pct.long-agent"] < 85
    assert 0 < m["key_slots_window_per_step.long-agent"] <= 4 * (1 + 24)
    assert m["key_slots_full_per_step.long-agent"] \
        > m["key_slots_window_per_step.long-agent"]
    assert 0 < m["moe_experts_touched.long-agent"] <= 4
    assert 20 < m["moe_local_share_pct.long-agent"] < 30   # 4 of 16 held
    assert m["moe_tile_visits.long-agent"] > 0
    assert m["chunks_per_decode_step.long-agent"] > 0
    assert m["prefill_chunk_ms.long-agent"] > 0
    assert m["decode_step_ms.long-agent"] > 0
    # 16 keys near weight 1 beside sinks drawn around ln 128
    assert 50 < m["sink_mass_pct.long-agent"] < 100


def test_step_bytes_of_the_cell_by_hand():
    """The issue's figures: a full layer's attention 89.1M, a window layer's
    94.4M (and 64 sinks), the dense SwiGLU 201.3M, an expert 25.2M, a router
    1.05M, the vocabulary's slice 2 x 78.1M, 3.43B in all; K and V 5,120 B a
    token in a window layer and 2,560 B in a full one; the ring 9 pages a
    sequence."""
    model = _config()
    full = 4096 * (64 * 192 + 4 * 192 + 4 * 128) + 8192 * 4096
    window = 4096 * (64 * 192 + 8 * 192 + 8 * 128) + 8192 * 4096 + 64
    assert mimo_yardstick.attention_params(model, "full") == full == 89128960
    assert mimo_yardstick.attention_params(model, "window") == window \
        == 94371904
    expert = 3 * 4096 * 2048
    assert mimo_yardstick.expert_params(model) == expert == 25165824
    router = 4096 * 256 + 256
    count = mimo_yardstick.count_parameters(model)
    assert count["experts_and_router_here"] == router + 16 * expert
    assert count["dense_feed_forward"] == 3 * 4096 * 16384 == 201326592
    vocabulary = 2 * 19072 * 4096 + 4096
    assert count["total"] == (full + 8192 + 201326592) \
        + 5 * (window + 8192 + router + 16 * expert) \
        + (full + 8192 + router + 16 * expert) + vocabulary
    assert mimo_yardstick.kv_bytes_per_token_layer(model, "window") == 5120
    assert mimo_yardstick.kv_bytes_per_token_layer(model, "full") == 2560
    assert mimo_yardstick.layers_by_kind(model) == {"window": 5, "full": 2}
    engine = model["engine"]
    assert engine["num_pages"] * engine["block_size"] * 2 * 2560 \
        == 2684354560                                   # 2.68 GB
    from benchmark.traffic import expand_deck
    deck = expand_deck(_json(ROOT, "benchmark", "traffic", "long-agent.json"))
    # the deck is the running set (32 callers, 32 entries, fixed lanes):
    # its worst case of full-kind pages fits, with the check's and warm-up's
    worst = sum(-(-(p + n) // 16) for p, n in deck)
    assert worst == 27406 and worst + 2200 <= engine["num_pages"]
    assert max(p + n for p, n in deck) <= model["max_position_embeddings"]
    outside = mimo_yardstick.decode_weight_params_outside_experts(model)
    assert outside == 2 * full + 5 * window + 201326592 + 6 * router \
        + 4096 * 19072
    # 32 lanes, 8 of 256: 1.0 pair a held expert, about 10 of 16 touched a
    # layer; the deck's contexts: 428k tokens, 4k inside the windows
    need = mimo_yardstick.decode_required_bytes(
        model, 6 * 10.1, 428000.0, 4064.0)
    assert need == pytest.approx(2.0 * (outside + 60.6 * expert)
                                 + 428000.0 * 2 * 2560 + 4064.0 * 5 * 5120)
    assert 6.5e9 < need < 8e9


def test_readers_and_the_parents_missing_counters():
    delta = {"decode_steps": 100, "decode_moe_expert_calls": 6060,
             "decode_moe_pairs_local": 1600, "decode_moe_pairs_routed": 25600,
             "prefill_moe_tile_visits": 60 * 6 * 19,
             "decode_context_tokens": 42_800_000,
             "decode_context_tokens_window": 406_400,
             "chunk_context_tokens": 60 * 8000,
             "decode_kv_pages_window": 28_800,
             "decode_kv_pages_full": 2_680_000,
             "decode_key_slots_window": 100 * 5 * 32 * 145,
             "decode_key_slots_full": 100 * 2 * 32 * 13500,
             "decode_sink_mass_milli": 100 * 412,
             "ph_decode_dispatch_ms": 300.0,
             "ph_decode_device_wait_ms": 2200.0,
             "decode_ms": 2700.0, "chunk_ms": 18000.0, "chunk_steps": 60}
    obs = {"engine_delta": delta, "device_kind": "TPU v5 lite",
           "window_s": 51.0}

    def read(name):
        return readers.read_metric("layer_metrics", f"{name}.long-agent", obs)

    model = _config()
    need = mimo_yardstick.decode_required_bytes(
        model, 60.6, 428000.0, 4064.0)
    assert read("decode_hbm_roofline_pct") == pytest.approx(
        100 * need / (819e9 * 25e-3))
    assert 0 < read("decode_hbm_roofline_pct") < 100
    held, every = 28_800 * 5 * 5120 + 2_680_000 * 2 * 2560, \
        2_680_000 * (5 * 5120 + 2 * 2560)
    assert read("kv_bytes_vs_uniform_pct") == pytest.approx(
        100 * held / every)
    assert 16 < read("kv_bytes_vs_uniform_pct") < 18
    assert read("key_slots_window_per_step") == pytest.approx(32 * 145)
    assert read("key_slots_full_per_step") == pytest.approx(32 * 13500)
    assert read("moe_experts_touched") == pytest.approx(10.1)
    assert read("moe_local_share_pct") == pytest.approx(6.25)
    assert read("moe_tile_visits") == pytest.approx(19.0)
    assert read("sink_mass_pct") == pytest.approx(41.2)
    assert read("prefill_chunk_ms") == pytest.approx(300.0)
    assert read("chunks_per_decode_step") == pytest.approx(0.6)
    assert read("decode_step_ms") == pytest.approx(27.0)
    for name in TRACED:             # no trace, no reading
        assert read(name) is None
    obs["trace"] = {"busy_s": 0.9, "window_s": 1.0, "op_seconds": dict(
        TRACE_KINDS, **{"fusion bf16[32,4096]": 0.2,
                        "convolution_bitcast_fusion bf16[32,1,13568]": 0.1})}
    window = sum(v for k, v in TRACE_KINDS.items() if k in WINDOW_KINDS)
    full = sum(v for k, v in TRACE_KINDS.items() if k in FULL_KINDS)
    experts = sum(v for k, v in TRACE_KINDS.items() if k in EXPERT_KINDS)
    assert read("attn_window_device_pct") == pytest.approx(100 * window / 0.9)
    assert read("attn_full_device_pct") == pytest.approx(100 * full / 0.9)
    assert read("moe_experts_device_pct") == pytest.approx(
        100 * experts / 0.9)
    # the window's K and V of the full layers, the slice's share of them by
    # time, over the matched operations' time at the chip's peak
    need = (42_800_000 + 480_000) * 2 * 2560 * 1.0 / 51.0
    assert read("attn_full_hbm_roofline_pct") == pytest.approx(
        100 * need / (819e9 * full))
    assert 0 < read("attn_full_hbm_roofline_pct") < 100
    # the parent's engine has none of the counters: left out, no raise
    mine = [m["name"] for m in _json(ROOT, "BENCHMARK.json")["per_layer"]
            if m.get("workloads") == [CELL]]
    assert sorted(mine) == sorted(f"{n}.long-agent" for n in MINE)
    parent = {"engine_delta": {"decode_steps": 100,
                               "ph_decode_dispatch_ms": 1.0,
                               "ph_decode_device_wait_ms": 1.0},
              "device_kind": "TPU v5 lite", "window_s": 51.0,
              "trace": {"busy_s": 2.0, "window_s": 4.0,
                        "op_seconds": {"fusion bf16[16,4096]": 1.0}}}
    for name in mine:
        assert readers.read_metric("layer_metrics", name, parent) is None


# operation kinds as the chip names them in the cell's programs (compiled
# for a described v5e, `tests/test_chip_compile.py`), with seconds for the
# readers' test
WINDOW_KINDS = ("fusion bf16[288,16,1536]", "reshape bf16[32,144,8,192]",
                "fusion f32[32,8,8,1]",
                "multiply_bitcast_fusion f32[32,8,8,1,128]",
                "fusion f32[1,8,8,1024,128]")
FULL_KINDS = ("fusion bf16[512,16,768]", "reshape bf16[32,256,4,192]",
              "bitcast_reduce_fusion (f32[4,16,1024], f32[1,4,256,1024,16])",
              "fusion f32[32,4,16,1]",
              "subtract_exponential_fusion f32[32,4,16,1]",
              "fusion f32[1,4,16,1024,128]")
EXPERT_KINDS = ("custom-call bf16[256,4096]", "custom-call bf16[8192,4096]")
TRACE_KINDS = {**{k: 0.02 for k in WINDOW_KINDS},
               **{k: 0.05 for k in FULL_KINDS},
               **{k: 0.04 for k in EXPERT_KINDS}}


def test_benchmark_json_gains_the_cell_by_additions_only():
    bench = _json(ROOT, "BENCHMARK.json")
    # by name, not by place: a later PR appends after this one
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    (entry,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
    assert cell["chips"] == 1 and cell["traffic"] == "long-agent"
    assert entry["file"] == "benchmark/" + FILE
    assert entry["source"] == _config()["source"]
    assert entry["reduced"] == _config()["reduced"] == REDUCED
    judged = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in judged["out_tokens_per_s"]["workloads"]
    assert CELL not in judged["itl_p50_ms"]["workloads"]
    listed = [m for m in bench["per_layer"] if CELL in m.get("workloads", [])]
    assert len(listed) == len(MINE) + 3 + 8     # .generate and .serve
    for m in listed:
        assert m["moves"] == "out_tokens_per_s"
        readers.load_metric("layer_metrics", m["name"])
    assert {m["name"] for m in listed
            if m["source"] == "device_trace"} == {
        f"{n}.long-agent" for n in TRACED} | {"device_idle_pct.generate"}
    assert all(len(w["why"]) <= 200 for w in bench["workloads"])
    assert all(len(c["why"]) <= 200 for c in bench["configs"])
    traffic = _json(ROOT, "benchmark", "traffic", "long-agent.json")
    from benchmark.traffic import expand_deck
    deck = expand_deck(traffic)
    assert len(deck) == 32 == traffic["callers"] \
        == _config()["engine"]["max_running"]
    assert (traffic["kind"], traffic["order"], traffic["ramp"],
            traffic["percentiles_over"]) == (
        "closed-loop", "fixed_lanes", "all_callers_streaming", "window")
    groups = [[p for p, _ in deck if lo <= p <= hi]
              for lo, hi in ((2048, 4096), (8192, 16384), (16385, 32768))]
    assert [len(g) for g in groups] == [8, 16, 8]
    assert [(min(g), max(g)) for g in groups] == [
        (2048, 4096), (8192, 16384), (16385, 32768)]
    prompts = sum(p for p, _ in deck)
    assert 417_000 < prompts < 419_000
    assert 0.93 < sum(groups[1] + groups[2]) / prompts < 0.95
    for lo in (2048, 8192, 16385):      # 256, 512, 768, 1,024 in each group
        news = [n for p, n in deck if lo <= p <= 2 * lo]
        assert news[:4] == [256, 512, 768, 1024] and set(news) == set(
            news[:4])
    assert sum(n for _, n in deck) == 20480
    assert traffic["check_prompts"][0] > 1024 > 144   # a chunk, the ring
    assert traffic["check_prompts"][1] >= 9000
    assert traffic["check_decode_steps"] == 3
    engine = _config()["engine"]
    assert (engine["prefix_cache"], engine["prefill_chunk"]) == (0, 1024)
