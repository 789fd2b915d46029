"""The Brumby configuration's benchmark files: the whole cell through the
harness at toy widths on the CPU, the file against the catalog, against
`BrumbyConfig` and against the traffic file, the yardstick's counts by hand,
and the readers on a parent that lacks the counters."""

import argparse
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import brumby_yardstick, readers, run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data")
CELL = "brumby-14b-l8.long-continue"
FILE = "configs/brumby-14b-l8.json"
NEW = ("retention_state_gb_per_step", "decode_hbm_roofline_pct",
       "retention_step_hbm_roofline_pct", "retention_device_pct",
       "prefill_chunk_ms", "chunks_per_decode_step")


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _config():
    return _json(ROOT, "benchmark", FILE)


def test_the_files_widths_are_the_published_ones_uncut():
    config = _config()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "Brumby-14B-Base"]
    assert config["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items()
                     if config.get(k, "absent") != v)
    assert differs == config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 40}
    assert config["num_hidden_layers"] == 8
    # every reading that is not a key of the catalog's config is stated
    for key in ("retention_degree", "retention_eps", "state_dim",
                "state_dtype", "qk_norm", "rope_layout", "gate",
                "query_scale", "head_grouping", "projection_biases",
                "embedding_scale", "switch_over", "contradictions", "weights",
                "sliding_window_max_window_layers_use_sliding_window"):
        assert key in config["assumed"]
    from benchmark.brumby_cell import brumby_engine
    from ray_tpu.models.brumby import Brumby, page_kinds, seq_state

    cfg = brumby_engine(config)["model_cfg"]
    assert (cfg.n_layer, cfg.d_model, cfg.n_head, cfg.n_kv_head,
            cfg.head_dim, cfg.ffn_dim, cfg.vocab_size, cfg.rope_theta,
            cfg.norm_eps, cfg.max_seq_len, cfg.state_dim) == (
        8, 5120, 40, 8, 128, 17408, 151936, 1e6, 1e-6, 32768, 8256)
    assert page_kinds(cfg) == ()
    assert [shape for shape, _ in seq_state(cfg)] == [
        (8, 8, 8256, 128), (8, 8, 8256)]
    # the file's own count is the yardstick's, and the module's: 4.20B
    assert config["parameters"] == brumby_yardstick.count_parameters(config)
    shapes = jax.eval_shape(Brumby(cfg).init, jax.random.PRNGKey(0),
                            jnp.ones((1, 8), jnp.int32))
    assert sum(int(np.prod(a.shape)) for a in
               jax.tree_util.tree_leaves(shapes)) \
        == config["parameters"]["total"] == 4198652992
    assert config["parameters"]["bf16_gb"] == 8.4
    assert sum(int(np.prod(shape)) * 4 for shape, _ in seq_state(cfg)) \
        == brumby_yardstick.state_bytes_a_sequence(config) == 272646144


def test_the_check_answer_length_is_the_traffic_files():
    """`bench_check` passes a reference no prompt length: the file repeats
    the check's answer length, and here the two are tied."""
    for config, traffic in (
            (_config(), _json(ROOT, "benchmark", "traffic",
                              "long-continue.json")),
            (_json(DATA, "configs", "tiny-brumby.json"),
             _json(DATA, "traffic", "tiny-long-continue.json"))):
        assert config["check"]["new_tokens"] == \
            1 + traffic["check_decode_steps"]


def test_the_cells_own_limit_lies_between_its_two_readings():
    """The file states the cell's own limit with its readings on the chip
    (my chip runs, PR 53): the sound program's largest, and the smallest of
    the controls that must fail. The reference applies it, and it is under
    the harness's."""
    from benchmark.serve_cell import SHORTFALL_TOLERANCE

    check = _config()["check"]
    sound, control = check["readings"]["sound_max"], \
        check["readings"]["control_min"]
    assert sound < check["shortfall_limit"] < control
    assert check["shortfall_limit"] < SHORTFALL_TOLERANCE
    for reading in (sound, control):
        assert f"{reading:g}" in check["shortfall_limit_why"]


def test_the_reference_applies_the_cells_limit():
    """`references/brumby.py:logits` puts a streamed token that falls short
    by more than `check.shortfall_limit` far under the top (the harness then
    reads not correct) and leaves every other row as computed."""
    from benchmark.references import brumby as ref
    from ray_tpu.models.brumby import Brumby, BrumbyConfig

    config = _json(DATA, "configs", "tiny-brumby.json")
    cfg = BrumbyConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32)
    params = Brumby(cfg).init(jax.random.PRNGKey(0),
                              jnp.ones((1, 8), jnp.int32))["params"]
    ids = np.random.default_rng(1).integers(0, 512, 20)
    assert ref.full_logits(params, config, ids).shape == (20, 512)
    # a greedy answer: every token the row's top
    answer = list(ids[:16])
    for _ in range(4):
        answer.append(int(np.asarray(ref.full_logits(
            params, config, np.asarray(answer)))[-1].argmax()))
    out = ref.logits(params, config, np.asarray(answer[:-1]))
    assert (out[:15] == 0).all() and out.shape == (19, 512)
    for r in (15, 16, 17):
        assert out[r].argmax() == answer[r + 1]
    # a token far from the top is refused: past any limit the harness has
    wrong = list(answer[:-1])
    wrong[17] = int(out[16].argmin())
    out = ref.logits(params, config, np.asarray(wrong))
    assert ref.shortfall(out[16], wrong[17]) > 10


@pytest.mark.parametrize("key, value", [
    ("model_type", "qwen3"), ("rope_scaling", {"type": "yarn"}),
    ("tie_word_embeddings", True), ("use_sliding_window", True),
    ("sliding_window", 4096), ("hidden_act", "gelu"),
    ("attention_bias", True)])
def test_the_builder_refuses_what_the_program_does_not_compute(key, value):
    from benchmark.brumby_cell import brumby_engine

    config = _json(DATA, "configs", "tiny-brumby.json")
    config[key] = value
    with pytest.raises(RuntimeError, match=key):
        brumby_engine(config)


@pytest.mark.parametrize("key, value", [
    ("retention_degree", 4), ("state_dtype", "bfloat16"),
    ("state_dim", 9216)])
def test_the_builder_refuses_another_reading_of_the_state(key, value):
    from benchmark.brumby_cell import brumby_engine

    config = _json(DATA, "configs", "tiny-brumby.json")
    config["assumed"][key] = value
    with pytest.raises(RuntimeError, match="state|" + key):
        brumby_engine(config)


def test_the_cell_runs_through_the_harness_at_toy_widths():
    """`run.py`'s own path on the CPU: the builder, one-shot and chunked
    prefill into the state arena's slots, decode through them with no page
    anywhere, `correct` against the reference, and every per-layer metric the
    cell lists but those of a device trace and the roofline (a CPU has no
    peak in the yardstick)."""
    args = argparse.Namespace(workload="tiny-brumby.long-continue", seed=7,
                              seconds=3.0, trace=1)
    try:
        line = run.run(args, require_tpu=False,
                       bench_file=os.path.join(DATA, "BENCHMARK.brumby.json"),
                       traffic_folder=os.path.join(DATA, "traffic"))
    finally:
        assert run.kill_leftovers() == []
    assert line["correct"] is True and line["failed"] == 0, line
    check = line["notes"]["check"]
    assert check["prompts"] == [12, 50] and check["tokens_checked"] == 8
    assert check["worst_shortfall"] < 1e-3          # float32 both
    listed = _json(DATA, "BENCHMARK.brumby.json")["per_layer"]
    missing = {m["name"] for m in listed if m["source"] != "device_trace"} \
        - set(line["metrics"])
    assert not missing, missing
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # the file's scale is the cell's 34,080,768 B a state row; a toy row is
    # 2 x 36 x 9 x 4 B, and a step of up to four lanes touches 3 layers each
    rows = m["retention_state_gb_per_step.long-continue"] / 0.068161536
    assert 3 * 1 <= rows <= 3 * 4
    assert 0 < m["prefill_chunk_ms.long-continue"]
    assert 0 < m["chunks_per_decode_step.long-continue"] < 1
    # no page anywhere: the deck's prompts of 20 to 40 went in chunks of 16
    assert any(key.startswith("chunk:") for key in
               line["notes"]["compiled_step_calls"])


def test_step_bytes_of_the_cell_by_hand():
    """The issue's figures: a layer 330,352,904 parameters, eight
    2,642,823,232, the embedding and the head 777,912,320 each; a state
    34,080,768 B a layer a sequence, 272.6 MB a sequence, 4.63 GB for 16
    sequences and the scratch slot; a decode step of 16 lanes has to move
    5.29 GB of layers + 1.56 GB of head + 2 x 4.36 GB of state = 15.57 GB,
    the state 56% of it."""
    model = _config()
    assert brumby_yardstick.layer_params(model) == 2 * 26214400 \
        + 2 * 5242880 + 40968 + 256 + 3 * 89128960 + 10240 == 330352904
    count = brumby_yardstick.count_parameters(model)
    assert count["the_stack"] == 2642823232
    assert count["embedding"] == count["head"] == 777912320
    assert brumby_yardstick.state_bytes_a_layer(model) \
        == 33816576 + 264192 == 34080768
    engine = model["engine"]
    slots = engine["max_running"] + 1
    assert round(slots * 272646144 / 1e9, 2) == 4.63
    weights = brumby_yardstick.decode_weight_bytes(model)
    assert weights == 2.0 * (2642823232 + 5120 + 777912320)
    state = brumby_yardstick.retention_step_required_bytes(model, 16 * 8)
    assert state == 2.0 * 16 * 8 * 34080768
    need = brumby_yardstick.decode_required_bytes(model, 16 * 8)
    assert need == weights + state and round(need / 1e9, 2) == 15.57
    assert round(100 * state / need) == 56
    assert round(need / 819e9 * 1e3, 1) == 19.0
    from benchmark.traffic import expand_deck
    deck = expand_deck(_json(ROOT, "benchmark", "traffic",
                             "long-continue.json"))
    assert max(p + n for p, n in deck) <= model["max_position_embeddings"]
    assert engine["max_running"] == 16 == len(deck)
    # a bucket at or over every deck length; every prompt is over the chunk
    # and goes in chunks, so no prefill program over 1,024 is ever compiled
    assert max(p for p, _ in deck) <= max(engine["prefill_buckets"])
    assert min(p for p, _ in deck) > engine["prefill_chunk"] == 1024
    assert engine["prefix_cache"] == 0 and "num_pages" not in engine


def test_readers_and_the_parents_missing_counters():
    delta = {"decode_steps": 100, "decode_retention_state_rows": 100 * 16 * 8,
             "decode_retention_tokens": 1600, "tokens_generated": 1610,
             "prefill_steps": 10, "chunk_steps": 20, "chunk_ms": 1500.0,
             "pump_wall_ms": 6000.0, "prefill_ms": 1500.0,
             "ph_decode_dispatch_ms": 300.0,
             "ph_decode_device_wait_ms": 3700.0}
    trace = {"busy_s": 0.9, "window_s": 1.0, "op_seconds": {
        "add_dynamic-update-slice_fusion f32[17,8,8,8256,128]": 0.2,
        "fusion f32[8,5,128]": 0.15, "fusion bf16[16,34816]": 0.3,
        "select_dynamic-update-slice_fusion f32[17,8,8,8256,128]": 0.1}}
    obs = {"engine_delta": delta, "device_kind": "TPU v5 lite",
           "trace": trace}

    def read(name):
        return readers.read_metric("layer_metrics", f"{name}.long-continue",
                                   obs)

    model = _config()
    need = brumby_yardstick.decode_required_bytes(model, 128.0)
    assert read("decode_hbm_roofline_pct") == pytest.approx(
        100 * need / (819e9 * 40e-3))
    assert 0 < read("decode_hbm_roofline_pct") < 100
    assert read("retention_state_gb_per_step") == pytest.approx(
        2 * 128 * 34080768 / 1e9)
    assert 8.4 < read("retention_state_gb_per_step") < 8.8
    assert read("prefill_chunk_ms") == pytest.approx(75.0)
    assert read("chunks_per_decode_step") == pytest.approx(0.2)
    # the slice held 1 s / 45 ms decode periods, each 8.72 GB of state
    spec = readers.load_metric("layer_metrics",
                               "retention_step_hbm_roofline_pct.long-continue")
    from benchmark import trace_reduce
    matched = trace_reduce.kernel_seconds(trace, spec["args"]["pattern"])
    assert matched == pytest.approx(0.35)
    assert read("retention_step_hbm_roofline_pct") == pytest.approx(
        100 * 2 * 128 * 34080768 * (1000 / 45) / (819e9 * 0.35))
    assert read("retention_device_pct") == pytest.approx(100 * 0.35 / 0.9)
    # the parent's engine has none of the counters: left out, no raise
    bench = _json(ROOT, "BENCHMARK.json")
    mine = [m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]]
    assert sorted(mine) == sorted(f"{name}.long-continue" for name in NEW)
    parent = {"engine_delta": {"decode_steps": 100,
                               "ph_decode_dispatch_ms": 1.0,
                               "ph_decode_device_wait_ms": 1.0},
              "device_kind": "TPU v5 lite"}
    for name in mine:
        assert readers.read_metric("layer_metrics", name, parent) is None


def test_benchmark_json_gains_the_cell_by_additions_only():
    bench = _json(ROOT, "BENCHMARK.json")
    # by name, not by place: a later PR appends after this one
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    (entry,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
    assert cell["chips"] == 1 and cell["traffic"] == "long-continue"
    assert entry["file"] == "benchmark/" + FILE
    assert entry["reduced"] == _config()["reduced"]
    assert entry["source"] == _config()["source"]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    judged = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in judged["out_tokens_per_s"]["workloads"]
    reported = {name for name, m in judged.items()
                if CELL in m.get("workloads", [CELL])}
    listed = [m for m in bench["per_layer"] if CELL in m.get("workloads", [])]
    # the family appends nothing to a page: `decode_kv_append_ms.generate`
    # is the one list of `ouro-2.6b.loop-reason`'s the cell is not added to
    assert len(listed) == len(NEW) + 9 + 8
    assert "decode_kv_append_ms.generate" not in {m["name"] for m in listed}
    for m in listed:
        assert m["moves"] in reported
        readers.load_metric("layer_metrics", m["name"])
    assert all(len(w["why"]) <= 200 for w in bench["workloads"])
    assert all(len(c["why"]) <= 200 for c in bench["configs"])
    traffic = _json(ROOT, "benchmark", "traffic", "long-continue.json")
    from benchmark.traffic import expand_deck
    deck = expand_deck(traffic)
    assert len(deck) == 16 == traffic["callers"]
    assert (traffic["kind"], traffic["order"], traffic["ramp"],
            traffic["percentiles_over"]) == (
        "closed-loop", "fixed_lanes", "all_callers_streaming", "window")
    groups = [(g["count"], g["prompt_from"], g["prompt_to"], g["new_tokens"])
              for g in traffic["deck"]]
    assert groups == [(8, 1500, 4000, [768, 1024]),
                      (6, 4001, 8000, [512, 768]), (2, 8001, 16000, [512])]
    assert traffic["check_prompts"] == [700, 2500]
    assert traffic["check_decode_steps"] == 3
