"""The Ouro configuration's benchmark files: the whole cell through the
harness at toy widths on the CPU, the file against the catalog, against
`OuroConfig` and against the traffic file, the yardstick's counts by hand,
and the readers on a parent that lacks the counters."""

import argparse
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import ouro_yardstick, readers, run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data")
CELL = "ouro-2.6b.loop-reason"
FILE = "configs/ouro-2.6b.json"
NEW = ("decode_hbm_roofline_pct", "loop_weight_bytes_share_pct",
       "kv_gb_per_step", "decode_key_padding", "loop_passes_per_token")


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _config():
    return _json(ROOT, "benchmark", FILE)


def test_the_files_widths_are_the_published_ones_uncut():
    config = _config()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "Ouro-2.6B"]
    assert config["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items()
                     if config.get(k, "absent") != v)
    assert differs == config["reduced"] == ["max_position_embeddings"]
    assert config["published"] == {"max_position_embeddings": 65536}
    # every reading that is not a key of the catalog's config is stated
    for key in ("embedding_scale", "projection_biases", "rope_layout",
                "sandwich_norm", "final_norm_every_pass", "exit_gate",
                "kv_rows_a_pass", "contradictions",
                "layer_types_max_window_layers_use_sliding_window"):
        assert key in config["assumed"]
    from benchmark.ouro_cell import ouro_engine
    from ray_tpu.models.ouro import Ouro, paged_layers

    cfg = ouro_engine(config)["model_cfg"]
    assert (cfg.n_layer, cfg.n_pass, cfg.d_model, cfg.n_head, cfg.n_kv_head,
            cfg.head_dim, cfg.ffn_dim, cfg.vocab_size, cfg.rope_theta,
            cfg.norm_eps, cfg.exit_threshold, cfg.max_seq_len) == (
        48, 4, 2048, 16, 16, 128, 5632, 49152, 1e6, 1e-6, 1.0, 1024)
    assert paged_layers(cfg) == ouro_yardstick.page_layers(config) == 192
    # the file's own count is the yardstick's, and the module's: 2.67B
    assert config["parameters"] == ouro_yardstick.count_parameters(config)
    shapes = jax.eval_shape(Ouro(cfg).init, jax.random.PRNGKey(0),
                            jnp.ones((1, 8), jnp.int32))
    assert sum(int(np.prod(a.shape)) for a in
               jax.tree_util.tree_leaves(shapes)) \
        == config["parameters"]["total"] == 2667974657
    assert config["parameters"]["bf16_gb"] == 5.34


def test_the_check_answer_length_is_the_traffic_files():
    """`bench_check` passes a reference no prompt length: the file repeats
    the check's answer length, and here the two are tied."""
    for config, traffic in (
            (_config(), _json(ROOT, "benchmark", "traffic",
                              "loop-reason.json")),
            (_json(DATA, "configs", "tiny-ouro.json"),
             _json(DATA, "traffic", "tiny-loop-reason.json"))):
        assert config["check"]["new_tokens"] == \
            1 + traffic["check_decode_steps"]


def test_the_cells_own_limit_lies_between_its_two_readings():
    """The file states the cell's own limit with its readings on the chip
    (my chip runs, PR 48): the sound program's largest, and the smallest of
    the controls that must fail (8-bit layer matrices; a loop fault). The
    reference applies it, and it is under the harness's."""
    from benchmark.serve_cell import SHORTFALL_TOLERANCE

    check = _config()["check"]
    sound, control = check["readings"]["sound_max"], \
        check["readings"]["control_min"]
    assert sound * 2 <= check["shortfall_limit"] <= control / 2
    assert check["shortfall_limit"] < SHORTFALL_TOLERANCE
    for reading in (sound, control):
        assert f"{reading:g}" in check["shortfall_limit_why"]


def test_the_reference_applies_the_cells_limit():
    """`references/ouro.py:logits` puts a streamed token that falls short by
    more than `check.shortfall_limit` far under the top (the harness then
    reads not correct) and leaves every other row as computed."""
    from benchmark.references import ouro as ref
    from ray_tpu.models.ouro import Ouro, OuroConfig

    config = _json(DATA, "configs", "tiny-ouro.json")
    cfg = OuroConfig.tiny(n_pass=4, dtype=jnp.float32,
                          param_dtype=jnp.float32)
    params = Ouro(cfg).init(jax.random.PRNGKey(0),
                            jnp.ones((1, 8), jnp.int32))["params"]
    ids = np.random.default_rng(1).integers(0, 512, 20)
    assert ref.full_logits(params, config, ids)[1].shape == (4, 20)
    # a greedy answer: every token the row's top
    answer = list(ids[:16])
    for _ in range(4):
        answer.append(int(np.asarray(ref.full_logits(
            params, config, np.asarray(answer))[0])[-1].argmax()))
    out = ref.logits(params, config, np.asarray(answer[:-1]))
    assert (out[:15] == 0).all() and out.shape == (19, 512)
    for r in (15, 16, 17):
        assert out[r].argmax() == answer[r + 1]
    # a token far from the top is refused: past any limit the harness has
    # (the refused logit itself widens the row's rms: 100 reads as 22 here)
    wrong = list(answer[:-1])
    wrong[17] = int(out[16].argmin())
    out = ref.logits(params, config, np.asarray(wrong))
    assert ref.shortfall(out[16], wrong[17]) > 10


@pytest.mark.parametrize("key, value", [
    ("early_exit_threshold", 0.9), ("rope_scaling", {"type": "yarn"}),
    ("tie_word_embeddings", True), ("use_sliding_window", True),
    ("sliding_window", 4096), ("hidden_act", "gelu"),
    ("layer_types", ["sliding_attention"] * 3)])
def test_the_builder_refuses_what_the_program_does_not_compute(key, value):
    from benchmark.ouro_cell import ouro_engine

    config = _json(DATA, "configs", "tiny-ouro.json")
    config[key] = value
    with pytest.raises(RuntimeError, match=key):
        ouro_engine(config)


def test_the_cell_runs_through_the_harness_at_toy_widths():
    """`run.py`'s own path on the CPU: the builder, one-shot prefill into 12
    page layers from 3 weight layers, decode through them with the prefix
    cache on, `correct` against the reference, and every per-layer metric
    the cell lists but those of a device trace and the roofline (a CPU has
    no peak in the yardstick)."""
    args = argparse.Namespace(workload="tiny-ouro.loop-reason", seed=7,
                              seconds=3.0, trace=1)
    try:
        line = run.run(args, require_tpu=False,
                       bench_file=os.path.join(DATA, "BENCHMARK.ouro.json"),
                       traffic_folder=os.path.join(DATA, "traffic"))
    finally:
        assert run.kill_leftovers() == []
    assert line["correct"] is True and line["failed"] == 0, line
    check = line["notes"]["check"]
    assert check["prompts"] == [12, 50] and check["tokens_checked"] == 8
    assert check["worst_shortfall"] < 1e-3          # float32 both
    listed = _json(DATA, "BENCHMARK.ouro.json")["per_layer"]
    missing = {m["name"] for m in listed if m["source"] != "device_trace"} \
        - set(line["metrics"])
    assert not missing, missing
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # the file divides by the cell's 48 layers: 4 passes of 3 layers here
    assert m["loop_passes_per_token.loop-reason"] == pytest.approx(
        4 * 3 / 48, rel=1e-2)
    assert 0 < m["kv_gb_per_step.loop-reason"]
    # one key block of the table's 128 slots a lane of the bucket, for
    # contexts of some tens of tokens (the file divides by the cell's 192
    # page layers: 12 here)
    assert m["decode_key_padding.loop-reason"] * 192 / 12 > 1.0
    assert 0 < m["loop_weight_bytes_share_pct.loop-reason"] < 100


def test_step_bytes_of_the_cell_by_hand():
    """The issue's figures: a layer 51,388,416 parameters, the stack
    2,466,643,968, the embedding and the head 100,663,296 each, the gate
    2,049; a token 1.5 MiB of cache, a 16-token page 25.2 MB; a decode step
    streams 19.7 GB of stack and 0.2 GB of head."""
    model = _config()
    assert ouro_yardstick.layer_params(model) == 4 * 2048 * 2048 \
        + 3 * 2048 * 5632 + 4 * 2048 == 51388416
    count = ouro_yardstick.count_parameters(model)
    assert count["the_stack"] == 2466643968
    assert count["embedding"] == count["head"] == 100663296
    assert count["exit_gate"] == 2049
    assert ouro_yardstick.kv_bytes_per_token(model) == 1572864 == 3 * 2**19
    engine = model["engine"]
    page = engine["block_size"] * 1572864
    assert round(page / 1e6, 1) == 25.2
    weights = ouro_yardstick.decode_weight_bytes(model)
    assert weights == 2.0 * (4 * (2466643968 + 4097) + 100663296)
    assert round(4 * 2 * 2466643968 / 1e9, 1) == 19.7
    need = ouro_yardstick.decode_required_bytes(model, 4000.0, 16.0)
    assert need == weights + 1572864 * 4016.0
    from benchmark.traffic import expand_deck
    deck = expand_deck(_json(ROOT, "benchmark", "traffic",
                             "loop-reason.json"))
    # the deck is the running set (16 callers, 16 entries, fixed lanes):
    # its reservations (prompt + answer, whole pages) fit, with room for the
    # prefix cache's turnover and the check beside them; the issue's 312 is
    # every group's longest prompt with its longest answer, which no entry is
    held = sum(-(-(p + n) // engine["block_size"]) for p, n in deck)
    assert held == 255 and held + 32 <= engine["num_pages"]
    traffic = _json(ROOT, "benchmark", "traffic", "loop-reason.json")
    assert sum(g["count"] * -(-(g["prompt_to"] + max(g["new_tokens"]))
                              // engine["block_size"])
               for g in traffic["deck"]) == 8 * 18 + 6 * 20 + 2 * 24 == 312
    assert max(p + n for p, n in deck) <= model["max_position_embeddings"]
    assert engine["max_running"] == 16 == len(deck)
    assert max(p for p, _ in deck) <= max(engine["prefill_buckets"])
    assert engine["prefix_cache"] == 1


def test_readers_and_the_parents_missing_counters():
    delta = {"decode_steps": 100, "decode_context_tokens": 400_000,
             "tokens_generated": 1610, "prefill_steps": 10,
             "decode_layer_passes": 1600 * 192,
             "decode_exit_pass_milli": 1600 * 1750,
             "decode_attn_key_slots": 100 * 16 * 192 * 513,
             "ph_decode_dispatch_ms": 300.0,
             "ph_decode_device_wait_ms": 5700.0}
    obs = {"engine_delta": delta, "device_kind": "TPU v5 lite"}

    def read(name):
        return readers.read_metric("layer_metrics", f"{name}.loop-reason",
                                   obs)

    model = _config()
    need = ouro_yardstick.decode_required_bytes(model, 4000.0, 16.0)
    assert read("decode_hbm_roofline_pct") == pytest.approx(
        100 * need / (819e9 * 60e-3))
    assert 0 < read("decode_hbm_roofline_pct") < 100
    assert read("loop_weight_bytes_share_pct") == pytest.approx(
        100 * ouro_yardstick.decode_weight_bytes(model) / need)
    assert read("kv_gb_per_step") == pytest.approx(4000 * 1572864 / 1e9)
    assert read("decode_key_padding") == pytest.approx(16 * 513 / 4000)
    assert read("loop_passes_per_token") == pytest.approx(4.0)
    # the parent's engine has none of the counters: left out, no raise
    bench = _json(ROOT, "BENCHMARK.json")
    mine = [m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]]
    assert sorted(mine) == sorted(f"{name}.loop-reason" for name in NEW)
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
    assert cell["chips"] == 1 and cell["traffic"] == "loop-reason"
    assert entry["file"] == "benchmark/" + FILE
    assert entry["reduced"] == _config()["reduced"]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    judged = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in judged["out_tokens_per_s"]["workloads"]
    reported = {name for name, m in judged.items()
                if CELL in m.get("workloads", [CELL])}
    listed = [m for m in bench["per_layer"] if CELL in m.get("workloads", [])]
    assert len(listed) == len(NEW) + 10 + 8
    for m in listed:
        assert m["moves"] in reported
        readers.load_metric("layer_metrics", m["name"])
    assert all(len(w["why"]) <= 200 for w in bench["workloads"])
    assert all(len(c["why"]) <= 200 for c in bench["configs"])
    traffic = _json(ROOT, "benchmark", "traffic", "loop-reason.json")
    from benchmark.traffic import expand_deck
    deck = expand_deck(traffic)
    assert len(deck) == 16 == traffic["callers"]
    assert (traffic["kind"], traffic["order"], traffic["ramp"],
            traffic["percentiles_over"]) == (
        "closed-loop", "fixed_lanes", "all_callers_streaming", "window")
    groups = [(g["count"], g["prompt_from"], g["prompt_to"], g["new_tokens"])
              for g in traffic["deck"]]
    assert groups == [(8, 32, 96, [128, 192]), (6, 97, 192, [96, 128]),
                      (2, 193, 320, [64])]
    assert traffic["check_prompts"] == [100, 300]
    assert traffic["check_decode_steps"] == 3
