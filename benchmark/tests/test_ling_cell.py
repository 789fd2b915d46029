"""The Ling hybrid configuration's benchmark files: its reference against
the program's model, the whole cell through the harness at toy widths on the
CPU, the yardstick's counts by hand, and the file against the catalog."""

import argparse
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import ling_yardstick, readers, run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data")
CELL = "ling-3.0-flash-vl-ep4-l7.reason-wide"
FILE = "configs/ling-3.0-flash-vl-ep4-l7.json"


def _config(name=FILE, base=None):
    with open(os.path.join(base or os.path.join(ROOT, "benchmark"),
                           name)) as f:
        return json.load(f)


def test_ling_reference_matches_the_model_forward():
    """The plain reference (the recurrence token by token) against
    `models/ling_hybrid.py`'s full forward (the blocked scan) at the toy
    file's widths, float32 both, 4 of 16 experts held from expert 4 on, 100
    tokens (a block and a part): 1e-4 on logits of deviation 0.1, the
    order of the sums apart."""
    from benchmark.ling_cell import ling_engine
    from benchmark.references import ling_hybrid as ref

    config = _config("configs/tiny-ling-hybrid.json", DATA)
    built = ling_engine(config)
    cfg = built["model_cfg"]
    assert built["model"] == "ling_hybrid"
    assert (cfg.first_expert, cfg.experts_held, cfg.n_experts) == (4, 4, 16)
    assert (cfg.kda_layers, cfg.mla_layers) == ((0, 1, 2, 3, 4, 6), (5,))
    params = built["net"].init(jax.random.PRNGKey(1),
                               jnp.ones((1, 8), jnp.int32))
    ids = np.random.default_rng(0).integers(0, config["vocab_size"], 100)
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
    (row,) = [r for r in rows if r["name"] == "Ling-3.0-flash-VL"]
    assert config["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items()
                     if config.get(k, "absent") != v)
    assert differs == sorted(config["reduced"])
    assert {k: row["config"][k] for k in differs} == config["published"]
    assert config["deployment_share"]["chips_sharing_a_layer"] == 4
    from benchmark.ling_cell import ling_engine

    cfg = ling_engine(config)["model_cfg"]
    assert (cfg.n_experts, cfg.experts_held, cfg.first_expert, cfg.top_k,
            cfg.n_group, cfg.topk_group) == (512, 128, 0, 8, 8, 4)
    assert (cfg.d_model, cfg.n_head, cfg.head_dim, cfg.mla.latent_dim,
            cfg.mla.row_dim) == (2560, 32, 128, 576, 640)
    assert (cfg.kda_layers, cfg.mla_layers) == ((0, 1, 2, 3, 4, 6), (5,))
    # the file's own count is the builder's, and the module's
    assert config["parameters"] == ling_yardstick.count_parameters(config)
    from ray_tpu.models.ling_hybrid import LingHybrid
    shapes = jax.eval_shape(LingHybrid(cfg).init, jax.random.PRNGKey(0),
                            jnp.ones((1, 8), jnp.int32))
    assert sum(int(np.prod(a.shape)) for a in
               jax.tree_util.tree_leaves(shapes)) \
        == config["parameters"]["total"] == 5231790208


@pytest.mark.parametrize("key, value", [
    ("use_qk_norm", False), ("kda_safe_gate", False), ("q_lora_rank", 1536),
    ("score_function", "softmax"), ("group_norm_size", 4)])
def test_the_builder_refuses_what_the_program_does_not_compute(key, value):
    from benchmark.ling_cell import ling_engine

    config = _config("configs/tiny-ling-hybrid.json", DATA)
    config[key] = value
    with pytest.raises(RuntimeError, match=key):
        ling_engine(config)


def test_a_clamp_on_a_kept_layer_is_refused():
    from benchmark.ling_cell import ling_engine

    config = _config("configs/tiny-ling-hybrid.json", DATA)
    config["expert_swiglu_limit_list"] = [0, 0, 4] + [0] * 39
    with pytest.raises(RuntimeError, match="swiglu_limit"):
        ling_engine(config)


def test_the_cell_runs_through_the_harness_at_toy_widths():
    """`run.py`'s own path on the CPU: the builder, one-shot and chunked
    prefill through the blocked scan, decode through the state arena and
    the latent pages, `correct` against the reference, and every per-layer
    metric the cell lists but those of a device trace and the rooflines (a
    CPU has no peak in the yardstick)."""
    args = argparse.Namespace(workload="tiny-ling.reason-wide", seed=7,
                              seconds=3.0, trace=1)
    try:
        line = run.run(args, require_tpu=False,
                       bench_file=os.path.join(DATA, "BENCHMARK.ling.json"),
                       traffic_folder=os.path.join(DATA, "traffic"))
    finally:
        assert run.kill_leftovers() == []
    assert line["correct"] is True and line["failed"] == 0, line
    check = line["notes"]["check"]
    assert check["prompts"] == [20, 45] and check["tokens_checked"] == 8
    with open(os.path.join(DATA, "BENCHMARK.ling.json")) as f:
        listed = json.load(f)["per_layer"]
    missing = {m["name"] for m in listed
               if m["source"] != "device_trace"
               and "roofline" not in m["name"]} - set(line["metrics"])
    assert not missing, missing
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert 5 < m["moe_local_share_pct.reason-wide"] < 60
    assert 0 < m["moe_experts_touched.reason-wide"] <= 4
    assert m["kda_state_gb_per_step.reason-wide"] > 0
    assert m["chunks_per_decode_step.reason-wide"] > 0
    assert m["prefill_chunk_ms.reason-wide"] > 0
    # one paged layer: 64 pages x 1 x 8 x 128 (a row of 24 padded) x 4 B
    assert m["latent_arena_gb.reason-wide"] == pytest.approx(
        64 * 1 * 8 * 128 * 4 / 1e9)


def test_decode_bytes_of_the_cell_by_hand():
    """The issue's table: KDA attention 63.05M a layer, MLA 31.97M, the
    dense feed-forward 47.19M, an expert 5.898M, the router 1.31M, the head
    100.6M; a state 2.10 MB, 576 values a cached token in one layer."""
    model = _config()
    assert ling_yardstick.kda_params(model) == \
        5 * 2560 * 4096 + 4096 * 2560 + 2560 * 32 + 12288 * 4 \
        + 4096 + 32 + 128
    assert ling_yardstick.mla_params(model) == \
        2560 * 32 * 192 + 192 + 2560 * 576 + 512 + 512 * 32 * 256 \
        + 2560 * 32 + 4096 * 2560
    assert ling_yardstick.expert_params(model) == 3 * 2560 * 768
    assert ling_yardstick.kda_state_bytes(model) == 2097152
    assert ling_yardstick.seq_state_bytes(model) == 6 * (2097152 + 73728)
    outside = ling_yardstick.decode_weight_params_outside_experts(model)
    assert outside == 6 * ling_yardstick.kda_params(model) \
        + ling_yardstick.mla_params(model) + 3 * 2560 * 6144 \
        + 6 * (3 * 2560 * 768 + 2560 * 512 + 512) + 2560 * 39296
    need = ling_yardstick.decode_required_bytes(model, 6 * 81.0, 6 * 64.0,
                                                64 * 2200.0)
    assert need == 2.0 * (outside + 486 * 3 * 2560 * 768
                          + 140800 * 576) + 2.0 * 384 * 2097152
    # the issue's count: about 8.8 GB, 10.7 ms at 819 GB/s
    assert 8.3e9 < need < 9.3e9
    assert ling_yardstick.kda_step_required_bytes(model, 64) == \
        2.0 * 64 * 6 * 2097152
    # the blocked scan, a block of 64 a head: A and B under the mask, the
    # solve's products, three products with the state, B U: 8.9 MFLOP
    assert ling_yardstick.kda_chunk_required_flops(model, 1024, 64) == \
        6 * 32 * 16 * (2 * 64 * 64 * 128 + 64 * 64 * 256
                       + 6 * 64 * 128 * 128 + 64 * 64 * 128)


def test_readers_and_the_parents_missing_counters():
    delta = {"decode_steps": 100, "decode_moe_expert_calls": 48600,
             "decode_kda_state_rows": 38400,
             "decode_context_tokens": 14_080_000,
             "ph_decode_dispatch_ms": 300.0,
             "ph_decode_device_wait_ms": 2700.0}
    obs = {"engine_delta": delta, "device_kind": "TPU v5 lite"}
    got = readers.read_metric("layer_metrics",
                              "decode_hbm_roofline_pct.reason-wide", obs)
    need = ling_yardstick.decode_required_bytes(_config(), 486.0, 384.0,
                                                140800.0)
    assert got == pytest.approx(100 * need / (819e9 * 30e-3))
    assert 0 < got < 100
    # the KDA share: a slice of 4 s at a period of (51,000 - 3,000) / 100
    # ms holds 8.33 decode steps of 64 lanes; kinds matched by whole type
    obs["engine_delta"].update(pump_wall_ms=51000.0, prefill_ms=3000.0)
    obs["trace"] = {"busy_s": 2.0, "window_s": 4.0, "op_seconds": {
        "fusion f32[64,6,32,128,128]": 0.02, "fusion f32[64,32,128]": 0.02,
        "fusion f32[65,6,32,128,128]": 0.01,
        "fusion (f32[64,32,128], f32[64,32,128])": 9.0,
        "fusion bf16[64,32,128]": 9.0, "fusion f32[64,32,640]": 9.0}}
    assert readers.read_metric(
        "layer_metrics", "kda_step_hbm_roofline_pct.reason-wide", obs) == \
        pytest.approx(100 * 2.0 * 64 * 6 * 2097152 * (4000 / 480)
                      / (819e9 * 0.05))
    assert readers.read_metric(
        "layer_metrics", "kda_device_pct.reason-wide", obs) == \
        pytest.approx(100 * 0.05 / 2.0)
    assert readers.read_metric(
        "layer_metrics", "kda_state_gb_per_step.reason-wide", obs) == \
        pytest.approx(384 * 2 * 2097152 / 1e9)
    # the parent's engine has none of the counters: left out, no raise
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        mine = [m["name"] for m in json.load(f)["per_layer"]
                if m.get("workloads") == [CELL]]
    assert len(mine) >= 8
    parent = {"engine_delta": {"decode_steps": 100,
                               "ph_decode_dispatch_ms": 1.0,
                               "ph_decode_device_wait_ms": 1.0,
                               "pump_wall_ms": 51000.0},
              "device_kind": "TPU v5 lite", "kv_arena_bytes": 1,
              "trace": {"busy_s": 2.0, "window_s": 4.0,
                        "op_seconds": {"fusion bf16[16,4096]": 1.0}}}
    for name in mine:
        if name != "latent_arena_gb.reason-wide":
            assert readers.read_metric("layer_metrics", name, parent) is None


def test_benchmark_json_gains_the_cell_by_additions_only():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # by name, not by place: a later PR appends after this one
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    (entry,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
    assert cell["chips"] == 1
    assert entry["file"] == "benchmark/" + FILE
    assert entry["reduced"] == _config()["reduced"]
    judged = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", []):
            assert CELL in judged[m["moves"]]["workloads"]
            readers.load_metric("layer_metrics", m["name"])
    assert all(len(w["why"]) <= 200 for w in bench["workloads"])
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "reason-wide.json")) as f:
        traffic = json.load(f)
    from benchmark.traffic import expand_deck
    deck = expand_deck(traffic)
    assert len(deck) == 32 and traffic["callers"] == 64
    assert max(p + n for p, n in deck) <= 8192
    assert 1700 < sum(p for p, _ in deck) / 32 < 1900
    assert sum(n for _, n in deck) / 32 == 800
