"""The harness on the CPU at tiny size: the result line, the refusals, and
that a new configuration, traffic mix and metric are found by name. The
tiny cells live in `tests/data/` and are new files plus entries of a
`BENCHMARK.json` of their own: nothing that was there is edited."""

import argparse
import json
import os
import re
import subprocess
import sys

import pytest

from benchmark import readers, run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _tiny(workload, trace, seed=3, seconds=2.0,
          bench="BENCHMARK.tiny.json"):
    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace)
    try:
        return run.run(args, require_tpu=False,
                       bench_file=os.path.join(DATA, bench),
                       traffic_folder=os.path.join(DATA, "traffic"))
    finally:
        assert run.kill_leftovers() == []


def _assert_contract_line(line, metric_names):
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["platform"] == "cpu"       # named as it is
    for name in metric_names:
        assert set(line["metrics"][name]) == {"value", "unit"}
    json.dumps(line)


def test_training_cell_prints_the_contracts_line():
    line = _tiny("tiny.pretrain", 0, seed=5_000_000_000)
    _assert_contract_line(line, ["train_tokens_per_s", "setup_s"])


def test_scoring_cell_reads_whole_laps_and_its_layer_metrics():
    line = _tiny("tiny.score", 1)
    _assert_contract_line(line, ["worker_ready_s", "compiles_in_window",
                                 "front_ms_p50.score",
                                 "prefill_step_ms.score",
                                 "ttft_p50_ms.score"])
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert line["notes"]["whole_laps"] >= 1
    # a CPU has no device trace: those metrics are left out, not invented
    assert "device_idle_pct.score" not in line["metrics"]


def test_generate_cell_streams_from_every_caller():
    line = _tiny("tiny.generate", 0)
    _assert_contract_line(line, ["out_tokens_per_s", "itl_p50_ms",
                                 "setup_s"])


def test_a_measuring_run_refuses_a_machine_without_a_tpu():
    assert run.local_chips() == 0, "this test is for the CPU sandbox"
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "gpt2-medium.pretrain", "--seed", "1", "--seconds",
         "1", "--trace", "0"], capture_output=True, text=True, timeout=120,
        env={**os.environ, "BENCH_RUN": "7"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_no_result_where_only_the_benchmark_is(tmp_path):
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "mistral-7b-l20.generate", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=120, env={k: v for k, v in os.environ.items()
                          if k != "PYTHONPATH"})
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_a_new_metric_file_is_found_by_name(tmp_path):
    folder = tmp_path / "layer_metrics"
    folder.mkdir()
    (folder / "tokens_per_step.new.json").write_text(json.dumps(
        {"reader": "ratio", "args": {"num": "tokens", "den": "steps"}}))
    (folder / "from_a_new_module.json").write_text(json.dumps(
        {"reader": "yardstick:percentile_of_step_ms", "args": {}}))
    obs = {"tokens": 4096, "steps": 4}
    assert readers.read_metric("layer_metrics", "tokens_per_step.new", obs,
                               base=str(tmp_path)) == 1024
    # a reader that finds nothing to read returns nothing
    assert readers.read_metric("layer_metrics", "tokens_per_step.new", {},
                               base=str(tmp_path)) is None
    with pytest.raises(AttributeError):
        readers.read_metric("layer_metrics", "from_a_new_module", obs,
                            base=str(tmp_path))


def test_new_cells_come_as_files_and_entries_only():
    with open(os.path.join(DATA, "BENCHMARK.tiny.json")) as f:
        tiny = json.load(f)
    _, cell, config, mix = run.load_cell(
        "tiny.open", os.path.join(DATA, "BENCHMARK.tiny.json"),
        os.path.join(DATA, "traffic"))
    assert config["name"] == "tiny-mistral" and mix["kind"] == "open-loop"
    assert {m["name"] for m in tiny["end_to_end"]} == \
        {m["name"] for m in _bench()["end_to_end"]}


def test_a_third_family_comes_as_files_only():
    """`data/families/llama_lm.py` (builder, required operations,
    reference), `data/configs/tiny-llama-lm.json` and a `BENCHMARK.json` of
    its own: a training job on a model the shipped cells only serve. No
    file of the harness names the family."""
    for name in ("run.py", "train_cell.py", "serve_cell.py", "readers.py",
                 "yardstick.py"):
        with open(os.path.join(ROOT, "benchmark", name)) as f:
            text = f.read()
        assert "llama_lm" not in text and "llama-lm" not in text
    line = _tiny("third.pretrain", 0, bench="BENCHMARK.third.json")
    _assert_contract_line(line, ["train_tokens_per_s", "setup_s"])
    check = line["notes"]["check"]
    assert check["loss"] == pytest.approx(check["ref_loss"], rel=1e-4)
    assert check["grad_norm"] == pytest.approx(check["ref_grad_norm"],
                                               rel=1e-3)
    _, _, config, mix = run.load_cell(
        "third.pretrain", os.path.join(DATA, "BENCHMARK.third.json"),
        os.path.join(DATA, "traffic"))
    # hand-worked: 2 layers of 64*(4+2*2)*16 + 4*16*64 + 3*64*128 = 36,864
    # matmul parameters each, 512*64 in the head; attention 6*32*64*2
    assert readers.resolve(config["required_flops"])(
        config, mix["seq_len"]) == 6 * (2 * 36864 + 32768) + 24576


def test_a_builder_the_file_does_not_name_is_an_error():
    with pytest.raises(KeyError):
        from benchmark.train_cell import build_job
        build_job({"family": "gpt2"}, {}, [])


# -- BENCHMARK.json against the contract, as far as a file can be read -------

def test_benchmark_json_keeps_the_contracts_limits():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["benchmark"] and 1 <= b["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert all(NAME.match(n) for n in names)
    for kind in ("configs", "workloads"):
        assert len({x["name"] for x in b[kind]}) == len(b[kind])
    metrics = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    four = sum(1 for w in b["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(b["workloads"]) // 4)
    assert all(w["chips"] in (1, 4) and len(w["why"]) <= 200
               for w in b["workloads"])
    assert any(m["name"] == "setup_s" for m in b["end_to_end"])
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"]: m for m in b["end_to_end"]}
    cells = [w["name"] for w in b["workloads"]]
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in moved.get("workloads", cells), (m["name"], cell)
    for w in b["workloads"]:
        own = run.metrics_of_cell(b, w, "end_to_end")
        assert len(own) >= 2 and run.metrics_of_cell(b, w, "per_layer")
    assert len(json.dumps(b)) < 64 * 1024


def test_every_name_in_benchmark_json_has_its_file():
    b = _bench()
    for c in b["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            config = json.load(f)
        assert config["source"] == c["source"]
        assert sorted(config["reduced"]) == sorted(c["reduced"])
        assert not any(re.search(r"hidden_size|intermediate_size|_dim$|"
                                 r"_rank$|n_embd|n_head", k) for k in c["reduced"])
        assert os.path.exists(os.path.join(ROOT, "benchmark",
                                           config["reference"]))
    for w in b["workloads"]:
        run.load_cell(w["name"])
    for kind, folder in (("end_to_end", "end_to_end"),
                         ("per_layer", "layer_metrics")):
        for m in b[kind]:
            reader = readers.load_metric(folder, m["name"])["reader"]
            # a function of `readers`, or `module:function` of a file a
            # later PR brought under `benchmark/`
            assert callable(readers.resolve(reader) if ":" in reader
                            else getattr(readers, reader)), m["name"]
