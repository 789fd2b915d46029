"""The two metric files PR 59 added for `sdar-30b-a3b-l7.block-denoise`
(`decode_key_padding.block-denoise`, `block_walk_device_pct.block-denoise`):
each loads, names a reader that exists, stands in `BENCHMARK.json` for this
cell alone, and reads a recorded sample of both sides of the PR: the ten
largest operation kinds of the ledger's traced seconds (PR 57, the walk to
the longest lane; PR 58, the work list) and an engine with and without the
counter."""

import json
import os

import pytest

from benchmark import readers

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "sdar-30b-a3b-l7.block-denoise"
MINE = ("decode_key_padding.block-denoise",
        "block_walk_device_pct.block-denoise")

# seconds of a traced second's operation kinds (ledger, PR 57 and PR 58's
# change side, `breakdown.device_ops`, the names as the reduced trace has them)
LOOP = {
    "custom-call bf16[2048,1536]": 0.284406, "fusion bf16[1024,16,4,128]":
    0.248103, "custom-call bf16[2048,2048]": 0.143669,
    "fusion f32[64,4,151936]": 0.044000, "fusion f32[64,4,8,4,128]": 0.023062,
    "fusion (f32[64,4,8,4], f32[64,4,8,4,256])": 0.015742,
    "fusion bf16[2048,2048]": 0.010106, "fusion bf16[64,4,5120]": 0.009546,
    "fusion bf16[924672,4,128]": 0.009183, "fusion f32[64,4,8,4]": 0.007705}
LIST = {
    "custom-call bf16[2048,1536]": 0.319419, "custom-call bf16[2048,2048]":
    0.161223, "fusion bf16[768,64,128]": 0.078153,
    "fusion f32[64,4,151936]": 0.050119,
    "convolution_add_fusion f32[64,4,8,4,128]": 0.013590,
    "fusion f32[64,4,8,4,128]": 0.013378, "fusion bf16[2048,2048]": 0.011386,
    "fusion (f32[64,4,8,4], f32[64,4,8,4,192])": 0.011106,
    "fusion bf16[64,4,5120]": 0.010722, "fusion bf16[924672,4,128]": 0.010470}


def _entries():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return {m["name"]: m for m in json.load(f)["per_layer"]}


@pytest.mark.parametrize("name", MINE)
def test_the_file_loads_and_its_entry_is_this_cells_alone(name):
    spec = readers.load_metric("layer_metrics", name)
    assert spec["reader"] in ("ratio", "kimi_yardstick:device_share_pct")
    entry = _entries()[name]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "out_tokens_per_s"
    assert entry["layer"] == "model step" and entry["better"] == "lower"
    assert entry["source"] == {"ratio": "program_counter"}.get(
        spec["reader"], "device_trace")


def test_the_padding_reads_the_counter_and_nothing_of_the_parent():
    # a window of 2,000 passes of 64 lanes at 1,100 cached keys a lane, the
    # list's 1.2 slots a key over 7 page layers
    context = 2000 * 64 * 1100
    obs = {"engine_delta": {"decode_steps": 2000,
                            "decode_context_tokens": context,
                            "decode_attn_key_slots": int(7 * 1.2 * context)}}
    assert readers.read_metric("layer_metrics", MINE[0], obs) \
        == pytest.approx(1.2, rel=1e-6)
    # the parent's engine does not count a block pass: left out, no raise
    del obs["engine_delta"]["decode_attn_key_slots"]
    assert readers.read_metric("layer_metrics", MINE[0], obs) is None
    assert readers.read_metric("layer_metrics", MINE[0],
                               {"engine_delta": {}}) is None


def test_the_walks_share_sees_both_walks_and_nothing_else():
    def read(name, ops, busy):
        return readers.read_metric("layer_metrics", name, {
            "trace": {"busy_s": busy, "window_s": 1.0, "op_seconds": ops}})

    # the loop: its gather, the state and the scores; the accepted pattern
    # reads the same operations there
    loop = 0.248103 + 0.023062 + 0.015742 + 0.007705
    assert read(MINE[1], LOOP, 0.8) == pytest.approx(100 * loop / 0.8)
    assert read("block_attention_device_pct.block-denoise", LOOP, 0.8) \
        == pytest.approx(100 * loop / 0.8)
    # the list: the rows' gather, the pairs' partial sums, the state, the
    # scores; the accepted pattern no longer sees the gather
    listed = 0.078153 + 0.013590 + 0.013378 + 0.011106
    assert read(MINE[1], LIST, 0.76) == pytest.approx(100 * listed / 0.76)
    assert read("block_attention_device_pct.block-denoise", LIST, 0.76) \
        == pytest.approx(100 * (listed - 0.078153) / 0.76)
    # not the experts' products, the head, the arena's write or the router
    pattern = readers.load_metric("layer_metrics", MINE[1])["args"]["pattern"]
    import re
    assert not [k for k in (*LOOP, *LIST) if re.search(pattern, k)
                and not re.search(r"\[\d+,4,8,4|,16,4,128\]|,64,128\]", k)]
    for other in ("fusion bf16[924672,4,128]", "fusion f32[64,4,151936]",
                  "custom-call bf16[2048,1536]", "fusion bf16[64,4,5120]"):
        assert not re.search(pattern, other)
    # a program without these operations, or a run without a trace: nothing
    assert read(MINE[1], {"fusion bf16[16,4096]": 1.0}, 2.0) is None
    assert readers.read_metric("layer_metrics", MINE[1], {}) is None
