"""The readings behind `check.shortfall_limit` of the MiMo cell, on the chip:

    chiprun -- python benchmark/tests/chip_limit_readings.py [sound 8bit ...]

A process a variant, one after the other (a process that touched jax holds
the chip, and the engine's executable cache knows a program by its
function's `id`, which a later engine's may reuse: a fault's engine never
shares a process with a sound one). For each variant and seed: the weights from
the seed as the benchmark's replica makes them, the engine at the
configuration's own settings (the pages cut to what the check needs), the
traffic file's check prompts streamed greedily through `LLMEngine` (chunked
prefill, then decode, through both kinds' pages), and every streamed token's
shortfall under the reference's top, in the row's rms: the harness's measure
(`serve_cell.bench_check`). The reference always judges by the seed's bf16
weights.

Variants: `sound`; `8bit` (every layer matrix rounded to 8-bit floats,
`tests/test_mimo_v2.py:to_8_bits`); and the three faults of
`tests/test_mimo_v2.py:planted`. One JSON line a reading on standard output
and in `chiprun_out/mimo_limit_readings.jsonl`. Not a test: pytest collects
nothing here.
"""

from __future__ import annotations

import gc
import json
import os
import random
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "tests")]

SEEDS = {"sound": 12, "8bit": 6}    # the others: 3
FIRST_SEED = 2_300_000_011          # large, as the driver's are


CONFIG = os.path.join(ROOT, "benchmark", "configs", "mimo-v2.5-ep16-l7.json")
TRAFFIC = os.path.join(ROOT, "benchmark", "traffic", "long-agent.json")


def main(variants, config_file=CONFIG, traffic_file=TRAFFIC,
         require_tpu=True) -> int:
    """(A rehearsal on the CPU passes the tiny files of `data/`.)"""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from test_mimo_v2 import FAULTS, planted, to_8_bits

    from benchmark.mimo_cell import mimo_engine
    from benchmark.references import mimo_v2 as ref
    from benchmark.train_cell import fold_seed
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine

    if require_tpu and jax.devices()[0].platform != "tpu":
        raise SystemExit("readings of the chip's programs need the chip")
    with open(config_file) as f:
        config = json.load(f)
    with open(traffic_file) as f:
        traffic = json.load(f)
    config["check"].pop("shortfall_limit")      # the rows as computed
    built = mimo_engine(config)
    settings = dict(config["engine"], max_running=2, batch_buckets=(1, 2),
                    num_pages=min(2048, config["engine"]["num_pages"]))
    settings["prefill_buckets"] = tuple(settings["prefill_buckets"])
    new = 1 + traffic["check_decode_steps"]
    out = os.path.join(ROOT, "chiprun_out", "mimo_limit_readings.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    init = jax.jit(built["net"].init)

    def weights(seed):
        return init(jax.random.PRNGKey(fold_seed(seed)),
                    jnp.ones((1, 256), jnp.int32))

    def streamed(params, prompts, seed):
        engine = LLMEngine(
            model=built["model"], model_cfg=built["model_cfg"], params=params,
            engine_config=EngineConfig(**settings), seed=seed)
        engine.start()
        try:
            return [engine.submit(ids, new).result(timeout=1500)
                    for ids in prompts]
        finally:
            engine.shutdown()

    for variant in variants:
        for seed in range(FIRST_SEED, FIRST_SEED + SEEDS.get(variant, 3)):
            t0 = time.time()
            rng = random.Random(seed * 1000003 + 41)    # as `serve_cell`
            prompts = [[rng.randrange(config["vocab_size"])
                        for _ in range(n)] for n in traffic["check_prompts"]]
            params = weights(seed)
            if variant == "8bit":
                params = jax.jit(to_8_bits, donate_argnums=0)(params)
            if variant in FAULTS:
                with planted(variant):
                    answers = streamed(params, prompts, seed)
            else:
                answers = streamed(params, prompts, seed)
            del params
            gc.collect()
            params = weights(seed)["params"]
            shortfalls = []
            for prompt, answer in zip(prompts, answers):
                ids = np.asarray(list(prompt) + list(answer[:-1]))
                rows = list(range(len(prompt) - 1, len(ids)))
                with jax.default_matmul_precision("highest"):
                    want = np.asarray(ref.full_logits(params, config, ids,
                                                      rows))
                shortfalls.append([round(ref.shortfall(row, token), 4)
                                   for row, token in zip(want, answer)])
            del params
            gc.collect()
            line = json.dumps({
                "variant": variant, "seed": seed,
                "prompts": traffic["check_prompts"],
                "worst": max(max(s) for s in shortfalls),
                "shortfalls": shortfalls, "seconds": round(time.time() - t0)})
            print(line, flush=True)
            with open(out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--one":
        sys.exit(main(sys.argv[2:]))
    import subprocess

    failed = 0
    for name in sys.argv[1:] or ["sound", "8bit", "sink_left_out",
                                 "v_at_the_full_kinds_head_count",
                                 "window_one_too_wide"]:
        failed += subprocess.call([sys.executable, __file__, "--one", name])
    sys.exit(1 if failed else 0)
