"""The serving cells: the benchmark's own subclass of `LLMDeployment` (the
replica's process holds the chip, so the profiler, `memory_stats` and the
comparison with the plain reference live there) and the driver's side, which
deploys it with `serve.run` and sends traffic through the handle.

The program is not edited: the subclass makes the weights on the device in
one jitted call and adds methods; `generate` is the program's own.
"""

from __future__ import annotations

import importlib
import random
import tempfile
import threading
import time
from typing import Any, Dict, List

from ray_tpu.serve.llm.deployment import LLMDeployment

# The streamed token must be one the reference also puts on top: its float32
# logit may fall short of the reference's largest by at most this share of
# the row's root-mean-square. bf16 weights and activations through 20 layers
# put the engine's logits 0.033-0.065 of a row's rms off the float32 ones (my
# chip runs, PR 23), so the difference of two logits is off by about 0.08 and
# greedy sampling can only prefer a token that close to the top. 0.5 is six
# times that. A random token falls short by about 4 (the top of 32,000
# draws), a wrong mask, rotation or page by the same, and an 8-bit float's
# ten times larger error passes 0.5 within a few tokens.
SHORTFALL_TOLERANCE = 0.5


def llama_engine(config: dict) -> dict:
    """`models/llama.py` at the file's Mistral keys, for `LLMEngine`. A
    configuration's `builder` names a function like this one
    (`module:function` under `benchmark/`): it returns the engine's `model`
    family, the `model_cfg` and the flax module that makes the weights."""
    import jax.numpy as jnp

    from ray_tpu.models.llama import Llama, LlamaConfig

    dtype = jnp.dtype(config["torch_dtype"])
    cfg = LlamaConfig(
        dtype=dtype, param_dtype=dtype,
        vocab_size=config["vocab_size"],
        n_layer=config["num_hidden_layers"],
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        d_model=config["hidden_size"],
        ffn_mult=config["intermediate_size"] / config["hidden_size"],
        max_seq_len=config["max_position_embeddings"],
        rope_theta=config["rope_theta"], norm_eps=config["rms_norm_eps"])
    if cfg.ffn_dim != config["intermediate_size"] or \
            cfg.head_dim != config["head_dim"]:
        raise RuntimeError("the model's widths are not the file's")
    return {"model": "llama", "model_cfg": cfg, "net": Llama(cfg)}


class BenchLLMDeployment(LLMDeployment):
    """`LLMDeployment` with weights made on the device from the seed, only
    the cell's own programs warmed, and the benchmark's instruments. It
    uses what the program offers in the open: `LLMEngine(...)`, `submit`,
    `metrics()`, `replica_info()`, the request recorder's ring."""

    def __init__(self, config: dict, seed: int, warm_prompts: List[int],
                 require_tpu: bool = True):
        import jax
        import jax.numpy as jnp

        from benchmark.device_memory import PeakSampler
        from benchmark.readers import resolve
        from benchmark.train_cell import fold_seed
        from ray_tpu._private.object_ref import get_core_worker
        from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine
        from ray_tpu.util import request_recorder

        dev = jax.devices()[0]
        self._ready_wall = time.time()
        if require_tpu and dev.platform != "tpu":
            raise RuntimeError(f"the replica computes on {dev.platform!r}, "
                               f"not a TPU")
        self._memory = PeakSampler(jax.devices())
        self._config = config
        built = resolve(config["builder"])(config)
        engine = dict(config["engine"])
        for key in ("batch_buckets", "prefill_buckets"):
            engine[key] = tuple(engine[key])
        cw = get_core_worker()
        self._tpu_chips = list(cw.tpu_chips) if cw is not None else []
        if cw is not None and require_tpu:
            self._refuse_host_compute_beside_a_chip(cw)
        key = jax.random.PRNGKey(fold_seed(seed))
        self._params = jax.jit(built["net"].init)(
            key, jnp.ones((1, min(engine["prefill_buckets"])), jnp.int32))
        self.engine = LLMEngine(
            model=built["model"], model_cfg=built["model_cfg"],
            params=self._params, engine_config=EngineConfig(**engine),
            store=cw.store if cw is not None else None, seed=seed)
        self.engine.start()
        # Warm-up: one request of one token for each prefill program the
        # cell's deck reaches, each with token ids of its own. The decode
        # programs, where the deck decodes, are met in the ramp-up, which
        # walks the running set up one stream at a time.
        t0 = time.perf_counter()
        for i, n in enumerate(warm_prompts):
            self.engine.submit([i + 1] * n, 1).result(timeout=1100)
        self._warmup_s = time.perf_counter() - t0
        # the engine's own record of every request, kept for the whole run
        request_recorder.set_enabled(True)
        request_recorder.ring().resize(8192)
        request_recorder.ring().clear()
        self._trace_dir = None

    # -- instruments ---------------------------------------------------------

    def bench_info(self) -> Dict[str, Any]:
        info = self.replica_info()
        m = self.engine.metrics()
        return {
            "platform": info["platform"], "kind": info["device_kind"],
            "count": info["device_count"], "ready_wall": self._ready_wall,
            "warmup_s": self._warmup_s,
            "memory": self._memory.report(),
            "kv_arena_bytes": info["kv_arena_bytes"],
            "cache_stats": info["cache_stats"],
            "engine": {k: v for k, v in m.items()
                       if isinstance(v, (int, float))},
            "compiled_step_calls": m["compiled_step_calls"],
        }

    def bench_engine_ttft_ms(self) -> List[list]:
        """[prompt tokens, submit to first token in ms] of the requests the
        engine has finished, oldest first, from the request recorder."""
        from ray_tpu.util import request_recorder

        return [[r.tokens_in, r.ttft_ms]
                for r in request_recorder.ring().recent()
                if r.role == "engine" and r.ttft_ms is not None]

    def bench_trace_start(self) -> bool:
        from benchmark import trace_reduce

        self._trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        trace_reduce.start_session(self._trace_dir)
        return True

    def bench_trace_stop(self) -> str:
        """Ends the session and names the directory it wrote: the replica
        and `run.py` share the machine and its `TMPDIR`, and the reduction
        is `run.py`'s, in a child, so this interpreter (the pump's and the
        readers') is held for the profiler's own write and no longer."""
        from benchmark import trace_reduce

        trace_reduce.stop_session()
        return self._trace_dir

    def bench_check(self, prompts: List[List[int]],
                    streamed: List[List[int]]) -> dict:
        """What `generate` streamed for the check prompts (greedy, through
        the handle, prefill and then decode through the paged cache) against
        the plain reference's full forward pass over prompt and answer: at
        every position the streamed token has to be one the reference puts
        on top, give or take the stated precision."""
        import flax.linen as nn
        import jax
        import jax.numpy as jnp
        import numpy as np

        config = self._config
        ref = importlib.import_module(
            "benchmark." + config["reference"][:-3].replace("/", "."))
        params = nn.meta.unbox(self._params)
        params = params.get("params", params)
        worst, same, total, failures = 0.0, 0, 0, []
        for prompt, answer in zip(prompts, streamed):
            ids = list(prompt) + list(answer[:-1])
            with jax.default_matmul_precision("highest"):
                want = np.asarray(ref.logits(
                    params, config, jnp.asarray(ids, jnp.int32)))
            for j, token in enumerate(answer):
                row = want[len(prompt) - 1 + j]
                short = float(row.max() - row[token]) \
                    / float(np.sqrt(np.mean(row ** 2)))
                worst = max(worst, short)
                same += int(token == int(row.argmax()))
                total += 1
        if not total or not worst <= SHORTFALL_TOLERANCE:
            failures.append(
                f"a streamed token's reference logit is {worst:.4g} of the "
                f"row's rms under the reference's top (tolerance "
                f"{SHORTFALL_TOLERANCE}), over {total} tokens")
        m = self.engine.metrics()
        if m["kv_pages_live"]:
            failures.append(f"{m['kv_pages_live']} KV pages still live")
        return {"failures": failures, "worst_shortfall": worst,
                "tokens_checked": total, "tokens_same_as_reference": same,
                "prompts": [len(p) for p in prompts]}


# -- the driver's side --------------------------------------------------------

def _wait_streams_started(samples, callers: int, deadline: float) -> None:
    """Ramp-up: every caller has a request with its first token."""
    while time.perf_counter() < deadline:
        started = sum(1 for s in list(samples) if s.token_times or s.error)
        if started >= callers:
            return
        time.sleep(0.05)
    raise RuntimeError("ramp-up: not every caller got a first token")


def _slice_taker(handle, seconds: float):
    """The traced slice of a serving cell: the session is the replica's,
    the judging `run.py`'s (a child), the two reached through the handle."""
    from benchmark import stage, trace_reduce

    def start():
        handle.bench_trace_start.remote().result(timeout=60)

    def stop():
        stage.enter("trace_stop")
        trace_dir = handle.bench_trace_stop.remote().result(timeout=120)
        stage.enter("window")
        return trace_dir

    return trace_reduce.SliceTaker(start, stop, seconds, serving=True)


def run_serve_cell(cell: dict, config: dict, traffic: dict, seed: int,
                   seconds: float, trace: bool, t_start_wall: float,
                   require_tpu: bool = True,
                   deployment_cls=BenchLLMDeployment) -> dict:
    import ray_tpu
    from ray_tpu import serve

    from benchmark import stage
    from benchmark import traffic as tg
    from benchmark.device_memory import over_limit

    obs: Dict[str, Any] = {"failures": []}
    # one deck length inside each prefill program the deck reaches
    buckets = sorted(config["engine"]["prefill_buckets"])
    warm = {}
    for length, _ in tg.expand_deck(traffic):
        warm[min(b for b in buckets if b >= length)] = length
    warm_prompts = [warm[b] for b in sorted(warm)]
    cluster = dict(config.get("cluster", {}))
    stage.enter("setup")
    ray_tpu.init(**cluster)
    try:
        deco = serve.deployment(
            name="llm", num_replicas=1,
            ray_actor_options={"num_tpus": 1} if require_tpu else None,
            **config.get("deployment", {}))
        handle = serve.run(deco(deployment_cls).bind(
            config=config, seed=seed, warm_prompts=warm_prompts,
            require_tpu=require_tpu))
        info0 = handle.bench_info.remote().result(timeout=1100)
        obs.update(platform=info0["platform"], device_kind=info0["kind"],
                   count=info0["count"],
                   worker_ready_s=info0["ready_wall"] - t_start_wall,
                   warmup_s=info0["warmup_s"],
                   kv_arena_bytes=info0["kv_arena_bytes"])

        def stream(ids, new):
            for chunk in handle.generate.options(stream=True).remote(
                    ids, new):
                yield chunk["token"]

        feeder = tg.DeckFeeder(traffic, config["vocab_size"], seed)
        stop = threading.Event()
        samples, threads = [], []
        if traffic["kind"] not in ("closed-loop", "open-loop"):
            raise ValueError(f"unknown traffic kind {traffic['kind']!r}")
        ramp = traffic.get("ramp")
        stage.enter("ramp")
        if ramp == "all_callers_streaming":
            # ramp-up is set-up: the window opens on a full running set
            samples, threads = tg.run_closed_loop(
                stream, feeder, traffic["callers"], stop,
                serial_start=feeder.fixed)
            _wait_streams_started(samples, traffic["callers"],
                                  time.perf_counter() + 300)
        elif ramp == "serial_requests":
            # a few requests of the deck's sizes warm the whole path; they
            # come from another stream of the seed and are not measured
            warm = tg.DeckFeeder(traffic, config["vocab_size"], seed + 1)
            for _ in range(traffic["ramp_requests"]):
                _, _, ids, new = warm.next()
                if len(list(stream(ids, new))) != new:
                    raise RuntimeError("a ramp-up request came back short")
        info1 = handle.bench_info.remote().result(timeout=60)
        t_open = time.perf_counter()
        obs["setup_s"] = time.time() - t_start_wall
        stage.enter("window")
        if traffic["kind"] == "open-loop":
            samples, threads = tg.run_open_loop(
                stream, feeder, tg.arrival_times(traffic, seed, seconds),
                t_open, stop)
        elif not threads:
            samples, threads = tg.run_closed_loop(
                stream, feeder, traffic["callers"], stop)
        if trace:
            taker = _slice_taker(handle, seconds)
            while not taker.idle and \
                    time.perf_counter() < t_open + seconds - 0.1:
                taker.poll(time.perf_counter() - t_open)
                time.sleep(0.05)
        time.sleep(max(0.0, t_open + seconds - time.perf_counter()))
        t_close = time.perf_counter()
        info2 = handle.bench_info.remote().result(timeout=60)
        if trace:
            taken = taker.close(t_close - t_open)
        stop.set()
        for t in threads:
            t.join(timeout=180)
        if any(t.is_alive() for t in threads):
            obs["failures"].append("a caller did not finish after the window")
        obs["engine_ttft_ms"] = handle.bench_engine_ttft_ms.remote().result(
            timeout=60)
        info3 = handle.bench_info.remote().result(timeout=60)
        # `correct`, through the path the callers used: greedy answers to a
        # few seeded prompts, judged by the reference in the replica
        stage.enter("check")
        rng = random.Random(seed * 1000003 + 41)
        prompts = [[rng.randrange(config["vocab_size"]) for _ in range(n)]
                   for n in traffic.get("check_prompts", [40, 200])]
        new = 1 + traffic.get("check_decode_steps", 3)
        streamed = [list(stream(ids, new)) for ids in prompts]
        check = handle.bench_check.remote(prompts, streamed).result(
            timeout=900)
        if any(len(answer) != new for answer in streamed):
            check["failures"].append("a check prompt's answer came short")
        stage.enter("shutdown")
        serve.shutdown()
    finally:
        ray_tpu.shutdown()
    if trace:
        from benchmark import trace_reduce
        trace_reduce.reduce_taken(obs, taken, serving=True)
    obs["failures"] += check["failures"]
    obs["check"] = check
    obs["samples"] = samples
    obs["t_open"], obs["t_close"] = t_open, t_close
    obs["window_s"] = t_close - t_open
    obs["memory"] = info3["memory"]
    obs["memory_peak_bytes"] = info3["memory"]["memory_peak_bytes"]
    obs["failures"] += over_limit(info3["memory"])
    obs["engine_delta"] = {k: info2["engine"][k] - info1["engine"].get(k, 0)
                           for k in info2["engine"]}
    obs["compiles_in_window"] = sum(
        info2["cache_stats"][k] - info1["cache_stats"][k]
        for k in ("misses", "retraces"))
    if obs["compiles_in_window"]:
        obs["failures"].append(f"{obs['compiles_in_window']} program(s) "
                               f"compiled inside the window")
    obs["compiled_step_calls"] = info3["compiled_step_calls"]
    if info3["cache_stats"]["retraces"]:
        obs["failures"].append(f"the replica retraced: {info3['cache_stats']}")
    return obs
