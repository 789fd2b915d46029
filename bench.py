"""Benchmark suite — prints ONE JSON line.

Headline: GPT-2-125M single-chip training throughput (tokens/sec/chip)
with computed MFU — BASELINE.json's north-star metric ("Ray Train GPT-2
tokens/sec/chip"). The reference repo has no checked-in tokens/sec number
(BASELINE.md "Not in-repo"), so vs_baseline for the headline is derived
from hardware peaks: the north star asks for >=0.9x of an A100+NCCL
baseline, and at the commonly reported ~30% MFU for GPT-2-class DDP
training an A100 (312 bf16 TFLOP/s) yields `0.30 * 312e12 /
flops_per_token` tokens/s/chip. vs_baseline = ours / (0.9 * that).
The headline is always that metric: on a machine with no TPU the model
phases fail, its value is null and the exit code is non-zero.

Every phase runs in a process of its own (`python bench.py --phase
NAME`), started by a parent that never imports jax: a process that has
touched jax holds the chip, and a later phase's workers that need it
would fail or hang. A phase that raises is recorded under the suite and
makes the exit code non-zero.

The `suite` field carries the rest of the reference's microbenchmark
shapes (`python/ray/_private/ray_perf.py`,
`release/perf_metrics/microbenchmark.json`), each with its own
vs_baseline against BASELINE.md:
- 1:1 sync actor calls        (baseline 2,097/s)
- 1:1 async actor calls       (baseline 9,063/s)
- n:n async actor calls       (baseline 27,688/s)
- single-client async tasks   (baseline 8,194/s)
- single-client put GB/s      (baseline 20.1 GB/s)
- single-client plasma get/s  (baseline 10,270/s)
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

BASELINES = {
    "1_1_actor_calls_sync": 2097.0,
    "1_1_actor_calls_async": 9063.0,
    "n_n_actor_calls_async": 27688.0,
    "single_client_tasks_sync": 971.0,
    "single_client_tasks_async": 8194.0,
    "multi_client_tasks_async": 21744.0,
    "single_client_put_gigabytes": 20.1,
    "multi_client_put_gigabytes": 35.9,
    "single_client_get_calls": 10270.0,
    "single_client_wait_1k_refs": 5.0,
    "single_client_get_object_containing_10k_refs": 13.3,
    "placement_group_create_removal": 839.0,
}

A100_BF16_PEAK = 312e12
A100_ASSUMED_MFU = 0.30
NORTH_STAR_FACTOR = 0.9

# any metric that dropped more than this vs the previous BENCH_r*.json
# is flagged in a REGRESSION block (ROADMAP item #5)
REGRESSION_DROP_FRACTION = 0.15


def _host_metadata() -> dict:
    """Box provenance for every row (VERDICT r3 weak #5: %-of-ceiling
    claims must be auditable — cpu model, core count, /dev/shm size and
    library versions pin down what 'this box' was)."""
    import platform

    meta = {
        "cpu_count": os.cpu_count(),
        "python": platform.python_version(),
        "platform": platform.platform(),
    }
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.lower().startswith("model name"):
                    meta["cpu_model"] = line.split(":", 1)[1].strip()
                    break
    except OSError:
        pass
    try:
        st = os.statvfs("/dev/shm")
        meta["dev_shm_bytes"] = st.f_frsize * st.f_blocks
    except OSError:
        pass
    from importlib import metadata

    for mod in ("jax", "numpy"):
        meta[f"{mod}_version"] = metadata.version(mod)
    return meta


def _scale_overrides() -> dict:
    """RAY_TPU_SCALE_SIZES decouples bench sizes from os.cpu_count()
    (ROADMAP item #5). Comma-separated ints, all optional, defaulting to
    the current host-scaled behavior, e.g.:

        RAY_TPU_SCALE_SIZES=raylets=50,actors=5000,tasks=20000,pgs=200,\
putters=8,put_mb=64
    """
    out = {}
    for part in os.environ.get("RAY_TPU_SCALE_SIZES", "").split(","):
        if "=" not in part:
            continue
        k, v = part.split("=", 1)
        try:
            out[k.strip()] = int(v)
        except ValueError:
            pass
    return out


def _store_stats() -> dict:
    """Lock/eviction counters of the live node's object store, emitted
    beside each phase-A row so contention claims are auditable."""
    try:
        from ray_tpu._private import worker_api

        store = worker_api._global_state.core_worker.store
        st = store.stats()
        st["num_shards"] = store.num_shards
        st["shards"] = store.shard_stats()
        return st
    except Exception as e:  # noqa: BLE001
        return {"error": repr(e)[:200]}


def _rpc_stats_snapshot() -> dict:
    """Driver-process RPC coalescing counters (rpc.RPC_STATS)."""
    from ray_tpu._private import rpc as rpc_mod

    st = rpc_mod.RPC_STATS
    return {k: getattr(st, k) for k in type(st).__slots__}


def _control_plane_attrib(before: dict) -> dict:
    """Where a control-plane number came from: the phase's driver-side
    frame-coalescing deltas plus the GCS/raylet scheduler + shard
    counters, scraped over RPC (`metrics_text`) from the live daemons.
    Driver-process counters only — worker subprocesses keep their own
    RPC_STATS — so msgs_per_frame understates cluster-wide coalescing.
    """
    now = _rpc_stats_snapshot()
    delta = {k: now[k] - before.get(k, 0) for k in now}
    delta["msgs_per_frame"] = round(
        delta["messages_sent"] / max(1, delta["frames_sent"]), 3)
    out = {"driver_rpc_delta": delta}
    try:
        from ray_tpu._private import worker_api

        cw = worker_api._global_state.core_worker

        async def scrape():
            gcs = await cw.gcs.call("metrics_text", {}, timeout=10.0)
            raylet = await cw._clients.get(cw.raylet_addr)
            ray = await raylet.call("metrics_text", {}, timeout=10.0)
            return gcs["text"], ray["text"]

        gcs_text, raylet_text = cw._run_sync(scrape())
        prefixes = ("scheduler_", "raylet_leases_granted",
                    "raylet_workers_returned", "raylet_pending_leases",
                    "gcs_table_shard_", "rpc_")

        def agg(text: str) -> dict:
            # sum labeled series per bare metric name — the artifact
            # wants attributable totals, not 8 shard rows per table
            rows = {}
            for ln in text.splitlines():
                if not ln or ln.startswith("#"):
                    continue
                name, _, val = ln.rpartition(" ")
                bare = name.split("{", 1)[0]
                if bare.startswith(prefixes):
                    try:
                        rows[bare] = round(
                            rows.get(bare, 0.0) + float(val), 3)
                    except ValueError:
                        pass
            return rows

        out["gcs"] = agg(gcs_text)
        out["raylet"] = agg(raylet_text)
    except Exception as e:  # noqa: BLE001
        out["scrape_error"] = repr(e)[:200]
    return out


def _check_regressions(suite: dict) -> list | None:
    """Self-comparison gate: load the newest BENCH_r*.json and flag any
    metric that dropped >15% (ROADMAP item #5). Returns the regression
    rows (also printed as a REGRESSION block on stderr) or None."""
    import glob
    import sys

    here = os.path.dirname(os.path.abspath(__file__))
    files = sorted(glob.glob(os.path.join(here, "BENCH_r*.json")))
    if not files:
        return None
    prev_path = files[-1]
    try:
        with open(prev_path) as f:
            prev = json.load(f)
        if "suite" in prev:
            prev_suite = prev["suite"]
        else:
            # driver-written artifact: the bench JSON line is embedded
            # (possibly truncated at the head) in the "tail" field —
            # raw-decode the suite object from its opening brace
            tail = prev.get("tail", "")
            key = tail.find('"suite"')
            brace = tail.find("{", key) if key != -1 else -1
            if brace == -1:
                return None
            prev_suite, _ = json.JSONDecoder().raw_decode(tail[brace:])
    except (OSError, ValueError):
        return None
    regressions = []
    for key, cur in suite.items():
        if not isinstance(cur, dict):
            continue
        now = cur.get("value")
        old = prev_suite.get(key)
        was = old.get("value") if isinstance(old, dict) else None
        if not isinstance(now, (int, float)) \
                or not isinstance(was, (int, float)) or was <= 0:
            continue
        if now < (1.0 - REGRESSION_DROP_FRACTION) * was:
            regressions.append({
                "metric": key,
                "prev": was,
                "now": now,
                "drop_pct": round(100 * (1 - now / was), 1),
                "baseline_file": os.path.basename(prev_path),
            })
    if regressions:
        print("REGRESSION (>15% drop vs "
              f"{os.path.basename(prev_path)}):", file=sys.stderr)
        for r in regressions:
            print(f"  {r['metric']}: {r['prev']} -> {r['now']} "
                  f"(-{r['drop_pct']}%)", file=sys.stderr)
    return regressions or None


# --------------------------------------------------------------------------
# Model benchmark (runs directly on the local accelerator, no cluster —
# matching the reference's release/train_tests harnesses which measure the
# framework's compute path, not the control plane).
# --------------------------------------------------------------------------

def _require_tpu():
    """The local accelerator, or an error: a model phase measures the
    chip, and a number from the host under its name would be a lie."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"model phases need a TPU; jax found {dev.platform!r} "
            f"({dev.device_kind})")
    return dev


def _bench_train(model, loss_fn, vocab_size: int, batch: int, seq: int,
                 steps: int = 20):
    """Shared model-training bench harness: synth tokens, adamw, donated
    jitted step, then a timed loop fenced by pulling the final step's
    scalar loss to the host. Returns (tokens_per_sec, n_params).
    """
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    rng = np.random.default_rng(0)
    tokens = jnp.asarray(
        rng.integers(0, vocab_size, (batch, seq + 1), np.int32))
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    params = jax.jit(model.init)(jax.random.PRNGKey(0), inputs)
    tx = optax.adamw(3e-4)
    opt_state = tx.init(params)

    @partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, inputs, targets):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(model, p, inputs, targets))(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    params, opt_state, loss = train_step(params, opt_state, inputs,
                                         targets)
    float(loss)  # compile + warm + fence
    start = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = train_step(params, opt_state, inputs,
                                             targets)
    float(loss)
    elapsed = time.perf_counter() - start
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    return batch * seq * steps / elapsed, n_params


def bench_gpt2_tokens_per_sec(steps: int = 20, batch: int = None,
                              seq: int = None):
    from functools import partial

    from ray_tpu._private.accelerators import peak_bf16_flops
    from ray_tpu.models import GPT, GPTConfig
    from ray_tpu.models.gpt import flops_per_token as gpt_flops_per_token
    from ray_tpu.ops import flash_attention, fused_cross_entropy

    dev = _require_tpu()
    batch, seq = batch or 16, seq or 1024  # sized for one chip
    cfg = GPTConfig.gpt2_125m(remat=False, max_seq_len=seq)
    peak_flops = peak_bf16_flops(dev.device_kind)

    # single-chip hot path: pallas flash attention (scores never touch
    # HBM) + fused LM-head CE (bf16 logits, hand-written backward)
    model = GPT(cfg, attention_fn=partial(flash_attention, causal=True))

    def loss_fn(model, p, inputs, targets):
        hidden, wte = model.apply(p, inputs, return_hidden=True)
        return fused_cross_entropy(hidden, wte, targets)

    tokens_per_sec, n_params = _bench_train(
        model, loss_fn, cfg.vocab_size, batch, seq, steps)

    # PaLM appendix-B accounting (6N + attention term), shared with the
    # model module so the two can't drift
    fpt = gpt_flops_per_token(cfg, seq)
    a100_tokens = A100_ASSUMED_MFU * A100_BF16_PEAK / fpt
    return {
        "tokens_per_sec_per_chip": round(tokens_per_sec, 1),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "params": int(n_params),
        "batch": batch,
        "seq": seq,
        "mfu": round(tokens_per_sec * fpt / peak_flops, 4),
        "vs_baseline": round(
            tokens_per_sec / (NORTH_STAR_FACTOR * a100_tokens), 3),
    }


def bench_gpt2_long_context(steps: int = 10):
    """Single-chip long-context: GPT-2 at seq 4096 through the flash
    kernel (dense attention's f32 scores would be ~3.2 GB per layer at
    this shape). Multi-chip long context is ring/Ulysses attention —
    exercised by the driver's dryrun, not benchable on one chip."""
    out = bench_gpt2_tokens_per_sec(steps=steps, batch=4, seq=4096)
    # vs_baseline is the seq-1024 north-star comparison; at 4096 the
    # per-token flops differ, so only throughput + MFU are meaningful
    out.pop("vs_baseline", None)
    return out


def bench_llama_tokens_per_sec(steps: int = 20):
    """Secondary model bench: Llama-125M (RMSNorm/RoPE/SwiGLU/GQA 12q:4kv)
    through the flash kernel's native grouped-KV path."""
    from functools import partial

    from ray_tpu._private.accelerators import peak_bf16_flops
    from ray_tpu.models.llama import Llama, LlamaConfig, flops_per_token
    from ray_tpu.ops import flash_attention, fused_cross_entropy

    dev = _require_tpu()
    cfg = LlamaConfig.llama_125m(remat=False, max_seq_len=1024)
    batch, seq = 16, 1024
    model = Llama(cfg, attention_fn=partial(flash_attention, causal=True))

    # same hot path as the GPT-2 bench: fused LM-head CE (bf16 hidden x
    # tied embedding, logits never hit HBM)
    def loss_fn(model, p, inputs, targets):
        hidden, wte = model.apply(p, inputs, return_hidden=True)
        return fused_cross_entropy(hidden, wte, targets)

    tokens_per_sec, _ = _bench_train(
        model, loss_fn, cfg.vocab_size, batch, seq, steps)
    mfu = tokens_per_sec * flops_per_token(cfg, seq) / \
        peak_bf16_flops(dev.device_kind)
    return {
        "tokens_per_sec_per_chip": round(tokens_per_sec, 1),
        "mfu": round(mfu, 4),
        "batch": batch,
        "seq": seq,
    }


# --------------------------------------------------------------------------
# Control-plane microbenchmarks (reference ray_perf.py shapes).
# --------------------------------------------------------------------------

def bench_pipeline_bubble():
    """Measured pipeline-schedule overhead on the 4-stage host mesh
    (VERDICT r2 item 9, r4 item 5; ROADMAP r5 #3): times the fused-loss
    pipeline train step through the AOT executable cache
    (`ray_tpu.parallel.fold_steps`) — params donated, grads applied
    in-jit, K=4 optimizer steps folded into ONE dispatch via lax.scan
    over prefetched on-device batches — which is how a dispatch-bound
    training loop should invoke it. Fits the structural model
    t(M) = a + c*(M + S - 1) by least squares over four microbatch
    counts and validates on a held-out fifth; `a` is the PER-STEP fixed
    driver overhead (the r5 #3 "< 2 ms" number) and the executable
    cache counters ride along for the dispatch_overhead phase.
    bubble = (S-1)/(M+S-1) (identical for GPipe and 1F1B in the
    single-jit formulation — see ray_tpu/parallel/pipeline.py). Runs in
    a forced-CPU subprocess so it never competes with the TPU phases
    for the chip."""
    code = r"""
import json, time
import jax
import jax.numpy as jnp
import numpy as np
from ray_tpu.parallel.mesh import build_mesh
from ray_tpu.parallel.compile_cache import (
    ExecutableCache, fold_steps, stack_batches)
from ray_tpu.parallel.pipeline import (
    bubble_fraction, pipeline_train_step, stack_stage_params)

S, DIM, MB_ROWS, K = 4, 256, 8, 4   # K = steps_per_call (one dispatch)
mesh = build_mesh({"pp": S}, devices=jax.devices()[:S])
rng = np.random.RandomState(0)
params = stack_stage_params([
    {"w": jnp.asarray(rng.randn(DIM, DIM) * 0.05, jnp.float32)}
    for _ in range(S)])

def stage_fn(p, h):
    for _ in range(4):
        h = jnp.tanh(h @ p["w"])
    return h

def loss_fn(o, t):
    return jnp.mean(jnp.square(o - t))

def train_step(ps, batch):
    x, y = batch
    loss, g = pipeline_train_step(
        stage_fn, loss_fn, ps, x, y, mesh,
        num_microbatches=batch_microbatches(x))
    return jax.tree_util.tree_map(
        lambda p, gg: p - 1e-3 * gg, ps, g), loss

def batch_microbatches(x):
    return x.shape[0] // MB_ROWS

cache = ExecutableCache()
multi = fold_steps(train_step, K, cache=cache)
_batches = {}

def _get_batches(M):
    # K prefetched on-device batches, stacked on a leading axis
    if M not in _batches:
        _batches[M] = stack_batches([
            (jnp.asarray(rng.randn(MB_ROWS * M, DIM), jnp.float32),
             jnp.asarray(rng.randn(MB_ROWS * M, DIM), jnp.float32))
            for _ in range(K)])
    return _batches[M]

def timed(M):
    batches = _get_batches(M)
    ps = jax.tree_util.tree_map(lambda p: p.copy(), params)
    ps, losses = multi(ps, batches)   # compile (first pass) + warm
    jax.block_until_ready(losses)
    n, t0 = 0, time.perf_counter()
    while time.perf_counter() - t0 < 1.5:
        ps, losses = multi(ps, batches)  # ONE dispatch per K steps
        jax.block_until_ready(losses)
        n += K
    return (time.perf_counter() - t0) / n

# palindromic double pass cancels slow drift on shared hosts
FIT_MS, HOLD_M = (4, 8, 24, 32), 16
order = FIT_MS + (HOLD_M,)
acc = {M: [] for M in order}
for M in order + order[::-1]:
    acc[M].append(timed(M))
ts = {M: sum(v) / len(v) for M, v in acc.items()}
# least-squares t = a + c*(M+S-1) over the fit points
xs = np.array([M + S - 1 for M in FIT_MS], np.float64)
ys = np.array([ts[M] for M in FIT_MS], np.float64)
c, a = np.polyfit(xs, ys, 1)
hold_pred = a + c * (HOLD_M + S - 1)
t1, t3 = ts[4], ts[32]
pred = ((4 + S - 1) / 4) / ((32 + S - 1) / 32)
meas = (t1 / 4) / (t3 / 32)
print(json.dumps({
    "bubble_m4": round(bubble_fraction(S, 4), 4),
    "bubble_m32": round(bubble_fraction(S, 32), 4),
    "step_s_m4": round(t1, 4), "step_s_m32": round(t3, 4),
    "per_microbatch_ratio_measured": round(meas, 3),
    "per_microbatch_ratio_predicted_no_overhead": round(pred, 3),
    "fixed_dispatch_overhead_s": round(float(a), 5),
    "per_microbatch_cost_s": round(float(c), 5),
    "steps_per_call": K,
    "executable_cache": cache.stats.as_dict() | {
        "entries": cache.size()},
    "holdout_m16_measured_s": round(ts[HOLD_M], 4),
    "holdout_m16_model_s": round(float(hold_pred), 4),
    "holdout_residual_pct": round(
        100 * abs(ts[HOLD_M] - hold_pred) / ts[HOLD_M], 2),
}))
"""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=420,
        cwd=os.path.dirname(os.path.abspath(__file__)),
        env=env)
    if proc.returncode != 0:
        raise RuntimeError("pipeline bench subprocess failed: "
                           + proc.stderr[-300:])
    return json.loads(proc.stdout.strip().splitlines()[-1])


def bench_dispatch_overhead(pipeline_bubble: dict | None = None):
    """Driver-dispatch overhead phase (ROADMAP r5 #3, twice missed).

    Reports the three numbers that define the sub-2 ms dispatch plane:
    (a) the fitted per-step fixed overhead `a` from
    `bench_pipeline_bubble` (AOT cached executable, donated carries,
    K-step folding) plus its executable-cache hit/miss counters, (b)
    the AOT dispatch cost in isolation — µs per call of a cached
    trivial executable, the floor any training step pays — and (c)
    compiled-DAG round-trip latency over the zero-pickle channel plane
    (3-stage actor chain, raw-header frames, FIFO-token wakeups).
    `compiled_dag_roundtrips_per_s` is emitted value-style so the >15%
    REGRESSION self-comparison gates it like every other rate."""
    import statistics

    out: dict = {"dispatch_overhead": {}}
    detail = out["dispatch_overhead"]
    if isinstance(pipeline_bubble, dict) and \
            "fixed_dispatch_overhead_s" in pipeline_bubble:
        detail["fixed_dispatch_overhead_s"] = \
            pipeline_bubble["fixed_dispatch_overhead_s"]
        detail["meets_2ms_target"] = \
            pipeline_bubble["fixed_dispatch_overhead_s"] < 0.002
        detail["steps_per_call"] = pipeline_bubble.get("steps_per_call")
        detail["executable_cache"] = pipeline_bubble.get(
            "executable_cache")

    # (b) bare AOT dispatch: cached-executable call overhead in µs
    import jax.numpy as jnp

    from ray_tpu.parallel.compile_cache import (ExecutableCache,
                                                compiled_step)

    cache = ExecutableCache()
    tick = compiled_step(lambda x: x + 1, cache=cache)
    x = jnp.zeros((), jnp.float32)
    for _ in range(50):
        x = tick(x)  # 1 miss + warm hits
    n, start = 0, time.perf_counter()
    while time.perf_counter() - start < 1.0:
        x = tick(x)
        n += 1
    x.block_until_ready()
    detail["aot_dispatch_us"] = round(
        1e6 * (time.perf_counter() - start) / n, 1)
    detail["aot_cache"] = cache.stats.as_dict()

    # (c) compiled-DAG round trip on the zero-pickle channel plane
    import ray_tpu
    from ray_tpu import dag as dag_mod

    ray_tpu.init(num_cpus=4, object_store_memory=64 << 20)
    try:
        @ray_tpu.remote
        class Stage:
            def __init__(self, add):
                self.add = add

            def f(self, x):
                return x + self.add

        a, b, c = Stage.remote(1), Stage.remote(10), Stage.remote(100)
        ray_tpu.get([a.f.remote(0), b.f.remote(0), c.f.remote(0)],
                    timeout=60)
        node = dag_mod.bind(
            c.f, dag_mod.bind(b.f, dag_mod.bind(
                a.f, dag_mod.InputNode())))
        compiled = node.experimental_compile()
        for i in range(100):
            compiled.execute(i)
        lat = []
        n, start = 0, time.perf_counter()
        while time.perf_counter() - start < 2.0:
            t0 = time.perf_counter()
            compiled.execute(n)
            lat.append(time.perf_counter() - t0)
            n += 1
        out["compiled_dag_roundtrips_per_s"] = n / (
            time.perf_counter() - start)
        detail["compiled_dag_rtt_us_p50"] = round(
            1e6 * statistics.median(lat), 1)
        compiled.teardown()
    finally:
        ray_tpu.shutdown()
    return out


def bench_observability_overhead():
    """Cost ceiling of the passive observability plane. ISSUE 20 widens
    the measured configuration: the interleaves below now run with the
    WHOLE health/alert plane live — a tsdb Sampler scraping at 1s, the
    SLO AlertEvaluator riding its scrape tick, and a Watchdog sweeping
    the registered loop probes (the engine pump registers one on
    start()) — so `observability_dispatch_per_s` /
    `observability_serve_req_per_s` and the <1% targets price
    recorder + evaluator + watchdog together, not the recorders alone.
    """
    from ray_tpu._private import health as health_mod
    from ray_tpu.util import slo as slo_mod
    from ray_tpu.util import tsdb as tsdb_mod

    sampler = tsdb_mod.Sampler(interval_s=1.0)
    evaluator = slo_mod.AlertEvaluator(sampler.db,
                                       register_metrics=False)
    evaluator.attach(sampler)
    sampler.start()
    watchdog = health_mod.Watchdog(source="BENCH",
                                   interval_s=0.5).start()
    try:
        out = _bench_observability_measured()
    finally:
        sampler.stop()
        watchdog.stop()
    out["observability_overhead"].update({
        "alert_plane_active": True,
        "alert_evaluations": evaluator.evaluations,
        "watchdog_checks": watchdog.checks,
        "alerts_fired_during_bench": evaluator.firing(),
    })
    return out


def _bench_observability_measured():
    """Cost ceiling of the flight-recorder plane (ISSUE 5): the step
    profiler is ALWAYS ON, so its price on the sub-2 ms dispatch path
    PR 4 bought must stay under 1%. Times the same cached-executable
    dispatch loop with the recorder disabled and enabled (palindromic
    interleave, medians — slow drift on shared hosts cancels), reports
    the delta, and emits `observability_dispatch_per_s` value-style so
    the >15% REGRESSION self-comparison gates the *absolute* dispatch
    rate with the recorder on. Also measures the raw record_step cost
    and proves the ring stays bounded under sustained stepping.

    ISSUE 12 extends the phase with the serve-path twin: the same
    on/off interleave over a closed-loop LLM engine holds the REQUEST
    recorder (per-request phase stamps + histogram folds) under 1% of
    serve req/s, and `observability_serve_req_per_s` rides the same
    >15% REGRESSION gate."""
    import statistics

    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.parallel.compile_cache import (ExecutableCache,
                                                compiled_step)
    from ray_tpu.serve.llm import EngineConfig, LLMEngine
    from ray_tpu.util import request_recorder as rr
    from ray_tpu.util import step_profiler as sp

    cache = ExecutableCache()
    w = jnp.asarray(np.random.RandomState(0).randn(192, 192),
                    jnp.float32)

    def step(x):
        for _ in range(8):
            x = jnp.tanh(x @ w)
        return x

    tick = compiled_step(step, cache=cache)
    x = jnp.ones((192, 192), jnp.float32)
    x = tick(x)  # compile
    x.block_until_ready()

    def per_call_us() -> float:
        nonlocal x
        n, start = 0, time.perf_counter()
        while time.perf_counter() - start < 0.35:
            x = tick(x)
            n += 1
        x.block_until_ready()
        return 1e6 * (time.perf_counter() - start) / n

    was_enabled = sp.enabled()
    dis, en = [], []
    try:
        per_call_us()  # warm both code paths before measuring
        # strict alternation, min-of-passes: min is robust against the
        # scheduler-noise spikes a shared/1-core box injects (the
        # recorder's cost is deterministic; the noise is one-sided)
        for on in (False, True) * 6:
            sp.set_enabled(on)
            (en if on else dis).append(per_call_us())
    finally:
        sp.set_enabled(was_enabled)
    dis_us = min(dis)
    en_us = min(en)
    overhead_pct = 100.0 * (en_us - dis_us) / dis_us

    # raw recorder costs, in isolation
    t0 = time.perf_counter()
    reps = 20000
    for i in range(reps):
        sp.record_step(i, 1.0, host_dispatch_ms=0.5, tokens=1)
    record_us = 1e6 * (time.perf_counter() - t0) / reps
    ring_len_after = len(sp.ring().recent())
    bounded = ring_len_after <= sp.ring().capacity

    # -- serve-path twin (ISSUE 12): the request recorder's price on
    # engine req/s. Two measurements: (a) an on/off interleave over a
    # closed-loop engine — empirical but noise-bounded on a 1-core box
    # (pass-to-pass scheduler/GC noise is ±2-3%, an order of magnitude
    # above the recorder's true cost; a fully STUBBED recorder still
    # reads 2-4% on this estimator), and (b) the isolated per-record
    # cost — the serve analog of `record_step_us` above — multiplied
    # by the measured steady req/s. The <1% target keys on (b): it is
    # deterministic and is exactly the recorder's share of request
    # wall time; (a) rides along as the empirical cross-check.
    import gc

    eng = LLMEngine(model="llama",
                    engine_config=EngineConfig(batch_buckets=(1, 2, 4),
                                               prefill_buckets=(8,)),
                    seed=0)
    eng.warmup()
    eng.start()

    def serve_req_per_s() -> float:
        gc.collect()  # cross-pass GC bleed dominates at this grain
        n = 0
        t0 = time.perf_counter()
        stop_at = t0 + 0.75
        while time.perf_counter() < stop_at:
            req = eng.submit([3, 4, 5], 4)
            req.result(timeout=60)
            n += 1
        return n / (time.perf_counter() - t0)

    rr_was = rr.enabled()
    srv_off, srv_on, deltas = [], [], []
    try:
        # warm to steady state FIRST: the engine's closed-loop rate
        # climbs for several seconds after start (allocator/dispatch
        # warmup), and drift inside the interleave biases whichever
        # side runs later
        prev = 0.0
        for _ in range(16):
            cur = serve_req_per_s()
            if prev and abs(cur - prev) / cur < 0.02:
                break
            prev = cur
        # adjacent-pair estimator: compare each on-pass against the
        # off-pass RIGHT NEXT to it, alternate which side goes first
        # (residual drift cancels across pairs), median pairwise delta
        for i in range(6):
            order = (False, True) if i % 2 == 0 else (True, False)
            pair = {}
            for on in order:
                rr.set_enabled(on)
                pair[on] = serve_req_per_s()
            srv_off.append(pair[False])
            srv_on.append(pair[True])
            deltas.append(100.0 * (pair[False] - pair[True])
                          / pair[False])
    finally:
        rr.set_enabled(rr_was)
        eng.quiesce(timeout=60)
        eng.shutdown()
    off_rps = max(srv_off)
    on_rps = max(srv_on)
    serve_interleave_pct = statistics.median(deltas)

    # (b) isolated per-record cost at this request shape x measured
    # req/s -> the recorder's share of request wall time
    rr.set_enabled(True)
    try:
        t0 = time.perf_counter()
        for i in range(reps):
            rr.record_engine(None, ts=0.0, total_ms=2.0, queue_ms=0.1,
                             admission_ms=0.1, prefill_ms=1.0,
                             decode_ms=0.8, ttft_ms=1.2, tpot_ms=0.3,
                             tokens_in=3, tokens_out=4, job="bench")
        record_req_us = 1e6 * (time.perf_counter() - t0) / reps
        rr.clear()
    finally:
        rr.set_enabled(rr_was)
    serve_cost_pct = record_req_us * on_rps / 1e4  # us/req * req/s

    detail = {
        "dispatch_us_recorder_off": round(dis_us, 2),
        "dispatch_us_recorder_on": round(en_us, 2),
        "overhead_pct": round(overhead_pct, 2),
        "meets_1pct_target": overhead_pct < 1.0,
        "record_step_us": round(record_us, 3),
        "dispatch_sample_interval": sp.dispatch_stats()[
            "sample_interval"],
        "ring_capacity": sp.ring().capacity,
        "ring_bounded_after_sustained_stepping": bounded,
        "serve_req_per_s_recorder_off": round(off_rps, 1),
        "serve_req_per_s_recorder_on": round(on_rps, 1),
        # empirical cross-check; on a 1-core box its noise floor is
        # ±2-3% (a stubbed recorder reads the same), so the target
        # keys on the deterministic cost share below
        "serve_interleave_pct": round(serve_interleave_pct, 2),
        "record_request_us": round(record_req_us, 3),
        "serve_recorder_cost_pct": round(serve_cost_pct, 3),
        "serve_meets_1pct_target": serve_cost_pct < 1.0,
    }
    return {
        "observability_overhead": detail,
        # value-keyed: the >15% REGRESSION gate compares these rates
        # like every other suite metric
        "observability_dispatch_per_s": 1e6 / en_us,
        "observability_serve_req_per_s": on_rps,
    }


def bench_scale_envelope():
    """Scale-envelope rows (reference `release/benchmarks/README.md`:
    2k+ nodes / 40k+ actors / 10k+ simultaneous tasks / 1k+ PGs across
    a 64-node cluster; harnesses `distributed/test_many_{actors,tasks,
    pgs}.py`). Scaled to one box: the raylets run in virtual-worker
    mode (`RAY_TPU_VIRTUAL_WORKERS` — in-process stub workers, real
    GCS/scheduler/gossip/lease machinery, the same trivial workload the
    reference envelope uses). Sizes scale with the host so the 1-core
    build box smoke-runs the same phase the driver box runs big."""
    import ray_tpu
    from ray_tpu._private.node import Cluster

    ncpu = os.cpu_count() or 1
    # RAY_TPU_SCALE_SIZES (raylets=/actors=/tasks=/pgs=) decouples the
    # envelope from os.cpu_count() so a 50-raylet/5k-actor run can be
    # recorded on any box; defaults preserve the host-scaled behavior
    scale = _scale_overrides()
    n_raylets = scale.get("raylets", max(8, min(50, 3 * ncpu)))
    n_actors = scale.get("actors", max(300, min(5000, 100 * ncpu)))
    n_tasks = scale.get("tasks", max(2000, min(20000, 400 * ncpu)))
    n_pgs = scale.get("pgs", max(20, min(200, 4 * ncpu)))
    out = {}
    os.environ["RAY_TPU_VIRTUAL_WORKERS"] = "1"
    cluster = None
    try:
        cluster = Cluster(head_resources={"CPU": 16.0},
                          object_store_memory=16 << 20)
        for _ in range(n_raylets - 1):
            cluster.add_node({"CPU": 16.0},
                             object_store_memory=16 << 20)
        ray_tpu.init(address=cluster.gcs_addr)

        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if len([n for n in ray_tpu.nodes() if n["Alive"]]) \
                    == n_raylets:
                break
            time.sleep(0.5)
        out["scale_num_raylets"] = len(
            [n for n in ray_tpu.nodes() if n["Alive"]])

        @ray_tpu.remote(num_cpus=0.1)
        class A:
            def ping(self):
                return None

        start = time.perf_counter()
        actors = [A.remote() for _ in range(n_actors)]
        ray_tpu.get([a.ping.remote() for a in actors], timeout=900)
        out["scale_actors_launched_per_sec"] = n_actors / (
            time.perf_counter() - start)
        out["scale_num_actors"] = n_actors

        @ray_tpu.remote(num_cpus=1.0)
        def noop():
            return None

        start = time.perf_counter()
        refs = [noop.remote() for _ in range(n_tasks)]
        ray_tpu.get(refs, timeout=900)
        out["scale_tasks_per_sec"] = n_tasks / (
            time.perf_counter() - start)
        out["scale_num_tasks"] = n_tasks

        start = time.perf_counter()
        pgs = [ray_tpu.placement_group([{"CPU": 0.5}, {"CPU": 0.5}],
                                       strategy="PACK")
               for _ in range(n_pgs)]
        created = sum(1 for pg in pgs if pg.ready(timeout=300))
        for pg in pgs:
            ray_tpu.remove_placement_group(pg)
        # only PGs that actually reached CREATED count toward the rate
        out["scale_pgs_per_sec"] = created / (time.perf_counter() - start)
        out["scale_num_pgs"] = created
        if created != n_pgs:
            out["scale_pgs_failed"] = n_pgs - created
        return out
    finally:
        os.environ.pop("RAY_TPU_VIRTUAL_WORKERS", None)
        try:
            ray_tpu.shutdown()
        except Exception:  # noqa: BLE001
            pass
        if cluster is not None:
            cluster.shutdown()


def bench_rpc_fanin():
    """Transport-level microbench, no cluster: 4 clients × 256-deep
    concurrent echo bursts against one RpcServer — the pure fan-in
    shape the write coalescer exists for — plus a serial ping-pong
    row pinning that coalescing adds no latency to request/response
    traffic. Runs in-process, so it is the one control-plane row
    that is stable on the 1-core build box."""
    import asyncio

    from ray_tpu._private import rpc as rpc_mod
    from ray_tpu._private.rpc import RpcClient, RpcServer

    async def run():
        server = RpcServer()

        async def echo(payload):
            return payload

        server.register("echo", echo)
        await server.start()
        clients = [await RpcClient(server.address).connect()
                   for _ in range(4)]

        async def burst(client, n):
            await asyncio.gather(
                *[client.call("echo", i) for i in range(n)])

        await asyncio.gather(*[burst(c, 64) for c in clients])  # warm
        before = _rpc_stats_snapshot()
        n, start = 0, time.perf_counter()
        while time.perf_counter() - start < 4.0:
            await asyncio.gather(*[burst(c, 256) for c in clients])
            n += 4 * 256
        fanin = n / (time.perf_counter() - start)
        now = _rpc_stats_snapshot()
        msgs = now["messages_sent"] - before["messages_sent"]
        frames = now["frames_sent"] - before["frames_sent"]

        c = clients[0]
        n, start = 0, time.perf_counter()
        while time.perf_counter() - start < 2.0:
            for _ in range(100):
                await c.call("echo", 1)
            n += 100
        serial = n / (time.perf_counter() - start)
        for c in clients:
            await c.close()
        await server.stop()
        return fanin, serial, msgs, frames

    fanin, serial, msgs, frames = asyncio.run(run())
    return {
        "rpc_fanin_calls_async": fanin,
        "rpc_serial_calls_sync": serial,
        "rpc_fanin_coalescing": {
            "messages_sent": msgs,
            "frames_sent": frames,
            "msgs_per_frame": round(msgs / max(1, frames), 3),
        },
    }


def bench_control_plane():
    """Each phase gets an isolated cluster sized to the machine: worker
    processes beyond the core count thrash instead of pipelining, and a
    phase's leftover actors would steal cycles from the next phase's
    measurement."""

    import numpy as np

    import ray_tpu

    ncpu = os.cpu_count() or 1
    scale = _scale_overrides()
    out = {}

    # -- phase A: object plane (no task workers at all) -----------------
    ray_tpu.init(num_cpus=1, object_store_memory=1 << 30)
    try:
        arr = np.ones(64 * 1024 * 1024, np.uint8)  # 64 MiB
        # the raw-memory ceiling `put` is up against on THIS box: a
        # single-thread copy of the same buffer (VERDICT r3 weak #5 —
        # the claimed %-of-ceiling must be measured, not asserted)
        dst = np.empty_like(arr)
        np.copyto(dst, arr)
        n, start = 0, time.perf_counter()
        while time.perf_counter() - start < 1.5:
            np.copyto(dst, arr)
            n += 1
        out["host_memcpy_gigabytes"] = (
            n * arr.nbytes / (time.perf_counter() - start) / 1e9)

        ray_tpu.put(arr)  # warm
        n, start = 0, time.perf_counter()
        while time.perf_counter() - start < 3.0:
            ray_tpu.put(arr)
            n += 1
        out["single_client_put_gigabytes"] = (
            n * arr.nbytes / (time.perf_counter() - start) / 1e9)
        out["single_client_put_store"] = _store_stats()

        small_ref = ray_tpu.put(np.ones(1024, np.uint8))
        for _ in range(100):
            ray_tpu.get(small_ref)
        n, start = 0, time.perf_counter()
        while time.perf_counter() - start < 3.0:
            for _ in range(100):
                ray_tpu.get(small_ref)
            n += 100
        out["single_client_get_calls"] = n / (time.perf_counter() - start)
    finally:
        ray_tpu.shutdown()

    # -- phase A2: multi-client puts (reference `put_multi`: 10 tasks
    # each putting 10 x 80 MB). Recorded as a writer-count scaling
    # curve (1/2/4 writers by default) so the sharded store's scaling —
    # not just one aggregate number — lands in the bench artifact.
    # RAY_TPU_SCALE_SIZES putters=/put_mb= decouple the shape from
    # os.cpu_count(). -----------------------------------------------------
    curve_counts = [1, 2, 4]
    if scale.get("putters"):
        curve_counts = sorted({1, 2, 4, scale["putters"]})
    nbytes = scale.get("put_mb", 32) << 20
    count = 4
    max_w = max(curve_counts)
    ray_tpu.init(num_cpus=max_w,
                 object_store_memory=min(8 << 30, (8 * nbytes) * max_w))
    try:
        @ray_tpu.remote
        def do_put(nbytes, count):
            import numpy as _np

            block = _np.ones(nbytes, _np.uint8)
            for _ in range(count):
                ray_tpu.put(block)
            return None

        ray_tpu.get([do_put.remote(nbytes, 1)
                     for _ in range(max_w)])  # warm workers
        curve = {}
        for writers in curve_counts:
            n, start = 0, time.perf_counter()
            while time.perf_counter() - start < 4.0:
                ray_tpu.get([do_put.remote(nbytes, count)
                             for _ in range(writers)])
                n += writers * count
            curve[str(writers)] = round(
                n * nbytes / (time.perf_counter() - start) / 1e9, 3)
        out["multi_client_put_scaling"] = {
            "writers_gigabytes": curve,
            "put_mb": nbytes >> 20,
        }
        # the headline multi-client number is the best multi-writer
        # aggregate (>=2 writers), matching the reference's
        # many-putters shape
        out["multi_client_put_gigabytes"] = max(
            v for w, v in curve.items() if int(w) > 1)
        out["multi_client_put_store"] = _store_stats()
    finally:
        ray_tpu.shutdown()

    # -- phase B: tasks --------------------------------------------------
    ray_tpu.init(num_cpus=min(4, ncpu), object_store_memory=256 << 20)
    try:
        @ray_tpu.remote
        def noop():
            return None

        ray_tpu.get(noop.remote())
        ray_tpu.get([noop.remote() for _ in range(64)])
        n, start = 0, time.perf_counter()
        while time.perf_counter() - start < 3.0:
            refs = [noop.remote() for _ in range(1000)]
            ray_tpu.get(refs)
            n += 1000
        out["single_client_tasks_async"] = n / (time.perf_counter() - start)

        n, start = 0, time.perf_counter()
        while time.perf_counter() - start < 3.0:
            ray_tpu.get(noop.remote())
            n += 1
        out["single_client_tasks_sync"] = n / (time.perf_counter() - start)

        # reference `wait_multiple_refs`: submit 1k tasks, then ray.wait
        # them out one at a time (1k wait calls per op)
        n, start = 0, time.perf_counter()
        while time.perf_counter() - start < 4.0:
            not_ready = [noop.remote() for _ in range(1000)]
            while not_ready:
                _ready, not_ready = ray_tpu.wait(not_ready)
            n += 1
        out["single_client_wait_1k_refs"] = (
            n / (time.perf_counter() - start))

        # reference `get_containing_object_ref`: one object holding 10k
        # refs, repeatedly fetched (exercises nested-ref deserialization
        # + borrower registration)
        @ray_tpu.remote
        def create_object_containing_refs():
            return [ray_tpu.put(1) for _ in range(10000)]

        obj = create_object_containing_refs.remote()
        ray_tpu.get(obj)
        n, start = 0, time.perf_counter()
        while time.perf_counter() - start < 4.0:
            ray_tpu.get(obj)
            n += 1
        out["single_client_get_object_containing_10k_refs"] = (
            n / (time.perf_counter() - start))

        # placement-group create+remove cycle (reference
        # `placement_group_create/removal`: 10 trivial PGs per loop).
        # Create the batch first so the GCS scheduler pass overlaps the
        # ready-polling (polling serially per PG would measure the 50 ms
        # poll granularity, not the control plane), and fail loudly if a
        # PG never schedules instead of counting it as done.
        n, start = 0, time.perf_counter()
        while time.perf_counter() - start < 3.0:
            pgs = [ray_tpu.placement_group([{"CPU": 0.01}])
                   for _ in range(10)]
            for pg in pgs:
                if not pg.ready(timeout=30.0):
                    raise RuntimeError("placement group never scheduled")
            for pg in pgs:
                ray_tpu.remove_placement_group(pg)
            n += 20  # 10 creations + 10 removals, reference accounting
        out["placement_group_create_removal"] = (
            n / (time.perf_counter() - start))
    finally:
        ray_tpu.shutdown()

    # -- phase C: actors -------------------------------------------------
    # reference actor_multi2 shape (`ray_perf.py:222`): cpu_count()//2
    # actors, 4 caller worker processes — the cluster must actually hold
    # them all or the callers starve on leases and the row measures the
    # self-imposed cap instead of the dispatch path
    n_actors = max(1, ncpu // 2)
    ray_tpu.init(num_cpus=max(2, n_actors + 6),
                 object_store_memory=256 << 20)
    try:
        @ray_tpu.remote
        class Sink:
            def ping(self):
                return None

        actor = Sink.remote()
        ray_tpu.get(actor.ping.remote())
        for _ in range(100):
            ray_tpu.get(actor.ping.remote())
        n, start = 0, time.perf_counter()
        while time.perf_counter() - start < 3.0:
            for _ in range(100):
                ray_tpu.get(actor.ping.remote())
            n += 100
        out["1_1_actor_calls_sync"] = n / (time.perf_counter() - start)

        n, start = 0, time.perf_counter()
        while time.perf_counter() - start < 3.0:
            refs = [actor.ping.remote() for _ in range(1000)]
            ray_tpu.get(refs)
            n += 1000
        out["1_1_actor_calls_async"] = n / (time.perf_counter() - start)

        # n:n — the reference's `actor_multi2` shape
        # (`python/ray/_private/ray_perf.py:227-232`): m=4 caller WORKER
        # PROCESSES, each async-calling n_cpu actors round-robin. The
        # callers parallelize submission exactly as the baseline run did;
        # a driver-only loop would measure one submitter thread instead.
        actors = [Sink.remote() for _ in range(n_actors)]
        ray_tpu.get([a.ping.remote() for a in actors])

        @ray_tpu.remote
        def caller_work(actors, n):
            ray_tpu.get([actors[i % len(actors)].ping.remote()
                         for i in range(n)])
            return None

        m, calls = 4, 1000
        ray_tpu.get([caller_work.remote(actors, 8) for _ in range(m)])
        rpc_before = _rpc_stats_snapshot()
        n, start = 0, time.perf_counter()
        while time.perf_counter() - start < 4.0:
            ray_tpu.get([caller_work.remote(actors, calls)
                         for _ in range(m)])
            n += m * calls
        out["n_n_actor_calls_async"] = n / (time.perf_counter() - start)
        out["n_n_actor_calls_attrib"] = _control_plane_attrib(rpc_before)
    finally:
        ray_tpu.shutdown()

    # -- phase D: multi-client task submission (reference `multi_task`:
    # m=4 actor clients each submitting n noop tasks, on a cluster with
    # every core available — the reference baseline ran uncapped) -------
    ray_tpu.init(num_cpus=max(4, ncpu),
                 object_store_memory=256 << 20)
    try:
        @ray_tpu.remote
        def small_value():
            return b"ok"

        @ray_tpu.remote
        class Client:
            def small_value_batch(self, n):
                ray_tpu.get([small_value.remote() for _ in range(n)])
                return 0

        m, calls = 4, 1000
        clients = [Client.remote() for _ in range(m)]
        ray_tpu.get([c.small_value_batch.remote(8) for c in clients])
        rpc_before = _rpc_stats_snapshot()
        n, start = 0, time.perf_counter()
        while time.perf_counter() - start < 4.0:
            ray_tpu.get([c.small_value_batch.remote(calls)
                         for c in clients])
            n += m * calls
        out["multi_client_tasks_async"] = n / (time.perf_counter() - start)
        out["multi_client_tasks_attrib"] = _control_plane_attrib(rpc_before)
    finally:
        ray_tpu.shutdown()
    return out


def bench_serve_llm():
    """Inference-plane phase (ISSUE 9): closed-loop load over the
    continuous-batching engine — `llm_clients` threads each keep one
    request in flight until `llm_requests` complete. Measures request
    throughput, tokens/s/chip and p50/p99 request latency, and holds
    the plane to its two hard gates: ZERO executable-cache retraces in
    steady state (every shape is a warmup-compiled bucket) and ZERO
    leaked KV pages at quiesce. Scale with
    RAY_TPU_SCALE_SIZES=llm_requests=1000000,llm_clients=32 (the
    full-scale artifact run; defaults keep the bench budget on a small
    box and are noted in the detail row)."""
    import statistics

    import jax

    from ray_tpu import parallel
    from ray_tpu.serve.llm import EngineConfig, LLMEngine
    from ray_tpu.util import request_recorder as rr

    ncpu = os.cpu_count() or 1
    scale = _scale_overrides()
    n_requests = scale.get("llm_requests", min(4000, 1000 * ncpu))
    n_clients = scale.get("llm_clients", min(16, 4 * ncpu))
    max_new = 8

    eng = LLMEngine(
        model="llama",
        engine_config=EngineConfig(batch_buckets=(1, 2, 4, 8, 16),
                                   prefill_buckets=(8, 16)),
        seed=0)
    t0 = time.perf_counter()
    eng.warmup()
    warmup_s = time.perf_counter() - t0
    eng.start()

    stats_before = parallel.cache_stats()
    # isolate this run's flight-recorder records (ISSUE 12): the ring
    # keeps the tail of the run; TTFT/TPOT and phase attribution below
    # come from these engine-role records
    rec_was_enabled = rr.enabled()
    rr.set_enabled(True)
    rr.clear()
    prompts = [[3 + (i % 5)] * (1 + i % 8) for i in range(16)]
    latencies = []
    lat_lock = threading.Lock()
    issued = iter(range(n_requests))

    def client(cid):
        mine = []
        while True:
            if next(issued, None) is None:  # GIL-atomic claim
                break
            req = eng.submit(prompts[cid % len(prompts)], max_new)
            req.result(timeout=300)
            mine.append((req.finish_ns - req.submit_ns) / 1e9)
        with lat_lock:
            latencies.extend(mine)

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(n_clients)]
    start = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - start

    eng.quiesce(timeout=60)
    stats_after = parallel.cache_stats()
    m = eng.metrics()
    leaked = eng.shutdown()
    retraces = stats_after["retraces"] - stats_before["retraces"]
    if retraces:
        raise RuntimeError(
            f"{retraces} retraces in steady-state decode")
    if leaked:
        raise RuntimeError(f"{leaked} KV pages leaked at quiesce")

    # -- request-path attribution (ISSUE 12 acceptance gate) -------------
    # Engine records stamp queue -> admission -> prefill -> decode so the
    # phases TILE the request: their sum must reconstruct the measured
    # end-to-end latency to within 5% for the p50 request.
    recs = [r for r in rr.ring().recent()
            if r.role == "engine" and r.outcome == "ok"
            and r.total_ms > 0]
    rr.set_enabled(rec_was_enabled)
    if not recs:
        raise RuntimeError("request recorder captured no engine records")

    def _q(vals, q):
        s = sorted(vals)
        return s[int(q * (len(s) - 1))]

    ratios = [r.phase_sum_ms() / r.total_ms for r in recs]
    p50_ratio = statistics.median(ratios)
    if abs(p50_ratio - 1.0) > 0.05:
        raise RuntimeError(
            "phase attribution broken: median phase-sum/e2e ratio "
            f"{p50_ratio:.3f} outside [0.95, 1.05]")
    ttfts = [r.ttft_ms for r in recs if r.ttft_ms is not None]
    tpots = [r.tpot_ms for r in recs if r.tpot_ms is not None]
    phase_ms = {
        ph: {"p50": round(_q([getattr(r, ph) for r in recs], 0.50), 3),
             "p99": round(_q([getattr(r, ph) for r in recs], 0.99), 3)}
        for ph in rr.PHASES}

    n_done = len(latencies)
    lat_sorted = sorted(latencies)
    chips = max(1, jax.device_count())
    detail = {
        "requests": n_done,
        "clients": n_clients,
        "max_new_tokens": max_new,
        "warmup_s": round(warmup_s, 2),
        "elapsed_s": round(elapsed, 2),
        "latency_p50_ms": round(1e3 * statistics.median(lat_sorted), 2),
        "latency_p99_ms": round(
            1e3 * lat_sorted[int(0.99 * (n_done - 1))], 2),
        "tokens_generated": int(m["tokens_generated"]),
        "prefill_steps": int(m["prefill_steps"]),
        "decode_steps": int(m["decode_steps"]),
        "retraces_steady_state": retraces,
        "kv_pages_leaked": leaked,
        "cache_hits_delta": stats_after["hits"] - stats_before["hits"],
        "full_scale": n_requests >= 1_000_000,
        # flight-recorder attribution (engine-role records, ring tail)
        "recorded_requests": len(recs),
        "ttft_ms_p50": round(_q(ttfts, 0.50), 3) if ttfts else None,
        "ttft_ms_p99": round(_q(ttfts, 0.99), 3) if ttfts else None,
        "tpot_ms_p50": round(_q(tpots, 0.50), 3) if tpots else None,
        "tpot_ms_p99": round(_q(tpots, 0.99), 3) if tpots else None,
        "phase_ms": phase_ms,
        "phase_sum_over_e2e_p50": round(p50_ratio, 4),
    }
    # -- shared-prefix A/B (ISSUE 18) ------------------------------------
    ab = _serve_llm_shared_prefix_ab(scale)
    detail["shared_prefix_ab"] = ab["detail"]
    # -- native-intake sub-phase (ISSUE 19) ------------------------------
    detail["native_intake"] = _serve_llm_native_intake(scale)

    return {
        "serve_llm": detail,
        # value-keyed: the >15% REGRESSION gate watches all three rates
        "serve_llm_requests_per_s": n_done / elapsed,
        "serve_llm_tokens_per_s_per_chip":
            m["tokens_generated"] / elapsed / chips,
        "serve_llm_shared_prefix_tokens_per_s":
            ab["cache_tokens_per_s"],
    }


def _serve_llm_shared_prefix_ab(scale: dict) -> dict:
    """Shared-prefix workload A/B (ISSUE 18): every request carries the
    same long prompt prefix with a private suffix — the RAG /
    system-prompt shape the COW prefix cache exists for. Two arms run
    the IDENTICAL workload in one process:

        base        prefix_cache=0  (the PR-7 engine)
        cache       prefix_cache=1  (COW prefix reuse)

    Greedy determinism makes the two token streams comparable: the
    arms must EMIT identical tokens (asserted), so tokens/s is an
    apples-to-apples rate. Zero retraces and zero leaked pages are hard
    gates in both arms. Shape knobs via RAY_TPU_SCALE_SIZES:
    llm_prefix=96,llm_suffix=16,llm_ab_requests=48,llm_ab_clients=4."""
    import numpy as np

    from ray_tpu import parallel
    from ray_tpu.serve.llm import EngineConfig, LLMEngine
    from ray_tpu.util import request_recorder as rr

    prefix_len = scale.get("llm_prefix", 96)
    suffix_len = scale.get("llm_suffix", 16)
    n_requests = scale.get("llm_ab_requests", 48)
    n_clients = scale.get("llm_ab_clients", 4)
    max_new = 8

    rng = np.random.RandomState(7)
    prefix = [int(t) for t in rng.randint(3, 500, size=prefix_len)]
    prompts = [prefix + [int(t) for t in rng.randint(3, 500,
                                                     size=suffix_len)]
               for _ in range(min(n_requests, 16))]

    # the chunk window matches the suffix: a prefix-cache hit prefills
    # ONLY the private suffix, in one suffix-sized chunk (without it
    # the suffix pads to the widest prefill bucket and the win drowns)
    arms = {
        "base": dict(prefix_cache=0),
        "cache": dict(prefix_cache=1, prefill_chunk=suffix_len),
    }
    out_detail: dict = {
        "prefix_tokens": prefix_len, "suffix_tokens": suffix_len,
        "requests_per_arm": n_requests, "clients": n_clients,
        "max_new_tokens": max_new,
    }
    emitted: dict = {}
    rates: dict = {}
    rec_was_enabled = rr.enabled()
    rr.set_enabled(True)
    for arm, knobs in arms.items():
        eng = LLMEngine(
            model="llama",
            engine_config=EngineConfig(
                batch_buckets=(1, 2, 4),
                prefill_buckets=(16, 32, 64, 128), **knobs),
            seed=0)
        eng.warmup()
        eng.start()
        stats_before = parallel.cache_stats()
        rr.clear()
        results: dict = {}
        res_lock = threading.Lock()
        issued = iter(range(n_requests))

        def client():
            while True:
                i = next(issued, None)  # GIL-atomic claim
                if i is None:
                    break
                req = eng.submit(prompts[i % len(prompts)], max_new)
                toks = req.result(timeout=300)
                with res_lock:
                    results[i % len(prompts)] = toks
        threads = [threading.Thread(target=client, daemon=True)
                   for _ in range(n_clients)]
        start = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - start
        eng.quiesce(timeout=60)
        m = eng.metrics()
        retraces = parallel.cache_stats()["retraces"] - \
            stats_before["retraces"]
        leaked = eng.shutdown()
        if retraces:
            raise RuntimeError(
                f"{arm}: {retraces} retraces in steady state")
        if leaked:
            raise RuntimeError(f"{arm}: {leaked} KV pages leaked")
        emitted[arm] = results
        rates[arm] = m["tokens_generated"] / elapsed

        recs = [r for r in rr.ring().recent()
                if r.role == "engine" and r.outcome == "ok"]
        ttfts = sorted(r.ttft_ms for r in recs
                       if r.ttft_ms is not None)
        tpots = sorted(r.tpot_ms for r in recs
                       if r.tpot_ms is not None)

        def _q(vals, q):
            return round(vals[int(q * (len(vals) - 1))], 3) \
                if vals else None
        arm_detail = {
            "tokens_per_s": round(rates[arm], 2),
            "elapsed_s": round(elapsed, 2),
            "ttft_ms_p50": _q(ttfts, 0.50),
            "ttft_ms_p99": _q(ttfts, 0.99),
            "tpot_ms_p50": _q(tpots, 0.50),
            "tpot_ms_p99": _q(tpots, 0.99),
        }
        if knobs.get("prefix_cache"):
            hit = m["prefix_cache_hit_tokens"]
            miss = m["prefix_cache_miss_tokens"]
            arm_detail["prefix_cache_hit_rate"] = round(
                hit / (hit + miss), 4) if hit + miss else 0.0
            arm_detail["prefix_cache_hit_tokens"] = int(hit)
        out_detail[arm] = arm_detail
    rr.set_enabled(rec_was_enabled)

    # greedy determinism: both arms emit the SAME streams
    if emitted["cache"] != emitted["base"]:
        raise RuntimeError("cache arm diverged from plain greedy output")

    ncpu = os.cpu_count() or 1
    out_detail["speedup_cache"] = round(rates["cache"] / rates["base"], 3)
    out_detail["two_x_target_met"] = rates["cache"] >= 2.0 * rates["base"]
    if not out_detail["two_x_target_met"] and ncpu <= 2:
        # the 2x acceptance target assumes real accelerator decode
        # (prefill FLOPs dominate); on the 1-core CPU box dispatch
        # overhead dominates and caps the cache win — noted, not fatal
        out_detail["note"] = (
            f"{ncpu}-core CPU box: dispatch-bound, 2x target waived "
            "(see README 1-core caveat)")
    return {
        "detail": out_detail,
        "cache_tokens_per_s": rates["cache"],
    }


def _serve_llm_native_intake(scale: dict) -> dict:
    """Native-intake sub-phase (ISSUE 19): the serve.llm zero-Python
    dispatch path in one process — raw token-id request frames enqueued
    through the native ring (mint + deadline + choice in C), the engine
    pump draining them batch-at-a-time ahead of step(), token frames
    flowing back through the client response plane. Gates: recorder
    attribution must survive the native path (engine records carry the
    NATIVELY-minted 16-hex trace ids and their phase sums tile e2e to
    within 5%), the native streams are bit-identical to the same
    engine's direct submit() path (greedy determinism), and the ring's
    inflight counters balance to zero at quiesce."""
    import statistics

    import numpy as np

    from ray_tpu.serve import dispatch as _dispatch
    from ray_tpu.serve.llm import EngineConfig, LLMEngine
    from ray_tpu.util import request_recorder as rr

    if _dispatch._load() is None:
        return {"skipped": "native dispatch library unavailable"}

    n_requests = scale.get("llm_native_requests", 24)
    max_new = 8
    rng = np.random.RandomState(11)
    prompts = [[int(t) for t in rng.randint(3, 500, size=1 + i % 8)]
               for i in range(8)]

    eng = LLMEngine(
        model="llama",
        engine_config=EngineConfig(batch_buckets=(1, 2, 4),
                                   prefill_buckets=(8, 16)),
        seed=0)
    eng.warmup()
    eng.start()
    # reference streams: the ordinary Python submit() path on the SAME
    # engine — greedy decode makes each prompt's stream deterministic
    expect = [eng.submit(p, max_new).result(timeout=300) for p in prompts]

    seg = f"/rtds.bench{os.getpid():x}"
    ring = _dispatch.DispatchRing(seg, table_cap=2, slots=256,
                                  slot_bytes=1024)
    rec_was = rr.enabled()
    rr.set_enabled(True)
    rr.clear()
    try:
        cookie = 0x5eed
        ring.publish(1, [cookie])
        eng.attach_intake(ring, ring.ring_of(cookie), "llm-native")
        plane = _dispatch.ClientPlane.get()
        traces = []
        native: dict = {}
        start = time.perf_counter()
        for i in range(n_requests):
            payload = _dispatch.encode_llm_request(
                prompts[i % len(prompts)], max_new, "bench")
            trace, _rid, _gen = ring.enqueue(payload, client=plane.cookie)
            mailbox = plane.register(trace)
            traces.append(trace)
            toks = []
            while True:
                f = mailbox.q.get(timeout=300)
                if f.tag == _dispatch.TAG_TOKEN:
                    toks.append(_dispatch._LLM_TOK.unpack(f.payload)[1])
                elif f.tag == _dispatch.TAG_DONE:
                    break
                else:
                    raise RuntimeError(
                        f.payload.decode("utf-8", "replace"))
            plane.unregister(trace)
            native[i % len(prompts)] = toks
        elapsed = time.perf_counter() - start
        eng.quiesce(timeout=60)

        for j, toks in native.items():
            if toks != expect[j]:
                raise RuntimeError(
                    "native intake stream diverged from the Python "
                    f"submit() path for prompt {j}")

        # recorder attribution: every native request's engine record is
        # keyed by the natively-minted trace id (16-hex wire format)
        native_ids = {_dispatch.format_trace(t) for t in traces}
        recs = [r for r in rr.ring().recent()
                if r.role == "engine" and r.outcome == "ok"
                and r.total_ms > 0 and r.req_id in native_ids]
        if len(recs) < n_requests:
            raise RuntimeError(
                f"only {len(recs)}/{n_requests} native requests "
                "stitched into engine-role records")
        ratio = statistics.median(
            r.phase_sum_ms() / r.total_ms for r in recs)
        if abs(ratio - 1.0) > 0.05:
            raise RuntimeError(
                "native-path phase attribution broken: median "
                f"phase-sum/e2e ratio {ratio:.3f} outside [0.95, 1.05]")

        _ver, rows = ring.snapshot()
        inflight = sum(row[2] for row in rows)
        if inflight:
            raise RuntimeError(
                f"{inflight} inflight frames leaked at quiesce")
        s = ring.stats()
        tokens = sum(len(t) for t in native.values()) * (
            n_requests // len(prompts))
        return {
            "requests": n_requests,
            "elapsed_s": round(elapsed, 2),
            "tokens_per_s": round(
                n_requests * max_new / elapsed, 2),
            "frames_enqueued": int(s["enqueued"]),
            "frames_per_drain_batch": round(
                s["drained"] / max(1, s["drain_batches"]), 2),
            "recorded_native_requests": len(recs),
            "phase_sum_over_e2e_p50": round(ratio, 4),
            "tokens_checked": tokens,
        }
    finally:
        rr.set_enabled(rec_was)
        eng.shutdown()
        ring.close(unlink=True)


def _dispatch_ring_frames(deployment: str) -> int:
    """Frames natively enqueued for a deployment's dispatch domain (0
    when the domain segment does not exist — the Python-path arm)."""
    from ray_tpu.serve import dispatch as _dispatch

    try:
        ring = _dispatch.DispatchRing(
            _dispatch.domain_segment(deployment), create=False)
    except Exception:  # noqa: BLE001
        return 0
    try:
        return int(ring.stats()["enqueued"])
    finally:
        ring.close()


def bench_serve_dispatch():
    """Dispatch plane v2 A/B (ISSUE 19): the same echo deployment and
    closed-loop clients, once over the native request ring
    (RAY_TPU_NATIVE_DISPATCH=1: mint + deadline + pow-2 choice on raw
    frames in C, Python entered once per batch) and once over the
    Python handle path (flag off — bit-for-bit the pre-PR path, kept as
    the fallback). Gates: the native arm must actually go native (the
    domain ring's frame counter advances), a fixed probe set returns
    bit-identical outputs in both arms, and on a multi-core box the
    native arm clears >=5x the Python-path request rate at p99 parity.
    On a 1-core box both arms timeshare one core with the replicas and
    the controller, so the ring's syscall/pickle wins drown in
    scheduler churn — the 5x target is noted, not fatal (README 1-core
    caveat); the full-scale artifact run proves it on real hardware."""
    import statistics

    import ray_tpu
    from ray_tpu import serve

    scale = _scale_overrides()
    ncpu = os.cpu_count() or 1
    duration = scale.get("dispatch_ab_seconds", 4)
    n_clients = scale.get("dispatch_ab_clients", min(8, 2 * ncpu))
    probe_n = 32

    def run_arm(native: bool) -> dict:
        os.environ["RAY_TPU_NATIVE_DISPATCH"] = "1" if native else "0"
        ray_tpu.init(num_cpus=max(4, ncpu), num_tpus=0,
                     object_store_memory=128 * 1024 * 1024)
        try:
            @serve.deployment(num_replicas=2, max_ongoing_requests=64)
            class DispatchEcho:
                def __call__(self, x):
                    return x * 2

            handle = serve.run(DispatchEcho.bind())
            for i in range(64):  # warm: replicas up, rings attached
                handle.remote(i).result(timeout=60)
            probe = [handle.remote(i).result(timeout=60)
                     for i in range(probe_n)]
            frames0 = _dispatch_ring_frames("DispatchEcho")
            lat: list = []
            lat_lock = threading.Lock()
            stop = time.perf_counter() + duration

            def client():
                mine = []
                while time.perf_counter() < stop:
                    t0 = time.perf_counter()
                    handle.remote(1).result(timeout=60)
                    mine.append(time.perf_counter() - t0)
                with lat_lock:
                    lat.extend(mine)

            threads = [threading.Thread(target=client, daemon=True)
                       for _ in range(n_clients)]
            t_start = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            elapsed = time.perf_counter() - t_start
            frames = _dispatch_ring_frames("DispatchEcho") - frames0
            lat.sort()
            return {
                "probe": probe,
                "requests": len(lat),
                "per_s": len(lat) / elapsed,
                "p50_ms": 1e3 * statistics.median(lat),
                "p99_ms": 1e3 * lat[int(0.99 * (len(lat) - 1))],
                "native_frames": frames,
            }
        finally:
            serve.shutdown()
            ray_tpu.shutdown()
            os.environ.pop("RAY_TPU_NATIVE_DISPATCH", None)

    py = run_arm(native=False)
    nat = run_arm(native=True)

    if nat["probe"] != py["probe"]:
        raise RuntimeError(
            "native and Python dispatch arms returned different outputs")
    if nat["native_frames"] <= 0:
        raise RuntimeError(
            "native arm never used the request ring — the 5x claim "
            "would be vacuous (is the native library building?)")
    if py["native_frames"] != 0:
        raise RuntimeError(
            "Python arm touched the native ring with the flag off")

    speedup = nat["per_s"] / max(1e-9, py["per_s"])
    p99_parity = nat["p99_ms"] <= 1.25 * py["p99_ms"]
    detail = {
        "clients": n_clients,
        "seconds_per_arm": duration,
        "native": {k: round(v, 2) for k, v in nat.items()
                   if k not in ("probe",)},
        "python": {k: round(v, 2) for k, v in py.items()
                   if k not in ("probe",)},
        "speedup": round(speedup, 2),
        "p99_parity": p99_parity,
        "five_x_target_met": speedup >= 5.0 and p99_parity,
    }
    if not detail["five_x_target_met"]:
        if ncpu > 2:
            raise RuntimeError(
                f"native dispatch {speedup:.2f}x vs Python path "
                f"(p99 parity={p99_parity}) — below the 5x-at-parity "
                "acceptance gate")
        detail["note"] = (
            f"{ncpu}-core CPU box: arms timeshare one core with the "
            "replicas, 5x target waived (see README 1-core caveat)")
    return {
        "serve_dispatch": detail,
        # value-keyed: the >15% REGRESSION gate watches both arms, so
        # neither the native path nor the guarded fallback can rot
        "serve_dispatch_native_per_s": nat["per_s"],
        "serve_dispatch_python_per_s": py["per_s"],
    }


def bench_soak():
    """Elastic-recovery soak (ISSUE 10): a wall-clock-budgeted
    continuous-pretraining campaign — streaming ingest -> fold-steps ->
    gang-durable checkpoints on a real multi-raylet cluster — under a
    seeded timed fault schedule spanning every plane (raylet kill +
    autoscaler replacement, GCS heartbeat brownout, checkpoint-persist
    failure, data stall). The recovery ledger measures MTTR per fault
    class and the phase holds the run to its hard gates EVERY time:
    zero non-injected failures, zero resume-accounting mismatches, zero
    batch-watermark violations, every fault recovered. Scale with
    RAY_TPU_SCALE_SIZES=soak_budget_s=600,soak_faults_per_class=2 (the
    >=10-min artifact run; defaults keep the bench budget on a small
    box and are noted in the detail row)."""
    from ray_tpu.soak import SoakConfig, run_soak

    scale = _scale_overrides()
    budget = float(scale.get("soak_budget_s", 90))
    per_class = int(scale.get("soak_faults_per_class",
                              1 if budget < 300 else 2))
    cfg = SoakConfig(
        budget_s=budget,
        mode="cluster",
        seed=1,
        fault_classes=("kill@raylet", "hb_brownout@gcs",
                       "ckpt_fail@train", "data_stall@train",
                       "drop_objects@raylet"),
        faults_per_class=per_class,
    )
    result = run_soak(cfg)
    ledger = result["ledger"]

    # hard gates: a soak whose failures weren't all injected, whose
    # restores don't match the commit ledger, or whose resumed shards
    # replayed/skipped a batch is a FAILED run, not a slow one
    if ledger["non_injected_failures"]:
        raise RuntimeError("non-injected failures during soak: "
                           f"{ledger['non_injected_failures']}")
    if ledger["resume_mismatches"]:
        raise RuntimeError("resume accounting mismatches: "
                           f"{ledger['resume_mismatches']}")
    if result["watermark_errors"]:
        raise RuntimeError("batch-watermark violations: "
                           f"{result['watermark_errors']}")
    if ledger["recovered_count"] < ledger["faults_injected"]:
        raise RuntimeError(
            f"only {ledger['recovered_count']}/"
            f"{ledger['faults_injected']} faults recovered")

    mttrs = sorted(m["mttr_s"] for m in ledger["recoveries"]
                   if m["recovered"])
    p50 = mttrs[int(0.50 * (len(mttrs) - 1))] if mttrs else None
    p95 = mttrs[int(0.95 * (len(mttrs) - 1))] if mttrs else None
    down = ledger["downtime_breakdown_s"]
    avail = 100.0 * (1.0 - down["dead_s"] / result["elapsed_s"])
    detail = {
        "budget_s": budget,
        "elapsed_s": result["elapsed_s"],
        "seed": cfg.seed,
        "fault_classes": len(ledger["mttr_by_class"]),
        "faults_injected": ledger["faults_injected"],
        "recovered": ledger["recovered_count"],
        "attempts": result["attempts"],
        "final_step": result["final_step"],
        "ingest_tokens_per_s": result["ingest_tokens_per_s"],
        "commits": ledger["commits"],
        "restores": ledger["restores"],
        "watermark_checks": result["watermark_checks"],
        "mttr_p50_s": round(p50, 3) if p50 is not None else None,
        "mttr_p95_s": round(p95, 3) if p95 is not None else None,
        "mttr_by_class": ledger["mttr_by_class"],
        "downtime_breakdown_s": down,
        "non_injected_failures": 0,
        "resume_mismatches": 0,
        "full_scale": budget >= 600,
    }
    out = {
        "soak": detail,
        # value-keyed: the >15% REGRESSION gate watches throughput and
        # availability directly; MTTR gates as its inverse (recoveries
        # per second of outage) so a >15% DROP flags MTTR growth
        "soak_steps_per_s": result["steps_per_s"],
        "soak_ingest_tokens_per_s": result["ingest_tokens_per_s"],
        "soak_availability_pct": avail,
    }
    if p95:
        out["soak_recovery_speed_p95_per_s"] = 1.0 / p95
    return out


def bench_reconstruction():
    """Lineage reconstruction (ISSUE 16): when the node holding an
    object's primary copy dies, the owner re-executes the producing
    task from recorded lineage through the normal lease path. Per
    object size (64 KiB -> 64 MiB) the phase pins one task return to a
    victim raylet, kills the raylet, and times the driver's get() until
    the recovered bytes land — death detection is excluded (polled out
    before the timer starts), so small sizes show lease + re-execution
    latency and large ones add the store write — then measures
    sustained recovery rate over a batch of lost objects. Every recovered value is checked bit-identical
    against a local recompute. Scale with
    RAY_TPU_SCALE_SIZES=reconstruction_max_mib=64,reconstruction_batch=32."""
    import numpy as np

    import ray_tpu
    from ray_tpu._private.node import Cluster

    scale = _scale_overrides()
    max_mib = int(scale.get("reconstruction_max_mib", 64))
    batch = int(scale.get("reconstruction_batch", 16))
    sizes = [64 * 1024]
    while sizes[-1] < (max_mib << 20):
        sizes.append(min(sizes[-1] * 8, max_mib << 20))
    # headroom for the largest object + its re-executed copy
    store = max(192 << 20, 3 * (max_mib << 20))

    cluster = None
    curve = []
    try:
        cluster = Cluster(head_resources={"CPU": 2.0},
                          object_store_memory=store)
        ray_tpu.init(address=cluster.gcs_addr)

        @ray_tpu.remote
        def produce(n, mult):
            return (np.arange(n, dtype=np.uint64) * mult).astype(np.uint8)

        def lose_and_time(make_refs):
            """Spin up a victim raylet, pin make_refs(affinity) to it,
            kill it, and time localizing every ref at the driver."""
            victim = cluster.add_node({"CPU": 2.0, "scratch": 1.0},
                                      object_store_memory=store)
            affinity = ray_tpu.NodeAffinitySchedulingStrategy(
                victim.node_id_hex, soft=True)
            refs = make_refs(affinity)
            ready, _ = ray_tpu.wait(refs, num_returns=len(refs),
                                    timeout=180)
            if len(ready) != len(refs):
                raise RuntimeError("producer batch never became ready")
            cluster.remove_node(victim)
            # exclude death-detection latency (heartbeat period x
            # failure threshold — constant per cluster config, already
            # measured by the soak MTTR rows) so the curve shows the
            # re-execute + store-write cost that actually scales with
            # object size
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                if not any(n["Alive"] and
                           n["NodeID"] == victim.node_id_hex
                           for n in ray_tpu.nodes()):
                    break
                time.sleep(0.05)
            start = time.perf_counter()
            vals = ray_tpu.get(refs, timeout=300)
            return time.perf_counter() - start, vals

        for size in sizes:
            elapsed, vals = lose_and_time(
                lambda aff, n=size: [produce.options(
                    scheduling_strategy=aff).remote(n, 7)])
            expect = (np.arange(size, dtype=np.uint64) * 7) \
                .astype(np.uint8)
            if not np.array_equal(vals[0], expect):
                raise RuntimeError(
                    f"reconstructed {size}-byte object not bit-identical")
            del vals, expect
            curve.append({
                "size_bytes": size,
                "latency_ms": round(elapsed * 1e3, 2),
                "mib_per_s": round((size / (1 << 20)) / elapsed, 3),
            })

        small = 256 * 1024
        elapsed, vals = lose_and_time(
            lambda aff: [produce.options(scheduling_strategy=aff)
                         .remote(small, i + 1) for i in range(batch)])
        for i, v in enumerate(vals):
            if int(v[1]) != ((i + 1) & 0xFF):
                raise RuntimeError("batch-recovered object corrupted")
        del vals
        rate = batch / elapsed

        largest = curve[-1]
        return {
            "reconstruction": {
                "sizes": len(curve),
                "curve": curve,
                "batch_objects": batch,
                "batch_object_bytes": small,
                "batch_s": round(elapsed, 3),
            },
            # value-keyed into the >15% REGRESSION gate: both are
            # higher-is-better, so latency growth flags as a drop
            "reconstructions_per_s": rate,
            "reconstruction_mib_per_s": largest["mib_per_s"],
        }
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:  # noqa: BLE001
            pass
        if cluster is not None:
            cluster.shutdown()


# Fairness submitter: one competing tenant. SPREAD tasks take one lease
# each, so the raylet's weighted-fair queue arbitrates EVERY task (the
# default pipelining would drain a whole backlog through one lease and
# hide the queue). Completions are counted by worker-side timestamp
# inside the shared [t0, t0+window] measurement interval — same-machine
# clocks, so no cross-process skew.
_MT_SUBMITTER = """
import json, sys, time
import ray_tpu

addr, weight, t0, window = (sys.argv[1], float(sys.argv[2]),
                            float(sys.argv[3]), float(sys.argv[4]))
ray_tpu.init(address=addr, job_quotas={"weight": weight})

@ray_tpu.remote(scheduling_strategy="SPREAD")
def work():
    import time as _t
    _t.sleep(0.005)
    return _t.time()

late_start = time.time() >= t0
refs = [work.remote() for _ in range(8)]
count = warm = 0
end = t0 + window
while time.time() < end:
    done, refs = ray_tpu.wait(refs, num_returns=1, timeout=30)
    for r in done:
        ts = ray_tpu.get(r)
        if t0 <= ts <= end:
            count += 1
        elif ts < t0:
            warm += 1
        refs.append(work.remote())
print(json.dumps({"job": ray_tpu.get_runtime_context().get_job_id(),
                  "weight": weight, "count": count, "warm": warm,
                  "late_start": late_start}))
ray_tpu.shutdown()
"""

# Overload offender: registers a byte quota at init, waits until the
# raylet has stamped it into the shared arena (the pubsub propagation
# under test), then fires the chaos `quota_flood` fault in-process. The
# flood hammers the CoreWorker-registered put target for the window; the
# store must cap the job at its quota (self-eviction first, then
# SS_QUOTA) without touching any other job's bytes.
_MT_OFFENDER = """
import sys, time
import ray_tpu
from ray_tpu._private import fault_injection as _fi
from ray_tpu._private.worker_api import _require_state

addr, jobfile, quota, flood_s = (sys.argv[1], sys.argv[2],
                                 int(sys.argv[3]), float(sys.argv[4]))
ray_tpu.init(address=addr,
             job_quotas={"weight": 1.0, "object_store_bytes": quota})
cw = _require_state().core_worker
with open(jobfile, "w") as f:
    f.write(cw.job_id.hex())
deadline = time.time() + 30
while time.time() < deadline:
    st = cw.store.job_stats(cw.job_id.binary())
    if st is not None and st["quota"] == quota:
        break
    time.sleep(0.05)
else:
    raise RuntimeError("byte quota never reached the store arena")
plan = _fi.install(_fi.FaultPlan(f"at=0.2:quota_flood:{flood_s}@driver"))
_fi.set_role("driver")  # arm the driver-scoped timed entry
deadline = time.time() + flood_s + 5
while time.time() < deadline and not any(
        s[0] == "timed.quota_flood.done" for s in plan.schedule):
    time.sleep(0.05)
done = [s for s in plan.schedule if s[0] == "timed.quota_flood.done"]
print("FLOOD=" + (done[0][2] if done else "missing"))
ray_tpu.shutdown()
"""


def bench_multitenant():
    """Multi-tenant isolation (ISSUE 11): three competing jobs with
    fair-share weights 1/2/4 submit backlogged SPREAD tasks against one
    1-CPU cluster — per-job throughput shares must land within 10%
    (relative) of the weight ratio. Then a quota-flood variant: an
    offender job with a byte quota floods the shared object store via
    the `quota_flood` chaos fault while the head job probes put latency
    — the offender stays capped at its quota, zero bytes are evicted
    from any other job, and the victim's put p99 regresses <15% vs its
    pre-flood window. Scale with RAY_TPU_SCALE_SIZES=
    mt_window_s=30,mt_flood_s=10 for the full artifact run."""
    import subprocess
    import sys
    import tempfile

    import ray_tpu
    from ray_tpu._private.ids import ObjectID
    from ray_tpu._private.worker_api import _require_state
    from ray_tpu.util import state as state_api

    scale = _scale_overrides()
    window = float(scale.get("mt_window_s", 10))
    warmup = float(scale.get("mt_warmup_s", 10))
    flood_s = float(scale.get("mt_flood_s", 4))
    quota = int(scale.get("mt_quota_mb", 8)) * 1024 * 1024
    weights = (1.0, 2.0, 4.0)

    ray_tpu.init(num_cpus=1, num_tpus=0,
                 object_store_memory=128 * 1024 * 1024,
                 job_quotas={"weight": 1.0})
    try:
        from ray_tpu._private import worker_api

        gcs_addr = worker_api._global_state.cluster.gcs_addr
        cw = _require_state().core_worker
        store = cw.store
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        here = os.path.dirname(os.path.abspath(__file__))

        # -- phase 1: weighted-fair throughput shares -------------------
        t0 = time.time() + warmup
        procs = [
            subprocess.Popen(
                [sys.executable, "-c", _MT_SUBMITTER, gcs_addr, str(w),
                 str(t0), str(window)],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True, cwd=here, env=env)
            for w in weights
        ]
        tenants = []
        for p in procs:
            out, err = p.communicate(timeout=warmup + window + 120)
            if p.returncode != 0:
                raise RuntimeError(f"submitter failed: {err[-500:]}")
            tenants.append(json.loads(out.strip().splitlines()[-1]))
        total = sum(t["count"] for t in tenants)
        total_w = sum(weights)
        if total < 20 * len(weights):
            raise RuntimeError(
                f"undersampled fairness window: {total} grants")
        fairness = []
        worst = 0.0
        for t in tenants:
            expected = t["weight"] / total_w
            share = t["count"] / total
            rel_err = abs(share / expected - 1.0)
            worst = max(worst, rel_err)
            fairness.append({
                "job": t["job"][:8], "weight": t["weight"],
                "tasks": t["count"], "warmup_tasks": t["warm"],
                "share": round(share, 4),
                "expected_share": round(expected, 4),
                "rel_err": round(rel_err, 4),
            })
        if worst > 0.10:
            raise RuntimeError(
                "fairness: share deviates >10% from weight: "
                f"{fairness}")

        # -- phase 2: quota-flood containment ---------------------------
        def put_p99(n):
            # victim probe: 64 KiB put+delete round trips on the shared
            # arena, p99 over the window
            lat = []
            payload = b"\x00" * 65536
            for _ in range(n):
                oid = ObjectID.from_random()
                t = time.perf_counter()
                store.put_value(oid, payload)
                lat.append(time.perf_counter() - t)
                store.delete(oid)
            lat.sort()
            return lat[int(0.99 * (len(lat) - 1))], len(lat)

        base_p99, base_n = put_p99(400)
        victim_before = store.job_stats(cw.job_id.binary()) or {}

        jobfile = tempfile.mktemp(prefix="ray_tpu_mt_job_")
        offender = subprocess.Popen(
            [sys.executable, "-c", _MT_OFFENDER, gcs_addr, jobfile,
             str(quota), str(flood_s)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, cwd=here, env=env)
        deadline = time.time() + 30
        offender_job = None
        while time.time() < deadline and offender_job is None:
            try:
                with open(jobfile) as f:
                    offender_job = bytes.fromhex(f.read().strip())
            except (OSError, ValueError):
                time.sleep(0.05)
        if offender_job is None:
            offender.kill()
            raise RuntimeError("offender never registered its job")

        # probe while the flood runs, sampling the offender's usage
        max_used = 0
        flood_lat = []
        payload = b"\x00" * 65536
        end = time.time() + flood_s + 1.0
        while time.time() < end:
            oid = ObjectID.from_random()
            t = time.perf_counter()
            store.put_value(oid, payload)
            flood_lat.append(time.perf_counter() - t)
            store.delete(oid)
            st = store.job_stats(offender_job)
            if st is not None:
                max_used = max(max_used, st["used"])
        out, err = offender.communicate(timeout=flood_s + 60)
        if offender.returncode != 0:
            raise RuntimeError(f"offender failed: {err[-500:]}")
        flood_line = [ln for ln in out.splitlines()
                      if ln.startswith("FLOOD=")][0]
        try:
            os.unlink(jobfile)
        except OSError:
            pass

        flood_lat.sort()
        flood_p99 = flood_lat[int(0.99 * (len(flood_lat) - 1))]
        off_stats = store.job_stats(offender_job) or {}
        victim_after = store.job_stats(cw.job_id.binary()) or {}

        # hard gates: containment must hold EVERY run, not on average.
        # The store reserves `used` with a fetch_add BEFORE admission
        # (check-and-reserve is one RMW), so a concurrent sample may
        # read up to one in-flight reservation over quota while a
        # create is inside its self-evict/recheck window; the quiesced
        # value is the strict cap.
        slack = 128 * 1024  # one aligned 64 KiB flood frame in flight
        if max_used > quota + slack:
            raise RuntimeError(
                f"offender exceeded its byte quota: {max_used} > {quota}")
        if off_stats.get("used", 0) > quota:
            raise RuntimeError(
                "offender over quota at quiesce: "
                f"{off_stats.get('used')} > {quota}")
        if off_stats.get("evicted_bytes", 0) + \
                off_stats.get("quota_rejects", 0) <= 0:
            raise RuntimeError(
                f"flood never hit the quota boundary: {off_stats}")
        if victim_after.get("evicted_bytes", 0) != \
                victim_before.get("evicted_bytes", 0):
            raise RuntimeError(
                "cross-job eviction: victim bytes were reclaimed for "
                f"the offender: {victim_before} -> {victim_after}")
        # latency floor guards micro-noise on sub-ms p99s
        p99_floor = max(base_p99, 0.0005)
        if flood_p99 > 1.15 * p99_floor:
            raise RuntimeError(
                f"victim put p99 regressed >15% under flood: "
                f"{base_p99 * 1e3:.3f}ms -> {flood_p99 * 1e3:.3f}ms")

        # per-job accounting rows as the dashboard /api/jobs serves them
        job_rows = []
        for jb in state_api.list_jobs():
            job_rows.append({
                "job_id": jb["job_id"][:8],
                "quotas": jb.get("quotas"),
                "finished": jb["finished"],
                "object_store": store.job_stats(
                    bytes.fromhex(jb["job_id"])),
            })

        detail = {
            "window_s": window,
            "tenants": fairness,
            "fairness_worst_rel_err": round(worst, 4),
            "flood": {
                "quota_bytes": quota,
                "flood_s": flood_s,
                "result": flood_line.split("=", 1)[1],
                "offender_max_used": max_used,
                "offender_stats": off_stats,
                "victim_put_p99_ms_base": round(base_p99 * 1e3, 3),
                "victim_put_p99_ms_flood": round(flood_p99 * 1e3, 3),
                "victim_probe_puts": base_n + len(flood_lat),
                "victim_evicted_bytes": victim_after.get(
                    "evicted_bytes", 0),
            },
            "jobs": job_rows,
            "full_scale": window >= 30,
        }
        return {
            "multitenant": detail,
            # value-keyed: the >15% REGRESSION gate watches the fairness
            # score (1.0 = shares exactly track weights), aggregate
            # fair-queue throughput, and victim put speed under flood
            # (1/p99 — a drop flags p99 growth)
            "multitenant_fairness_score": round(1.0 - worst, 4),
            "multitenant_tasks_per_s": round(total / window, 2),
            "multitenant_victim_put_speed_under_flood_per_s":
                round(1.0 / flood_p99, 1),
        }
    finally:
        ray_tpu.shutdown()


def _fold(suite: dict, result: dict, digits: int,
          only_floats: bool = False) -> None:
    """Merge a phase's rows into the suite: scalars become value rows
    (the REGRESSION gate's shape), detail dicts ride along as they are."""
    for k, v in result.items():
        wrap = isinstance(v, float) if only_floats \
            else not isinstance(v, dict)
        suite[k] = {
            "value": round(v, digits),
            "vs_baseline": round(v / BASELINES[k], 3)
            if k in BASELINES else None,
        } if wrap else v


# name -> (function, seconds of budget that must remain to start it,
# digits its scalar rows are rounded to; None: one row under its name).
# Order is run order; the headline phase has no gate.
PHASES = {
    "gpt2_125m_train": (bench_gpt2_tokens_per_sec, 0, None),
    "llama_125m_train": (bench_llama_tokens_per_sec, 240, None),
    "gpt2_long_context_4096": (bench_gpt2_long_context, 240, None),
    "pipeline_bubble": (bench_pipeline_bubble, 120, None),
    # its fit feeds on the pipeline phase's result
    "dispatch_overhead": (bench_dispatch_overhead, 60, 2),
    "observability_overhead": (bench_observability_overhead, 45, 2),
    "rpc_fanin": (bench_rpc_fanin, 120, 2),
    "control_plane": (bench_control_plane, 120, 2),
    "scale_envelope": (bench_scale_envelope, 90, 2),
    "serve_llm": (bench_serve_llm, 60, 2),
    "serve_dispatch": (bench_serve_dispatch, 60, 2),
    "soak": (bench_soak, 150, 3),
    "reconstruction": (bench_reconstruction, 90, 3),
    "multitenant": (bench_multitenant, 90, 3),
}


def _run_phase(name: str, arg=None) -> dict:
    """One phase in a process of its own; raises with the end of its
    stderr when it fails."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", name]
    if arg is not None:
        cmd += ["--input", json.dumps(arg)]
    proc = subprocess.run(cmd, capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(f"phase {name} exited {proc.returncode}: "
                           + proc.stderr.strip()[-300:])
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main() -> int:
    suite = {}
    failed = []
    started = time.perf_counter()
    # secondary phases are skipped once the soft budget is spent (each
    # TPU bench costs a 1-3 min compile)
    budget = float(os.environ.get("RAY_TPU_BENCH_BUDGET_S", "900"))

    for name, (_fn, needs_s, digits) in PHASES.items():
        if budget - (time.perf_counter() - started) <= needs_s:
            suite[name] = {"skipped": "budget"}
            continue
        try:
            result = _run_phase(
                name, suite.get("pipeline_bubble")
                if name == "dispatch_overhead" else None)
        except Exception as e:  # noqa: BLE001 — recorded; exit code says so
            failed.append(name)
            if digits is None:
                suite[name] = {"error": repr(e)[:400]}
            else:
                suite[f"{name}_error"] = repr(e)[:400]
            continue
        if digits is None:
            suite[name] = result
        else:
            _fold(suite, result, digits,
                  only_floats=name == "scale_envelope")

    gpt2 = suite["gpt2_125m_train"]
    headline = {
        "metric": "gpt2_125m_tokens_per_sec_per_chip",
        "value": gpt2.get("tokens_per_sec_per_chip"),
        "unit": "tokens/s",
        "vs_baseline": gpt2.get("vs_baseline"),
        "mfu": gpt2.get("mfu"),
        "failed_phases": failed,
    }
    headline["host"] = _host_metadata()
    # self-comparison gate BEFORE this run is written as the new
    # baseline: any suite metric down >15% vs the latest BENCH_r*.json
    # prints a REGRESSION block and rides along in the artifact
    regressions = _check_regressions(suite)
    if regressions:
        headline["regressions"] = regressions
    headline["suite"] = suite
    print(json.dumps(headline))
    return 1 if failed else 0


def _phase_main(name: str, arg) -> None:
    fn = PHASES[name][0]
    print(json.dumps(fn() if arg is None else fn(arg)))


if __name__ == "__main__":
    import argparse

    parser = argparse.ArgumentParser()
    parser.add_argument("--phase", choices=sorted(PHASES))
    parser.add_argument("--input", type=json.loads, default=None)
    cli = parser.parse_args()
    if cli.phase:
        _phase_main(cli.phase, cli.input)
    else:
        sys.exit(main())
