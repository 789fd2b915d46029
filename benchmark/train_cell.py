"""The training cells: what runs inside the TrainWorker that
`train.JaxTrainer(...).fit()` starts, and the driver's side of it.

The worker holds the chip, so everything that needs jax (the step, the fence,
the profiler, `memory_stats`, the comparison with the plain reference) happens
in `train_loop`; it hands one dict of observations back through
`train.report`. The driver's process never imports jax.
"""

from __future__ import annotations

import math
import os
import shutil
import tempfile
import time


def fold_seed(seed: int) -> int:
    """Any whole number to what `jax.random.PRNGKey` takes everywhere."""
    return (seed ^ (seed >> 31)) & 0x7FFFFFFF


def build_job(config: dict, traffic: dict, devices) -> dict:
    """The model, the step and how the carry and batches are placed, from
    the configuration and traffic files alone. The configuration's `builder`
    names the function (`module:function` under `benchmark/`), so a new
    family comes as a file of its own. It returns a dict of pieces: `step`,
    `make_carry`, `loss_of`, `block0_of` (the first block's subtree of an
    unboxed tree of parameters or gradients), `place`, `batch`, `seq`, and
    `mesh`, `rules`, `shardings` (None on one chip)."""
    from benchmark.readers import resolve

    return resolve(config["builder"])(config, traffic, devices)


def gpt2_job(config: dict, traffic: dict, devices) -> dict:
    """`models/gpt.py` at the file's GPT-2 keys, adamw 3e-4."""
    from functools import partial

    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import GPT, GPTConfig
    from ray_tpu.models.gpt import cross_entropy_loss
    from ray_tpu.ops import flash_attention, fused_cross_entropy
    from ray_tpu.parallel import ShardingStrategy, logical_axis_rules
    from ray_tpu.parallel.sharding import param_shardings, shard_batch

    job = config["train"]
    batch, seq = traffic["batch"], traffic["seq_len"]
    cfg = GPTConfig(
        vocab_size=config["assumed"]["vocab_rows"],
        n_layer=config["n_layer"], n_head=config["n_head"],
        d_model=config["n_embd"], max_seq_len=config["n_positions"],
        remat=job["remat"])
    flash = job["attention"] == "flash"
    model = GPT(cfg, attention_fn=partial(flash_attention, causal=True)
                if flash else None)
    tx = optax.adamw(3e-4)

    def loss_of(params, inputs, targets):
        if job["loss"] == "fused_cross_entropy":
            hidden, wte = model.apply(params, inputs, return_hidden=True)
            return fused_cross_entropy(hidden, wte, targets)
        return cross_entropy_loss(model.apply(params, inputs), targets)

    def make_carry(key):
        params = model.init(key, jnp.zeros((batch, seq), jnp.int32))
        return params, tx.init(params)

    mesh = rules = shardings = None
    if job["sharding"]:
        strategy = ShardingStrategy(**job["sharding"])
        mesh = strategy.build_mesh(list(devices))
        rules = logical_axis_rules(strategy)
        with mesh, nn.logical_axis_rules(rules):
            shardings = param_shardings(
                mesh, jax.eval_shape(make_carry, jax.random.PRNGKey(0)),
                rules)

        def place(batch_np):
            return shard_batch(batch_np, mesh, strategy)
    else:
        def place(batch_np):
            return batch_np  # numpy: the program puts it on the chip

    def step(carry, data):
        params, opt_state = carry
        loss, grads = jax.value_and_grad(loss_of)(params, *data)
        updates, opt_state = tx.update(grads, opt_state, params)
        carry = (optax.apply_updates(params, updates), opt_state)
        if shardings is not None:
            carry = jax.lax.with_sharding_constraint(carry, shardings)
        return carry, loss

    return {"cfg": cfg, "model": model, "loss_of": loss_of, "step": step,
            "block0_of": lambda tree: tree["params"]["h0"],
            "make_carry": make_carry, "mesh": mesh, "rules": rules,
            "shardings": shardings, "place": place, "batch": batch,
            "seq": seq}


def _batches(seed: int, vocab: int, batch: int, seq: int, place):
    """Host-side iterator: a fresh seeded batch for every step."""
    import numpy as np

    step = 0
    while True:
        rng = np.random.default_rng([seed, step])
        tokens = rng.integers(0, vocab, (batch, seq + 1), dtype=np.int32)
        yield place((np.ascontiguousarray(tokens[:, :-1]),
                     np.ascontiguousarray(tokens[:, 1:])))
        step += 1


def _check(job, carry, config, traffic, seed, losses, devices):
    """`correct`, decided outside the window on the cell's own weights."""
    import contextlib
    import importlib

    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np

    failures = []
    vocab = config["vocab_size"]
    ln_v = math.log(vocab)
    # fresh random tokens cannot be learned: the loss starts at ln(rows) and
    # may only drift down to the entropy of uniform tokens, ln(vocab), give
    # or take the noise of a batch (a few hundredths) -- 5% covers both
    if not all(math.isfinite(x) for x in losses):
        failures.append("non-finite loss in the window")
    elif max(abs(x - ln_v) for x in losses) > 0.05 * ln_v:
        failures.append(f"a loss in the window left ln(vocab)={ln_v:.3f} "
                        f"by more than 5%: min {min(losses):.3f} "
                        f"max {max(losses):.3f}")
    n, seq = traffic["check_sequences"], traffic["seq_len"]
    rng = np.random.default_rng([seed, 1 << 40])
    tokens = rng.integers(0, vocab, (n, seq + 1), dtype=np.int32)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    params = carry[0]

    def norm(tree):
        return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                            for x in jax.tree_util.tree_leaves(tree)))

    def program(params, inputs, targets):
        loss, grads = jax.value_and_grad(job["loss_of"])(
            params, inputs, targets)
        return loss, norm(job["block0_of"](nn.meta.unbox(grads)))

    scope = contextlib.ExitStack()
    if job["mesh"] is not None:
        scope.enter_context(job["mesh"])
        scope.enter_context(nn.logical_axis_rules(job["rules"]))
    with scope:
        got_loss, got_norm = jax.jit(program)(params, inputs, targets)
    ref = importlib.import_module(
        "benchmark." + config["reference"][:-3].replace("/", "."))
    plain = jax.device_get(nn.meta.unbox(params)["params"])
    with jax.default_device(devices[0]), \
            jax.default_matmul_precision("highest"):
        want_loss, want_norm = ref.loss_and_block0_grad_norm(
            plain, config, jnp.asarray(inputs), jnp.asarray(targets))
    got_loss, got_norm = float(got_loss), float(got_norm)
    want_loss, want_norm = float(want_loss), float(want_norm)
    # bf16 activations against float32 on the same weights: over the chip
    # runs of PR 23 the loss (~10.9) differed by 2e-6 to 4e-5 of itself and
    # one block's gradient norm by 0.03% to 0.7% (the widest on four chips).
    # The tolerances leave a factor of ten and of four; an 8-bit float or
    # int8 path keeps 3-4 bits and misses both.
    if abs(got_loss - want_loss) > 5e-4 * abs(want_loss):
        failures.append(f"loss {got_loss:.5f} vs reference {want_loss:.5f} "
                        f"(tolerance 0.05%)")
    if abs(got_norm - want_norm) > 2.5e-2 * abs(want_norm):
        failures.append(f"block-0 gradient norm {got_norm:.5g} vs reference "
                        f"{want_norm:.5g} (tolerance 2.5%)")
    return failures, {"loss": got_loss, "ref_loss": want_loss,
                      "grad_norm": got_norm, "ref_grad_norm": want_norm}


def train_loop(loop_config):
    """Runs in the worker. Warm-up, the window, then what only this process
    can see: the device, its memory, the trace, `correct`."""
    import jax

    from benchmark import stage
    from benchmark.device_memory import PeakSampler, over_limit
    from ray_tpu import parallel, train
    from ray_tpu.util import step_profiler

    stage.write_to(loop_config["stage_file"])
    stage.enter("setup")
    config, traffic = loop_config["config"], loop_config["traffic"]
    seed, seconds = loop_config["seed"], loop_config["seconds"]
    devices = jax.devices()
    ready_wall = time.time()
    dev = devices[0]
    obs = {"platform": dev.platform, "device_kind": dev.device_kind,
           "count": len(devices), "worker_ready_wall": ready_wall,
           "failures": []}
    if loop_config["require_tpu"] and (
            dev.platform != "tpu" or len(devices) != config["chips"]):
        obs["failures"].append(
            f"needs {config['chips']} TPU chip(s); the worker sees "
            f"{len(devices)} x {dev.platform}")
        train.report({"bench": obs})
        return
    memory = PeakSampler(devices)
    job = build_job(config, traffic, devices[:config["chips"]])
    key = jax.random.PRNGKey(fold_seed(seed))
    if job["mesh"] is not None:
        import flax.linen as nn
        with job["mesh"], nn.logical_axis_rules(job["rules"]):
            carry = jax.jit(job["make_carry"],
                            out_shardings=job["shardings"])(key)
    else:
        carry = jax.jit(job["make_carry"])(key)
    runner = train.TrainStepRunner(
        job["step"], mesh=job["mesh"], on_retrace="error",
        tokens_per_step=job["batch"] * job["seq"])
    batches = _batches(seed, config["vocab_size"], job["batch"], job["seq"],
                       job["place"])
    for _ in range(traffic["warmup_steps"]):
        carry, loss = runner.run(carry, batches)
    jax.block_until_ready((carry, loss))
    stats0 = parallel.cache_stats()
    step_profiler.clear()
    losses, step_ms = [], []
    taker = _slice_taker(losses, seconds) if loop_config["trace"] else None
    stage.enter("window")
    t_open_wall = time.time()
    t0 = time.perf_counter()
    while True:
        if taker is not None:
            # between steps: opens the slice, or fences the last step and
            # ends it, or looks whether its check has come back
            taker.poll(time.perf_counter() - t0)
        t1 = time.perf_counter()
        if t1 - t0 >= seconds:
            break
        carry, loss = runner.run(carry, batches)
        losses.append(loss)
        step_ms.append((time.perf_counter() - t1) * 1e3)
    if taker is not None:
        taker.stop_open_slice()
    jax.block_until_ready((carry, loss))     # the fence on the last step
    window_s = time.perf_counter() - t0

    stats1 = parallel.cache_stats()
    rows = step_profiler.recent()
    obs["memory"] = memory.stop()
    obs.update({
        "window_open_wall": t_open_wall, "window_s": window_s,
        "steps": len(losses),
        "tokens": len(losses) * job["batch"] * job["seq"],
        "tokens_per_step": job["batch"] * job["seq"],
        "step_ms": step_ms,
        "host_dispatch_ms": [r["host_dispatch_ms"] for r in rows],
        "data_wait_ms": [r["data_wait_ms"] for r in rows],
        "compiles_in_window": (stats1["misses"] - stats0["misses"])
        + (stats1["retraces"] - stats0["retraces"]),
        "cache_stats": stats1,
        "memory_peak_bytes": obs["memory"]["memory_peak_bytes"],
    })
    if taker is not None:
        # the worker held the session; the reduction is the driver's, in a
        # child, once `fit()` has returned (`run_train_cell`)
        obs["trace_taken"] = taker.close(window_s)
    stage.enter("check")
    losses = [float(x) for x in losses]
    obs["loss_first"], obs["loss_last"] = losses[0], losses[-1]
    failures, obs["check"] = _check(job, carry, config, traffic, seed,
                                    losses, devices)
    if stats1["retraces"]:
        failures.append(f"the executable cache retraced: {stats1}")
    if obs["compiles_in_window"]:
        failures.append(f"{obs['compiles_in_window']} program(s) compiled "
                        f"inside the window")
    failures += over_limit(obs["memory"])
    if job["mesh"] is not None:
        failures += _check_spread(carry)
    obs["failures"] += failures
    train.report({"bench": obs})


def _slice_taker(losses: list, seconds: float):
    """The traced slice of a training cell: the worker holds the session,
    so it starts and stops it between steps; the stop fences the last step
    so that the slice ends on whole steps."""
    import jax

    from benchmark import stage, trace_reduce

    dirs = []

    def start():
        dirs.append(tempfile.mkdtemp(prefix="bench_trace_"))
        trace_reduce.start_session(dirs[-1])

    def stop():
        stage.enter("trace_stop")
        jax.block_until_ready(losses[-1])
        trace_reduce.stop_session()
        stage.enter("window")
        return dirs[-1]

    return trace_reduce.SliceTaker(start, stop, seconds, serving=False)


def _check_spread(carry) -> list:
    """Four chips: arrays whose spec names a mesh axis really are split."""
    import jax

    bad = []
    for path, leaf in jax.tree_util.tree_leaves_with_path(carry):
        split = any(axis is not None for axis in leaf.sharding.spec)
        if split and leaf.addressable_shards[0].data.shape == leaf.shape:
            bad.append(jax.tree_util.keystr(path))
    return [f"whole on one device though sharded by spec: {bad[:4]}"] \
        if bad else []


def run_train_cell(cell: dict, config: dict, traffic: dict, seed: int,
                   seconds: float, trace: bool, t_start_wall: float,
                   require_tpu: bool = True) -> dict:
    """The driver's side: the cluster, the trainer, one fit()."""
    import ray_tpu
    from ray_tpu import train
    from ray_tpu.air.config import RunConfig, ScalingConfig

    from benchmark import stage

    storage = tempfile.mkdtemp(prefix="bench_train_")
    # the worker's stage, for a failed run's last line; kept when it fails
    stage_file = os.path.join(tempfile.gettempdir(),
                              f"bench_stage_{os.getpid()}")
    stage.read_from(stage_file)
    stage.enter("setup")
    ray_tpu.init()
    try:
        result = train.JaxTrainer(
            train_loop,
            train_loop_config={
                "config": config, "traffic": traffic, "seed": seed,
                "seconds": seconds, "trace": trace,
                "require_tpu": require_tpu, "stage_file": stage_file},
            scaling_config=ScalingConfig(
                num_workers=1, use_tpu=require_tpu,
                tpus_per_worker=cell["chips"] if require_tpu else 0),
            run_config=RunConfig(storage_path=storage, name="bench"),
        ).fit()
    finally:
        ray_tpu.shutdown()
        shutil.rmtree(storage, ignore_errors=True)
    stage.enter("shutdown")
    stage.read_from(None)
    if os.path.exists(stage_file):
        os.remove(stage_file)
    obs = result.metrics["bench"]
    taken = obs.pop("trace_taken", None)
    if taken is not None:
        from benchmark import trace_reduce
        trace_reduce.reduce_taken(obs, taken, serving=False)
    if "window_open_wall" in obs:
        obs["setup_s"] = obs["window_open_wall"] - t_start_wall
        obs["worker_ready_s"] = obs["worker_ready_wall"] - t_start_wall
    return obs
