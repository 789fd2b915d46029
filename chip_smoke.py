"""chip_smoke.py — the quickest proof that ray_tpu still starts on the chip.

    python chip_smoke.py             # one chip: a train phase, a serve phase
    python chip_smoke.py --chips 4   # one four-chip host: the sharded step
                                     # and four one-chip replicas, nothing else

Every phase goes through the entry points a user calls: `ray_tpu.init()`,
`train.JaxTrainer(...).fit()` with `train.TrainStepRunner` in the loop, and
`serve.run(serve.llm.build_app(...))` with streamed `generate` calls. This
process never imports jax — a process that has touched jax holds the chip and
a worker that needs it then fails or hangs — so the device is asked for in a
short child first, and a machine without a TPU is refused within seconds.

Output: one JSON object per phase, then as the LAST line
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}`.
Any failed check, or no TPU, prints `"ok": false` there and exits non-zero.
Sizes are the real ones (GPT-2-125M at 16 x 1024 in bf16 through the Pallas
flash kernel and `fused_cross_entropy`; `llama_125m` in bf16 behind the
router); weights and tokens are random, made from `--seed`.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import glob
import json
import math
import os
import random
import re
import shutil
import subprocess
import sys
import tempfile
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))

TRAIN = {"preset": "gpt2_125m", "batch": 16, "seq": 1024, "steps": 6}
SERVE = {
    "model_config": {"n_layer": 12, "n_head": 12, "n_kv_head": 4,
                     "d_model": 768, "vocab_size": 32000,
                     "max_seq_len": 2048},
    # six programs to compile (two prefill buckets, the chunk, three decode
    # batches): a cold warm-up stays well under the controller's start-up
    # grace; prompts longer than 128 tokens go through chunked prefill
    "engine_config": {"batch_buckets": (1, 4, 8),
                      "prefill_buckets": (32, 128), "prefill_chunk": 128},
    "prompt_lens": (5, 23, 100, 300),
    "max_new_tokens": 16,
}

_PROBE = ("import json, jax; d = jax.devices(); print(json.dumps({"
          "'platform': d[0].platform, 'kind': d[0].device_kind, "
          "'count': len(d)}))")


def probe_device() -> dict:
    """What jax finds, asked in a child so this process stays off the chip."""
    out = subprocess.run([sys.executable, "-c", _PROBE], check=True,
                         capture_output=True, text=True, timeout=300)
    return json.loads(out.stdout.strip().splitlines()[-1])


def build_native() -> None:
    """The native libraries come from what git tracks: the loader trusts an
    existing .so by mtime, and a copied tree can carry a stale one."""
    subprocess.run(["make", "-s", "-C", os.path.join(REPO, "ray_tpu", "native"),
                    "clean", "all"], check=True)


# ---------------------------------------------------------------------------
# train: runs inside the TrainWorker the trainer starts
# ---------------------------------------------------------------------------

def _device_failures(dev, n_devices: int, want_devices: int) -> list:
    out = []
    if dev.platform != "tpu":
        out.append(f"worker computes on {dev.platform!r}, not the TPU")
    if n_devices != want_devices:
        out.append(f"worker sees {n_devices} devices, wants {want_devices}")
    return out


def train_loop(config):
    """GPT-2 through the single-chip hot path: flash kernel + fused CE +
    adamw inside TrainStepRunner, a few steps on one repeated seeded batch."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu import parallel, train
    from ray_tpu.models import GPT, GPTConfig
    from ray_tpu.models.gpt import flops_per_token
    from ray_tpu.ops import flash_attention, fused_cross_entropy
    from ray_tpu.ops.flash_attention import path_calls
    from ray_tpu.parallel.ring_attention import full_attention

    batch, seq, seed = config["batch"], config["seq"], config["seed"]
    dev = jax.devices()[0]
    failures = _device_failures(dev, jax.device_count(), 1)
    cfg = getattr(GPTConfig, config["preset"])(remat=False, max_seq_len=seq)
    model = GPT(cfg, attention_fn=partial(flash_attention, causal=True))
    tokens = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch, seq + 1), dtype=np.int32)
    data = (jnp.asarray(tokens[:, :-1]), jnp.asarray(tokens[:, 1:]))
    params = jax.jit(model.init)(jax.random.PRNGKey(seed), data[0])
    tx = optax.adamw(3e-4)
    carry = (params, tx.init(params))

    def step(carry, batch):
        params, opt_state = carry
        inputs, targets = batch

        def loss_fn(p):
            hidden, wte = model.apply(p, inputs, return_hidden=True)
            return fused_cross_entropy(hidden, wte, targets)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state), loss

    has_kernel = "tpu_custom_call" in jax.jit(step).lower(carry, data).as_text()
    runner = train.TrainStepRunner(
        step, on_retrace="error", tokens_per_step=batch * seq,
        flops_per_step=flops_per_token(cfg, seq) * batch * seq)
    losses, step_s = [], []
    for _ in range(config["steps"]):
        t0 = time.perf_counter()
        carry, loss = runner.run(carry, data)
        losses.append(float(loss))  # the host needs the value: a fence
        step_s.append(round(time.perf_counter() - t0, 4))
    paths = path_calls()

    # the compiled kernel against the dense reference at the step's own
    # attention shape (interpret mode is what the unit tests compare)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    shape = (batch, seq, cfg.n_head, cfg.d_model // cfg.n_head)
    q, k, v = (jax.random.normal(key, shape, jnp.bfloat16)
               for key in (kq, kk, kv))
    got = jax.jit(partial(flash_attention, causal=True))(q, k, v)
    want = jax.jit(partial(full_attention, causal=True))(q, k, v)
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    # bf16 keeps 8 bits: a few units in the last place of either output,
    # absolute below 1 and relative above it
    attn_err = float(jnp.max(jnp.abs(got - want) / (1 + jnp.abs(want))))

    stats = parallel.cache_stats()
    ln_v = math.log(cfg.vocab_size)
    if not has_kernel:
        failures.append("no tpu_custom_call in the lowered train step")
    if paths["dense"] or not paths["pallas"]:
        failures.append(f"flash_attention took the dense path: {paths}")
    if not all(math.isfinite(x) for x in losses):
        failures.append(f"non-finite loss: {losses}")
    if abs(losses[0] - ln_v) > 0.03 * ln_v:
        failures.append(f"step-0 loss {losses[0]:.4f} is not within 3% of "
                        f"ln(vocab) = {ln_v:.4f}")
    if not losses[-1] < losses[0]:
        failures.append(f"loss did not fall on the repeated batch: {losses}")
    if stats["retraces"]:
        failures.append(f"executable cache retraced: {stats}")
    if not attn_err <= 2e-2:
        failures.append(f"flash kernel differs from full_attention by "
                        f"{attn_err:.4g} of 1 + |reference| at {shape} "
                        f"(bf16 tolerance 2e-2)")
    train.report({"smoke": {
        "platform": dev.platform, "device_kind": dev.device_kind,
        "model": config["preset"], "params": int(sum(
            x.size for x in jax.tree_util.tree_leaves(carry[0]))),
        "batch": batch, "seq": seq, "dtype": str(jnp.dtype(cfg.dtype)),
        "losses": [round(x, 4) for x in losses], "ln_vocab": round(ln_v, 4),
        "first_step_s": step_s[0], "later_step_s": step_s[1:],
        "pallas_in_lowered_step": has_kernel, "flash_paths": paths,
        "attn_shape": list(shape), "attn_max_err": attn_err,
        "cache_stats": stats, "failures": failures,
    }})


def sharded_train_loop(config):
    """The same model as one program over four devices: fsdp=2 x tp=2 on
    `build_mesh` of the real devices, dense attention and the plain
    cross-entropy (the kernel is the single-chip path and cannot be
    partitioned). Step-0 loss is compared with the same seed and batch on
    one device, as `__graft_entry__._dryrun_impl` does at tiny size."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu import parallel, train
    from ray_tpu.models import GPT, GPTConfig
    from ray_tpu.models.gpt import cross_entropy_loss
    from ray_tpu.parallel import ShardingStrategy, logical_axis_rules
    from ray_tpu.parallel.sharding import param_shardings, shard_batch

    batch, seq, seed = config["batch"], config["seq"], config["seed"]
    devices = jax.devices()
    failures = _device_failures(devices[0], len(devices), 4)
    strategy = ShardingStrategy(fsdp=2, tp=2)
    mesh = strategy.build_mesh(devices[:4])
    rules = logical_axis_rules(strategy)
    cfg = getattr(GPTConfig, config["preset"])(max_seq_len=seq)
    model = GPT(cfg)
    tokens = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch, seq + 1), dtype=np.int32)
    inputs_host = np.ascontiguousarray(tokens[:, :-1])
    targets_host = np.ascontiguousarray(tokens[:, 1:])
    tx = optax.adamw(3e-4)

    def loss_of(params, inputs, targets):
        return cross_entropy_loss(model.apply(params, inputs), targets)

    def make_carry():
        params = model.init(jax.random.PRNGKey(seed),
                            jnp.zeros((batch, seq), jnp.int32))
        return params, tx.init(params)

    with mesh, nn.logical_axis_rules(rules):
        data = shard_batch((inputs_host, targets_host), mesh, strategy)
        # adamw's moments keep the parameters' partitioning metadata, so
        # one rule set places the whole carry
        shardings = param_shardings(mesh, jax.eval_shape(make_carry), rules)
        carry = jax.jit(make_carry, out_shardings=shardings)()

        def step(carry, batch):
            params, opt_state = carry
            loss, grads = jax.value_and_grad(loss_of)(params, *batch)
            updates, opt_state = tx.update(grads, opt_state, params)
            carry = (optax.apply_updates(params, updates), opt_state)
            return jax.lax.with_sharding_constraint(carry, shardings), loss

        runner = train.TrainStepRunner(step, mesh=mesh, on_retrace="error")
        losses = []
        for _ in range(config["steps"]):
            carry, loss = runner.run(carry, data)
            losses.append(float(loss))

    def whole_on_one_device(tree, what):
        """Arrays whose spec names a mesh axis yet sit unsplit on a device."""
        bad = []
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
            split = any(axis is not None for axis in leaf.sharding.spec)
            shard = leaf.addressable_shards[0].data.shape
            if split and (shard == leaf.shape
                          or len(leaf.sharding.device_set) != 4):
                bad.append(f"{what}{jax.tree_util.keystr(path)} "
                           f"{leaf.shape} spec={leaf.sharding.spec}")
        return bad

    unsplit = whole_on_one_device(carry, "carry") + \
        whole_on_one_device(data, "batch")
    n_split = sum(any(a is not None for a in leaf.sharding.spec)
                  for leaf in jax.tree_util.tree_leaves(carry))
    with jax.default_device(devices[0]):
        ref_params = jax.jit(model.init)(jax.random.PRNGKey(seed),
                                         jnp.asarray(inputs_host))
        ref_loss = float(jax.jit(loss_of)(
            ref_params, jnp.asarray(inputs_host), jnp.asarray(targets_host)))

    stats = parallel.cache_stats()
    if unsplit:
        failures.append(f"not spread over the mesh: {unsplit[:4]}")
    if not n_split:
        failures.append("no array of the carry has a split spec")
    if not all(math.isfinite(x) for x in losses):
        failures.append(f"non-finite loss: {losses}")
    if abs(losses[0] - ref_loss) > 1e-2 * abs(ref_loss):
        failures.append(f"sharded step-0 loss {losses[0]:.5f} differs from "
                        f"one device's {ref_loss:.5f} by more than 1%")
    if not losses[-1] < losses[0]:
        failures.append(f"loss did not fall on the repeated batch: {losses}")
    if stats["retraces"]:
        failures.append(f"executable cache retraced: {stats}")
    train.report({"smoke": {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind, "devices": len(devices),
        "mesh": dict(mesh.shape), "model": config["preset"],
        "batch": batch, "seq": seq,
        "losses": [round(x, 5) for x in losses],
        "one_device_loss": round(ref_loss, 5),
        "carry_arrays_split": int(n_split), "cache_stats": stats,
        "failures": failures,
    }})


def train_phase(size: dict, seed: int, chips: int = 1) -> dict:
    import ray_tpu
    from ray_tpu import train
    from ray_tpu.air.config import RunConfig, ScalingConfig

    storage = tempfile.mkdtemp(prefix="chip_smoke_train_")
    ray_tpu.init()
    try:
        result = train.JaxTrainer(
            train_loop if chips == 1 else sharded_train_loop,
            train_loop_config=dict(size, seed=seed),
            scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                         tpus_per_worker=chips),
            run_config=RunConfig(storage_path=storage, name="chip_smoke"),
        ).fit()
    finally:
        ray_tpu.shutdown()
        shutil.rmtree(storage, ignore_errors=True)
    return result.metrics["smoke"]


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def serve_phase(size: dict, seed: int, replicas: int = 1) -> dict:
    import ray_tpu
    from ray_tpu import serve

    vocab = size["model_config"]["vocab_size"]
    max_new = size["max_new_tokens"]
    rng = random.Random(seed)
    prompts = [[rng.randrange(vocab) for _ in range(n)]
               for n in size["prompt_lens"]]

    def stream(prompt):
        return [chunk["token"] for chunk in handle.generate.options(
            stream=True).remote(prompt, max_new)]

    def ask_every_replica(timeout):
        """`replica_info()` by pid. Routing is random, so ask until all
        have spoken."""
        infos = {}
        for _ in range(40 * replicas):
            info = handle.replica_info.remote().result(timeout=timeout)
            infos[info["pid"]] = info
            if len(infos) == replicas:
                break
        return infos

    ray_tpu.init()
    try:
        t0 = time.perf_counter()
        handle = serve.run(serve.llm.build_app(
            name="llm", num_replicas=replicas, model="llama",
            model_config=size["model_config"],
            engine_config=size["engine_config"], seed=seed))
        # every replica answers (its warm-up is over) before the streams
        # start, so the router spreads them
        infos = ask_every_replica(timeout=900)
        ready_s = time.perf_counter() - t0
        # alone, then every prompt at once (a decode batch, chunked
        # prefill between its steps), then the first one alone again
        alone = stream(prompts[0])
        with concurrent.futures.ThreadPoolExecutor(
                len(prompts) * replicas) as pool:
            streams = list(pool.map(stream, prompts * replicas))
        again = stream(prompts[0])
        after = ask_every_replica(timeout=60)
        serve.shutdown()
    finally:
        ray_tpu.shutdown()

    failures = []
    if len(after) != replicas:
        failures.append(f"{len(after)} of {replicas} replicas answered")
    for info in after.values():
        if info["platform"] != "tpu":
            failures.append(f"replica {info['pid']} computes on "
                            f"{info['platform']!r}, not the TPU")
        if info["cache_stats"]["retraces"]:
            failures.append(f"replica {info['pid']} retraced: "
                            f"{info['cache_stats']}")
        if info["kv_pages_live"]:
            failures.append(f"replica {info['pid']} leaked "
                            f"{info['kv_pages_live']} KV pages")
    for toks in [alone, again] + streams:
        if len(toks) != max_new or \
                not all(isinstance(t, int) and 0 <= t < vocab for t in toks):
            failures.append(f"bad stream (want {max_new} ids below {vocab}): "
                            f"{toks}")
    # greedy decoding through the same programs: the same answer. (Inside
    # a batch the programs differ, and bf16 logits of random weights are
    # near ties, so the batched streams are held to shape only.)
    if alone != again:
        failures.append(f"the same prompt gave {alone} and then {again}")
    chips = sorted(tuple(info["tpu_chips"]) for info in after.values())
    answered = sum(1 for info in after.values() if info["requests_completed"])
    if replicas > 1:
        if len(set(chips)) != replicas or not all(chips):
            failures.append(f"replicas do not hold distinct chips: {chips}")
        if answered < 2:
            failures.append(f"requests were answered by {answered} replica")
    first = next(iter(after.values()), {})
    return {
        "platform": first.get("platform"),
        "device_kind": first.get("device_kind"),
        "model": "llama", "model_config": size["model_config"],
        "replicas": replicas, "replica_chips": [list(c) for c in chips],
        "replicas_that_answered": answered,
        "requests": len(streams) + 2, "prompt_lens": size["prompt_lens"],
        "max_new_tokens": max_new, "stream_of_prompt_0": again,
        "ready_s": round(ready_s, 2),
        "warmup_s": [round(i["warmup_s"], 2) for i in infos.values()],
        # the arena is device memory: a step reads and writes it in place
        "kv_arena_bytes_on_device": first.get("kv_arena_bytes"),
        "cache_stats": [i["cache_stats"] for i in after.values()],
        "failures": failures,
    }


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def _left_behind(arenas_before: set) -> list:
    """Daemons or workers of this process's clusters that outlived
    shutdown (a worker holds its chip until it dies), and shm arenas."""
    mine = re.compile(rf"session_\d+_{os.getpid()}\b")
    deadline = time.monotonic() + 30
    while True:
        procs = []
        for path in glob.glob("/proc/[0-9]*/cmdline"):
            try:
                with open(path, "rb") as f:
                    cmd = f.read().replace(b"\0", b" ").decode(errors="replace")
            except OSError:
                continue  # exited while we looked
            if "ray_tpu._private" in cmd and mine.search(cmd):
                procs.append(cmd[:200])
        arenas = sorted(set(glob.glob("/dev/shm/ray_tpu_*")) - arenas_before)
        if not (procs or arenas) or time.monotonic() > deadline:
            return [f"left running: {p}" for p in procs] + \
                [f"left in /dev/shm: {a}" for a in arenas]
        time.sleep(0.5)


def _keep_logs(phase: str) -> None:
    """A failed phase's daemon and worker logs (its cluster is this
    process's newest session), where the chip tool brings them back from."""
    sessions = sorted(glob.glob(f"/tmp/ray_tpu/session_*_{os.getpid()}"))
    if sessions:
        shutil.copytree(
            os.path.join(sessions[-1], "logs"),
            os.path.join(REPO, "chiprun_out", "chip_smoke_logs", phase),
            dirs_exist_ok=True)


def run_phase(name: str, fn, *args) -> bool:
    """Run one phase, print its record as one JSON line, say if it passed."""
    arenas_before = set(glob.glob("/dev/shm/ray_tpu_*"))
    t0 = time.perf_counter()
    try:
        record = fn(*args)
    except Exception:  # noqa: BLE001 — reported: the phase failed
        record = {"failures": [traceback.format_exc()[-3000:]]}
    record["failures"] += _left_behind(arenas_before)
    ok = not record["failures"]
    if not ok:
        _keep_logs(name)
    print(json.dumps({"phase": name, "ok": ok,
                      "wall_s": round(time.perf_counter() - t0, 2), **record}),
          flush=True)
    return ok


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()

    device = {}
    ok = False
    try:
        device = probe_device()
        if device["platform"] != "tpu" or device["count"] < args.chips:
            print(json.dumps({"phase": "probe", "ok": False, "failures": [
                f"needs {args.chips} TPU chip(s), jax found {device}"]}))
        else:
            build_native()
            if args.chips == 1:
                phases = [("train", train_phase, TRAIN, args.seed),
                          ("serve", serve_phase, SERVE, args.seed)]
            else:
                phases = [("train_4chip", train_phase, TRAIN, args.seed, 4),
                          ("serve_4replicas", serve_phase, SERVE, args.seed,
                           4)]
            # every phase runs even after a failure: one call, all faults
            ok = all([run_phase(*phase) for phase in phases])
            if "jax" in sys.modules:
                ok = False
                print(json.dumps({"phase": "driver", "ok": False, "failures": [
                    "the driver process imported jax"]}))
    except Exception:  # noqa: BLE001 — reported on the last line's "ok"
        traceback.print_exc()
    print(json.dumps({"ok": ok, "device": device}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
