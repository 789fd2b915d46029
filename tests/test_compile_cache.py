"""AOT executable cache + steps_per_call folding (ROADMAP r5 #3).

Covers the dispatch plane behind sub-2 ms driver overhead: hit/miss
counters, donation actually taking effect (the donated carry's buffer is
consumed), the retrace guard firing on an abstract-signature change, and
loss-trajectory equivalence of one folded K-step dispatch vs K single
steps.
"""

import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.parallel.compile_cache import (
    ExecutableCache,
    RetraceError,
    cache_stats,
    compiled_step,
    fold_steps,
    global_cache,
    stack_batches,
)


def _sgd_step(w, batch):
    x, y = batch
    def loss_fn(w):
        return jnp.mean((x @ w - y) ** 2)
    loss, g = jax.value_and_grad(loss_fn)(w)
    return w - 0.1 * g, loss


def _make_data(seed, n=32, d=4):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(n, d), jnp.float32)
    true_w = jnp.asarray(rng.randn(d), jnp.float32)
    return x, x @ true_w


def test_hit_miss_counters_and_entries():
    cache = ExecutableCache()
    step = compiled_step(_sgd_step, donate_argnums=(0,), cache=cache)
    w = jnp.zeros(4)
    batch = _make_data(0)
    w, _ = step(w, batch)
    assert cache.stats.as_dict() == {"hits": 0, "misses": 1,
                                     "retraces": 0}
    assert cache.size() == 1
    for _ in range(3):
        w, _ = step(w, batch)
    assert cache.stats.hits == 3
    assert cache.stats.misses == 1
    assert cache.size() == 1  # one executable serves every step


def test_donation_buffer_consumed():
    """donate_argnums must reach the AOT executable: the donated carry
    is consumed by the call (its buffer was reused for the output)."""
    cache = ExecutableCache()
    step = compiled_step(_sgd_step, donate_argnums=(0,), cache=cache)
    batch = _make_data(1)
    w0 = jnp.zeros(4)
    w1, _ = step(w0, batch)  # compile + run
    assert w0.is_deleted(), "donated carry should be consumed"
    w2, _ = step(w1, batch)  # cached-executable path donates too
    assert w1.is_deleted()
    assert not w2.is_deleted()
    # and without donation the input survives
    cache2 = ExecutableCache()
    step_nd = compiled_step(_sgd_step, cache=cache2)
    w3 = jnp.zeros(4)
    step_nd(w3, batch)
    assert not w3.is_deleted()


def test_retrace_guard_fires_on_shape_change():
    cache = ExecutableCache()
    step = compiled_step(_sgd_step, donate_argnums=(0,), cache=cache)
    step(jnp.zeros(4), _make_data(0, d=4))
    assert cache.stats.retraces == 0
    # same function, new aval signature: miss + retrace recorded
    step(jnp.zeros(8), _make_data(0, d=8))
    assert cache.stats.retraces == 1
    assert cache.stats.misses == 2
    # strict mode raises instead of silently compiling a third variant
    strict = compiled_step(_sgd_step, donate_argnums=(0,), cache=cache,
                           on_retrace="error")
    with pytest.raises(RetraceError, match="new abstract signature"):
        strict(jnp.zeros(16), _make_data(0, d=16))


def test_dtype_change_is_a_retrace():
    cache = ExecutableCache()
    f = compiled_step(lambda x: x * 2, cache=cache)
    f(jnp.zeros(4, jnp.float32))
    f(jnp.zeros(4, jnp.int32))
    assert cache.stats.retraces == 1


def test_fold_steps_matches_k_single_steps():
    """One steps_per_call=K dispatch must walk the same loss trajectory
    as K single-step dispatches."""
    k = 4
    x, y = _make_data(2)
    batches = [( x[i * 8:(i + 1) * 8], y[i * 8:(i + 1) * 8])
               for i in range(k)]

    w_ref = jnp.zeros(4)
    ref_losses = []
    for b in batches:
        w_ref, loss = _sgd_step(w_ref, b)
        ref_losses.append(float(loss))

    cache = ExecutableCache()
    multi = fold_steps(_sgd_step, k, cache=cache)
    assert multi.steps_per_call == k
    w_fold, losses = multi(jnp.zeros(4), stack_batches(batches))
    assert losses.shape == (k,)
    np.testing.assert_allclose(np.asarray(losses), ref_losses,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w_fold), np.asarray(w_ref),
                               rtol=1e-5)
    # the folded program is ONE cached executable: driver cost for the
    # next K steps is a single hit
    w2, _ = multi(w_fold, stack_batches(batches))
    assert cache.stats.as_dict() == {"hits": 1, "misses": 1,
                                     "retraces": 0}


def test_fold_steps_donates_carry():
    k = 2
    x, y = _make_data(3)
    batches = stack_batches([(x, y)] * k)
    cache = ExecutableCache()
    multi = fold_steps(_sgd_step, k, cache=cache)
    w0 = jnp.zeros(4)
    multi(w0, batches)
    assert w0.is_deleted(), "folded carry should be donated"


def test_train_step_runner_equivalence_and_stats():
    from ray_tpu.train import TrainStepRunner

    k = 3
    x, y = _make_data(4)
    batches = [(x, y)] * (2 * k)

    w_ref = jnp.zeros(4)
    ref_losses = []
    for b in batches:
        w_ref, loss = _sgd_step(w_ref, b)
        ref_losses.append(float(loss))

    # the runner steps through the process-wide cache, which every earlier
    # test of this worker has used: count from here
    before = cache_stats()
    runner = TrainStepRunner(_sgd_step, steps_per_call=k)
    w = jnp.zeros(4)
    it = iter(batches)
    got = []
    for _ in range(2):
        w, losses = runner.run(w, it)
        got.extend(float(v) for v in losses)
    np.testing.assert_allclose(got, ref_losses, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w), np.asarray(w_ref),
                               rtol=1e-5)
    stats = runner.cache_stats()
    assert stats["misses"] - before["misses"] == 1
    assert stats["hits"] - before["hits"] == 1

    # steps_per_call=1 path: plain per-batch stepping, same trajectory
    runner1 = TrainStepRunner(_sgd_step)
    w1 = jnp.zeros(4)
    for b in batches:
        w1, _ = runner1.run(w1, b)
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w_ref),
                               rtol=1e-5)


def test_global_cache_stats_shape():
    before = cache_stats()
    assert set(before) == {"hits", "misses", "retraces", "entries",
                           "lowering_ms", "lookup_ms", "lookups",
                           "activation_constraints",
                           "activation_constraints_skipped", "lowerings",
                           "programs", "program_trace_ms",
                           "program_lower_ms", "program_load_ms",
                           "program_compile_ms", "persistent_hits",
                           "persistent_misses"}

    @compiled_step
    def bump(x):
        return x + 1

    bump(jnp.zeros(2))
    bump(jnp.zeros(2))
    after = cache_stats()
    assert after["misses"] >= before["misses"] + 1
    assert after["hits"] >= before["hits"] + 1
    # every lookup times itself, hit or miss; the compile is not in it
    assert after["lookups"] >= before["lookups"] + 2
    assert after["lookup_ms"] > before["lookup_ms"]
    assert after["lookup_ms"] - before["lookup_ms"] < \
        after["lowering_ms"] - before["lowering_ms"]
    global_cache().clear()
    cleared = cache_stats()
    assert cleared["entries"] == 0 and cleared["lowerings"] == []


@pytest.mark.parametrize("folded", [False, True])
def test_cache_stats_count_the_activation_constraints_of_each_lowering(
        folded):
    """A lowering for a mesh emits the model's constraints, one for no mesh
    passes them through; `cache_stats()` has a row for each, and the sums.
    `fold_steps` hands its mesh on, so its scan body is traced for it."""
    from ray_tpu.parallel import build_mesh, logical_constraint

    def twice(x, _batch=None):
        x = logical_constraint(x, ("batch", "embed"))
        x = logical_constraint(x * 2, ("batch", "embed"))
        return (x, x.sum()) if folded else x

    def wrap(**kw):
        return fold_steps(twice, 1, **kw) if folded else \
            compiled_step(twice, **kw)

    global_cache().clear()
    mesh = build_mesh({"fsdp": 2, "tp": 2}, jax.devices()[:4])
    x, args = jnp.ones((4, 8)), ((jnp.zeros((1,)),) if folded else ())
    for kw in ({"mesh": mesh}, {}):
        out = wrap(**kw)(jnp.ones((4, 8)), *args)
        np.testing.assert_allclose(out[0] if folded else out, 2 * x)
    stats = cache_stats()
    name = "fold_steps(twicex1)" if folded else "twice"
    keys = ("fn", "door", "activation_constraints",
            "activation_constraints_skipped")
    assert [{k: row[k] for k in keys} for row in stats["lowerings"]] == [
        {"fn": name, "door": "compiled_step", "activation_constraints": 2,
         "activation_constraints_skipped": 0},
        {"fn": name, "door": "compiled_step", "activation_constraints": 0,
         "activation_constraints_skipped": 2}]
    assert stats["activation_constraints"] == 2
    assert stats["activation_constraints_skipped"] == 2
    global_cache().clear()


def test_python_scalar_is_part_of_the_key():
    """Non-array leaves are baked into the trace; a changed scalar must
    be a different executable, not a stale cache hit."""
    cache = ExecutableCache()
    f = compiled_step(lambda x, s: x * s, cache=cache)
    a = f(jnp.ones(2), 2.0)
    b = f(jnp.ones(2), 3.0)
    np.testing.assert_allclose(np.asarray(a), [2.0, 2.0])
    np.testing.assert_allclose(np.asarray(b), [3.0, 3.0])
    assert cache.size() == 2


def test_sharded_carry_steps_twice_without_a_retrace():
    """A carry placed with `P(None)` comes back from the jitted step as
    `P()`: one layout, two spellings. The key must see one signature (the
    second step of every sharded loop was a RetraceError), and a real
    `jax.sharding.Mesh` must be accepted as the `mesh` key."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("x", "y"))
    w = jax.device_put(jnp.ones((8, 4)), NamedSharding(mesh, P("x", None)))
    b = jax.device_put(jnp.zeros((4,)), NamedSharding(mesh, P(None)))

    def step(carry, lr):
        w, b = carry
        return (w - lr * w, b + w.sum(0)), w.sum()

    cache = ExecutableCache()
    run = compiled_step(step, donate_argnums=(0,), mesh=mesh, cache=cache,
                        on_retrace="error")
    carry = (w, b)
    for _ in range(3):
        carry, _ = run(carry, jnp.float32(0.5))
    assert cache.stats.as_dict() == {"hits": 2, "misses": 1, "retraces": 0}
    # a different layout is still a different signature
    moved = jax.device_put(carry[0], NamedSharding(mesh, P(None, "y")))
    with pytest.raises(RetraceError):
        run((moved, carry[1]), jnp.float32(0.5))


# -- a row for every program the process starts -----------------------------

def _program_rows(since):
    from ray_tpu.util import tracing

    return [r for r in tracing.startup_rows()[since:]
            if r["name"] == "program"]


@pytest.mark.parametrize("door", ["compiled_step", "jit"])
def test_a_program_leaves_one_row_with_its_door_and_stages(door):
    from ray_tpu.parallel import compile_cache
    from ray_tpu.util import tracing

    tracing.clear_startup()

    def poly(x):
        return jnp.tanh(x @ x.T).sum() + 41.5

    x = jnp.ones((8, 8))
    jax.block_until_ready(x)
    before = cache_stats()
    since = len(tracing.startup_rows())
    if door == "compiled_step":
        cache = ExecutableCache()
        step = compiled_step(poly, cache=cache, name="poly:8")
        step(x)
    else:
        jax.jit(poly)(x)
    (row,) = _program_rows(since)
    attrs = row["attrs"]
    assert attrs["door"] == door
    assert attrs["fn"] == ("poly:8" if door == "compiled_step"
                           else "jit(poly)")
    parts = [attrs[k] for k in ("trace_s", "lower_s", "load_s", "compile_s")]
    assert all(p >= 0 for p in parts)
    assert attrs["trace_s"] > 0 and attrs["lower_s"] > 0
    assert sum(parts) <= (row["end_ns"] - row["begin_ns"]) / 1e9
    assert attrs["backend_s"] >= attrs["load_s"] + attrs["compile_s"] - 1e-9
    # either the persistent cache held it or the backend compiled it
    assert (attrs["load_s"] > 0) == bool(attrs["persistent_hit"])
    assert (attrs["compile_s"] > 0) == (not attrs["persistent_hit"])
    after = cache_stats()
    assert after["programs"] == before["programs"] + 1
    for stage in ("trace", "lower", "load", "compile"):
        assert after[f"program_{stage}_ms"] - before[f"program_{stage}_ms"] \
            == pytest.approx(attrs[f"{stage}_s"] * 1e3, abs=2e-3)
    if door == "compiled_step":
        # the row is the `compiled_step.lower` phase, and the lowering's
        (lowering,) = cache.lowerings
        assert {k: lowering[k] for k in attrs} == attrs
        assert (row["end_ns"] - row["begin_ns"]) / 1e6 == pytest.approx(
            cache.phases.ms("compiled_step.lower"), abs=1e-3)
        calls = compile_cache.listener_calls
        step(x)                         # a cached executable: no listener
        assert compile_cache.listener_calls == calls
        assert _program_rows(since) == [row]


def test_an_eager_program_inside_a_steps_trace_is_a_row_of_its_own():
    """Values computed eagerly while the step is traced start programs of
    their own: rows of the `jit` door inside the step's row, whose trace
    time is the step's own."""
    from ray_tpu.util import tracing

    tracing.clear_startup()

    def step(x):
        with jax.ensure_compile_time_eval():
            table = jnp.cumsum(jnp.arange(7.0)) * 3.25
        return x * table.sum()

    x = jnp.ones(7)
    jax.block_until_ready(x)
    since = len(tracing.startup_rows())
    compiled_step(step, cache=ExecutableCache(), name="step:7")(x)
    rows = _program_rows(since)
    own = rows[-1]
    assert own["attrs"]["fn"] == "step:7"
    assert own["attrs"]["door"] == "compiled_step"
    inner = rows[:-1]
    assert inner and all(r["attrs"]["door"] == "jit" for r in inner)
    assert all(own["begin_ns"] <= r["begin_ns"] and r["end_ns"]
               <= own["end_ns"] for r in inner)
    inside = sum(r["end_ns"] - r["begin_ns"] for r in inner) / 1e9
    parts = sum(own["attrs"][k] for k in ("trace_s", "lower_s", "load_s",
                                          "compile_s"))
    assert parts + inside <= (own["end_ns"] - own["begin_ns"]) / 1e9 + 5e-3


def test_a_second_process_reads_the_program_from_the_persistent_cache(
        tmp_path):
    import json
    import subprocess
    import sys

    code = (
        "import json, jax, jax.numpy as jnp\n"
        "from ray_tpu.parallel import compiled_step, cache_stats\n"
        "from ray_tpu.util import tracing\n"
        "def f(x):\n"
        "    return jnp.sin(x) @ jnp.cos(x).T * 0.8125\n"
        "x = jnp.ones((16, 16)); jax.block_until_ready(x)\n"
        "tracing.clear_startup()\n"
        "compiled_step(f, name='f:16')(x)\n"
        "jax.jit(lambda x: (x * 1.625).sum())(x)\n"
        "rows = [r['attrs'] for r in tracing.startup_rows()\n"
        "        if r['name'] == 'program']\n"
        "s = cache_stats()\n"
        "print(json.dumps([rows, s['persistent_hits'],\n"
        "                  s['persistent_misses']]))\n")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0",
               JAX_PLATFORMS="cpu")
    runs = []
    for _ in range(2):
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=300,
                             cwd=os.path.dirname(os.path.dirname(
                                 os.path.abspath(__file__))))
        assert out.returncode == 0, out.stderr[-2000:]
        runs.append(json.loads(out.stdout.strip().splitlines()[-1]))
    (cold, cold_hits, cold_misses), (warm, warm_hits, warm_misses) = runs
    assert [r["fn"] for r in cold] == [r["fn"] for r in warm]
    assert {r["door"] for r in cold} == {"compiled_step", "jit"}
    assert all(r["persistent_hit"] is False and r["load_s"] == 0
               and r["compile_s"] > 0 for r in cold)
    assert all(r["persistent_hit"] is True and r["load_s"] > 0
               and r["compile_s"] == 0 for r in warm)
    assert (cold_hits, warm_misses) == (0, 0)
    # the totals count the programs from before the rows were cleared too
    assert cold_misses == warm_hits >= len(cold) >= 2


def test_the_metrics_text_prints_the_programs():
    from ray_tpu.parallel import compile_cache

    jax.jit(lambda x: x * 2.125)(jnp.ones(3))
    text = compile_cache._metrics_text()
    s = cache_stats()
    assert s["programs"] >= 1
    assert f"compile_cache_programs_total {s['programs']}" in text
    for stage in ("trace", "lower", "load", "compile"):
        assert f'compile_cache_program_ms_total{{stage="{stage}"}} ' in text
    assert "compile_cache_persistent_hits_total " in text
    assert "compile_cache_persistent_misses_total " in text


def test_the_frames_of_every_programs_trace_keep_their_lines():
    """`lookup`, `_lookup`'s lowering and the wrapper's calls are frames
    above every traced program: their positions are in a Pallas kernel's
    locations and so in the persistent cache's key. An edit that moves
    them sends every kernel-bearing program of every cell through a cold
    start once (and `setup_s` with it): move them on purpose or not at
    all, and put what the file gains at its end."""
    import ast
    import inspect

    from ray_tpu.parallel import compile_cache

    tree = ast.parse(inspect.getsource(compile_cache))
    calls = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef) and node.name in (
                "lookup", "_lookup", "wrapper"):
            for call in ast.walk(node):
                if isinstance(call, ast.Call) and isinstance(
                        call.func, ast.Attribute) and call.func.attr in (
                        "_lookup", "lower", "lookup"):
                    calls.setdefault(node.name, []).append(
                        (call.lineno, call.col_offset,
                         call.end_lineno, call.end_col_offset))
    assert {k: sorted(v) for k, v in calls.items()} == {
        "lookup": [(161, 19, 162, 65)], "_lookup": [(197, 23, 200, 36)],
        "wrapper": [(294, 23, 297, 38), (302, 19, 305, 34)]}
