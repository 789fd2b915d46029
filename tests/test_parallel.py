"""Parallelism primitives on the 8-device virtual CPU mesh: mesh building,
sharding rules, collectives, ring attention, Ulysses, pipeline, MoE."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from ray_tpu.parallel import (
    MeshConfig,
    ShardingStrategy,
    build_mesh,
    mesh_shape_for,
)
from ray_tpu.parallel import collectives
from ray_tpu.parallel.moe import apply_moe
from ray_tpu.parallel.pipeline import (
    bubble_fraction,
    pipeline_apply,
    pipeline_loss,
    pipeline_train_step,
    stack_stage_params,
)
from ray_tpu.parallel.ring_attention import (
    full_attention,
    ring_attention,
    ulysses_attention,
)


def test_eight_virtual_devices():
    assert len(jax.devices()) == 8


def test_mesh_config_inference():
    assert MeshConfig({"dp": -1, "tp": 2}).resolved(8) == {"dp": 4, "tp": 2}
    assert mesh_shape_for(8, tp=2, sp=2) == {"dp": 2, "tp": 2, "sp": 2}
    with pytest.raises(ValueError):
        MeshConfig({"dp": 3}).resolved(8)


def test_build_mesh_axes():
    mesh = build_mesh({"dp": 2, "fsdp": 2, "tp": 2})
    assert mesh.shape == {"dp": 2, "fsdp": 2, "tp": 2}


def test_device_allreduce():
    mesh = build_mesh({"dp": 8})
    x = jnp.arange(8.0)
    out = collectives.device_allreduce(mesh, x, axis="dp")
    # Each dp member holds one element; psum yields the total, replicated.
    assert float(np.asarray(out)[0]) == 28.0


def test_strategy_data_axes():
    s = ShardingStrategy(dp=2, fsdp=2, tp=2)
    assert s.data_axes == ("dp", "fsdp")
    assert ShardingStrategy(dp=8).data_axes == ("dp",)


def _reference_attention(q, k, v, causal):
    return full_attention(q, k, v, causal=causal)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_dense(causal):
    mesh = build_mesh({"dp": 2, "sp": 4})
    b, t, h, d = 2, 32, 4, 8
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    with mesh:
        out = ring_attention(q, k, v, mesh, causal=causal, head_axis=None)
    expected = _reference_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)


def test_ring_attention_jit_grad():
    mesh = build_mesh({"sp": 8})
    b, t, h, d = 1, 64, 2, 4
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)

    def loss(q, k, v):
        return ring_attention(q, k, v, mesh, causal=True, head_axis=None,
                              batch_axes=()).sum()

    def ref_loss(q, k, v):
        return full_attention(q, k, v, causal=True).sum()

    g = jax.jit(jax.grad(loss))(q, k, v)
    g_ref = jax.grad(ref_loss)(q, k, v)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_dense(causal):
    mesh = build_mesh({"sp": 4, "dp": 2})
    b, t, h, d = 2, 16, 4, 8
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    with mesh:
        out = ulysses_attention(q, k, v, mesh, causal=causal)
    expected = _reference_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)


def test_pipeline_matches_sequential():
    mesh = build_mesh({"pp": 4}, devices=jax.devices()[:4])
    n_stages, batch, dim = 4, 8, 16
    rng = np.random.RandomState(3)
    stage_ws = [jnp.asarray(rng.randn(dim, dim) * 0.1, jnp.float32)
                for _ in range(n_stages)]
    params = stack_stage_params([{"w": w} for w in stage_ws])
    x = jnp.asarray(rng.randn(batch, dim), jnp.float32)

    def stage_fn(p, h):
        return jnp.tanh(h @ p["w"])

    out = pipeline_apply(stage_fn, params, x, mesh, num_microbatches=4)
    expected = x
    for w in stage_ws:
        expected = jnp.tanh(expected @ w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=1e-5)


def test_pipeline_grad():
    mesh = build_mesh({"pp": 2}, devices=jax.devices()[:2])
    rng = np.random.RandomState(4)
    params = stack_stage_params([
        {"w": jnp.asarray(rng.randn(8, 8) * 0.1, jnp.float32)}
        for _ in range(2)
    ])
    x = jnp.asarray(rng.randn(4, 8), jnp.float32)

    def stage_fn(p, h):
        return jnp.tanh(h @ p["w"])

    def loss(params):
        return pipeline_apply(stage_fn, params, x, mesh,
                              num_microbatches=2).sum()

    g = jax.jit(loss)(params), jax.grad(loss)(params)
    assert float(jnp.abs(g[1]["w"]).sum()) > 0


def test_pipeline_fused_loss_and_grads_match_single_device():
    """VERDICT r2 item 9 'done' criterion: the fused-loss pipeline's
    loss AND per-stage grads equal a plain single-device forward/backward
    of the same stack — with remat on (the 1F1B-equivalent memory mode)
    and gradient accumulation over microbatches built in."""
    n_stages, batch, dim, n_mb = 4, 16, 8, 8
    mesh = build_mesh({"pp": n_stages}, devices=jax.devices()[:n_stages])
    rng = np.random.RandomState(7)
    stage_ws = [jnp.asarray(rng.randn(dim, dim) * 0.3, jnp.float32)
                for _ in range(n_stages)]
    params = stack_stage_params([{"w": w} for w in stage_ws])
    x = jnp.asarray(rng.randn(batch, dim), jnp.float32)
    y = jnp.asarray(rng.randn(batch, dim), jnp.float32)

    def stage_fn(p, h):
        return jnp.tanh(h @ p["w"])

    def loss_fn(out, tgt):
        return jnp.mean(jnp.square(out - tgt))

    loss, grads = jax.jit(
        lambda ps: pipeline_train_step(
            stage_fn, loss_fn, ps, x, y, mesh,
            num_microbatches=n_mb))(params)

    # single-device reference: same microbatch averaging (mean of
    # per-microbatch MSE == global MSE here since equal sizes)
    def ref_loss(ps):
        h = x
        for i in range(n_stages):
            h = jnp.tanh(h @ ps["w"][i])
        return jnp.mean(jnp.square(h - y))

    ref_l, ref_g = jax.value_and_grad(ref_loss)(params)
    np.testing.assert_allclose(float(loss), float(ref_l), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(grads["w"]),
                               np.asarray(ref_g["w"]),
                               atol=1e-5, rtol=1e-4)
    # remat (the 1F1B-equivalent memory mode) is bit-stable vs no-remat
    loss2, grads2 = jax.jit(
        lambda ps: pipeline_train_step(
            stage_fn, loss_fn, ps, x, y, mesh,
            num_microbatches=n_mb, remat=False))(params)
    np.testing.assert_allclose(float(loss2), float(loss), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(grads2["w"]),
                               np.asarray(grads["w"]), rtol=1e-5)


def test_pipeline_loss_scalar_only_psum():
    """pipeline_loss returns a replicated scalar; raising microbatches
    shrinks the structural bubble."""
    mesh = build_mesh({"pp": 2}, devices=jax.devices()[:2])
    rng = np.random.RandomState(8)
    params = stack_stage_params([
        {"w": jnp.asarray(rng.randn(4, 4) * 0.1, jnp.float32)}
        for _ in range(2)])
    x = jnp.asarray(rng.randn(8, 4), jnp.float32)
    y = jnp.asarray(rng.randn(8, 4), jnp.float32)
    l = pipeline_loss(
        lambda p, h: h @ p["w"], lambda o, t: jnp.mean((o - t) ** 2),
        params, x, y, mesh, num_microbatches=4)
    assert l.shape == ()
    assert bubble_fraction(2, 4) == pytest.approx(1 / 5)
    assert bubble_fraction(4, 16) < bubble_fraction(4, 4)


def test_moe_dispatch_combines():
    mesh = build_mesh({"ep": 4, "dp": 2})
    b, s, d, n_experts = 2, 16, 8, 4
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(b, s, d), jnp.float32)
    router_w = jnp.asarray(rng.randn(d, n_experts) * 0.1, jnp.float32)
    expert_w = jnp.asarray(rng.randn(n_experts, d, d) * 0.1, jnp.float32)

    def expert_fn(w, tokens):
        return tokens @ w

    with mesh:
        y, aux = apply_moe(
            x, router_w, expert_w, expert_fn, mesh,
            capacity_factor=8.0,  # ample capacity: no token dropped
        )
    assert y.shape == x.shape
    assert float(aux) > 0

    # Compare against dense single-shard dispatch.
    mesh1 = build_mesh({"dp": 8})
    y_ref, _ = apply_moe(x, router_w, expert_w, expert_fn, mesh1,
                         capacity_factor=8.0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-4)


@pytest.mark.parametrize("variant", ["ring", "ulysses"])
def test_sequence_parallel_attention_gqa(variant):
    """GQA (fewer KV heads) through the sequence-parallel paths: ring
    rotates KV at its narrow h_kv width (expanding per-block); Ulysses
    all_to_alls the narrow KV then expands post-split. Both must match
    dense attention over query-side-expanded KV."""
    import numpy as np

    from ray_tpu.parallel.mesh import build_mesh
    from ray_tpu.parallel.ring_attention import (full_attention,
                                                 ring_attention,
                                                 ulysses_attention)

    mesh = build_mesh({"dp": 2, "sp": 4})
    rng = np.random.default_rng(0)
    b, t, h, h_kv, d = 2, 64, 8, 2, 16
    q = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, h_kv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, t, h_kv, d)), jnp.float32)
    ref = full_attention(q, jnp.repeat(k, h // h_kv, axis=2),
                         jnp.repeat(v, h // h_kv, axis=2), causal=True)
    if variant == "ring":
        with mesh:
            got = ring_attention(q, k, v, mesh, causal=True,
                                 head_axis=None)
    else:
        # h_kv=2 not divisible by sp=4 -> pre-expansion fallback; also
        # exercise the narrow path with h_kv=4
        with mesh:
            got = ulysses_attention(q, k, v, mesh, causal=True)
        k4 = jnp.asarray(rng.standard_normal((b, t, 4, d)), jnp.float32)
        v4 = jnp.asarray(rng.standard_normal((b, t, 4, d)), jnp.float32)
        ref4 = full_attention(q, jnp.repeat(k4, 2, axis=2),
                              jnp.repeat(v4, 2, axis=2), causal=True)
        with mesh:
            got4 = ulysses_attention(q, k4, v4, mesh, causal=True)
        np.testing.assert_allclose(np.asarray(got4), np.asarray(ref4),
                                   atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


def test_hybrid_mesh_multislice():
    """Hybrid dcn x ici mesh (VERDICT r4 item 2): 2 virtual slices x 4
    devices; dcn outermost; each ici column stays within one slice's
    device block."""
    import jax
    from ray_tpu.parallel.mesh import build_hybrid_mesh

    mesh = build_hybrid_mesh({"fsdp": 4}, {"dcn": 2})
    assert mesh.axis_names == ("dcn", "fsdp")
    assert mesh.shape == {"dcn": 2, "fsdp": 4}
    devs = jax.devices()
    arr = mesh.devices
    # virtual slices are contiguous device blocks
    assert [d.id for d in arr[0]] == [d.id for d in devs[:4]]
    assert [d.id for d in arr[1]] == [d.id for d in devs[4:8]]


def test_multislice_strategy_allreduce():
    """A dcn-data-parallel + in-slice fsdp strategy trains identically to
    the unsharded computation: psum over ('dcn','fsdp') sums all 8 data
    shards."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.parallel import ShardingStrategy

    strategy = ShardingStrategy(dcn_dp=2, fsdp=4)
    assert strategy.data_axes == ("dcn", "fsdp")
    mesh = strategy.build_mesh()
    x = np.arange(16, dtype=np.float32).reshape(8, 2)
    xs = jax.device_put(x, NamedSharding(mesh, P(("dcn", "fsdp"), None)))

    @jax.jit
    def global_sum(x):
        return jnp.sum(x)

    np.testing.assert_allclose(float(global_sum(xs)), x.sum())


def test_multislice_scaling_config_bundles():
    from ray_tpu.air.config import ScalingConfig

    sc = ScalingConfig(num_workers=4, num_slices=2)
    assert sc.workers_per_slice == 2
    assert len(sc.bundles()) == 2      # one slice's gang
    assert len(sc.total_bundles()) == 4
    import pytest as _pytest

    with _pytest.raises(ValueError):
        ScalingConfig(num_workers=3, num_slices=2).workers_per_slice


# -- activation constraints (PR 34) ----------------------------------------
# `gpt2-large.pretrain-fsdp2tp2` ran for eleven PRs with every activation
# annotation dead: the partitioner split activations by the parameters'
# `embed -> fsdp` axis and all-reduced whole-batch partial sums. These pin
# the repair where it can be seen without a chip: in the lowered text and in
# the collectives of the module compiled for four host devices.

_COLLECTIVE = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = (.*?) (all-reduce|all-gather|all-to-all|"
    r"collective-permute|reduce-scatter)(?:-start)?\(", re.M)
_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "pred": 1}


def _collectives(compiled_text):
    """[(kind, [(dtype, dims), ...], bytes)] of a compiled module."""
    found = []
    for match in _COLLECTIVE.finditer(compiled_text):
        arrays = [(d, tuple(int(n) for n in dims.split(",") if n))
                  for d, dims in re.findall(r"([a-z]+[0-9]*)\[([0-9,]*)\]",
                                            match.group(1))]
        found.append((match.group(2), arrays, sum(
            _DTYPE_BYTES[d] * int(np.prod(dims)) for d, dims in arrays)))
    return found


def _gpt2_large_job(devices):
    """The cell's own builder on the cell's own file, cut to two layers."""
    import json
    import os

    from benchmark import train_cell

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark/configs/gpt2-large.json")) as f:
        config = json.load(f)
    config["n_layer"] = 2
    return train_cell.gpt2_job(config, {"batch": 8, "seq_len": 1024},
                               devices)


def _llama_job(devices):
    """`train_cell.gpt2_job`'s step around `models/llama.py` at the same
    widths (20 heads of 64, rows padded to 50,304, remat)."""
    import flax.linen as nn
    import optax

    from ray_tpu.models import Llama, LlamaConfig
    from ray_tpu.models.gpt import cross_entropy_loss
    from ray_tpu.parallel import logical_axis_rules
    from ray_tpu.parallel.sharding import param_shardings

    cfg = LlamaConfig(vocab_size=50304, n_layer=2, n_head=20, n_kv_head=20,
                      d_model=1280, max_seq_len=1024, remat=True)
    model, tx = Llama(cfg), optax.adamw(3e-4)
    strategy = ShardingStrategy(fsdp=2, tp=2)
    mesh = strategy.build_mesh(list(devices))
    rules = logical_axis_rules(strategy)

    def make_carry(key):
        params = model.init(key, jnp.zeros((8, 1024), jnp.int32))
        return params, tx.init(params)

    with mesh, nn.logical_axis_rules(rules):
        shardings = param_shardings(
            mesh, jax.eval_shape(make_carry, jax.random.PRNGKey(0)), rules)

    def step(carry, data):
        params, opt_state = carry
        loss, grads = jax.value_and_grad(
            lambda p, x, y: cross_entropy_loss(model.apply(p, x), y))(
                params, *data)
        updates, opt_state = tx.update(grads, opt_state, params)
        carry = (optax.apply_updates(params, updates), opt_state)
        return jax.lax.with_sharding_constraint(carry, shardings), loss

    return {"step": step, "make_carry": make_carry, "mesh": mesh,
            "rules": rules, "shardings": shardings}


@pytest.mark.parametrize("family,job_of,bytes_limit", [
    ("gpt", _gpt2_large_job, 1400e6),     # parent: 3,783 MB; now 1,178
    ("llama", _llama_job, 1900e6),        # parent: 4,511 MB; now 1,623
])
def test_four_chip_step_keeps_the_batch_split(family, job_of, bytes_limit):
    """The step of `gpt2-large.pretrain-fsdp2tp2` (two layers), lowered
    through the executable cache for the mesh as `TrainStepRunner` does:
    the model's annotations reach the compiler, so no collective carries
    the whole batch of 8 (a chip holds 4 sequences) nor the logits, and a
    step moves a third of the parent's bytes."""
    import flax.linen as nn
    from jax.sharding import NamedSharding

    from ray_tpu.parallel import ExecutableCache, compiled_step, tracing_for

    job = job_of(jax.devices()[:4])
    mesh = job["mesh"]
    with mesh, nn.logical_axis_rules(job["rules"]):
        carry = jax.eval_shape(
            jax.jit(job["make_carry"], out_shardings=job["shardings"]),
            jax.random.PRNGKey(0))
    data = tuple(jax.ShapeDtypeStruct(
        (8, 1024), jnp.int32, sharding=NamedSharding(mesh, P("fsdp", None)))
        for _ in range(2))
    cache = ExecutableCache()
    step = compiled_step(job["step"], donate_argnums=(0,), mesh=mesh,
                         cache=cache)
    compiled = cache.lookup(step.__wrapped__, (carry, data), {},
                            donate_argnums=(0,), mesh=mesh)
    # the embedding table at its two uses, the embedded tokens, and q, k,
    # v and the block's output in each layer
    (lowering,) = cache.lowerings
    assert {k: lowering[k] for k in (
        "fn", "activation_constraints",
        "activation_constraints_skipped")} == {
        "fn": "step", "activation_constraints": 3 + 4 * 2,
        "activation_constraints_skipped": 0}
    with tracing_for(mesh):
        lowered = jax.jit(job["step"], donate_argnums=0).lower(
            carry, data).as_text()
    on_activations = re.findall(
        r"sdy\.sharding_constraint[^\n]*: tensor<8x1024x[0-9x]+x(?:bf16|f32)>",
        lowered)
    assert len(on_activations) >= 1 + 4 * 2, lowered.count("sdy.shard")
    found = _collectives(compiled.as_text())
    whole_batch = [(kind, arrays) for kind, arrays, _ in found
                   if any(len(dims) >= 3 and dims[0] == 8
                          for _, dims in arrays)]
    assert not whole_batch, whole_batch
    logits = [(kind, arrays) for kind, arrays, _ in found
              if any(dims[-2:] == (1024, 25152) for _, dims in arrays)]
    assert not logits, logits
    assert sum(nbytes for *_, nbytes in found) < bytes_limit


def test_activation_constraint_is_the_identity_without_a_mesh():
    from ray_tpu.parallel import logical_constraint, tracing_for

    x = jnp.ones((4, 8, 16))
    assert logical_constraint(x, ("batch", "seq", "embed")) is x
    seen = []

    def traced(x):
        seen.append(logical_constraint(x, ("batch", "seq", "embed")) is x)
        return x

    with tracing_for(None) as tally:
        jax.jit(traced).lower(x)
    assert seen == [True] and (tally.emitted, tally.skipped) == (0, 1)
    # outside a trace there is nothing to constrain, mesh or no mesh
    with tracing_for(build_mesh({"dp": 4}, jax.devices()[:4])) as tally:
        assert logical_constraint(x, ("batch", "seq", "embed")) is x
    assert (tally.emitted, tally.skipped) == (0, 1)


@pytest.mark.parametrize("program", ["gpt_train_step", "llama_prefill"])
def test_one_chip_programs_hold_no_trace_of_the_constraints(program,
                                                            monkeypatch):
    """With no mesh a program lowers to the text it has with the helper
    patched to the identity: the one-chip cells and the serving engine keep
    the programs (and the machine's compile-cache entries) they had."""
    from ray_tpu.models import GPT, GPTConfig, LlamaConfig, gpt, llama
    from ray_tpu.models.gpt import cross_entropy_loss

    tokens = jnp.zeros((2, 16), jnp.int32)
    if program == "gpt_train_step":
        model = GPT(GPTConfig.tiny())
        args = (jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens),
                tokens)

        def build():
            return lambda params, tokens: jax.value_and_grad(
                lambda p: cross_entropy_loss(model.apply(p, tokens),
                                             tokens))(params)
    else:
        cfg = LlamaConfig.tiny()
        args = (jax.eval_shape(llama.Llama(cfg).init, jax.random.PRNGKey(0),
                               tokens), tokens, jnp.ones((2,), jnp.int32))

        def build():
            return lambda v, tokens, n: llama.prefill_step(v, cfg, tokens, n)

    # a new function each time: jax keeps traces by function
    with_helper = jax.jit(build()).lower(*args).as_text()
    for module in (gpt, llama):     # llama's table is `gpt.embedding_table`
        monkeypatch.setattr(module, "logical_constraint",
                            lambda x, names: x)
    without = jax.jit(build()).lower(*args).as_text()
    assert "sharding_constraint" not in with_helper
    assert with_helper == without


@pytest.mark.parametrize("rules_from", ["the_mesh", "flax"])
def test_a_mesh_axis_the_mesh_lacks_leaves_the_dimension_whole(rules_from):
    """A `dp`-only mesh under rules that send `heads` to `tp`: unsharded,
    not an error; with no rules active the mesh's own axes spell them."""
    import contextlib

    import flax.linen as nn

    from ray_tpu.parallel import (logical_axis_rules, logical_constraint,
                                  tracing_for)

    mesh = build_mesh({"dp": 4}, jax.devices()[:4])
    rules = nn.logical_axis_rules(logical_axis_rules(
        ShardingStrategy(dp=2, tp=2))) if rules_from == "flax" \
        else contextlib.nullcontext()
    x = jnp.ones((8, 16, 4, 8))
    with tracing_for(mesh) as tally, rules:
        text = jax.jit(lambda x: logical_constraint(
            x, ("batch", "seq", "heads", None))).lower(x).as_text()
    assert (tally.emitted, tally.skipped) == (1, 0)
    assert re.search(
        r'sdy\.sharding_constraint %\w+ <@mesh, \[\{"dp"\}, \{\}, \{\}, \{\}\]>',
        text), text
