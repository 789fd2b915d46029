"""Pallas kernel correctness (interpret mode on the CPU mesh).

The flash-attention kernel must agree with the dense XLA reference
(`full_attention`) in both forward and backward — same contract the
sharded attention variants are held to in test_parallel.py.
"""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.flash_attention import _flash
from ray_tpu.parallel.ring_attention import full_attention


def _qkv(b=2, t=256, h=4, d=64, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    return mk(), mk(), mk()


def _flash_bthd(q, k, v, causal, block_q=128, block_k=128):
    # test through the raw kernel with interpret=True (public wrapper
    # only engages the kernel on real TPU)
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    group = q.shape[2] // k.shape[2]
    out = _flash(qt, kt, vt, q.shape[-1] ** -0.5, causal, block_q,
                 block_k, group, True)
    return out.transpose(0, 2, 1, 3)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_forward_matches_dense(causal):
    q, k, v = _qkv()
    ref = full_attention(q, k, v, causal=causal)
    got = _flash_bthd(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_grads_match_dense(causal):
    q, k, v = _qkv()

    def loss_ref(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=causal) ** 2)

    def loss_fl(q, k, v):
        return jnp.sum(_flash_bthd(q, k, v, causal) ** 2)

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss_fl, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gf):
        scale = float(jnp.abs(a).max())
        np.testing.assert_allclose(np.asarray(b) / scale,
                                   np.asarray(a) / scale, atol=1e-5)


@pytest.mark.parametrize("h_kv", [1, 2])
def test_flash_gqa_matches_expanded_dense(h_kv):
    """Grouped-query attention through the kernel's KV index map must
    equal dense attention over query-side-expanded KV — forward and
    both KV gradients (dK/dV accumulate across each head group)."""
    rng = np.random.default_rng(3)
    b, t, h, d = 2, 256, 4, 64
    q = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, h_kv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, t, h_kv, d)), jnp.float32)
    group = h // h_kv

    def expand(x):
        return jnp.repeat(x, group, axis=2)

    ref = full_attention(q, expand(k), expand(v), causal=True)
    got = _flash_bthd(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)

    def loss_ref(q, k, v):
        return jnp.sum(full_attention(q, expand(k), expand(v),
                                      causal=True) ** 2)

    def loss_fl(q, k, v):
        return jnp.sum(_flash_bthd(q, k, v, causal=True) ** 2)

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss_fl, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gr, gf):
        scale = float(jnp.abs(a).max())
        np.testing.assert_allclose(np.asarray(b_) / scale,
                                   np.asarray(a) / scale, atol=1e-5)


def test_gqa_autoexpand_in_dense_path():
    """full_attention accepts unexpanded GQA KV directly (the Llama
    block passes n_kv_head KV to any attention_fn)."""
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.standard_normal((2, 64, 4, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 64, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 64, 2, 32)), jnp.float32)
    ref = full_attention(q, jnp.repeat(k, 2, axis=2),
                         jnp.repeat(v, 2, axis=2), causal=True)
    got = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref))


def test_flash_block_q_shapes():
    # uneven T falls back to the dense path inside the public wrapper
    from ray_tpu.ops import flash_attention
    q, k, v = _qkv(t=192)  # not divisible by 128
    ref = full_attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_flash_in_gpt_model():
    # the model accepts the kernel as its attention_fn (bench wiring)
    from functools import partial
    from ray_tpu.models import GPT, GPTConfig
    from ray_tpu.ops.flash_attention import flash_attention as fa

    cfg = GPTConfig.tiny()
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 128)))
    dense = GPT(cfg)
    params = dense.init(jax.random.PRNGKey(0), tokens)
    out_dense = dense.apply(params, tokens)
    flash = GPT(cfg, attention_fn=partial(fa, causal=True))
    out_flash = flash.apply(params, tokens)
    # off-TPU the wrapper falls back to dense — outputs must be identical
    np.testing.assert_allclose(np.asarray(out_flash),
                               np.asarray(out_dense), atol=1e-5)


# --------------------------------------------------------------------------
# fused LM-head cross-entropy
# --------------------------------------------------------------------------

def test_fused_ce_matches_reference():
    from ray_tpu.models.gpt import cross_entropy_loss
    from ray_tpu.ops import fused_cross_entropy

    rng = np.random.default_rng(1)
    B, T, D, V = 2, 64, 32, 512
    h = jnp.asarray(rng.standard_normal((B, T, D)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((V, D)), jnp.float32)
    y = np.asarray(rng.integers(0, V, (B, T)), np.int32)
    y[0, :5] = -1  # ignored positions
    y = jnp.asarray(y)

    ref_fn = lambda h, w: cross_entropy_loss(  # noqa: E731
        jnp.einsum("btd,vd->btv", h, w), y)
    fus_fn = lambda h, w: fused_cross_entropy(h, w, y)  # noqa: E731
    np.testing.assert_allclose(float(fus_fn(h, w)), float(ref_fn(h, w)),
                               rtol=1e-5)
    gr = jax.grad(ref_fn, (0, 1))(h, w)
    gf = jax.grad(fus_fn, (0, 1))(h, w)
    for a, b in zip(gr, gf):
        scale = float(jnp.abs(a).max())
        np.testing.assert_allclose(np.asarray(b) / scale,
                                   np.asarray(a) / scale, atol=1e-5)


def test_fused_ce_in_train_step():
    # end-to-end: a tiny GPT trains through the fused head and the loss
    # decreases (the bench.py wiring)
    import optax
    from functools import partial
    from ray_tpu.models import GPT, GPTConfig
    from ray_tpu.ops import fused_cross_entropy

    cfg = GPTConfig.tiny()
    model = GPT(cfg)
    rng = np.random.default_rng(2)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 65)))
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    params = model.init(jax.random.PRNGKey(0), inputs)
    tx = optax.adam(1e-3)
    opt_state = tx.init(params)

    @partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state):
        def loss_fn(p):
            hidden, wte = model.apply(p, inputs, return_hidden=True)
            return fused_cross_entropy(hidden, wte, targets)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    params, opt_state, first = step(params, opt_state)
    for _ in range(20):
        params, opt_state, loss = step(params, opt_state)
    assert float(loss) < float(first)


@pytest.mark.parametrize("bq,bk", [(128, 256), (256, 128)])
def test_flash_asymmetric_blocks(bq, bk):
    """Chunked-KV online softmax with block_q != block_k (the causal
    chunk-skip predicate must be right for partial diagonal overlaps)."""
    q, k, v = _qkv(t=512, seed=9)

    ref = full_attention(q, k, v, causal=True)
    got = _flash_bthd(q, k, v, causal=True, block_q=bq, block_k=bk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)

    def loss_ref(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=True) ** 2)

    def loss_fl(q, k, v):
        return jnp.sum(_flash_bthd(q, k, v, causal=True,
                                   block_q=bq, block_k=bk) ** 2)

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss_fl, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gr, gf):
        scale = float(jnp.abs(a).max())
        np.testing.assert_allclose(np.asarray(b_) / scale,
                                   np.asarray(a) / scale, atol=1e-5)


def test_flash_gqa_with_asymmetric_blocks():
    """The riskiest composition: GQA head-group folding in the dK/dV
    kernel (hk*group + jj//nq index arithmetic) together with
    block_q != block_k causal skipping."""
    rng = np.random.default_rng(11)
    b, t, h, h_kv, d = 2, 512, 4, 2, 64
    q = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, h_kv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, t, h_kv, d)), jnp.float32)

    def expand(x):
        return jnp.repeat(x, h // h_kv, axis=2)

    def loss_ref(q, k, v):
        return jnp.sum(full_attention(q, expand(k), expand(v),
                                      causal=True) ** 2)

    def loss_fl(q, k, v):
        return jnp.sum(_flash_bthd(q, k, v, causal=True,
                                   block_q=128, block_k=256) ** 2)

    ref = full_attention(q, expand(k), expand(v), causal=True)
    got = _flash_bthd(q, k, v, causal=True, block_q=128, block_k=256)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss_fl, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gr, gf):
        scale = float(jnp.abs(a).max())
        np.testing.assert_allclose(np.asarray(b_) / scale,
                                   np.asarray(a) / scale, atol=1e-5)


# -- the grouped expert product ------------------------------------------------

def _grouped_case(rows, held, sizes, k=128, n=256, seed=0):
    rng = np.random.default_rng(seed)
    lhs = jnp.asarray(rng.standard_normal((rows, k)), jnp.bfloat16)
    rhs = jnp.asarray(rng.standard_normal((held, k, n)) / np.sqrt(k),
                      jnp.bfloat16)
    return lhs, rhs, jnp.asarray(sizes, jnp.int32)


def _visits_by_hand(sizes, tile):
    """(row tile, group) pairs that hold a row, one group at a time."""
    start, visits = 0, 0
    for size in sizes:
        if size:
            visits += (start + size - 1) // tile - start // tile + 1
        start += size
    return visits


def _walked(lhs, rhs, group_sizes, **kw):
    """The kernel in interpret mode over the walk `parallel.moe` makes."""
    from ray_tpu.ops.grouped_matmul import grouped_matmul
    from ray_tpu.parallel import moe

    rows = lhs.shape[0]
    walk = moe.tile_walk(*moe.group_tiles(group_sizes, rows), rows)
    return grouped_matmul(lhs, rhs, walk, tm=moe.ROW_TILE, interpret=True,
                          **kw)


def _spread(held, live, seed):
    return np.random.default_rng(seed).multinomial(
        live, np.ones(held) / held).tolist()


@pytest.mark.parametrize("rows, held, sizes", [
    (256, 8, [0, 0, 0, 40, 0, 0, 0, 0]),            # every group empty but one
    (256, 12, [0] * 12),                            # no local pair at all
    (1024, 12, _spread(12, 37, 1)),                 # n_local well under rows
    (256, 8, _spread(8, 256, 2)),                   # n_local equal to rows
    (512, 8, [5, 0, 300, 0, 0, 1, 0, 7]),           # a group over three tiles
    (128, 8, _spread(8, 4, 3)),                     # a decode bucket of Kimi's
    (128, 128, _spread(128, 100, 4)),
    (8192, 128, _spread(128, 2048, 5)),             # a chunk a quarter live
    (8, 12, [1, 0, 2, 0, 0, 0, 1, 0, 0, 0, 0, 1]),  # less than a tile: padded
], ids=["one_group", "nothing_local", "few_local", "all_local",
        "group_spans_three_tiles", "rows_128_held_8", "rows_128_held_128",
        "rows_8192_held_128", "rows_8_held_12"])
def test_grouped_matmul_matches_ragged_dot(rows, held, sizes):
    """The Pallas grouped product (interpret mode) against `lax.ragged_dot`:
    equal to bf16's last place on the rows that belong to a group, whatever
    lies past them; and `tile_visits` is the count made by hand."""
    from ray_tpu.parallel.moe import ROW_TILE, group_tiles

    k, n = (128, 256) if rows < 8192 else (64, 128)
    lhs, rhs, group_sizes = _grouped_case(rows, held, sizes, k, n)
    got = jax.jit(_walked)(lhs, rhs, group_sizes)
    want = jax.lax.ragged_dot(lhs, rhs, group_sizes)
    assert got.shape == want.shape == (rows, n) and got.dtype == jnp.bfloat16
    live = sum(sizes)
    got, want = (np.asarray(a[:live], np.float32) for a in (got, want))
    # one place of bf16: 2**-8 of the value (2**-7 across a binade's edge)
    np.testing.assert_allclose(got, want, rtol=2**-7, atol=2**-9)
    assert int(group_tiles(group_sizes, rows)[2].sum()) \
        == _visits_by_hand(sizes, ROW_TILE)
    assert _visits_by_hand(sizes, ROW_TILE) <= -(-rows // ROW_TILE) + held - 1


def test_grouped_matmul_splits_the_contraction():
    """A matrix wider than one weight block is streamed in `[tk, n]` blocks
    and accumulated in float32: same result."""
    from ray_tpu.ops import grouped_matmul as G

    sizes = [3, 0, 130, 20]
    lhs, rhs, group_sizes = _grouped_case(256, 4, sizes, k=512, n=128)
    with mock.patch.object(G, "WEIGHT_BLOCK_BYTES", 128 * 128 * 2):
        assert G._k_tile(512, 128, 2) == 128
        got = _walked(lhs, rhs, group_sizes)
    want = jax.lax.ragged_dot(lhs, rhs, group_sizes)
    np.testing.assert_allclose(
        np.asarray(got[:153], np.float32), np.asarray(want[:153], np.float32),
        rtol=2**-7, atol=2**-9)


@pytest.mark.parametrize("n, held, first, valid_rows", [
    (40, 16, 0, 40), (40, 4, 8, 40), (40, 4, 4, 31), (24, 16, 0, 19)])
def test_expert_shard_layer_is_the_same_on_both_products(n, held, first,
                                                         valid_rows):
    """`expert_shard_layer` whole, over `lax.ragged_dot` (this backend) and as
    on the TPU (the kernel, in interpret mode, from `ROW_TILE` sorted rows up;
    below that `lax.ragged_dot` there too): the same result, the same
    `MOE_COUNTS`, and `tile_visits` is the count made in numpy from the pairs
    the router keeps here."""
    from functools import partial

    from ray_tpu.ops import grouped_matmul as G
    from ray_tpu.parallel import moe

    rng = np.random.default_rng(held + first)
    d, f, n_experts, top_k = 128, 64, 16, 4
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.bfloat16)
    router = rng.standard_normal((d, n_experts)).astype(np.float32) / 8
    bias = np.zeros(n_experts, np.float32)
    experts = {
        "gate_up": jnp.asarray(rng.standard_normal((held, d, 2 * f)) / 8,
                               jnp.bfloat16),
        "down": jnp.asarray(rng.standard_normal((held, f, d)) / 8,
                            jnp.bfloat16)}
    valid = np.arange(n) < valid_rows
    layer = partial(moe.expert_shard_layer, x, router, bias, experts, first,
                    n_experts, top_k, 1.0, valid=valid)
    plain, plain_counts = layer()
    kernel = mock.Mock(wraps=partial(G.grouped_matmul, interpret=True))
    with mock.patch.object(jax, "default_backend", lambda: "tpu"), \
            mock.patch.object(G, "grouped_matmul", kernel):
        walked, walked_counts = layer()
    # both products of a layer, or neither below one row tile
    assert kernel.call_count == (2 if n * top_k >= moe.ROW_TILE else 0)
    np.testing.assert_allclose(np.asarray(walked, np.float32),
                               np.asarray(plain, np.float32),
                               rtol=2**-6, atol=2**-8)
    np.testing.assert_array_equal(walked_counts, plain_counts)
    expert, _ = moe.sigmoid_topk_route(x, router, bias, top_k, 1.0)
    expert = np.asarray(expert)[valid] - first
    sizes = [int(np.sum(expert == e)) for e in range(held)]
    counts = dict(zip(moe.MOE_COUNTS, np.asarray(plain_counts).tolist()))
    assert counts == {
        "pairs_routed": valid_rows * top_k, "pairs_local": sum(sizes),
        "pairs_computed": sum(sizes),
        "expert_calls": sum(size > 0 for size in sizes),
        "tile_visits": _visits_by_hand(sizes, moe.ROW_TILE)}


def test_the_engine_and_the_families_import_no_pallas():
    """The kernel is imported where a program on a TPU is traced through an
    expert layer and nowhere sooner: a process that serves `llama`, `ouro` or
    `brumby`, a driver, a CPU test never pays Pallas's second of imports."""
    import subprocess
    import sys

    code = ("import sys, ray_tpu.parallel.moe, ray_tpu.models.layers, "
            "ray_tpu.serve.llm.engine as e\n"
            "[e.model_family(name) for name in e.MODEL_FAMILIES]\n"
            "print([m for m in sys.modules if 'pallas' in m])")
    out = subprocess.run([sys.executable, "-c", code], check=True,
                         capture_output=True, text=True).stdout
    assert out.strip() == "[]", out
