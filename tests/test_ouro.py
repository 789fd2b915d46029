"""The Ouro family (one stack of layers run `n_pass` times a token, the same
weights in every pass and K/V rows of its own a pass) against its plain
reference, through the paged engine's own cache manager: `n_pass * n_layer`
page layers from `n_layer` weight layers. Tiny widths (3 layers of 64, block
4, chunk 8), float32, seeded weights, on the CPU.

Tolerances: the program and the reference are both float32 here and differ
in the order of their sums (a running softmax over key blocks against one
softmax over all the keys, a fused projection against its slices): logits of
order 0.2 agree to 2e-5 absolute (they read 4e-7 apart). What a lower
precision, or a fault in the loop, moves them by is measured below and is
thousands of times that: every layer matrix rounded to 8-bit floats 0.10, a
pass left out 0.26, a pass that reads the pass before's page layers 0.29.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import ouro as ref
from ray_tpu.models import llama
from ray_tpu.models import ouro as O
from ray_tpu.serve.llm.kv_cache import PagedKVCache

# the steps as the engine runs them: one program a shape
PREFILL = jax.jit(O.prefill_step, static_argnums=1)
CHUNK = jax.jit(O.chunk_step, static_argnums=1)
DECODE = jax.jit(O.decode_step, static_argnums=1)
ATOL = 2e-5
# what a fault has to move the logits by to count as seen: 500 tolerances
SEEN = 500 * ATOL
BLOCK = 4


def tiny(**kw):
    kw.setdefault("dtype", jnp.float32)
    kw.setdefault("param_dtype", jnp.float32)
    return O.OuroConfig.tiny(**kw)


def file_of(cfg: O.OuroConfig) -> dict:
    """The configuration file's keys for `cfg`, as the reference reads."""
    return {
        "num_hidden_layers": cfg.n_layer, "rms_norm_eps": cfg.norm_eps,
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_head,
        "num_key_value_heads": cfg.n_kv_head, "head_dim": cfg.head_dim,
        "rope_theta": cfg.rope_theta, "total_ut_steps": cfg.n_pass,
        "early_exit_threshold": cfg.exit_threshold,
        "check": {"new_tokens": 4}}


def make(cfg, seed=3, n=40):
    """(variables, token ids, the reference's logits and exit distribution
    over them)."""
    variables = O.Ouro(cfg).init(jax.random.PRNGKey(seed),
                                 jnp.ones((1, 8), jnp.int32))
    ids = np.random.default_rng(5).integers(0, cfg.vocab_size, n)
    with jax.default_matmul_precision("highest"):
        want, exits = ref.full_logits(variables["params"], file_of(cfg), ids)
    return variables, ids, np.asarray(want), np.asarray(exits)


@pytest.fixture(scope="module", params=[1, 2, 4],
                ids=["one_pass", "two_passes", "four_passes"])
def case(request):
    cfg = tiny(n_pass=request.param)
    return (cfg,) + make(cfg)


def cache_of(cfg, pages=24):
    return PagedKVCache(pages, O.paged_layers(cfg), BLOCK, cfg.n_kv_head,
                        cfg.head_dim, dtype=np.float32,
                        max_seq_len=cfg.max_seq_len)


def table_of(kv, held):
    table = np.zeros((1, kv.pools[0].width), np.int32)
    table[0, :len(held)] = held
    return table


def prefilled(cfg, variables, ids, n):
    """A cache that holds the first `n` of `ids` (one-shot prefill), its
    page list and table, and the prefill's (logits, k, v, counts)."""
    kv = cache_of(cfg)
    held = kv.alloc(kv.pages_for_tokens(n + 8), "seq")
    toks = np.zeros((1, 32), np.int32)
    toks[0, :n] = ids[:n]
    with jax.default_matmul_precision("highest"):
        out = PREFILL(variables, cfg, toks, np.asarray([n], np.int32))
    kv.write_prefill(held, out[1][0], out[2][0], n)
    return kv, held, table_of(kv, held), out


def decode(cfg, variables, kv, table, token, pos, **kw):
    with jax.default_matmul_precision("highest"):
        return DECODE(variables, cfg, np.asarray([token], np.int32),
                      np.asarray([pos], np.int32), *kv.arena, table, **kw)


def test_reference_matches_the_family_forward(case):
    cfg, variables, ids, want, _ = case
    with jax.default_matmul_precision("highest"):
        got = O.Ouro(cfg).apply(variables,
                                jnp.asarray(ids[None], jnp.int32))[0]
    np.testing.assert_allclose(got, want, atol=ATOL)
    # a model whose logits were all alike would pass any comparison
    assert np.std(want) > 0.05


@pytest.mark.parametrize("how", ["oneshot", "chunked"])
def test_prefill_then_paged_decode_match_the_reference(case, how):
    """The prompt through `prefill_step` or `chunk_step` windows of 8 (the
    last one ragged) into a cache of `n_pass * n_layer` page layers, then
    decode steps through it: every logit row is the reference's full
    pass's."""
    cfg, variables, ids, want, _ = case
    n, steps, c = 27, 9, 8
    kv = cache_of(cfg)
    assert kv.n_layer == cfg.n_pass * cfg.n_layer
    held = kv.alloc(kv.pages_for_tokens(n + steps), "seq")
    table = table_of(kv, held)
    with jax.default_matmul_precision("highest"):
        if how == "oneshot":
            toks = np.zeros((1, 32), np.int32)
            toks[0, :n] = ids[:n]
            logits, k, v, counts = PREFILL(
                variables, cfg, toks, np.asarray([n], np.int32))
            np.testing.assert_allclose(logits[0], want[n - 1], atol=ATOL)
            kv.write_prefill(held, k[0], v[0], n)
        else:
            for start in range(0, n, c):
                take = min(c, n - start)
                toks = np.zeros((1, c), np.int32)
                toks[0, :take] = ids[start:start + take]
                logits, k, v, counts = CHUNK(
                    variables, cfg, toks, np.asarray([start], np.int32),
                    *kv.arena, table)
                np.testing.assert_allclose(
                    logits[0, :take], want[start:start + take], atol=ATOL)
                kv.write_prefill(held, k[0], v[0], take, start)
        assert len(counts) == len(O.STEP_COUNTS)
        for j in range(steps):
            pos = n + j
            logits, k, v, counts = decode(cfg, variables, kv, table,
                                          ids[pos], pos)
            assert k.shape == (1, O.paged_layers(cfg), cfg.n_kv_head,
                               cfg.head_dim)
            kv.append(held, pos, k[0], v[0])
            np.testing.assert_allclose(logits[0], want[pos], atol=ATOL)
    counted = dict(zip(O.STEP_COUNTS, np.asarray(counts).tolist()))
    assert counted["layer_passes"] == cfg.n_pass * cfg.n_layer
    kv.free(held, "seq")
    kv.assert_quiesced()


def eight_bit(tree):
    """Every layer matrix rounded to 8-bit floats (e4m3, one scale an
    output channel), eagerly: under one `jit` XLA removes the f32 -> f8 ->
    f32 pair (`xla_allow_excess_precision`; PERF.md §6, PR 46)."""
    def rounded(w):
        if w.ndim != 2:
            return w
        scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 448.0
        return (w / scale).astype(jnp.float8_e4m3fn).astype(w.dtype) * scale
    return {name: jax.tree_util.tree_map(rounded, sub)
            if name.startswith("layer") else sub
            for name, sub in tree.items()}


def test_eight_bit_weights_fail_the_tolerance():
    """The nearest precision below the stated one is seen: with every layer
    matrix in 8-bit floats the three steps' logits leave the reference's (of
    the weights as they are) by hundreds of tolerances."""
    cfg = tiny(n_pass=4)
    variables, ids, want, _ = make(cfg)
    low = {"params": eight_bit(variables["params"])}
    n = 27
    kv, held, table, (logits, *_) = prefilled(cfg, low, ids, n)
    assert np.abs(np.asarray(logits[0]) - want[n - 1]).max() > SEEN
    got = decode(cfg, low, kv, table, ids[n], n)[0]
    assert np.abs(np.asarray(got[0]) - want[n]).max() > SEEN
    with jax.default_matmul_precision("highest"):
        got = CHUNK(low, cfg, ids[None, n:n + 8].astype(np.int32),
                           np.asarray([n], np.int32), *kv.arena, table)[0]
    assert np.abs(np.asarray(got[0]) - want[n:n + 8]).max() > SEEN


@pytest.mark.parametrize("fault", ["a_pass_left_out", "the_pass_before"])
def test_a_loop_fault_fails_the_tolerance(fault):
    """The two loop faults the chip's control plants: three passes for four,
    and pass t reading pass t - 1's page layers (the arena's page layers
    rolled by one pass). Either moves the decode logits by hundreds of
    tolerances."""
    cfg = tiny(n_pass=4)
    variables, ids, want, _ = make(cfg)
    n = 27
    if fault == "a_pass_left_out":
        cfg = dataclasses.replace(cfg, n_pass=3)
    kv, held, table, _ = prefilled(cfg, variables, ids, n)
    if fault == "the_pass_before":
        kv.arena = tuple(jnp.roll(a, cfg.n_layer, axis=1) for a in kv.arena)
    got = decode(cfg, variables, kv, table, ids[n], n)[0]
    assert np.abs(np.asarray(got[0]) - want[n]).max() > SEEN


def test_seeded_weights_are_drawn_in_float32_then_rounded():
    """`jax.random.normal` in bfloat16 takes 128 distinct values with a mean
    of -0.0117 deviations; every matrix of such weights maps the all-ones
    direction onto itself and the looped stack's stream ends on it whatever
    the prompt (PERF.md §6, PR 48). The family draws in float32 and rounds:
    a matrix's mean lies within four standard errors of 0, and it takes
    more values than that initializer has."""
    cfg = O.OuroConfig.tiny()                         # bf16 parameters
    assert cfg.param_dtype == jnp.bfloat16
    p = O.unboxed_params(O.Ouro(cfg).init(jax.random.PRNGKey(0),
                                          jnp.ones((1, 8), jnp.int32)))
    for name, dev in (("wte", 1.0), ("lm_head", 0.02)):
        w = np.asarray(p[name].astype(jnp.float32))
        assert w.dtype == np.float32 and p[name].dtype == jnp.bfloat16
        assert abs(w.mean()) < 4 * dev / np.sqrt(w.size), name
        assert abs(w.std() / dev - 1) < 0.02, name
        assert len(np.unique(w)) > 1000, name
    for name, gain in zip(("post_attn_norm", "post_mlp_norm"),
                          O.OUTPUT_GAINS):
        np.testing.assert_allclose(
            np.asarray(p["layer0"][name].astype(jnp.float32)),
            gain * (2 * cfg.n_layer) ** -0.5, rtol=4e-3)


def test_a_pass_writes_its_own_page_layers():
    """Page layer `t * L + l` holds what pass t of layer l computed: the
    first L page layers of a two-pass model's rows are the one-pass model's
    (pass 0 knows nothing of a later pass), the next L are not a copy of
    them, and swapping the two passes' page layers in the arena moves the
    decode logits."""
    cfg = tiny(n_pass=2)
    variables, ids, want, _ = make(cfg)
    n, L = 27, cfg.n_layer
    kv, held, table, (_, k2, v2, _) = prefilled(cfg, variables, ids, n)
    one = dataclasses.replace(cfg, n_pass=1)
    _, _, _, (_, k1, v1, _) = prefilled(one, variables, ids, n)
    np.testing.assert_allclose(k2[:, :n, :L], k1[:, :n], atol=1e-6)
    np.testing.assert_allclose(v2[:, :n, :L], v1[:, :n], atol=1e-6)
    assert np.abs(np.asarray(k2[:, :n, L:] - k2[:, :n, :L])).max() > 0.1
    sound = decode(cfg, variables, kv, table, ids[n], n)[0]
    np.testing.assert_allclose(sound[0], want[n], atol=ATOL)
    kv.arena = tuple(jnp.concatenate([a[:, L:], a[:, :L]], axis=1)
                     for a in kv.arena)
    swapped = decode(cfg, variables, kv, table, ids[n], n)[0]
    assert np.abs(np.asarray(swapped - sound)).max() > SEEN


def test_a_page_layer_is_read_by_its_pass_and_layer_alone():
    """Noise in page layer (pass 1, layer 1) of the arena: the decode step's
    new rows of pass 0, and of pass 1 up to and with layer 1 (a layer's key
    is made before its attention reads), are what they were; the rows of
    the layers after it, and the logits, move."""
    cfg = tiny(n_pass=2)
    variables, ids, _, _ = make(cfg)
    n, L = 27, cfg.n_layer
    kv, held, table, _ = prefilled(cfg, variables, ids, n)
    logits, k, v, _ = decode(cfg, variables, kv, table, ids[n], n)
    hit = L + 1
    noise = np.random.default_rng(0).normal(
        size=kv.arena[0][:, hit].shape).astype(np.float32)
    kv.arena = tuple(a.at[:, hit].add(noise) for a in kv.arena)
    logits2, k2, v2, _ = decode(cfg, variables, kv, table, ids[n], n)
    np.testing.assert_array_equal(k2[:, :hit + 1], k[:, :hit + 1])
    np.testing.assert_array_equal(v2[:, :hit + 1], v[:, :hit + 1])
    assert np.abs(np.asarray(k2[:, hit + 1:] - k[:, hit + 1:])).max() > 1e-3
    assert np.abs(np.asarray(logits2 - logits)).max() > SEEN


def test_one_pass_differs_from_llama_only_where_the_equations_say():
    """One pass of the stack on llama's weights (tied head, no gate): up to
    the first residual add the equations are llama's (the first norm, the
    fused projection, the rotation), so layer 0's K and V rows are llama's
    to the bit's neighbourhood; from there on a norm sits on every
    sub-layer's output, so the next layer's rows and the logits are not."""
    cfg = tiny(n_pass=1, n_layer=2)
    lcfg = llama.LlamaConfig.tiny(
        vocab_size=cfg.vocab_size, max_seq_len=cfg.max_seq_len,
        n_kv_head=cfg.n_kv_head, norm_eps=cfg.norm_eps,
        rope_theta=cfg.rope_theta, ffn_mult=cfg.ffn_dim / cfg.d_model,
        dtype=jnp.float32, param_dtype=jnp.float32)
    assert (lcfg.n_layer, lcfg.d_model, lcfg.n_head, lcfg.ffn_dim) == (
        cfg.n_layer, cfg.d_model, cfg.n_head, cfg.ffn_dim)
    theirs = llama.Llama(lcfg).init(jax.random.PRNGKey(1),
                                    jnp.ones((1, 8), jnp.int32))
    lp = llama.unboxed_params(theirs)
    ones = jnp.ones((cfg.d_model,), jnp.float32)
    mine = {"top": {"wte": lp["wte"], "lm_head": lp["wte"].T,
                    "final_norm": lp["final_norm"]["scale"],
                    "exit_gate": jnp.zeros((cfg.d_model, 1), jnp.float32),
                    "exit_bias": jnp.zeros((1,), jnp.float32)}}
    for i in range(cfg.n_layer):
        layer = lp[f"layer{i}"]
        mine[f"layer{i}"] = {
            "attn_norm": layer["attn_norm"]["scale"],
            "attn_qkv": layer["attn_qkv"]["kernel"],
            "attn_out": layer["attn_out"]["kernel"],
            "mlp_norm": layer["mlp_norm"]["scale"],
            "mlp_gate_up": layer["mlp_gate_up"]["kernel"],
            "mlp_down": layer["mlp_down"]["kernel"],
            "post_attn_norm": ones, "post_mlp_norm": ones}
    toks = np.random.default_rng(2).integers(
        0, cfg.vocab_size, (1, 16)).astype(np.int32)
    n = np.asarray([16], np.int32)
    with jax.default_matmul_precision("highest"):
        logits, k, v, _ = PREFILL({"params": mine}, cfg, toks, n)
        l_logits, l_k, l_v = llama.prefill_step(theirs, lcfg, toks, n)
    np.testing.assert_allclose(k[:, :, 0], l_k[:, :, 0], atol=1e-6)
    np.testing.assert_allclose(v[:, :, 0], l_v[:, :, 0], atol=1e-6)
    assert np.abs(np.asarray(k[:, :, 1] - l_k[:, :, 1])).max() > 1e-2
    assert np.abs(np.asarray(logits - l_logits)).max() > 1e-2


def test_the_exit_distribution_sums_to_one_and_is_the_references(case):
    """The gate's `p_t` over the passes sums to 1 at every token, and what
    the steps count of it (`exit_pass_milli`: 1,000 x the expected exit
    pass, summed over the rows that are tokens) is the reference's."""
    cfg, variables, ids, _, exits = case
    assert exits.shape == (cfg.n_pass, len(ids))
    np.testing.assert_allclose(exits.sum(axis=0), 1.0, atol=1e-6)
    assert (exits >= 0).all()
    gates = jax.random.uniform(jax.random.PRNGKey(0), (cfg.n_pass, 5))
    np.testing.assert_allclose(
        O.exit_distribution(gates),
        np.stack(ref.exit_distribution(list(gates))), atol=1e-6)
    n = 27
    expected = (np.arange(1, cfg.n_pass + 1)[:, None] * exits).sum(axis=0)
    if cfg.n_pass > 1:      # a gate that never opened would pass any test
        assert expected[:n].min() < cfg.n_pass - 0.2
    kv, held, table, (_, _, _, counts) = prefilled(cfg, variables, ids, n)
    # every row of the bucket of 32 counts where no `valid` is given: the
    # pad rows hold token 0 at positions 27..31
    valid = np.zeros((1, 32), bool)
    valid[0, :n] = True
    toks = np.zeros((1, 32), np.int32)
    toks[0, :n] = ids[:n]
    with jax.default_matmul_precision("highest"):
        counts = PREFILL(variables, cfg, toks,
                                np.asarray([n], np.int32), valid=valid)[-1]
    counted = dict(zip(O.STEP_COUNTS, np.asarray(counts).tolist()))
    assert abs(counted["exit_pass_milli"] - 1000 * expected[:n].sum()) < 2
    counts = decode(cfg, variables, kv, table, ids[n], n,
                    valid=np.asarray([True]))[-1]
    counted = dict(zip(O.STEP_COUNTS, np.asarray(counts).tolist()))
    assert abs(counted["exit_pass_milli"] - 1000 * expected[n]) < 2


def test_a_threshold_other_than_one_is_refused():
    with pytest.raises(ValueError, match="exit_threshold=0.9.*last pass"):
        tiny(exit_threshold=0.9)
    cfg = tiny()
    variables, ids, _, _ = make(cfg, n=8)
    with pytest.raises(ValueError, match="early_exit_threshold 0.9"):
        ref.full_logits(variables["params"],
                        {**file_of(cfg), "early_exit_threshold": 0.9}, ids)


def test_layer_passes_are_192_a_valid_token_at_the_published_depth():
    """48 layers, 4 passes (narrow, so that the CPU compiles it): a token
    leaves rows in 192 page layers and costs 192 layer applications; a pad
    lane of the bucket costs the program the same and is not counted."""
    cfg = tiny(n_layer=48, n_pass=4, d_model=16, n_head=2, n_kv_head=2,
               ffn_dim=16, vocab_size=32, max_seq_len=32)
    assert O.paged_layers(cfg) == 192
    full = O.OuroConfig()
    assert O.paged_layers(full) == 192 and full.head_dim == 128
    variables = O.Ouro(cfg).init(jax.random.PRNGKey(0),
                                 jnp.ones((1, 4), jnp.int32))
    kv = cache_of(cfg, pages=8)
    table = np.zeros((4, kv.pools[0].width), np.int32)
    valid = np.asarray([True, False, True, True])
    logits, k, v, counts = DECODE(
        variables, cfg, np.asarray([1, 0, 2, 3], np.int32),
        np.zeros(4, np.int32), *kv.arena, table, valid=valid)
    assert k.shape == (4, 192, 2, 8)
    counted = dict(zip(O.STEP_COUNTS, np.asarray(counts).tolist()))
    assert counted["layer_passes"] == 192 * 3


def test_lanes_of_one_bucket_decode_as_they_would_alone():
    """Three sequences of different lengths and a pad lane in one decode
    call: each lane's logits and rows are those of the lane decoded alone."""
    cfg = tiny(n_pass=2)
    variables, ids, want, _ = make(cfg)
    lengths = (27, 5, 14)
    kv = cache_of(cfg, pages=40)
    tables = np.zeros((4, kv.pools[0].width), np.int32)
    for lane, n in enumerate(lengths):
        held = kv.alloc(kv.pages_for_tokens(n + 1), f"seq{lane}")
        toks = np.zeros((1, 32), np.int32)
        toks[0, :n] = ids[:n]
        with jax.default_matmul_precision("highest"):
            _, k, v, _ = PREFILL(variables, cfg, toks,
                                        np.asarray([n], np.int32))
        kv.write_prefill(held, k[0], v[0], n)
        tables[lane, :len(held)] = held
    tokens = np.asarray([ids[n] for n in lengths] + [0], np.int32)
    positions = np.asarray(lengths + (0,), np.int32)
    with jax.default_matmul_precision("highest"):
        logits, k, v, counts = DECODE(
            variables, cfg, tokens, positions, *kv.arena, tables,
            valid=np.asarray([True, True, True, False]))
    for lane, n in enumerate(lengths):
        np.testing.assert_allclose(logits[lane], want[n], atol=ATOL)
        alone = decode(cfg, variables, kv, tables[lane:lane + 1], ids[n], n)
        np.testing.assert_allclose(k[lane], alone[1][0], atol=1e-6)
    assert np.asarray(counts)[0] == 3 * O.paged_layers(cfg)
