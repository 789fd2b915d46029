"""The MiMo-V2 family (window layers with a learned sink beside full layers,
the two kinds with K/V head counts of their own, key rows wider than value
rows, rope on a part of every head with a base a kind, sigmoid-routed
experts held as one chip's share and no shared expert) against its plain
reference, through the paged engine's own cache manager: pages by layer
kind at each kind's own row shapes, the window kind a ring. Tiny widths
(window 8, block 4, chunk 8, K rows of 4 x 24 and 2 x 24, V rows of 4 x 16
and 2 x 16, contexts past three windows), seeded weights, on the CPU.

Tolerances: in float32 the program and the reference differ in the order
of their sums (a grouped product against a loop over experts, a running
softmax that starts from the sink against one denominator over all the
keys): logits of order 1 agree to 1e-4 absolute, which a left-out sink, a
key one position outside the window, V of another head or a dropped pair
misses by two orders of magnitude and more. With bfloat16 activations
(weights float32, the reference float32) seven bits of mantissa through
four layers leave 0.05; every planted fault is over that too.
"""

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import mimo_v2 as ref
from ray_tpu.models import layers
from ray_tpu.models import mimo_v2 as M
from ray_tpu.models.llama import key_block_pairs, key_block_trips
from ray_tpu.parallel.moe import MOE_COUNTS
from ray_tpu.serve.llm.kv_cache import PagedKVCache, PageKind

ATOL = {jnp.float32: 1e-4, jnp.bfloat16: 5e-2}
BLOCK = 4


def tiny(**kw):
    kw.setdefault("dtype", jnp.float32)
    kw.setdefault("param_dtype", jnp.float32)
    return M.MimoV2Config.tiny(**kw)


def file_of(cfg: M.MimoV2Config) -> dict:
    """The configuration file's keys for `cfg`, as the reference reads."""
    return {
        "num_hidden_layers": cfg.n_layer, "layernorm_epsilon": cfg.norm_eps,
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_head,
        "num_key_value_heads": cfg.n_kv_head,
        "swa_num_key_value_heads": cfg.n_kv_head_window,
        "head_dim": cfg.head_dim, "v_head_dim": cfg.v_head_dim,
        # floor(0.334 x 24) = 8, as floor(0.334 x 192) = 64
        "partial_rotary_factor": 0.334,
        "rope_theta": cfg.rope_theta, "swa_rope_theta": cfg.rope_theta_window,
        "attention_value_scale": cfg.value_scale,
        "sliding_window": cfg.window,
        "hybrid_layer_pattern": [int(t == M.WINDOW) for t in cfg.types],
        "moe_layer_freq": [int(i >= cfg.n_dense_layer)
                           for i in range(cfg.n_layer)],
        "add_swa_attention_sink_bias": True,
        "add_full_attention_sink_bias": False,
        "num_experts_per_tok": cfg.top_k, "routed_scaling_factor": None,
        "scoring_func": "sigmoid", "norm_topk_prob": True, "n_group": 1,
        "n_shared_experts": None,
        "deployment_share": {"first_expert": cfg.first_expert},
        "check": {"new_tokens": 4}}


def make(cfg, seed=3, n=60):
    """(variables, token ids, the reference's logits over them)."""
    variables = M.MimoV2(cfg).init(jax.random.PRNGKey(seed),
                                   jnp.ones((1, 8), jnp.int32))
    ids = np.random.default_rng(5).integers(0, cfg.vocab_size, n)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.full_logits(variables["params"], file_of(cfg),
                                          ids))
    return variables, ids, want


CASES = {
    "mixed": {},                                    # f w f w, 1 dense
    "share_4_of_16": {"experts_held": 4, "first_expert": 8},
    "all_window": {"layer_types": (M.WINDOW,) * 4},
    "all_full": {"layer_types": (M.FULL,) * 4},
    "mixed_bf16": {"dtype": jnp.bfloat16},
}


@pytest.fixture(scope="module", params=list(CASES), ids=list(CASES))
def case(request):
    cfg = tiny(**CASES[request.param])
    return (cfg,) + make(cfg)


def cache_of(cfg, pages=40, seqs=2):
    kinds = tuple(PageKind(*k) for k in M.page_kinds(cfg))
    return PagedKVCache(pages, 0, BLOCK, kinds=kinds, dtype=np.float32,
                        seq_slots=seqs, max_seq_len=cfg.max_seq_len)


def tables_of(kv, held):
    tables = []
    for pool, pages in zip(kv.pools, held):
        table = np.zeros((1, pool.width), np.int32)
        table[0, :len(pages)] = pages
        tables.append(table)
    return tables


def write(kv, held, rows, n, start=0):
    for kind, pool in enumerate(kv.pools):
        kv.write_rows(held[kind], [r[0] for r in rows[pool.arrays]], n,
                      start, kind=kind)


def test_the_tiny_config_has_what_the_published_one_has():
    """Two kinds whose arrays differ in shape, K wider than V, rope on the
    leading third, and the published pattern's rule (layer 0 full, then
    every sixth)."""
    cfg = tiny()
    assert M.page_kinds(cfg) == (
        ("window", 2, ((4 * 24,), (4 * 16,)), 8),
        ("full", 2, ((2 * 24,), (2 * 16,)), None))
    assert M.layer_slots(cfg) == ((1, 0), (0, 0), (1, 1), (0, 1))
    big = M.MimoV2Config()
    assert big.rope_dim == int(0.334 * big.head_dim) == 64
    assert [i for i, t in enumerate(big.types) if t == M.FULL] == [
        0, 5, 11, 17, 23, 29, 35, 41, 47]
    assert M.page_kinds(big) == (
        ("window", 39, ((8 * 192,), (8 * 128,)), 128),
        ("full", 9, ((4 * 192,), (4 * 128,)), None))
    kv = cache_of(cfg)
    # a row is stored flat: whole TPU tiles at the published widths
    assert [a.shape for a in kv.arena] == [
        (6, 2, 4, 96), (6, 2, 4, 64), (40, 2, 4, 48), (40, 2, 4, 32)]
    # bytes follow each array's own rows
    assert kv.arena_nbytes == 4 * (6 * 2 * 4 * 4 * 40 + 40 * 2 * 4 * 2 * 40)


def test_reference_matches_the_family_forward(case):
    cfg, variables, ids, want = case
    with jax.default_matmul_precision("highest"):
        got = M.MimoV2(cfg).apply(variables,
                                  jnp.asarray(ids[None], jnp.int32))[0]
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               atol=ATOL[cfg.dtype], rtol=1e-4)
    # a model whose logits were all alike would pass any comparison
    assert np.std(want) > 0.05


REFERENCE_MOVES = {
    # a query sees itself and 7 before it: position 7 is the last whose
    # window of 8 holds everything, and a window of 9 changes position 8 on
    "window": ({"sliding_window": 9}, 8),
    "sink": ({"add_swa_attention_sink_bias": False}, 0),
    # (at these widths scores are near 0 and a base of 1e4 against 1e7 moves
    # a logit by 2e-4: the window layers of this case turn by a base of 2)
    "rope_base": ({"swa_rope_theta": 1e7}, 1),
    "rope_part": ({"partial_rotary_factor": 0.5}, 1),
    "value_scale": ({"attention_value_scale": 1.0}, 0),
}


@pytest.mark.parametrize("what", sorted(REFERENCE_MOVES))
def test_each_mechanism_shows_in_the_reference(what):
    """What the other tests would miss if program and reference shared a
    fault: the reference's logits move, from the expected position on, when
    the window does, when the sink is left out (its weights dropped), when
    a window layer takes a full layer's rope base, when rope turns half of a
    head, and when the value scale is 1."""
    cfg = tiny(rope_theta_window=2.0) if what == "rope_base" else tiny()
    variables, ids, want = make(cfg)
    params = variables["params"]
    changed, first = REFERENCE_MOVES[what]
    if what == "sink":
        params = {name: {k: v for k, v in group.items() if k != "sink"}
                  for name, group in params.items()}
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.full_logits(
            params, {**file_of(cfg), **changed}, ids))
    np.testing.assert_allclose(got[:first], want[:first], atol=1e-6)
    assert np.abs(got[first:] - want[first:]).max() > 20 * ATOL[jnp.float32]


@pytest.mark.parametrize("how", ["oneshot", "chunked"])
def test_prefill_then_paged_decode_match_the_reference(case, how):
    """The prompt through `prefill_step` or `chunk_step` windows of 8 (the
    last one ragged) into a cache of pages by kind, each kind at its own
    rows, then decode steps through it: every logit row is the reference's
    full pass's. 39 prompt tokens and 15 decoded are past six windows of 8,
    and the window kind's ring of 3 pages (12 positions) wraps four
    times."""
    cfg, variables, ids, want = case
    atol = ATOL[cfg.dtype]
    n, steps, c = 39, 15, 8
    kv = cache_of(cfg)
    owner = object()
    held = kv.reserve(n + steps, owner)
    for pool, pages in zip(kv.pools, held):
        assert len(pages) == (3 if pool.kind.window else 14)
    tables = tables_of(kv, held)
    with jax.default_matmul_precision("highest"):
        if how == "oneshot":
            toks = np.zeros((1, 48), np.int32)
            toks[0, :n] = ids[:n]
            logits, *rows, counts = M.prefill_step(
                variables, cfg, toks, np.asarray([n], np.int32))
            np.testing.assert_allclose(np.asarray(logits[0], np.float32),
                                       want[n - 1], atol=atol)
            write(kv, held, rows, n)
        else:
            for start in range(0, n, c):
                take = min(c, n - start)
                toks = np.zeros((1, c), np.int32)
                toks[0, :take] = ids[start:start + take]
                logits, *rows, counts = M.chunk_step(
                    variables, cfg, toks, np.asarray([start], np.int32),
                    *kv.arena, *tables)
                np.testing.assert_allclose(
                    np.asarray(logits[0, :take], np.float32),
                    want[start:start + take], atol=atol)
                write(kv, held, rows, take, start)
        assert len(counts) == len(M.STEP_COUNTS)
        for j in range(steps):
            pos = n + j
            logits, *rows, counts = M.decode_step(
                variables, cfg, np.asarray([ids[pos]], np.int32),
                np.asarray([pos], np.int32), *kv.arena, *tables)
            for kind, pool in enumerate(kv.pools):
                kv.append(held[kind], pos, *[r[0] for r in rows[pool.arrays]],
                          kind=kind)
            np.testing.assert_allclose(np.asarray(logits[0], np.float32),
                                       want[pos], atol=atol)
    got = dict(zip(M.STEP_COUNTS, np.asarray(counts).tolist()))
    n_window = cfg.types.count(M.WINDOW)
    # a decode step's walk: a window layer never past its ring
    assert got["key_slots_window"] <= n_window * (1 + 3 * BLOCK)
    # a full layer as far as the block that holds the last position
    trips, keys = key_block_trips(np.asarray([n + steps - 1]),
                                  cfg.max_seq_len // BLOCK, BLOCK, np)
    assert got["key_slots_full"] == (cfg.n_layer - n_window) * (
        1 + int(trips) * keys)
    # one live lane: the sink's share of a row's mass, per mille (8 keys of
    # weight near 1 beside sinks near 128)
    assert (0 < got["sink_mass_milli"] < 1000) == bool(n_window)
    kv.release(held, owner)
    kv.assert_quiesced()


# cached positions of the lanes of one decode call (pages of 4): none (a
# pad lane of the bucket), under a ring, past the wrap of a ring of 12
# positions, past a key block of 256, and one much longer than the others
LANES = (0, 7, 100, 300, 700)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
def test_lanes_of_mixed_lengths_walk_their_own_blocks(dtype):
    """One decode call over `LANES`, through both kinds' pages: every
    lane's logits are those of the lane decoded alone and the reference's;
    the step counts each live lane's own key and its own blocks, a kind; a
    pad lane adds nothing to the sink's count."""
    cfg = tiny(max_seq_len=1024, dtype=dtype)
    atol = ATOL[dtype]
    longest = max(LANES)
    variables, ids, want = make(cfg, n=longest + 1)
    kv = cache_of(cfg, pages=sum(-(-(n + 1) // BLOCK) for n in LANES),
                  seqs=len(LANES))
    live = np.asarray(LANES) > 0
    tables = [np.zeros((len(LANES), pool.width), np.int32)
              for pool in kv.pools]
    with jax.default_matmul_precision("highest"):
        toks = np.zeros((1, longest + 4), np.int32)
        toks[0, :longest] = ids[:longest]
        _, *rows, _ = M.prefill_step(variables, cfg, toks,
                                     np.asarray([longest], np.int32))
        for lane, n in enumerate(LANES):
            if n:       # a position's K and V follow from the tokens before
                held = kv.reserve(n + 1, lane)
                write(kv, held, rows, n)
                for table, pages in zip(tables, held):
                    table[lane, :len(pages)] = pages
        positions = np.asarray(LANES, np.int32)
        tokens = np.where(live, ids[positions], 0).astype(np.int32)
        logits, *_, counts = M.decode_step(
            variables, cfg, tokens, positions, *kv.arena, *tables,
            valid=live)
        alone_mass = 0
        for lane in np.flatnonzero(live):
            alone, *_, n = M.decode_step(
                variables, cfg, tokens[lane:lane + 1],
                positions[lane:lane + 1], *kv.arena,
                *[t[lane:lane + 1] for t in tables],
                valid=np.ones(1, bool))
            alone_mass += int(n[-1])
            np.testing.assert_allclose(np.asarray(logits[lane], np.float32),
                                       np.asarray(alone[0], np.float32),
                                       atol=atol)
            np.testing.assert_allclose(np.asarray(logits[lane], np.float32),
                                       want[LANES[lane]], atol=atol)
    got = dict(zip(M.STEP_COUNTS, np.asarray(counts).tolist()))
    for pool in kv.pools:
        blocks, *_, keys = key_block_pairs(positions, pool.width, BLOCK, np)
        assert got[f"key_slots_{pool.kind.name}"] == pool.kind.n_layer * (
            live.sum() + blocks.sum() * keys)
    # the step's count is the mean over its live lanes
    assert abs(got["sink_mass_milli"] - alone_mass / live.sum()) <= 1


def _one_key(window, start, back, sink):
    """`window_attend`'s output for one query at position `start` (two
    groups of two query heads, K rows of 8 and V rows of 4) against cached
    keys that are all zeros but the one at `start - back`, which scores
    high and whose value is marked."""
    rng = np.random.default_rng(0)
    kvh, h, d, dv, ring = 2, 4, 8, 4, 3
    q = jnp.asarray(rng.normal(size=(1, 1, h, d)), jnp.float32)
    k_new = jnp.zeros((1, 1, kvh, d), jnp.float32)
    v_new = jnp.zeros((1, 1, kvh, dv), jnp.float32)
    n_pages = ring if window else 16
    j = start - back
    k_pages = np.zeros((6, 1, BLOCK, kvh, d), np.float32)
    v_pages = np.zeros((6, 1, BLOCK, kvh, dv), np.float32)
    if window:
        table = np.asarray([[4, 1, 3]], np.int32)
        slot = (j // BLOCK) % ring
    else:
        slot = j // BLOCK
        table = np.zeros((1, n_pages), np.int32)
        table[0, slot] = 5
    page = table[0, slot]
    k_pages[page, 0, j % BLOCK, 0] = np.asarray(q[0, 0, 0]) * 5
    v_pages[page, 0, j % BLOCK, 0] = 1.0
    return M.window_attend(
        q, k_new, v_new, (jnp.asarray(k_pages), jnp.asarray(v_pages)), 0,
        jnp.asarray(table), jnp.asarray([start], jnp.int32), window=window,
        scale=1.0, sink=sink, value_scale=0.5)


@pytest.mark.parametrize("window", [None, 8])
def test_mask_is_exact_at_both_edges_of_the_window_with_the_sink(window):
    """The one key shows in the output exactly where i - j < window (j =
    start - 7 is in, start - 8 is out), whatever lap of the ring it lies in,
    with a sink in every row's denominator: the sink takes mass and adds
    no key, so a row that sees no cached key but its own zero one is all
    zeros. V's rows are narrower than K's and the output is [1, 1, 4 x 4]."""
    sink = jnp.asarray([0.5, -1.0, 2.0, 0.0], jnp.float32)
    for start in (9, 12, 13, 30):
        for back in (1, 7, 8, 9):
            out, _, mass = _one_key(window, start, back, sink)
            assert out.shape == (1, 1, 16) and mass.shape == (1, 1, 4)
            seen = float(np.abs(np.asarray(out)).max()) > 1e-3
            assert seen == (window is None or back < window), \
                (window, start, back)
            assert np.all((0 < np.asarray(mass)) & (np.asarray(mass) < 1))


@pytest.mark.parametrize("sink, share", [(-np.inf, 0.0), (-30.0, 0.0),
                                         (10.0, None), (90.0, 1.0)])
def test_the_sink_is_a_term_of_the_denominator(sink, share):
    """A sink of -inf (or far under every score) gives the plain softmax,
    to the bit what no sink gives; a large one drains the row: its output
    goes to zero and the sink's share of the mass to 1; in between the
    output is the plain one times (1 - the sink's share)."""
    plain, _, none = _one_key(8, 13, 3, None)
    out, _, mass = _one_key(8, 13, 3, jnp.full((4,), sink, jnp.float32))
    assert none is None
    plain, out, mass = (np.asarray(a)[0, 0] for a in (plain, out, mass))
    if share == 0.0:
        np.testing.assert_array_equal(out, plain)
        np.testing.assert_allclose(mass, 0.0, atol=1e-12)
    elif share == 1.0:
        np.testing.assert_allclose(out, 0.0, atol=1e-12)
        np.testing.assert_allclose(mass, 1.0, atol=1e-6)
    else:
        assert np.all((0 < mass) & (mass < 1)) and np.ptp(mass) > 0.1
        np.testing.assert_allclose(
            out.reshape(4, 4), plain.reshape(4, 4) * (1 - mass)[:, None],
            atol=1e-6)


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    """An expert layer's routed part summed over the 16 shares of one expert
    each is the layer with all 16 held: there is no shared expert to count
    once, and a share's result is its experts' part alone."""
    cfg = tiny()
    variables, _, _ = make(cfg)
    lp = M.unboxed_params(variables)["layer2"]
    assert "shared_gate_up" not in lp and cfg.n_shared == 0
    h = jnp.asarray(np.random.default_rng(2).normal(size=(24, cfg.d_model)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, counts = layers.routed_feed_forward(lp, cfg, 2, h, None)
        total, local = 0.0, 0
        for first in range(16):
            share = dataclasses.replace(cfg, experts_held=1,
                                        first_expert=first)
            part = {**lp, "experts_gate_up": lp["experts_gate_up"][
                first:first + 1], "experts_down": lp["experts_down"][
                first:first + 1]}
            y, n = layers.routed_feed_forward(part, share, 2, h, None)
            total = total + y
            local += int(n[MOE_COUNTS.index("pairs_local")])
    np.testing.assert_allclose(total, whole, atol=1e-5)
    assert local == 24 * cfg.top_k == int(
        counts[MOE_COUNTS.index("pairs_routed")])


def to_8_bits(tree):
    """Every matrix rounded to 8-bit floats (e4m3: 4 significant bits, the
    smallest normal 2^-6, steps of 2^-9 under it) with one scale an output
    channel (the chip's control of `check.shortfall_limit` rounds the same
    way). Rounded by arithmetic, in float32: a v5e has no such type, and a
    cast there and back compiled for it left bf16 weights as they were (the
    control's first readings on the chip were the sound program's to the
    digit)."""
    def cast(a):
        if a.ndim < 2:
            return a
        x = a.astype(jnp.float32)
        scale = jnp.max(jnp.abs(x), axis=-2, keepdims=True) / 448.0
        x = x / scale
        _, exponent = jnp.frexp(x)              # |x| in [2^(e-1), 2^e)
        step = jnp.exp2(jnp.maximum(exponent, -5).astype(jnp.float32) - 4)
        return (jnp.round(x / step) * step * scale).astype(a.dtype)
    return jax.tree_util.tree_map(cast, tree)


def test_eight_bit_rounding_is_the_types_own():
    """`to_8_bits`' arithmetic gives what a cast to `float8_e4m3fn` and back
    gives where the backend has the type (here, the CPU)."""
    a = jnp.asarray(np.random.default_rng(0).normal(size=(64, 48)) * 0.02,
                    jnp.float32)
    scale = jnp.max(jnp.abs(a), axis=-2, keepdims=True) / 448.0
    want = (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    got = to_8_bits({"w": a})["w"]
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=0)
    assert float(jnp.abs(got - a).max()) > 1e-4


@contextlib.contextmanager
def planted(fault):
    """`models/mimo_v2.py` with one fault in what is new in it, for a
    control that has to fail: the steps trace the module's `window_attend`
    when they are compiled, so an engine built inside computes the fault.
    (The chip's readings of `check.shortfall_limit` plant the same three.)"""
    sound = M.window_attend

    def attend(q, k, v, pages, *args, window, sink, **kw):
        if fault == "sink_left_out":
            out, slots, _ = sound(q, k, v, pages, *args, window=window,
                                  sink=None, **kw)
            return out, slots, None
        if fault == "window_one_too_wide" and window is not None:
            window += 1
        if fault == "v_at_the_full_kinds_head_count" and window is not None:
            # a window layer's V read as if the kind had the full kind's
            # K/V heads: query group g reads V head g // 2 of the first half
            def halved(x):
                return jnp.repeat(x[..., :x.shape[-2] // 2, :], 2, axis=-2)
            v = halved(v)
            if pages is not None:
                pages = (pages[0], halved(pages[1]))
        return sound(q, k, v, pages, *args, window=window, sink=sink, **kw)

    if fault not in FAULTS:
        raise ValueError(fault)
    M.window_attend = attend
    try:
        yield
    finally:
        M.window_attend = sound


FAULTS = ("sink_left_out", "v_at_the_full_kinds_head_count",
          "window_one_too_wide")


def _paged_worst(cfg, variables, ids, want, n=39, steps=6, c=8):
    """The largest miss of the chunked prompt's and the decode steps' logit
    rows against the reference."""
    kv = cache_of(cfg)
    held = kv.reserve(n + steps, "seq")
    tables = tables_of(kv, held)
    worst = 0.0
    with jax.default_matmul_precision("highest"):
        for start in range(0, n, c):
            take = min(c, n - start)
            toks = np.zeros((1, c), np.int32)
            toks[0, :take] = ids[start:start + take]
            logits, *rows, _ = M.chunk_step(
                variables, cfg, toks, np.asarray([start], np.int32),
                *kv.arena, *tables)
            worst = max(worst, float(np.abs(
                np.asarray(logits[0, :take], np.float32)
                - want[start:start + take]).max()))
            write(kv, held, rows, take, start)
        for pos in range(n, n + steps):
            logits, *rows, _ = M.decode_step(
                variables, cfg, np.asarray([ids[pos]], np.int32),
                np.asarray([pos], np.int32), *kv.arena, *tables)
            for kind, pool in enumerate(kv.pools):
                kv.append(held[kind], pos, *[r[0] for r in rows[pool.arrays]],
                          kind=kind)
            worst = max(worst, float(np.abs(
                np.asarray(logits[0], np.float32) - want[pos]).max()))
    return worst


@pytest.mark.parametrize("fault", FAULTS + ("eight_bit_weights",))
def test_a_fault_in_the_mechanism_fails_the_tolerance(fault):
    """Each planted fault, and weights rounded to 8 bits, puts the chunked
    prompt's and the decode steps' rows over a hundred times the float32
    tolerance that the sound program keeps (one key too many in a window of
    8 moves a logit by 0.02; the sink left out or V of another head by
    more than the bfloat16 tolerance too)."""
    cfg = tiny()
    variables, ids, want = make(cfg)
    assert _paged_worst(cfg, variables, ids, want) < ATOL[jnp.float32]
    if fault == "eight_bit_weights":
        worst = _paged_worst(cfg, to_8_bits(variables), ids, want)
    else:
        with planted(fault):
            worst = _paged_worst(cfg, variables, ids, want)
    assert worst > 100 * ATOL[jnp.float32]
    if fault in FAULTS[:2]:
        assert worst > ATOL[jnp.bfloat16]


def test_the_references_own_limit_refuses_a_token_far_from_the_top():
    """`logits` (what the harness calls) returns the answer's rows and
    applies the file's own limit: a streamed token the limit passes leaves
    its row as computed, one past it is put `REFUSED` rms under the top."""
    cfg = tiny()
    variables, ids, want = make(cfg)
    config = {**file_of(cfg), "check": {"new_tokens": 4,
                                        "shortfall_limit": 0.05}}
    seq = ids[:40].copy()
    with jax.default_matmul_precision("highest"):
        for at in (37, 38, 39):                         # greedy: shortfall 0
            seq[at] = int(np.asarray(ref.full_logits(
                variables["params"], config, seq[:at]))[-1].argmax())
        rows = ref.logits(variables["params"], config, seq)
        fresh = np.asarray(ref.full_logits(variables["params"], config, seq))
    np.testing.assert_allclose(rows[36:40], fresh[36:40], atol=1e-5)
    assert not rows[:36].any()
    seq[38] = int(fresh[37].argmin())
    with jax.default_matmul_precision("highest"):
        rows = ref.logits(variables["params"], config, seq)
    # far past the harness's 0.5 (the refused logit itself swells the rms)
    assert ref.shortfall(rows[37], seq[38]) > 10
