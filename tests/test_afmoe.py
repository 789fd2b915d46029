"""The AFMoE family (window and full attention layers mixed, gated
grouped-query attention with q/k norms, sigmoid-routed experts held as one
chip's share) against its plain reference, through the paged engine's own
cache manager: pages by layer kind, the window kind a ring. Tiny widths
(window 8, block 4, chunk 8, contexts past three windows), float32, seeded
weights, on the CPU.

Tolerances: the program and the reference are both float32 here and differ
in the order of their sums (a grouped product against a loop over experts, a
running softmax over key blocks against one softmax over all the keys):
logits of order 1 agree to 1e-4 absolute, which a key one position outside
the window, a page of an older lap, a missing rotation or a dropped pair
misses by three orders of magnitude and more.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import afmoe as ref
from ray_tpu.models import afmoe as A
from ray_tpu.models import layers
from ray_tpu.models.llama import (KEY_BLOCK, key_block_pairs,
                                  key_block_trips)
from ray_tpu.parallel.moe import MOE_COUNTS
from ray_tpu.serve.llm.kv_cache import PagedKVCache, PageKind

ATOL = 1e-4
BLOCK = 4
HF = {A.SLIDING: "sliding_attention", A.FULL: "full_attention"}


def tiny(**kw):
    kw.setdefault("dtype", jnp.float32)
    kw.setdefault("param_dtype", jnp.float32)
    return A.AfmoeConfig.tiny(**kw)


def file_of(cfg: A.AfmoeConfig) -> dict:
    """The configuration file's keys for `cfg`, as the reference reads."""
    return {
        "num_hidden_layers": cfg.n_layer, "rms_norm_eps": cfg.norm_eps,
        "hidden_size": cfg.d_model, "mup_enabled": cfg.mup,
        "num_attention_heads": cfg.n_head,
        "num_key_value_heads": cfg.n_kv_head, "head_dim": cfg.head_dim,
        "rope_theta": cfg.rope_theta, "sliding_window": cfg.window,
        "layer_types": [HF[t] for t in cfg.types],
        "num_experts_per_tok": cfg.top_k, "route_scale": cfg.routed_scale,
        "score_func": "sigmoid", "route_norm": True, "n_group": 1,
        "deployment_share": {"first_expert": cfg.first_expert},
        "check": {"new_tokens": 4}}


def make(cfg, seed=3, n=60):
    """(variables, token ids, the reference's logits over them)."""
    variables = A.Afmoe(cfg).init(jax.random.PRNGKey(seed),
                                  jnp.ones((1, 8), jnp.int32))
    ids = np.random.default_rng(5).integers(0, cfg.vocab_size, n)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.full_logits(variables["params"], file_of(cfg),
                                          ids))
    return variables, ids, want


CASES = {
    "mixed": {},                                        # s s s f, 1 dense
    "share_4_of_16": {"experts_held": 4, "first_expert": 8},
    "all_sliding": {"layer_types": (A.SLIDING,) * 4},
    "all_full": {"layer_types": (A.FULL,) * 4},
}


@pytest.fixture(scope="module", params=list(CASES), ids=list(CASES))
def case(request):
    cfg = tiny(**CASES[request.param])
    return (cfg,) + make(cfg)


def cache_of(cfg, pages=40, seqs=2):
    kinds = tuple(PageKind(*k) for k in A.page_kinds(cfg))
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


def test_reference_matches_the_family_forward(case):
    cfg, variables, ids, want = case
    with jax.default_matmul_precision("highest"):
        got = A.Afmoe(cfg).apply(variables,
                                 jnp.asarray(ids[None], jnp.int32))[0]
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=1e-4)
    # a model whose logits were all alike would pass any comparison
    assert np.std(want) > 0.05


def test_the_window_and_the_rotation_show_in_the_reference():
    """What the other tests would miss if program and reference shared a
    fault: the reference's logits past the window move when the window does,
    and when a sliding layer is made a full one (no rotation, no window)."""
    cfg = tiny()
    variables, ids, want = make(cfg)
    params = variables["params"]
    with jax.default_matmul_precision("highest"):
        wider = np.asarray(ref.full_logits(
            params, {**file_of(cfg), "sliding_window": 9}, ids))
        full = np.asarray(ref.full_logits(params, file_of(
            dataclasses.replace(cfg, layer_types=(A.FULL,) * 4)), ids))
    # a query sees itself and 7 before it: position 7 is the last whose
    # window of 8 holds everything, and a window of 9 changes position 8 on
    np.testing.assert_allclose(wider[:8], want[:8], atol=1e-6)
    assert np.abs(wider[8:] - want[8:]).max() > 1e-2
    assert np.abs(full[1:] - want[1:]).max() > 1e-2


@pytest.mark.parametrize("how", ["oneshot", "chunked"])
def test_prefill_then_paged_decode_match_the_reference(case, how):
    """The prompt through `prefill_step` or `chunk_step` windows of 8 (the
    last one ragged) into a cache of pages by kind, then decode steps
    through it: every logit row is the reference's full pass's. 39 prompt
    tokens and 15 decoded are past six windows of 8, and the window kind's
    ring of 3 pages (12 positions) wraps four times."""
    cfg, variables, ids, want = case
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
            logits, *rows, counts = A.prefill_step(
                variables, cfg, toks, np.asarray([n], np.int32))
            np.testing.assert_allclose(logits[0], want[n - 1], atol=ATOL)
            write(kv, held, rows, n)
        else:
            for start in range(0, n, c):
                take = min(c, n - start)
                toks = np.zeros((1, c), np.int32)
                toks[0, :take] = ids[start:start + take]
                logits, *rows, counts = A.chunk_step(
                    variables, cfg, toks, np.asarray([start], np.int32),
                    *kv.arena, *tables)
                np.testing.assert_allclose(
                    logits[0, :take], want[start:start + take], atol=ATOL)
                write(kv, held, rows, take, start)
        assert len(counts) == len(A.STEP_COUNTS)
        for j in range(steps):
            pos = n + j
            logits, *rows, counts = A.decode_step(
                variables, cfg, np.asarray([ids[pos]], np.int32),
                np.asarray([pos], np.int32), *kv.arena, *tables)
            for kind, pool in enumerate(kv.pools):
                kv.append(held[kind], pos, *[r[0] for r in rows[pool.arrays]],
                          kind=kind)
            np.testing.assert_allclose(logits[0], want[pos], atol=ATOL)
    # a decode step's walk: a window layer never past its ring
    slots = dict(zip(A.STEP_COUNTS, np.asarray(counts).tolist()))
    n_window = cfg.types.count(A.SLIDING)
    assert slots["key_slots_window"] <= n_window * (1 + 3 * BLOCK)
    # a full layer as far as the block that holds the last position
    trips, keys = key_block_trips(np.asarray([n + steps - 1]),
                                  cfg.max_seq_len // BLOCK, BLOCK, np)
    assert slots["key_slots_full"] == (cfg.n_layer - n_window) * (
        1 + int(trips) * keys)
    kv.release(held, owner)
    kv.assert_quiesced()


# cached positions of the lanes of one decode call (pages of 4, so a key
# block is 64 pages): none (a pad lane of the bucket), under a block, a
# block to its last slot, past the wrap of a ring of 524 positions, and one
# three blocks longer than any other
LANES = (0, 100, KEY_BLOCK, 600, 1400)


@pytest.mark.parametrize("window", [None, 8, 520])
def test_lanes_of_mixed_lengths_walk_their_own_blocks(window):
    """One decode call over `LANES`: every lane's logits are those of the
    lane decoded alone (the bucket of one: the loop as far as the longest,
    which is its own) and the reference's, and the step counts each live
    lane's own key and its own blocks, not every lane as far as the
    longest. Without a window every layer is a full one; a window of 8 is a
    ring of 3 pages, one block; of 520 a ring of 131 pages, three blocks,
    the last of 3 pages."""
    cfg = tiny(max_seq_len=2048, window=window or 8,
               layer_types=() if window else (A.FULL,) * 4)
    longest = max(LANES)
    variables, ids, want = make(cfg, n=longest + 1)
    kv = cache_of(cfg, pages=sum(-(-(n + 1) // BLOCK) for n in LANES),
                  seqs=len(LANES))
    live = np.asarray(LANES) > 0
    tables = [np.zeros((len(LANES), pool.width), np.int32)
              for pool in kv.pools]
    with jax.default_matmul_precision("highest"):
        toks = np.zeros((1, longest + 8), np.int32)
        toks[0, :longest] = ids[:longest]
        _, *rows, _ = A.prefill_step(variables, cfg, toks,
                                     np.asarray([longest], np.int32))
        for lane, n in enumerate(LANES):
            if n:       # a position's K and V follow from the tokens before
                held = kv.reserve(n + 1, lane)
                write(kv, held, rows, n)
                for table, pages in zip(tables, held):
                    table[lane, :len(pages)] = pages
        positions = np.asarray(LANES, np.int32)
        tokens = np.where(live, ids[positions], 0).astype(np.int32)
        logits, *_, counts = A.decode_step(
            variables, cfg, tokens, positions, *kv.arena, *tables,
            valid=live)
        for lane in np.flatnonzero(live):
            alone, *_ = A.decode_step(
                variables, cfg, tokens[lane:lane + 1],
                positions[lane:lane + 1], *kv.arena,
                *[t[lane:lane + 1] for t in tables],
                valid=np.ones(1, bool))
            np.testing.assert_allclose(logits[lane], alone[0], atol=ATOL)
            np.testing.assert_allclose(logits[lane], want[LANES[lane]],
                                       atol=ATOL)
    got = dict(zip(A.STEP_COUNTS, np.asarray(counts).tolist()))
    for pool in kv.pools:
        blocks, *_, keys = key_block_pairs(positions, pool.width, BLOCK, np)
        layers = pool.kind.n_layer
        assert got[f"key_slots_{pool.kind.name}"] == layers * (
            live.sum() + blocks.sum() * keys)
        if pool.kind.window is None:
            assert blocks.tolist() == [0, 1, 1, 3, 6] and keys == KEY_BLOCK
            assert got["key_slots_full"] < layers * len(LANES) * (
                1 + blocks.max() * keys) / 2
        else:
            assert blocks.tolist() == {8: [0, 1, 1, 1, 1],
                                       520: [0, 1, 1, 3, 3]}[window]
    if not window:
        assert got["key_slots_window"] == 0


@pytest.mark.parametrize("lengths", [(0, 0, 0), (5, 0, 700, 256, 257),
                                     (1, 1, 1, 1), (9000,), (0, 513, 0, 90)])
def test_every_live_pair_is_in_the_work_list_once(lengths):
    """`key_block_pairs`: lane by lane, each lane's blocks in order, each
    once and first in the list; what is past them is not live and reads
    lane 0's block 0; trips of B pairs are never more than the walk as far
    as the longest; the program's form gives the host's list."""
    positions = np.asarray(lengths, np.int32)
    b = len(lengths)
    blocks, lane, at, live, keys = key_block_pairs(positions, 512, 16, np)
    assert keys == KEY_BLOCK and len(lane) == b * (512 * 16 // KEY_BLOCK)
    assert blocks.tolist() == [min(-(-n // KEY_BLOCK), 32) for n in lengths]
    assert -(-int(blocks.sum()) // b) <= int(
        key_block_trips(positions, 512, 16, np)[0])
    assert live.tolist() == (np.arange(len(lane)) < blocks.sum()).tolist()
    assert not (lane[~live].any() or at[~live].any())
    assert list(zip(lane[live].tolist(), at[live].tolist())) == [
        (i, j) for i in range(b) for j in range(blocks[i])]
    for mine, theirs in zip(key_block_pairs(jnp.asarray(positions), 512, 16),
                            (blocks, lane, at, live, keys)):
        np.testing.assert_array_equal(mine, theirs)


@pytest.mark.parametrize("window", [None, 8])
def test_mask_is_exact_at_both_edges_of_the_window(window):
    """`window_attend` of one query at position `start` against cached keys
    that are all zeros but one: the one key shows in the output exactly
    where i - j < window (j = start - 7 is in, start - 8 is out), whatever
    lap of the ring it lies in; a key of an older lap left in the ring's
    rows (position < start - ring) never shows."""
    rng = np.random.default_rng(0)
    b, kvh, h, d, ring = 1, 1, 2, 8, 3
    pool_pages = 6
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
    k_new = jnp.zeros((b, 1, kvh, d), jnp.float32)
    v_new = jnp.zeros((b, 1, kvh, d), jnp.float32)
    n_pages = ring if window else 16
    table = np.asarray([[4, 1, 3] + [0] * (n_pages - 3)], np.int32)
    for start in (9, 12, 13, 30):
        for back in (1, 7, 8, 9):
            j = start - back
            k_pages = np.zeros((pool_pages, 1, BLOCK, kvh, d), np.float32)
            v_pages = np.zeros_like(k_pages)
            if window:
                slot = (j // BLOCK) % ring
            else:
                slot = j // BLOCK
                table = np.zeros((1, n_pages), np.int32)
                table[0, slot] = 5
            page = table[0, slot]
            # a large score for this one key, and a value that marks it
            k_pages[page, 0, j % BLOCK, 0] = np.asarray(q[0, 0, 0]) * 5
            v_pages[page, 0, j % BLOCK, 0] = 1.0
            out, slots, _ = A.window_attend(
                q, k_new, v_new, (jnp.asarray(k_pages), jnp.asarray(v_pages)),
                0, jnp.asarray(table), jnp.asarray([start], jnp.int32),
                window=window, scale=1.0)
            seen = float(np.abs(np.asarray(out)).max()) > 1e-3
            assert seen == (window is None or back < window), \
                (window, start, back)


def test_a_ring_never_shows_an_older_lap():
    """A ring whose rows all hold a loud key and a marked value, but only
    `start` positions of the sequence were ever written: with start = 5 of a
    ring of 12 rows, the 7 rows past position 4 are nobody's and weigh
    nothing; with start = 14 (wrapped) the rows of positions 12-13 are this
    lap's, rows of 2-3 were overwritten, and every row of the ring is a
    position in [2, 14), of which the window of 8 sees 7-13."""
    rng = np.random.default_rng(1)
    d, ring, window = 8, 3, 8
    q = jnp.asarray(rng.normal(size=(1, 1, 1, d)), jnp.float32)
    zero = jnp.zeros((1, 1, 1, d), jnp.float32)
    table = jnp.asarray([[2, 0, 1]], jnp.int32)
    k_pages = jnp.zeros((3, 1, BLOCK, 1, d), jnp.float32)
    # the value of a row is its (page, offset) as a one-hot of 12
    v = np.zeros((3, 1, BLOCK, 1, 12), np.float32)
    for page in range(3):
        for off in range(BLOCK):
            v[page, 0, off, 0, page * BLOCK + off] = 1.0
    for start, want_rows in ((5, [(0, o) for o in range(4)] + [(1, 0)]),
                             (14, [(1, 3)] + [(2, o) for o in range(4)]
                              + [(0, 0), (0, 1)])):
        out, _, _ = A.window_attend(
            q, zero, zero, (k_pages, jnp.asarray(v[..., :d])), 0, table,
            jnp.asarray([start], jnp.int32), window=window, scale=1.0)
        # all scores are 0: the softmax is uniform over the seen keys and
        # the query's own (whose value is zero)
        weights = np.zeros(12)
        for slot, off in want_rows:
            weights[int(table[0, slot]) * BLOCK + off] = 1 / (
                len(want_rows) + 1)
        np.testing.assert_allclose(np.asarray(out)[0, 0], weights[:d],
                                   atol=1e-6)


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    """An expert layer's routed part summed over the 4 shares of 4 experts
    each, plus the shared expert once, is the layer with all 16 held."""
    cfg = tiny()
    variables, _, _ = make(cfg)
    lp = A.unboxed_params(variables)["layer2"]
    h = jnp.asarray(np.random.default_rng(2).normal(size=(24, cfg.d_model)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, counts = layers.routed_feed_forward(lp, cfg, 2, h, None)
        shared = layers.swiglu(h, lp["shared_gate_up"], lp["shared_down"],
                               cfg.dtype)
        total, local = shared, 0
        for first in range(0, 16, 4):
            share = dataclasses.replace(cfg, experts_held=4,
                                        first_expert=first)
            part = {**lp, "experts_gate_up": lp["experts_gate_up"][
                first:first + 4], "experts_down": lp["experts_down"][
                first:first + 4]}
            y, n = layers.routed_feed_forward(part, share, 2, h, None)
            total = total + (y - shared)
            local += int(n[MOE_COUNTS.index("pairs_local")])
    np.testing.assert_allclose(total, whole, atol=1e-5)
    assert local == 24 * cfg.top_k == int(
        counts[MOE_COUNTS.index("pairs_routed")])


def _to_8_bits(tree):
    """Every matrix through float8_e4m3 with one scale an output channel."""
    def cast(a):
        if a.ndim < 2:
            return a
        scale = jnp.max(jnp.abs(a), axis=-2, keepdims=True) / 448.0
        q = (a / scale).astype(jnp.float8_e4m3fn).astype(a.dtype)
        return q * scale
    return jax.tree_util.tree_map(cast, tree)


def test_eight_bit_weights_fail_the_stated_tolerance():
    """The tolerance of these tests is tight enough that weights rounded to
    8 bits are told apart: their logits miss the reference's by a hundred
    times ATOL."""
    cfg = tiny()
    variables, ids, want = make(cfg)
    with jax.default_matmul_precision("highest"):
        got = A.Afmoe(cfg).apply(_to_8_bits(variables),
                                 jnp.asarray(ids[None], jnp.int32))[0]
    assert np.abs(np.asarray(got) - want).max() > 100 * ATOL


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
