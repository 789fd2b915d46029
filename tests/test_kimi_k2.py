"""The Kimi-K2 family (latent attention, sigmoid-routed experts held as one
chip's share) against its plain reference, through the paged engine's own
cache manager. Tiny widths, float32, seeded weights, on the CPU.

Tolerances: the program and the reference are both float32 here and differ
in the order of their sums (a grouped product against a loop over experts,
the absorbed product against the expanded one, eight heads at a time
against all): logits of order 1 agree to 1e-4 absolute, which a dropped
pair, a wrong rope or a wrong page misses by four orders of magnitude.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import kimi_k2 as ref
from ray_tpu.models import kimi_k2 as K
from ray_tpu.parallel.moe import (MOE_COUNTS, expert_shard_layer,
                                  sigmoid_topk_route)
from ray_tpu.serve.llm.engine import (MODEL_FAMILIES, EngineConfig,
                                      LLMEngine)
from ray_tpu.serve.llm.kv_cache import (OutOfPagesError, PagedKVCache,
                                        PrefixCache)

ATOL = 1e-4


def tiny(**kw):
    kw.setdefault("dtype", jnp.float32)
    kw.setdefault("param_dtype", jnp.float32)
    return K.KimiK2Config.tiny(**kw)


def file_of(cfg: K.KimiK2Config) -> dict:
    """The configuration file's keys for `cfg`, as the reference reads."""
    return {
        "num_hidden_layers": cfg.n_layer, "rms_norm_eps": cfg.norm_eps,
        "num_attention_heads": cfg.n_head, "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_dim,
        "qk_rope_head_dim": cfg.qk_rope_dim, "v_head_dim": cfg.v_head_dim,
        "rope_theta": cfg.rope_theta,
        "num_experts_per_tok": cfg.top_k,
        "routed_scaling_factor": cfg.routed_scale,
        "deployment_share": {"first_expert": cfg.first_expert},
        "rope_scaling": {
            "type": "yarn", "factor": cfg.rope_factor,
            "beta_fast": cfg.rope_beta_fast, "beta_slow": cfg.rope_beta_slow,
            "mscale": cfg.rope_mscale,
            "mscale_all_dim": cfg.rope_mscale_all_dim,
            "original_max_position_embeddings": cfg.rope_original_max}}


@pytest.fixture(scope="module", params=[
    {}, {"experts_held": 4, "first_expert": 8}],
    ids=["all_experts", "share_4_of_16"])
def case(request):
    """(cfg, variables, token ids, the reference's logits over them)."""
    cfg = tiny(**request.param)
    variables = K.KimiK2(cfg).init(jax.random.PRNGKey(3),
                                   jnp.ones((1, 8), jnp.int32))
    ids = np.random.default_rng(5).integers(0, cfg.vocab_size, 60)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.logits(variables["params"], file_of(cfg),
                                     jnp.asarray(ids, jnp.int32)))
    return cfg, variables, ids, want


def test_reference_matches_the_family_forward(case):
    cfg, variables, ids, want = case
    with jax.default_matmul_precision("highest"):
        got = K.KimiK2(cfg).apply(variables,
                                  jnp.asarray(ids[None], jnp.int32))[0]
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=1e-4)
    # a model whose logits were all alike would pass any comparison
    assert np.std(want) > 0.05


@pytest.mark.parametrize("how", ["oneshot", "chunked"])
def test_prefill_then_paged_decode_match_the_reference(case, how):
    """The prompt through `prefill_step` or three `chunk_step` windows
    (the last one ragged) into a latent `PagedKVCache`, then decode steps
    through it: every logit row is the reference's full pass's."""
    cfg, variables, ids, want = case
    n, steps, block, c = 39, 6, 4, 16
    kv = PagedKVCache(24, cfg.n_layer, block, rows=K.cache_rows(cfg),
                      dtype=np.float32)
    assert [a.shape for a in kv.arena] == [(24, cfg.n_layer, block,
                                            cfg.row_dim)]
    owner = object()
    pages = kv.alloc(kv.pages_for_tokens(n + steps), owner)
    table = np.zeros((1, cfg.max_seq_len // block), np.int32)
    table[0, :len(pages)] = pages
    with jax.default_matmul_precision("highest"):
        if how == "oneshot":
            toks = np.zeros((1, 48), np.int32)
            toks[0, :n] = ids[:n]
            logits, lat, _ = K.prefill_step(variables, cfg, toks,
                                            np.asarray([n], np.int32))
            np.testing.assert_allclose(logits[0], want[n - 1], atol=ATOL)
            kv.write_rows(pages, (lat[0],), n)
        else:
            for start in range(0, n, c):
                take = min(c, n - start)
                toks = np.zeros((1, c), np.int32)
                toks[0, :take] = ids[start:start + take]
                logits, lat, _ = K.chunk_step(
                    variables, cfg, toks, np.asarray([start], np.int32),
                    *kv.arena, table)
                np.testing.assert_allclose(
                    logits[0, :take], want[start:start + take], atol=ATOL)
                kv.write_rows(pages, (lat[0],), take, start)
        for j in range(steps):
            pos = n + j
            logits, lat, _ = K.decode_step(
                variables, cfg, np.asarray([ids[pos]], np.int32),
                np.asarray([pos], np.int32), *kv.arena, table)
            kv.append(pages, pos, lat[0])
            np.testing.assert_allclose(logits[0], want[pos], atol=ATOL)
    kv.free(pages, owner)
    kv.assert_quiesced()


def _paged(rng, cfg, starts, block, table_pages, fill=np.nan):
    """An arena of `fill` in which each sequence's first `start` slots (of
    layer 1) hold random latents, behind a shuffled page table; returns
    (pages [P, L, block, row], table [B, table_pages], the latents
    [B, table_pages * block, row] a dense form would see: `fill` past
    `start`)."""
    b = len(starts)
    ids = rng.permutation(b * table_pages + 3)[:b * table_pages]
    table = ids.reshape(b, table_pages).astype(np.int32)
    dense = np.full((b, table_pages * block, cfg.row_dim), fill, np.float32)
    for i, start in enumerate(starts):
        dense[i, :start] = rng.normal(size=(start, cfg.row_dim))
    dense[..., cfg.latent_dim:] = 0
    pages = np.full((b * table_pages + 3, cfg.n_layer, block, cfg.row_dim),
                    fill, np.float32)
    pages[table, 1] = dense.reshape(b, table_pages, block, cfg.row_dim)
    return pages, table, dense


def _dense_attention(lp, cfg, q_nope, q_rope, lat_all, valid):
    """The expanded attention with nothing blocked: every head's keys and
    values of all K latents, one softmax a row. lat_all [B, K, row]; valid
    [B, C, K]."""
    w = K._kv_b(lp, cfg)
    c_all, k_rope = lat_all[..., :cfg.kv_lora_rank], \
        lat_all[..., cfg.kv_lora_rank:cfg.latent_dim]
    kv = jnp.einsum("bkc,chn->bkhn", c_all, w)
    k_nope, v = kv[..., :cfg.qk_nope_dim], kv[..., cfg.qk_nope_dim:]
    scores = (jnp.einsum("bqhn,bkhn->bhqk", q_nope, k_nope)
              + jnp.einsum("bqhr,bkr->bhqk", q_rope, k_rope)) \
        * K.softmax_scale(cfg)
    p = jax.nn.softmax(jnp.where(valid[:, None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bkhv->bqhv", p, v)
    return out.reshape(out.shape[:2] + (-1,))


@pytest.fixture(scope="module")
def one_layer():
    """(cfg with a table of four key blocks, layer 1's weights). `kv_b` is
    scaled up from its initial 0.02: a softmax over a thousand keys that
    score alike would average any fault away."""
    cfg = tiny(max_seq_len=4 * K.KEY_BLOCK)
    variables = K.KimiK2(cfg).init(jax.random.PRNGKey(0),
                                   jnp.ones((1, 8), jnp.int32))
    lp = K.unboxed_params(variables)["layer1"]
    return cfg, {**lp, "kv_b": lp["kv_b"] * 20}


def test_absorbed_attention_is_the_expanded_attention(one_layer):
    """One layer's attention of one new token over a paged cache of up to
    20 latents: scored in the latent space (decode, through the pages and the
    work list) and with expanded keys and values (prefill, chunks), the same
    numbers."""
    cfg, lp = one_layer
    rng = np.random.default_rng(1)
    lengths = [20, 7, 0]
    b, block = len(lengths), 4
    pages, table, _ = _paged(rng, cfg, lengths, block, 5, fill=0.0)
    lat_new = rng.normal(size=(b, cfg.row_dim)).astype(np.float32)
    lat_new[..., cfg.latent_dim:] = 0
    q_nope = rng.normal(size=(b, cfg.n_head, cfg.qk_nope_dim)) \
        .astype(np.float32)
    q_rope = rng.normal(size=(b, cfg.n_head, cfg.qk_rope_dim)) \
        .astype(np.float32)
    positions = np.asarray(lengths, np.int32)
    with jax.default_matmul_precision("highest"):
        absorbed = K.attend_absorbed(
            lp, cfg, q_nope, q_rope, lat_new, jnp.asarray(pages), 1,
            K.listed_walk(positions, table, block))
        expanded, slots = K.attend_expanded(
            lp, cfg, q_nope[:, None], q_rope[:, None], lat_new[:, None],
            positions, jnp.asarray(pages), table, 1)
    np.testing.assert_allclose(absorbed, expanded[:, 0], atol=1e-5,
                               rtol=1e-4)
    assert int(slots) == 20 + 1


# -- the absorbed path's walk: each lane as far as its own last block ---------

def lanes_of(lanes: int, end: int, keys: int):
    """Cached positions a lane, unequal: past several key blocks, nothing
    (a pad lane of the bucket), the table's end, a few keys, then lengths in
    between. Never nothing first: the last trip's dead pairs read lane 0's
    first block, masked, and it has to hold numbers."""
    first = [2 * keys + 37, 0, end, 5]
    rest = [keys, keys + 1, end - keys - 3, 3 * keys, 1, 0, keys - 1, 700,
            end - 1, 2 * keys, 16, 0]
    return np.asarray((first + rest)[:lanes], np.int32)


def absorbed_case(cfg, lp, positions, dtype, block=16, seed=0):
    """Inputs of `attend_absorbed` for lanes of `positions` cached latents,
    rounded to `dtype`: the arena holds numbers where a lane's positions
    reach, large finite garbage in the rest of each lane's last visited key
    block (masked before it weighs; a NaN there would poison the sum under
    its zero weight) and NaN everywhere else, the pages of no lane too (never
    read), page 0 apart: a table that is no whole number of key blocks is
    padded with it, and the last block of a lane at the table's end reads it,
    masked. Returns (lp, q_nope, q_rope, lat_new, pages, table)."""
    rng = np.random.default_rng(seed + len(positions))
    n_pages = cfg.max_seq_len // block
    pages, table, _ = _paged(rng, cfg, positions, block, n_pages)
    keys = K.absorbed_walk(positions, n_pages, block, np)[2]
    for i, n in enumerate(positions):
        visited = table[i, :-(-int(n) // keys) * keys // block]
        pages[visited, 1] = np.nan_to_num(pages[visited, 1], nan=1e6)
    pages[0, 1] = np.nan_to_num(pages[0, 1], nan=1e6)
    b = len(positions)
    lat_new = rng.normal(size=(b, cfg.row_dim))
    lat_new[..., cfg.latent_dim:] = 0
    q_nope = rng.normal(size=(b, cfg.n_head, cfg.qk_nope_dim))
    q_rope = rng.normal(size=(b, cfg.n_head, cfg.qk_rope_dim))
    return ({**lp, "kv_b": jnp.asarray(lp["kv_b"], dtype)},
            *(jnp.asarray(a, dtype) for a in (q_nope, q_rope, lat_new,
                                              pages)), table)


def absorbed_in_float64(lp, cfg, q_nope, q_rope, lat_new, pages, table,
                        positions):
    """The absorbed attention a lane at a time in float64: ONE softmax over
    the lane's own `positions[i]` cached latents (layer 1's) and its own."""
    f64 = lambda a: np.asarray(a, np.float32).astype(np.float64)  # noqa: E731
    w_uk, w_uv = np.split(f64(K._kv_b(lp, cfg)), [cfg.qk_nope_dim], axis=-1)
    rows = f64(pages)[:, 1]
    out = []
    for i, n in enumerate(positions):
        lat = np.concatenate([rows[table[i]].reshape(-1, cfg.row_dim)[:n],
                              f64(lat_new[i:i + 1])])
        q = np.concatenate([np.einsum("hn,chn->hc", f64(q_nope[i]), w_uk),
                            f64(q_rope[i])], axis=-1)
        s = q @ lat[:, :cfg.latent_dim].T * K.softmax_scale(cfg)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out.append(np.einsum("hc,chv->hv", p @ lat[:, :cfg.kv_lora_rank],
                             w_uv).reshape(-1))
    assert np.isfinite(out).all()
    return np.stack(out)


# the walk against the float64 softmax, as a share of the output's rms:
# float32 differs in the order of its sums; bfloat16 rounds the query in the
# latent space, the weights and the weighted latent (2^-8 each)
WALK_TOLERANCE = {jnp.float32: 1e-5, jnp.bfloat16: 3e-2}
WALK_CASES = [(1, 0), (1, 1), (1, 2), (1, 3), (2, 0), (4, 0), (16, 0)]


def check_the_walk(cfg, lp, lanes, shift, dtype):
    """`attend_absorbed` for `lanes` lanes of `lanes_of` (rotated by `shift`
    where there is one lane, so that each kind of lane stands alone once)
    against `absorbed_in_float64`."""
    block = 16
    # two layers of pages: the arena's size goes with the config's layers
    cfg = dataclasses.replace(cfg, dtype=dtype, n_layer=2)
    n_pages = cfg.max_seq_len // block
    keys = K.absorbed_walk(np.zeros(lanes, np.int32), n_pages, block, np)[2]
    positions = lanes_of(4, cfg.max_seq_len, keys)[shift:shift + 1] \
        if lanes == 1 else lanes_of(lanes, cfg.max_seq_len, keys)
    lp, q_nope, q_rope, lat_new, pages, table = absorbed_case(
        cfg, lp, positions, dtype, block)
    with jax.default_matmul_precision("highest"):
        got = K.attend_absorbed(
            lp, cfg, q_nope, q_rope, lat_new, pages, 1,
            K.listed_walk(jnp.asarray(positions), jnp.asarray(table), block))
    want = absorbed_in_float64(lp, cfg, q_nope, q_rope, lat_new, pages, table,
                               positions)
    rms = float(np.sqrt(np.mean(want ** 2)))
    assert rms > 0.05
    worst = np.max(np.abs(np.asarray(got, np.float32) - want), axis=-1) / rms
    assert (worst < WALK_TOLERANCE[dtype]).all(), worst


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("lanes, shift", WALK_CASES, ids=str)
def test_the_walk_is_one_softmax_over_each_lanes_own_latents(
        one_layer, lanes, shift, dtype):
    """Lanes of unequal positions in one bucket (one past several key
    blocks, one that holds nothing, one at the table's end, one of a few
    keys; each of them alone in the bucket of one) at 1, 2, 4 and 16 lanes:
    every lane's output is one dense float64 softmax over its own latents,
    whatever the others hold, with every slot past a lane's position garbage
    or NaN."""
    check_the_walk(*one_layer, lanes, shift, dtype)


@pytest.mark.parametrize("lanes", [1, 2, 4, 16])
def test_the_host_and_the_program_count_one_walk(one_layer, lanes):
    """`absorbed_walk` with `xp=np` and with `xp=jnp` are one walk, its trips
    are the live pairs of `llama.key_block_pairs` in whole trips, and
    `decode_step` counts lanes + trips x pairs a trip x keys a block slots a
    layer."""
    from ray_tpu.models import llama

    cfg, _ = one_layer
    block, n_pages = 16, cfg.max_seq_len // 16
    positions = lanes_of(lanes, cfg.max_seq_len, K.LATENT_BLOCK)
    trips, width, keys, listed = K.absorbed_walk(positions, n_pages, block,
                                                 np)
    blocks, lane, at, live, _ = llama.key_block_pairs(
        positions, n_pages, block, np, K.LATENT_BLOCK)
    assert (width, keys) == (K.PAIRS_A_LANE * lanes, K.LATENT_BLOCK)
    assert int(trips) == -(-int(live.sum()) // width) \
        == -(-int(np.sum(-(-positions // keys))) // width)
    program = K.absorbed_walk(jnp.asarray(positions), n_pages, block)
    assert (int(program[0]),) + program[1:3] == (int(trips), width, keys)
    for mine, theirs, plain in zip(listed, program[3], (lane, at, live)):
        assert len(mine) % width == 0
        assert (np.asarray(mine) == np.asarray(theirs)).all()
        assert (np.asarray(mine)[:len(plain)] == plain).all()
    variables = K.KimiK2(cfg).init(jax.random.PRNGKey(0),
                                   jnp.ones((1, 8), jnp.int32))
    counts = jax.jit(K.decode_step, static_argnums=1)(
        variables, cfg, np.zeros(lanes, np.int32), positions,
        np.zeros((lanes * n_pages, cfg.n_layer, block, cfg.row_dim),
                 np.float32),
        np.arange(lanes * n_pages, dtype=np.int32).reshape(lanes, n_pages))[2]
    assert int(counts[K.STEP_COUNTS.index("attn_key_slots")]) \
        == cfg.n_layer * (lanes + int(trips) * width * keys)


@pytest.mark.parametrize("starts", [
    (0,), (1,), (1023,), (1024,), (1025,), (3500,),
    (1025, 3), (0, 2049), (3500, 1024)], ids=str)
def test_blocked_attention_is_the_dense_attention(one_layer, starts):
    """A window of 8 tokens against `start` cached latents in pages of 16,
    walked in key blocks of 1,024 under a running softmax, equals one
    dense softmax over the cached latents and the window. In a batch the
    trip count is the largest start's and the mask each row's own. Every
    slot past a sequence's `start` is NaN: the visited block's are masked
    before they weigh anything, the others never read."""
    cfg, lp = one_layer
    rng = np.random.default_rng(sum(starts))
    b, c, block = len(starts), 8, 16
    pages, table, cached = _paged(rng, cfg, starts, block,
                                  cfg.max_seq_len // block)
    # NaN would poison a sum even under a zero weight: the visited blocks'
    # slots past `start` are made finite
    visited = -(-max(starts) // K.KEY_BLOCK)
    head = table[:, :visited * K.KEY_BLOCK // block]
    pages[head, 1] = np.nan_to_num(pages[head, 1], nan=1e6)
    lat = rng.normal(size=(b, c, cfg.row_dim)).astype(np.float32)
    lat[..., cfg.latent_dim:] = 0
    q_nope = rng.normal(size=(b, c, cfg.n_head, cfg.qk_nope_dim)) \
        .astype(np.float32)
    q_rope = rng.normal(size=(b, c, cfg.n_head, cfg.qk_rope_dim)) \
        .astype(np.float32)
    t_max = cached.shape[1]
    valid = np.concatenate(
        [np.broadcast_to((np.arange(t_max)[None] < np.asarray(starts)[:, None])
                         [:, None], (b, c, t_max)),
         np.broadcast_to(np.tril(np.ones((c, c), bool)), (b, c, c))], axis=-1)
    with jax.default_matmul_precision("highest"):
        want = _dense_attention(
            lp, cfg, q_nope, q_rope,
            np.concatenate([np.nan_to_num(cached), lat], axis=1), valid)
        got, slots = jax.jit(K.attend_expanded, static_argnums=(1, 8))(
            lp, cfg, q_nope, q_rope, lat, np.asarray(starts, np.int32),
            pages, table, 1)
    assert np.isfinite(np.asarray(want)).all() and np.std(want) > 0.05
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-4)
    assert int(slots) == visited * K.KEY_BLOCK + c


def test_key_slots_count_blocks_and_nothing_past_start_is_read(case):
    """`chunk_step`'s and `decode_step`'s `attn_key_slots` grow with `start`
    a key block at a time (1,024 keys and `LATENT_BLOCK`), and the logits do
    not depend on what lies past `start`: large
    finite garbage in the rest of the last visited block (masked; a NaN
    there would poison the sum under its zero weight), NaN in every page
    of the blocks never visited and every page of no sequence."""
    cfg, variables, ids, want = case
    cfg = dataclasses.replace(cfg, max_seq_len=4 * K.KEY_BLOCK)
    c, block, n = 16, 16, 39
    kv = PagedKVCache(300, cfg.n_layer, block, rows=K.cache_rows(cfg),
                      dtype=np.float32)
    owner = object()
    pages = kv.alloc(cfg.max_seq_len // block, owner)
    table = np.asarray([pages], np.int32)
    per_block = K.KEY_BLOCK // block
    arena = np.full(kv.arena[0].shape, np.nan, np.float32)
    arena[pages[:per_block]] = 1e6
    kv.arena = (jnp.asarray(arena),)
    step = jax.jit(K.chunk_step, static_argnums=1)
    slots = {}
    with jax.default_matmul_precision("highest"):
        for start in range(0, n, c):
            take = min(c, n - start)
            toks = np.zeros((1, c), np.int32)
            toks[0, :take] = ids[start:start + take]
            logits, lat, counts = step(
                variables, cfg, toks, np.asarray([start], np.int32),
                *kv.arena, table)
            np.testing.assert_allclose(
                logits[0, :take], want[start:start + take], atol=ATOL)
            kv.write_rows(pages, (lat[0],), take, start)
        toks = np.zeros((1, c), np.int32)
        for start in (0, 1, 1024, 1025, 2048, 2049, 4096):
            # past 39 the cache is garbage: only the count is looked at
            counts = step(variables, cfg, toks,
                          np.asarray([start], np.int32), *kv.arena, table)[2]
            slots[start] = int(counts[K.STEP_COUNTS.index("attn_key_slots")])
    assert slots == {start: cfg.n_layer * (-(-start // K.KEY_BLOCK)
                                           * K.KEY_BLOCK + c)
                     for start in slots}
    # the decode step the same, a block of `LATENT_BLOCK` at a time: its own
    # latent, then its lane's blocks as far as the one that holds `positions`
    step = jax.jit(K.decode_step, static_argnums=1)
    with jax.default_matmul_precision("highest"):
        logits, _, _ = step(variables, cfg, ids[n:n + 1].astype(np.int32),
                            np.asarray([n], np.int32), *kv.arena, table)
        np.testing.assert_allclose(logits[0], want[n], atol=ATOL)
        slots = {at: int(step(
            variables, cfg, np.zeros(1, np.int32), np.asarray([at], np.int32),
            *kv.arena, table)[2][K.STEP_COUNTS.index("attn_key_slots")])
            for at in (0, 1, 640, 641, 1280, 4095, 4096)}
    assert slots == {at: cfg.n_layer * (-(-at // K.LATENT_BLOCK)
                                        * K.LATENT_BLOCK + 1)
                     for at in slots}
    kv.free(pages, owner)


def _moe_weights(rng, d, f, n_experts):
    return (rng.normal(size=(d, n_experts)).astype(np.float32),
            (0.1 * rng.normal(size=n_experts)).astype(np.float32),
            {"gate_up": (rng.normal(size=(n_experts, d, 2 * f)) / 8)
             .astype(np.float32),
             "down": (rng.normal(size=(n_experts, f, d)) / 8)
             .astype(np.float32)})


def _uncut_layer(x, router, bias, experts, top_k, scale):
    """The whole routed layer by the reference's equations: a loop over
    every expert, each over every token."""
    expert, weight = ref.route(x, router, bias, top_k, scale)
    out = jnp.zeros_like(x)
    for e in range(experts["down"].shape[0]):
        w_e = jnp.sum(jnp.where(expert == e, weight, 0.0), axis=-1)
        out = out + w_e[:, None] * ref.swiglu(x, experts["gate_up"][e],
                                              experts["down"][e])
    return out


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    """The guide's share test: 16 experts over 4 chips of 4. The partial
    results of the four shares, plus the shared expert counted once, are
    the uncut layer (the reference's loop over all 16 plus the shared
    expert); and their pair counts add up to every routed pair."""
    rng = np.random.default_rng(2)
    n, d, f, n_experts, top_k, scale = 24, 32, 16, 16, 4, 2.827
    x = rng.normal(size=(n, d)).astype(np.float32)
    router, bias, experts = _moe_weights(rng, d, f, n_experts)
    shared = {"gate_up": (rng.normal(size=(d, 2 * f)) / 8).astype(np.float32),
              "down": (rng.normal(size=(f, d)) / 8).astype(np.float32)}
    with jax.default_matmul_precision("highest"):
        want = _uncut_layer(x, router, bias, experts, top_k, scale) \
            + ref.swiglu(x, shared["gate_up"], shared["down"])
        total = ref.swiglu(x, shared["gate_up"], shared["down"])
        counts = np.zeros(len(MOE_COUNTS), np.int64)
        for first in range(0, n_experts, 4):
            held = {k: v[first:first + 4] for k, v in experts.items()}
            part, c = expert_shard_layer(x, router, bias, held, first,
                                         n_experts, top_k, scale)
            total = total + part
            counts += np.asarray(c)
    np.testing.assert_allclose(total, want, atol=ATOL, rtol=1e-4)
    routed, local, computed = counts[:3]
    assert routed == 4 * n * top_k and local == computed == n * top_k


def test_no_pair_is_dropped_under_a_skewed_router():
    """Every token prefers the same four experts, all held here: the
    grouped product takes all N * top_k pairs (a capacity of 1.25 would
    keep under a third), padded lanes route nowhere, and the result is the
    reference's loop."""
    rng = np.random.default_rng(4)
    n, d, f, n_experts, top_k = 32, 32, 16, 16, 4
    x = np.abs(rng.normal(size=(n, d))).astype(np.float32)
    router, bias, experts = _moe_weights(rng, d, f, n_experts)
    router *= 0.01
    router[:, :4] += 0.05               # x > 0: experts 0-3 win everywhere
    valid = np.arange(n) < 29
    held = {k: v[:8] for k, v in experts.items()}
    with jax.default_matmul_precision("highest"):
        got, counts = expert_shard_layer(x, router, bias, held, 0,
                                         n_experts, top_k, 1.0, valid=valid)
        expert, weight = sigmoid_topk_route(x, router, bias, top_k, 1.0)
        want = jnp.zeros_like(x)
        for e in range(8):
            w_e = jnp.sum(jnp.where(expert == e, weight, 0.0), axis=-1)
            want = want + w_e[:, None] * ref.swiglu(
                x, experts["gate_up"][e], experts["down"][e])
    assert set(np.asarray(expert).ravel()) == {0, 1, 2, 3}
    counts = dict(zip(MOE_COUNTS, np.asarray(counts).tolist()))
    assert counts == {"pairs_routed": 29 * top_k, "pairs_local": 29 * top_k,
                      "pairs_computed": 29 * top_k, "expert_calls": 4,
                      # 116 sorted rows over four experts, all in one tile
                      "tile_visits": 4}
    np.testing.assert_allclose(got[:29], want[:29], atol=ATOL, rtol=1e-4)
    np.testing.assert_array_equal(got[29:], 0)


def test_routing_weights_and_the_selection_bias():
    """The weights of a token's experts sum to the scaling factor; the bias
    decides which experts are taken and is not in their weights."""
    rng = np.random.default_rng(6)
    x = rng.normal(size=(10, 32)).astype(np.float32)
    router, _, _ = _moe_weights(rng, 32, 8, 16)
    zero = np.zeros(16, np.float32)
    e0, w0 = sigmoid_topk_route(x, router, zero, 4, 2.827)
    np.testing.assert_allclose(np.sum(w0, axis=-1), 2.827, rtol=1e-5)
    # a bias of +10 on expert 15 puts it among every token's experts ...
    bias = zero.copy()
    bias[15] = 10.0
    e1, w1 = sigmoid_topk_route(x, router, bias, 4, 2.827)
    assert np.all(np.any(np.asarray(e1) == 15, axis=-1))
    assert not np.all(np.any(np.asarray(e0) == 15, axis=-1))
    np.testing.assert_allclose(np.sum(w1, axis=-1), 2.827, rtol=1e-5)
    # ... with the weight its score alone gives it, over the chosen four
    g = np.asarray(jax.nn.sigmoid(jnp.dot(
        x, router, precision=jax.lax.Precision.HIGHEST)))
    chosen = np.take_along_axis(g, np.asarray(e1), axis=-1)
    np.testing.assert_allclose(
        w1, chosen / chosen.sum(-1, keepdims=True) * 2.827, rtol=1e-5)
    assert np.all(np.asarray(w1) < 2.827)
    # the reference's routing is the same choice and the same weights
    e2, w2 = ref.route(x, router, bias, 4, 2.827)
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2))
    np.testing.assert_allclose(w1, w2, rtol=1e-5)


def test_yarn_tables_are_the_references_frequencies():
    cfg = tiny()
    cos, sin = K.yarn_tables(cfg)
    inv = np.asarray(ref.yarn_inv_freq(
        cfg.qk_rope_dim, cfg.rope_theta, file_of(cfg)["rope_scaling"]))
    ang = np.arange(cfg.max_seq_len)[:, None] * inv[None, :]
    np.testing.assert_allclose(cos, np.cos(ang), atol=2e-5)
    np.testing.assert_allclose(sin, np.sin(ang), atol=2e-5)
    # at the published numbers: the fast channels keep their frequency,
    # the slow ones are divided by the factor, the scale is (0.1 ln 64 + 1)^2
    big = K.KimiK2Config()
    inv = np.asarray(ref.yarn_inv_freq(64, 50000.0, {
        "factor": 64, "beta_fast": 32, "beta_slow": 1,
        "original_max_position_embeddings": 4096}))
    plain = 50000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[0], plain[0], rtol=1e-6)
    np.testing.assert_allclose(inv[-1], plain[-1] / 64, rtol=1e-6)
    assert K.softmax_scale(big) == pytest.approx(
        192 ** -0.5 * (0.1 * math.log(64) + 1) ** 2)
    assert K.cache_rows(big) == ((640,),) and big.latent_dim == 576


# -- through the engine -------------------------------------------------------

def _engine(**kw):
    kw.setdefault("batch_buckets", (1, 2, 4))
    kw.setdefault("prefill_buckets", (16, 32))
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("num_pages", 48)
    kw.setdefault("block_size", 4)
    return LLMEngine(model="kimi_k2", engine_config=EngineConfig(**kw))


def _greedy(eng, prompt, new):
    """`new` greedy tokens after `prompt` by the reference's full pass."""
    ids = list(prompt)
    for _ in range(new):
        with jax.default_matmul_precision("highest"):
            row = ref.logits(eng.params["params"], file_of(eng.model_cfg),
                             jnp.asarray(ids, jnp.int32))[-1]
        ids.append(int(np.argmax(np.asarray(row))))
    return ids[len(prompt):]


def test_engine_serves_the_family_and_counts_its_experts():
    eng = _engine()
    try:
        assert [a.shape[-1] for a in eng.kv.arena] == [eng.model_cfg.row_dim]
        rng = np.random.default_rng(8)
        long = rng.integers(1, 500, 39).tolist()       # three chunks
        short = rng.integers(1, 500, 9).tolist()       # one-shot prefill
        reqs = [eng.submit(long, 5), eng.submit(short, 5)]
        eng.run_until_idle()
        assert reqs[0].result() == _greedy(eng, long, 5)
        assert reqs[1].result() == _greedy(eng, short, 5)
        eng.quiesce()
        m = eng.metrics()
        cfg = eng.model_cfg
        moe_layers = cfg.n_layer - cfg.n_dense_layer
        assert m["chunk_steps"] == 3 and m["chunk_ms"] > 0
        # valid rows only: the prompts' tokens, then a token a lane a step
        assert m["prefill_moe_pairs_routed"] == 48 * cfg.top_k * moe_layers
        assert m["decode_moe_pairs_routed"] == 8 * cfg.top_k * moe_layers
        for kind in ("prefill", "decode"):
            # every expert is held here, and no pair is dropped
            assert m[f"{kind}_moe_pairs_local"] \
                == m[f"{kind}_moe_pairs_computed"] \
                == m[f"{kind}_moe_pairs_routed"]
            assert 0 < m[f"{kind}_moe_expert_calls"] \
                <= (m["decode_steps"] if kind == "decode" else 4) \
                * moe_layers * cfg.experts_held
        # the counts came over the link with the logits
        assert m["decode_link_bytes"] % 4 == 0 and m["kv_pages_live"] == 0
    finally:
        eng.shutdown()


def test_lanes_of_unequal_length_give_the_references_tokens():
    """Five requests of 30 to 1,950 prompt tokens (one to four key blocks
    of 640), the short ones first, so that they decode while the long ones
    are chunked in: the buckets of one, two and eight (three pad lanes).
    Every request's tokens are the reference's greedy ones, and after every
    step `decode_attn_key_slots` has grown by the count of the walk the
    program ran, made on the host with the program's function."""
    eng = LLMEngine(
        model="kimi_k2", seed=0, model_cfg=tiny(max_seq_len=2048),
        engine_config=EngineConfig(
            batch_buckets=(1, 2, 8), prefill_buckets=(64,), prefill_chunk=256,
            num_pages=760, block_size=8))
    try:
        forward, steps = eng._decode_forward, []

        def counted(fn, args):
            before = eng.metrics()["decode_attn_key_slots"]
            out = forward(fn, args)
            steps.append((np.array(args[2]),
                          eng.metrics()["decode_attn_key_slots"] - before))
            return out

        eng._decode_forward = counted
        rng = np.random.default_rng(12)
        sizes = [(30, 44), (700, 38), (1300, 30), (1400, 14), (1950, 6)]
        prompts = [rng.integers(1, 500, n).tolist() for n, _ in sizes]
        reqs = [eng.submit(p, new) for p, (_, new) in zip(prompts, sizes)]
        eng.run_until_idle()
        cfg, most = eng.model_cfg, 0
        for req, prompt, (n, new) in zip(reqs, prompts, sizes):
            # one full pass over the prompt and the answer: every token is
            # the largest logit of the row before it, so the answer is the
            # reference's greedy one
            tokens = req.result()
            with jax.default_matmul_precision("highest"):
                rows = np.asarray(ref.logits(
                    eng.params["params"], file_of(cfg),
                    jnp.asarray(prompt + tokens[:-1], jnp.int32)))[n - 1:]
            assert len(tokens) == new
            assert tokens == np.argmax(rows, axis=-1).tolist()
        for positions, grown in steps:
            trips, width, keys, _ = K.absorbed_walk(
                positions, eng.max_pages_per_seq, 8, np)
            assert (width, keys) == (K.PAIRS_A_LANE * len(positions),
                                     K.LATENT_BLOCK)
            most = max(most, int(trips))
            assert grown == cfg.n_layer * (
                len(positions) + int(trips) * width * keys) > 0
        assert {len(p) for p, _ in steps} == {1, 2, 8} and most > 1
        m = eng.metrics()
        assert m["decode_attn_key_slots"] > cfg.n_layer \
            * m["decode_context_tokens"]
        eng.quiesce()
    finally:
        eng.shutdown()


def test_prefix_cache_hit_and_page_reuse_on_the_latent_arena():
    """A second request with the first's 32-token prefix takes its latent
    pages from the prefix cache and prefills the suffix alone; a third,
    after both are gone and the cache is drained, is served from the freed
    pages. All three stream what the reference's full pass gives."""
    eng = _engine(num_pages=24)
    try:
        rng = np.random.default_rng(9)
        shared = rng.integers(1, 500, 32).tolist()
        a = shared + rng.integers(1, 500, 7).tolist()
        b = shared + rng.integers(1, 500, 5).tolist()
        ra = eng.submit(a, 3)
        eng.run_until_idle()
        rb = eng.submit(b, 3)
        eng.run_until_idle()
        m = eng.metrics()
        assert m["prefix_cache_hit_tokens"] == 32
        assert ra.result() == _greedy(eng, a, 3)
        assert rb.result() == _greedy(eng, b, 3)
        eng.quiesce()
        assert isinstance(eng.prefix, PrefixCache)
        eng.prefix.drain()
        assert eng.kv.free_pages == 24
        c = rng.integers(1, 500, 80).tolist()          # 21 of the 24 pages
        rc = eng.submit(c, 4)
        eng.run_until_idle()
        assert rc.result() == _greedy(eng, c, 4)
        eng.quiesce()
    finally:
        eng.shutdown()


def test_live_and_cached_page_counts_follow_the_holders():
    """`live_pages` / `cached_pages` are kept as holders come and go (the
    metrics poll no longer walks 8,192 holder lists under the engine's
    lock): through prompts that share prefixes, finish, and evict each
    other's cached pages, they are what a walk over the holders counts.
    (A hit whose own entry is evicted to make room for the remainder used
    to raise KeyError out of `acquire` with the pages taken.)"""
    from ray_tpu.serve.llm.kv_cache import _PrefixEntry

    kv = PagedKVCache(12, 1, 4, rows=((8,),))
    prefix = PrefixCache(kv)
    rng = np.random.default_rng(11)
    stems = [rng.integers(0, 50, 16).tolist() for _ in range(3)]
    running = []

    def check():
        walk_live = sum(
            1 for hs in kv._holders.values()
            if any(not isinstance(h, _PrefixEntry) for h in hs))
        assert kv.live_pages == walk_live
        assert kv.cached_pages == len(kv._holders) - walk_live

    for step in range(60):
        if running and (len(running) >= 3 or rng.random() < 0.4):
            pages, owner = running.pop(int(rng.integers(len(running))))
            kv.free(pages, owner)
        else:
            prompt = stems[int(rng.integers(3))][:int(rng.integers(4, 17))] \
                + rng.integers(50, 99, int(rng.integers(0, 6))).tolist()
            owner = object()
            try:
                pages, _ = prefix.acquire(
                    prompt, owner, kv.pages_for_tokens(len(prompt) + 2))
            except OutOfPagesError:      # nothing was taken: try later
                check()
                continue
            prefix.insert(prompt, pages)
            running.append((pages, owner))
        check()
    for pages, owner in running:
        kv.free(pages, owner)
    check()
    kv.assert_quiesced()
    prefix.drain()
    assert kv.free_pages == 12 and kv.cached_pages == 0


def test_engine_registry_refuses_an_unknown_family():
    assert {"llama", "gpt", "kimi_k2"} <= set(MODEL_FAMILIES)
    with pytest.raises(ValueError, match="unknown model family 'mamba'"):
        LLMEngine(model="mamba")


def test_a_family_the_engine_does_not_run_is_not_imported():
    """`ray_tpu.serve.llm` and an engine of another family leave
    `models/kimi_k2.py` unimported (Mistral's set-up pays nothing for it)."""
    import subprocess
    import sys

    code = ("import sys; import ray_tpu.serve.llm; import ray_tpu.models; "
            "from ray_tpu.serve.llm.engine import LLMEngine; "
            "LLMEngine(model='llama').shutdown(); "
            "assert 'ray_tpu.models.kimi_k2' not in sys.modules; "
            "from ray_tpu.models import KimiK2Config; "
            "assert 'ray_tpu.models.kimi_k2' in sys.modules")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=300)


def test_config_arithmetic_of_the_benchmark_cell():
    """The parameter counts the issue's table gives for the cut, from the
    family's own shapes at the published widths."""
    cfg = K.KimiK2Config(vocab_size=20480, n_layer=7, experts_held=12,
                         max_seq_len=8192)

    def count(shapes):
        return sum(math.prod(shape) for shape, _ in shapes.values())

    dense, expert = count(K.layer_shapes(cfg, 0)), \
        count(K.layer_shapes(cfg, 1))
    assert round(dense / 1e6, 1) == 497.5
    assert round(expert / 1e6, 1) == 676.4
    total = dense + 6 * expert + 2 * cfg.vocab_size * cfg.d_model \
        + cfg.d_model
    assert round(total / 1e9, 2) == 4.85
    assert dataclasses.replace(cfg, kv_lora_rank=576).row_dim == 640
