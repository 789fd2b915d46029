"""The Ling hybrid family (`ray_tpu/models/ling_hybrid.py`): Kimi Delta
Attention in its two forms against the recurrence that defines it,
group-limited routing against a plain selection, the chip's share against
the uncut layer, and the family through the paged engine (a state a
sequence beside latent pages) against its plain reference.

CPU, tiny sizes, seeded weights; float32 unless a test says otherwise.
"""

import functools
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import kimi_k2
from ray_tpu.models import ling_hybrid as lh
from ray_tpu.parallel import moe
from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine
from test_kimi_k2 import WALK_CASES, check_the_walk

LOWER = -5.0


# -- the recurrence, as the module's docstring writes it ----------------------

def _recurrence(q, k, v, g, beta, state):
    """Token by token in float64 numpy. q, k, g [T, H, dk]; v [T, H, dv];
    beta [T, H]; state [H, dk, dv]. Returns (o [T, H, dv], state)."""
    q, k, v, g, beta = (np.asarray(x, np.float64) for x in (q, k, v, g, beta))
    s = np.asarray(state, np.float64).copy()
    out = np.zeros(v.shape)
    for t in range(q.shape[0]):
        s = np.exp(g[t])[:, :, None] * s
        seen = np.einsum("hkv,hk->hv", s, k[t])
        s = s + (beta[t][:, None] * k[t])[:, :, None] \
            * (v[t] - seen)[:, None, :]
        out[t] = np.einsum("hkv,hk->hv", s, q[t])
    return out, s


def _kda_inputs(t, decays, seed=0, h=2, dk=16, dv=16):
    """Unit q (scaled) and k, v of order one, beta in (0, 1), and log
    decays at the slow end of (e^-5, 1), at the fast end, or each channel at
    one of the two."""
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.normal(size=(t, h, dk))) / np.sqrt(dk)
    k = unit(rng.normal(size=(t, h, dk)))
    v = rng.normal(size=(t, h, dv))
    beta = 1 / (1 + np.exp(-rng.normal(size=(t, h))))
    slow = LOWER * rng.uniform(1e-4, 2e-3, size=(t, h, dk))
    fast = LOWER * rng.uniform(0.97, 0.99999, size=(t, h, dk))
    g = {"slow": slow, "fast": fast,
         "mixed": np.where(rng.random((1, h, dk)) < 0.5, slow, fast)}[decays]
    state = rng.normal(size=(h, dk, dv))
    return tuple(np.asarray(x, np.float32) for x in (q, k, v, g, beta, state))


def _chunk(q, k, v, g, beta, state):
    o, s = jax.jit(lh.kda_chunk)(*(jnp.asarray(x)[None]
                                   for x in (q, k, v, g, beta, state)))
    return np.asarray(o[0]), np.asarray(s[0])


def _close(got, want, rel=1e-4):
    """`rel` of the largest magnitude of what is compared: the entries of a
    state or an output that decay has brought to 1e-30 are not held to
    four digits of their own."""
    scale = float(np.max(np.abs(want)))
    np.testing.assert_allclose(got, want, rtol=0, atol=rel * scale)


@pytest.mark.parametrize("decays", ["slow", "fast", "mixed"])
@pytest.mark.parametrize("t", [16, 64, 100, 130])
def test_blocked_scan_equals_the_recurrence(t, decays):
    """`kda_chunk` against the token-by-token recurrence from a state that
    is not zero, at lengths under, at and over `KDA_BLOCK` and not a
    multiple of it, with decays at both ends of (e^-5, 1): 1e-4 of the
    result's largest entry, float32 against float64. At the fast end 64
    steps of the running sum reach -320: a form that divided by a
    cumulative decay would return inf here."""
    q, k, v, g, beta, state = _kda_inputs(t, decays, seed=t)
    want_o, want_s = _recurrence(q, k, v, g, beta, state)
    got_o, got_s = _chunk(q, k, v, g, beta, state)
    assert np.isfinite(got_o).all() and np.isfinite(got_s).all()
    _close(got_o, want_o)
    _close(got_s, want_s)


@pytest.mark.parametrize("cuts", [(64, 64), (37, 90, 3)])
def test_blocked_scan_chunk_by_chunk_equals_one_pass(cuts):
    """The state one call returns carried into the next: windows of whole
    blocks and of odd lengths give what one pass over all tokens gives."""
    t = sum(cuts)
    q, k, v, g, beta, state = _kda_inputs(t, "mixed", seed=5)
    want_o, want_s = _chunk(q, k, v, g, beta, state)
    outs, at = [], 0
    for n in cuts:
        o, state = _chunk(*(x[at:at + n] for x in (q, k, v, g, beta)), state)
        outs.append(o)
        at += n
    _close(np.concatenate(outs), want_o)
    _close(state, want_s)


def test_padded_rows_leave_the_state_as_it_was():
    """Rows with g = 0 and beta = 0 (what `_window_forward` makes of rows
    past the window's tokens) change nothing: the state after 40 tokens and
    24 padded rows is the state after the 40, bit for bit where the block
    structure is the same, and a window of padding alone is the identity."""
    q, k, v, g, beta, state = _kda_inputs(64, "mixed", seed=9)
    g[40:], beta[40:] = 0.0, 0.0
    _, padded = _chunk(q, k, v, g, beta, state)
    _, exact = _recurrence(q[:40], k[:40], v[:40], g[:40], beta[:40], state)
    _close(padded, exact)
    _, same = _chunk(q, k, v, np.zeros_like(g), np.zeros_like(beta), state)
    np.testing.assert_array_equal(same, state)


@pytest.mark.parametrize("decays", ["slow", "fast"])
def test_decode_update_is_one_step_of_the_recurrence(decays):
    q, k, v, g, beta, state = _kda_inputs(3, decays, seed=2)
    want_o, want_s = _recurrence(q[:1], k[:1], v[:1], g[:1], beta[:1], state)
    o, s = jax.jit(lh.kda_step)(*(jnp.asarray(x[0])[None]
                                  for x in (q, k, v, g, beta)),
                                jnp.asarray(state)[None])
    _close(np.asarray(o), want_o, 1e-5)
    _close(np.asarray(s[0]), want_s, 1e-5)


# -- the recurrence on the state arena where it lies --------------------------

def _by_gather(s_arena, j, slots, q, k, v, g, beta, lanes=None):
    """What the in-place walks replace: `kda_step` on the lanes' states
    gathered from their slots, the new ones scattered back."""
    o, new = lh.kda_step(q, k, v, g, beta, s_arena[slots, j])
    return o, s_arena.at[slots, j].set(new)


# (slots of the arena beside the scratch slot, the slot each of four lanes
# names; the scratch slot is the padded lanes'). A bucket of four lanes
# covers an arena of up to four slots and walks it in slot order
# (`kda_slots`); against a larger arena it walks its lanes (`kda_lanes`)
ARENAS = {
    "slots-permuted": (4, [2, 0, 3, 1]),
    "slots-holes": (4, [3, 4, 1, 4]),       # slots 0 and 2 are nobody's
    "slots-padded": (3, [2, 0, 1, 3]),
    "lanes-permuted": (6, [5, 0, 3, 1]),
    "lanes-holes": (8, [6, 2, 0, 4]),
    "lanes-padded": (6, [2, 6, 0, 6]),
}


def _tiny_arena(cfg, n_slots, seed):
    """A state arena of `n_slots` and the scratch slot with every entry
    drawn: states of order one, tails of order one in the model's type."""
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=(n_slots + 1,) + shape), dtype)
                 for shape, dtype in lh.seq_state(cfg))


@pytest.mark.parametrize("case", sorted(ARENAS))
def test_a_walk_updates_its_layer_of_the_named_slots_and_nothing_else(case):
    """`kda_slots` and `kda_lanes` on one layer of an arena, against
    `kda_step` on the gathered states: the lanes' outputs and the named
    slots' new states within the step's tolerance; every other layer of
    every slot, and every slot that no lane names, bit for bit as it was
    (a slot may be held by a sequence that is mid-prefill)."""
    n_slots, slots = ARENAS[case]
    layer, slots = 2, jnp.asarray(slots, jnp.int32)
    walk = functools.partial(lh.kda_slots,
                             lanes=lh.slot_lanes(slots, n_slots)) \
        if case.startswith("slots") else lh.kda_lanes
    q, k, v, g, beta, _ = _kda_inputs(4, "mixed", seed=n_slots, h=4)
    rng = np.random.default_rng(7)
    arena = jnp.asarray(rng.normal(size=(n_slots + 1, 6, 4, 16, 16)),
                        jnp.float32)
    lanes = tuple(jnp.asarray(x) for x in (q, k, v, g, beta))
    got_o, got = jax.jit(walk, static_argnums=1)(arena, layer, slots, *lanes)
    want_o, want = _by_gather(arena, layer, slots, *lanes)
    live = np.asarray(slots) < n_slots
    named = np.asarray(slots)[live]
    _close(np.asarray(got_o)[live], np.asarray(want_o)[live], 1e-5)
    _close(np.asarray(got)[named, layer], np.asarray(want)[named, layer],
           1e-5)
    # the named slots did change, and nothing else did
    assert not np.array_equal(np.asarray(got)[named, layer],
                              np.asarray(arena)[named, layer])
    others = np.ones(arena.shape[:2], bool)
    others[named, layer] = False
    others[n_slots, layer] = False      # the scratch slot is nobody's
    np.testing.assert_array_equal(np.asarray(got)[others],
                                  np.asarray(arena)[others])


def _tiny_step_inputs(cfg, lanes, seed):
    """Weights, a paged latent arena of four pages with every row drawn,
    and a decode step's tokens and positions for `lanes` lanes."""
    rng = np.random.default_rng(seed)
    variables = lh.LingHybrid(cfg).init(jax.random.PRNGKey(seed),
                                        jnp.ones((1, 8), jnp.int32))
    (row,) = lh.cache_rows(cfg)
    pages = jnp.asarray(rng.normal(size=(4, lh.paged_layers(cfg), 8) + row),
                        cfg.dtype)
    table = jnp.asarray(rng.permutation(4)[None, :2].repeat(lanes, 0),
                        jnp.int32)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, lanes), jnp.int32)
    positions = jnp.asarray(rng.integers(0, 16, lanes), jnp.int32)
    return variables, pages, table, tokens, positions


@pytest.mark.parametrize("case", sorted(ARENAS))
def test_decode_step_on_the_arena_equals_kda_step_on_gathered_states(
        case, monkeypatch):
    """`decode_step` on an arena whose lanes name their slots in a permuted
    order, leave holes, or are padded lanes on the scratch slot, in both
    walks: the live lanes' logits and the named slots' states are what
    `kda_step` on the gathered states gives; the convolution's tails are
    the same numbers; every slot that no lane names keeps both its arrays
    bit for bit; and the step counts the states it had to move and the
    (slot, layer) states it walked."""
    n_slots, slots = ARENAS[case]
    cfg = lh.LingHybridConfig.tiny(dtype=jnp.float32,
                                   param_dtype=jnp.float32)
    variables, pages, table, tokens, positions = _tiny_step_inputs(cfg, 4, 3)
    arena = _tiny_arena(cfg, n_slots, seed=5)
    slots = jnp.asarray(slots, jnp.int32)
    live = np.asarray(slots) < n_slots
    step = jax.jit(lambda *a: lh.decode_step(
        variables, cfg, tokens, positions, pages, table, seq_state=a[:2],
        slots=a[2], valid=a[3]))
    logits, latents, states, tails, counts = step(*arena, slots,
                                                  jnp.asarray(live))
    monkeypatch.setattr(lh, "kda_slots", _by_gather)
    monkeypatch.setattr(lh, "kda_lanes", _by_gather)
    want_logits, want_latents, want_states, want_tails, _ = lh.decode_step(
        variables, cfg, tokens, positions, pages, table, seq_state=arena,
        slots=slots, valid=jnp.asarray(live))
    named = np.asarray(slots)[live]
    _close(np.asarray(logits)[live], np.asarray(want_logits)[live], 1e-5)
    _close(np.asarray(latents)[live], np.asarray(want_latents)[live], 1e-5)
    _close(np.asarray(states)[named], np.asarray(want_states)[named], 1e-5)
    _close(np.asarray(tails)[named], np.asarray(want_tails)[named], 1e-5)
    unnamed = np.setdiff1d(np.arange(n_slots), named)
    for got, was in zip((states, tails), arena):
        assert got.shape == was.shape and got.dtype == was.dtype
        np.testing.assert_array_equal(np.asarray(got)[unnamed],
                                      np.asarray(was)[unnamed])
    walked = n_slots if case.startswith("slots") else 4
    by_name = dict(zip(lh.STEP_COUNTS, np.asarray(counts).tolist()))
    assert by_name["kda_state_rows"] == 6 * int(live.sum())
    assert by_name["kda_slot_rows"] == 6 * walked


@pytest.mark.parametrize("n_slots", [1, 3], ids=["slots", "lanes"])
def test_a_chunk_then_a_decode_on_one_slot_is_one_scan_over_all_tokens(
        n_slots):
    """A window of 21 tokens through `chunk_step` into a slot that held
    another sequence's state, then the 22nd through `decode_step` on that
    slot, in both walks (a bucket of one against an arena of one slot, and
    of three): the slot's state is the state after ONE `chunk_step` over
    all 22 tokens (`kda_chunk` over the lot), the tail its last three
    inputs, the logits the 22nd row's, and the other slots are as they
    were."""
    from ray_tpu.serve.llm.kv_cache import scatter_arena

    cfg = lh.LingHybridConfig.tiny(dtype=jnp.float32,
                                   param_dtype=jnp.float32)
    variables, pages, _, _, _ = _tiny_step_inputs(cfg, 1, 4)
    arena = _tiny_arena(cfg, n_slots, seed=6)
    rng = np.random.default_rng(8)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 32)), jnp.int32)
    table = jnp.asarray([[2, 0, 3, 1]], jnp.int32)
    slot = jnp.asarray([n_slots - 1], jnp.int32)
    start = jnp.zeros((1,), jnp.int32)

    def chunk(n, pages, arena):
        logits, latents, *state, _ = lh.chunk_step(
            variables, cfg, ids, start, pages, table, seq_state=arena,
            slots=slot, valid=jnp.arange(32)[None, :] < n)
        at = np.arange(n)
        (pages,) = scatter_arena(
            (pages,), (latents[0, :n],),
            jnp.asarray(np.asarray(table)[0][at // 8]), jnp.asarray(at % 8))
        return logits, pages, tuple(state)

    want_logits, _, want = chunk(22, pages, arena)
    _, pages, state = chunk(21, pages, arena)
    logits, _, *state, counts = jax.jit(
        lambda pages, *a: lh.decode_step(
            variables, cfg, ids[0, 21:22], jnp.asarray([21], jnp.int32),
            pages, table, seq_state=a, slots=slot))(pages, *state)
    _close(np.asarray(logits), np.asarray(want_logits), 1e-4)
    for got, wanted, was in zip(state, want, arena):
        _close(np.asarray(got)[n_slots - 1], np.asarray(wanted)[n_slots - 1])
        np.testing.assert_array_equal(np.asarray(got)[:n_slots - 1],
                                      np.asarray(was)[:n_slots - 1])
    by_name = dict(zip(lh.STEP_COUNTS, np.asarray(counts).tolist()))
    assert (by_name["kda_state_rows"], by_name["kda_slot_rows"]) == (6, 6)


def test_exponents_stay_in_float32s_range():
    """A sub-block at the strongest decay is the largest exponent the
    blocked scan forms; a bound that would pass it is refused when the
    config is made."""
    assert -LOWER * lh.KDA_SUB <= lh.KDA_MAX_EXPONENT < 88
    assert lh.KDA_BLOCK % lh.KDA_SUB == 0
    with pytest.raises(ValueError, match="float32"):
        lh.LingHybridConfig.tiny(kda_lower_bound=-6.0)


# -- routing ------------------------------------------------------------------

def _numpy_route(scores, bias, top_k, scale, n_group, topk_group):
    """The issue's words, a token at a time; the largest first and the
    lowest index first among equals (`lax.top_k`'s order)."""
    experts, weights = [], []
    for s in np.asarray(scores, np.float64):
        c = s + bias
        size = len(c) // n_group
        group = [np.sort(c[i * size:(i + 1) * size])[-2:].sum()
                 for i in range(n_group)]
        kept = np.argsort(-np.asarray(group), kind="stable")[:topk_group]
        masked = np.full(len(c), -np.inf)
        for i in kept:
            masked[i * size:(i + 1) * size] = c[i * size:(i + 1) * size]
        chosen = np.argsort(-masked, kind="stable")[:top_k]
        experts.append(chosen)
        weights.append(s[chosen] / s[chosen].sum() * scale)
    return np.asarray(experts), np.asarray(weights)


@pytest.mark.parametrize("case", ["random", "ties_in_a_group",
                                  "tied_groups"])
def test_group_limited_routing_equals_a_plain_selection(case):
    """Top 2 of 4 groups by the sum of each group's two best biased
    scores, then the top 4 among their experts, against numpy; with equal
    scores inside a group and with two groups whose scores are equal."""
    n, e, groups = 24, 16, 4
    rng = np.random.default_rng(3)
    logits = rng.normal(size=(n, e))
    bias = rng.normal(size=e) * 0.01
    if case == "ties_in_a_group":
        logits[:, 4:8] = logits[:, 4:5]
        bias[4:8] = bias[4]
    if case == "tied_groups":
        logits[:, 8:12] = logits[:, 0:4]
        bias[8:12] = bias[0:4]
    # the router as a diagonal: x W is the logits themselves
    x = jnp.asarray(logits, jnp.float32)
    expert, weight = moe.sigmoid_topk_route(
        x, jnp.eye(e, dtype=jnp.float32), jnp.asarray(bias, jnp.float32),
        4, 2.5, n_group=groups, topk_group=2)
    scores = np.asarray(jax.nn.sigmoid(x))
    want_e, want_w = _numpy_route(scores, np.asarray(bias, np.float32), 4,
                                  2.5, groups, 2)
    np.testing.assert_array_equal(np.asarray(expert), want_e)
    np.testing.assert_allclose(np.asarray(weight), want_w, rtol=1e-6)
    # every chosen expert lies in one of two groups
    assert all(len(set(row // 4)) <= 2 for row in np.asarray(expert))


def test_one_group_routes_as_before_bit_for_bit():
    """`n_group=1` traces nothing new: the answers and the lowered text of
    the old signature and of the new one with one group are the same."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(10, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, 16)) * 0.1, jnp.float32)
    b = jnp.asarray(rng.normal(size=16) * 0.01, jnp.float32)

    def old(x, w, b):
        scores = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), w.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        _, expert = jax.lax.top_k(scores + b.astype(jnp.float32), 4)
        weight = jnp.take_along_axis(scores, expert, axis=-1)
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True) * 2.5
        return expert.astype(jnp.int32), weight

    def new(x, w, b):
        return moe.sigmoid_topk_route(x, w, b, 4, 2.5)

    for got, want in zip(new(x, w, b), old(x, w, b)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert jax.jit(new).lower(x, w, b).as_text().replace("new", "old") \
        == jax.jit(old).lower(x, w, b).as_text()


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The expert layer of the tiny model on each of four chips (experts
    0-3, 4-7, 8-11, 12-15 of 16, group-limited routing over all 16), the
    shared expert counted once, against the plain reference's layer with
    every expert held."""
    from benchmark.references import ling_hybrid as ref

    cfg = lh.LingHybridConfig.tiny(dtype=jnp.float32,
                                   param_dtype=jnp.float32)
    params = lh.unboxed_params(lh.LingHybrid(cfg).init(
        jax.random.PRNGKey(2), jnp.ones((1, 8), jnp.int32)))
    lp = params["layer2"]
    h = jnp.asarray(np.random.default_rng(1).normal(size=(40, cfg.d_model)),
                    jnp.float32)
    config = {"num_experts": 16, "num_experts_per_tok": cfg.top_k,
              "routed_scaling_factor": cfg.routed_scale,
              "n_group": cfg.n_group, "topk_group": cfg.topk_group}
    with jax.default_matmul_precision("highest"):
        whole = ref.feed_forward(h, lp, config)
        shared = ref.swiglu(h, lp["shared_gate_up"], lp["shared_down"])
        total, pairs = shared, 0
        for first in (0, 4, 8, 12):
            held = {"gate_up": lp["experts_gate_up"][first:first + 4],
                    "down": lp["experts_down"][first:first + 4]}
            part, counts = moe.expert_shard_layer(
                h, lp["router"], lp["router_bias"], held, first, 16,
                cfg.top_k, cfg.routed_scale, n_group=cfg.n_group,
                topk_group=cfg.topk_group)
            total = total + part
            pairs += int(counts[1])
    assert pairs == 40 * cfg.top_k          # every pair is local somewhere
    np.testing.assert_allclose(total, whole, atol=1e-5, rtol=1e-4)
    assert float(np.std(np.asarray(whole - shared))) > 1e-3


# -- through the engine -------------------------------------------------------

TINY = {"rms_norm_eps": 1e-6, "num_attention_heads": 4, "head_dim": 16,
        "short_conv_kernel_size": 4, "kda_lower_bound": -5,
        "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
        "v_head_dim": 8, "rope_theta": 6e6, "num_hidden_layers": 7,
        "layer_group_size": 6, "num_experts": 16, "num_experts_per_tok": 4,
        "routed_scaling_factor": 2.5, "n_group": 4, "topk_group": 2}


def _engine(dtype=jnp.float32, **kw):
    base = dict(batch_buckets=(1, 4), prefill_buckets=(16, 32),
                prefill_chunk=32, num_pages=64, block_size=8, prefix_cache=0)
    base.update(kw)
    cfg = lh.LingHybridConfig.tiny(dtype=dtype, param_dtype=dtype)
    return LLMEngine(model="ling_hybrid", model_cfg=cfg,
                     engine_config=EngineConfig(**base))


def _reference_logits(eng, ids):
    import flax.linen as nn

    from benchmark.references import ling_hybrid as ref

    params = nn.meta.unbox(eng.params)["params"]
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits(params, TINY,
                                     jnp.asarray(ids, jnp.int32)))


def _engine_logits(eng, prompt, steps):
    """The logits rows the engine's tokens were chosen from: the prefill's
    last row, caught at `np.argmax` (`_emit_first`), then a decode step's
    one live row a token. A decode program returns the token it chose and
    not the logits (PR 51), so a step's row is the family's `decode_step`'s
    on the arguments and the arena the engine is about to hand its own
    program of the bucket of one: the engine's maker over a module whose
    step returns its logits a second time, as its last output (a program
    passes on what a step returns after its rows and states). The engine's
    program chose that row's largest."""
    import types

    def decode_step(*args, **kwargs):
        logits, *out = lh.decode_step(*args, **kwargs)
        return (logits, *out, logits)

    rows = []
    real, forward, mod = np.argmax, eng._decode_forward, eng._mod
    eng._mod = types.SimpleNamespace(decode_step=decode_step)
    try:
        probe = jax.jit(eng._make_decode_fn(1))
    finally:
        eng._mod = mod

    def spy(row, *a, **kw):
        rows.extend(np.atleast_2d(np.array(row, np.float32)))
        return real(row, *a, **kw)

    def step_spy(fn, args):
        _, *_, logits = probe(*args)        # before the call: it donates
        chosen = forward(fn, args)
        rows.extend(np.array(logits, np.float32))
        assert chosen.tolist() == [int(real(np.asarray(logits)[0]))]
        return chosen

    np.argmax, eng._decode_forward = spy, step_spy
    try:
        req = eng.submit(prompt, steps)
        eng.run_until_idle()
    finally:
        np.argmax, eng._decode_forward = real, forward
    return req.result(), np.stack(rows)


# The engine's rows against the reference's full forward pass over prompt
# and answer: the largest difference over 6 rows of 512 logits, as a share
# of the rows' rms. float32: the blocked scan and the absorbed attention
# order their sums differently, 2e-4. bf16 (weights, activations, latents,
# the logits themselves; the KDA state, decays and routing stay float32):
# the largest of 3,072 roundings through seven layers of width 64 reads
# 0.10 (at the published widths the errors average: the serving cells read
# 0.03-0.065 of a row's rms, PERF.md); a state, a tail or a slot that is
# not the sequence's own is another row, 1.4.
TOLERANCE = {jnp.float32: 2e-4, jnp.bfloat16: 0.2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("n, path", [(20, "prefill:32"), (75, "chunk:32")],
                         ids=["oneshot", "chunked"])
def test_prefill_then_decode_agrees_with_the_reference(n, path, dtype):
    """One-shot prefill (20 tokens in the bucket of 32) and chunked
    prefill (75 tokens: two whole chunks and one of 11, state and
    convolution tail carried), then 5 decode steps through the state arena
    and the latent pages."""
    eng = _engine(dtype)
    try:
        prompt = [int(x) for x in
                  np.random.default_rng(n).integers(0, 512, n)]
        tokens, rows = _engine_logits(eng, prompt, 6)
        want = _reference_logits(eng, prompt + tokens[:-1])[n - 1:]
        rms = float(np.sqrt(np.mean(want ** 2)))
        assert np.max(np.abs(rows - want)) < TOLERANCE[dtype] * rms
        calls = eng.metrics()["compiled_step_calls"]
        assert path in calls and calls["decode:1"] == 5
        eng.quiesce()
    finally:
        assert eng.shutdown() == 0


def test_a_reused_slot_gives_what_a_fresh_engine_gives():
    """The second request takes the slot the first gave back, whose state
    the first left behind: its first prefill unit starts from zero, one-shot
    and chunked, and its logits are a fresh engine's bit for bit."""
    prompts = [[int(x) for x in np.random.default_rng(s).integers(0, 512, n)]
               for s, n in ((1, 30), (2, 25), (3, 70))]
    eng = _engine()
    try:
        _engine_logits(eng, prompts[0], 8)
        assert eng.kv.free_slots == 4
        reused = [_engine_logits(eng, p, 4) for p in prompts[1:]]
    finally:
        assert eng.shutdown() == 0
    for prompt, (tokens, rows) in zip(prompts[1:], reused):
        fresh = _engine()
        try:
            want_tokens, want_rows = _engine_logits(fresh, prompt, 4)
        finally:
            assert fresh.shutdown() == 0
        assert tokens == want_tokens
        np.testing.assert_array_equal(rows, want_rows)


def test_three_sequences_of_different_lengths_share_a_decode_bucket():
    """Three running sequences (one-shot and chunked prompts) in the bucket
    of four, one lane padded: each streams what it streams alone, the
    padded lane's scratch slot apart, `kda_state_rows` counts the live
    lanes only and `kda_slot_rows` the slots or lanes a step walked."""
    rng = np.random.default_rng(11)
    prompts = [[int(x) for x in rng.integers(0, 512, n)]
               for n in (9, 40, 70)]
    alone = []
    for prompt in prompts:
        eng = _engine()
        try:
            alone.append(_engine_logits(eng, prompt, 6)[0])
        finally:
            assert eng.shutdown() == 0
    eng = _engine()
    try:
        reqs = [eng.submit(p, 6) for p in prompts]
        eng.run_until_idle()
        assert [r.result() for r in reqs] == alone
        m = eng.metrics()
        assert m["compiled_step_calls"]["decode:4"] >= 3
        assert m["decode_kda_state_rows"] == 6 * (
            m["tokens_generated"] - 3)      # a live lane a token, 6 layers
        # the bucket of four covers the arena's four slots and walks them
        # all, idle ones too; the bucket of one walks its lane
        calls = m["compiled_step_calls"]
        assert m["decode_kda_slot_rows"] == 6 * (
            4 * calls["decode:4"] + calls.get("decode:1", 0))
        eng.quiesce()
        assert (m["state_slots_live"], m["state_slots_free"]) == (0, 4)
    finally:
        assert eng.shutdown() == 0


@pytest.mark.parametrize("n", [20, 75], ids=["oneshot", "chunked"])
def test_a_request_beside_live_lanes_in_a_slot_just_freed(n):
    """What the benchmark's check cannot reach (it streams its prompts one
    at a time into an idle engine): three sequences stay live in the bucket
    of four, a short one ends, and the request under test takes the slot it
    left, prefills (one-shot, or three chunks with the others decoding in
    between) and decodes as the fourth lane. Every token it streams is the
    one the reference's full forward pass puts on top, and what it streams
    alone."""
    rng = np.random.default_rng(100 + n)
    prompt = [int(x) for x in rng.integers(0, 512, n)]
    others = [[int(x) for x in rng.integers(0, 512, m)]
              for m in (12, 33, 50, 9)]
    alone = _engine()
    try:
        want = _engine_logits(alone, prompt, 6)[0]
    finally:
        assert alone.shutdown() == 0
    eng = _engine()
    try:
        live = [eng.submit(p, 40) for p in others[:3]]
        short = eng.submit(others[3], 3)
        while not short.done.is_set():
            eng.step()
        assert eng.kv.free_slots == 1 and eng.kv.live_slots == 3
        req = eng.submit(prompt, 6)
        while not req.done.is_set():
            eng.step()
        assert not any(r.done.is_set() for r in live)   # beside live lanes
        assert eng.metrics()["compiled_step_calls"]["decode:4"] >= 5
        eng.run_until_idle()
        eng.quiesce()
    finally:
        assert eng.shutdown() == 0
    tokens = req.result()
    assert tokens == want
    rows = _reference_logits(eng, prompt + tokens[:-1])[n - 1:]
    assert tokens == [int(r.argmax()) for r in rows]


# -- the MLA layer's walk: each lane as far as its own last key block ---------

@pytest.fixture(scope="module")
def mla_layer():
    """(the MLA layer's shapes as Kimi's functions read them: eight heads a
    latent here, rope without scaling; its `kv_b`, scaled up from its
    initial 0.02 as Kimi's `one_layer`)."""
    cfg = lh.LingHybridConfig.tiny(n_head=8, max_seq_len=4096,
                                   dtype=jnp.float32,
                                   param_dtype=jnp.float32)
    variables = lh.LingHybrid(cfg).init(jax.random.PRNGKey(0),
                                        jnp.ones((1, 8), jnp.int32))
    lp = lh.unboxed_params(variables)[f"layer{cfg.mla_layers[0]}"]
    assert cfg.mla.n_head == 8 and cfg.mla.rope_factor == 1.0
    return cfg.mla, {"kv_b": lp["kv_b"] * 20}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("lanes, shift", WALK_CASES, ids=str)
def test_the_mla_walk_is_one_softmax_over_each_lanes_own_latents(
        mla_layer, lanes, shift, dtype):
    """Kimi's absorbed attention at this family's shapes (`cfg.mla`), lanes
    of unequal positions at 1, 2, 4 and 16 lanes against one dense float64
    softmax a lane (`tests/test_kimi_k2.py` `check_the_walk`): a lane that
    holds nothing, a few keys, several key blocks, the whole table; what
    lies past a lane's position is garbage or NaN."""
    check_the_walk(*mla_layer, lanes, shift, dtype)


def test_lanes_of_unequal_length_give_the_references_tokens():
    """Five requests of 30 to 1,950 prompt tokens (one to four key blocks
    of 640), the short ones first, so that they decode while the long ones
    are chunked in: the buckets of one, four and eight. Every request's
    tokens are the reference's greedy ones, and after every step
    `decode_attn_key_slots` has grown by the count of the walk the program
    ran (one MLA layer), made on the host with the program's function."""
    cfg = lh.LingHybridConfig.tiny(max_seq_len=2048, dtype=jnp.float32,
                                   param_dtype=jnp.float32)
    eng = LLMEngine(
        model="ling_hybrid", model_cfg=cfg, engine_config=EngineConfig(
            batch_buckets=(1, 4, 8), prefill_buckets=(64,), prefill_chunk=256,
            num_pages=760, block_size=8, prefix_cache=0))
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
        prompts = [[int(x) for x in rng.integers(1, 500, n)]
                   for n, _ in sizes]
        reqs = [eng.submit(p, new) for p, (_, new) in zip(prompts, sizes)]
        eng.run_until_idle()
        for req, prompt, (n, new) in zip(reqs, prompts, sizes):
            # one full pass over the prompt and the answer: every token is
            # the largest logit of the row before it
            tokens = req.result()
            rows = _reference_logits(eng, prompt + tokens[:-1])[n - 1:]
            assert len(tokens) == new
            assert tokens == np.argmax(rows, axis=-1).tolist()
        most = 0
        for positions, grown in steps:
            trips, width, keys, _ = kimi_k2.absorbed_walk(
                positions, eng.max_pages_per_seq, 8, np)
            assert (width, keys) == (
                kimi_k2.PAIRS_A_LANE * len(positions), kimi_k2.LATENT_BLOCK)
            most = max(most, int(trips))
            assert grown == len(cfg.mla_layers) * (
                len(positions) + int(trips) * width * keys) > 0
        assert {len(p) for p, _ in steps} == {1, 4, 8} and most > 1
        eng.quiesce()
    finally:
        assert eng.shutdown() == 0


def test_slots_are_taken_with_the_pages_and_freed_at_the_end():
    """A slot a sequence from admission to its last token; `metrics()`
    counts them; quiesce proves none is left; a leaked slot fails quiesce
    as a leaked page does and is counted by `shutdown`."""
    from ray_tpu.serve.llm import KVCacheError

    eng = _engine()
    # the family's module declares what a sequence keeps beside its pages,
    # and the engine built its state arena from that: a row a slot
    assert [(a.shape[1:], a.dtype) for a in eng.kv.state] == [
        (shape, jnp.dtype(dtype))
        for shape, dtype in lh.seq_state(eng.model_cfg)]
    assert eng.kv.num_slots == 4 and eng.kv.scratch_slot == 4
    assert [a.shape[0] for a in eng.kv.state] == [5, 5]
    assert eng.kv.arena[0].shape[1] == 1            # one paged layer of 7
    reqs = [eng.submit([3, 4, 5, 6], 12) for _ in range(5)]
    for _ in range(4):
        eng.step()
    m = eng.metrics()
    assert m["state_slots_live"] == 4 and m["state_slots_free"] == 0
    assert m["queue_depth"] == 1                    # the fifth waits
    with pytest.raises(KVCacheError, match="no state slot free"):
        eng.kv.take_slot("a fifth")                 # a bug, not load
    assert m["state_arena_bytes"] == 5 * 6 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    eng.run_until_idle()
    assert all(len(r.result()) == 12 for r in reqs)
    eng.quiesce()
    assert eng.kv.free_slots == 4 and eng.metrics()["kv_pages_live"] == 0
    leaked = eng.kv.take_slot("someone")
    with pytest.raises(KVCacheError, match="slot leak"):
        eng.quiesce()
    with pytest.raises(KVCacheError):
        eng.kv.free_slot(leaked, "someone else")
    assert eng.shutdown() == 1


def test_the_prefix_cache_is_refused_with_a_reason():
    with pytest.raises(ValueError, match="one state a sequence"):
        LLMEngine(model="ling_hybrid",
                  engine_config=EngineConfig(prefix_cache=1))
    # the other families keep theirs, and keep no slots
    eng = LLMEngine(model="llama", engine_config=EngineConfig(
        batch_buckets=(1,), prefill_buckets=(8,)))
    try:
        assert eng.prefix is not None and eng.kv.state == ()
        assert "state_slots_live" not in eng.metrics()
    finally:
        assert eng.shutdown() == 0


def test_the_family_is_imported_only_when_selected():
    """`ray_tpu.models`, `ray_tpu.serve.llm` and an engine of another
    family leave `ling_hybrid` unimported; the lazy export finds it."""
    code = (
        "import sys; import ray_tpu.models, ray_tpu.serve.llm.engine; "
        "from ray_tpu.serve.llm.engine import LLMEngine; "
        "LLMEngine(model='llama').shutdown(); "
        "assert 'ray_tpu.models.ling_hybrid' not in sys.modules; "
        "from ray_tpu.models import LingHybrid, LingHybridConfig; "
        "assert 'ray_tpu.models.ling_hybrid' in sys.modules")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=300)
