"""The Brumby family (`ray_tpu/models/brumby.py`): the symmetric degree-2
embedding, power retention in its chunk form and its step form against the
plain reference's attention form (which has no state and no embedding), and
the family through the engine on state alone (no kind of page, the state
arena updated in place by slot) against the reference's full forward pass.

CPU, tiny sizes, seeded weights; float32 unless a test says otherwise.
"""

import contextlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import brumby as ref
from ray_tpu.models import brumby as bm
from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine

EPS = 1e-6
TINY = {"num_hidden_layers": 3, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 8, "rms_norm_eps": 1e-6,
        "rope_theta": 1e6,
        "assumed": {"retention_degree": 2, "retention_eps": EPS}}


# -- the embedding ------------------------------------------------------------

@pytest.mark.parametrize("d", [2, 8, 128])
def test_the_embeddings_inner_product_is_the_squared_dot(d):
    rng = np.random.default_rng(d)
    x, y = (jnp.asarray(rng.normal(size=(3, 5, d)), jnp.float32)
            for _ in range(2))
    px, py = bm.phi(x), bm.phi(y)
    assert px.shape == (3, 5, d * (d + 1) // 2)
    np.testing.assert_allclose(
        np.sum(np.asarray(px, np.float64) * np.asarray(py, np.float64), -1),
        np.sum(np.asarray(x, np.float64) * np.asarray(y, np.float64), -1)
        ** 2, rtol=1e-4, atol=1e-4)
    # every unordered pair once: the entries are the products themselves
    one = rng.normal(size=d).astype(np.float32)
    pairs = [one[a] * one[b] * (1.0 if a == b else np.sqrt(2.0))
             for a in range(d) for b in range(a, d)]
    np.testing.assert_allclose(
        np.sort(np.asarray(bm.phi(jnp.asarray(one)))), np.sort(pairs),
        rtol=1e-5, atol=1e-7)
    assert bm.BrumbyConfig().state_dim == 8256
    assert bm.BrumbyConfig.tiny().state_dim == 36


# -- the retention, three ways ------------------------------------------------

def _inputs(t, decays, seed=0, b=2, h=4, kv=2, d=8):
    """q (scaled), k, v and log g [B, T, ...] with decays at both ends: a
    head that forgets in two tokens beside one that keeps everything."""
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(b, t, h, d)) * d ** -0.5
    k, v = rng.normal(size=(2, b, t, kv, d))
    g = {"slow": rng.uniform(0.995, 0.99999, (b, t, kv)),
         "fast": rng.uniform(0.05, 0.5, (b, t, kv)),
         "mixed": np.stack([rng.uniform(0.05, 0.5, (b, t)),
                            rng.uniform(0.999, 1.0, (b, t))], -1)}[decays]
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, np.log(g)))


def _zero_state(b=2, kv=2, d=8):
    big = d * (d + 1) // 2
    return jnp.zeros((b, kv, big, d)), jnp.zeros((b, kv, big))


def _attention_form(q, k, v, log_g):
    with jax.default_matmul_precision("highest"):
        return np.stack([np.asarray(ref.retention(
            q[i], k[i], v[i], log_g[i], EPS)) for i in range(q.shape[0])])


def _step_by_step(q, k, v, log_g, s, z):
    out = []
    for i in range(q.shape[1]):
        o, s, z = bm.retention_step(q[:, i], k[:, i], v[:, i], log_g[:, i],
                                    s, z, EPS)
        out.append(o)
    return jnp.stack(out, 1), s, z


# the state form sums D signed products where the attention form squares one
# dot: where a query is nearly orthogonal to every key it has seen, float32
# leaves the two 1e-4 of the largest output apart (the faults planted below
# move it by tenths)
def _close(got, want, rel=5e-4):
    got, want = np.asarray(got), np.asarray(want)
    assert np.max(np.abs(got - want)) <= rel * np.max(np.abs(want)), \
        np.max(np.abs(got - want)) / np.max(np.abs(want))


@pytest.mark.parametrize("decays", ["slow", "fast", "mixed"])
@pytest.mark.parametrize("t, block", [(16, 16), (48, 16), (50, 16), (37, 8)])
def test_chunk_form_equals_step_form_equals_attention_form(t, block, decays):
    """Blocks that do and do not divide the sequence; the chunk form, the
    recurrence token by token, and the reference's [T, T] weights give one
    result, and the first two one state."""
    q, k, v, log_g = _inputs(t, decays, seed=t)
    want = _attention_form(q, k, v, log_g)
    o_chunk, s_chunk, z_chunk = bm.retention_chunk(
        q, k, v, log_g, *_zero_state(), EPS, block=block)
    o_step, s_step, z_step = _step_by_step(q, k, v, log_g, *_zero_state())
    _close(o_chunk, want)
    _close(o_step, want)
    _close(s_chunk, s_step, 2e-5)
    _close(z_chunk, z_step, 2e-5)


@pytest.mark.parametrize("cuts", [(16, 16, 16), (5, 30, 13)])
def test_windows_with_the_state_carried_equal_one_pass(cuts):
    q, k, v, log_g = _inputs(48, "mixed", seed=3)
    want = _attention_form(q, k, v, log_g)
    state, out, at = _zero_state(), [], 0
    for n in cuts:
        o, *state = bm.retention_chunk(
            *(x[:, at:at + n] for x in (q, k, v, log_g)), *state, EPS,
            block=16)
        out.append(o)
        at += n
    _close(jnp.concatenate(out, 1), want)


def test_padded_rows_leave_the_state_as_it_was():
    """A row with k = 0 and log g = 0 (what the window forward makes of a
    row that is no token) changes neither S nor z."""
    q, k, v, log_g = _inputs(20, "mixed", seed=5)
    _, s, z = bm.retention_chunk(q[:, :13], k[:, :13], v[:, :13],
                                 log_g[:, :13], *_zero_state(), EPS, block=8)
    live = (jnp.arange(20) < 13)[None, :, None]
    _, s_pad, z_pad = bm.retention_chunk(
        q, jnp.where(live[..., None], k, 0.0), v, jnp.where(live, log_g, 0.0),
        *_zero_state(), EPS, block=8)
    _close(s_pad, s, 1e-6)
    _close(z_pad, z, 1e-6)


def test_every_exponent_is_at_most_zero():
    """Decays are one scalar a key head a token: the strongest decay over a
    whole window underflows to 0 and nothing overflows."""
    q, k, v, _ = _inputs(64, "fast", seed=7)
    log_g = jnp.full((2, 64, 2), -40.0)         # exp(-40 * 64) is 0
    o, s, z = bm.retention_chunk(q, k, v, log_g, *_zero_state(), EPS,
                                 block=16)
    for x in (o, s, z):
        assert bool(jnp.all(jnp.isfinite(x)))
    _close(o, _attention_form(q, k, v, log_g))


# -- through the engine -------------------------------------------------------

def _engine(dtype=jnp.float32, **kw):
    base = dict(batch_buckets=(1, 4), prefill_buckets=(16, 32),
                prefill_chunk=32, max_running=4, prefix_cache=0)
    base.update(kw)
    cfg = bm.BrumbyConfig.tiny(dtype=dtype, param_dtype=dtype)
    return LLMEngine(model="brumby", model_cfg=cfg,
                     engine_config=EngineConfig(**base))


def _reference_logits(eng, ids):
    import flax.linen as nn

    params = nn.meta.unbox(eng.params)["params"]
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.full_logits(params, TINY,
                                          jnp.asarray(ids, jnp.int32)))


def _engine_logits(eng, prompt, steps):
    """The logits rows the engine's tokens were chosen from, as
    `tests/test_ling_hybrid.py` catches them: the prefill's last row at
    `np.argmax` (`_emit_first`), then a decode step's one live row a token
    from the engine's own program maker over a module whose step returns its
    logits a second time."""
    import types

    def decode_step(*args, **kwargs):
        logits, *out = bm.decode_step(*args, **kwargs)
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


def _prompt(seed, n):
    return [int(x) for x in np.random.default_rng(seed).integers(0, 512, n)]


# The engine's rows against the reference's full forward pass over prompt
# and answer: the largest difference over 6 rows of 512 logits, as a share
# of the rows' rms. float32: the chunk form, the recurrence and the
# reference's [T, T] weights order their sums differently, 2e-4 (the planted
# faults below read 4e-3 and more: a state stored in bf16, the gate left out,
# a chunk that starts from a zero state, degree 1, no normaliser). bf16
# weights and activations with the state, the decays and the retention's
# arithmetic float32: the largest of 3,072 roundings through three layers of
# width 64 reads 0.05; another sequence's state is another row, over 1.
TOLERANCE = {jnp.float32: 2e-4, jnp.bfloat16: 0.15}


def _worst(eng, n, steps=6):
    prompt = _prompt(n, n)
    tokens, rows = _engine_logits(eng, prompt, steps)
    want = _reference_logits(eng, prompt + tokens[:-1])[n - 1:]
    return float(np.max(np.abs(rows - want)) / np.sqrt(np.mean(want ** 2)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("n, path", [(20, "prefill:32"), (75, "chunk:32")],
                         ids=["oneshot", "chunked"])
def test_prefill_then_decode_agrees_with_the_reference(n, path, dtype):
    """One-shot prefill (20 tokens in the bucket of 32) and chunked prefill
    (75 tokens: two whole chunks and one of 11, the state carried through the
    arena's slot), then 5 decode steps through the slot."""
    eng = _engine(dtype)
    try:
        assert _worst(eng, n) < TOLERANCE[dtype]
        calls = eng.metrics()["compiled_step_calls"]
        assert path in calls and calls["decode:1"] == 5
        eng.quiesce()
    finally:
        assert eng.shutdown() == 0


@contextlib.contextmanager
def planted(fault):
    """`models/brumby.py` with one fault in the mechanism, for a control that
    has to fail: the engine traces the module's functions when it compiles, so
    an engine built inside computes the fault. (The chip's readings of
    `check.shortfall_limit` plant the same five.)"""
    saved = {name: getattr(bm, name) for name in (
        "_project", "phi_turn", "power", "_normalised", "_window_forward",
        "seq_state")}
    if fault == "gate_left_out":            # g = 1
        def project(*args):
            q, k, v, log_g = saved["_project"](*args)
            return q, k, v, jnp.zeros_like(log_g)
        bm._project = project
    elif fault == "degree_1":               # w_ij = exp(c_i - c_j) (q_i . k_j)
        bm.phi_turn = lambda x, turned, t: x * (t == 0)     # phi(x) = [x, 0]
        bm.power = lambda scores: scores
    elif fault == "normaliser_left_out":
        bm._normalised = lambda num, den, eps: num
    elif fault == "chunk_from_zero":
        def window_forward(*args, carried):
            return saved["_window_forward"](
                *args, carried=jnp.zeros_like(carried))
        bm._window_forward = window_forward
    elif fault == "state_in_bf16":
        bm.seq_state = lambda cfg: tuple(
            (shape, jnp.bfloat16) for shape, _ in saved["seq_state"](cfg))
    else:
        raise ValueError(fault)
    try:
        yield
    finally:
        for name, fn in saved.items():
            setattr(bm, name, fn)


FAULTS = ("gate_left_out", "degree_1", "normaliser_left_out",
          "chunk_from_zero", "state_in_bf16")


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_in_the_mechanism_fails_the_tolerance(fault):
    """Each planted fault puts the chunked prompt's rows over twenty times
    the float32 tolerance that the sound program keeps."""
    with planted(fault):
        eng = _engine()
        try:
            assert _worst(eng, 75) > 20 * TOLERANCE[jnp.float32]
        finally:
            eng.shutdown()


def test_a_reused_slot_starts_from_zero():
    """The second and third requests take slots that earlier ones gave back,
    whose states they left behind: a sequence's first prefill unit starts
    from zero, one-shot and chunked, and its logits are a fresh engine's bit
    for bit."""
    prompts = [_prompt(s, n) for s, n in ((1, 30), (2, 25), (3, 70))]
    eng = _engine()
    try:
        _engine_logits(eng, prompts[0], 8)
        assert eng.kv.free_slots == 4
        assert float(jnp.max(jnp.abs(eng.kv.state[0]))) > 0    # left behind
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


def test_four_lanes_of_different_lengths_share_a_decode_bucket():
    """Four running sequences (one-shot and chunked prompts) in the bucket of
    four, then three with one lane padded as they end: each streams the
    tokens the reference's full forward pass puts on top, and the family's
    counters count the live lanes only."""
    prompts = [_prompt(20 + n, n) for n in (9, 40, 70, 23)]
    news = (6, 9, 7, 12)
    eng = _engine()
    try:
        reqs = [eng.submit(p, n) for p, n in zip(prompts, news)]
        eng.run_until_idle()
        for prompt, req in zip(prompts, reqs):
            tokens = req.result()
            rows = _reference_logits(eng, prompt + tokens[:-1])
            assert tokens == [int(r.argmax())
                              for r in rows[len(prompt) - 1:]]
        m = eng.metrics()
        assert m["compiled_step_calls"]["decode:4"] >= 5
        decoded = m["tokens_generated"] - 4
        assert decoded == sum(news) - 4
        # a live lane a token: three layers' states read and written
        assert m["decode_retention_state_rows"] == 3 * decoded
        assert m["decode_retention_tokens"] == decoded
        # every prompt token folded once; a state row a unit a layer: the
        # prompts of 9 and 23 one unit, 40 two chunks, 70 three
        assert m["prefill_retention_tokens"] == 9 + 40 + 70 + 23
        assert m["prefill_retention_state_rows"] == 3 * (1 + 2 + 3 + 1)
        eng.quiesce()
    finally:
        assert eng.shutdown() == 0


def test_a_cache_manager_with_no_kind_of_page():
    """Admission takes a slot and nothing else: no page exists, whatever
    counts pages reads 0, more requests than slots wait and then run, quiesce
    proves no slot is left, and a leaked slot fails it."""
    from ray_tpu.serve.llm import KVCacheError

    eng = _engine()
    kv = eng.kv
    assert kv.pools == () and kv.arena == () and kv.kinds == ()
    assert (kv.num_pages, kv.arena_nbytes, kv.n_layer) == (0, 0, 0)
    assert [(a.shape[1:], a.dtype) for a in kv.state] == [
        (shape, jnp.dtype(dtype))
        for shape, dtype in bm.seq_state(eng.model_cfg)]
    assert kv.num_slots == 4 and kv.scratch_slot == 4
    assert kv.reserve(10_000, "anyone") == ()       # nothing, and no refusal
    kv.release((), "anyone")
    assert eng.prefix is None
    reqs = [eng.submit(_prompt(i, 6 + 9 * i), 12) for i in range(7)]
    for _ in range(6):
        eng.step()
    m = eng.metrics()
    assert m["state_slots_live"] == 4 and m["state_slots_free"] == 0
    assert m["queue_depth"] == 3                    # three wait for a slot
    assert m["kv_pages_live"] == m["kv_pages_total"] == 0
    assert m["kv_pages_cached"] == 0 and m["kv_page_utilization"] == 0.0
    assert m["state_arena_bytes"] == 5 * 3 * 2 * 36 * (8 + 1) * 4
    with pytest.raises(KVCacheError, match="no state slot free"):
        kv.take_slot("a fifth")
    eng.run_until_idle()
    assert all(len(r.result()) == 12 for r in reqs)
    m = eng.metrics()
    assert m["decode_context_tokens"] == m["chunk_context_tokens"] == 0
    assert m["requests_completed"] == 7
    eng.quiesce()
    assert kv.free_slots == 4 and kv.live_pages == 0
    leaked = kv.take_slot("someone")
    with pytest.raises(KVCacheError, match="slot leak"):
        eng.quiesce()
    kv.free_slot(leaked, "someone")
    eng.quiesce()
    assert eng.shutdown() == 0


def test_a_decode_step_updates_the_arena_at_the_lanes_slots_alone():
    """The step takes the arena and returns the arena: the live lanes' slots
    hold their new states, every other slot is what it was bit for bit, and
    padded lanes write the scratch slot only."""
    cfg = bm.BrumbyConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32)
    variables = bm.Brumby(cfg).init(jax.random.PRNGKey(0),
                                    jnp.ones((1, 8), jnp.int32))
    rng = np.random.default_rng(0)
    arena = tuple(jnp.asarray(rng.normal(size=(6,) + shape), dt)
                  for shape, dt in bm.seq_state(cfg))
    slots = jnp.asarray([3, 0, 5, 5], jnp.int32)    # two live, two padded
    logits, s, z, counts = bm.decode_step(
        variables, cfg, jnp.asarray([5, 6, 0, 0]), jnp.asarray([9, 2, 0, 0]),
        seq_state=arena, slots=slots,
        valid=jnp.asarray([True, True, False, False]))
    assert logits.shape == (4, 512) and counts.tolist() == [2 * 3, 2]
    for new, old in ((s, arena[0]), (z, arena[1])):
        changed = [bool(jnp.any(new[i] != old[i])) for i in range(6)]
        assert changed == [True, False, False, True, False, True]


def test_the_prefix_cache_is_refused_with_a_reason():
    with pytest.raises(ValueError, match="one state a sequence"):
        LLMEngine(model="brumby", engine_config=EngineConfig(prefix_cache=1))


def test_the_family_is_imported_only_when_selected():
    code = (
        "import sys; import ray_tpu.models, ray_tpu.serve.llm.engine; "
        "from ray_tpu.serve.llm.engine import LLMEngine; "
        "LLMEngine(model='llama').shutdown(); "
        "assert 'ray_tpu.models.brumby' not in sys.modules; "
        "from ray_tpu.models import Brumby, BrumbyConfig; "
        "assert 'ray_tpu.models.brumby' in sys.modules")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=300)
