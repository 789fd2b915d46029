"""The SDAR-MoE family (`ray_tpu/models/sdar_moe.py`): generation by
diffusion over blocks through the paged engine against its plain reference
(`benchmark/references/sdar_moe.py`), the block-causal mask in its three
steps, the softmax router against a plain selection, and the chip's share
against the uncut layer.

CPU, tiny sizes, seeded weights, float32. Tolerances: a logit row of the
program against the reference's within 1e-3 of the row's rms (both float32;
what differs is the order of the sums: a running softmax over key blocks
against one softmax, a grouped product over sorted pairs against every
expert weighted); the three wrong programs below lie 0.02 to 1 of a row's
rms away or reveal other positions.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import sdar_moe as ref
from ray_tpu import parallel
from ray_tpu.models import sdar_moe as sdar
from ray_tpu.parallel import moe
from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine

ROW_TOLERANCE = 1e-3
ENGINE = dict(batch_buckets=(1, 2, 8), prefill_buckets=(8, 16),
              prefill_chunk=8, prefix_cache=0, block_size=8, num_pages=96)


def _ref_config(cfg, new_tokens=0):
    """The reference's view of a model config: the configuration file's
    keys."""
    return {"num_hidden_layers": cfg.n_layer,
            "num_attention_heads": cfg.n_head,
            "num_key_value_heads": cfg.n_kv_head, "head_dim": cfg.head_dim,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "num_experts": cfg.n_experts, "num_experts_per_tok": cfg.top_k,
            "norm_topk_prob": True,
            "generation": {"block_length": cfg.block_length,
                           "denoise_steps": cfg.denoise_steps,
                           "mask_token_id": cfg.mask_token,
                           "check_new_tokens": new_tokens}}


def _ref_params(eng):
    return jax.tree_util.tree_map(np.asarray,
                                  sdar.unboxed_params(eng.params))


class _Spy:
    """Records every pass of an engine stepped by hand: the logits the
    program chose from (a callback inside `choose`, traced into the
    engine's programs), and, from the sequences' flags before and after,
    which positions each lane revealed."""

    def __init__(self, monkeypatch):
        self.logits = []
        choose = sdar.choose

        def spied(logits, mask_token):
            jax.debug.callback(
                lambda x: self.logits.append(np.asarray(x)), logits,
                ordered=True)
            return choose(logits, mask_token)

        monkeypatch.setattr(sdar, "choose", spied)

    def attach(self, eng, tamper=None):
        forward = eng._decode_forward
        self.lanes = None

        def spied(fn, args):
            self.lanes = [(seq, seq.pos, list(seq.revealed))
                          for seq in eng._running]
            if tamper is not None:
                args = tamper(eng, args)
            out = forward(fn, args)
            jax.effects_barrier()
            return out

        eng._decode_forward = spied

    def drive(self, eng, reqs):
        """Step until idle. Returns a log a request: `rows` {position: the
        logits it was chosen from}, `reveals` [(block start, positions)],
        `passes` (lane passes), `commits`."""
        logs = {id(r): {"rows": {}, "reveals": [], "passes": 0,
                        "commits": 0} for r in reqs}
        for _ in range(10_000):
            if not eng.has_work():
                return [logs[id(r)] for r in reqs]
            self.lanes = None
            eng.step()
            for i, (seq, pos, was) in enumerate(self.lanes or ()):
                log = logs[id(seq.req)]
                log["passes"] += 1
                if not all(was):
                    now = [j for j in range(len(was))
                           if seq.revealed[j] and not was[j]]
                    log["reveals"].append((pos, [pos + j for j in now]))
                    for j in now:
                        log["rows"][pos + j] = self.logits[-1][i, j]
                else:
                    log["commits"] += 1
        raise AssertionError("the engine did not drain")


def _engine(**kw):
    return LLMEngine(model="sdar_moe",
                     engine_config=EngineConfig(**{**ENGINE, **kw}), seed=0)


def _distance(log, want, first, last):
    """(largest distance of a program's row from the reference's, in the
    reference row's rms, over the answer's positions; whether the program
    revealed what the reference revealed, pass by pass)."""
    worst = max(
        float(np.max(np.abs(log["rows"][at] - want["rows"][at]))
              / np.sqrt(np.mean(want["rows"][at] ** 2)))
        for at in range(first, last + 1))
    return worst, log["reveals"] == want["reveals"]


def _prompt(n, seed):
    return [int(t) for t in
            np.random.RandomState(seed).randint(1, 500, size=n)]


# -- the router and the share -------------------------------------------------

def test_softmax_topk_route_is_a_plain_softmax_top_k():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(37, 24)).astype(np.float32)
    w = rng.normal(size=(24, 16)).astype(np.float32)
    expert, weight = moe.softmax_topk_route(jnp.asarray(x), jnp.asarray(w), 4)
    logit = x.astype(np.float64) @ w.astype(np.float64)
    p = np.exp(logit - logit.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.argsort(-p, axis=-1)[:, :4]
    assert (np.asarray(expert) == want).all()
    chosen = np.take_along_axis(p, want, -1)
    np.testing.assert_allclose(
        weight, chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)
    assert weight.dtype == jnp.float32 and expert.dtype == jnp.int32


@pytest.mark.parametrize("shards", [1, 2, 4])
def test_the_shares_add_up_to_the_uncut_layer(shards):
    """The tie of share to model: the partial results of the chips that
    hold 16 / shards experts each add up to every expert computed for
    every token and weighted by the route (the reference's layer)."""
    rng = np.random.default_rng(1)
    n, d, f, e, k = 29, 32, 16, 16, 4
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    lp = {"router": jnp.asarray(rng.normal(size=(d, e)), jnp.float32),
          "experts_gate_up": jnp.asarray(
              rng.normal(size=(e, d, 2 * f)) * 0.2, jnp.float32),
          "experts_down": jnp.asarray(
              rng.normal(size=(e, f, d)) * 0.2, jnp.float32)}
    config = {"norm_topk_prob": True, "num_experts_per_tok": k,
              "num_experts": e}
    with jax.default_matmul_precision("highest"):
        want = ref.experts(lp, config, x)
        held = e // shards
        total, pairs = 0.0, 0
        for s in range(shards):
            part, counts = moe.expert_shard_layer(
                x, lp["router"], None,
                {"gate_up": lp["experts_gate_up"][s * held:(s + 1) * held],
                 "down": lp["experts_down"][s * held:(s + 1) * held]},
                s * held, e, k, 1.0, route=moe.softmax_topk_route)
            total = total + part
            pairs += int(counts[1])
            assert int(counts[0]) == n * k
    assert pairs == n * k
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=1e-4)


# -- the steps under the block-causal mask ------------------------------------

def test_the_modules_forward_is_the_references():
    cfg = sdar.SdarMoeConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32)
    net = sdar.SdarMoe(cfg)
    params = net.init(jax.random.PRNGKey(2), jnp.ones((1, 8), jnp.int32))
    ids = np.asarray(_prompt(23, 5))
    with jax.default_matmul_precision("highest"):
        got = net.apply(params, jnp.asarray(ids[None]))[0]
    want = ref.full_logits(sdar.unboxed_params(params), _ref_config(cfg),
                           ids)
    assert float(np.std(np.asarray(want))) > 0.01
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)
    # causal inside a block is another model: position 0's row moves
    causal = ref.full_logits(
        sdar.unboxed_params(params),
        {**_ref_config(cfg), "generation": {"block_length": 1}}, ids)
    assert float(np.max(np.abs(np.asarray(causal)[0] - np.asarray(want)[0]))) \
        > 1e-3


@pytest.mark.parametrize("n_prompt, new", [
    (13, 8), (16, 8), (6, 5), (7, 4), (2, 5), (3, 1), (27, 1), (29, 12)])
def test_a_generation_is_the_replayed_references(monkeypatch, n_prompt, new):
    """Prompts of every length mod 4, shorter than a block, one-shot (up to
    8 whole-block tokens) and chunked (up to four chunks of 8); answers that end
    on a block's edge and inside a block. Every logit row a token was
    chosen from against the reference's replay, the revealed positions pass
    by pass, the tokens, the passes, and every page free at the end."""
    spy = _Spy(monkeypatch)
    eng = _engine()
    try:
        spy.attach(eng)
        prompt = _prompt(n_prompt, 100 + n_prompt)
        req = eng.submit(prompt, new)
        (log,) = spy.drive(eng, [req])
        tokens = req.result(timeout=5)
        assert len(tokens) == new and req.finish_reason == "length"
        cfg = eng.model_cfg
        want = ref.replay(_ref_params(eng), _ref_config(cfg), prompt, tokens,
                          new)
        worst, same = _distance(log, want, n_prompt, n_prompt + new - 1)
        assert same, (log["reveals"], want["reveals"])
        assert worst < ROW_TOLERANCE
        assert tokens == want["tokens"]
        assert cfg.mask_token not in tokens
        # the harness's call: prompt + answer[:-1], rows read at
        # len(prompt) - 1 + j; the last token is the reference's own choice
        rows = ref.logits(_ref_params(eng), _ref_config(cfg, new),
                          np.asarray(prompt + tokens[:-1]))
        for j, token in enumerate(tokens):
            assert int(rows[n_prompt - 1 + j].argmax()) == token or \
                rows[n_prompt - 1 + j][token] == \
                np.delete(rows[n_prompt - 1 + j], cfg.mask_token).max()
        m = eng.metrics()
        whole = n_prompt - n_prompt % 4
        assert m["prefill_steps"] == (1 if whole else 0)
        assert m["chunk_steps"] == (-(-whole // 8) if whole > 8 else 0)
        assert m["decode_lane_passes"] == log["passes"] == m["decode_steps"]
        assert m["decode_lane_commits"] == m["decode_blocks_committed"] \
            == log["commits"] == (n_prompt % 4 + new - 1) // 4
        assert m["decode_tokens_revealed"] == \
            sum(len(r) for _, r in log["reveals"])
        assert m["tokens_generated"] == new
        assert m["decode_moe_pairs_routed"] == \
            log["passes"] * 4 * cfg.top_k * cfg.n_layer
        eng.quiesce()
        assert m["kv_pages_live"] == 0
    finally:
        assert eng.shutdown() == 0


@pytest.mark.parametrize("lanes", [2, 5])
def test_lanes_at_different_passes_share_a_program_call(monkeypatch, lanes):
    """Requests admitted one a step stand at different passes of their
    blocks (denoising, committing, a part block) in every call of the
    decode program; each gets the tokens and the logit rows it gets alone,
    and nothing is traced again across the passes."""
    spy = _Spy(monkeypatch)
    sizes = [(13, 9), (6, 8), (16, 5), (3, 12), (22, 6)][:lanes]
    prompts = [_prompt(n, 7 + n) for n, _ in sizes]
    alone = []
    for prompt, (_, new) in zip(prompts, sizes):
        eng = _engine()
        try:
            spy.attach(eng)
            req = eng.submit(prompt, new)
            (log,) = spy.drive(eng, [req])
            alone.append((req.result(timeout=5), log))
        finally:
            assert eng.shutdown() == 0
    eng = _engine()
    try:
        spy.attach(eng)
        before = parallel.cache_stats()
        reqs = [eng.submit(p, new) for p, (_, new) in zip(prompts, sizes)]
        logs = spy.drive(eng, reqs)
        after = parallel.cache_stats()
        assert after["retraces"] == before["retraces"] == 0
        # a prefill bucket or two, the chunk, and the decode buckets the
        # running set walked through: nothing else was compiled
        assert after["misses"] - before["misses"] <= 6
        m = eng.metrics()
        assert m["decode_steps"] < m["decode_lane_passes"]
        for req, log, (tokens, log_alone) in zip(reqs, logs, alone):
            assert req.result(timeout=5) == tokens
            assert log["reveals"] == log_alone["reveals"]
            for at, row in log_alone["rows"].items():
                np.testing.assert_allclose(log["rows"][at], row, atol=2e-5,
                                           rtol=1e-4)
        eng.quiesce()
    finally:
        assert eng.shutdown() == 0


# -- the walk over cached keys: each lane as far as its own last block --------

def _lanes_of(kind, n_pages, page):
    """`start` a lane, whole blocks of 4: a lane that holds nothing, a
    single page, the table's end, a pad lane of the bucket (nothing, and a
    page table of zeros), then lengths in between."""
    end = n_pages * page
    return {2: [page, end], 4: [0, page, end, 0],
            6: [end, end - 3 * page, 0, 320, page, 388]}[kind]


def _attend_plainly(q, k_new, v_new, k_pages, v_pages, layer, table, start,
                    scale):
    """One lane's block in float64: one softmax over its `start` cached
    keys and the block's own, every key of the block visible."""
    kvh, d = k_new.shape[1:]
    k = np.concatenate([k_pages[table, layer].reshape(-1, kvh, d)[:start],
                        k_new]).astype(np.float64)
    v = np.concatenate([v_pages[table, layer].reshape(-1, kvh, d)[:start],
                        v_new]).astype(np.float64)
    c, h, _ = q.shape
    qg = q.astype(np.float64).reshape(c, kvh, h // kvh, d)
    s = np.einsum("cgrd,kgd->cgrk", qg, k) * scale
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("cgrk,kgd->cgrd", p, v).reshape(c, h * d)


@pytest.mark.parametrize("dtype, tolerance", [
    (jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("lanes", [2, 4, 6])
def test_unequal_lanes_walk_their_own_blocks_and_lose_no_key(lanes, dtype,
                                                             tolerance):
    """`block_attend` over lanes of unequal `start` (blocks of 192 keys, a
    pair a lane a trip. 2 lanes: 1 + 3 pairs, two trips; 4: 0 + 1 + 3 + 0,
    one trip; 6: 3 + 3 + 0 + 2 + 1 + 3 = 12 pairs, two full trips) is each
    lane through the bucket of one's loop and the plain softmax."""
    page, n_pages, layers, kvh, group, d, c = 4, 128, 2, 2, 4, 16, 4
    start = np.asarray(_lanes_of(lanes, n_pages, page), np.int32)
    walk = sdar.key_walk(start, n_pages, page, np)
    assert walk[1:3] == (lanes, 192)
    assert int(walk[0]) == {2: 2, 4: 1, 6: 2}[lanes]
    rng = np.random.default_rng(lanes)
    n_arena = lanes * n_pages + 1
    k_pages, v_pages = (
        jnp.asarray(rng.normal(size=(n_arena, layers, page, kvh, d)), dtype)
        for _ in range(2))
    q = jnp.asarray(rng.normal(size=(lanes, c, kvh * group, d)), dtype)
    k_new, v_new = (jnp.asarray(rng.normal(size=(lanes, c, kvh, d)), dtype)
                    for _ in range(2))
    table = rng.permutation(n_arena - 1)[:lanes * n_pages].reshape(
        lanes, n_pages).astype(np.int32) + 1
    table[start == 0] = 0           # a lane without a sequence
    layer, scale = 1, d ** -0.5
    got = np.asarray(sdar.block_attend(
        q, k_new, v_new, (k_pages, v_pages), layer, jnp.asarray(table),
        jnp.asarray(start), block_length=4, scale=scale), np.float32)
    assert got.shape == (lanes, c, kvh * group * d)
    for i in range(lanes):
        alone = sdar.block_attend(
            q[i:i + 1], k_new[i:i + 1], v_new[i:i + 1], (k_pages, v_pages),
            layer, jnp.asarray(table[i:i + 1]), jnp.asarray(start[i:i + 1]),
            block_length=4, scale=scale)
        np.testing.assert_allclose(got[i], np.asarray(alone[0], np.float32),
                                   atol=tolerance, rtol=tolerance)
        want = _attend_plainly(
            *(np.asarray(a[i], np.float32) for a in (q, k_new, v_new)),
            np.asarray(k_pages, np.float32), np.asarray(v_pages, np.float32),
            layer, table[i], int(start[i]), scale)
        np.testing.assert_allclose(got[i], want, atol=tolerance,
                                   rtol=tolerance)


@pytest.mark.parametrize("lanes", [1, 2, 5, 8])
def test_the_host_and_the_program_count_one_walk(lanes):
    """`decode_key_walk` with `xp=np` (the engine's count) and with `xp=jnp`
    (what `block_attend` runs): the same trips, width, block and list."""
    cfg = sdar.SdarMoeConfig.tiny()
    rng = np.random.default_rng(lanes)
    positions = (rng.integers(0, 33, lanes) * 4).astype(np.int32)
    positions[-1] = 0 if lanes > 1 else 100
    host = sdar.decode_key_walk(cfg, positions, 16, 8, np)
    program = sdar.decode_key_walk(cfg, jnp.asarray(positions), 16, 8, jnp)
    assert (int(host[0]), host[1], host[2]) \
        == (int(program[0]), program[1], program[2])
    assert host[3] is None      # the host reads no pair
    if lanes == 1:      # the loop: one block a trip, the whole table's 128
        assert host[1:] == program[1:] == (1, 128, None)
        assert int(host[0]) == 1
    else:               # blocks of 128 too: the table is shorter than 192
        assert host[1:3] == (lanes, 128)
        listed = sdar.key_walk(positions, 16, 8, np)
        assert int(listed[0]) == int(host[0]) == -(-int(np.sum(
            -(-positions // 128))) // lanes) == -(-listed[3][2].sum()
                                                  // lanes)
        for mine, theirs in zip(listed[3], program[3]):
            assert len(mine) % lanes == 0
            assert (np.asarray(mine) == np.asarray(theirs)).all()


def test_lanes_of_unequal_length_replay_the_reference(monkeypatch):
    """Five requests of 3 to 391 prompt tokens (one to three key blocks of
    192), the short ones first, so that they decode while the long ones are
    chunked in: the buckets of one, two and eight (three pad lanes). Every
    request's rows, reveals and tokens are the reference's replay, and after
    every pass `decode_attn_key_slots` has grown by the host's count of the
    walk the program ran."""
    spy = _Spy(monkeypatch)
    eng = LLMEngine(
        model="sdar_moe", seed=0, model_cfg=sdar.SdarMoeConfig.tiny(
            dtype=jnp.float32, max_seq_len=512),
        engine_config=EngineConfig(**{
            **ENGINE, "prefill_buckets": (8, 64), "prefill_chunk": 64,
            "num_pages": 200}))
    try:
        spy.attach(eng)
        forward, passes = eng._decode_forward, []

        def counted(fn, args):
            before = eng.metrics()["decode_attn_key_slots"]
            out = forward(fn, args)
            passes.append((len(eng._running), np.array(args[2]), before))
            return out

        eng._decode_forward = counted
        sizes = [(3, 60), (100, 48), (203, 40), (250, 36), (391, 28)]
        prompts = [_prompt(n, 31 + n) for n, _ in sizes]
        reqs = [eng.submit(p, new) for p, (_, new) in zip(prompts, sizes)]
        logs = spy.drive(eng, reqs)
        cfg = eng.model_cfg
        for req, log, prompt, (n, new) in zip(reqs, logs, prompts, sizes):
            tokens = req.result(timeout=5)
            want = ref.replay(_ref_params(eng), _ref_config(cfg), prompt,
                              tokens, new)
            worst, same = _distance(log, want, n, n + new - 1)
            assert same and worst < ROW_TOLERANCE
            assert tokens == want["tokens"]
        after = [before for _, _, before in passes[1:]] \
            + [eng.metrics()["decode_attn_key_slots"]]
        most = 0
        for (running, positions, before), now in zip(passes, after):
            trips, width, keys, _ = sdar.decode_key_walk(
                cfg, positions, eng.max_pages_per_seq, 8, np)
            # the bucket of one loops in blocks of 256; eight lanes walk
            # whole trips of 8 pairs of 192, dead pairs included
            assert (width, keys) == (len(positions),
                                     256 if len(positions) == 1 else 192)
            most = max(most, int(trips))
            assert now - before == cfg.n_layer * (
                running * 4 + int(trips) * width * keys) > 0
        assert {len(p) for _, p, _ in passes} == {1, 2, 8} and most > 1
        m = eng.metrics()
        assert m["decode_attn_key_slots"] > cfg.n_layer * \
            m["decode_context_tokens"]
        eng.quiesce()
    finally:
        assert eng.shutdown() == 0


# -- three wrong programs, each of which the comparison has to refuse ---------

def _causal_in_block(monkeypatch):
    attend = sdar.block_attend

    def causal(*args, block_length, scale):
        return attend(*args, block_length=1, scale=scale)

    monkeypatch.setattr(sdar, "block_attend", causal)
    return None


def _commit_before_whole(monkeypatch):
    """A lane writes its block's rows in its last denoising pass, when some
    of its positions still hold the mask token, and the pass over the whole
    block writes nothing."""
    def tamper(eng, args):
        args = list(args)
        w_page, w_off = args[-3].copy(), args[-2].copy()
        length, reveal, _ = eng._block
        for i, seq in enumerate(eng._running):
            left = seq.revealed.count(False)
            if 0 < left <= reveal:
                w_page[i], w_off[i] = eng.kv.write_index(
                    seq.pages[0], seq.pos, length)
            elif not left:
                w_page[i] = eng.kv.num_pages
        args[-3], args[-2] = w_page, w_off
        return tuple(args)

    return tamper


def _reveal_the_least_sure(monkeypatch):
    choose = sdar.choose

    def least(logits, mask_token):
        token, prob = choose(logits, mask_token)
        return token, -prob

    monkeypatch.setattr(sdar, "choose", least)
    return None


@pytest.mark.parametrize("wrong", [
    _causal_in_block, _commit_before_whole, _reveal_the_least_sure])
def test_a_wrong_program_fails_the_comparison(monkeypatch, wrong):
    tamper = wrong(monkeypatch)
    spy = _Spy(monkeypatch)
    eng = _engine()
    try:
        spy.attach(eng, tamper)
        prompt, new = _prompt(13, 113), 12
        req = eng.submit(prompt, new)
        (log,) = spy.drive(eng, [req])
        tokens = req.result(timeout=5)
        want = ref.replay(_ref_params(eng), _ref_config(eng.model_cfg),
                          prompt, tokens, new)
        worst, same = _distance(
            {**log, "rows": {**want["rows"], **log["rows"]}}, want, 13,
            13 + new - 1)
        assert not same or worst > 20 * ROW_TOLERANCE, (worst, same)
    finally:
        eng.shutdown()


# -- the engine's options -----------------------------------------------------

def test_the_schedule_is_the_models_and_the_options_are_checked():
    cfg = sdar.SdarMoeConfig.tiny(block_length=8, denoise_steps=4)
    assert sdar.block_schedule(cfg) == (8, 2, 511)
    with pytest.raises(ValueError, match="do not divide"):
        sdar.block_schedule(sdar.SdarMoeConfig.tiny(denoise_steps=3))
    with pytest.raises(ValueError, match="prefix_cache"):
        _engine(prefix_cache=1)
    with pytest.raises(ValueError, match="multiples"):
        _engine(block_size=6)
    with pytest.raises(ValueError, match="multiples"):
        _engine(prefill_chunk=6)
    assert not any(f.name in ("block_length", "denoise_steps", "mask_token")
                   for f in EngineConfig.__dataclass_fields__.values())


def test_a_prompt_of_mask_token_ids_is_a_prompt(monkeypatch):
    """What is revealed is a flag the engine keeps, never `token == mask`:
    a prompt that holds the mask token's id is context like any other."""
    spy = _Spy(monkeypatch)
    eng = _engine()
    try:
        spy.attach(eng)
        prompt = [eng.model_cfg.mask_token] * 6
        req = eng.submit(prompt, 6)
        (log,) = spy.drive(eng, [req])
        tokens = req.result(timeout=5)
        want = ref.replay(_ref_params(eng), _ref_config(eng.model_cfg),
                          prompt, tokens, 6)
        worst, same = _distance(log, want, 6, 11)
        assert same and worst < ROW_TOLERANCE
        assert log["reveals"][0] == (4, sorted(log["reveals"][0][1]))
        assert all(at >= 6 for _, shown in log["reveals"] for at in shown)
    finally:
        assert eng.shutdown() == 0


def test_the_family_is_imported_only_when_selected():
    """`ray_tpu.models`, `ray_tpu.serve.llm` and an engine of another
    family leave `sdar_moe` unimported, and that engine has no block
    counter; the lazy export finds the family."""
    import subprocess
    import sys

    code = (
        "import sys; import ray_tpu.models, ray_tpu.serve.llm.engine; "
        "from ray_tpu.serve.llm.engine import LLMEngine; "
        "eng = LLMEngine(model='llama'); "
        "assert 'decode_lane_passes' not in eng.metrics(); eng.shutdown(); "
        "assert 'ray_tpu.models.sdar_moe' not in sys.modules; "
        "from ray_tpu.models import SdarMoe, SdarMoeConfig; "
        "assert 'ray_tpu.models.sdar_moe' in sys.modules")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=300)


def test_a_probability_that_is_no_number_does_not_stall_a_lane(monkeypatch):
    """Weights gone wrong give NaN probabilities: the lane still reveals
    `reveal` hidden positions a pass (the lowest indices, all tied at 0),
    ends and frees its pages, where a NaN sorted behind the revealed
    positions would reveal nothing for ever."""
    choose = sdar.choose

    def nan(logits, mask_token):
        token, prob = choose(logits, mask_token)
        return token, prob * jnp.nan

    monkeypatch.setattr(sdar, "choose", nan)
    spy = _Spy(monkeypatch)
    eng = _engine()
    try:
        spy.attach(eng)
        req = eng.submit(_prompt(5, 3), 7)
        (log,) = spy.drive(eng, [req])
        assert len(req.result(timeout=5)) == 7
        assert log["reveals"] == [(4, [5, 6]), (4, [7]), (8, [8, 9]),
                                  (8, [10, 11])]
        eng.quiesce()
    finally:
        assert eng.shutdown() == 0
