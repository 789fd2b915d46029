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
