"""serve.llm perf-plane tests: copy-on-write prefix caching, chunked
prefill, and speculative decoding.

The load-bearing properties:
  * shared pages are refcounted — a sequence freeing aliased pages can
    never force-free pages the prefix cache (or a sibling sequence)
    still references, and a page re-enters the free list only at
    refcount zero;
  * only FULL pages are ever aliased (a partial page's tail is still
    appended to), and the page holding the last prompt token is never
    aliased (its forward pass produces the first output token);
  * chunked prefill and speculative decoding are INVISIBLE in the
    output: token streams bit-match plain one-shot greedy for both
    model families, and accept-length variation never retraces.
"""

import numpy as np
import pytest


def _cache(**kw):
    from ray_tpu.serve.llm import PagedKVCache
    base = dict(num_pages=16, n_layer=2, block_size=4, n_kv_head=2,
                head_dim=4)
    base.update(kw)
    return PagedKVCache(**base)


def _prefix(kv):
    from ray_tpu.serve.llm import PrefixCache
    return PrefixCache(kv)


# ---------------------------------------------------------------------------
# prefix cache: aliasing + refcount accounting (no jax, no cluster)
# ---------------------------------------------------------------------------


def test_prefix_cache_hit_and_miss():
    kv = _cache()
    pc = _prefix(kv)
    prompt = list(range(100, 110))  # 10 tokens, block 4 -> 2 full pages
    a = object()
    pages_a, cached = pc.acquire(prompt, a, kv.pages_for_tokens(10))
    assert cached == 0  # cold cache: pure miss
    pc.insert(prompt, pages_a)
    assert pc.stats()["misses"] == 1 and pc.stats()["hits"] == 0
    # same prompt again: both full pages alias, only the tail page is new
    b = object()
    pages_b, cached = pc.acquire(prompt, b, kv.pages_for_tokens(10))
    assert cached == 8
    assert pages_b[:2] == pages_a[:2]      # aliased page ids
    assert pages_b[2] != pages_a[2]        # private tail page
    # page 0 backs BOTH registered sub-prefixes (4- and 8-token) plus
    # the two sequences — every hold is an independent refcount
    assert kv.page_refcount(pages_a[0]) == 4
    # a different prompt with the same first page: 1-page hit
    other = prompt[:4] + [999] * 6
    c = object()
    pages_c, cached = pc.acquire(other, c, kv.pages_for_tokens(10))
    assert cached == 4 and pages_c[0] == pages_a[0]
    st = pc.stats()
    assert st["hits"] == 2 and st["hit_tokens"] == 12
    assert st["miss_tokens"] == 10 + 2 + 6


def test_prefix_partial_page_boundary_never_aliased():
    kv = _cache()
    pc = _prefix(kv)
    a = object()
    prompt = list(range(7))  # 1 full page + 3 tokens
    pages, cached = pc.acquire(prompt, a, kv.pages_for_tokens(7))
    pc.insert(prompt, pages)
    # only the full page was registered — the partial page is mutable
    # (its tail is still appended to) and must stay private
    assert pc.entries == 1
    b = object()
    pages_b, cached = pc.acquire(prompt, b, kv.pages_for_tokens(7))
    assert cached == 4
    assert pages_b[1] != pages[1]
    # a prompt that IS page-aligned never aliases its own last page:
    # at least one suffix token must run prefill for next-logits
    aligned = list(range(50, 58))  # exactly 2 pages
    c, d = object(), object()
    pages_c, _ = pc.acquire(aligned, c, kv.pages_for_tokens(8))
    pc.insert(aligned, pages_c)
    pages_d, cached = pc.acquire(aligned, d, kv.pages_for_tokens(8))
    assert cached == 4  # NOT 8: the last page holds the last token
    assert pages_d[1] != pages_c[1]


def test_aliased_free_keeps_shared_pages():
    """The bugfix: freeing a sequence that aliased cached pages must
    not force-free pages still referenced by the prefix cache or by
    another running sequence (the pre-refcount free path released a
    page to the free list unconditionally — a sibling's next alloc
    would then scribble over live cached K/V)."""
    from ray_tpu.serve.llm import KVCacheError
    kv = _cache()
    pc = _prefix(kv)
    a, b = object(), object()
    prompt = list(range(10))
    pages_a, _ = pc.acquire(prompt, a, 3)
    pc.insert(prompt, pages_a)
    pages_b, cached = pc.acquire(prompt, b, 3)
    assert cached == 8
    shared = pages_b[:2]
    kv.write_prefill(pages_a, np.ones((8, 2, 2, 4), np.float32),
                     np.ones((8, 2, 2, 4), np.float32), 8)
    free_before = kv.free_pages
    kv.free(pages_a, a)
    # shared pages survive a's free (cache + b still hold them) and the
    # bytes are untouched; only a's private tail page was released
    assert kv.free_pages == free_before + 1
    for p in shared:
        assert kv.page_refcount(p) >= 2  # b + at least one cache entry
        assert float(kv.k_pages[p].sum()) > 0
    # double free by the same (gone) owner raises, releases nothing
    with pytest.raises(KVCacheError, match="not held by owner"):
        kv.free(pages_a, a)
    assert kv.free_pages == free_before + 1
    kv.free(pages_b, b)
    assert kv.page_refcount(shared[0]) == 2  # the 2 cache entries pin it
    kv.assert_quiesced()  # cached pages are not leaks
    pc.drain()
    assert kv.free_pages == kv.num_pages
    assert kv.close() == 0


def test_refcount_zero_reuse():
    """A page re-enters the free list only when its LAST holder lets
    go — in either order (sequence first or cache first)."""
    kv = _cache(num_pages=4)
    pc = _prefix(kv)
    a = object()
    prompt = list(range(8))
    pages, _ = pc.acquire(prompt, a, 2)
    pc.insert(prompt, pages)
    page0 = pages[0]
    # cache entry evicted while the sequence still runs: page survives
    pc._evict_for_locked  # (exercised via drain below on live refs)
    pc.drain()
    assert kv.page_refcount(page0) == 1
    assert page0 not in kv._free
    kv.free(pages, a)
    assert page0 in kv._free


def test_lru_eviction_under_arena_pressure():
    """Allocation shortfall evicts COLD prefixes oldest-first; a
    just-hit prefix is MRU and survives; pages a live sequence shares
    survive their entry's eviction."""
    kv = _cache(num_pages=6)
    pc = _prefix(kv)
    owners = [object(), object()]
    p1 = list(range(0, 8))     # 2 pages
    p2 = list(range(100, 108))  # 2 pages
    pages1, _ = pc.acquire(p1, owners[0], 2)
    pc.insert(p1, pages1)
    kv.free(pages1, owners[0])
    pages2, _ = pc.acquire(p2, owners[1], 2)
    pc.insert(p2, pages2)
    kv.free(pages2, owners[1])
    assert kv.free_pages == 2 and pc.entries >= 2
    # touch p2 (a hit) so p1 becomes LRU
    toucher = object()
    pt, cached = pc.acquire(p2, toucher, 2)
    assert cached == 4
    kv.free(pt, toucher)
    # demand 4 pages: only 2-3 free -> the p1 entries evict, p2 stays
    big = kv.alloc(4, "big")
    assert len(big) == 4
    assert pc.stats()["evicted"] >= 1
    survivor = object()
    _, cached = pc.acquire(p2, survivor, 2)
    assert cached == 4  # MRU entry survived the pressure


def test_assert_quiesced_with_cached_prefixes():
    """A populated prefix cache is quiesced state, not a leak — but a
    live sequence holder still trips the gate; close() after drain
    reports zero."""
    from ray_tpu.serve.llm import KVCacheError
    kv = _cache()
    pc = _prefix(kv)
    a = object()
    prompt = list(range(12))
    pages, _ = pc.acquire(prompt, a, 3)
    pc.insert(prompt, pages)
    with pytest.raises(KVCacheError, match="leak"):
        kv.assert_quiesced()  # the sequence itself is live
    kv.free(pages, a)
    kv.assert_quiesced()      # cache-only holds: quiesced
    # 12 tokens = 3 full pages, all cache-pinned (4/8/12-token entries)
    assert kv.cached_pages == 3 and kv.live_pages == 0
    pc.drain()
    assert kv.close() == 0


# ---------------------------------------------------------------------------
# engine: chunked prefill + speculative decoding equivalence (jax cpu)
# ---------------------------------------------------------------------------


def _perturbed_draft(params, seed=99, scale=1.0):
    """A draft that mostly-but-not-always agrees with the target:
    target weights + noise. (Two independently-initialized tiny
    tied-head models agree on argmax almost everywhere — the embedding
    similarity term dominates — so disagreement has to be injected
    around the target's own weights to scatter accept lengths.)"""
    import jax
    import jax.numpy as jnp
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    pert = [l + scale * jnp.std(l) * jax.random.normal(k, l.shape)
            for l, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, pert)


def _adversarial_draft(params):
    """A draft that structurally DISAGREES with the target: the
    embedding table is rolled one row, so the draft's tied head scores
    a shifted vocabulary — rejection-heavy rounds exercise the
    accept-length-0 path (one target token per round, like plain
    decode but through the verify window)."""
    import jax
    import jax.numpy as jnp

    def roll_wte(path, leaf):
        if any(getattr(p, "key", None) == "wte" for p in path):
            return jnp.roll(leaf, 1, axis=0)
        return leaf

    return jax.tree_util.tree_map_with_path(roll_wte, params)


def _reference_greedy(engine, prompt, max_new):
    import jax.numpy as jnp
    mod = engine._mod
    cfg = engine.model_cfg
    net = (mod.Llama if engine.model_name == "llama" else mod.GPT)(cfg)
    toks = list(prompt)
    out = []
    for _ in range(max_new):
        logits = net.apply(engine.params,
                           jnp.asarray([toks], jnp.int32))
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        toks.append(nxt)
    return out


def _engine(model="llama", **cfg_kw):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine
    base = dict(batch_buckets=(1, 2), prefill_buckets=(8, 16),
                block_size=4)
    base.update(cfg_kw)
    eng = LLMEngine(model=model, engine_config=EngineConfig(**base),
                    seed=0)
    eng.warmup()
    return eng


def test_chunked_prefill_matches_oneshot():
    """A prompt longer than every prefill bucket windows in chunk by
    chunk and yields exactly the one-shot math's tokens (the chunk
    kernel attends cached pages + the causal window — same einsums,
    same mask floor). Short prompts on the same engine still take the
    one-shot bucket path."""
    rng = np.random.RandomState(3)
    eng = _engine(prefill_chunk=8, prefix_cache=0)
    try:
        long_p = list(rng.randint(1, 500, size=27))   # > max bucket 16
        short_p = list(rng.randint(1, 500, size=5))
        r_long = eng.submit(long_p, 6)
        r_short = eng.submit(short_p, 6)
        eng.run_until_idle(timeout=120)
        assert r_long.result(timeout=10) == \
            _reference_greedy(eng, long_p, 6)
        assert r_short.result(timeout=10) == \
            _reference_greedy(eng, short_p, 6)
        m = eng.metrics()
        assert m["chunk_steps"] >= 4  # 27 tokens / 8-wide windows
        eng.quiesce()
    finally:
        assert eng.shutdown() == 0


def test_prefix_cache_reuse_in_engine():
    """Requests sharing a long prefix prefill only their suffix after
    the first; outputs are identical to the cold path and the arena
    quiesces with the cache still populated (then drains at
    shutdown)."""
    rng = np.random.RandomState(4)
    shared = list(rng.randint(1, 500, size=13))
    prompts = [shared + list(rng.randint(1, 500, size=3))
               for _ in range(3)]
    cold = _engine(prefix_cache=0)
    try:
        reqs = [cold.submit(p, 5) for p in prompts]
        cold.run_until_idle(timeout=120)
        want = [r.result(timeout=10) for r in reqs]
        cold.quiesce()
    finally:
        assert cold.shutdown() == 0
    eng = _engine(prefix_cache=1)
    try:
        reqs = [eng.submit(p, 5) for p in prompts]
        eng.run_until_idle(timeout=120)
        assert [r.result(timeout=10) for r in reqs] == want
        m = eng.metrics()
        # 13-token shared prefix = 3 full pages (block 4): requests 2+3
        # alias them instead of recomputing
        assert m["prefix_cache_hits"] == 2
        assert m["prefix_cache_hit_tokens"] == 24
        assert m["kv_pages_cached"] > 0
        eng.quiesce()                       # cached pages != leaks
        assert m["kv_pages_live"] == 0
        text = eng._metrics_text()
        assert "serve_llm_prefix_cache_hit_tokens_total" in text
        assert "serve_llm_kv_pages_cached" in text
        assert "serve_llm_compiled_step_calls_total" in text
    finally:
        assert eng.shutdown() == 0          # drain happens here


@pytest.mark.parametrize("model", ["llama", "gpt"])
def test_speculative_bitmatch_plain_greedy(model):
    """Greedy speculative output == plain greedy token-for-token, for
    both a self-draft (accepts everything) and an INDEPENDENT draft
    (random weights — most proposals rejected), for both families."""
    rng = np.random.RandomState(5)
    prompts = [list(rng.randint(1, 500, size=n)) for n in (4, 9, 14)]
    plain = _engine(model=model, spec_k=0, prefix_cache=0)
    try:
        reqs = [plain.submit(p, 7) for p in prompts]
        plain.run_until_idle(timeout=120)
        want = [r.result(timeout=10) for r in reqs]
        plain.quiesce()
    finally:
        assert plain.shutdown() == 0

    for perturbed in (False, True):  # False -> self-draft
        from ray_tpu.serve.llm import EngineConfig, LLMEngine
        eng = LLMEngine(model=model, engine_config=EngineConfig(
            batch_buckets=(1, 2), prefill_buckets=(8, 16),
            block_size=4, spec_k=3, prefix_cache=0), seed=0)
        if perturbed:
            # structurally-disagreeing draft (rolled embedding):
            # proposals diverge from the target's argmaxes, so rounds
            # run rejection-heavy — the accept-length-0 path
            eng.draft_params = _adversarial_draft(eng.params)
        eng.warmup()
        try:
            reqs = [eng.submit(p, 7) for p in prompts]
            eng.run_until_idle(timeout=180)
            got = [r.result(timeout=10) for r in reqs]
            assert got == want, f"perturbed={perturbed}"
            m = eng.metrics()
            assert m["spec_rounds"] > 0
            if not perturbed:
                # self-draft proposals are the target's own argmaxes
                assert m["spec_accepted"] == m["spec_proposed"]
            else:
                assert m["spec_accepted"] < m["spec_proposed"]
            eng.quiesce()
        finally:
            assert eng.shutdown() == 0


def test_speculative_round_at_the_end_writes_only_its_own_pages():
    """The last verify window of a request reaches past its reserved
    pages (12 tokens fill 3 pages of 4 exactly): those rows are dropped,
    no page of another owner or of the free list changes, and the stream
    is plain greedy's, token for token."""
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    prompt, new = [7, 3, 9, 1, 4, 2], 6
    plain = _engine(spec_k=0, prefix_cache=0)
    try:
        req = plain.submit(prompt, new)
        plain.run_until_idle(timeout=120)
        want = req.result(timeout=10)
    finally:
        assert plain.shutdown() == 0

    eng = LLMEngine(model="llama", engine_config=EngineConfig(
        batch_buckets=(1, 2), prefill_buckets=(8, 16), block_size=4,
        spec_k=3, prefix_cache=0), seed=0)
    eng.warmup()
    try:
        kv = eng.kv
        assert kv.alloc(1, "other") == [0]
        rng = np.random.default_rng(3)
        fill = rng.normal(size=(2,) + kv.k_pages.shape).astype(np.float32)
        kv.k_pages = kv.k_pages * 0 + fill[0]
        kv.v_pages = kv.v_pages * 0 + fill[1]
        req = eng.submit(prompt, new)
        eng.run_until_idle(timeout=180)
        assert req.result(timeout=10) == want
        m = eng.metrics()
        # self-draft: 1 token from the prefill, 4 from the first round,
        # and a second round whose window covers positions 10..13
        assert m["spec_rounds"] == 2
        own = [1, 2, 3]
        others = [p for p in range(kv.num_pages) if p not in own]
        for got, was in zip((np.asarray(kv.k_pages),
                             np.asarray(kv.v_pages)), fill):
            np.testing.assert_array_equal(got[others], was[others])
            assert not np.array_equal(got[own], was[own])
        kv.free([0], "other")
        eng.quiesce()
    finally:
        assert eng.shutdown() == 0


def test_spec_zero_retrace_across_accept_lengths():
    """Accept-length variation must bucket, never retrace: after
    warmup, a burst whose accept lengths scatter (independent draft)
    adds ZERO compile-cache misses and zero retraces — the draft loop
    varies only its host-side dispatch count, and the verify window is
    always K+1 wide."""
    from ray_tpu import parallel
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    eng = LLMEngine(
        model="llama",
        engine_config=EngineConfig(
            batch_buckets=(1, 2), prefill_buckets=(8, 16),
            block_size=4, spec_k=3, prefix_cache=1),
        seed=0)
    eng.draft_params = _perturbed_draft(eng.params, seed=77)
    eng.warmup()
    try:
        rng = np.random.RandomState(6)
        # shapes seen once -> compiled
        warm = [eng.submit(list(rng.randint(1, 500, size=5)), 6)
                for _ in range(3)]
        eng.run_until_idle(timeout=180)
        [r.result(timeout=10) for r in warm]
        before = parallel.cache_stats()
        reqs = [eng.submit(list(rng.randint(1, 500, size=n)), 8)
                for n in (3, 7, 6, 4)]
        eng.run_until_idle(timeout=180)
        [r.result(timeout=10) for r in reqs]
        after = parallel.cache_stats()
        assert after["retraces"] == before["retraces"]
        assert after["misses"] == before["misses"]
        assert after["hits"] > before["hits"]
        m = eng.metrics()
        # the burst's rounds really did scatter accept lengths
        assert 0 < m["spec_accepted"] < m["spec_proposed"]
        eng.quiesce()
    finally:
        assert eng.shutdown() == 0
