"""serve.llm perf-plane tests: copy-on-write prefix caching, chunked
prefill, and `EngineConfig` as the engine's one source of options.

The load-bearing properties:
  * shared pages are refcounted — a sequence freeing aliased pages can
    never force-free pages the prefix cache (or a sibling sequence)
    still references, and a page re-enters the free list only at
    refcount zero;
  * only FULL pages are ever aliased (a partial page's tail is still
    appended to), and the page holding the last prompt token is never
    aliased (its forward pass produces the first output token);
  * chunked prefill is INVISIBLE in the output: token streams bit-match
    plain one-shot greedy for both model families, and the number of
    chunks a prompt takes never retraces;
  * what an engine runs with is what its `EngineConfig` says: no
    environment variable is read, and `spec_k` is accepted at 0 only.
"""

import dataclasses
import json
import pathlib

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
    # page 0 has ONE cache holder (its node, which both registered
    # prefixes pass through) plus the two sequences
    assert kv.page_refcount(pages_a[0]) == 3
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
    assert kv.page_refcount(shared[0]) == 1  # its one cache node pins it
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


def _small(num_pages, block_size=4):
    return _cache(num_pages=num_pages, block_size=block_size, n_layer=1,
                  n_kv_head=1, head_dim=1)


def _fill(pc, prompt, answer=1):
    """One request's life as the engine drives the cache: pages for the
    prompt and its answer, the prefill's insert. Returns (pages, cached,
    owner); the caller frees."""
    owner = object()
    pages, cached = pc.acquire(
        prompt, owner, pc.kv.pages_for_tokens(len(prompt) + answer))
    pc.insert(prompt, pages)
    return pages, cached, owner


def test_prefix_cache_work_is_a_prompts_tokens_once():
    """One node a page: admission and insert of an 8,000-token prompt
    copy and hash each token id once (the entry-a-prefix structure copied
    16 K^2 of them and scanned K^3 / 6 holders at K = 500 pages), and a
    page has one cache holder however many prefixes pass through it."""
    kv = _small(1100, block_size=16)
    pc = _prefix(kv)
    prompt = np.random.RandomState(0).randint(0, 1000, 8000).tolist()
    pages, cached, a = _fill(pc, prompt)
    assert cached == 0 and pc.entries == 500
    assert pc.stats()["inserted"] == 500
    assert pc.stats()["key_tokens"] <= 2 * len(prompt)
    assert max(kv.page_refcount(p) for p in pages) == 2   # `a` and a node
    # a hit walks the path once more and the insert after it once more
    before = pc.stats()["key_tokens"]
    pages_b, cached, b = _fill(pc, prompt)
    assert cached == 499 * 16 and pages_b[:499] == pages[:499]
    assert pc.stats()["key_tokens"] - before <= 2 * len(prompt)
    assert pc.entries == 500
    assert max(kv.page_refcount(p) for p in pages) == 3
    # a miss at the first page costs one look-up
    before = pc.stats()["key_tokens"]
    c = object()
    pc.acquire([7] * 8000, c, 501)
    assert pc.stats()["key_tokens"] - before == 16


def test_prefix_cache_hits_what_an_entry_a_prefix_hit():
    """The chain of nodes answers every admission as the structure it
    replaced did, one entry for each full-page prefix keyed by the whole
    token tuple: the same `cached` at every `acquire` and the same
    `entries`, over a stream of prompts that share stems (no arena
    pressure, so nothing is evicted on either side)."""
    block = 4
    kv = _small(4096, block)
    pc = _prefix(kv)
    rng = np.random.RandomState(7)
    stems = [rng.randint(0, 30, 48).tolist() for _ in range(6)]
    old_rule = set()
    hits = 0
    for _ in range(200):
        stem = stems[rng.randint(6)]
        prompt = stem[:rng.randint(1, 49)] \
            + rng.randint(30, 60, rng.randint(0, 9)).tolist()
        want = next((k * block
                     for k in range((len(prompt) - 1) // block, 0, -1)
                     if tuple(prompt[:k * block]) in old_rule), 0)
        pages, cached, owner = _fill(pc, prompt)
        assert cached == want
        hits += bool(cached)
        old_rule.update(tuple(prompt[:k * block])
                        for k in range(1, len(prompt) // block + 1))
        assert pc.entries == len(old_rule)
        kv.free(pages, owner)
    assert 100 < hits == pc.stats()["hits"]
    assert pc.stats()["evicted"] == 0
    assert pc.stats()["inserted"] == len(old_rule) == kv.cached_pages
    kv.assert_quiesced()


def _check_tree(pc):
    """Every node's parent is cached (no page unreachable but held), a
    node's `children` is what the keys say, and no child is younger than
    its parent."""
    nodes = list(pc._entries.values())
    age = {id(n): i for i, n in enumerate(nodes)}
    children = {}
    for n in nodes:
        parent = n.key[0]
        if parent is not None:
            assert pc._entries.get(parent.key) is parent
            assert age[id(n)] < age[id(parent)]
            children[id(parent)] = children.get(id(parent), 0) + 1
    assert all(n.children == children.get(id(n), 0) for n in nodes)


def _checked_eviction(pc):
    """Wrap `_evict_for_locked`: whatever it pops had no child."""
    evict = pc._evict_for_locked

    def checked(shortfall):
        before = list(pc._entries.values())
        evict(shortfall)
        for n in before:
            if pc._entries.get(n.key) is not n:
                assert n.children == 0
        _check_tree(pc)

    pc._evict_for_locked = checked


def test_eviction_takes_leaves_oldest_first():
    """Under arena pressure a prompt that fell out of use loses its tail
    and keeps its head hittable; a path just hit is the last to go; no
    node with a child is ever released."""
    kv = _small(16)
    pc = _prefix(kv)
    _checked_eviction(pc)
    a = list(range(0, 25))        # 6 full pages, cached first: the oldest
    b = list(range(100, 117))     # 4 full pages
    for prompt in (a, b):
        pages, _, owner = _fill(pc, prompt, answer=0)
        kv.free(pages, owner)
    assert pc.entries == 10 and kv.free_pages == 6
    big = kv.alloc(8, "big")      # two short: a's two last pages go
    assert pc.stats()["evicted"] == 2 and pc.entries == 8
    kv.free(big, "big")
    pages, cached, owner = _fill(pc, a[:17] + [999])   # no new full page
    assert cached == 16           # a's head is hittable, and now young
    kv.free(pages, owner)
    pages, cached, owner = _fill(pc, b, answer=0)
    assert cached == 16           # b hit after it: the youngest path
    kv.free(pages, owner)
    big = kv.alloc(11, "big")     # three short: a's tail again, not b
    assert pc.stats()["evicted"] == 5 and pc.entries == 5
    kv.free(big, "big")
    _, cached_b = pc.acquire(b, object(), 5)
    _, cached_a = pc.acquire(a, object(), 7)
    assert (cached_b, cached_a) == (16, 4)
    _check_tree(pc)


def test_two_fills_of_one_prompt_share_one_chain():
    """A page's K/V depend on the token prefix alone, so a chain may hold
    pages of several sequences: two requests that both missed both insert,
    the second adds only what the first left out, and a third request
    hits a chain of mixed pages. Every hold is given back."""
    kv = _small(32)
    pc = _prefix(kv)
    short = list(range(13))                     # 3 full pages
    long = short[:12] + list(range(50, 59))     # the same 3, then 2 more
    oa, ob = object(), object()
    pages_a, cached_a = pc.acquire(short, oa, 4)
    pages_b, cached_b = pc.acquire(long, ob, 6)
    assert cached_a == cached_b == 0            # both miss
    pc.insert(short, pages_a)
    pc.insert(long, pages_b)                    # finds 3 nodes, adds 2
    assert pc.entries == 5 and pc.stats()["inserted"] == 5
    assert [kv.page_refcount(p) for p in pages_b[:5]] == [1, 1, 1, 2, 2]
    pc.insert(long, pages_b)                    # again: nothing to add
    assert pc.entries == 5 and pc.stats()["inserted"] == 5
    pages_c, cached_c, oc = _fill(pc, long)
    assert cached_c == 20
    assert pages_c[:5] == pages_a[:3] + pages_b[3:5]    # mixed pages
    # one prompt, two fills: the second's own pages stay its own
    od = object()
    pages_d, cached_d = pc.acquire(long, od, 6)
    assert cached_d == 20 and pages_d[:5] == pages_c[:5]
    for pages, owner in ((pages_a, oa), (pages_b, ob), (pages_c, oc),
                         (pages_d, od)):
        kv.free(pages, owner)
    assert kv.live_pages == 0 and kv.cached_pages == 5
    kv.assert_quiesced()
    pc.drain()
    assert kv.free_pages == kv.num_pages
    assert kv.close() == 0


def test_a_hit_whose_remainder_evicts_is_left_whole():
    """Making room for a hit's remainder takes the nodes past the hit
    first (their pages come free, the hit's live on under the owner and
    the cache); when it reaches the hit itself there was nothing else to
    take, the allocation fails, and nothing stays taken."""
    from ray_tpu.serve.llm import OutOfPagesError
    kv = _small(8)
    pc = _prefix(kv)
    _checked_eviction(pc)
    a = list(range(13))                         # 3 full pages
    pages_a, _, owner = _fill(pc, a, answer=0)
    kv.free(pages_a, owner)
    assert (kv.live_pages, kv.cached_pages, kv.free_pages) == (0, 3, 5)
    # hits a's first two pages, needs six more of five free: a's third
    # page, past the hit, is the oldest leaf
    fork = a[:8] + [70, 71]
    ob = object()
    pages_b, cached = pc.acquire(fork, ob, 8)
    assert cached == 8 and pages_b[:2] == pages_a[:2]
    assert pages_a[2] in pages_b[2:]            # freed, and taken again
    assert pc.stats()["evicted"] == 1 and pc.entries == 2
    assert (kv.live_pages, kv.cached_pages, kv.free_pages) == (8, 0, 0)
    kv.free(pages_b[4:], ob)                    # keeps the hit and two more
    # the same hit again, five more wanted of four free: the hit's own
    # nodes are all there is to evict, and they free nothing
    oc = object()
    with pytest.raises(OutOfPagesError):
        pc.acquire(fork, oc, 7)
    assert pc.entries == 0 and pc.stats()["evicted"] == 3
    assert (kv.live_pages, kv.cached_pages, kv.free_pages) == (4, 0, 4)
    assert [kv.page_refcount(p) for p in pages_b[:4]] == [1] * 4
    pages_c, cached = pc.acquire(fork, oc, 4)   # what fits is a plain miss
    assert cached == 0
    kv.free(pages_c, oc)
    kv.free(pages_b[:4], ob)
    kv.assert_quiesced()
    assert kv.free_pages == kv.num_pages


# ---------------------------------------------------------------------------
# engine: chunked prefill and prefix reuse equal the one-shot path (jax cpu)
# ---------------------------------------------------------------------------


def _assert_greedy(engine, prompt, tokens):
    """`tokens` are the greedy continuation of `prompt` under the flax
    forward: one pass over prompt + tokens (the model is causal), whose
    argmax at each position from the prompt's last is the next token."""
    import jax.numpy as jnp
    mod = engine._mod
    net = (mod.Llama if engine.model_name == "llama" else mod.GPT)(
        engine.model_cfg)
    seq = list(prompt) + list(tokens)
    logits = net.apply(engine.params, jnp.asarray([seq[:-1]], jnp.int32))
    want = np.asarray(jnp.argmax(logits[0, len(prompt) - 1:], axis=-1))
    assert list(tokens) == want.tolist()


def _engine(model="llama", **cfg_kw):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine
    base = dict(batch_buckets=(1, 2), prefill_buckets=(8, 16),
                block_size=4)
    base.update(cfg_kw)
    eng = LLMEngine(model=model, engine_config=EngineConfig(**base),
                    seed=0)
    eng.warmup()
    return eng


@pytest.mark.parametrize("model", ["llama", "gpt"])
def test_chunked_prefill_matches_oneshot(model):
    """A prompt longer than every prefill bucket windows in chunk by
    chunk and yields exactly the one-shot math's tokens (the chunk
    kernel attends cached pages + the causal window — same einsums,
    same mask floor). Short prompts on the same engine still take the
    one-shot bucket path."""
    rng = np.random.RandomState(3)
    eng = _engine(model=model, prefill_chunk=8, prefix_cache=0)
    try:
        long_p = list(rng.randint(1, 500, size=27))   # > max bucket 16
        short_p = list(rng.randint(1, 500, size=5))
        r_long = eng.submit(long_p, 6)
        r_short = eng.submit(short_p, 6)
        eng.run_until_idle(timeout=120)
        long_t, short_t = r_long.result(timeout=10), \
            r_short.result(timeout=10)
        assert len(long_t) == len(short_t) == 6
        _assert_greedy(eng, long_p, long_t)
        _assert_greedy(eng, short_p, short_t)
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


def test_engine_metrics_carry_the_prefix_caches_key_tokens():
    """`prefix_cache_key_tokens` is the cache's own count of the token ids
    it copied into keys, at most a prompt's tokens a call: two calls a
    request (`acquire` at admission, `insert` after the prefill)."""
    eng = _engine(prefix_cache=1)
    try:
        prompts = [list(range(1, 14)), list(range(1, 10)) + [77] * 6]
        for p in prompts:
            eng.submit(p, 3)
        eng.run_until_idle(timeout=120)
        m = eng.metrics()
        assert m["prefix_cache_key_tokens"] \
            == eng.prefix.counters["key_tokens"] > 0
        assert m["prefix_cache_key_tokens"] \
            <= 2 * sum(len(p) for p in prompts)
        assert m["prefix_cache_entries"] == 3 + 1   # two pages are shared
        eng.quiesce()
    finally:
        assert eng.shutdown() == 0


@pytest.mark.parametrize("model", ["llama", "gpt"])
def test_zero_retrace_across_chunk_counts(model):
    """How many units a prompt's prefill takes is the host's affair: a
    prompt of one unit (the one-shot bucket), of two and of three chunks,
    and the suffix of a prompt whose prefix the cache holds, all run the
    programs `warmup()` compiled. No retrace, no miss, and no kind of
    program beside prefill, chunk and decode."""
    from ray_tpu import parallel

    eng = _engine(model=model, prefill_chunk=8, prefix_cache=1)
    try:
        before = parallel.cache_stats()
        rng = np.random.RandomState(6)
        prompts = [list(rng.randint(1, 500, size=n)) for n in (5, 12, 20)]
        prompts.append(prompts[2][:14] + [7, 8, 9])   # 3 pages held
        streams = []
        for p in prompts:
            req = eng.submit(p, 4)
            eng.run_until_idle(timeout=120)
            streams.append(req.result(timeout=10))
        after = parallel.cache_stats()
        assert after["retraces"] == before["retraces"]
        assert after["misses"] == before["misses"]
        assert after["hits"] > before["hits"]
        m = eng.metrics()
        assert m["chunk_steps"] == 2 + 3 + 1
        assert m["prefix_cache_hit_tokens"] == 12
        assert {key.split(":")[0] for key in m["compiled_step_calls"]} \
            == {"prefill", "chunk", "decode"}
        eng.quiesce()
        for p, tokens in zip(prompts, streams):
            assert len(tokens) == 4
            _assert_greedy(eng, p, tokens)
    finally:
        assert eng.shutdown() == 0


# ---------------------------------------------------------------------------
# EngineConfig: the one source of the engine's options (no jax)
# ---------------------------------------------------------------------------

_BENCHMARK_CONFIGS = pathlib.Path(__file__).parent.parent / "benchmark" \
    / "configs"


@pytest.mark.parametrize("name, value", [
    ("RAY_TPU_LLM_BLOCK_SIZE", "32"),
    ("RAY_TPU_LLM_BATCH_BUCKETS", "1,2"),
    ("RAY_TPU_LLM_PREFILL_BUCKETS", "8,16"),
    ("RAY_TPU_LLM_MAX_RUNNING", "2"),
    ("RAY_TPU_LLM_PREFIX_CACHE", "0"),
    ("RAY_TPU_LLM_PREFILL_CHUNK", "8"),
    ("RAY_TPU_LLM_SPEC_K", "3"),
])
def test_engine_config_reads_no_environment(monkeypatch, name, value):
    """Each of these variables once changed what `resolved()` returned;
    none is read now."""
    from ray_tpu.serve.llm import EngineConfig

    monkeypatch.delenv(name, raising=False)
    want = EngineConfig().resolved(128)
    monkeypatch.setenv(name, value)
    assert EngineConfig().resolved(128) == want
    assert want == EngineConfig(
        block_size=16, num_pages=64, batch_buckets=(1, 2, 4, 8),
        prefill_buckets=(16, 32, 64, 128), max_running=8,
        prefix_cache=1, prefill_chunk=0, spec_k=0)


def test_engine_config_takes_spec_k_zero_and_refuses_any_other():
    """The engine has one decode path. `spec_k` stays a field, at 0, for
    the files under `benchmark/` that pass it (ROADMAP D2): each engine
    block there still makes a config."""
    from ray_tpu.serve.llm import EngineConfig

    assert EngineConfig(spec_k=0).resolved(128).spec_k == 0
    with pytest.raises(ValueError, match="spec_k"):
        EngineConfig(spec_k=2).resolved(128)
    blocks = {path.name: json.loads(path.read_text()).get("engine")
              for path in sorted(_BENCHMARK_CONFIGS.glob("*.json"))}
    blocks = {name: block for name, block in blocks.items() if block}
    assert {"mistral-7b-l20.json", "kimi-k2.6-ep32-l7.json"} <= set(blocks)
    for name, block in blocks.items():
        cfg = EngineConfig(**block).resolved(4096)
        assert cfg.spec_k == 0, name
        assert cfg.num_pages > 0 and cfg.max_running > 0, name


def test_engine_config_derives_pages_and_lanes_from_the_model():
    """`resolved()` derives and reads nothing: a lane for each row of the
    largest batch bucket unless fewer are asked for, pages for every
    lane's longest sequence unless a count is given, prefill buckets and
    the chunk no longer than the model's context."""
    from ray_tpu.serve.llm import EngineConfig

    cfg = EngineConfig(block_size=4, batch_buckets=(1, 2, 4)).resolved(30)
    assert cfg.max_running == 4
    assert cfg.num_pages == 4 * 8            # ceil(30 / 4) pages a lane
    assert cfg.prefill_buckets == (16,)      # 32, 64, 128 do not fit
    assert EngineConfig().resolved(8).prefill_buckets == (8,)
    capped = EngineConfig(batch_buckets=(1, 2), max_running=6,
                          prefill_chunk=512).resolved(128)
    assert capped.max_running == 2 and capped.prefill_chunk == 128
    fewer = EngineConfig(max_running=3, num_pages=11).resolved(128)
    assert (fewer.max_running, fewer.num_pages) == (3, 11)
    assert cfg.resolved(30) == cfg           # a fixed point
    assert {f.name for f in dataclasses.fields(EngineConfig)} == {
        "block_size", "num_pages", "batch_buckets", "prefill_buckets",
        "max_running", "eos_token", "prefix_cache", "prefill_chunk",
        "spec_k"}
