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
