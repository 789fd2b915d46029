"""serve.llm tests: paged KV-cache accounting, decode-path math,
continuous batching on the compile cache, streaming, deadlines, and the
full Serve integration.

The load-bearing properties:
  * page accounting is exact — leaks fail loudly at quiesce;
  * continuous batching (join/leave) produces the SAME tokens as
    one-at-a-time greedy decoding (iteration-level scheduling must not
    change the math);
  * steady-state serving never retraces (`parallel.cache_stats()`).
"""

import os
import threading
import time

import numpy as np
import pytest

import ray_tpu


# ---------------------------------------------------------------------------
# paged KV-cache: allocation accounting (no jax, no cluster)
# ---------------------------------------------------------------------------


def _cache(**kw):
    from ray_tpu.serve.llm import PagedKVCache
    base = dict(num_pages=8, n_layer=2, block_size=4, n_kv_head=2,
                head_dim=4)
    base.update(kw)
    return PagedKVCache(**base)


def test_page_alloc_free_roundtrip():
    kv = _cache()
    owner = object()
    assert kv.free_pages == 8 and kv.live_pages == 0
    pages = kv.alloc(3, owner)
    assert len(pages) == 3 and len(set(pages)) == 3
    assert kv.free_pages == 5 and kv.live_pages == 3
    assert abs(kv.utilization() - 3 / 8) < 1e-9
    kv.free(pages, owner)
    assert kv.free_pages == 8 and kv.live_pages == 0
    kv.assert_quiesced()
    assert kv.close() == 0


def test_page_double_free_and_foreign_free_raise():
    from ray_tpu.serve.llm import KVCacheError
    kv = _cache()
    a, b = object(), object()
    pa = kv.alloc(2, a)
    kv.alloc(2, b)
    with pytest.raises(KVCacheError):
        kv.free(pa, b)  # foreign owner
    kv.free(pa, a)
    with pytest.raises(KVCacheError):
        kv.free(pa, a)  # double free
    # nothing was partially freed by the failing calls
    assert kv.live_pages == 2


def test_page_exhaustion_is_atomic():
    from ray_tpu.serve.llm import OutOfPagesError
    kv = _cache(num_pages=4)
    kv.alloc(3, "x")
    with pytest.raises(OutOfPagesError):
        kv.alloc(2, "y")
    # the failed alloc took nothing
    assert kv.free_pages == 1
    assert kv.pages_for_tokens(1) == 1
    assert kv.pages_for_tokens(4) == 1
    assert kv.pages_for_tokens(5) == 2


def test_leak_detected_at_quiesce():
    from ray_tpu.serve.llm import KVCacheError
    kv = _cache()
    kv.alloc(1, "leaker")
    with pytest.raises(KVCacheError, match="leak"):
        kv.assert_quiesced()
    assert kv.close() == 1  # close reports the leak


def test_append_and_prefill_layout():
    kv = _cache(num_pages=4, n_layer=2, block_size=4, n_kv_head=2,
                head_dim=3)
    pages = kv.alloc(2, "s")
    rng = np.random.default_rng(0)
    k_seq = rng.normal(size=(6, 2, 2, 3)).astype(np.float32)
    v_seq = rng.normal(size=(6, 2, 2, 3)).astype(np.float32)
    kv.write_prefill(pages, k_seq, v_seq, 6)
    # token t lives at page[t // block], offset t % block
    for t in range(6):
        page, off = pages[t // 4], t % 4
        np.testing.assert_array_equal(kv.k_pages[page, :, off], k_seq[t])
        np.testing.assert_array_equal(kv.v_pages[page, :, off], v_seq[t])
    # append one more token at position 6
    k7 = rng.normal(size=(2, 2, 3)).astype(np.float32)
    v7 = rng.normal(size=(2, 2, 3)).astype(np.float32)
    kv.append(pages, 6, k7, v7)
    np.testing.assert_array_equal(kv.k_pages[pages[1], :, 2], k7)
    np.testing.assert_array_equal(kv.v_pages[pages[1], :, 2], v7)


def _numpy_arena_write(k_np, v_np, pages, k_seq, v_seq, n, start, block):
    """The host arena this replaced, kept as the reference: row by row."""
    for j in range(n):
        pos = start + j
        k_np[pages[pos // block], :, pos % block] = k_seq[j]
        v_np[pages[pos // block], :, pos % block] = v_seq[j]


@pytest.mark.parametrize("start", [0, 8, 5], ids=["start0", "aligned",
                                                  "unaligned"])
@pytest.mark.parametrize("shape", [(2, 2, 16), (2, 2, 32)],
                         ids=["llama_tiny", "gpt_tiny"])
def test_device_arena_writes_equal_a_numpy_model(shape, start):
    """`write_prefill` and `append` on the device arena against a numpy
    model of it, for the page shapes of both tiny families: the same rows,
    and not one element besides."""
    n_layer, n_kv_head, head_dim = shape
    block = 4
    kv = _cache(num_pages=8, n_layer=n_layer, block_size=block,
                n_kv_head=n_kv_head, head_dim=head_dim)
    rng = np.random.default_rng(start)
    fill = rng.normal(size=(2,) + kv.k_pages.shape).astype(np.float32)
    kv.k_pages, kv.v_pages = kv.k_pages + fill[0], kv.v_pages + fill[1]
    k_np, v_np = fill[0].copy(), fill[1].copy()
    kv.alloc(2, "other")
    pages = kv.alloc(5, "s")
    n = 9
    k_seq = rng.normal(size=(n + 3, n_layer, n_kv_head, head_dim)) \
        .astype(np.float32)
    v_seq = rng.normal(size=k_seq.shape).astype(np.float32)
    kv.write_prefill(pages, k_seq, v_seq, n, start=start)  # rows [:n] only
    _numpy_arena_write(k_np, v_np, pages, k_seq, v_seq, n, start, block)
    kv.append(pages, start + n, k_seq[n], v_seq[n])
    _numpy_arena_write(k_np, v_np, pages, k_seq[n:], v_seq[n:], 1,
                       start + n, block)
    np.testing.assert_array_equal(np.asarray(kv.k_pages), k_np)
    np.testing.assert_array_equal(np.asarray(kv.v_pages), v_np)
    assert kv.arena_nbytes == k_np.nbytes + v_np.nbytes


def test_write_index_drops_rows_without_a_page():
    """Padding rows and positions past the sequence's last page get the
    page id no page has, which the scatter drops."""
    kv = _cache(num_pages=8, block_size=4)
    kv.alloc(3, "other")
    pages = kv.alloc(2, "s")
    w_page, w_off = kv.write_index(pages, 6, 4, rows=6)
    # positions 6, 7 lie in the second page; 8, 9 own none; two pad rows
    assert list(w_page) == [pages[1], pages[1], 8, 8, 8, 8]
    assert list(w_off) == [2, 3, 0, 1, 2, 3]
    assert w_page.dtype == w_off.dtype == np.int32


# ---------------------------------------------------------------------------
# engine: decode math + continuous batching (jax cpu, no cluster)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def llama_engine():
    from ray_tpu.serve.llm import EngineConfig, LLMEngine
    eng = LLMEngine(model="llama",
                    engine_config=EngineConfig(
                        batch_buckets=(1, 2, 4), prefill_buckets=(8, 16)),
                    seed=0)
    eng.warmup()
    yield eng
    assert eng.shutdown() == 0  # zero leaked pages at teardown


def _reference_greedy(engine, prompt, max_new):
    """One-at-a-time greedy over the model's FULL forward pass — the
    ground truth continuous batching must reproduce."""
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


def test_continuous_batching_matches_one_at_a_time(llama_engine):
    """Requests of different lengths joining and leaving the decode
    batch mid-flight generate exactly the same tokens as sequential
    full-forward greedy decoding."""
    eng = llama_engine
    prompts = [[5, 9, 3], [7], [1, 2, 3, 4, 5, 6, 7, 8], [11, 13]]
    new = [6, 9, 3, 7]  # different lengths -> staggered leave/join
    reqs = [eng.submit(p, n) for p, n in zip(prompts, new)]
    eng.run_until_idle()
    for p, n, r in zip(prompts, new, reqs):
        got = r.result(timeout=30)
        assert got == _reference_greedy(eng, p, n), (p, n)
        assert r.finish_reason == "length"
    eng.quiesce()


def test_no_retrace_in_steady_state(llama_engine):
    """After warmup every bucketed shape is an executable-cache hit:
    zero retraces AND zero new misses across a steady-state burst."""
    from ray_tpu import parallel
    eng = llama_engine
    # populate every bucket once (shapes seen -> compiled)
    reqs = [eng.submit([3 + i], 4) for i in range(4)]
    eng.run_until_idle()
    [r.result(timeout=30) for r in reqs]
    before = parallel.cache_stats()
    reqs = [eng.submit([i + 1, i + 2], 5) for i in range(4)]
    eng.run_until_idle()
    [r.result(timeout=30) for r in reqs]
    after = parallel.cache_stats()
    assert after["retraces"] == before["retraces"]
    assert after["misses"] == before["misses"]
    assert after["hits"] > before["hits"]
    eng.quiesce()


def test_streaming_order_and_indices(llama_engine):
    eng = llama_engine
    req = eng.submit([5, 9, 3], 6)
    eng.run_until_idle()
    streamed = list(req.stream(timeout=30))
    assert streamed == req.result(timeout=5)
    assert len(streamed) == 6


def test_pump_thread_and_queueing_past_capacity(llama_engine):
    """More concurrent requests than max_running: the overflow waits on
    the queue and completes as pages free up; zero pages live after."""
    eng = llama_engine
    eng.start()
    try:
        reqs = [eng.submit([2 + (i % 5)], 5) for i in range(10)]
        outs = [r.result(timeout=60) for r in reqs]
        assert all(len(o) == 5 for o in outs)
        # same prompt -> same tokens, regardless of batch placement
        assert outs[0] == outs[5]
        eng.quiesce()
        assert eng.metrics()["kv_pages_live"] == 0
    finally:
        eng.stop()


def test_engine_deadline_shed(llama_engine):
    """A queued request whose deadline passed before admission is failed
    with a timeout and counted — never prefilled."""
    eng = llama_engine
    req = eng.submit([4, 4], 4, timeout_s=0.001)
    time.sleep(0.05)
    before = eng.metrics()["requests_timed_out"]
    eng.run_until_idle()
    from ray_tpu.serve.llm import RequestRejected
    with pytest.raises(RequestRejected, match="deadline"):
        req.result(timeout=10)
    assert eng.metrics()["requests_timed_out"] == before + 1
    assert req.tokens == []


def test_submit_validation(llama_engine):
    from ray_tpu.serve.llm import RequestRejected
    eng = llama_engine
    with pytest.raises(RequestRejected, match="empty"):
        eng.submit([], 4)
    with pytest.raises(RequestRejected, match="prefill bucket"):
        eng.submit(list(range(17)), 4)  # largest bucket is 16
    with pytest.raises(RequestRejected, match="max_seq_len"):
        eng.submit([1, 2], 1000)


def test_engine_metrics_text(llama_engine):
    text = llama_engine._metrics_text()
    for name in ("serve_llm_running_seqs", "serve_llm_kv_pages_live",
                 "serve_llm_tokens_generated_total",
                 "serve_llm_requests_timed_out_total"):
        assert name in text


# ---------------------------------------------------------------------------
# the pump's time ledger (util/tracing phases)
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# the arena stays on the device: what a program may write, what crosses
# the host link, and that the update is a donation
# ---------------------------------------------------------------------------


def _fill_arena(kv, seed=0):
    """Give every element of the arena a value of its own, as device
    arrays placed like the ones they replace; returns the numpy copies."""
    rng = np.random.default_rng(seed)
    fill = rng.normal(size=(2,) + kv.k_pages.shape).astype(np.float32)
    kv.k_pages, kv.v_pages = kv.k_pages * 0 + fill[0], \
        kv.v_pages * 0 + fill[1]
    return fill[0], fill[1]


def _assert_rows_written(kv, fill, pages, written, block):
    """Of the sequence's `pages`, exactly the positions in `written`
    changed; every other page of the arena is bit-identical."""
    for got, was in zip((np.asarray(kv.k_pages), np.asarray(kv.v_pages)),
                        fill):
        others = [p for p in range(kv.num_pages) if p not in pages]
        np.testing.assert_array_equal(got[others], was[others])
        for pos in range(len(pages) * block):
            page, off = pages[pos // block], pos % block
            same = np.array_equal(got[page, :, off], was[page, :, off])
            assert same != (pos in written), (pos, same)


def _small_engine(model="llama", **cfg_kw):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine
    base = dict(batch_buckets=(4,), prefill_buckets=(8, 16), block_size=4,
                prefix_cache=0)
    base.update(cfg_kw)
    eng = LLMEngine(model=model, engine_config=EngineConfig(**base),
                    seed=0)
    eng.warmup()
    return eng


def test_decode_lanes_beyond_the_running_set_write_nothing():
    """One sequence in a bucket of four: the three idle lanes compute on
    a page table of zeros, and page 0 belongs to somebody else. Only the
    running sequence's rows change, warm-up included."""
    eng = _small_engine()
    try:
        assert eng.kv.alloc(1, "other") == [0]
        fill = _fill_arena(eng.kv)
        eng.warmup()                       # writes nothing either
        req = eng.submit([5, 9, 3], 6)
        eng.step()                         # the prefill and a decode step
        pages = list(eng._running[0].pages[0])
        eng.run_until_idle()
        assert len(req.tokens) == 6 and 0 not in pages and len(pages) == 3
        # 3 prompt rows, then the 5 tokens that were fed back
        _assert_rows_written(eng.kv, fill, pages, set(range(8)), 4)
        assert eng.metrics()["compiled_step_calls"] == {
            "decode:4": 5, "prefill:8": 1}
        eng.kv.free([0], "other")
        eng.quiesce()
    finally:
        assert eng.shutdown() == 0


def test_a_decode_pass_hands_its_tokens_over_after_the_next_call():
    """A decode pass records its tokens at once (the next pass feeds on
    them) and hands them to their readers when the next call into a
    program is on its way, or when a request of the pass ends: a reader
    sees every token once, in order, before `done`."""
    eng = _small_engine()
    try:
        short, long = eng.submit([5, 9, 3], 3), eng.submit([7, 2], 5)
        eng.step()          # short's prefill hands its token over; a pass
        assert (len(short.tokens), short.out_q.qsize()) == (2, 1)
        eng.step()          # long's prefill is the next call; a pass
        assert (len(short.tokens), short.out_q.qsize()) == (3, 4)
        assert short.done.is_set()          # three tokens, then `done`
        assert (len(long.tokens), long.out_q.qsize()) == (2, 2)
        eng.step()          # a pass: its call hands over nothing new
        assert (len(long.tokens), long.out_q.qsize()) == (3, 2)
        eng.step()
        assert (len(long.tokens), long.out_q.qsize()) == (4, 3)
        eng.run_until_idle()
        for req, n in ((short, 3), (long, 5)):
            items = [req.out_q.get_nowait() for _ in range(n + 1)]
            assert items == [("token", i, t)
                             for i, t in enumerate(req.tokens)] \
                + [("done", "length")]
            assert req.out_q.empty()
        assert not eng._held
        eng.quiesce()
    finally:
        assert eng.shutdown() == 0


# ---------------------------------------------------------------------------
# a request's stages and a stream's gaps, on the phase ledger's clock
# ---------------------------------------------------------------------------

STAGES = ("queue", "admission", "prefill_span", "first_hold")


def _assert_stages_tile(reqs):
    """Each request's four stages are >= 0 and tile submit -> its first
    hand-over (-> its end, where it never was handed a token) to the
    nanosecond; returns their sums in ms over the requests that were handed
    a first token, as the `req_*` counters keep them."""
    sums = dict.fromkeys(STAGES, 0.0)
    for req in reqs:
        assert req.done.is_set() and req.finish_ns is not None
        stages = req.stages_ns()
        assert all(ns >= 0 for ns in stages), (req, stages)
        end = req.first_handed_ns or req.finish_ns
        assert sum(stages) == end - req.submit_ns, (req, stages)
        if req.first_handed_ns is None:
            assert not req.tokens and req.last_handed_ns is None
            continue
        stamps = [req.submit_ns, req.considered_ns, req.admitted_ns,
                  req.logits_ready_ns, req.first_handed_ns,
                  req.last_handed_ns, req.finish_ns]
        assert stamps == sorted(stamps), (req, stamps)
        for name, ns in zip(STAGES, stages):
            sums[name] += ns / 1e6
    return sums


def _assert_gap_histogram(delta):
    """The histogram's counts are the gaps, and its edges bound their sum."""
    from ray_tpu.serve.llm.engine import _GAP_KEYS, STREAM_GAP_EDGES_MS

    counts = [delta[k] for k in _GAP_KEYS]
    assert sum(counts) == delta["stream_gaps"]
    lower = (0.0,) + STREAM_GAP_EDGES_MS
    assert sum(n * e for n, e in zip(counts, lower)) \
        <= delta["stream_gap_ms"] + 1e-6
    if not counts[-1]:
        assert delta["stream_gap_ms"] <= 1e-6 + sum(
            n * e for n, e in zip(counts, STREAM_GAP_EDGES_MS))


@pytest.mark.parametrize("family", ["tokens", "blocks"])
def test_a_requests_stages_tile_its_way_to_the_first_token(family):
    """Every finished request, whatever became of it: one that streams, one
    of a single token, one shed at its deadline before admission, one the
    shutdown fails while it waits and, for the block family, a prompt
    shorter than a block (nothing to prefill). The `req_*` counters are the
    sums over those that were handed a first token; the gaps' histogram
    holds every later hand-over."""
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine

    if family == "tokens":
        eng = _small_engine(batch_buckets=(2,), prefill_chunk=8)
        jobs = [([5, 9, 3], 6), ([7, 2], 1), (list(range(3, 16)), 5),
                ([4, 4, 8], 3)]
    else:
        eng = LLMEngine(model="sdar_moe", seed=0, engine_config=EngineConfig(
            batch_buckets=(1, 2), prefill_buckets=(8, 16), prefill_chunk=8,
            block_size=8, num_pages=32, prefix_cache=0))
        eng.warmup()
        # 3 < a block of 4: admitted straight into the running set
        jobs = [(list(range(5, 18)), 9), ([5, 6, 7], 6), ([9] * 6, 5)]
    leaked = None
    try:
        before = eng.metrics()
        reqs = [eng.submit(p, n) for p, n in jobs]
        shed = eng.submit([4, 4], 4, timeout_s=0.001)
        time.sleep(0.01)
        eng.run_until_idle()
        eng.quiesce()
        delta = _delta(eng.metrics(), before)
        left = eng.submit([6, 6, 6], 2)     # never stepped: fails below
        leaked = eng.shutdown()
        assert shed.error and left.error and not left.tokens
        assert [len(r.tokens) for r in reqs] == [n for _, n in jobs]
        sums = _assert_stages_tile(reqs + [shed, left])
        assert shed.considered_ns is None       # shed before admission
        assert delta["req_first_tokens"] == len(reqs)
        for name in STAGES:
            assert delta[f"req_{name}_ms"] == pytest.approx(
                sums[name], rel=1e-9, abs=1e-9)
        _assert_gap_histogram(delta)
        bursts = delta["stream_gaps"] + delta["req_first_tokens"]
        if family == "tokens":
            # a token a hand-over: every token after a request's first
            assert delta["stream_gaps"] == \
                delta["tokens_generated"] - len(reqs)
            # a prefill's logits wait for nothing but the fetch and argmax
            assert all(r.logits_ready_ns < r.first_handed_ns for r in reqs)
        else:
            # four tokens reach their reader in at most two bursts, and a
            # burst is one gap however many tokens it brings
            blocks = sum(-(-n // 4) for _, n in jobs)
            assert blocks <= bursts <= 2 * blocks
            assert bursts < delta["tokens_generated"]
            short = reqs[1]                     # nothing to prefill
            assert short.logits_ready_ns == short.admitted_ns
    finally:
        assert (eng.shutdown() if leaked is None else leaked) == 0


def test_a_pass_is_handed_over_at_one_instant_and_stalls_are_counted():
    """Two streams run while a third request's prompt goes through in
    chunks: the lanes of a pass share one hand-over stamp (one clock read
    a hand-over, none a token), each step's prefill unit is charged to
    `stall_prefill_lane_ms` once a running lane and its admission to
    `stall_admit_lane_ms` the same way."""
    eng = _small_engine(prefill_chunk=8)
    try:
        a, b = eng.submit([5, 9, 3], 12), eng.submit([7, 2], 12)
        eng.step()
        eng.step()                      # both prefilled, two lanes running
        eng.step()
        assert len(eng._running) == 2
        assert a.last_handed_ns == b.last_handed_ns > a.first_handed_ns
        before = eng.metrics()
        assert before["stall_prefill_lane_ms"] > 0      # b's prefill, 1 lane
        long = eng.submit(list(range(3, 23)), 4)        # 20 tokens: 3 chunks
        eng.step()                      # admits it, one chunk, a pass
        d = _delta(eng.metrics(), before)
        assert d["chunk_steps"] == 1 and d["prefill_steps"] == 0
        assert d["stall_prefill_lane_ms"] == pytest.approx(
            2 * d["prefill_ms"], rel=1e-9)
        assert d["stall_admit_lane_ms"] == pytest.approx(
            2 * d["ph_admit_ms"], rel=1e-6)
        assert d["stall_admit_lane_ms"] >= \
            2 * (long.admitted_ns - long.considered_ns) / 1e6
        assert d["stream_gaps"] == 2                    # a pass, two lanes
        # the gap of that step holds the chunk and the admission
        assert d["stream_gap_ms"] >= d["stall_prefill_lane_ms"] \
            + d["stall_admit_lane_ms"]
        eng.step()
        two_chunks = long.prefill_ms
        eng.step()      # the last chunk: its first token, then a pass
        assert long.logits_ready_ns is not None and len(long.tokens) == 2
        # the pass's token is recorded and held: not handed over yet
        assert long.last_handed_ns == long.first_handed_ns
        # between its chunks the other streams' passes ran: the span holds
        # more than the request's own units up to the last one's logits,
        # and span and hold together all of them
        _, _, span, hold = (ns / 1e6 for ns in long.stages_ns())
        assert span > two_chunks > 0
        assert span + hold > long.prefill_ms > two_chunks
        eng.run_until_idle()
        _assert_stages_tile([a, b, long])
        eng.quiesce()
    finally:
        assert eng.shutdown() == 0


@pytest.mark.parametrize("mode, n", [("oneshot", 5), ("chunk", 13)])
def test_prefill_rows_past_the_true_length_write_nothing(mode, n):
    """A prompt shorter than its bucket, and a last chunk shorter than
    the window: the padding rows compute and are written nowhere."""
    eng = _small_engine(prefill_chunk=8 if mode == "chunk" else 0)
    try:
        assert eng.kv.alloc(1, "other") == [0]
        fill = _fill_arena(eng.kv)
        req = eng.submit(list(range(1, n + 1)), 1)
        eng.run_until_idle()
        assert len(req.tokens) == 1
        calls = eng.metrics()["compiled_step_calls"]
        assert calls == ({"prefill:8": 1} if mode == "oneshot"
                         else {"chunk:8": 2})
        # pages come off the free list in order, after the other owner's
        pages = list(range(1, 1 + eng.kv.pages_for_tokens(n + 1)))
        _assert_rows_written(eng.kv, fill, pages, set(range(n)), 4)
        eng.kv.free([0], "other")
        eng.quiesce()
    finally:
        assert eng.shutdown() == 0


def _backend_donates():
    import jax
    import jax.numpy as jnp
    x = jnp.zeros(8)
    jax.jit(lambda a: a + 1, donate_argnums=0)(x)
    return x.is_deleted()


def test_steady_state_compiles_nothing_and_donates_the_arena(llama_engine):
    """After warm-up, 3 prefills and over 20 decode steps are all cache
    hits, and every step that ran a program consumed the arena handle it
    was given: the update is a donation, not a copy beside the old one."""
    from ray_tpu import parallel
    eng = llama_engine
    donates = _backend_donates()
    before, m0 = parallel.cache_stats(), eng.metrics()
    reqs = [eng.submit([i + 1] * 3, 22) for i in range(3)]
    while eng.has_work():
        held = (eng.kv.k_pages, eng.kv.v_pages)
        assert eng.step()
        if donates:
            assert held[0].is_deleted() and held[1].is_deleted()
        assert not eng.kv.k_pages.is_deleted()
    assert all(len(r.result(timeout=10)) == 22 for r in reqs)
    after, m1 = parallel.cache_stats(), eng.metrics()
    steps = m1["decode_steps"] - m0["decode_steps"]
    assert m1["prefill_steps"] - m0["prefill_steps"] == 3 and steps >= 20
    assert after["misses"] == before["misses"]
    assert after["retraces"] == before["retraces"]
    assert after["hits"] - before["hits"] == steps + 3
    eng.quiesce()


@pytest.mark.parametrize("model", ["llama", "kimi_k2"])
def test_link_counters_hold_ids_tables_and_logits_only(model):
    """`decode_link_bytes` a step is the host arguments' bytes plus the
    fetched token ids' (one int32 a lane: the program chose them), and
    `prefill_link_bytes` a prefill the host arguments' plus the fetched
    logits' (one row a request), to the byte: no K or V is among them. A
    family that counts on the device (`step_counts`) fetches its vector of
    int32 counts with them."""
    eng = _small_engine(model, prefill_buckets=(16,))
    try:
        m0 = eng.metrics()
        assert m0["decode_link_bytes"] == m0["prefill_link_bytes"] == 0
        reqs = [eng.submit([3 + i] * 5, 4) for i in range(3)]
        eng.run_until_idle()
        assert all(len(r.tokens) == 4 for r in reqs)
        m = eng.metrics()
        vocab_row = eng.model_cfg.vocab_size * 4          # float32 logits
        lanes, bucket, int32 = 4, 16, 4
        counts = int32 * len(eng._step_counts)
        # kimi_k2: the five `MOE_COUNTS` (`tile_visits` since PR 54) and
        # `attn_key_slots`
        assert counts == {"llama": 0, "kimi_k2": 24}[model]
        # token ids, positions, the page table, a page id and an offset a
        # lane; the lanes' chosen ids (and the step's counts) back
        a_step = lanes * int32 * (4 + eng.max_pages_per_seq) \
            + lanes * int32 + counts
        assert a_step < vocab_row
        # token ids, the true length, a page id and an offset a row; one
        # row of logits back
        a_prefill = bucket * int32 * 3 + int32 + vocab_row + counts
        assert m["decode_steps"] > 0 and m["prefill_steps"] == 3
        assert m["decode_link_bytes"] == m["decode_steps"] * a_step
        assert m["prefill_link_bytes"] == 3 * a_prefill
        # a single prompt row's K and V (its latent) is more than a whole
        # prefill moves
        one_row = sum(a[0, :, 0].nbytes for a in eng.kv.arena)
        assert a_prefill < 5 * one_row + vocab_row
        eng.quiesce()
    finally:
        assert eng.shutdown() == 0


# ---------------------------------------------------------------------------
# a decode program ends in the greedy choice (PR 51): it returns the token
# ids, int32 [batch], and not the logits; the families' `decode_step` still
# return logits, and a prefill or a chunk its rows of them
# ---------------------------------------------------------------------------

TOKEN_FAMILIES = ("llama", "gpt", "kimi_k2", "ling_hybrid", "afmoe", "ouro",
                  "mimo_v2")
_TOKEN_ENGINE = dict(batch_buckets=(1, 2, 4), prefill_buckets=(8,),
                     prefill_chunk=8, block_size=4, num_pages=64,
                     prefix_cache=0)


@pytest.fixture(scope="module")
def token_engines():
    """`get(name)`: the tiny engine of a token family (float32; `llama_bf16`
    is `llama` with bfloat16 activations and logits), built and warmed when
    first asked for and shared by the tests below; the programs its
    construction and warm-up compiled; and a place for the tests' probes of
    it."""
    import jax.numpy as jnp
    from ray_tpu import parallel
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    built = {}

    def get(name):
        if name not in built:
            before = parallel.cache_stats()["misses"]
            eng = LLMEngine(
                model=name.removesuffix("_bf16"),
                engine_config=EngineConfig(**_TOKEN_ENGINE),
                model_cfg=LlamaConfig.tiny(dtype=jnp.bfloat16)
                if name == "llama_bf16" else None, seed=0)
            eng.warmup()
            built[name] = (eng, parallel.cache_stats()["misses"] - before, {})
        return built[name]

    yield get
    for eng, *_ in built.values():
        assert eng.shutdown() == 0


def _mixed_batch(eng, lengths=(5, 13, 3, 7, 20), news=(6, 9, 4, 7, 5)):
    """Requests of one-shot and chunked prompts that join and leave the
    running set at different steps, one more than the lanes; the ids are a
    fixed draw, so a family's token lists are a property of its seed."""
    rng = np.random.default_rng(7)
    vocab = eng.model_cfg.vocab_size
    prompts = [rng.integers(0, vocab, n).tolist() for n in lengths]
    return [eng.submit(p, n) for p, n in zip(prompts, news)]


def _decode_probe(eng, batch, plant=None):
    """The engine's own maker's decode program of a bucket, over a module
    whose `decode_step` hands its logits on a second time, as the step's
    last output, once `plant` has had them: a program passes on what a step
    returns after its rows and states, as it does the step's counts. Not
    donating, so it can run before the engine's own program on the same
    arguments."""
    import types

    import jax

    def decode_step(*args, **kwargs):
        logits, *out = mod.decode_step(*args, **kwargs)
        if plant is not None:
            logits = plant(logits)
        return (logits, *out, logits)

    mod = eng._mod
    eng._mod = types.SimpleNamespace(decode_step=decode_step)
    try:
        return jax.jit(eng._make_decode_fn(batch))
    finally:
        eng._mod = mod


def _plant_tie_and_nan(logits):
    """Columns 5 and 9 of every lane above the row's largest, by the same
    amount; a NaN in lane 1."""
    import jax.numpy as jnp
    top = logits.max(axis=-1) + 1
    return logits.at[:, 9].set(top).at[:, 5].set(top).at[1, 7].set(jnp.nan)


@pytest.mark.parametrize("case", ["live", "dead_lane", "tie"])
@pytest.mark.parametrize("family", TOKEN_FAMILIES + ("llama_bf16",))
def test_decode_program_returns_the_argmax_of_the_family_step(
        token_engines, monkeypatch, family, case):
    """The ids a decode program returns are `np.argmax` of the logits the
    family's `decode_step` gives on the same arena and arguments, lane for
    lane, a lane past the running set included, in the logits' own dtype:
    the lowest index wins a tie and a NaN counts as the largest, in the
    program as on the host. The program is the engine's own maker's over a
    module that also returns the logits; without a planted tie, the engine's
    own program of the bucket returns the same ids."""
    eng, _, probes = token_engines(family)
    plant = _plant_tie_and_nan if case == "tie" else None
    if plant not in probes:
        probes[plant] = _decode_probe(eng, 4, plant)
    probe, seen, forward = probes[plant], [], eng._decode_forward

    def spy(fn, args):
        if len(args[1]) != 4:
            return forward(fn, args)
        # before the engine's own call, which consumes the arena
        ids, *_, logits = probe(*args)
        live = len(eng._running)
        got = forward(fn, args)
        seen.append((live, np.asarray(ids), np.asarray(logits), got))
        return got

    monkeypatch.setattr(eng, "_decode_forward", spy)
    reqs = _mixed_batch(eng, (5, 13, 3), (9, 9, 8)) \
        if case == "dead_lane" else _mixed_batch(eng)
    eng.run_until_idle()
    assert all(len(r.tokens) == r.max_new_tokens for r in reqs)
    eng.quiesce()
    assert len(seen) >= 3
    # three requests leave the fourth lane dead in every step of the bucket
    assert (max(live for live, *_ in seen) == 3) == (case == "dead_lane")
    for _, ids, logits, got in seen:
        assert ids.dtype == got.dtype == np.int32
        assert ids.shape == got.shape == (4,)
        assert logits.shape == (4, eng.model_cfg.vocab_size)
        assert logits.dtype == np.dtype(eng.model_cfg.dtype)
        np.testing.assert_array_equal(ids, np.argmax(logits, axis=-1))
        if plant is not None:
            assert ids.tolist() == [5, 7, 5, 5]
        else:
            np.testing.assert_array_equal(got, ids)


# What the parent of PR 51 (commit bd22068: the logits fetched, `np.argmax`
# on the host) streamed for `_mixed_batch` from each family's tiny engine of
# seed 0, recorded there.
PARENT_TOKENS = {
    "llama": [[296] * 6, [408] * 9, [418] * 4, [227] * 7, [22] * 5],
    "gpt": [[296, 283, 283, 283, 283, 283],
            [408, 408, 408, 408, 408, 408, 408, 6, 6], [418] * 4, [227] * 7,
            [461] * 5],
    "kimi_k2": [[12, 447, 357, 176, 441, 35],
                [423, 485, 42, 381, 407, 349, 347, 277, 399],
                [276, 420, 14, 250], [202, 260, 412, 63, 351, 446, 395],
                [140, 503, 335, 211, 122]],
    "ling_hybrid": [[475, 305, 344, 354, 500, 47],
                    [289, 451, 324, 134, 342, 134, 376, 495, 50],
                    [148, 409, 327, 344],
                    [339, 366, 298, 289, 156, 454, 376],
                    [244, 218, 459, 332, 410]],
    "afmoe": [[38, 277, 434, 434, 434, 277],
              [327, 447, 447, 276, 433, 475, 45, 258, 415],
              [260, 260, 219, 134], [452, 373, 452, 452, 452, 276, 13],
              [277, 194, 63, 129, 301]],
    "ouro": [[453, 119, 265, 265, 47, 163],
             [173, 173, 173, 229, 173, 229, 173, 229, 173],
             [26, 357, 74, 432], [103, 510, 283, 283, 283, 283, 283],
             [246, 403, 283, 246, 403]],
    # (PR 57 brought the family: recorded on that PR, from programs that
    # always chose the tokens themselves)
    "mimo_v2": [[211, 13, 102, 211, 13, 278],
                [423, 58, 456, 272, 497, 322, 278, 295, 108],
                [337, 232, 454, 410], [4, 320, 4, 320, 366, 366, 366],
                [177, 49, 489, 269, 292]],
}


@pytest.mark.parametrize("family", TOKEN_FAMILIES)
def test_streamed_tokens_are_the_parents(token_engines, family):
    """A mixed batch through `LLMEngine.submit` (one-shot and chunked
    prefills, buckets of one, two and four, a request that waits for a
    lane) streams, request for request, the tokens it streamed when the host
    took the argmax."""
    eng, *_ = token_engines(family)
    m0 = eng.metrics()["compiled_step_calls"]
    reqs = _mixed_batch(eng)
    eng.run_until_idle()
    assert [list(r.stream(timeout=30)) for r in reqs] \
        == PARENT_TOKENS[family]
    calls = _delta(eng.metrics()["compiled_step_calls"], m0)
    assert calls == {"chunk:8": 5, "decode:1": 3, "decode:2": 2,
                     "decode:4": 6, "prefill:8": 3}
    eng.quiesce()


@pytest.mark.parametrize("family", TOKEN_FAMILIES)
def test_decode_fetch_is_ids_not_logits(token_engines, monkeypatch, family):
    """What a decode step moves over the host link is its host arguments
    and, back, one int32 a lane of its bucket (and the family's vector of
    counts, where it has one), to the byte: under a tenth of one lane's row
    of logits."""
    eng, *_ = token_engines(family)
    steps, forward = [], eng._decode_forward

    def spy(fn, args):
        steps.append((len(args[1]), sum(
            a.nbytes for a in args if isinstance(a, np.ndarray))))
        return forward(fn, args)

    monkeypatch.setattr(eng, "_decode_forward", spy)
    m0 = eng.metrics()
    reqs = _mixed_batch(eng)
    eng.run_until_idle()
    assert all(len(r.tokens) == r.max_new_tokens for r in reqs)
    m = _delta(eng.metrics(), m0)
    eng.quiesce()
    counts = 4 * len(eng._step_counts)
    assert len(steps) == m["decode_steps"] == 11
    assert m["decode_link_bytes"] == sum(
        host + 4 * batch + counts for batch, host in steps)
    assert 4 * 4 + counts < eng.model_cfg.vocab_size * 4 / 10


@pytest.mark.parametrize("family", TOKEN_FAMILIES)
def test_engine_builds_one_program_a_bucket_and_the_chunk(token_engines,
                                                          family):
    """`len(batch_buckets) + len(prefill_buckets) + 1` programs, the ones
    there were: a decode bucket is one program, which returns ids, with no
    second one beside it that still returns logits; warm-up compiles each
    once and serving compiles nothing more."""
    from ray_tpu import parallel

    eng, compiled, _ = token_engines(family)
    want = len(eng.config.batch_buckets) + len(eng.config.prefill_buckets) + 1
    assert sorted(eng._decode_fns) == [1, 2, 4]
    assert sorted(eng._prefill_fns) == [8] and callable(eng._chunk_fn)
    fns = [*eng._decode_fns.values(), *eng._prefill_fns.values(),
           eng._chunk_fn]
    assert len({id(fn) for fn in fns}) == want == compiled == 5
    before = parallel.cache_stats()
    reqs = _mixed_batch(eng)
    eng.run_until_idle()
    assert all(len(r.tokens) == r.max_new_tokens for r in reqs)
    after = parallel.cache_stats()
    assert (after["misses"], after["retraces"]) \
        == (before["misses"], before["retraces"])
    eng.quiesce()


@pytest.mark.parametrize("model", ["llama", "kimi_k2", "ouro"])
def test_chunk_context_and_key_slot_counters(model):
    """Three chunks of 16 over a prompt of 39: `chunk_context_tokens` is
    the keys the causal mathematics needs (16 + 32 + 39), counted on the
    host for every family. A family whose steps count (`step_counts`)
    also says what its programs scored, padding included: Kimi's chunk
    walks the cached slots a key block at a time (here the whole table of
    128, under the model's 1,024), its decode step all of them. The
    `llama` family's decode step walks key blocks too, and the engine
    counts them on the host (`decode_attn_key_slots`)."""
    eng = _small_engine(model, prefill_buckets=(16,), prefill_chunk=16)
    try:
        req = eng.submit(list(range(3, 42)), 4)
        eng.run_until_idle()
        assert len(req.tokens) == 4
        m = eng.metrics()
        assert m["chunk_steps"] == 3 and m["chunk_context_tokens"] == 87
        slots = eng.max_pages_per_seq * eng.kv.block_size
        # the arena's layers: the model's, but for a looped stack, whose
        # every pass keeps rows of its own (`ouro`: 2 passes)
        layers, lanes = eng.kv.n_layer, 4
        assert layers == eng.model_cfg.n_layer * (2 if model == "ouro"
                                                  else 1)
        assert slots == 128
        if model == "llama":
            # the host counts the decode steps' slots, nothing of prefill
            assert "prefill_attn_key_slots" not in m
        elif model == "ouro":
            # its steps count passes and the gate, no slots: the host's
            # count below is the one there is
            assert "prefill_key_slots" not in m
        else:
            assert m["prefill_attn_key_slots"] \
                == layers * (16 + 2 * (slots + 16))
        if model == "kimi_k2":
            # its own walk: one key block of the table's 128 slots (under
            # the model's KEY_BLOCK) and the token's own key, every lane
            assert m["decode_attn_key_slots"] \
                == m["decode_steps"] * layers * lanes * (slots + 1)
        else:
            # `llama.paged_attend`'s list: the one running lane's own key
            # and one trip of the list, four pairs a lane of the bucket: the
            # lane's one block of 64 (llama's grouped heads) or three of 16
            # (ouro's ungrouped ones) live, the rest dead, all scored
            from ray_tpu.models.llama import PAIRS_A_LANE, pair_block
            cfg = eng.model_cfg
            assert m["decode_attn_key_slots"] == m["decode_steps"] * layers \
                * (1 + PAIRS_A_LANE * lanes
                   * pair_block(cfg.n_head // cfg.n_kv_head))
        eng.quiesce()
    finally:
        assert eng.shutdown() == 0


def _paged_attend_reference(q, k_new, v_new, k_pages, v_pages, layer,
                            page_table, positions, scale):
    """The decode attention as it was before the key-block walk, kept as
    the plain reference: one layer of the arena, every slot of every
    table row gathered, K and V repeated for the query heads, one softmax
    over all slots and the token's own key. numpy, float32."""
    b, h, d = q.shape
    kvh = k_new.shape[1]
    kc = k_pages[:, layer][page_table].reshape(b, -1, kvh, d)
    vc = v_pages[:, layer][page_table].reshape(b, -1, kvh, d)
    k_all = np.repeat(np.concatenate([kc, k_new[:, None]], 1), h // kvh, 2)
    v_all = np.repeat(np.concatenate([vc, v_new[:, None]], 1), h // kvh, 2)
    t_max = kc.shape[1]
    key_idx = np.arange(t_max + 1)
    valid = (key_idx[None] < positions[:, None]) | (key_idx[None] == t_max)
    logits = np.einsum("bhd,bkhd->bhk", q, k_all) * scale
    logits = np.where(valid[:, None, :], logits, -1e30)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    out = np.einsum("bhk,bkhd->bhd", p, v_all)
    return out / np.maximum(p.sum(-1, keepdims=True), 1e-20)


# cached keys a lane, by the name of what the case is about; `t` is the
# table's slots, `kb` the key block of the walk the case takes: a batch of
# lanes walks the work list (blocks of `pair_block`, sixteen pairs a trip
# for four lanes), one lane alone the loop (blocks of `KEY_BLOCK`)
_POSITION_CASES = {
    "ragged": lambda t, kb: [3, kb + 70, 0, 2 * kb - 5],
    "idle": lambda t, kb: [0, 0, 0, 0],
    "one": lambda t, kb: [1, 0, 1, 1],
    "block": lambda t, kb: [kb, kb, kb, kb],
    "block_plus_one": lambda t, kb: [kb + 1, 1, kb, 0],
    "last_slot": lambda t, kb: [t, t - 1, 7, 0],
    # 17 pairs: the second trip holds one live pair and fifteen dead ones
    "short_last_trip": lambda t, kb: [4 * kb + 1, t, 4 * kb + 1, kb + 1],
    # 16 pairs: one trip, full
    "full_trip": lambda t, kb: [4 * kb, 3 * kb + 1, t, 3 * kb],
    # one lane alone, into its second block and at its table's end
    "lone": lambda t, kb: [kb + 70],
    "lone_last_slot": lambda t, kb: [t],
}


@pytest.mark.parametrize("case", list(_POSITION_CASES))
@pytest.mark.parametrize("heads", [(32, 8, 128), (12, 4, 64), (12, 12, 64),
                                   (16, 16, 128)],
                         ids=["mistral_32to8x128", "llama125m_12to4x64",
                              "gpt_12x64", "ouro_16x128"])
def test_paged_attend_matches_the_gather_all_repeat_reference(heads, case):
    """`paged_attend` (grouped heads, pages gathered by (page, layer), each
    lane's own key blocks under one running softmax: a batch of lanes in
    trips of the work list, one lane alone in the loop) against the formula
    it replaced, in float32. Page ids are shuffled, the table is not a whole
    number of key blocks, and every slot no sequence holds (other pages,
    other layers, a tail page's rest, the rows a table pads with) is garbage
    that a wrong mask would let in."""
    import jax.numpy as jnp
    from ray_tpu.models import llama

    h, kvh, d = heads
    page, layers, layer = 16, 3, 1
    kb = llama.KEY_BLOCK if case.startswith("lone") \
        else llama.pair_block(h // kvh)
    n_pages = (4 * kb + 48) // page          # four blocks and a ragged fifth
    t_max = n_pages * page
    positions = np.asarray(_POSITION_CASES[case](t_max, kb), np.int32)
    b = len(positions)
    rng = np.random.default_rng(1000 * h + d)
    num_pages = b * n_pages + 5
    k_pages, v_pages = (
        rng.normal(size=(num_pages, layers, page, kvh, d)).astype(np.float32)
        * 1e3 for _ in range(2))
    page_table = rng.permutation(num_pages)[:b * n_pages].reshape(
        b, n_pages).astype(np.int32)
    for lane, pos in enumerate(positions):   # what the sequences hold
        for arr in (k_pages, v_pages):
            rows = rng.normal(size=(pos, kvh, d)).astype(np.float32)
            for t in range(pos):
                arr[page_table[lane, t // page], layer, t % page] = rows[t]
    q, k_new, v_new = (rng.normal(size=(b, n, d)).astype(np.float32)
                       for n in (h, kvh, kvh))
    scale = d ** -0.5
    want = _paged_attend_reference(q, k_new, v_new, k_pages, v_pages, layer,
                                   page_table, positions, scale)
    got = llama.paged_attend(
        jnp.asarray(q), jnp.asarray(k_new), jnp.asarray(v_new),
        jnp.asarray(k_pages), jnp.asarray(v_pages), layer,
        jnp.asarray(page_table), jnp.asarray(positions), scale)
    assert got.shape == (b, h, d) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("position", ["0", "1", "KEY_BLOCK", "KEY_BLOCK+1"])
def test_key_block_trips_is_one_function_for_host_and_program(position):
    """The bound of the one-lane loop of `paged_attend` (and of
    `sdar_moe.block_attend`'s and `afmoe.window_attend`'s), under `numpy`
    (a host's count) and under `jax.jit` (the program): the same trips."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import llama

    kb = llama.KEY_BLOCK
    longest = {"0": 0, "1": 1, "KEY_BLOCK": kb,
               "KEY_BLOCK+1": kb + 1}[position]
    positions = np.asarray([0, longest, min(longest, 1)], np.int32)
    n_pages, page = 3 * kb // 16, 16
    want = -(-longest // kb)
    fn = llama.key_block_trips
    host, k_blk = fn(positions, n_pages, page, np)
    prog, _ = jax.jit(lambda p: fn(p, n_pages, page, jnp))(positions)
    assert (int(host), int(prog), k_blk) == (want, want, kb)
    # a table shorter than a key block is one block; the trips stop at it
    assert llama.key_block_trips(positions, 4, 16, np) \
        == (min(want, 1), 64)


@pytest.mark.parametrize("lanes", ["one", "idle", "short", "long_and_short",
                                   "past_the_table", "three"])
@pytest.mark.parametrize("family", ["llama", "gpt", "ouro", "group_of_4"])
def test_key_block_walk_is_one_function_for_host_and_program(family, lanes):
    """What `paged_attend` walks (`key_block_walk`: trips, pairs a trip,
    keys a block, the list), under `numpy` (the engine's count) and under
    `jax.jit` (the program), by each family's name for it
    (`decode_key_walk`) and as `paged_attend` calls it: the same walk, and
    the one the positions call for. One lane has the loop's trips and no
    list; a batch of lanes has `ceil(pairs / pairs a trip)` trips of a list
    that is a whole number of trips long, lane by lane and block by block,
    dead past its live pairs."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    from ray_tpu.models import gpt, llama, ouro

    fn, group = {
        "llama": (partial(llama.decode_key_walk, llama.LlamaConfig.tiny()), 2),
        "gpt": (partial(gpt.decode_key_walk, gpt.GPTConfig.tiny()), 1),
        "ouro": (partial(ouro.decode_key_walk, ouro.OuroConfig.tiny()), 1),
        "group_of_4": (lambda p, n, page, xp: llama.key_block_walk(
            p, n, page, 4, xp), 4),
    }[family]
    kb, pb = llama.KEY_BLOCK, llama.pair_block(group)
    assert pb == (16 if group == 1 else 64)
    n_pages, page = 3 * kb // 16, 16
    positions = np.asarray({
        "one": [kb + 1], "idle": [0, 0, 0, 0], "short": [1, pb, 5, 0],
        "long_and_short": [3 * kb - 2, 3, pb + 1, 9],
        "past_the_table": [3 * kb + 40, 1], "three": [pb + 1, 0, 2 * pb],
    }[lanes], np.int32)
    b = len(positions)
    host = fn(positions, n_pages, page, np)
    prog = jax.jit(lambda p: fn(p, n_pages, page, jnp))(positions)
    assert (int(host[0]), host[1], host[2]) \
        == (int(prog[0]), prog[1], prog[2])
    if b == 1:
        assert host[3] is None and prog[3] is None
        assert (int(host[0]), host[1], host[2]) == (2, 1, kb)
        return
    trips, width, keys, (lane, at, live) = host
    assert (width, keys) == (llama.PAIRS_A_LANE * b, pb)
    blocks = [min(-(-int(n) // pb), 3 * kb // pb) for n in positions]
    assert int(trips) == -(-sum(blocks) // width)
    assert len(lane) % width == 0 and len(lane) >= int(trips) * width
    pairs = [(i, j) for i, n in enumerate(blocks) for j in range(n)]
    assert list(zip(lane[live].tolist(), at[live].tolist())) == pairs
    assert live.sum() == len(pairs) and not live[len(pairs):].any()
    assert not lane[~live].any() and not at[~live].any()
    for mine, theirs in zip(host[3], prog[3]):
        np.testing.assert_array_equal(mine, np.asarray(theirs))


def test_decode_key_slots_counter_follows_each_lanes_own_blocks():
    """Three sequences in a bucket of four, one of them crossing a block's
    edge of the work list while it decodes, which takes the list past a
    trip's sixteen pairs: after every decode step `decode_attn_key_slots`
    has grown by layers x (the running lanes' own keys + trips x pairs a
    trip x the list's block), the trips being whole, dead pairs and all,
    over each lane's own blocks at the positions handed to the program in
    that step: a long lane's blocks are counted once and not once a lane."""
    import jax.numpy as jnp
    from ray_tpu.models import llama
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    kb = llama.KEY_BLOCK
    cfg = llama.LlamaConfig.tiny(max_seq_len=2 * kb, dtype=jnp.float32)
    pb = llama.pair_block(cfg.n_head // cfg.n_kv_head)
    width = llama.PAIRS_A_LANE * 4
    eng = LLMEngine(
        model="llama", model_cfg=cfg,
        engine_config=EngineConfig(batch_buckets=(4,),
                                   prefill_buckets=(8, 2 * kb),
                                   prefix_cache=0),
        seed=0)
    eng.warmup()
    try:
        assert eng.metrics()["decode_attn_key_slots"] == 0   # warm-up apart
        handed, live = [], []
        forward = eng._decode_forward

        def spy(fn, args):
            handed.append(np.array(args[2]))
            live.append(len(eng._running))
            return forward(fn, args)

        eng._decode_forward = spy
        # 7 blocks that become 8, 8 blocks and 1: sixteen pairs, then 17
        reqs = [eng.submit(list(range(3, 3 + n)), 8)
                for n in (7 * pb - 3, 7 * pb + 5, 7)]
        eng.run_until_idle()
        assert [len(r.tokens) for r in reqs] == [8, 8, 8]
        layers = eng.model_cfg.n_layer
        assert len(handed[0]) == 4
        pairs = [sum(-(-int(n) // pb) for n in p) for p in handed]
        assert {-(-n // width) for n in pairs} == {1, 2}
        want = sum(layers * (n + -(-held // width) * width * pb)
                   for n, held in zip(live, pairs))
        m = eng.metrics()
        assert m["decode_steps"] == len(handed)
        assert m["decode_attn_key_slots"] == want
        # under every lane as far as the longest, which it was
        assert want < sum(4 * layers * (-(-int(p.max()) // kb) * kb + 1)
                          for p in handed)
        eng.quiesce()
    finally:
        assert eng.shutdown() == 0


@pytest.mark.parametrize("model", ["llama", "ouro"])
def test_one_long_lane_of_paged_attend_does_not_make_the_short_ones_walk(
        model):
    """A prompt of 600 tokens decoding beside three of a page or two, in a
    bucket of four: the step scores each lane's own blocks, so
    `decode_attn_key_slots` stays far under lanes x the longest's walk x
    steps, which is what it read when every lane walked as far as the
    batch's longest, and is the count of whole trips over the positions'
    own blocks; the tokens are the reference's."""
    import jax.numpy as jnp
    from ray_tpu.models import llama, ouro
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    cfg = {"llama": llama.LlamaConfig, "ouro": ouro.OuroConfig}[model].tiny(
        max_seq_len=1024, dtype=jnp.float32, param_dtype=jnp.float32)
    kb, pb = llama.KEY_BLOCK, llama.pair_block(cfg.n_head // cfg.n_kv_head)
    width = llama.PAIRS_A_LANE * 4
    eng = LLMEngine(model=model, model_cfg=cfg, engine_config=EngineConfig(
        batch_buckets=(4,), prefill_buckets=(16,), prefill_chunk=64,
        block_size=4, num_pages=256, prefix_cache=0), seed=0)
    eng.warmup()
    try:
        handed, live = [], []
        forward = eng._decode_forward

        def spy(fn, args):
            handed.append(np.array(args[2]))
            live.append(len(eng._running))
            return forward(fn, args)

        eng._decode_forward = spy
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, 512, n).tolist() for n in (600, 9, 14, 11)]
        reqs = [eng.submit(p, 24 if len(p) > 100 else 40) for p in prompts]
        eng.run_until_idle()
        for req, prompt in zip(reqs, prompts):
            if model == "ouro":
                rows = _loop_reference_rows(eng, prompt, req.tokens)
            else:
                ids = jnp.asarray([prompt + req.tokens[:-1]], jnp.int32)
                rows = np.asarray(llama.Llama(cfg).apply(
                    eng.params, ids))[0, len(prompt) - 1:]
            assert [int(r.argmax()) for r in rows] == req.tokens
        eng.quiesce()
        m = eng.metrics()
        layers = eng.kv.n_layer
        assert layers == cfg.n_layer * (2 if model == "ouro" else 1)
        assert m["decode_steps"] == len(handed)
        assert sum(int(p.max()) >= 600 for p in handed) == 24 - 1
        # every step the running lanes' own keys and whole trips over the
        # lanes' own blocks
        pairs = [sum(-(-int(n) // pb) for n in p) for p in handed]
        mine = [layers * (n + -(-held // width) * width * pb)
                for n, held in zip(live, pairs)]
        assert m["decode_attn_key_slots"] == sum(mine)
        # the walk to the longest: every lane of the bucket three blocks of
        # `KEY_BLOCK` while the long lane runs
        theirs = [4 * layers * (-(-int(p.max()) // kb) * kb + 1)
                  for p in handed]
        beside = [int(p.max()) >= 600 for p in handed]
        assert sum(a for a, long in zip(mine, beside) if long) \
            < 0.4 * sum(a for a, long in zip(theirs, beside) if long)
        assert all(a <= b for a, b in zip(mine, theirs))
    finally:
        assert eng.shutdown() == 0


def _delta(after, before):
    return {k: after[k] - before.get(k, 0) for k in after
            if isinstance(after[k], (int, float))}


def _phase_sum(delta, prefix="ph_"):
    return sum(v for k, v in delta.items() if k.startswith(prefix))


@pytest.fixture(scope="module")
def ledger_engine():
    """Wide enough that a step is milliseconds: the ledger's own
    bookkeeping (microseconds a step) stays far inside the 2% it is held
    to. No prefix cache: every prompt prefills."""
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.llm import EngineConfig, LLMEngine
    eng = LLMEngine(
        model="llama",
        model_cfg=LlamaConfig(
            vocab_size=512, max_seq_len=128, n_layer=4, n_head=8,
            n_kv_head=2, d_model=512, dtype=jnp.float32),
        engine_config=EngineConfig(
            batch_buckets=(4,), prefill_buckets=(32,), prefix_cache=0),
        seed=0)
    eng.warmup()
    yield eng
    assert eng.shutdown() == 0


def test_pump_ledger_sums_to_the_pump_wall_time(ledger_engine):
    """Driven for a second with nothing else contending: the phases' self
    times add up to the pump thread's wall time, and the decode and
    prefill phases to the two counters that used to lump them."""
    from ray_tpu.serve.llm.engine import PUMP_PHASES

    eng = ledger_engine
    eng.start()
    try:
        time.sleep(0.1)                         # some idle time in it too
        before = eng.metrics()
        assert {f"ph_{n.replace('.', '_')}_ms" for n in PUMP_PHASES} \
            <= set(before)                      # listed before they ran
        t0 = time.monotonic()
        while time.monotonic() - t0 < 1.0:
            # long answers: a finishing request wakes its caller, and the
            # pump then waits its turn for the interpreter wherever it is
            reqs = [eng.submit([3 + i] * 20, 40) for i in range(4)]
            for r in reqs:
                assert len(r.result(timeout=60)) == 40
        eng.quiesce()
        after = eng.metrics()
    finally:
        eng.stop()
    d = _delta(after, before)
    assert d["pump_wall_ms"] >= 1000
    assert abs(_phase_sum(d) - d["pump_wall_ms"]) <= 0.02 * d["pump_wall_ms"]
    assert d["decode_steps"] > 20 and d["prefill_steps"] >= 8
    inside = d["ph_lock_wait_ms"] + d["ph_finish_ms"]
    assert inside <= 0.02 * d["decode_ms"]
    assert abs(_phase_sum(d, "ph_decode_") - d["decode_ms"]) \
        <= 0.02 * d["decode_ms"]
    # a request's span holds what lies between its prefill phases
    prefill = _phase_sum(d, "ph_prefill_") + d["ph_llm_prefill_ms"]
    assert abs(prefill - d["prefill_ms"]) <= 0.02 * d["prefill_ms"]
    assert d["ph_llm_prefill_ms"] <= 0.02 * d["prefill_ms"]
    for key in ("ph_decode_dispatch_ms", "ph_decode_device_wait_ms",
                "ph_decode_fetch_ms", "ph_decode_kv_append_ms",
                "ph_decode_sample_ms", "ph_prefill_dispatch_ms",
                "ph_prefill_kv_write_ms",
                "ph_pump_idle_ms", "ph_admit_ms", "ph_finish_ms"):
        assert d[key] > 0, key
    # K and V stay on the device: the phase that fetched them is gone, and
    # what the host still does for a write (the rows' coordinates,
    # positions) is small
    assert "ph_prefill_kv_fetch_ms" not in after
    assert d["ph_prefill_kv_write_ms"] <= 0.02 * d["prefill_ms"]
    assert d["ph_decode_kv_append_ms"] <= 0.02 * d["decode_ms"]
    # metrics() times itself: calls that had finished when it was read
    assert d["metrics_calls"] == 1 and d["metrics_ms"] > 0
    # a stopped pump's wall time stands still; the operator's family
    assert eng.metrics()["pump_wall_ms"] == eng.metrics()["pump_wall_ms"]
    text = eng._metrics_text()
    for name in PUMP_PHASES:
        assert f'serve_llm_phase_ms_total{{phase="{name}"}}' in text


def test_pump_lock_wait_shows_a_held_engine_lock(ledger_engine):
    eng = ledger_engine
    eng.start()
    try:
        before = eng.metrics()
        reqs = [eng.submit([5 + i] * 20, 8) for i in range(4)]
        for r in reqs:
            r.result(timeout=60)
        quiet = _delta(eng.metrics(), before)
        assert quiet["ph_lock_wait_ms"] < 1.0   # uncontended
        before = eng.metrics()
        reqs = [eng.submit([9 + i] * 20, 24) for i in range(4)]
        time.sleep(0.02)                        # the pump is stepping
        with eng._lock:
            time.sleep(0.05)
        for r in reqs:
            r.result(timeout=60)
        held = _delta(eng.metrics(), before)
    finally:
        eng.stop()
    assert 30 <= held["ph_lock_wait_ms"] < 500


def _rt_events(log_dir):
    """[(name, stats)] of the `rt/` host events of a profiler session."""
    import glob

    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert paths, f"no .xplane.pb under {log_dir}"
    return [(e.name, dict(e.stats))
            for plane in ProfileData.from_file(paths[-1]).planes
            for line in plane.lines for e in line.events
            if e.name.startswith("rt/")]


def test_profiler_session_holds_the_engine_phases(ledger_engine, tmp_path):
    """Whatever profiler session is on, the pump's phases are host events
    of its `.xplane.pb`, and a request's prefill phases carry its id."""
    from ray_tpu.util import request_recorder as rr
    from ray_tpu.util import tracing

    eng = ledger_engine
    ctxs = [rr.new_context("llm") for _ in range(3)]
    for ctx in ctxs:
        # the recorder's ids are 16 hex digits, and one in a few dozen is
        # all decimal digits but a single `e` ("48542e8800000033"): the
        # profile's reader hands such a stat back as the float inf, which
        # failed this test whenever the process's id prefix had that form
        ctx["req_id"] = "req-" + ctx["req_id"]
    with tracing.device_trace(str(tmp_path / "trace")) as log_dir:
        for i, ctx in enumerate(ctxs):
            with rr.serving(ctx):
                eng.submit([7 + i] * 20, 4)
        direct = eng.submit([2] * 20, 4, request_id="direct-1")
        eng.run_until_idle()
    assert len(direct.tokens) == 4
    events = _rt_events(log_dir)
    names = {name for name, _ in events}
    assert {"rt/engine_step", "rt/admit", "rt/llm.prefill",
            "rt/prefill_dispatch", "rt/prefill_device_wait",
            "rt/prefill_kv_write",
            "rt/prefill_sample", "rt/decode_assemble",
            "rt/decode_dispatch", "rt/decode_device_wait",
            "rt/decode_fetch", "rt/decode_kv_append", "rt/decode_sample",
            "rt/finish", "rt/cache_lookup"} <= names
    # busy iterations are numbered steps of the profiler's overview
    steps = [st["step_num"] for name, st in events
             if name == "rt/engine_step" and "step_num" in st]
    assert steps == sorted(steps) and len(steps) >= 4
    want = {ctx["req_id"] for ctx in ctxs} | {"direct-1"}
    for phase in ("rt/llm.prefill", "rt/prefill_dispatch",
                  "rt/prefill_device_wait",
                  "rt/prefill_kv_write", "rt/prefill_sample"):
        got = [st.get("req_id") for name, st in events if name == phase]
        assert set(got) == want and len(got) >= 4, (phase, got)


def test_trace_shard_still_holds_llm_prefill_with_its_flow(ledger_engine,
                                                          tmp_path):
    """`RAY_TPU_TRACE=1`: the request span the handle's flow arrow ends on
    is a phase now, under its old name and with its old attributes."""
    from ray_tpu.util import request_recorder as rr
    from ray_tpu.util import tracing
    from ray_tpu.util.timeline import unified_timeline

    eng = ledger_engine
    trace_dir = str(tmp_path / "traces")
    os.environ["RAY_TPU_TRACE"] = "1"
    os.environ["RAY_TPU_TRACE_DIR"] = trace_dir
    tracing.refresh()
    tracing._reset_writer()
    ctx = rr.new_context("llm")
    try:
        with tracing.span("serve.llm.request", kind="producer",
                          attrs={"req_id": ctx["req_id"],
                                 "flow_id": f"req:{ctx['req_id']}"}):
            with rr.serving(ctx):
                req = eng.submit([11] * 20, 3)
        eng.run_until_idle()
    finally:
        os.environ.pop("RAY_TPU_TRACE", None)
        os.environ.pop("RAY_TPU_TRACE_DIR", None)
        tracing.refresh()
        tracing._reset_writer()
    assert len(req.tokens) == 3
    spans = tracing.collect(trace_dir)
    prefill = [s for s in spans if s["name"] == "llm.prefill"]
    assert len(prefill) == 1
    assert prefill[0]["kind"] == "consumer"
    assert prefill[0]["attrs"] == {
        "bucket": 32, "tokens_in": 20, "req_id": ctx["req_id"],
        "flow_id": f"req:{ctx['req_id']}"}
    inner = [s for s in spans if s["name"] == "prefill_dispatch"]
    assert inner and inner[0]["parent_id"] == prefill[0]["span_id"]
    assert inner[0]["attrs"]["req_id"] == ctx["req_id"]
    assert any(s["name"] == "decode_dispatch" for s in spans)
    # the arrow from the producer span still ends on it
    events = unified_timeline(trace_dir=trace_dir, include_tasks=False)
    flow = f"req:{ctx['req_id']}"
    assert [e for e in events if e.get("ph") == "s" and e.get("id") == flow]
    assert [e for e in events if e.get("ph") == "f" and e.get("id") == flow]


def test_gpt_decode_matches_full_forward():
    """The GPT decode path (LayerNorm + learned positions + biases) is
    bit-compatible with the full forward too."""
    from ray_tpu.serve.llm import EngineConfig, LLMEngine
    eng = LLMEngine(model="gpt",
                    engine_config=EngineConfig(
                        batch_buckets=(1, 2), prefill_buckets=(8,)),
                    seed=1)
    eng.warmup()
    try:
        cases = [([5, 9, 3], 5), ([2, 4], 6)]
        reqs = [eng.submit(p, n) for p, n in cases]
        eng.run_until_idle()
        for (p, n), r in zip(cases, reqs):
            assert r.result(timeout=30) == _reference_greedy(eng, p, n)
        eng.quiesce()
    finally:
        assert eng.shutdown() == 0


# ---------------------------------------------------------------------------
# @serve.batch satellite: per-item errors + flush-flag reset
# ---------------------------------------------------------------------------


def test_batch_per_item_exception():
    """A batched fn returning an Exception INSTANCE in an item's slot
    fails that caller alone; batch-mates get their results."""
    from ray_tpu.serve.batching import batch

    @batch(max_batch_size=3, batch_wait_timeout_s=5.0)
    def work(items):
        return [ValueError(f"bad {x}") if x < 0 else x * 2
                for x in items]

    results, errors = {}, {}

    def call(x):
        try:
            results[x] = work(x)
        except Exception as e:  # noqa: BLE001
            errors[x] = e

    threads = [threading.Thread(target=call, args=(x,))
               for x in (1, -5, 3)]
    [t.start() for t in threads]
    [t.join(timeout=30) for t in threads]
    assert results == {1: 2, 3: 6}
    assert isinstance(errors[-5], ValueError)


def test_batch_flush_flag_resets_when_timer_fails():
    """If the flush timer can't start, the scheduled flag must reset —
    otherwise no later submit ever schedules a flush and every queued
    caller hangs."""
    from ray_tpu.serve.batching import _Batcher

    calls = []

    def fn(items):
        calls.append(list(items))
        return [x + 1 for x in items]

    b = _Batcher(fn, max_batch_size=4, batch_wait_timeout_s=0.05)

    class _BoomTimer:
        def __init__(self, *a, **k):
            self.daemon = True

        def start(self):
            raise RuntimeError("no threads left")

    import ray_tpu.serve.batching as batching_mod
    real_timer = batching_mod.threading.Timer
    batching_mod.threading.Timer = _BoomTimer
    try:
        with pytest.raises(RuntimeError, match="no threads left"):
            b.submit(None, 1)
        assert b._flush_scheduled is False  # un-wedged
    finally:
        batching_mod.threading.Timer = real_timer
    # the batcher still works: next submit schedules a real flush that
    # drains the stranded first item too
    out = b.submit(None, 2)
    assert out == 3
    assert sorted(sum(calls, [])) == [1, 2]


# ---------------------------------------------------------------------------
# Serve integration (cluster)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def cluster():
    from ray_tpu import serve
    ray_tpu.init(num_cpus=8, num_tpus=0,
                 object_store_memory=256 * 1024 * 1024)
    yield
    serve.shutdown()
    ray_tpu.shutdown()


@pytest.fixture()
def clean_deployments(cluster):
    from ray_tpu import serve
    yield
    for name in list(serve.status()):
        serve.delete(name)


def test_handle_timeout_s_sheds_expired(clean_deployments):
    """handle.options(timeout_s=...) sheds a request whose deadline
    passed before dispatch, raises RequestTimeoutError, and counts it in
    serve_request_timeouts."""
    from ray_tpu import serve
    from ray_tpu.serve.handle import REQUEST_TIMEOUTS

    @serve.deployment
    def echo(x):
        return x

    handle = serve.run(echo.bind())
    assert handle.remote(1).result(timeout=30) == 1  # warm route
    def shed_count():
        return sum(REQUEST_TIMEOUTS._values.values())

    before = shed_count()
    with pytest.raises(serve.RequestTimeoutError):
        handle.options(timeout_s=-0.001).remote(2)
    assert shed_count() == before + 1
    # a sane deadline still dispatches
    assert handle.options(timeout_s=30.0).remote(3).result(timeout=30) == 3


def test_llm_deployment_device_trace_through_the_handle(clean_deployments):
    """Only the replica's process can trace its device: the handle asks it
    to, and gets back where the `.xplane.pb` lies, the engine's phases in
    it."""
    from ray_tpu import serve

    handle = serve.run(serve.llm.build_app(name="llm", num_replicas=1))
    pending = handle.device_trace.remote(0.2)
    assert len(handle.generate_once.remote([5, 9, 3], 4).result(
        timeout=60)) == 4
    path = pending.result(timeout=60)
    assert os.path.isdir(path)
    names = {name for name, _ in _rt_events(path)}
    assert "rt/pump_idle" in names
    m = handle.engine_metrics.remote().result(timeout=60)
    assert m["pump_wall_ms"] > 200 and m["ph_pump_idle_ms"] > 0
    import shutil

    shutil.rmtree(path, ignore_errors=True)


def test_serve_llm_end_to_end(clean_deployments):
    """build_app -> serve.run -> stream tokens over the handle; replica
    reports queue depth + KV occupancy + arena id through the controller
    poll."""
    from ray_tpu import serve

    handle = serve.run(serve.llm.build_app(name="llm", num_replicas=1))
    streamed = [c["token"] for c in
                handle.generate.options(stream=True).remote([5, 9, 3], 8)]
    assert len(streamed) == 8
    unary = handle.generate_once.remote([5, 9, 3], 8).result(timeout=60)
    assert unary == streamed  # greedy determinism across paths

    m = handle.engine_metrics.remote().result(timeout=60)
    assert m["requests_completed"] >= 2
    assert m["kv_pages_live"] == 0  # all pages returned

    # the controller's poll sees the merged autoscaling metrics
    ctrl = ray_tpu.get_actor("SERVE_CONTROLLER")
    info = ray_tpu.get(ctrl.get_replicas.remote("llm"), timeout=30)
    rm = ray_tpu.get(info["replicas"][0].get_metrics.remote(), timeout=30)
    for key in ("ongoing", "queue_depth", "kv_pages_live",
                "kv_pages_total"):
        assert key in rm


def test_serve_llm_end_to_end_with_the_latent_cache_family(clean_deployments):
    """The Kimi-K2 family through the same door: `build_app(model=...)` ->
    `serve.run` -> `handle.generate`, chunked prefill and decode over the
    latent arena in the replica, the tokens a local engine of the same
    seed gives, and the expert counters in the replica's metrics."""
    from ray_tpu import serve
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine

    engine_config = {"batch_buckets": (1, 2), "prefill_buckets": (16,),
                     "prefill_chunk": 16, "num_pages": 32, "block_size": 8}
    handle = serve.run(serve.llm.build_app(
        name="llm", num_replicas=1, model="kimi_k2",
        engine_config=engine_config))
    prompt = list(range(3, 40))                      # three chunks of 16
    streamed = [c["token"] for c in
                handle.generate.options(stream=True).remote(prompt, 6)]
    local = LLMEngine(model="kimi_k2",
                      engine_config=EngineConfig(**engine_config))
    try:
        want = local.submit(prompt, 6)
        local.run_until_idle()
        assert streamed == want.result()
    finally:
        local.shutdown()
    m = handle.engine_metrics.remote().result(timeout=60)
    assert m["model"] == "kimi_k2" and m["kv_pages_live"] == 0
    assert m["chunk_steps"] == 3
    assert m["decode_moe_pairs_routed"] > 0


def test_serve_llm_end_to_end_with_the_sequence_state_family(
        clean_deployments):
    """The Ling hybrid family through the same door: `build_app(model=...)`
    -> `serve.run` -> `handle.generate`, chunked prefill with the KDA state
    carried and decode over the state arena and the latent pages in the
    replica, the tokens a local engine of the same seed gives, and the slot
    counters in the replica's metrics."""
    from ray_tpu import serve
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine

    engine_config = {"batch_buckets": (1, 2), "prefill_buckets": (16,),
                     "prefill_chunk": 16, "num_pages": 32, "block_size": 8,
                     "prefix_cache": 0}
    handle = serve.run(serve.llm.build_app(
        name="llm", num_replicas=1, model="ling_hybrid",
        engine_config=engine_config))
    prompt = list(range(3, 40))                      # three chunks of 16
    streamed = [c["token"] for c in
                handle.generate.options(stream=True).remote(prompt, 6)]
    local = LLMEngine(model="ling_hybrid",
                      engine_config=EngineConfig(**engine_config))
    try:
        want = local.submit(prompt, 6)
        local.run_until_idle()
        assert streamed == want.result()
    finally:
        local.shutdown()
    m = handle.engine_metrics.remote().result(timeout=60)
    assert m["model"] == "ling_hybrid" and m["kv_pages_live"] == 0
    assert m["chunk_steps"] == 3
    assert (m["state_slots_live"], m["state_slots_free"]) == (0, 2)
    assert m["decode_kda_state_rows"] == 5 * 6
    assert m["prefill_kda_state_rows"] == 3 * 6


def test_serve_llm_end_to_end_with_the_block_diffusion_family(
        clean_deployments):
    """The SDAR-MoE family through the same door: `build_app(model=...)` ->
    `serve.run` -> `handle.generate`: chunked prefill of the prompt's whole
    blocks, denoising and commit passes in the replica, tokens streamed in
    position order with their indices, the tokens a local engine of the
    same seed gives, and the block counters in the replica's metrics."""
    from ray_tpu import serve
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine

    engine_config = {"batch_buckets": (1, 2), "prefill_buckets": (16,),
                     "prefill_chunk": 16, "num_pages": 32, "block_size": 8,
                     "prefix_cache": 0}
    handle = serve.run(serve.llm.build_app(
        name="llm", num_replicas=1, model="sdar_moe",
        engine_config=engine_config))
    prompt = list(range(3, 40))          # 36 in three chunks, 1 opens a block
    chunks = list(handle.generate.options(stream=True).remote(prompt, 6))
    assert [c["index"] for c in chunks] == list(range(6))
    streamed = [c["token"] for c in chunks]
    local = LLMEngine(model="sdar_moe",
                      engine_config=EngineConfig(**engine_config))
    try:
        want = local.submit(prompt, 6)
        local.run_until_idle()
        assert streamed == want.result()
    finally:
        local.shutdown()
    m = handle.engine_metrics.remote().result(timeout=60)
    assert m["model"] == "sdar_moe" and m["kv_pages_live"] == 0
    assert (m["chunk_steps"], m["prefill_steps"]) == (3, 1)
    # [p, ., ., .] two passes and a commit, then [., ., ., .] until the
    # sixth token shows: two passes, no commit
    assert (m["decode_lane_passes"], m["decode_lane_commits"],
            m["decode_blocks_committed"]) == (5, 1, 1)
    assert m["decode_tokens_revealed"] == 3 + 4
    assert m["tokens_generated"] == 6
    assert m["decode_moe_pairs_routed"] > 0


@pytest.mark.parametrize("model", ["sdar_moe"])
def test_chunked_prefill_matches_oneshot(model):
    """A family that generates by blocks: the whole blocks of a prompt
    leave the same K and V rows and lead to the same tokens whether they
    go through the chunk program (three windows of 8, block-causal against
    the pages) or one shot (a bucket of 32); the rest of the prompt opens
    the first block either way."""
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine

    prompt = [int(t) for t in
              np.random.RandomState(11).randint(1, 500, size=22)]
    got = {}
    for name, cfg in (("chunked", dict(prefill_buckets=(8,),
                                       prefill_chunk=8)),
                      ("oneshot", dict(prefill_buckets=(32,),
                                       prefill_chunk=32))):
        eng = LLMEngine(model=model, seed=0, engine_config=EngineConfig(
            batch_buckets=(1,), block_size=4, num_pages=16, prefix_cache=0,
            **cfg))
        try:
            req = eng.submit(prompt, 7)
            while not eng._running:
                eng.step()
            seq = eng._running[0]
            # the step that ends the prefill also makes the first pass
            assert (seq.pos, seq.block[:2], seq.revealed[:2]) == \
                (20, prompt[20:], [True, True])
            rows = [np.asarray(pages)[seq.pages[0][:5]] for pages in eng.kv.arena]
            eng.run_until_idle(timeout=120)
            m = eng.metrics()
            got[name] = (req.result(timeout=5), rows, m["chunk_steps"])
            eng.quiesce()
        finally:
            assert eng.shutdown() == 0
    assert (got["chunked"][2], got["oneshot"][2]) == (3, 0)
    assert got["chunked"][0] == got["oneshot"][0]
    for a, b in zip(got["chunked"][1], got["oneshot"][1]):
        assert np.abs(a).max() > 0
        np.testing.assert_allclose(a, b, atol=2e-6, rtol=1e-5)


def test_block_family_streams_in_position_order_and_stops_inside_a_block():
    """Through the pump thread: a reader sees every token once, in
    position order with its index, though a pass may reveal a block's later
    position first; the engine has no call that cancels a running request
    (of any family), so the cut is the pump's: stopped between a lane's
    passes, once the last pass's tokens are handed over (`stop` does it) the
    reader has every token recorded so far, the cut sequence's pages are
    what `shutdown` reports, and nothing else is lost."""
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine

    eng = LLMEngine(model="sdar_moe", seed=0, engine_config=EngineConfig(
        batch_buckets=(1, 2), prefill_buckets=(8, 16), prefill_chunk=8,
        block_size=8, num_pages=32, prefix_cache=0))
    try:
        eng.start()
        reqs = [eng.submit(list(range(5, 5 + n)), new)
                for n, new in ((13, 9), (6, 5))]
        for req, new in zip(reqs, (9, 5)):
            seen = list(req.stream(timeout=60))
            assert seen == req.tokens and len(seen) == new
        eng.quiesce()
        eng.stop()
        # by hand now: a request left inside its second block
        req = eng.submit(list(range(7, 20)), 12)
        while not (eng._running and eng._running[0].pos == 16
                   and eng._running[0].revealed.count(True) == 2):
            eng.step()
        held = len(req.tokens)
        assert 3 <= held < 12 and not req.done.is_set()
        eng._hand_over_held()       # what a running pump's `stop` does
        got = []
        while not req.out_q.empty():
            got.append(req.out_q.get_nowait())
        assert [(k, i) for k, i, _ in got] == \
            [("token", i) for i in range(held)]
        assert [t for _, _, t in got] == req.tokens
    finally:
        # 13 + 12 tokens reserve 4 pages of 8: the cut sequence's, no more
        assert eng.shutdown() == 4


# sha256 (16 hex digits) of the lowered text of the engine's programs for
# the tiny default model of each family that was there before the Ling
# hybrid family came (commit c564374, PR 31): a bucket of each kind. A later
# PR that means to change one of these programs replaces its line; one that
# adds a family, a field or an argument for another family's sake must not.
# (`decode4` of `llama` and of `gpt` changed on purpose in PR 49: a bucket of
# two lanes and more walks `llama.paged_attend`'s work list of (lane, key
# block) pairs; `decode1`, the kept loop, and the others stood. `decode1` and
# `decode4` of all three changed on purpose in PR 51: a decode program ends in
# the greedy argmax and returns int32 [batch] where it returned the logits;
# its inputs and the outputs after the first are as they were. `prefill16` and
# `chunk16` stand: they are the parent's programs, and its cache entries.
# All four of `kimi_k2` changed on purpose in PR 54: `MOE_COUNTS` gained
# `tile_visits`, the (row tile, expert) pairs the grouped product walks on the
# TPU, counted from the group sizes on every backend (`moe.group_tiles`,
# before the products); the products here are still `lax.ragged_dot`. `llama` and `gpt` held to the digit.
# `decode1` and `decode4` of `kimi_k2` changed on purpose in PR 61: the absorbed
# attention reads each lane's cached latents on the work list of its live
# (lane, key block) pairs (`kimi_k2.walk_latents` over `listed_walk`, every
# bucket, the bucket of one too) where it gathered and scored every slot of
# every lane's page table, and `attn_key_slots` is counted from the walk's
# trips; its `prefill16` and `chunk16`, `llama` and `gpt` held to the digit.)
NEIGHBOUR_PROGRAMS = {
    "llama": {"prefill16": "431c15dfcac7aa97", "decode1": "6bf27b12ae6cc48a",
              "decode4": "a673da1cf2b122ee", "chunk16": "6a7f8549a7f8d7f8"},
    "gpt": {"prefill16": "b2ca98029a10a332", "decode1": "910442728ae47e50",
            "decode4": "56f63d1e86807bca", "chunk16": "d8e225f431a39fd7"},
    "kimi_k2": {"prefill16": "38d0038cacae10d6",
                "decode1": "983bafa7642e516a",
                "decode4": "4b2dd7aac3e9dd18",
                "chunk16": "f032712f3eae8ada"},
}


@pytest.mark.parametrize("model", sorted(NEIGHBOUR_PROGRAMS))
def test_neighbour_programs_lower_to_the_text_they_had(model):
    """PR 26 was refused for moving a neighbour's `setup_s` by touching its
    programs: a family that keeps no sequence state is served by the very
    programs it had (no state arrays, no slots, nothing new traced with one
    routing group), so its entries of the machine's compile cache stay
    good."""
    import hashlib

    import jax

    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine

    eng = LLMEngine(model=model, engine_config=EngineConfig(
        batch_buckets=(1, 4), prefill_buckets=(16,), prefill_chunk=16,
        num_pages=16, block_size=8), seed=0)
    try:
        kv = eng.kv
        assert kv.state == ()
        donate = tuple(range(3, 3 + len(kv.arena)))
        programs = {"prefill16": (eng._prefill_fns[16], (
            eng.params, np.zeros((1, 16), np.int32), np.ones((1,), np.int32),
            *kv.arena, np.full(16, kv.num_pages, np.int32),
            np.zeros(16, np.int32)))}
        for name, fn, shape in (("decode1", eng._decode_fns[1], (1,)),
                                ("decode4", eng._decode_fns[4], (4,)),
                                ("chunk16", eng._chunk_fn, (1, 16))):
            programs[name] = (fn, (
                eng.params, np.zeros(shape, np.int32),
                np.zeros(shape[0], np.int32), *kv.arena,
                np.zeros((shape[0], eng.max_pages_per_seq), np.int32),
                np.full(shape, kv.num_pages, np.int32),
                np.zeros(shape, np.int32)))
        got = {name: hashlib.sha256(
            jax.jit(fn.__wrapped__, donate_argnums=donate).lower(
                *args).as_text().encode()).hexdigest()[:16]
            for name, (fn, args) in programs.items()}
        assert got == NEIGHBOUR_PROGRAMS[model]
    finally:
        eng.shutdown()


# -- pages by layer kind (the AFMoE family: window and full layers) ---------

def _window_engine(model_kw=None, **cfg_kw):
    """The AFMoE family's tiny model (3 sliding layers of window 8 and a
    full one, float32) behind an engine of block 4, chunk 8: the window
    kind's ring is 8 / 4 + 1 = 3 pages a sequence."""
    import jax.numpy as jnp

    from ray_tpu.models.afmoe import AfmoeConfig
    from ray_tpu.serve.llm import EngineConfig, LLMEngine
    base = dict(batch_buckets=(1, 2, 4), prefill_buckets=(8,),
                prefill_chunk=8, block_size=4, num_pages=64, prefix_cache=0)
    base.update(cfg_kw)
    cfg = AfmoeConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32,
                           **(model_kw or {}))
    return LLMEngine(model="afmoe", model_cfg=cfg,
                     engine_config=EngineConfig(**base), seed=0)


def _afmoe_reference_rows(eng, prompt, tokens):
    """The plain reference's logits rows from which `tokens` were chosen."""
    import jax

    from benchmark.references import afmoe as ref
    cfg = eng.model_cfg
    config = {
        "num_hidden_layers": cfg.n_layer, "rms_norm_eps": cfg.norm_eps,
        "hidden_size": cfg.d_model, "mup_enabled": cfg.mup,
        "num_attention_heads": cfg.n_head,
        "num_key_value_heads": cfg.n_kv_head, "head_dim": cfg.head_dim,
        "rope_theta": cfg.rope_theta, "sliding_window": cfg.window,
        "layer_types": [t + "_attention" for t in cfg.types],
        "num_experts_per_tok": cfg.top_k, "route_scale": cfg.routed_scale,
        "score_func": "sigmoid", "route_norm": True, "n_group": 1}
    ids = np.asarray(list(prompt) + list(tokens[:-1]))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.full_logits(eng.params["params"], config, ids))
    return want[len(prompt) - 1:]


def test_short_and_long_in_one_batch_through_pages_of_two_kinds():
    """Prompts of 5, 13, 30 and 70 tokens in one running set (one-shot and
    chunked prefill, then decode, 3 to 9 windows of context): every token is
    the reference's, no sequence ever holds more than the ring's 3 pages of
    the window kind while the full kind holds its whole length, the counters
    by kind add up, and both kinds are quiesced at the end."""
    eng = _window_engine()
    try:
        kv = eng.kv
        assert [(p.kind.name, p.num_pages, p.ring, p.width)
                for p in kv.pools] == [("window", 12, 3, 3),
                                       ("full", 64, None, 32)]
        assert [a.shape[:2] for a in kv.arena] == [(12, 3)] * 2 \
            + [(64, 1)] * 2
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 512, n).tolist() for n in (5, 30, 70, 13)]
        news = (12, 20, 30, 9)
        reqs = [eng.submit(p, n) for p, n in zip(prompts, news)]
        held = {}
        while eng.has_work():
            eng.step()
            for seq in eng._running + eng._prefilling:
                held[seq.req.id] = tuple(len(p) for p in seq.pages)
            m = eng.metrics()
            assert m["kv_pages_live"] == m["kv_pages_live_window"] \
                + m["kv_pages_live_full"]
        for req, prompt, new in zip(reqs, prompts, news):
            assert len(req.tokens) == new
            rows = _afmoe_reference_rows(eng, prompt, req.tokens)
            assert [int(r.argmax()) for r in rows] == req.tokens
            total = len(prompt) + new
            assert held[req.id] == (min(3, -(-total // 4)), -(-total // 4))
        eng.quiesce()
        m = eng.metrics()
        assert m["kv_pages_window_seq_max"] == 3
        assert m["kv_pages_full_seq_max"] == 25
        assert (m["kv_pages_live"], m["kv_tokens_live"]) == (0, 0)
        steps = m["decode_steps"]
        # no lane's window reaches past 7 cached positions
        assert 0 < m["decode_context_tokens_window"] <= 7 * 4 * steps
        assert m["decode_context_tokens_window"] < m["decode_context_tokens"]
        assert m["decode_kv_pages_window_lane_max"] == 3 * steps
        assert m["decode_kv_pages_window"] <= 3 * 4 * steps
        # a sliding layer's walk ends at the ring: its own key and 12 slots
        assert m["decode_key_slots_window"] <= 3 * 13 * 4 * steps
        assert m["decode_key_slots_full"] > m["decode_key_slots_window"] / 3
    finally:
        assert eng.shutdown() == 0


# The AFMoE family's tiny programs that PR 47 left alone when the decode
# buckets of two lanes and more took a work list of (lane, key block) pairs
# (hashes taken on commit ce75fa1, in the manner of `NEIGHBOUR_PROGRAMS`):
# the one-shot prefill has no cache, the chunk and the bucket of one have
# one lane, whose own blocks are the batch's longest's. (`decode1` changed on
# purpose in PR 51, as `NEIGHBOUR_PROGRAMS` says: it returns the token it
# chose. All of them, `decode4` too, changed on purpose in PR 54 for the
# `tile_visits` count, as `NEIGHBOUR_PROGRAMS` says of `kimi_k2`.)
AFMOE_ONE_LANE_PROGRAMS = {"prefill8": "fa3577f6c60f909a",
                           "decode1": "31c3535cba267799",
                           "chunk8": "c03001617a7afb2b"}
# and the bucket of four, which walks the list (PR 49 gave `llama.paged_attend`
# a list of its own and moved the fold of a trip's pairs to `llama.py`: the
# helper's default and the fold were held; changed on purpose in PR 51 with
# `decode1`: the walk is the one it was, the first output the chosen tokens)
AFMOE_PROGRAMS = {**AFMOE_ONE_LANE_PROGRAMS, "decode4": "c1d82c3af190020b"}


@pytest.mark.parametrize("program", sorted(AFMOE_PROGRAMS))
def test_afmoe_programs_lower_to_the_text_they_had(program):
    import hashlib

    import jax

    eng = _window_engine()
    try:
        kv = eng.kv
        if program == "prefill8":
            fn, args = eng._prefill_fns[8], (
                np.zeros((1, 8), np.int32), np.ones((1,), np.int32),
                *kv.arena, *eng._no_rows((8,), None))
        else:
            fn, shape = {"decode1": (eng._decode_fns[1], (1,)),
                         "decode4": (eng._decode_fns[4], (4,)),
                         "chunk8": (eng._chunk_fn, (1, 8))}[program]
            args = (np.zeros(shape, np.int32), np.zeros(shape[0], np.int32),
                    *kv.arena, *eng._no_rows(shape, shape[0]))
        text = jax.jit(fn.__wrapped__, donate_argnums=tuple(
            range(3, 3 + len(kv.arena)))).lower(eng.params, *args).as_text()
        assert hashlib.sha256(text.encode()).hexdigest()[:16] \
            == AFMOE_PROGRAMS[program]
    finally:
        eng.shutdown()


# The programs of the three families that came after `NEIGHBOUR_PROGRAMS` was
# taken and had no line of their own: the Ling hybrid (KDA states and slots
# in the arguments), SDAR (a decode pass carries a block a lane and returns
# what it chose; a prefill returns no logits) and Ouro (the rolled loop over
# the passes). Hashes taken on commit bdee1a2 (PR 51), before `models/layers.py`
# gathered the layer math these files had been importing from each other by
# underscore names: a PR that moves shared layer code shows here, on a CPU,
# that it moved no program, before it asks for a chip. A later PR that means
# to change one of these programs replaces its line. (`brumby`, PR 53: no page
# kind, so one bool array of the rows that are tokens where the others have
# page tables and coordinates, and the state arena returned by the step
# itself; its hashes were taken on the PR that brought it. `ling_hybrid` and
# `sdar_moe` changed on purpose in PR 54 for the `tile_visits` count, as
# `NEIGHBOUR_PROGRAMS` says of `kimi_k2`; `ouro` and `brumby` held to the
# digit. `ling_hybrid` changed on purpose in PR 55: the family declares
# `STATE_IN_PLACE`, its steps update the state arena themselves (the decode
# step in slot order or lane order, no gather, stack or scatter of states,
# `kda_step` reading the old state once), a chunk and a prefill compute one
# row of logits, and the steps count `kda_slot_rows`; `brumby`, whose two
# slot helpers moved into `models/layers.py` in that PR, `ouro` and
# `sdar_moe` held to the digit. `mimo_v2`, PR 57: two kinds of page whose
# arrays differ in row shape, so four arena arrays of four shapes, and a count
# of the sink's mass in the steps' vector; its hashes were taken on the PR that
# brought it, which left every other line here, `NEIGHBOUR_PROGRAMS` and
# `AFMOE_PROGRAMS` to the digit: `afmoe.window_attend` serves both families,
# and an argument that is None adds no operation. `sdar_moe`'s `decode4`
# changed on purpose in PR 59: a block pass of two lanes and more walks the
# work list of its lanes' live (lane, key block) pairs, each lane as far as
# its own last block (`sdar_moe.block_attend`, `key_walk`); its `prefill16`,
# `decode1` and `chunk16`, one lane each, keep the loop and held to the digit,
# as did every other line here, `NEIGHBOUR_PROGRAMS` and `AFMOE_PROGRAMS`.
# `ling_hybrid`'s `decode1` and `decode4` changed on purpose in PR 61: its MLA
# layer calls Kimi's absorbed attention, which walks the work list of live
# (lane, key block) pairs where it gathered every slot of every lane's table
# (`NEIGHBOUR_PROGRAMS` says the same of `kimi_k2`); its `prefill16` and
# `chunk16` and every other family's lines held to the digit.)
STATEFUL_AND_LOOP_PROGRAMS = {
    "ling_hybrid": {"prefill16": "5552f28192e60bc7", "decode1": "3e3f9a768a968c99",
                    "decode4": "595d664083082613", "chunk16": "6ba55ad58f9eea4d"},
    "sdar_moe": {"prefill16": "f764035387a6f05d", "decode1": "98e070ae0b42068f",
                 "decode4": "0b4d536aaa2d8ab6", "chunk16": "36c8461ffeb5fe4c"},
    "ouro": {"prefill16": "4484f00252d4386c", "decode1": "e10c4608e3b11682",
             "decode4": "2e2e7b24963ce4c9", "chunk16": "948472f3189d1356"},
    "brumby": {"prefill16": "ac59851bf3eb37c4", "decode1": "721ba2cd9f168cb3",
               "decode4": "0abbdbb37c879b25", "chunk16": "f782efea551a9e1d"},
    "mimo_v2": {"prefill16": "92c28963b0794606", "decode1": "674b8cfe5f4bbdb1",
                "decode4": "2bc4de8300632f41", "chunk16": "06cba1096c035204"},
}


def _program_hash(eng, program: str) -> str:
    """sha256 (16 hex digits) of the lowered text of one of `eng`'s programs,
    `prefill<s>`, `decode<b>` or `chunk<c>`, with the arguments `warmup`
    hands it (the arena and the sequence states donated)."""
    import hashlib

    import jax

    kv = eng.kv
    kind = program.rstrip("0123456789")
    size = int(program[len(kind):])
    if kind == "prefill":
        fn, args = eng._prefill_fns[size], (
            np.zeros((1, size), np.int32), np.ones((1,), np.int32),
            *kv.arena, *kv.state, *eng._no_rows((size,), None),
            *eng._slots_of((), 1))
    else:
        fn, rows, last = eng._chunk_fn, (1, size), ()
        if kind == "decode":
            fn, rows = eng._decode_fns[size], (size,)
            if eng._block is not None:      # a block a lane, and `live`
                rows, last = (size, eng._block[0]), (np.zeros(size, bool),)
        args = (np.zeros(rows, np.int32), np.zeros(rows[0], np.int32),
                *kv.arena, *kv.state, *eng._no_rows(rows, rows[0]),
                *eng._slots_of((), rows[0]), *last)
    donate = tuple(range(3, 3 + len(kv.arena) + len(kv.state)))
    text = jax.jit(fn.__wrapped__, donate_argnums=donate).lower(
        eng.params, *args).as_text()
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("model,program", [
    (model, program) for model in sorted(STATEFUL_AND_LOOP_PROGRAMS)
    for program in sorted(STATEFUL_AND_LOOP_PROGRAMS[model])])
def test_stateful_and_loop_programs_lower_to_the_text_they_had(model,
                                                               program):
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine

    eng = LLMEngine(model=model, engine_config=EngineConfig(
        batch_buckets=(1, 4), prefill_buckets=(16,), prefill_chunk=16,
        num_pages=16, block_size=8, prefix_cache=0), seed=0)
    try:
        assert bool(eng.kv.state) == (model in ("ling_hybrid", "brumby"))
        assert bool(eng.kv.pools) == (model != "brumby")
        assert _program_hash(eng, program) \
            == STATEFUL_AND_LOOP_PROGRAMS[model][program]
    finally:
        eng.shutdown()


# sha256 (16 hex digits) over the sorted (path, dtype, shape, bytes) of the
# weights `net.init` makes for each family's tiny config at seed 0, taken on
# commit bdee1a2 (PR 51). Flax draws a parameter from its path and its place
# among its module's parameters, not from the class that declares it: the
# benchmark's cells serve seeded weights and compare tokens with a reference
# fed the same, so a refactoring of the declaring classes has to leave every
# array as it was.
SEEDED_WEIGHTS = {
    "llama": "b0cdb511189bdd12", "gpt": "73bcb0e11354d63e", "kimi_k2": "4d03b6f9054bc62b",
    "ling_hybrid": "ef2c74e1429d131d", "sdar_moe": "59ef927d1cc8ae71",
    "afmoe": "336dd938d5385478", "ouro": "8c778830b7b0fe3d",
    "brumby": "68293459d8ec1493", "mimo_v2": "49e3b6f1352886f2",
}


@pytest.mark.parametrize("model", sorted(SEEDED_WEIGHTS))
def test_seeded_weights_are_the_parents(model):
    import hashlib

    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from ray_tpu.serve.llm.engine import model_family

    family, mod = model_family(model)
    cfg = getattr(mod, family.config).tiny()
    variables = nn.meta.unbox(getattr(mod, family.net)(cfg).init(
        jax.random.PRNGKey(0), jnp.ones((1, 16), jnp.int32)))
    leaves = sorted(
        (jax.tree_util.keystr(path), str(leaf.dtype), tuple(leaf.shape),
         np.asarray(leaf).tobytes())
        for path, leaf in jax.tree_util.tree_flatten_with_path(variables)[0])
    digest = hashlib.sha256()
    for path, dtype, shape, data in leaves:
        digest.update(f"{path} {dtype} {shape} ".encode())
        digest.update(data)
    assert digest.hexdigest()[:16] == SEEDED_WEIGHTS[model]


def test_one_long_lane_does_not_make_the_short_ones_walk_its_blocks():
    """A prompt of 600 tokens (three key blocks of 64 pages in the full
    layer) decoding beside three of a block each: the step scores each
    lane's own blocks, so `decode_key_slots_full` stays far under lanes x
    the longest's walk x steps, which is what it read when every lane
    walked as far as the batch's longest; the tokens are the reference's."""
    from ray_tpu.models.llama import KEY_BLOCK
    eng = _window_engine(model_kw={"max_seq_len": 1024}, num_pages=256,
                         prefill_buckets=(16,), prefill_chunk=64)
    try:
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, 512, n).tolist() for n in (600, 9, 14, 11)]
        reqs = [eng.submit(p, 24 if len(p) > 100 else 60) for p in prompts]
        eng.run_until_idle()
        for req, prompt in zip(reqs, prompts):
            rows = _afmoe_reference_rows(eng, prompt, req.tokens)
            assert [int(r.argmax()) for r in rows] == req.tokens
        eng.quiesce()
        m = eng.metrics()
        # a lane's first token is its prefill's; each decode step scores
        # its own key and its own blocks (one full layer): the long lane's
        # three, a short lane's one
        long_steps, short_steps = 24 - 1, 3 * (60 - 1)
        longest = -(-600 // KEY_BLOCK)
        assert m["decode_key_slots_full"] == long_steps + short_steps + (
            longest * long_steps + short_steps) * KEY_BLOCK
        assert m["decode_key_slots_full"] \
            < 4 * (1 + longest * KEY_BLOCK) * m["decode_steps"]
    finally:
        assert eng.shutdown() == 0


def test_admission_takes_pages_of_both_kinds_or_neither():
    """A full kind of 12 pages: a request of 40 tokens takes 10 and a ring
    of 3; the next one of 20 (5 pages) finds 2 and is given nothing, of
    either kind, until the first ends; `reserve` itself leaves no page
    behind when a kind runs out."""
    from ray_tpu.serve.llm.kv_cache import OutOfPagesError
    eng = _window_engine(num_pages=12, batch_buckets=(1, 2))
    try:
        kv = eng.kv
        assert [p.num_pages for p in kv.pools] == [6, 12]
        first = eng.submit(list(range(1, 31)), 10)
        second = eng.submit(list(range(40, 52)), 8)
        eng.step()
        assert (kv.free_pages_of(0), kv.free_pages_of(1)) == (3, 2)
        for _ in range(6):
            eng.step()
        # the second request waits, considered and holding nothing
        assert second.considered_ns is not None and second.admitted_ns is None
        assert (kv.live_pages_of(0), kv.live_pages_of(1)) == (3, 10)
        with pytest.raises(OutOfPagesError, match="full"):
            kv.reserve(20, "other")
        assert (kv.free_pages_of(0), kv.free_pages_of(1)) == (3, 2)
        eng.run_until_idle()
        assert len(first.tokens) == 10 and len(second.tokens) == 8
        eng.quiesce()
    finally:
        assert eng.shutdown() == 0


def test_a_cut_sequence_and_a_shed_request_are_counted_in_both_kinds():
    """What the replica-kill leak gate reads: an engine shut down while a
    sequence decodes reports that sequence's pages of BOTH kinds (3 of the
    ring and 10 of the full kind), `assert_quiesced` names the kind that
    leaks, and a request shed at its deadline before admission held none."""
    from ray_tpu.serve.llm.kv_cache import KVCacheError
    eng = _window_engine(batch_buckets=(1,))
    try:
        req = eng.submit(list(range(1, 31)), 10)
        late = eng.submit([7, 8, 9], 4, timeout_s=1e-6)
        for _ in range(6):
            eng.step()
        assert late.error == "deadline passed before admission"
        assert 0 < len(req.tokens) < 10
        with pytest.raises(KVCacheError, match=r"KV page leak \(window\)"):
            eng.kv.assert_quiesced()
        m = eng.metrics()
        assert (m["kv_pages_live_window"], m["kv_pages_live_full"]) == (3, 10)
    finally:
        assert eng.shutdown() == 13


def test_prefix_cache_is_refused_for_a_family_with_window_pages():
    with pytest.raises(ValueError, match="ring of pages a sequence"):
        _window_engine(prefix_cache=1)
    from ray_tpu.serve.llm.kv_cache import (KVCacheError, PagedKVCache,
                                            PageKind, PrefixCache)
    kv = PagedKVCache(8, 0, 4, seq_slots=2, kinds=(
        PageKind("window", 1, ((2, 8),) * 2, 8),))
    with pytest.raises(KVCacheError, match="one kind"):
        PrefixCache(kv)


@pytest.mark.parametrize("start, n, want_dropped", [
    (0, 5, 0), (0, 12, 0), (0, 20, 8), (9, 8, 0), (30, 16, 4)])
def test_a_rings_write_index_wraps_and_drops_what_the_write_overruns(
        start, n, want_dropped):
    """Window 8, block 4: a ring of 3 pages (12 rows). Position p goes to
    the sequence's page (p // 4) mod 3; of a write longer than the ring the
    oldest positions, which a later one of the same write lands on, are
    dropped, so no two rows share a place."""
    from ray_tpu.serve.llm.kv_cache import PagedKVCache, PageKind
    kv = PagedKVCache(16, 0, 4, seq_slots=2, kinds=(
        PageKind("window", 1, ((2, 8),) * 2, 8),
        PageKind("full", 1, ((2, 8),) * 2)), max_seq_len=64)
    owner = object()
    held = kv.reserve(start + n, owner)
    assert len(held[0]) == min(3, -(-(start + n) // 4))
    w_page, w_off = kv.write_index(held[0], start, n, n + 3, kind=0)
    kept = w_page[:n] < kv.pools[0].num_pages
    assert (w_page[n:] == kv.pools[0].num_pages).all()
    assert int((~kept).sum()) == want_dropped
    assert kept[want_dropped:].all()
    pos = start + np.arange(n)
    np.testing.assert_array_equal(
        w_page[:n][kept], np.asarray(held[0])[(pos[kept] // 4) % 3])
    np.testing.assert_array_equal(w_off[:n], pos % 4)
    places = set(zip(w_page[:n][kept].tolist(), w_off[:n][kept].tolist()))
    assert len(places) == int(kept.sum())
    # the full kind writes every position to its own page
    f_page, _ = kv.write_index(held[1], start, n, kind=1)
    np.testing.assert_array_equal(f_page, np.asarray(held[1])[pos // 4])
    kv.release(held, owner)
    kv.assert_quiesced()


def test_serve_llm_end_to_end_with_the_window_family(clean_deployments):
    """The AFMoE family through the same door: `build_app(model=...)` ->
    `serve.run` -> `handle.generate`: chunked prefill into pages of two
    kinds and decode past the window in the replica, the tokens a local
    engine of the same seed gives, and the counters by kind in the
    replica's metrics."""
    from ray_tpu import serve
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine

    engine_config = {"batch_buckets": (1, 2), "prefill_buckets": (16,),
                     "prefill_chunk": 16, "num_pages": 32, "block_size": 4,
                     "prefix_cache": 0}
    handle = serve.run(serve.llm.build_app(
        name="llm", num_replicas=1, model="afmoe",
        engine_config=engine_config))
    prompt = list(range(3, 40))                      # three chunks of 16
    streamed = [c["token"] for c in
                handle.generate.options(stream=True).remote(prompt, 6)]
    local = LLMEngine(model="afmoe",
                      engine_config=EngineConfig(**engine_config))
    try:
        want = local.submit(prompt, 6)
        local.run_until_idle()
        assert streamed == want.result()
    finally:
        local.shutdown()
    m = handle.engine_metrics.remote().result(timeout=60)
    assert m["model"] == "afmoe" and m["kv_pages_live"] == 0
    assert m["chunk_steps"] == 3
    # window 8 in pages of 4: a ring of 3; 43 tokens: 11 pages of the other
    assert (m["kv_pages_window_seq_max"], m["kv_pages_full_seq_max"]) \
        == (3, 11)
    assert (m["kv_pages_live_window"], m["kv_pages_live_full"]) == (0, 0)
    assert m["decode_moe_pairs_routed"] > 0
    assert m["decode_key_slots_full"] > 0


@pytest.mark.parametrize("n", [30, 50], ids=["oneshot", "chunked"])
def test_a_write_longer_than_the_ring_still_counts_every_row_a_token(n):
    """A prefill bucket and a chunk of 32 against a ring of 12 rows: the
    window kind drops the rows that the same write overruns, the full kind
    writes them all, and every one of them is a token to the step (routed
    to its experts, counted): the answer is the reference's."""
    eng = _window_engine(prefill_buckets=(32,), prefill_chunk=32,
                         batch_buckets=(1,))
    try:
        prompt = np.random.default_rng(n).integers(0, 512, n).tolist()
        req = eng.submit(prompt, 6)
        eng.run_until_idle()
        rows = _afmoe_reference_rows(eng, prompt, req.tokens)
        assert [int(r.argmax()) for r in rows] == req.tokens
        m = eng.metrics()
        # 3 expert layers x 4 experts a token, every prompt token once
        assert m["prefill_moe_pairs_routed"] == n * 12
        eng.quiesce()
    finally:
        assert eng.shutdown() == 0


# -- more page layers than weight layers (the Ouro family: a looped stack) ----

def _loop_engine(**cfg_kw):
    """The Ouro family's tiny model (3 layers run 2 times a token, float32)
    behind an engine of block 4: a token leaves rows in 6 page layers."""
    import jax.numpy as jnp

    from ray_tpu.models.ouro import OuroConfig
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine

    base = dict(batch_buckets=(1, 2, 4), prefill_buckets=(16, 32),
                prefill_chunk=32, block_size=4, num_pages=64)
    base.update(cfg_kw)
    return LLMEngine(model="ouro",
                     model_cfg=OuroConfig.tiny(dtype=jnp.float32,
                                               param_dtype=jnp.float32),
                     engine_config=EngineConfig(**base), seed=0)


def _loop_reference_rows(eng, prompt, tokens):
    """The plain reference's logit rows from which `tokens` were chosen."""
    import flax.linen as nn
    import jax

    from benchmark.references import ouro as ref

    cfg = eng.model_cfg
    file = {"num_hidden_layers": cfg.n_layer, "rms_norm_eps": cfg.norm_eps,
            "num_attention_heads": cfg.n_head,
            "num_key_value_heads": cfg.n_kv_head, "head_dim": cfg.head_dim,
            "rope_theta": cfg.rope_theta, "total_ut_steps": cfg.n_pass,
            "early_exit_threshold": 1}
    ids = np.asarray(list(prompt) + list(tokens[:-1]))
    with jax.default_matmul_precision("highest"):
        rows, _ = ref.full_logits(nn.meta.unbox(eng.params)["params"], file,
                                  ids)
    return np.asarray(rows)[len(prompt) - 1:]


def test_a_prefix_hit_reuses_every_passes_rows_of_a_shared_page():
    """Two prompts that share their first 12 tokens (three pages of 4)
    through the looped family with the prefix cache on: the second aliases
    the first's three pages, and a page holds the rows of ALL page layers
    (both passes' of every layer), so its suffix alone is computed (one
    chunk call) and its tokens are the reference's full pass's."""
    eng = _loop_engine()
    try:
        assert eng.kv.n_layer == 6 and eng.kv.arena[0].shape[1] == 6
        shared = list(range(5, 17))
        first = eng.submit(shared + [40, 41, 42], 5)
        eng.run_until_idle()
        m0 = eng.metrics()
        assert (m0["prefill_steps"], m0["chunk_steps"]) == (1, 0)
        second = eng.submit(shared + [50, 51], 5)
        eng.run_until_idle()
        m1 = eng.metrics()
        # nothing but the suffix went through a program: its prefill was one
        # chunk window
        assert (m1["prefill_steps"], m1["chunk_steps"]) == (2, 1)
        assert m1["prefix_cache_hit_tokens"] - m0["prefix_cache_hit_tokens"] \
            == 12
        assert m1["prefill_layer_passes"] - m0["prefill_layer_passes"] \
            == 2 * 6        # two suffix tokens x 6 page layers
        for req in (first, second):
            rows = _loop_reference_rows(eng, req.prompt, req.tokens)
            assert [int(r.argmax()) for r in rows] == req.tokens
        eng.quiesce()
    finally:
        assert eng.shutdown() == 0


def test_admission_is_held_back_by_pages_and_not_by_lanes():
    """A token costs `n_pass` times a one-pass model's cache, so the page
    count bounds the batch before `max_running` does: with 12 pages and
    requests of 20 tokens (5 pages each) two run and the third waits for
    pages while two of the four lanes stay empty; it runs when the first
    gives its pages back."""
    eng = _loop_engine(num_pages=12, prefix_cache=0)
    try:
        reqs = [eng.submit(list(range(3 + i, 15 + i)), 8) for i in range(3)]
        for _ in range(5):
            eng.step()
        assert len(eng._running) + len(eng._prefilling) == 2
        assert len(eng._waiting) == 1 and eng.config.max_running == 4
        assert eng.kv.free_pages == 2
        assert reqs[2].considered_ns is not None      # looked at, no pages
        eng.run_until_idle()
        assert [len(r.tokens) for r in reqs] == [8, 8, 8]
        for req in reqs:
            rows = _loop_reference_rows(eng, req.prompt, req.tokens)
            assert [int(r.argmax()) for r in rows] == req.tokens
        m = eng.metrics()
        assert m["decode_layer_passes"] == 6 * (m["tokens_generated"] - 3)
        eng.quiesce()
    finally:
        assert eng.shutdown() == 0


def test_serve_llm_end_to_end_with_the_looped_family(clean_deployments):
    """The Ouro family through the same door: `build_app(model=...)` ->
    `serve.run` -> `handle.generate`: prefill and decode over `n_pass *
    n_layer` page layers in the replica with the prefix cache on, the
    tokens a local engine of the same seed gives, and the loop's counters in
    the replica's metrics."""
    from ray_tpu import serve
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine

    engine_config = {"batch_buckets": (1, 2), "prefill_buckets": (16, 64),
                     "prefill_chunk": 64, "num_pages": 32, "block_size": 4}
    handle = serve.run(serve.llm.build_app(
        name="llm", num_replicas=1, model="ouro",
        engine_config=engine_config))
    prompt = list(range(3, 40))
    streamed = [c["token"] for c in
                handle.generate.options(stream=True).remote(prompt, 6)]
    again = [c["token"] for c in
             handle.generate.options(stream=True).remote(prompt, 6)]
    local = LLMEngine(model="ouro",
                      engine_config=EngineConfig(**engine_config))
    try:
        want = local.submit(prompt, 6)
        local.run_until_idle()
        assert streamed == again == want.result()
        layers = local.kv.n_layer
        assert layers == 2 * local.model_cfg.n_layer
    finally:
        local.shutdown()
    m = handle.engine_metrics.remote().result(timeout=60)
    assert m["model"] == "ouro" and m["kv_pages_live"] == 0
    # the second request hit the first's nine full pages (36 tokens) and
    # computed its last prompt token alone
    assert m["prefix_cache_hit_tokens"] == 36 and m["chunk_steps"] == 1
    assert m["prefill_layer_passes"] == layers * (37 + 1)
    assert m["decode_layer_passes"] == layers * 2 * 5
    assert 1000 * 10 <= m["decode_exit_pass_milli"] <= 4000 * 10
    assert m["decode_attn_key_slots"] > 0
