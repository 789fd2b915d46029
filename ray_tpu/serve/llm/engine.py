"""Continuous-batching LLM engine on the AOT compile cache.

Orca-style iteration-level scheduling (reference: Orca OSDI'22, vllm
`llm_engine.py`): every `step()` admits at most one request, advances
one prompt's prefill by one unit (a whole bucket or one chunk) and runs
one decode iteration over the whole running set. Sequences join and
leave the decode batch *between* steps — a finished sequence frees its
KV pages immediately and the next step simply assembles a smaller
batch; no request ever waits for a batch-mate to finish.

Shape discipline is what makes this serveable on TPU: prompts pad into a
small set of prefill buckets and the decode batch pads into a small set
of batch buckets, and each bucket owns its own `parallel.compiled_step`
wrapper compiled with ``on_retrace="error"`` — one abstract signature
per executable, so steady-state serving can never silently retrace
(`parallel.cache_stats()` proves it; the bench asserts retraces == 0
across the run).

The KV plane is a `PagedKVCache` (see kv_cache.py) whose pages are device
arrays. Every compiled program takes the arena donated, reads it through
per-sequence page-table rows, scatters its new K/V rows into their pages
at coordinates the host computed, and returns the arena, which the engine
stores back for the next call: no K or V crosses the host link. The host
keeps the allocator, the page tables and the positions. A decode program
of the `llama`, `gpt` and `ouro` families reads, a page layer, the (page,
layer) rows of each lane's own key blocks, once, in trips of a work list of
(lane, block) pairs, and nothing of the rest of the table (`models/llama.py`
`paged_attend`; the bucket of one walks its one lane's blocks in a loop);
what it scored is counted here, on the host, from the positions handed to
it (`decode_attn_key_slots`, over the arena's layers, beside
`decode_context_tokens`, what it had to). A family whose layers keep one
state a sequence has a second arena of those, a slot a sequence; where the
states are too large to stand twice the family's steps update that arena
themselves and hand it back (`STATE_IN_PLACE`), and a family may have no
kind of page at all: its cache is the state arena alone, admission takes a
slot and nothing else, and the host tells the programs which rows are
tokens where no page coordinate does.

Greedy (argmax) sampling keeps generation deterministic — the property
the continuous-batching equivalence test and the mid-stream chaos
replay both lean on. A decode program ends in that choice and returns a
token id a lane; a prefill returns its last row of logits, one a request.
"""

from __future__ import annotations

import bisect
import dataclasses
import importlib
import itertools
import queue
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ray_tpu.serve.llm.kv_cache import (OutOfPagesError, PagedKVCache,
                                        PageKind, PrefixCache,
                                        scatter_arena, scatter_state)
from ray_tpu.util import metrics as _metrics
from ray_tpu.util import request_recorder as _rr
from ray_tpu.util import tracing as _tracing


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Scheduler and cache options: these fields are their only source."""

    block_size: int = 16
    num_pages: int = 0             # 0 -> worst case for max_running
    batch_buckets: Tuple[int, ...] = (1, 2, 4, 8)
    prefill_buckets: Tuple[int, ...] = (16, 32, 64, 128)
    max_running: int = 0           # 0 -> the largest batch bucket
    eos_token: Optional[int] = None
    # copy-on-write shared-prefix page reuse
    prefix_cache: int = 1
    # chunked prefill window (0 = off: long prompts then stay capped at
    # the largest prefill bucket)
    prefill_chunk: int = 0
    # accepted at 0 and refused at any other value: speculative decoding
    # is gone, and files under benchmark/ still pass `"spec_k": 0`
    # (ROADMAP D2)
    spec_k: int = 0

    def resolved(self, max_seq_len: int) -> "EngineConfig":
        """What the engine derives from the model: the prefill buckets and
        the chunk clipped to `max_seq_len`, a lane for each row of the
        largest batch bucket, pages for every lane's worst case."""
        if self.spec_k:
            raise ValueError(
                f"spec_k={self.spec_k}: the engine has one decode path "
                f"(spec_k is accepted only at 0)")
        batch = tuple(self.batch_buckets)
        prefill = tuple(s for s in self.prefill_buckets
                        if s <= max_seq_len) or (max_seq_len,)
        max_running = min(self.max_running or max(batch), max(batch))
        pages_per_seq = -(-max_seq_len // self.block_size)
        return dataclasses.replace(
            self, batch_buckets=batch, prefill_buckets=prefill,
            max_running=max_running,
            num_pages=self.num_pages or max_running * pages_per_seq,
            prefix_cache=int(bool(self.prefix_cache)),
            prefill_chunk=max(0, min(self.prefill_chunk, max_seq_len)))


class RequestRejected(RuntimeError):
    pass


@dataclasses.dataclass(frozen=True)
class ModelFamily:
    """A row of the registry: the module under `ray_tpu.models` with its
    three step functions (imported when the family is selected, never
    before), the flax module that makes weights and its config class
    (`.tiny()` is the default model). What a token leaves in the cache is the
    module's own to say, under the names `_family_cache` reads."""

    module: str
    net: str
    config: str


MODEL_FAMILIES: Dict[str, ModelFamily] = {
    "llama": ModelFamily("ray_tpu.models.llama", "Llama", "LlamaConfig"),
    "gpt": ModelFamily("ray_tpu.models.gpt", "GPT", "GPTConfig"),
    "kimi_k2": ModelFamily("ray_tpu.models.kimi_k2", "KimiK2",
                           "KimiK2Config"),
    "ling_hybrid": ModelFamily("ray_tpu.models.ling_hybrid", "LingHybrid",
                               "LingHybridConfig"),
    "sdar_moe": ModelFamily("ray_tpu.models.sdar_moe", "SdarMoe",
                            "SdarMoeConfig"),
    "afmoe": ModelFamily("ray_tpu.models.afmoe", "Afmoe", "AfmoeConfig"),
    "ouro": ModelFamily("ray_tpu.models.ouro", "Ouro", "OuroConfig"),
    "brumby": ModelFamily("ray_tpu.models.brumby", "Brumby", "BrumbyConfig"),
    "mimo_v2": ModelFamily("ray_tpu.models.mimo_v2", "MimoV2",
                           "MimoV2Config"),
}


def model_family(model: str):
    """(the registry's row, its module) of a family's name; the module is
    imported here, when the family is selected."""
    family = MODEL_FAMILIES.get(model)
    if family is None:
        raise ValueError(f"unknown model family {model!r} "
                         f"(known: {sorted(MODEL_FAMILIES)})")
    return family, importlib.import_module(family.module)


def _valid_rows(counted: bool, pools, arena, w_pages, live=()) -> dict:
    """`valid=` for the steps of a family that counts (`step_counts`): a
    row is a token's where the program is to write it, in any kind of page
    (`w_pages`: a kind's w_page each; a ring drops the rows of a write that
    the same write overruns, and they are tokens all the same). Where the
    cache has no kind of page the host says which rows are (`live`)."""
    if not counted:
        return {}
    if not pools:
        return {"valid": live[0]}
    valid = None
    for pool, w_page in zip(pools, w_pages):
        here = w_page < arena[pool.arrays][0].shape[0]
        valid = here if valid is None else valid | here
    return {"valid": valid}


def _scatter_kinds(pools, arena, rows, w_pages, w_offs) -> tuple:
    """`scatter_arena` a kind: the step's new `rows` (one array for each of
    `arena`) go to each kind's own arrays at the kind's own coordinates
    (`w_pages`, `w_offs`: one a kind). Returns the updated arena."""
    out = ()
    for pool, w_page, w_off in zip(pools, w_pages, w_offs):
        out += scatter_arena(arena[pool.arrays], rows[pool.arrays], w_page,
                             w_off)
    return out


def _by_kind(pools, coords, each: int) -> list:
    """What a program takes after the arena's arrays, sorted: `each` tuples
    with an entry a kind of page ((page tables,) w_pages, w_offs), then
    `live`. A cache with no kind of page has no coordinates: one bool array
    stands there, rows-shaped, true where a row is a token (`live`, a tuple
    of it; empty for every other cache)."""
    if pools:
        return [coords[i::each] for i in range(each)] + [()]
    return [()] * each + [coords]


def _flat(arrays) -> list:
    """Each of `arrays` with its leading two axes as one: a window's or a
    block's [lanes, positions, ...] rows and coordinates, a row each."""
    return [a.reshape((-1,) + a.shape[2:]) for a in arrays]


def _state_args(state, slots=None) -> dict:
    """`seq_state=` and `slots=` for the chunk and decode steps of a family
    that keeps sequence state; nothing for the others."""
    return {"seq_state": state, "slots": slots} if state else {}


def _new_state(in_place: bool, state, out, slots) -> tuple:
    """The state arena's arrays after a step: what the step returned where
    the family updates them in place, else the sequences' new states `out`
    scattered to their slots."""
    return tuple(out) if in_place else scatter_state(state, out, *slots)


def _kv_rows(cfg) -> Tuple[Tuple[int, int], ...]:
    """K and V of [n_kv_head, head_dim] (a family without grouped-query
    attention has a key head for every query head)."""
    n_kv_head = getattr(cfg, "n_kv_head", None)
    if n_kv_head is None:
        n_kv_head = cfg.n_head
    return ((n_kv_head, cfg.d_model // cfg.n_head),) * 2


def _family_cache(mod, cfg):
    """What a family's module `mod` declares of its cache for the model
    `cfg`, read here and nowhere else: (page kinds, sequence state, step
    counters, block schedule, key walk, whether the state is updated in
    place). The names a family file may define
    beside `prefill_step`, `chunk_step` and `decode_step`, each optional:

    - `page_kinds(cfg)` -> a tuple of `kv_cache.PageKind`'s fields (name,
      layers, rows, window) a kind, where the paged layers differ in how much
      of a sequence they read: the arena holds each kind's arrays in that
      order, the chunk and decode steps take a page table a kind after all
      the arrays, and every step returns its cache rows in the arrays' order.
      Where it is not given there is one kind, `full`, of:
    - `paged_layers(cfg)` -> int, the layers of rows a token leaves in the
      arena (default `cfg.n_layer`; fewer where some layers keep a state
      instead, more where the stack runs several times a token), and
    - `cache_rows(cfg)` -> the row shapes, one arena array each (default K
      and V of [n_kv_head, d_model // n_head]).
    - `seq_state(cfg)` -> one (shape, dtype) an array of what a SEQUENCE
      keeps beside its pages (a recurrent layer's state): the cache manager
      keeps a slot a sequence, the chunk and decode steps take the arena's
      arrays and the lanes' slots (`seq_state=`, `slots=`), and every step
      returns the sequences' new states after the cache rows.
    - `STATE_IN_PLACE` -> True where a state is too large to stand twice:
      every step, the prefill too, takes `seq_state=` and `slots=` and returns
      THE ARENA'S ARRAYS after the cache rows, each layer's lanes read and
      written at their slots (the arrays are donated: an update in place, and
      no array of [lanes, layers, ...] beside the arena). A chunk of such a
      family returns one row of logits a sequence, its last token's, as a
      prefill does.
    - `STEP_COUNTS` -> a tuple of counter names: the steps take `valid=` (the
      rows that are tokens) and return an int32 vector of that length last,
      which the engine adds to `decode_<name>` / `prefill_<name>`.
    - `block_schedule(cfg)` -> (positions a block, positions a pass reveals,
      the mask token) of a family that generates by diffusion over blocks:
      its prefill steps cover a prompt's whole blocks and return None for
      logits, and its decode step takes one block a lane and returns, first,
      the (token, probability) it chose at every position, then the block's
      cache rows (`_decode_blocks`).
    - `decode_key_walk(cfg, positions, n_pages, page, xp)` -> (trips, blocks
      a trip, keys a block, the work list) of a decode step's or a block
      pass's walk over the cached keys: the engine calls it on the host to
      count what the program scored (`decode_attn_key_slots`)."""
    def declared(name, default=None):
        fn = getattr(mod, name, None)
        return default if fn is None else fn(cfg)

    kinds = declared("page_kinds")
    if kinds is None:
        kinds = (("full", declared("paged_layers", cfg.n_layer),
                  declared("cache_rows") or _kv_rows(cfg)),)
    return (tuple(PageKind(*kind) for kind in kinds),
            declared("seq_state", ()), tuple(getattr(mod, "STEP_COUNTS", ())),
            declared("block_schedule"), getattr(mod, "decode_key_walk", None),
            bool(getattr(mod, "STATE_IN_PLACE", False)))


# The pump thread's time ledger (util/tracing.PhaseTable): between start()
# and stop() every nanosecond of the thread is one of these names' self
# time. `pump_loop` and `engine_step` are the loop's and step()'s own
# bookkeeping; `llm.prefill*` what lies between the phases of one request's
# prefill (the spans a request's flow arrow ends on). `prefill_kv_write` /
# `decode_kv_append` hold the host's share of a write (the rows' arena
# coordinates, positions, the prefix cache).
PUMP_PHASES = (
    "pump_loop", "pump_idle", "intake", "lock_wait", "engine_step", "admit",
    "llm.prefill", "llm.prefill_chunk",
    "prefill_assemble", "prefill_dispatch", "prefill_device_wait",
    "prefill_kv_write", "prefill_sample",
    "decode_assemble", "decode_dispatch", "decode_device_wait",
    "decode_fetch", "decode_kv_append", "decode_sample", "finish")

# Upper edges, in ms, of the histogram of a stream's gaps (the time from one
# hand-over of tokens to a request to the next): 1 ms to 4 s in 38 steps of
# x1.244, each bucket (lower, upper]; `stream_gap_le_<edge>` in
# `engine.metrics()`, what is longer under `stream_gap_le_inf`.
STREAM_GAP_EDGES_MS = tuple(
    float(f"{4000.0 ** (i / 38):.4g}") for i in range(39))
_GAP_EDGES_NS = tuple(round(e * 1e6) for e in STREAM_GAP_EDGES_MS)
_GAP_KEYS = tuple(f"stream_gap_le_{e:g}" for e in STREAM_GAP_EDGES_MS) \
    + ("stream_gap_le_inf",)
# the counters of a request's stages, in the order of `Request.stages_ns`
_STAGE_KEYS = ("req_queue_ms", "req_admission_ms", "req_prefill_span_ms",
               "req_first_hold_ms")


_req_counter = itertools.count(1)


class Request:
    """One generation request; tokens stream into `out_q` as produced.

    Queue items: ("token", index, token_id) per generated token, then
    one terminal ("done", reason) / ("error", message).
    """

    def __init__(self, prompt: List[int], max_new_tokens: int,
                 deadline: Optional[float], request_id: str,
                 tenant: str = "none"):
        self.id = request_id
        self.tenant = tenant  # submitting job's label ({job=} metrics)
        self.prompt = list(prompt)
        self.max_new_tokens = max_new_tokens
        self.deadline = deadline
        self.out_q: "queue.Queue" = queue.Queue()
        # dispatch plane v2: when set, engine-side events ship through
        # this callable (straight onto the requester's response ring)
        # instead of accumulating in out_q, which nothing would read
        self.sink = None
        self.tokens: List[int] = []   # generated tokens, in order
        self.done = threading.Event()
        self.error: Optional[str] = None
        self.finish_reason: Optional[str] = None
        # the propagated request context captured at submit(): the pump
        # thread can't see the submitter's contextvars, so the ctx must
        # ride the Request object
        self.ctx: Optional[dict] = None
        self.submit_wall = time.time()
        # Stamps on the phase ledger's clock (`perf_counter_ns`), each
        # taken once, by the pump, at a transition it makes anyway and,
        # but for the first and the last, at the edge of an `rt/` phase:
        # `considered_ns` the begin of the `admit` phase that first looked
        # at the request, `admitted_ns` the end of the one that gave it
        # pages, `logits_ready_ns` the end of its last unit's
        # `prefill_device_wait`, `first_handed_ns` / `last_handed_ns` the
        # first and the newest hand-over of its tokens to their reader.
        self.submit_ns = time.perf_counter_ns()
        self.considered_ns: Optional[int] = None
        self.admitted_ns: Optional[int] = None
        self.logits_ready_ns: Optional[int] = None
        self.first_handed_ns: Optional[int] = None
        self.last_handed_ns: Optional[int] = None
        self.finish_ns: Optional[int] = None
        self.prefill_ms = 0.0   # the ledger's time of its own prefill units

    def __repr__(self):
        return f"Request({self.id})"

    # -- consumer side ---------------------------------------------------

    def result(self, timeout: Optional[float] = 60.0) -> List[int]:
        """Block until generation finishes; returns the generated ids."""
        if not self.done.wait(timeout):
            raise TimeoutError(f"request {self.id} not done "
                               f"after {timeout}s")
        if self.error is not None:
            raise RequestRejected(self.error)
        return list(self.tokens)

    def stream(self, timeout: float = 60.0):
        """Yield generated token ids as the engine produces them."""
        while True:
            kind, *rest = self.out_q.get(timeout=timeout)
            if kind == "token":
                yield rest[1]
            elif kind == "done":
                return
            else:
                raise RequestRejected(rest[0])

    def stages_ns(self) -> Tuple[int, int, int, int]:
        """(queue, admission, prefill_span, first_hold): submit ->
        considered (waiting behind others) -> admitted (pages, lane, slot,
        the prefix hash) -> logits ready (its own prefill units and the
        decode passes between its chunks) -> first token handed over
        (`prefix.insert`, the fetch, the argmax; a block family's first
        passes). They tile `first_handed_ns - submit_ns` to the
        nanosecond. A stamp that a request ended before reads as its end,
        so the stages of one that never was handed a token tile
        `finish_ns - submit_ns`."""
        end = self.first_handed_ns or self.finish_ns \
            or time.perf_counter_ns()
        edges = [self.submit_ns]
        edges += [end if stamp is None else stamp for stamp in (
            self.considered_ns, self.admitted_ns, self.logits_ready_ns)]
        edges.append(end)
        return tuple(b - a for a, b in zip(edges, edges[1:]))

    # -- engine side -----------------------------------------------------

    def _record(self, token: int) -> int:
        """Note the token as generated; returns its index."""
        self.tokens.append(token)
        return len(self.tokens) - 1

    def _hand_over(self, index: int, token: int):
        """Pass a recorded token on to whoever reads the request."""
        if self.sink is not None:
            self.sink("token", index, token)
        else:
            self.out_q.put(("token", index, token))

    def _finish(self, reason: str):
        self.finish_reason = reason
        self.finish_ns = time.perf_counter_ns()
        if self.sink is not None:
            self.sink("done", reason)
        else:
            self.out_q.put(("done", reason))
        self.done.set()

    def _fail(self, msg: str):
        self.error = msg
        self.finish_ns = time.perf_counter_ns()
        if self.sink is not None:
            self.sink("error", msg)
        else:
            self.out_q.put(("error", msg))
        self.done.set()


class _Sequence:
    """A running request's decode state.

    `pages` is a page list for each kind of page the cache has (one, for
    most families). `pos` is the number of tokens in the KV cache (= prompt +
    generated - 1 in steady state: the newest token rides as the next
    dispatch's input). `prefilled`/`cached` track the chunked-prefill
    frontier (prefilled starts at the prefix-cache hit length). `slot` is
    the sequence's row of the state arena, for a family that keeps one.
    `prefill_len` is how much of the prompt the prefill covers: all of it,
    or its whole blocks for a family that generates by blocks. Such a
    sequence also keeps its open block, positions [pos, pos + block):
    `block` the token ids (the mask token where nothing is revealed),
    `revealed` a flag a position (never read off the ids: a prompt or a
    choice may hold the mask token's id), `handed` how many leading
    positions are the prompt's or already recorded as generated."""

    __slots__ = ("req", "pages", "pos", "prefilled", "cached", "slot",
                 "prefill_len", "block", "revealed", "handed")

    def __init__(self, req: Request, pages: Tuple[List[int], ...], pos: int,
                 cached: int = 0, slot: Optional[int] = None,
                 prefill_len: Optional[int] = None):
        self.req = req
        self.pages = pages
        self.slot = slot
        self.pos = pos  # tokens already written to the KV cache
        self.prefilled = pos or cached
        self.cached = cached
        self.prefill_len = len(req.prompt) if prefill_len is None \
            else prefill_len
        self.block: List[int] = []
        self.revealed: List[bool] = []
        self.handed = 0

    @property
    def last_token(self) -> int:
        toks = self.req.tokens
        return toks[-1] if toks else self.req.prompt[-1]

    @property
    def n_generated(self) -> int:
        return len(self.req.tokens)


class LLMEngine:
    """Continuous-batching engine for one model replica.

    `model` selects the family (a key of `MODEL_FAMILIES`); `model_cfg`
    defaults to the family's tiny config in float32 (the 1-core build
    box target — a real deployment passes its own config + params).
    `store` is accepted and unused: the KV arena is device memory of
    this process (it once could live in the node's shm ObjectStore).
    """

    def __init__(self, model: str = "llama", model_cfg=None, params=None,
                 engine_config: Optional[EngineConfig] = None,
                 store=None, seed: int = 0):
        import jax
        import jax.numpy as jnp
        from ray_tpu.parallel import compiled_step

        family, mod = model_family(model)
        self.model_cfg = model_cfg or getattr(mod, family.config).tiny(
            dtype=jnp.float32)
        # `_block`: (positions a block, positions a pass reveals, the mask
        # token) of a family that generates by diffusion over blocks, else
        # None; `_key_walk`: the layout of a `llama.paged_attend` decode
        # step's walk over the cached keys, for the host's count, else None
        # `_state_in_place`: the family's steps update the state arena's
        # arrays themselves and hand them back
        kinds, seq_state, self._step_counts, self._block, self._key_walk, \
            self._state_in_place = _family_cache(mod, self.model_cfg)
        self.model_name = model
        self._mod = mod
        cfg = (engine_config or EngineConfig()).resolved(
            self.model_cfg.max_seq_len)
        self.config = cfg
        if self._block is not None:
            self._check_block_config(model, cfg)
        self.max_pages_per_seq = -(-self.model_cfg.max_seq_len
                                   // cfg.block_size)

        if params is None:
            net = getattr(mod, family.net)(self.model_cfg)
            params = net.init(
                jax.random.PRNGKey(seed),
                jnp.ones((1, min(cfg.prefill_buckets)), jnp.int32))
        self.params = params
        self._block_until_ready = jax.block_until_ready

        self._phases = _tracing.PhaseTable(PUMP_PHASES)
        # metrics() runs on its callers' threads: a ledger of its own
        self._metrics_phases = _tracing.PhaseTable(("metrics",))
        self._pump_phase: Optional[_tracing.Phase] = None
        self._pump_wall_ns = 0  # of pump threads that have ended

        if seq_state and cfg.prefix_cache:
            raise ValueError(
                f"prefix_cache=1 with the {model!r} family: its layers keep "
                f"one state a sequence, which a page alias cannot restore "
                f"(a prefix hit would start the suffix from a state that "
                f"never saw the prefix); pass prefix_cache=0")
        if cfg.prefix_cache and any(kind.window for kind in kinds):
            raise ValueError(
                f"prefix_cache=1 with the {model!r} family: its window "
                f"layers keep a ring of pages a sequence, which a page "
                f"alias cannot restore (a prefix's window pages are "
                f"overwritten as the sequence that filled them goes on); "
                f"pass prefix_cache=0")
        self.kv = PagedKVCache(
            cfg.num_pages, 0, cfg.block_size, kinds=kinds,
            dtype=jnp.dtype(self.model_cfg.dtype),
            lock=_tracing.TimedLock(self._phases, threading.Lock()),
            seq_state=seq_state, seq_slots=cfg.max_running,
            max_seq_len=self.model_cfg.max_seq_len)
        self.prefix = PrefixCache(self.kv) if cfg.prefix_cache else None

        # one compiled_step wrapper per bucket: each sees exactly one
        # abstract signature, so on_retrace="error" turns any shape
        # drift in steady-state serving into a loud failure. Every one
        # takes the arena's arrays (the pages', then the sequence states')
        # from argument 3 on, donated.
        arena_args = tuple(range(
            3, 3 + len(self.kv.arena) + len(self.kv.state)))

        def program(kind, bucket, fn):      # named as `compiled_step_calls` is
            return compiled_step(fn, donate_argnums=arena_args,
                                 on_retrace="error", name=f"{kind}:{bucket}")

        self._prefill_fns = {s: program("prefill", s, self._make_prefill_fn(s))
                             for s in cfg.prefill_buckets}
        make_decode = self._make_decode_fn if self._block is None \
            else self._make_block_decode_fn
        self._decode_fns = {b: program("decode", b, make_decode(b))
                            for b in cfg.batch_buckets}
        # one chunk executable (B=1, C=_chunk_size) covers both chunked
        # prefill windows and prefix-cache-hit suffixes: every window
        # pads to the same width, so a chunk is a bucket by construction
        self._chunk_size = size = cfg.prefill_chunk or max(cfg.prefill_buckets)
        self._chunk_fn = program("chunk", size, self._make_chunk_fn(size))

        self._waiting: List[Request] = []
        self._prefilling: List[_Sequence] = []
        self._running: List[_Sequence] = []
        # (request, index, token) a decode pass sampled and has not yet
        # passed on to its readers (`_hand_over_held`)
        self._held: List[Tuple[Request, int, int]] = []
        # dispatch plane v2: (ring, sub-ring index, deployment) once a
        # replica attaches its native intake — drained by the pump
        self._intake = None
        # the pump's waits on these (and on the KV cache's lock) are its
        # `lock_wait` phase
        self._lock = _tracing.TimedLock(       # guards queues + counters
            self._phases, threading.Lock())
        self._step_lock = _tracing.TimedLock(  # serializes step()
            self._phases, threading.Lock())
        self._work = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._step_no = 0
        self.counters: Dict[str, float] = {
            "requests_submitted": 0, "requests_completed": 0,
            "requests_failed": 0, "requests_timed_out": 0,
            "tokens_generated": 0, "prefill_steps": 0,
            "decode_steps": 0, "prefill_ms": 0.0, "decode_ms": 0.0,
            "chunk_steps": 0,
            # what crosses the host link: bytes of the host arrays handed
            # to a prefill-shaped / decode-shaped call and of the outputs
            # fetched to numpy (no K or V: token ids, tables, logits)
            "prefill_link_bytes": 0, "decode_link_bytes": 0,
            # the `llm.prefill_chunk` phase's total, beside `chunk_steps`
            "chunk_ms": 0.0,
            # cached positions the decode steps attended over, summed over
            # lanes and steps (what a step has to read of the cache)
            "decode_context_tokens": 0,
            # the same for the chunks: a chunk's own tokens and the cached
            # positions before them (the keys its causal attention needs)
            "chunk_context_tokens": 0,
            # A request's stages (`Request.stages_ns`), added when its
            # first token is handed over, over `req_first_tokens`: their
            # sum a request is what a freed lane waits for its successor.
            "req_first_tokens": 0, **dict.fromkeys(_STAGE_KEYS, 0.0),
            # A stream's gaps: every hand-over of one or more tokens to a
            # request that was handed tokens before is one gap since the
            # hand-over before (a block's tokens in one burst are one gap);
            # their histogram is `_GAP_KEYS`, below. What the gaps were
            # spent on beside the decode passes: the ledger time of a
            # step's prefill unit, and of its `admit` phases, each times
            # the sequences running meanwhile.
            "stream_gaps": 0, "stream_gap_ms": 0.0,
            "stall_prefill_lane_ms": 0.0, "stall_admit_lane_ms": 0.0,
        }
        self.counters.update(dict.fromkeys(_GAP_KEYS, 0))
        # what the family's steps count on the device (`step_counts`),
        # fetched with the first output: decode steps and prefill units apart
        for name in self._step_counts:
            self.counters[f"decode_{name}"] = 0
            self.counters[f"prefill_{name}"] = 0
        if self._block is not None:
            # a pass over the running set yields what its lanes' blocks
            # reveal: live lanes summed over the passes, those of them that
            # did nothing but write a whole block's rows, positions
            # revealed, blocks whose rows were written
            for name in ("lane_passes", "lane_commits", "tokens_revealed",
                         "blocks_committed"):
                self.counters[f"decode_{name}"] = 0
        if len(self.kv.pools) > 1:
            for pool in self.kv.pools:      # see `_count_kinds`
                self.counters[f"decode_kv_pages_{pool.kind.name}"] = 0
                if pool.kind.window is not None:
                    self.counters[
                        f"decode_kv_pages_{pool.kind.name}_lane_max"] = 0
                    self.counters[
                        f"decode_context_tokens_{pool.kind.name}"] = 0
        if self._key_walk is not None:
            # the key slots a decode step's query rows were scored
            # against, padding included, over lanes and layers: the
            # running lanes' own and every (lane, key block) pair of every
            # trip the program ran, the last trip's dead pairs too
            self.counters["decode_attn_key_slots"] = 0
        # per-bucket compiled_step dispatch counts: (kind, bucket) ->
        # calls. Every entry maps 1:1 onto one AOT executable, so the
        # rows in /metrics show exactly which compiled programs serve
        # the steady state (and the bench can assert none was missing)
        self.bucket_calls: Dict[Tuple[str, int], int] = {}
        # per-tenant rows ({job=} labels in /metrics): shed decisions and
        # throughput attributable to the submitting job — the serve
        # plane's view of the multi-tenant quota plane
        self.tenant_counters: Dict[str, Dict[str, float]] = {}
        _metrics.DEFAULT_REGISTRY.register_callback(
            "serve_llm", self._metrics_text)

    # -- compiled kernels -------------------------------------------------

    # Each program is the model's step, then `scatter_arena` of the step's
    # new cache rows into the donated arena, which it returns after the
    # logits (a decode program: after the tokens chosen from them) and
    # before the step's counts, where the family has any. The
    # arena's arrays are `rest[:n]`; a row is a token's where it is written.
    # A family that keeps sequence state has its `m` arrays next and the
    # lanes' slots last: the step's new states, which follow its cache
    # rows, are scattered to the slots (`scatter_state`) and the arrays
    # returned after the pages' (a `STATE_IN_PLACE` family's steps return the
    # arrays themselves). What follows the arrays is, a kind of page
    # (`kv.pools`; most families have one), the sequences' page table of the
    # kind (not in a prefill) and the rows' coordinates in it, w_page and
    # w_off; where the cache has no kind of page, one bool array in their
    # place, true for the rows that are tokens (`_by_kind`); then the slots.

    def _make_prefill_fn(self, bucket: int):
        mod, n, m = self._mod, len(self.kv.arena), len(self.kv.state)
        counted, in_place = bool(self._step_counts), self._state_in_place
        cfg, pools = self.model_cfg, self.kv.pools
        end = n + m + (2 * len(pools) or 1)

        def fn(variables, tokens, true_len, *rest):
            arena, state = rest[:n], rest[n:n + m]
            coords, slots = rest[n + m:end], rest[end:]
            w_pages, w_offs, live = _by_kind(pools, coords, 2)
            logits, *out = mod.prefill_step(
                variables, cfg, tokens, true_len,
                **(_state_args(state, *slots) if in_place else {}),
                **_valid_rows(counted, pools, arena,
                              [w[None] for w in w_pages],
                              [w[None] for w in live]))
            return (logits,) + _scatter_kinds(
                pools, arena, [rows[0] for rows in out[:n]],
                w_pages, w_offs) \
                + _new_state(in_place, state, out[n:n + m], slots) \
                + tuple(out[n + m:])

        fn.__name__ = f"llm_prefill_s{bucket}"
        return fn

    def _make_decode_fn(self, batch: int):
        """A token family's decode step. Its first output is the tokens it
        chose, int32 [batch], and not the logits they were chosen from:
        greedy sampling needs no more of a step on the host."""
        import jax.numpy as jnp

        mod, n, m = self._mod, len(self.kv.arena), len(self.kv.state)
        counted, in_place = bool(self._step_counts), self._state_in_place
        cfg, pools = self.model_cfg, self.kv.pools
        end = n + m + (3 * len(pools) or 1)

        def fn(variables, tokens, positions, *rest):
            arena, state = rest[:n], rest[n:n + m]
            coords, slots = rest[n + m:end], rest[end:]
            tables, w_pages, w_offs, live = _by_kind(pools, coords, 3)
            logits, *out = mod.decode_step(
                variables, cfg, tokens, positions, *arena, *tables,
                **_state_args(state, *slots),
                **_valid_rows(counted, pools, arena, w_pages, live))
            # the greedy choice, in the logits' own dtype: the lowest index
            # wins a tie and a NaN counts as the largest, as in np.argmax
            chosen = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (chosen,) + _scatter_kinds(
                pools, arena, out[:n], w_pages, w_offs) \
                + _new_state(in_place, state, out[n:n + m], slots) \
                + tuple(out[n + m:])

        fn.__name__ = f"llm_decode_b{batch}"
        return fn

    def _check_block_config(self, model: str, cfg: EngineConfig) -> None:
        """What generation by blocks asks of the options: a block never
        straddles a page or a chunk, and no page is aliased."""
        length = self._block[0]
        chunk = cfg.prefill_chunk or max(cfg.prefill_buckets)
        if cfg.block_size % length or chunk % length:
            raise ValueError(
                f"the {model!r} family generates by blocks of {length}: "
                f"block_size={cfg.block_size} and the prefill chunk "
                f"({chunk}) have to be multiples of it")
        if cfg.prefix_cache:
            raise ValueError(
                f"prefix_cache=1 with the {model!r} family: a prefill "
                f"covers a prompt's whole blocks alone, and the prefix "
                f"cache's admission and insertion count whole prompts; "
                f"no test has shown the pair right; pass prefix_cache=0")

    def _make_block_decode_fn(self, batch: int):
        """One pass over one block a lane (`block_schedule` families):
        tokens, `w_page` and `w_off` are [batch, block]; `live` [batch]
        marks the lanes that hold a sequence. A lane whose block is not
        whole names the dropped page id in all its rows and writes
        nothing. The first output is the step's (token, probability), each
        [batch, block]."""
        mod, n = self._mod, len(self.kv.arena)
        cfg, pools = self.model_cfg, self.kv.pools

        def fn(variables, tokens, positions, *rest):
            arena = rest[:n]
            *coords, live = rest[n:]
            chosen, *out = mod.decode_step(
                variables, cfg, tokens, positions, *arena, *coords[0::3],
                valid=live)
            return (chosen,) + _scatter_kinds(
                pools, arena, _flat(out[:n]), _flat(coords[1::3]),
                _flat(coords[2::3])) \
                + tuple(out[n:])

        fn.__name__ = f"llm_decode_b{batch}"
        return fn

    def _make_chunk_fn(self, size: int):
        """A window of `size` tokens of one sequence (chunked prefill, a
        prefix-cache suffix): `w_page` / `w_off` are [1, size]."""
        mod, n, m = self._mod, len(self.kv.arena), len(self.kv.state)
        counted, in_place = bool(self._step_counts), self._state_in_place
        cfg, pools = self.model_cfg, self.kv.pools
        end = n + m + (3 * len(pools) or 1)

        def fn(variables, tokens, start, *rest):
            arena, state = rest[:n], rest[n:n + m]
            coords, slots = rest[n + m:end], rest[end:]
            tables, w_pages, w_offs, live = _by_kind(pools, coords, 3)
            logits, *out = mod.chunk_step(
                variables, cfg, tokens, start, *arena, *tables,
                **_state_args(state, *slots),
                **_valid_rows(counted, pools, arena, w_pages, live))
            return (logits,) + _scatter_kinds(
                pools, arena, _flat(out[:n]), _flat(w_pages),
                _flat(w_offs)) \
                + _new_state(in_place, state, out[n:n + m], slots) \
                + tuple(out[n + m:])

        fn.__name__ = f"llm_chunk_c{size}"
        return fn

    def _note_call(self, kind: str, bucket: int):
        """Per-(kind, bucket) dispatch counter — one row per compiled
        executable actually exercised."""
        with self._lock:
            key = (kind, bucket)
            self.bucket_calls[key] = self.bucket_calls.get(key, 0) + 1

    def warmup(self):
        """Compile every bucket up front so steady state is all cache
        hits (the bench snapshots `cache_stats()` after this). The calls
        have the serving path's abstract signature (the cache keys on
        leaf avals including placement: numpy for what the host makes,
        the device arena donated) and write nothing: every row's page id
        is the dropped one."""
        kv = self.kv
        for s, fn in self._prefill_fns.items():
            self._call(fn, (
                self.params, np.zeros((1, s), np.int32),
                np.ones((1,), np.int32), *kv.arena, *kv.state,
                *self._no_rows((s,), None), *self._slots_of((), 1)))
        for b, fn in self._decode_fns.items():
            if self._block is None:
                self._warm_call(fn, (b,))
            else:
                self._warm_call(fn, (b, self._block[0]),
                                np.zeros(b, bool))
        self._warm_call(self._chunk_fn, (1, self._chunk_size))

    def _call(self, fn, args):
        """One call of a program. `args` hold the arena, donated: its
        successor, which follows the first output, goes back into `self.kv`
        (the pages' arrays, then the sequence states'). Returns the first
        output (a prefill's logits, a decode step's chosen tokens), still on
        the device, and what the family's step counted (a tuple, empty for
        most families)."""
        out = fn(*args)
        self._hand_over_held()
        n = len(self.kv.arena)
        m = n + len(self.kv.state)
        self.kv.arena = tuple(out[1:1 + n])
        self.kv.state = tuple(out[1 + n:1 + m])
        return out[0], tuple(out[1 + m:])

    def _slots_of(self, seqs, lanes: int) -> Tuple[np.ndarray, ...]:
        """The programs' last argument for a family that keeps sequence
        state: lane i's slot of the state arena, the scratch slot for a
        lane past `seqs`. Nothing for the other families."""
        if not self.kv.state:
            return ()
        slots = np.full(lanes, self.kv.scratch_slot, np.int32)
        slots[:len(seqs)] = [seq.slot for seq in seqs]
        return (slots,)

    def _add_step_counts(self, kind: str, counts, link: str) -> None:
        """Fetch what a step counted on the device (`step_counts` of the
        family: one small int32 vector) and add it to `<kind>_<name>`."""
        for vector in counts:
            vector = np.asarray(vector)
            with self._lock:
                self.counters[link] += vector.nbytes
                for name, n in zip(self._step_counts, vector.tolist()):
                    self.counters[f"{kind}_{name}"] += n

    def _warm_call(self, fn, rows: Tuple[int, ...], *last):
        """One decode- or chunk-shaped call: tokens and write coordinates
        are `rows`-shaped, positions and the page table one a lane; `last`
        is what a block pass takes after them."""
        b, kv = rows[0], self.kv
        self._call(fn, (
            self.params, np.zeros(rows, np.int32), np.zeros(b, np.int32),
            *kv.arena, *kv.state, *self._no_rows(rows, b),
            *self._slots_of((), b), *last))

    def _no_rows(self, rows: Tuple[int, ...], lanes: Optional[int]) -> list:
        """What a program takes after the arrays, for every kind of page,
        before any sequence is filled in: a page table of zeros for `lanes`
        sequences (none for a prefill, which takes no table) and `rows`-
        shaped coordinates that write nowhere, every page id the kind's
        dropped one. Where the cache has no kind of page: the `rows`-shaped
        flags of the rows that are tokens, none of them yet (`_mark_live`)."""
        if not self.kv.pools:
            return [np.zeros(rows, bool)]
        out = []
        for pool in self.kv.pools:
            if lanes is not None:
                out.append(np.zeros((lanes, pool.width), np.int32))
            out += [np.full(rows, pool.num_pages, np.int32),
                    np.zeros(rows, np.int32)]
        return out

    def _mark_live(self, coords, rows) -> None:
        """Where the cache has no kind of page, no coordinate says which
        rows of a call are tokens: the host does, `rows` an index into the
        flags `_no_rows` made."""
        if not self.kv.pools:
            coords[0][rows] = True

    # -- submission -------------------------------------------------------

    def _tenant_row(self, tenant: str) -> Dict[str, float]:
        """Per-tenant counter row; caller holds self._lock."""
        row = self.tenant_counters.get(tenant)
        if row is None:
            row = self.tenant_counters[tenant] = {
                "requests_submitted": 0, "requests_completed": 0,
                "requests_timed_out": 0, "tokens_generated": 0,
            }
        return row

    def submit(self, prompt: List[int], max_new_tokens: int = 16, *,
               request_id: Optional[str] = None,
               timeout_s: Optional[float] = None,
               tenant: Optional[str] = None) -> Request:
        if not prompt:
            raise RequestRejected("empty prompt")
        if not self.config.prefill_chunk:
            # chunked prefill off: a prompt must fit one prefill bucket
            # (with chunking on, any prompt up to max_seq_len windows in)
            limit = max(self.config.prefill_buckets)
            if len(prompt) > limit:
                raise RequestRejected(
                    f"prompt of {len(prompt)} tokens exceeds the "
                    f"largest prefill bucket ({limit})")
        total = len(prompt) + max_new_tokens
        if total > self.model_cfg.max_seq_len:
            raise RequestRejected(
                f"prompt+max_new_tokens {total} exceeds max_seq_len "
                f"{self.model_cfg.max_seq_len}")
        deadline = (time.monotonic() + timeout_s) if timeout_s else None
        if tenant is None:
            # default attribution: the submitting process's job
            from ray_tpu._private.object_ref import get_core_worker
            cw = get_core_worker()
            tenant = cw.job_id.hex()[:8] if cw is not None else "none"
        req = Request(prompt, max_new_tokens, deadline,
                      request_id or f"llm-{next(_req_counter)}",
                      tenant=tenant)
        # the replica's serving(ctx) region is live during submit (it
        # happens inside handle_request_streaming's yield-from); the
        # pump thread reads the ctx back off the request
        req.ctx = _rr.current()
        with self._lock:
            self.counters["requests_submitted"] += 1
            self._tenant_row(tenant)["requests_submitted"] += 1
            self._waiting.append(req)
        self._work.set()
        return req

    # -- scheduler --------------------------------------------------------

    def step(self) -> bool:
        """One engine iteration: admit one request, advance one prompt's
        prefill by one unit, then one decode pass over the running set.
        Returns False when there was nothing to do."""
        phases = self._phases
        # bare reads: an iteration with work is a step of a profiler's
        # overview, a poll of empty queues is not
        with self._step_lock, phases.phase(
                "engine_step",
                step=self._step_no + 1 if self._waiting or self._prefilling
                or self._running else None):
            # a stretch's wall time is what the ledger charged
            # meanwhile: its phases, and any lock_wait inside them
            prefill_ns = decode_ns = 0
            tokens_out = 0
            advanced = False
            # what the running streams wait for before this step's decode
            # pass: the admission and the prefill unit, a lane each
            stall_admit = stall_prefill = 0
            lanes = len(self._running)
            with phases.phase("admit") as admit:
                self._shed_expired()
            stall_admit += admit.ns * lanes
            if not self._prefilling:
                with phases.phase("admit") as admit:
                    seq = self._admit_one(admit.begin_ns)
                if seq is not None:
                    seq.req.admitted_ns = admit.end_ns
                    if not seq.prefill_len:     # nothing to prefill
                        seq.req.logits_ready_ns = admit.end_ns
                stall_admit += admit.ns * lanes
            if self._prefilling:
                lanes = len(self._running)
                mark = phases.total_ns()
                # ONE chunk (or one-shot bucket prefill) per step: a long
                # prompt spreads across steps while decode below keeps
                # running — the head-of-line fix
                tokens_out += self._advance_prefill()
                advanced = True
                prefill_ns += phases.total_ns() - mark
                stall_prefill = prefill_ns * lanes
            if self._running:
                mark = phases.total_ns()
                tokens_out += self._decode_once()
                # work, whatever it yields: a pass over blocks may reveal
                # nothing that can be streamed yet
                advanced = True
                decode_ns += phases.total_ns() - mark
            did = bool(tokens_out) or advanced
            if did:
                self._step_no += 1
                prefill_ms, decode_ms = prefill_ns / 1e6, decode_ns / 1e6
                with self._lock:
                    self.counters["prefill_ms"] += prefill_ms
                    self.counters["decode_ms"] += decode_ms
                    self.counters["tokens_generated"] += tokens_out
                    self.counters["stall_prefill_lane_ms"] += \
                        stall_prefill / 1e6
                    self.counters["stall_admit_lane_ms"] += \
                        stall_admit / 1e6
            return did

    def _shed_expired(self):
        now = time.monotonic()
        with self._lock:
            keep = []
            shed = []
            for req in self._waiting:
                if req.deadline is not None and now > req.deadline:
                    self.counters["requests_timed_out"] += 1
                    self._tenant_row(req.tenant)["requests_timed_out"] += 1
                    shed.append(req)
                else:
                    keep.append(req)
            self._waiting = keep
        for req in shed:
            req._fail("deadline passed before admission")
            self._emit_request_record(req, "timed_out")

    def _admit_one(self, now_ns: int) -> Optional[_Sequence]:
        """Pop the oldest waiting request whose worst-case page demand
        fits right now (pages reserved up front: a running sequence can
        never hit OutOfPages mid-decode; a family with several kinds of
        page gets all of them or none, `kv.reserve`). With the prefix cache on,
        admission aliases the longest cached full-page prefix into the
        new page table atomically with the remainder allocation — the
        sequence then prefills only the uncached suffix. `now_ns` is the
        begin of the caller's `admit` phase."""
        with self._lock:
            if not self._waiting or \
                    len(self._running) + len(self._prefilling) >= \
                    self.config.max_running:
                return None
            req = self._waiting[0]
            # queue phase ends at the FIRST admission consideration —
            # time spent retrying page reservation after this point is
            # admission wait, not queue wait
            if req.considered_ns is None:
                req.considered_ns = max(now_ns, req.submit_ns)
            total = len(req.prompt) + req.max_new_tokens
            cached = 0
            try:
                if self.prefix is not None:
                    held, cached = self.prefix.acquire(
                        req.prompt, req, self.kv.pages_for_tokens(total))
                    pages = (held,)
                else:
                    # every kind's pages, or none
                    pages = self.kv.reserve(total, req)
            except OutOfPagesError:
                return None
            # a slot a running sequence (`seq_slots=max_running`), and the
            # cap above has left room: one is free
            slot = self.kv.take_slot(req) if self.kv.state else None
            self._waiting.pop(0)
            seq = _Sequence(req, pages, pos=0, cached=cached, slot=slot,
                            prefill_len=self._prefill_len(req))
            if seq.prefill_len:
                self._prefilling.append(seq)
            else:
                # a prompt shorter than a block: nothing to prefill
                self._open_block(seq)
                self._running.append(seq)
        return seq

    def _prefill_len(self, req: Request) -> int:
        """How much of the prompt the prefill covers: all of it, or, for a
        family that generates by blocks, its whole blocks (the rest opens
        the first block as revealed positions)."""
        s = len(req.prompt)
        return s if self._block is None else s - s % self._block[0]

    def _open_block(self, seq: _Sequence) -> None:
        """The block at `seq.pos`: what is left of the prompt past its
        whole blocks as revealed positions, the mask token elsewhere."""
        length, _, mask = self._block
        known = seq.req.prompt[seq.pos:]
        seq.block = list(known) + [mask] * (length - len(known))
        seq.revealed = [True] * len(known) + [False] * (length - len(known))
        seq.handed = len(known)

    # -- prefill (one-shot bucket / chunked / prefix-cache suffix) --------

    def _advance_prefill(self) -> int:
        """Advance the oldest in-flight prefill by one unit of work:
        a one-shot bucket prefill when the whole prompt fits, otherwise
        one chunk of the prompt. Returns tokens emitted (1 exactly when
        the prefill completes: the first token comes from the final
        chunk's logits)."""
        # this unit's own time (what lies between the phases below, the
        # hand-over to the running set) is `prefill_assemble`
        with self._phases.phase("prefill_assemble"):
            seq = self._prefilling[0]
            req = seq.req
            s = seq.prefill_len
            mark = self._phases.total_ns()
            oneshot = (seq.prefilled == 0
                       and s <= max(self.config.prefill_buckets)
                       and (not self.config.prefill_chunk
                            or s <= self._chunk_size))
            if oneshot:
                emitted = self._prefill_oneshot(seq)
            else:
                emitted = self._chunk_advance(seq)
                with self._lock:
                    self.counters["chunk_ms"] += \
                        (self._phases.total_ns() - mark) / 1e6
            req.prefill_ms += (self._phases.total_ns() - mark) / 1e6
            if seq.prefilled >= s or seq.req.done.is_set():
                with self._lock:
                    if seq in self._prefilling:
                        self._prefilling.remove(seq)
                if not seq.req.done.is_set():
                    if self._block is not None:
                        self._open_block(seq)
                    with self._lock:
                        self._running.append(seq)
            return emitted

    def _emit_first(self, seq: _Sequence, next_logits_row) -> int:
        """Emit the prompt's next token; on finish, release everything
        (a one-token request never reaches the running set)."""
        row = np.asarray(next_logits_row)
        self._count_link("prefill_link_bytes", row)
        tok = int(np.argmax(row))
        self._hand_over(((seq.req, seq.req._record(tok), tok),),
                        time.perf_counter_ns())
        if self._seq_finished(seq, tok):
            self._finish(seq)
        return 1

    def _request_phase(self, name: str, req: Request,
                       attrs: Dict[str, Any]) -> _tracing.Phase:
        """The phase one request's prefill work nests in. It carries the
        request's id (the recorder's, where the request came through a
        handle), and its JSONL span is where the handle's flow arrow
        ends."""
        req_id = req.id
        if req.ctx:
            req_id = req.ctx["req_id"]
            attrs["flow_id"] = f"req:{req_id}"
        return self._phases.phase(name, req_id=req_id, kind="consumer",
                                  attrs=attrs)

    def _count_link(self, counter: str, *arrays) -> None:
        """Add the bytes of the numpy arrays among `arrays` (a call's host
        arguments, an output fetched to the host) to a link counter."""
        n = sum(a.nbytes for a in arrays if isinstance(a, np.ndarray))
        with self._lock:
            self.counters[counter] += n

    def _prefill_forward(self, req: Request, fn, args):
        """What every prefill shares (one-shot, chunk): the call, which
        leaves the rows' K and V in their pages, and the wait, whose end
        is when the request's logits are ready (its last unit's stands).
        `args` hold the arena, donated: its successor goes back into
        `self.kv`. Returns the logits, still on the device."""
        phase = self._phases.phase
        with phase("prefill_dispatch"):
            self._count_link("prefill_link_bytes", *args)
            logits, counts = self._call(fn, args)
        with phase("prefill_device_wait") as wait:
            self._block_until_ready((logits, self.kv.arena, self.kv.state))
            self._add_step_counts("prefill", counts, "prefill_link_bytes")
        req.logits_ready_ns = wait.end_ns
        return logits

    def _prefill_oneshot(self, seq: _Sequence) -> int:
        req = seq.req
        s = seq.prefill_len
        bucket = min(b for b in self.config.prefill_buckets if b >= s)
        phase = self._phases.phase
        with self._request_phase("llm.prefill", req,
                                 {"bucket": bucket, "tokens_in": s}):
            with phase("prefill_assemble"):
                toks = np.zeros((1, bucket), np.int32)
                toks[0, :s] = req.prompt[:s]
                self._note_call("prefill", bucket)
            with phase("prefill_kv_write"):
                coords = [c for kind, held in enumerate(seq.pages)
                          for c in self.kv.write_index(held, 0, s, bucket,
                                                       kind=kind)] \
                    or self._no_rows((bucket,), None)
                self._mark_live(coords, np.s_[:s])
            next_logits = self._prefill_forward(
                req, self._prefill_fns[bucket],
                (self.params, toks, np.asarray([s], np.int32),
                 *self.kv.arena, *self.kv.state, *coords,
                 *self._slots_of((seq,), 1)))
            with phase("prefill_kv_write"):
                seq.prefilled = s
                seq.pos = s
                if self.prefix is not None:
                    self.prefix.insert(req.prompt, seq.pages[0])
                with self._lock:
                    self.counters["prefill_steps"] += 1
            with phase("prefill_sample"):
                # no logits: a family that generates by blocks, whose
                # prefill yields no token
                return 0 if next_logits is None \
                    else self._emit_first(seq, next_logits[0])

    def _chunk_advance(self, seq: _Sequence) -> int:
        """One chunk: forward the next `_chunk_size` prompt tokens
        against the pages filled so far (prefix-cache hits enter here
        with `prefilled == cached > 0`, so the cached pages are attended
        but never recomputed)."""
        req = seq.req
        s = seq.prefill_len
        c = self._chunk_size
        take = min(c, s - seq.prefilled)
        phase = self._phases.phase
        with self._request_phase(
                "llm.prefill_chunk", req,
                {"chunk": c, "start": seq.prefilled, "tokens_in": take}):
            with phase("prefill_assemble"):
                toks = np.zeros((1, c), np.int32)
                toks[0, :take] = \
                    req.prompt[seq.prefilled:seq.prefilled + take]
                coords = self._no_rows((1, c), 1)
                for table, held in zip(coords[0::3], seq.pages):
                    table[0, :len(held)] = held
                self._mark_live(coords, np.s_[0, :take])
                self._note_call("chunk", c)
            with phase("prefill_kv_write"):
                for kind, held in enumerate(seq.pages):
                    coords[3 * kind + 1][0], coords[3 * kind + 2][0] = \
                        self.kv.write_index(held, seq.prefilled, take, c,
                                            kind=kind)
            logits = self._prefill_forward(
                req, self._chunk_fn,
                (self.params, toks, np.asarray([seq.prefilled], np.int32),
                 *self.kv.arena, *self.kv.state, *coords,
                 *self._slots_of((seq,), 1)))
            with phase("prefill_kv_write"):
                seq.prefilled += take
                with self._lock:
                    self.counters["chunk_steps"] += 1
                    if self.kv.pools:   # no page, no cached position
                        self.counters["chunk_context_tokens"] += \
                            seq.prefilled
                if seq.prefilled < s:
                    return 0
                seq.pos = s
                if self.prefix is not None:
                    self.prefix.insert(req.prompt, seq.pages[0])
                with self._lock:
                    self.counters["prefill_steps"] += 1
            with phase("prefill_sample"):
                if logits is None:
                    return 0
                # a window's rows of logits, or its last token's alone
                return self._emit_first(
                    seq, logits[0] if self._state_in_place
                    else logits[0, take - 1])

    def _decode_forward(self, fn, args):
        """One decode call: the call, which leaves the written rows' K and
        V in their pages, the wait, the chosen tokens (a block pass's two
        arrays) to the host. `args` hold
        the arena, donated: its successor goes back into `self.kv`."""
        phase = self._phases.phase
        with phase("decode_dispatch"):
            chosen, counts = self._call(fn, args)
            # a block pass's (token, probability), else a token a lane
            pair = isinstance(chosen, tuple)
            first = chosen if pair else (chosen,)
            # small arrays: their way to the host starts with the call, so
            # the fetch below finds them there once the wait is over
            for out in (*first, *counts):
                out.copy_to_host_async()
        with phase("decode_device_wait"):
            # the np.asarray below would block on the tokens anyway
            self._block_until_ready((chosen, self.kv.arena, self.kv.state))
        with phase("decode_fetch"):
            fetched = tuple(np.asarray(a) for a in first)
            self._count_link("decode_link_bytes", *fetched, *args)
            self._add_step_counts("decode", counts, "decode_link_bytes")
            return fetched if pair else fetched[0]

    def _decode_once(self) -> int:
        if self._block is not None:
            return self._decode_blocks()
        phase = self._phases.phase
        # the pass's own time (batch assembly, what lies between the
        # phases below) is `decode_assemble`
        with phase("decode_assemble"):
            with self._lock:
                runs = list(self._running)
            bb = min(b for b in self.config.batch_buckets
                     if b >= len(runs))
            tokens = np.zeros(bb, np.int32)
            positions = np.zeros(bb, np.int32)
            # a lane beyond the running set computes on a page table
            # of zeros and writes nowhere: its page id is dropped
            coords = self._no_rows((bb,), bb)
            for i, seq in enumerate(runs):
                tokens[i] = seq.last_token
                positions[i] = seq.pos
                for table, held in zip(coords[0::3], seq.pages):
                    table[i, :len(held)] = held
            self._mark_live(coords, np.s_[:len(runs)])
            with phase("decode_kv_append"):
                self._write_coords(runs, coords)
            self._note_call("decode", bb)
            chosen = self._decode_forward(
                self._decode_fns[bb],
                (self.params, tokens, positions,
                 *self.kv.arena, *self.kv.state, *coords,
                 *self._slots_of(runs, bb)))
            with phase("decode_kv_append"):
                # cached positions the step read: none where nothing pages
                context = int(positions.sum()) if self.kv.pools else 0
                self._count_kinds(runs, positions)
                for seq in runs:
                    seq.pos += 1
            finished = []
            with phase("decode_sample"):
                toks = chosen[:len(runs)].tolist()
                for seq, tok in zip(runs, toks):
                    self._held.append((seq.req, seq.req._record(tok), tok))
                    if self._seq_finished(seq, tok):
                        finished.append(seq)
                key_slots = 0
                if self._key_walk is not None:
                    trips, width, keys, _ = self._key_walk(
                        self.model_cfg, positions, self.max_pages_per_seq,
                        self.kv.block_size, np)
                    key_slots = self.kv.n_layer * (
                        len(runs) + int(trips) * width * keys)
                with self._lock:
                    self.counters["decode_steps"] += 1
                    self.counters["decode_context_tokens"] += context
                    if key_slots:
                        self.counters["decode_attn_key_slots"] += key_slots
            for seq in finished:
                self._finish(seq)
            return len(runs)

    def _write_coords(self, runs, coords, lanes=None) -> None:
        """Where the decode pass's new rows go, a kind of page: into
        `coords` (`_no_rows`), for `lanes` of `runs` (all of them where none
        are named), the page that holds the lane's position `pos` (in a
        ring, (pos // block_size) mod the ring) and the offset in it."""
        block = self.kv.block_size
        for kind, pool in enumerate(self.kv.pools):
            w_page, w_off = coords[3 * kind + 1], coords[3 * kind + 2]
            for i in range(len(runs)) if lanes is None else lanes:
                slot, w_off[i] = divmod(runs[i].pos, block)
                w_page[i] = runs[i].pages[kind][slot % pool.width]

    def _count_kinds(self, runs, positions) -> None:
        """A decode pass's counts a kind of page, where the cache has more
        than one, each summed over the passes: the kind's pages that
        sequences hold now (`decode_kv_pages_<kind>`) and, for a kind with
        a window, the most of them that one lane of the pass holds
        (`decode_kv_pages_<kind>_lane_max`) and the cached positions the
        pass had to read of it (`decode_context_tokens_<kind>`: no lane
        more than the window's)."""
        if len(self.kv.pools) <= 1:
            return
        add = {}
        for kind, pool in enumerate(self.kv.pools):
            name, window = pool.kind.name, pool.kind.window
            add[f"decode_kv_pages_{name}"] = self.kv.live_pages_of(kind)
            if window is not None:
                add[f"decode_kv_pages_{name}_lane_max"] = max(
                    len(seq.pages[kind]) for seq in runs)
                add[f"decode_context_tokens_{name}"] = int(
                    np.minimum(positions, window - 1).sum())
        with self._lock:
            for name, n in add.items():
                self.counters[name] += n

    def _decode_blocks(self) -> int:
        """One pass over the running set of a family that generates by
        diffusion over blocks: every lane's open block goes through the
        program as it stands. A lane whose block is whole COMMITS: its
        rows are written (the other lanes name the dropped page id) and the
        next block opens, all masks. Any other lane reveals the positions
        the program was surest of, `reveal` of them or all that are left
        (the lowest index on a tie); a revealed token is final. What grows
        the revealed prefix of a block is recorded as generated, in
        position order, and handed over as a token pass's are. Returns the
        tokens recorded: 0 to a block's length a lane."""
        phase = self._phases.phase
        length, reveal, mask = self._block
        with phase("decode_assemble"):
            with self._lock:
                runs = list(self._running)
            bb = min(b for b in self.config.batch_buckets
                     if b >= len(runs))
            tokens = np.full((bb, length), mask, np.int32)
            shown = np.ones((bb, length), bool)
            positions = np.zeros(bb, np.int32)
            live = np.arange(bb) < len(runs)
            coords = self._no_rows((bb, length), bb)
            for i, seq in enumerate(runs):
                tokens[i] = seq.block
                shown[i] = seq.revealed
                positions[i] = seq.pos
                for table, held in zip(coords[0::3], seq.pages):
                    table[i, :len(held)] = held
            hidden = ~shown         # nothing is hidden in a lane past `runs`
            commits = live & shown.all(axis=1)
            with phase("decode_kv_append"):
                # a block never straddles a page: one page id a lane, the
                # block's offsets in it
                self._write_coords(runs, coords, np.flatnonzero(commits))
                for w_off in coords[2::3]:
                    w_off += np.arange(length, dtype=np.int32)
            self._note_call("decode", bb)
            chosen, prob = self._decode_forward(
                self._decode_fns[bb],
                (self.params, tokens, positions, *self.kv.arena,
                 *coords, live))
            with phase("decode_kv_append"):
                context = int(positions.sum())
                self._count_kinds(runs, positions)
                for i in np.flatnonzero(commits):
                    runs[i].pos += length
                    self._open_block(runs[i])
            finished = []
            emitted = 0
            with phase("decode_sample"):
                # the hidden positions by falling probability, a stable
                # sort so that the lowest index wins a tie; `reveal` of
                # them, or all that are left (a NaN counts as 0: it must
                # not sort behind the revealed and stall its lane)
                order = np.argsort(
                    np.where(hidden, -np.nan_to_num(prob), np.inf), axis=1,
                    kind="stable")
                take = np.minimum(hidden.sum(axis=1), reveal)
                for i in np.flatnonzero(take):
                    seq = runs[i]
                    for j in order[i, :take[i]].tolist():
                        seq.block[j] = int(chosen[i, j])
                        seq.revealed[j] = True
                    while seq.handed < length and seq.revealed[seq.handed]:
                        tok = seq.block[seq.handed]
                        seq.handed += 1
                        self._held.append(
                            (seq.req, seq.req._record(tok), tok))
                        emitted += 1
                        if self._seq_finished(seq, tok):
                            finished.append(seq)
                            break
                n_commits = int(commits.sum())
                key_slots = 0
                if self._key_walk is not None:
                    # as `_decode_once` counts a token step's (its lines
                    # stay where they are: the compile cache's keys), a
                    # lane's own keys here the block's
                    trips, width, keys, _ = self._key_walk(
                        self.model_cfg, positions, self.max_pages_per_seq,
                        self.kv.block_size, np)
                    key_slots = self.kv.n_layer * (
                        len(runs) * length + int(trips) * width * keys)
                with self._lock:
                    self.counters["decode_steps"] += 1
                    self.counters["decode_context_tokens"] += context
                    if key_slots:
                        self.counters["decode_attn_key_slots"] += key_slots
                    self.counters["decode_lane_passes"] += len(runs)
                    self.counters["decode_lane_commits"] += n_commits
                    self.counters["decode_blocks_committed"] += n_commits
                    self.counters["decode_tokens_revealed"] += \
                        int(take.sum())
            for seq in finished:
                self._finish(seq)
            return emitted

    def _seq_finished(self, seq: _Sequence, tok: int) -> bool:
        if seq.n_generated >= seq.req.max_new_tokens:
            seq.req.finish_reason = "length"
            return True
        if self.config.eos_token is not None and \
                tok == self.config.eos_token:
            seq.req.finish_reason = "stop"
            return True
        return False

    def _hand_over_held(self):
        """Pass the tokens of the last decode pass on to their readers.
        Every reader that wakes wants the interpreter, so the pass holds
        its tokens until the next call into a program is on its way (or
        a request of it ends): the readers then run while the device
        does, not between a step's logits and the next step's call."""
        if self._held:
            with self._phases.phase("decode_sample") as sample:
                held, self._held = self._held, []
                self._hand_over(held, sample.begin_ns)

    def _hand_over(self, held, now: int):
        """Pass recorded tokens, (request, index, token) each, on to their
        readers, all at `now` on the ledger's clock: a request's first
        hand-over closes its stages, any later one is a gap of its stream
        since the one before, however many tokens it brings. The lanes a
        pass handed over before share one instant, so the gaps are counted
        by that instant and folded once a distinct one."""
        firsts: List[Request] = []
        since: Dict[int, int] = {}
        for req, index, tok in held:
            last = req.last_handed_ns
            if last != now:
                req.last_handed_ns = now
                if last is None:
                    firsts.append(req)
                else:
                    since[last] = since.get(last, 0) + 1
            req._hand_over(index, tok)
        with self._lock:
            count = self.counters
            for last, n in since.items():
                count["stream_gaps"] += n
                count["stream_gap_ms"] += n * (now - last) / 1e6
                count[_GAP_KEYS[bisect.bisect_left(
                    _GAP_EDGES_NS, now - last)]] += n
            for req in firsts:
                req.first_handed_ns = now
                count["req_first_tokens"] += 1
                for key, ns in zip(_STAGE_KEYS, req.stages_ns()):
                    count[key] += ns / 1e6

    def _finish(self, seq: _Sequence):
        self._hand_over_held()
        with self._phases.phase("finish"):
            # refcounted free: pages the prefix cache (or a sibling
            # sequence) still aliases survive this — only the refcount
            # drops
            self.kv.release(seq.pages, seq.req)
            if seq.slot is not None:
                self.kv.free_slot(seq.slot, seq.req)
            with self._lock:
                if seq in self._running:
                    self._running.remove(seq)
                self.counters["requests_completed"] += 1
                row = self._tenant_row(seq.req.tenant)
                row["requests_completed"] += 1
                row["tokens_generated"] += len(seq.req.tokens)
            seq.req._finish(seq.req.finish_reason or "length")
            self._emit_request_record(seq.req, "ok")

    def _emit_request_record(self, req: Request, outcome: str):
        """Fold one finished request into the flight recorder: engine
        role, authoritative phase split, from the request's stamps. The
        four stages (`Request.stages_ns`), the decode span (first to last
        hand-over) and the finish (last hand-over to the end) tile the
        end-to-end time; TTFT and TPOT are taken where a token is handed
        to its reader; `prefill_ms` is the part of the prefill span that
        was the request's own units."""
        if not _rr.enabled():
            return
        end = req.finish_ns or time.perf_counter_ns()
        queue, admission, span, hold = (ns / 1e6 for ns in req.stages_ns())
        first, last = req.first_handed_ns, req.last_handed_ns
        n = len(req.tokens)
        ttft_ms = tpot_ms = None
        decode_ms = 0.0
        if first is not None:
            ttft_ms = (first - req.submit_ns) / 1e6
            decode_ms = (last - first) / 1e6
            if n > 1 and decode_ms > 0:
                tpot_ms = decode_ms / (n - 1)
        _rr.record_engine(
            req.ctx,
            ts=req.submit_wall,
            total_ms=(end - req.submit_ns) / 1e6,
            queue_ms=queue, admission_ms=admission,
            prefill_span_ms=span, first_hold_ms=hold,
            prefill_ms=req.prefill_ms, decode_ms=decode_ms,
            finish_ms=(end - (last or end)) / 1e6,
            ttft_ms=ttft_ms, tpot_ms=tpot_ms,
            tokens_in=len(req.prompt), tokens_out=n,
            outcome=outcome, job=req.tenant,
            finish_reason=req.finish_reason or req.error or "")

    # -- native intake (dispatch plane v2) --------------------------------

    def attach_intake(self, ring, idx: int, deployment: str) -> None:
        """Drain raw request frames from the native dispatch ring inside
        the pump loop: the batch drain runs on the engine thread right
        before step(), so the only per-batch Python entry is the decode
        itself — no pickle, no actor RPC, no per-request task."""
        self._intake = (ring, idx, deployment)
        self._work.set()

    def _drain_intake(self) -> bool:
        it = self._intake
        if it is None:
            return False
        ring, idx, deployment = it
        frames = ring.drain(idx, max_frames=64)
        for f in frames:
            self._admit_frame(ring, f, deployment)
        return bool(frames)

    def _admit_frame(self, ring, f, deployment: str) -> None:
        """Admit one natively-dispatched frame: decode the raw prompt,
        submit under the adopted trace context (recorder attribution
        stays intact — the natively-minted id IS the request id), and
        wire a sink that ships token/terminal frames straight onto the
        requester's response ring. `rr_done` fires on the terminal
        event with the enqueue's generation, so the shared snapshot's
        in-flight count balances even across replica churn."""
        from ray_tpu.serve import dispatch as _dispatch

        def _ship(resp, payload: bytes, tag: int) -> None:
            if resp is None:
                return
            for _ in range(400):  # bounded spin on a wedged reader
                if resp.enqueue_to(0, payload, trace=f.trace, tag=tag):
                    return
                time.sleep(0.002)

        resp = _dispatch.response_ring(f.client)
        try:
            prompt, max_new, job = _dispatch.decode_llm_request(f.payload)
        except Exception:
            ring.done(f.rid, f.gen)
            return
        ctx = _rr.adopt_context(f.trace_id, deployment, job)
        timeout_s = None
        if f.deadline_ns:
            timeout_s = max(0.001, f.deadline_ns / 1e9 - time.monotonic())
        try:
            with _rr.serving(ctx):
                req = self.submit(prompt, max_new,
                                  request_id=f.trace_id,
                                  timeout_s=timeout_s, tenant=job)
        except Exception as e:  # noqa: BLE001 — shipped to caller
            _ship(resp, f"{type(e).__name__}: {e}".encode()[:256],
                  _dispatch.TAG_ERROR)
            ring.done(f.rid, f.gen)
            return

        def sink(kind: str, *rest) -> None:
            if kind == "token":
                _ship(resp, _dispatch._LLM_TOK.pack(rest[0], rest[1]),
                      _dispatch.TAG_TOKEN)
                return
            if kind == "done":
                _ship(resp, (rest[0] or "stop").encode()[:256],
                      _dispatch.TAG_DONE)
            else:
                _ship(resp, (rest[0] or "error").encode()[:256],
                      _dispatch.TAG_ERROR)
            ring.done(f.rid, f.gen)

        # safe after submit: emission happens in step(), on this thread
        req.sink = sink

    # -- pump thread ------------------------------------------------------

    def start(self):
        if self._thread is not None:
            return
        self._stop.clear()
        # deadman probe: one beat per pump pass, backlog read lock-free
        # (bare len() under the GIL — the watchdog must never need the
        # engine lock, or it could not fire while that lock is stuck)
        from ray_tpu._private import health as health_mod

        self._pump_probe = health_mod.watch_loop(
            f"llm_engine_pump_{id(self) & 0xffffff:06x}",
            backlog_fn=lambda: (len(self._waiting)
                                + len(self._prefilling)
                                + len(self._running)))
        health_mod.ensure_watchdog(source="SERVE_LLM")
        self._thread = threading.Thread(
            target=self._pump, name="llm-engine", daemon=True)
        self._thread.start()

    def _pump(self):
        phase = self._phases.phase
        with phase("pump_loop") as loop:
            with self._lock:
                self._pump_phase = loop
            try:
                while not self._stop.is_set():
                    self._pump_probe.beat()
                    with phase("intake"):
                        drained = self._drain_intake()
                    if self.step() or drained:
                        continue
                    with phase("pump_idle"):
                        self._work.clear()
                        it = self._intake
                        if it is not None:
                            # park on the ring's wakeup FIFO so a native
                            # enqueue wakes the pump without a poll; local
                            # submits still set _work, observed at the next
                            # bounded slice
                            it[0].wait(it[1], 0.02)
                        else:
                            self._work.wait(0.02)
            finally:
                with self._lock:
                    self._pump_wall_ns += loop.elapsed_ns()
                    self._pump_phase = None

    def stop(self):
        self._stop.set()
        self._work.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None
            self._hand_over_held()
            from ray_tpu._private import health as health_mod

            health_mod.unwatch_loop(
                f"llm_engine_pump_{id(self) & 0xffffff:06x}")

    # -- lifecycle / introspection ---------------------------------------

    def has_work(self) -> bool:
        with self._lock:
            return bool(self._waiting or self._prefilling
                        or self._running)

    def run_until_idle(self, timeout: float = 60.0):
        """Drive the engine inline (no pump thread) until drained."""
        deadline = time.monotonic() + timeout
        while self.has_work():
            if time.monotonic() > deadline:
                raise TimeoutError("engine did not drain")
            if not self.step():
                time.sleep(0.001)

    def quiesce(self, timeout: float = 60.0):
        """Wait for all in-flight work, then prove zero live KV pages."""
        deadline = time.monotonic() + timeout
        while self.has_work():
            if time.monotonic() > deadline:
                raise TimeoutError("engine did not quiesce")
            if self._thread is None:
                self.step()
            else:
                time.sleep(0.002)
        # a request's done-event fires inside the step, before the
        # step's own counter accounting lands — barrier on any
        # in-flight step so metrics read after quiesce are settled
        with self._step_lock:
            pass
        self.kv.assert_quiesced()

    def shutdown(self) -> int:
        """Stop the pump and drop the KV arena; returns leaked pages
        (0 after a clean quiesce). Waiting requests are failed."""
        self.stop()
        with self._lock:
            waiting, self._waiting = self._waiting, []
        for req in waiting:
            req._fail("engine shut down")
            self._emit_request_record(req, "failed")
        _metrics.DEFAULT_REGISTRY.register_callback(
            "serve_llm", lambda: "")
        if self.prefix is not None:
            # cached prefixes are reusable state, not leaks: release
            # them so close() reports only true sequence leaks
            self.prefix.drain()
        return self.kv.close()

    def metrics(self) -> Dict[str, Any]:
        """Counters and gauges of this engine. `ph_<phase>_ms` is the pump
        thread's time ledger (self time by `PUMP_PHASES` name, dots
        written `_`), which sums to `pump_wall_ms`; `metrics_ms` is the
        time spent in here, over `metrics_calls` finished calls."""
        with self._metrics_phases.phase("metrics"):
            return self._metrics()

    def _metrics(self) -> Dict[str, Any]:
        with self._lock:
            out = dict(self.counters)
            pump = self._pump_phase
            out["pump_wall_ms"] = (self._pump_wall_ns + (
                pump.elapsed_ns() if pump is not None else 0)) / 1e6
            out.update(
                queue_depth=len(self._waiting),
                prefilling=len(self._prefilling),
                running=len(self._running),
                kv_pages_live=self.kv.live_pages,
                kv_pages_cached=self.kv.cached_pages,
                kv_pages_total=self.kv.num_pages,
                kv_tokens_live=sum(seq.pos for seq in self._running)
                + sum(seq.prefilled for seq in self._prefilling),
                kv_page_utilization=self.kv.utilization(),
                model=self.model_name,
                compiled_step_calls={
                    f"{kind}:{bucket}": calls
                    for (kind, bucket), calls in
                    sorted(self.bucket_calls.items())},
                tenants={t: dict(row)
                         for t, row in self.tenant_counters.items()},
            )
        if len(self.kv.pools) > 1:
            for kind, pool in enumerate(self.kv.pools):
                name = pool.kind.name
                out[f"kv_pages_live_{name}"] = self.kv.live_pages_of(kind)
                out[f"kv_pages_total_{name}"] = pool.num_pages
                # the most pages of the kind one sequence was given
                out[f"kv_pages_{name}_seq_max"] = pool.seq_max
        if self.kv.state:
            out.update(state_slots_live=self.kv.live_slots,
                       state_slots_free=self.kv.free_slots,
                       state_arena_bytes=self.kv.state_nbytes)
        if self.prefix is not None:
            ps = self.prefix.stats()
            out.update(
                prefix_cache_hit_tokens=ps["hit_tokens"],
                prefix_cache_miss_tokens=ps["miss_tokens"],
                prefix_cache_hits=ps["hits"],
                prefix_cache_misses=ps["misses"],
                prefix_cache_entries=ps["entries"],
                prefix_cache_evicted=ps["evicted"],
                prefix_cache_key_tokens=ps["key_tokens"],
            )
        for name, ns in self._phases.snapshot_ns().items():
            out[f"ph_{name.replace('.', '_')}_ms"] = ns / 1e6
        out["metrics_calls"] = self._metrics_phases.count("metrics")
        out["metrics_ms"] = self._metrics_phases.ms("metrics")
        return out

    def _metrics_text(self) -> str:
        m = self.metrics()
        lines = [
            "# TYPE serve_llm_running_seqs gauge",
            f"serve_llm_running_seqs {m['running']}",
            "# TYPE serve_llm_waiting_seqs gauge",
            f"serve_llm_waiting_seqs {m['queue_depth']}",
            "# TYPE serve_llm_kv_pages_live gauge",
            f"serve_llm_kv_pages_live {m['kv_pages_live']}",
            "# TYPE serve_llm_kv_page_utilization gauge",
            f"serve_llm_kv_page_utilization "
            f"{m['kv_page_utilization']:.6f}",
            "# TYPE serve_llm_tokens_generated_total counter",
            f"serve_llm_tokens_generated_total "
            f"{int(m['tokens_generated'])}",
            "# TYPE serve_llm_requests_completed_total counter",
            f"serve_llm_requests_completed_total "
            f"{int(m['requests_completed'])}",
            "# TYPE serve_llm_requests_timed_out_total counter",
            f"serve_llm_requests_timed_out_total "
            f"{int(m['requests_timed_out'])}",
            "# TYPE serve_llm_prefill_ms_total counter",
            f"serve_llm_prefill_ms_total {m['prefill_ms']:.3f}",
            "# TYPE serve_llm_decode_ms_total counter",
            f"serve_llm_decode_ms_total {m['decode_ms']:.3f}",
            "# TYPE serve_llm_phase_ms_total counter",
        ]
        lines += [f'serve_llm_phase_ms_total{{phase="{name}"}} '
                  f"{m['ph_' + name.replace('.', '_') + '_ms']:.3f}"
                  for name in PUMP_PHASES]
        if "prefix_cache_hit_tokens" in m:
            lines += [
                "# TYPE serve_llm_prefix_cache_hit_tokens_total counter",
                f"serve_llm_prefix_cache_hit_tokens_total "
                f"{int(m['prefix_cache_hit_tokens'])}",
                "# TYPE serve_llm_prefix_cache_miss_tokens_total counter",
                f"serve_llm_prefix_cache_miss_tokens_total "
                f"{int(m['prefix_cache_miss_tokens'])}",
                "# TYPE serve_llm_prefix_cache_entries gauge",
                f"serve_llm_prefix_cache_entries "
                f"{int(m['prefix_cache_entries'])}",
                "# TYPE serve_llm_kv_pages_cached gauge",
                f"serve_llm_kv_pages_cached "
                f"{int(m['kv_pages_cached'])}",
            ]
        if m.get("compiled_step_calls"):
            lines.append(
                "# TYPE serve_llm_compiled_step_calls_total counter")
            for key, calls in m["compiled_step_calls"].items():
                kind, bucket = key.rsplit(":", 1)
                lines.append(
                    f'serve_llm_compiled_step_calls_total'
                    f'{{kind="{kind}",bucket="{bucket}"}} {calls}')
        # per-tenant rows: shed decisions + throughput per job label
        for tenant, row in sorted(m.get("tenants", {}).items()):
            for key in ("requests_submitted", "requests_completed",
                        "requests_timed_out", "tokens_generated"):
                lines.append(
                    f'serve_llm_{key}_total{{job="{tenant}"}} '
                    f"{int(row[key])}")
        return "\n".join(lines) + "\n"


def _as_engine_build_stage(init):
    """`LLMEngine.__init__` as the `engine_build` row of the start-up
    ledger: the family, the arena and the sequence states allocated, the
    programs' makers (none compiled). Applied here, at the file's end, and
    not as a decorator's line above: the lines of the methods that call the
    compiled steps are frames of every program's trace, and their positions
    are part of a kernel-bearing program's cache key (`PERF.md` §7)."""
    import functools

    @functools.wraps(init)
    def __init__(self, model: str = "llama", *args, **kwargs):
        with _tracing.startup_stage("engine_build", {"model": model},
                                    flush=True) as built:
            init(self, model, *args, **kwargs)
            built["kv_arena_bytes"] = self.kv.arena_nbytes

    return __init__


LLMEngine.__init__ = _as_engine_build_stage(LLMEngine.__init__)
