"""Paged KV-cache whose pages live on the device.

vLLM-style paged attention (reference: vllm `block_manager.py` /
`PagedAttention`): the arena is a tuple of device arrays, one for each
kind of row a token leaves in the cache, of shape [num_pages, n_layer,
block_size, *row]. The model family says what the rows are: K and V of
[n_kv_head, head_dim] for llama and gpt, one latent of [kv_lora_rank +
rope] for the MLA family. The engine's compiled programs take the arrays
as donated arguments, gather a sequence's history through its page-table
row, scatter the new rows into their pages (`scatter_arena`) and hand the
arena back: it is updated in place and never crosses the host link.
Growing a sequence never moves bytes, only appends a page id.

What stays on the host is the control plane: the free list, the holders
of every page, the prefix cache, and the arena coordinates of the rows a
program is to write (`write_index`: a page id and an offset per row, an
out-of-range page id for a row that must not be written).

Pages are REFCOUNTED: a page can be held by several sequences at once
(copy-on-write shared-prefix reuse — see `PrefixCache`), and it returns
to the free list only when its last holder releases it. Accounting is
strict: every page is either on the free list or held by at least one
owner, `free()` by a non-holder raises, and `assert_quiesced()` proves
zero sequence-live pages — the leak gate the engine (and the chaos
replica-kill test) hold the plane to. Pages held only by the prefix
cache count as quiesced (they are reusable state, not leaks); draining
the cache returns them all.

Copy-on-write discipline: only FULL pages are ever shared (a partial
page's tail is still being appended to), so a shared page is immutable
by construction — aliasing is a page-table row edit plus a refcount,
never a byte copy, and no writer ever touches a shared page.

A family whose layers keep one state a SEQUENCE (a recurrent layer's
matrix, overwritten every step) declares those arrays (`seq_state`), and the
cache manager keeps a second arena of them, [slots, *shape] each, beside the
pages: a sequence takes one slot when it takes its pages and gives it back
with them (`take_slot`, `free_slot`); the engine's programs take the arrays
donated after the pages, gather and scatter a lane's slot, and hand them
back (`scatter_state`). The last slot belongs to nobody: it is where the
padded lanes of a decode bucket read and write.

A family whose layers differ in how much of a sequence they read declares
KINDS of paged layer (`PageKind`): each kind has its own arrays [pages of
the kind, layers of the kind, block_size, *row], its own free list and
holders, and a sequence holds a page list a kind. A row's shape is its
array's own: two kinds need not agree on it (`mimo_v2`: a window layer's K
and V rows are 1,536 and 1,024 wide, a full layer's 768 and 512), nor K and V
of one kind, and every byte count follows the arrays' shapes. A kind with a `window`
(its queries read the last `window` cached positions and nothing before
them) is a RING: a sequence holds at most `ring` = pages_for_tokens(window)
+ 1 pages of it, position p lies in the sequence's page (p // block_size)
mod ring, and a page is overwritten when the window has passed it, so a
window layer holds a window and not a context. A sequence shorter than
the ring holds what its length needs, and nothing wraps. One more page than
the window's own lets a program read the window's oldest page from the
arena it was given while its new rows go to the page after the newest: a
program's writes land after its reads. Reservation (`reserve`) takes every
kind's pages under one hold of the lock, or none. A family that declares
nothing has one kind, unbounded, through the same code. A family that
declares NO kind (every layer keeps a state a sequence) has no pages at all:
the arena is the state arena alone, a sequence costs its slot whatever its
length, a reservation is empty and cannot fail, and whatever counts pages
reads 0.

A dead replica's arena dies with its process: the device memory is the
process's own, so there is nothing for a peer to reclaim.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


class KVCacheError(RuntimeError):
    pass


class OutOfPagesError(KVCacheError):
    """Allocation would exceed the arena; caller should queue, not crash."""


def scatter_arena(arena, rows, w_page, w_off):
    """Row n of each of `rows` ([N, n_layer, *row], one array for each
    array of `arena`) goes to `pages[w_page[n], :, w_off[n]]`; returns the
    updated arena, a tuple. A row whose `w_page` is `num_pages` or more is
    dropped: nothing is read or written for it, which is how idle lanes
    and padding rows, which own no page, cost nothing. Traced inside the
    engine's programs, with the arena donated, this is an update in
    place.

    The layer is an index of its own, so that the scattered unit is one
    row where it lies: indexed by page and offset alone
    (`.at[w_page, :, w_off]`), a prefill's scatter has the TPU compiler
    re-lay the whole arena out and back, two copies of it a call (compiled
    for a described v5e, PR 25)."""
    at = (w_page[:, None], np.arange(arena[0].shape[1])[None, :],
          w_off[:, None])
    return tuple(pages.at[at].set(new.astype(pages.dtype), mode="drop")
                 for pages, new in zip(arena, rows))


def scatter_state(state, new, slots=None):
    """Sequence n's new state (`new`: one array [N, *shape] for each array
    of `state`, [slots, *shape]) goes to its slot `slots[n]`; returns the
    updated arrays, a tuple. Traced inside the engine's programs with the
    arrays donated, an update in place. Padded lanes all name the scratch
    slot, and which of them lands there last is nobody's business. A
    family that keeps no such state has no arrays and no `slots`."""
    return tuple(arr.at[slots].set(x.astype(arr.dtype))
                 for arr, x in zip(state, new))


@functools.lru_cache(maxsize=None)
def _scatter_arena_jit():
    """`scatter_arena` as a program of its own, for callers that hold the
    rows themselves (`write_rows`, `append`); the engine's programs trace
    it after the model's step instead. jax is imported on first use: a
    process that only drives replicas imports this module too."""
    import jax

    return jax.jit(scatter_arena, donate_argnums=(0,))


@dataclasses.dataclass(frozen=True)
class PageKind:
    """One kind of paged layer of a model family: its name (counters carry
    it), how many of the model's layers are of it, what a token leaves in
    each (`rows`: one shape an arena array), and `window`: the most cached
    positions before a query that a layer of the kind reads (a sliding
    window of W keys: W - 1 and the query's own), None where a layer
    reads them all."""

    name: str
    n_layer: int
    rows: Tuple[Tuple[int, ...], ...]
    window: Optional[int] = None


class _Pool:
    """The pages of one kind: the allocator's maps (guarded by the cache's
    lock) and where the kind's arrays lie in `PagedKVCache.arena`."""

    def __init__(self, kind: PageKind, num_pages: int, ring: Optional[int],
                 width: int, first_array: int):
        self.kind = kind
        self.num_pages = num_pages
        # a bounded kind: the most pages a sequence holds; and the length
        # of a sequence's row of the kind's page table, either way
        self.ring = ring
        self.width = width
        self.arrays = slice(first_array, first_array + len(kind.rows))
        # LIFO free list: recently-freed pages are re-used first (warm)
        self.free: List[int] = list(range(num_pages - 1, -1, -1))
        # page -> holder list (refcount == len). A holder is a request/
        # sequence object, or a _PrefixEntry when the prefix cache
        # pinned the page for reuse.
        self.holders: Dict[int, List[object]] = {}
        # page -> how many of its holders are not the prefix cache, for
        # the pages that have one; kept as the holders change: `metrics()`
        # asks under the engine's lock, and a walk over 8,192 holder lists
        # there stalls the pump (seconds under a profiler's Python tracer)
        self.live: Dict[int, int] = {}
        self.seq_max = 0    # the most pages one reservation took


class PagedKVCache:
    """Fixed-size page allocator over an arena on the device.

    `arena` is a tuple of device arrays [num_pages, n_layer, block_size,
    *row] in `dtype`, one for each entry of `rows` (the row shapes; K and
    V of [n_kv_head, head_dim] when only those two are given, and then
    `k_pages` / `v_pages` name the two arrays). A compiled program that is
    given them donated returns their successors, and whoever made the call
    stores those back here before the next one; the old handles are dead
    from the call on. `store` is accepted for callers of the host arena
    this replaced and backs nothing.

    `kinds` (of `PageKind`) instead of `n_layer` and `rows`: the arena then
    holds every kind's arrays, in the kinds' order, and every method that
    names pages takes the kind's index (`kind=`, the first where it is not
    given). `num_pages` counts the pages of a kind without a window; a kind
    with one gets `seq_slots` (the sequences that run at once) times its
    ring, and no more than `num_pages`. `max_seq_len` is the longest
    sequence, for the width of an unbounded kind's page table. With `kinds`
    empty there is no page: `num_pages` is not read and reads 0.
    """

    def __init__(self, num_pages: int, n_layer: int, block_size: int,
                 n_kv_head: Optional[int] = None,
                 head_dim: Optional[int] = None, dtype=np.float32,
                 store=None, lock=None,
                 rows: Optional[Tuple[Tuple[int, ...], ...]] = None,
                 seq_state=(), seq_slots: int = 0,
                 kinds: Optional[Sequence[PageKind]] = None,
                 max_seq_len: int = 0):
        import jax.numpy as jnp

        if kinds is not None and not kinds:
            num_pages = 0           # no kind of page: nothing to count
        elif num_pages <= 0 or block_size <= 0:
            raise KVCacheError("num_pages and block_size must be positive")
        if kinds is None:
            if rows is None:
                rows = ((n_kv_head, head_dim),) * 2
            kinds = (PageKind("full", n_layer, rows),)
        self.kinds = tuple(dataclasses.replace(
            kind, rows=tuple(tuple(int(n) for n in row) for row in kind.rows))
            for kind in kinds)
        self.num_pages = num_pages
        self.n_layer = sum(kind.n_layer for kind in self.kinds)
        self.block_size = block_size
        self.dtype = np.dtype(dtype)
        # the engine passes a lock whose waits show in its time ledger
        self._lock = lock if lock is not None else threading.Lock()
        table = self.pages_for_tokens(max_seq_len) or num_pages
        self.pools: Tuple[_Pool, ...] = ()
        shapes = []
        for kind in self.kinds:
            ring, pages = None, num_pages
            if kind.window is not None:
                if seq_slots <= 0:
                    raise KVCacheError(
                        f"the {kind.name!r} kind keeps a window a sequence: "
                        f"seq_slots says how many sequences")
                ring = self.pages_for_tokens(kind.window) + 1
                pages = min(num_pages, seq_slots * ring)
            self.pools += (_Pool(kind, pages, ring, ring or table,
                                 len(shapes)),)
            shapes += [(pages, kind.n_layer, block_size) + row
                       for row in kind.rows]
        # from the shape: other threads ask while a step holds the handles
        self.arena_nbytes = sum(int(np.prod(shape)) for shape in shapes) \
            * self.dtype.itemsize
        self.arena = tuple(jnp.zeros(shape, self.dtype) for shape in shapes)
        self._prefix_cache: Optional["PrefixCache"] = None
        # what a sequence keeps beside its pages: `seq_state` is one
        # (shape, dtype) an array, `seq_slots` how many sequences; one
        # more slot, the last, is scratch for lanes that hold no sequence
        state_shapes = [((seq_slots + 1,) + tuple(shape), np.dtype(dt))
                        for shape, dt in seq_state]
        self.state_nbytes = sum(int(np.prod(shape)) * dt.itemsize
                                for shape, dt in state_shapes)
        self.state = tuple(jnp.zeros(shape, dt) for shape, dt in state_shapes)
        self.num_slots = seq_slots if seq_state else 0
        self.scratch_slot = self.num_slots
        self._free_slots: List[int] = list(range(self.num_slots - 1, -1, -1))
        self._slot_owner: Dict[int, object] = {}
        self._closed = False

    @property
    def k_pages(self):
        return self.arena[0]

    # the arena's handles belong to the single writer (the engine's step
    # thread), as in `_scatter`; the lock guards the allocator's maps
    @k_pages.setter
    def k_pages(self, pages):
        # raylint: disable=lock-discipline
        self.arena = (pages,) + self.arena[1:]

    @property
    def v_pages(self):
        return self.arena[1]

    @v_pages.setter
    def v_pages(self, pages):
        # raylint: disable=lock-discipline
        self.arena = self.arena[:1] + (pages,) + self.arena[2:]

    # the first kind's maps under their old names (the prefix cache, which
    # serves a cache of one kind, and tests that look inside)
    @property
    def _free(self) -> List[int]:
        return self.pools[0].free

    @property
    def _holders(self) -> Dict[int, List[object]]:
        return self.pools[0].holders

    # -- allocation -------------------------------------------------------

    def free_pages_of(self, kind: int = 0) -> int:
        with self._lock:
            return len(self.pools[kind].free)

    @property
    def free_pages(self) -> int:
        return self.free_pages_of()

    def live_pages_of(self, kind: int) -> int:
        with self._lock:
            return len(self.pools[kind].live)

    @property
    def live_pages(self) -> int:
        """Pages held by at least one sequence, over the kinds
        (prefix-cache-only pages are reusable state, not live work — see
        `cached_pages`)."""
        with self._lock:
            return sum(len(pool.live) for pool in self.pools)

    @property
    def cached_pages(self) -> int:
        """Pages held ONLY by the prefix cache (reusable on hit,
        evictable under pressure)."""
        with self._lock:
            return sum(len(pool.holders) - len(pool.live)
                       for pool in self.pools)

    def utilization(self) -> float:
        with self._lock:
            return sum(len(pool.holders) for pool in self.pools) \
                / (sum(pool.num_pages for pool in self.pools) or 1)

    def page_refcount(self, page: int, kind: int = 0) -> int:
        with self._lock:
            return len(self.pools[kind].holders.get(page, ()))

    def pages_for_tokens(self, n_tokens: int) -> int:
        return -(-n_tokens // self.block_size)  # ceil div

    def pages_by_kind(self, n_tokens: int) -> Tuple[int, ...]:
        """Pages a sequence of `n_tokens` holds, a kind: what its length
        needs, and in a ring no more than the ring."""
        need = self.pages_for_tokens(n_tokens)
        return tuple(need if pool.ring is None else min(need, pool.ring)
                     for pool in self.pools)

    def reserve(self, n_tokens: int, owner) -> Tuple[List[int], ...]:
        """Every kind's pages for a sequence of `n_tokens` (`pages_by_kind`),
        for `owner`: all of them or, with OutOfPagesError, none."""
        with self._lock:
            taken: List[List[int]] = []
            try:
                for kind, n in enumerate(self.pages_by_kind(n_tokens)):
                    taken.append(self._alloc_locked(n, owner, kind))
            except OutOfPagesError:
                for kind, pages in enumerate(taken):
                    self._free_locked(pages, owner, kind)
                raise
            return tuple(taken)

    def release(self, pages: Sequence[List[int]], owner) -> None:
        """Give back what `reserve` took (a page list a kind)."""
        with self._lock:
            for kind, held in enumerate(pages):
                self._free_locked(held, owner, kind)

    def alloc(self, n: int, owner, kind: int = 0) -> List[int]:
        """Take `n` pages for `owner`; raises OutOfPagesError when the
        arena can't satisfy the request (nothing is partially taken).
        On shortfall, cold prefix-cache entries are evicted LRU-first
        before giving up — cached prefixes never crowd out live work."""
        with self._lock:
            return self._alloc_locked(n, owner, kind)

    def _alloc_locked(self, n: int, owner, kind: int = 0) -> List[int]:
        self._check_open()
        pool = self.pools[kind]
        if n > len(pool.free) and self._prefix_cache is not None:
            self._prefix_cache._evict_for_locked(n - len(pool.free))
        if n > len(pool.free):
            raise OutOfPagesError(
                f"need {n} pages, {len(pool.free)} free "
                f"of {pool.num_pages} ({pool.kind.name})")
        pages = [pool.free.pop() for _ in range(n)]
        for p in pages:
            pool.holders[p] = [owner]
        if not isinstance(owner, _PrefixEntry):
            pool.live.update((p, 1) for p in pages)
            pool.seq_max = max(pool.seq_max, n)
        return pages

    def share(self, pages: List[int], owner, kind: int = 0) -> None:
        """Alias already-allocated pages into `owner`'s page table
        (incref). The pages must be live; the same owner may not hold a
        page twice (accounting bugs fail loudly)."""
        with self._lock:
            self._share_locked(pages, owner, kind)

    def _share_locked(self, pages: List[int], owner, kind: int = 0) -> None:
        self._check_open()
        pool = self.pools[kind]
        for p in pages:
            hs = pool.holders.get(p)
            if hs is None:
                raise KVCacheError(f"share of free page {p}")
            if any(h is owner for h in hs):
                raise KVCacheError(
                    f"share of page {p} already held by this owner")
        sequence = not isinstance(owner, _PrefixEntry)
        for p in pages:
            pool.holders[p].append(owner)
            if sequence:
                pool.live[p] = pool.live.get(p, 0) + 1

    def free(self, pages: List[int], owner, kind: int = 0) -> None:
        """Release `owner`'s hold on each page; a page returns to the
        free list only at refcount zero — a page still aliased by the
        prefix cache or another running sequence survives the free.
        Raises on double-free or a page the owner doesn't hold."""
        with self._lock:
            self._free_locked(pages, owner, kind)

    def _free_locked(self, pages: List[int], owner, kind: int = 0) -> None:
        self._check_open()
        pool = self.pools[kind]
        for p in pages:
            hs = pool.holders.get(p)
            if hs is None or not any(h is owner for h in hs):
                held = "free" if hs is None else f"held by {hs!r}"
                raise KVCacheError(
                    f"free of page {p} not held by owner ({held})")
        sequence = not isinstance(owner, _PrefixEntry)
        for p in pages:
            hs = pool.holders[p]
            for i, h in enumerate(hs):
                if h is owner:
                    del hs[i]
                    break
            if sequence:
                if pool.live[p] > 1:
                    pool.live[p] -= 1
                else:
                    del pool.live[p]
            if not hs:
                del pool.holders[p]
                pool.free.append(p)

    # -- sequence-state slots --------------------------------------------

    @property
    def free_slots(self) -> int:
        with self._lock:
            return len(self._free_slots)

    @property
    def live_slots(self) -> int:
        with self._lock:
            return len(self._slot_owner)

    def take_slot(self, owner) -> int:
        """A slot of the sequence-state arena for `owner`. The engine holds
        as many slots as it runs sequences, so none free is a bug, not
        load: KVCacheError. What the slot held is the last owner's: a
        sequence's first prefill unit starts from zero."""
        with self._lock:
            self._check_open()
            if not self._free_slots:
                raise KVCacheError(
                    f"no state slot free of {self.num_slots}")
            slot = self._free_slots.pop()
            self._slot_owner[slot] = owner
            return slot

    def free_slot(self, slot: int, owner) -> None:
        """Give `owner`'s slot back; raises on a slot it does not hold."""
        with self._lock:
            self._check_open()
            if self._slot_owner.get(slot) is not owner:
                raise KVCacheError(
                    f"free of state slot {slot} not held by owner")
            del self._slot_owner[slot]
            self._free_slots.append(slot)

    # -- data plane -------------------------------------------------------

    def write_index(self, pages: List[int], start: int, n: int,
                    rows: Optional[int] = None, kind: int = 0
                    ) -> Tuple[np.ndarray, np.ndarray]:
        """Arena coordinates (page id, offset in the page; int32, one per
        row) of positions [start, start + n) of a sequence that holds
        `pages` of the kind, padded to `rows` rows. A padding row, and a
        position past the last of `pages`, gets the kind's `num_pages` for
        a page id, which `scatter_arena` drops. In a ring the page is
        (position // block_size) mod the ring, and a position that a later
        one of the same write lands on is dropped too: the window has
        passed it before anything could read it."""
        pool = self.pools[kind]
        rows = n if rows is None else rows
        pos = start + np.arange(rows)
        slot = pos // self.block_size
        own = np.arange(rows) < n
        if pool.ring is not None:
            own &= pos >= start + n - pool.ring * self.block_size
            slot %= pool.ring
        held = np.asarray(pages, np.int32)
        own &= slot < len(held)
        w_page = np.full(rows, pool.num_pages, np.int32)
        w_page[own] = held[slot[own]]
        return w_page, (pos % self.block_size).astype(np.int32)

    def _scatter(self, rows, w_page, w_off, kind: int = 0) -> None:
        # data-plane writes are lock-free by design: the engine's step
        # thread is the single writer, and an appendable (tail) page
        # belongs to exactly one sequence — shared prefix pages are
        # always full, so no write ever lands on an aliased page (the
        # lock guards only the allocator maps)
        arrays = self.pools[kind].arrays
        arena = list(self.arena)
        arena[arrays] = _scatter_arena_jit()(
            tuple(arena[arrays]), tuple(rows), w_page, w_off)
        # raylint: disable=lock-discipline
        self.arena = tuple(arena)

    def append(self, pages: List[int], pos: int, *rows,
               kind: int = 0) -> None:
        """Write one token's rows (each [n_layer, *row]; K then V for an
        arena of those) at logical position `pos` of a sequence holding
        `pages`."""
        self._scatter([r[None] for r in rows],
                      *self.write_index(pages, pos, 1, kind=kind), kind=kind)

    def write_rows(self, pages: List[int], rows, n: int,
                   start: int = 0, kind: int = 0) -> None:
        """Bulk-write a prefill's rows (one array [n, n_layer, *row] for
        each array of the kind) for positions [start, start+n) across the
        sequence's pages (chunked prefill passes start > 0, which need not
        be page-aligned)."""
        self._scatter([r[:n] for r in rows],
                      *self.write_index(pages, start, n, kind=kind),
                      kind=kind)

    def write_prefill(self, pages: List[int], k_seq, v_seq, n: int,
                      start: int = 0) -> None:
        """`write_rows` for an arena of K and V."""
        self.write_rows(pages, (k_seq, v_seq), n, start)

    # -- lifecycle --------------------------------------------------------

    def assert_quiesced(self) -> None:
        """Prove zero sequence-live pages, of every kind. Pages held only
        by the prefix cache are quiesced state (drain the cache to release
        them); any other holder is a leak."""
        with self._lock:
            for pool in self.pools:
                name = pool.kind.name
                live = {p: hs for p, hs in pool.holders.items()
                        if any(not isinstance(h, _PrefixEntry) for h in hs)}
                if set(live) != set(pool.live):
                    raise KVCacheError(
                        f"live-page count out of step ({name}): "
                        f"{len(pool.live)} counted, {len(live)} held by a "
                        f"sequence")
                if live:
                    owners = sorted({repr(h) for hs in live.values()
                                     for h in hs
                                     if not isinstance(h, _PrefixEntry)})
                    raise KVCacheError(
                        f"KV page leak ({name}): {len(live)} live pages at "
                        f"quiesce (owners: {owners[:4]})")
                if len(pool.free) + len(pool.holders) != pool.num_pages:
                    raise KVCacheError(
                        f"free-list corrupt ({name}): {len(pool.free)} free "
                        f"+ {len(pool.holders)} held != {pool.num_pages}")
            if self._slot_owner:
                owners = sorted(repr(o) for o in self._slot_owner.values())
                raise KVCacheError(
                    f"state slot leak: {len(self._slot_owner)} live slots "
                    f"at quiesce (owners: {owners[:4]})")
            if len(self._free_slots) != self.num_slots:
                raise KVCacheError(
                    f"slot free-list corrupt: {len(self._free_slots)} free "
                    f"of {self.num_slots}")

    def close(self) -> int:
        """Drop the arenas. Returns the number of pages (of every kind) and
        state slots still sequence-live (0 when the engine quiesced
        cleanly; prefix-cache holds are not leaks — `PrefixCache.drain()`
        first for a strict zero-held close)."""
        with self._lock:
            if self._closed:
                return 0
            self._closed = True
            leaked = sum(len(pool.live) for pool in self.pools) \
                + len(self._slot_owner)
            for array in self.arena + self.state:
                if not array.is_deleted():
                    array.delete()  # the device memory, now
            self.arena = self.state = ()
            return leaked

    def _check_open(self):
        if self._closed:
            raise KVCacheError("KV cache is closed")


class _PrefixEntry:
    """One cached full page of one prompt prefix, and the holder token of
    the cache's one hold on it. `key` is (the node of the page before it,
    None at a prompt's first page; this page's own token ids)."""

    __slots__ = ("key", "page", "children")

    def __init__(self, key: Tuple[Optional["_PrefixEntry"], Tuple[int, ...]],
                 page: int):
        self.key = key
        self.page = page
        self.children = 0

    def __repr__(self):
        return f"PrefixEntry(page {self.page}, {self.children} children)"


class PrefixCache:
    """Copy-on-write shared-prefix page cache over a `PagedKVCache`.

    One node a cached page, chained to the node of the page before it: a
    full-page-aligned prompt prefix is a path from the root, and its node
    is keyed by (the parent node itself, the page's own `block_size` token
    ids). Keys are exact (the ids are compared, a parent by identity: no
    hash collisions), and a walk copies and hashes each token of a prompt
    once (`counters["key_tokens"]`), a miss at the first page one page's.
    A page's K/V depend on the token prefix alone, so a chain may hold
    pages that different sequences filled: of two requests with one prompt
    that both missed, the second to insert finds the first's nodes and
    adds only what is missing. A page has ONE cache holder, its node.

    Admission (`acquire`) aliases the longest cached path into the new
    sequence's page table (incref, zero bytes copied) and allocates only
    the pages the uncached suffix needs, so prefill runs only past the
    cached boundary. The last prompt token is never aliased (the engine
    needs its forward pass for next-token logits), and a partial page is
    never cached (its tail is still appended to).

    The lookup, the alias (incref), and the remainder allocation happen
    under ONE lock hold: check-then-alias across a lock release would
    race eviction (the raylint-pinned TOCTOU; see the fixture pair in
    tests/test_raylint.py). Eviction is LRU, leaves only, and only
    triggered by arena pressure: `PagedKVCache._alloc_locked` calls back
    into `_evict_for_locked` on shortfall, which releases the oldest node
    until the allocation fits (a page another holder still has survives
    its node: refcounts, not force-frees). `_path_locked` keeps the
    invariant that makes the oldest node a leaf: whatever touches a path
    (a hit, an insert) moves it to the young end leaf first and root last,
    so no child is ever younger than its parent, and a prompt falling out
    of use loses its tail before its head.
    """

    def __init__(self, kv: PagedKVCache):
        if len(kv.pools) != 1 or kv.pools[0].ring is not None:
            raise KVCacheError(
                "the prefix cache aliases pages of one kind: a ring's pages "
                "are overwritten as the window passes them")
        self.kv = kv
        # ONE lock with the allocator: atomic lookup+alias+alloc
        self._lock = kv._lock
        self._entries: "OrderedDict[tuple, _PrefixEntry]" = OrderedDict()
        self.counters: Dict[str, int] = {
            "hits": 0, "misses": 0, "hit_tokens": 0, "miss_tokens": 0,
            "inserted": 0, "evicted": 0, "key_tokens": 0,
        }
        kv._prefix_cache = self

    def _path_locked(self, prompt: List[int], n: int,
                     pages: Optional[List[int]] = None
                     ) -> List[_PrefixEntry]:
        """The nodes of `prompt`'s first `n` pages, root first, as far as
        they are cached; given the sequence's `pages`, the missing ones are
        made. The path is touched leaf first (caller holds the kv lock)."""
        block = self.kv.block_size
        path: List[_PrefixEntry] = []
        parent = None
        for i in range(n):
            key = (parent, tuple(prompt[i * block:(i + 1) * block]))
            self.counters["key_tokens"] += block
            node = self._entries.get(key)
            if node is None:
                if pages is None:
                    break
                node = _PrefixEntry(key, pages[i])
                self.kv._share_locked([node.page], node)
                self._entries[key] = node
                if parent is not None:
                    parent.children += 1
                self.counters["inserted"] += 1
            path.append(node)
            parent = node
        for node in reversed(path):
            self._entries.move_to_end(node.key)
        return path

    # -- admission --------------------------------------------------------

    def acquire(self, prompt: List[int], owner,
                total_pages: int) -> Tuple[List[int], int]:
        """Atomically: find the longest cached full-page prefix of
        `prompt`, alias its pages to `owner`, and allocate the
        remaining `total_pages - cached` fresh pages (evicting cold
        nodes on shortfall). Returns (page list, cached token count).
        Raises OutOfPagesError leaving no partial state."""
        block = self.kv.block_size
        with self._lock:
            # never alias the page holding the last prompt token: at
            # least one suffix token must run prefill for next-logits.
            # The hit is touched before room is made for the remainder:
            # eviction reaches it last, and frees nothing by it (`owner`
            # holds its pages).
            path = self._path_locked(prompt, (len(prompt) - 1) // block)
            cached = [node.page for node in path]
            # alias under the SAME hold as the lookup: a release here
            # would let eviction free the pages before the incref lands
            self.kv._share_locked(cached, owner)
            try:
                fresh = self.kv._alloc_locked(total_pages - len(path), owner)
            except OutOfPagesError:
                self.kv._free_locked(cached, owner)
                raise
            self.counters["hits" if path else "misses"] += 1
            self.counters["hit_tokens"] += len(path) * block
            self.counters["miss_tokens"] += len(prompt) - len(path) * block
            return cached + fresh, len(path) * block

    def insert(self, prompt: List[int], pages: List[int]) -> None:
        """Register every full page of a just-prefilled prompt that is not
        cached yet (each full-page-aligned prefix becomes hittable). Only
        FULL pages are cached — they are immutable from here on (decode
        appends land in later pages), which is the whole copy-on-write
        guarantee."""
        with self._lock:
            if not self.kv._closed:
                self._path_locked(prompt, len(prompt) // self.kv.block_size,
                                  pages)

    # -- eviction / lifecycle ---------------------------------------------

    def _evict_for_locked(self, shortfall: int) -> None:
        """Release the oldest node, always a leaf, until `shortfall` pages
        came free or the cache is empty (caller holds kv lock). Releasing
        a node frees its page only if no one else holds it."""
        before = len(self.kv._free)
        while self._entries and len(self.kv._free) - before < shortfall:
            (parent, _), node = self._entries.popitem(last=False)
            if node.children:
                raise KVCacheError(f"prefix cache: oldest is no leaf: {node}")
            if parent is not None:
                parent.children -= 1
            self.kv._free_locked([node.page], node)
            self.counters["evicted"] += 1

    def drain(self) -> None:
        """Release every cached prefix (shutdown path: after drain, a
        quiesced cache closes with zero held pages)."""
        with self._lock:
            while self._entries:
                _, node = self._entries.popitem()
                self.kv._free_locked([node.page], node)

    @property
    def entries(self) -> int:
        with self._lock:
            return len(self._entries)

    def stats(self) -> Dict[str, int]:
        with self._lock:
            out = dict(self.counters)
            out["entries"] = len(self._entries)
            return out
