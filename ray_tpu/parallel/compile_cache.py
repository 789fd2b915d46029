"""AOT executable cache + multi-step dispatch folding.

The driver hot path of a training loop is one `step(carry, batch)` call
per step; under plain `jax.jit` every call pays Python dispatch plus the
jit call-time cache probe, and any accidental re-construction of the jit
(fresh closure per step) silently retraces. This module makes the
steady-state cost of a step one executable invocation:

``compiled_step``
    Wraps a function with a process-wide AOT executable cache keyed by
    (function identity, argument treedefs/avals, mesh): the first call
    lowers and compiles once via ``jax.jit(...).lower(...).compile()``
    (reference: the jax AOT API), every subsequent call with the same
    abstract signature dispatches the cached executable directly. The
    step is traced *for* the mesh it is given: the lowering runs inside
    ``sharding.tracing_for(mesh)``, so the model's ``logical_constraint``
    annotations reach the compiler (with no mesh they pass through; the
    counts of both are in `cache_stats()`). Hits, misses, and retraces
    are counted (`cache_stats()` — surfaced by bench.py's
    `dispatch_overhead` phase). A *retrace* is a miss for a
    function that already has a cached executable (shape/dtype/treedef
    drift): the guard warns by default and raises with
    ``on_retrace="error"`` — the silent-retrace failure mode the
    raylint ``jit-cache-stability`` check flags statically.

``fold_steps``
    The opt-in ``steps_per_call`` wrapper: folds K optimizer steps into
    ONE dispatch with a ``lax.scan`` over prefetched on-device batches
    (leading [K, ...] axis) and a donated carry, so XLA updates the
    parameter buffers in place and the fixed per-dispatch overhead is
    amortized K-fold. This is the Pathways-style dispatch-amortization
    move: the driver submits one program per K steps instead of K.

The single-controller analogy to the compiled-DAG channel plane
(ray_tpu/dag.py) is deliberate: both turn per-step driver work into a
constant-size doorbell on a pre-built execution plan.
"""

from __future__ import annotations

import functools
import logging
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
from jax import lax

from ray_tpu._private.accelerators import configure_compile_cache
from ray_tpu.parallel.sharding import tracing_for
from ray_tpu.util import metrics as _metrics
from ray_tpu.util import step_profiler as _sp
from ray_tpu.util import tracing as _tracing

logger = logging.getLogger(__name__)


class RetraceError(RuntimeError):
    """A compiled_step function was called with a new abstract signature
    while ``on_retrace="error"`` (shape/dtype/treedef drift would
    silently recompile every step)."""


@dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    retraces: int = 0
    # the time spent in lower()+compile() is the `compiled_step.lower` phase's
    # (`ExecutableCache.phases`); where it went, stage by stage, is on the
    # lowering's row of `lowerings` and in the start-up ledger (file's end)

    def as_dict(self) -> Dict[str, int]:
        return {"hits": self.hits, "misses": self.misses,
                "retraces": self.retraces}


def _sharding_key(sharding):
    """Placement part of a leaf's key. A NamedSharding is keyed by what it
    places, not how it prints: trailing Nones of a spec say nothing
    (`P("x", None)` and `P("x")` are one layout) and a jitted step hands
    back either form, which read as a retrace on the second step."""
    spec = getattr(sharding, "spec", None)
    if spec is None:
        return None if sharding is None else repr(sharding)
    axes = list(spec)
    while axes and axes[-1] is None:
        axes.pop()
    return (repr(sharding.mesh), tuple(axes), sharding.memory_kind)


def _leaf_key(leaf: Any):
    """Abstract (aval) key for one pytree leaf: shape+dtype+sharding for
    arrays, value identity for hashable Python scalars."""
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is not None and dtype is not None:
        return ("aval", tuple(shape), str(dtype),
                _sharding_key(getattr(leaf, "sharding", None)))
    # non-array leaf (python int/float/bool/None): its VALUE is baked
    # into the trace as a weak-typed constant, so it is part of the key
    return ("const", type(leaf).__name__, repr(leaf))


def _abstract_key(args: tuple, kwargs: dict):
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    return treedef, tuple(_leaf_key(leaf) for leaf in leaves)


def _mesh_key(mesh) -> Optional[tuple]:
    if mesh is None:
        return None
    return (tuple(mesh.shape.items()),
            tuple(str(d) for d in mesh.devices.flat))


class ExecutableCache:
    """Process-wide cache of AOT-compiled executables.

    Key: (function identity, arg treedefs/avals, mesh, donate/static
    config). Function identity is ``id(fn)`` paired with a strong
    reference to ``fn`` held by the entry — an id can therefore never
    be recycled into a false hit while its entry is alive.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: Dict[tuple, Any] = {}
        self._fn_signatures: Dict[tuple, set] = {}
        self.stats = CacheStats()
        # one row a lowering, in order: the function, and how many of the
        # model's `sharding.logical_constraint` calls became a constraint
        # or passed through (no mesh): "annotated but dead" shows here
        self.lowerings: List[Dict[str, Any]] = []
        # `cache_lookup`: every lookup's own time (the key, the probe),
        # without the compile nested in it on a miss
        self.phases = _tracing.PhaseTable(
            ("cache_lookup", "compiled_step.lower"))

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._fn_signatures.clear()
            self.stats = CacheStats()
            self.lowerings = []
        self.phases.clear()

    def size(self) -> int:
        with self._lock:
            return len(self._entries)

    def lookup(self, fn: Callable, args: tuple, kwargs: dict, *,
               donate_argnums: Tuple[int, ...] = (),
               static_argnums: Tuple[int, ...] = (),
               mesh=None, on_retrace: str = "warn"):
        """Return the compiled executable for this abstract call
        signature, lowering+compiling on first use."""
        with self.phases.phase("cache_lookup"):
            return self._lookup(fn, args, kwargs, donate_argnums,
                                static_argnums, mesh, on_retrace)

    def _lookup(self, fn, args, kwargs, donate_argnums, static_argnums,
                mesh, on_retrace):
        treedef, avals = _abstract_key(args, kwargs)
        fn_key = (id(fn), getattr(fn, "__qualname__", None))
        key = (fn_key, treedef, avals, _mesh_key(mesh),
               tuple(donate_argnums), tuple(static_argnums))
        sig = (treedef, avals)
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self.stats.hits += 1
                return entry[1]
            self.stats.misses += 1
            prior = self._fn_signatures.setdefault(fn_key, set())
            retraced = bool(prior) and sig not in prior
            if retraced:
                self.stats.retraces += 1
            prior.add(sig)
        if retraced:
            name = getattr(fn, "__name__", repr(fn))
            msg = (f"compiled_step retrace: {name} called with a new "
                   f"abstract signature (shape/dtype/structure changed) "
                   f"— every such change compiles a fresh executable")
            if on_retrace == "error":
                raise RetraceError(msg)
            logger.warning(msg)
        # in-process users too: executables go to the placed persistent cache
        configure_compile_cache()
        fn_name = _PROGRAM_NAMES.get(fn) or getattr(fn, "__name__", "?")
        with self.phases.phase("compiled_step.lower", attrs={
                "fn": fn_name, "retrace": retraced}) as lowering, \
                tracing_for(mesh) as constraints, \
                _lowering_programs() as programs:
            compiled = jax.jit(
                fn, donate_argnums=donate_argnums,
                static_argnums=static_argnums,
            ).lower(*args, **kwargs).compile()
        program = _lowered_program(fn_name, lowering, programs)
        with self._lock:
            # keep fn alive alongside its executable (id-key safety)
            self._entries[key] = (fn, compiled)
            self.lowerings.append({
                **program,
                "activation_constraints": constraints.emitted,
                "activation_constraints_skipped": constraints.skipped})
        return compiled


_GLOBAL_CACHE = ExecutableCache()


def global_cache() -> ExecutableCache:
    return _GLOBAL_CACHE


def cache_stats() -> Dict[str, Any]:
    """Process-wide executable-cache counters (bench `dispatch_overhead`
    and the /metrics scrape read these): hits / misses / retraces /
    entries / cumulative lowering ms (the `compiled_step.lower` phase),
    what the lookups themselves cost (`lookup_ms` over `lookups` calls,
    compiles not included), and the model's activation constraints:
    `activation_constraints` emitted and `activation_constraints_skipped`
    passed through, summed and, under `lowerings`, a row for each lowering
    with its `program` row's times. `programs` counts every program the
    process started, through `compiled_step` or any other door of jax, with
    where their starts went (`program_*_ms`) and how many the persistent
    cache held (`persistent_hits`) or did not."""
    stats = _GLOBAL_CACHE.stats.as_dict()
    stats["entries"] = _GLOBAL_CACHE.size()
    stats["lowering_ms"] = round(
        _GLOBAL_CACHE.phases.ms("compiled_step.lower"), 3)
    stats["lowerings"] = list(_GLOBAL_CACHE.lowerings)
    with _totals_lock:
        totals = dict(_totals)
    for key in ("programs", "persistent_hits", "persistent_misses"):
        stats[key] = totals[key]
    for stage in ("trace", "lower", "load", "compile"):
        stats[f"program_{stage}_ms"] = round(totals[f"{stage}_s"] * 1e3, 3)
    for count in ("activation_constraints", "activation_constraints_skipped"):
        stats[count] = sum(row[count] for row in stats["lowerings"])
    stats["lookup_ms"] = round(_GLOBAL_CACHE.phases.ms("cache_lookup"), 3)
    stats["lookups"] = _GLOBAL_CACHE.phases.count("cache_lookup")
    return stats


# The lines of `lookup`, of `_lookup`'s lowering and of `compiled_step`'s
# wrapper are frames of every program's trace: their positions enter a Pallas
# kernel's locations and so the persistent cache's key (PERF.md §7). What
# this file gains goes to its end, and edits above leave those lines in place.


def compiled_step(fn: Optional[Callable] = None, *,
                  donate_argnums: Tuple[int, ...] = (),
                  static_argnums: Tuple[int, ...] = (),
                  mesh=None, cache: Optional[ExecutableCache] = None,
                  on_retrace: str = "warn",
                  name: Optional[str] = None) -> Callable:
    """Decorator/wrapper: dispatch ``fn`` through the AOT executable
    cache.

    The first call with a given abstract signature lowers and compiles
    once; later calls invoke the cached executable with no jit-layer
    dispatch. ``donate_argnums`` marks carries (params/opt-state) whose
    buffers XLA reuses in place. ``mesh`` is the mesh the step is traced
    for: part of the key, and the ``sharding.tracing_for`` scope of the
    lowering, so ``logical_constraint`` in the model resolves against it;
    None keeps the scope the caller is in, if any. ``name``
    is what the program's rows call it (`cache_stats()["lowerings"]`, the
    start-up ledger's `program` rows): the function's own name otherwise.
    The wrapper exposes ``.cache`` and ``.stats`` for tests and bench
    counters.
    """
    if fn is None:
        return functools.partial(
            compiled_step, donate_argnums=donate_argnums,
            static_argnums=static_argnums, mesh=mesh, cache=cache,
            on_retrace=on_retrace, name=name)
    use_cache = cache if cache is not None else _GLOBAL_CACHE
    if name is not None:
        _PROGRAM_NAMES[fn] = name
    fn_name = getattr(fn, "__name__", "step")

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        # flight recorder: sampled host-dispatch timing (1 in N calls —
        # the unsampled cost is one integer increment, which is what
        # keeps the observability_overhead bench phase under 1% on the
        # sub-2 ms dispatch path)
        if _sp.enabled() and _sp.count_dispatch():
            t0 = time.perf_counter()
            compiled = use_cache.lookup(
                fn, args, kwargs, donate_argnums=donate_argnums,
                static_argnums=static_argnums, mesh=mesh,
                on_retrace=on_retrace)
            out = compiled(*args, **kwargs)
            _sp.record_dispatch(fn_name,
                                (time.perf_counter() - t0) * 1e3)
            return out
        compiled = use_cache.lookup(
            fn, args, kwargs, donate_argnums=donate_argnums,
            static_argnums=static_argnums, mesh=mesh,
            on_retrace=on_retrace)
        return compiled(*args, **kwargs)

    wrapper.cache = use_cache
    wrapper.stats = use_cache.stats
    wrapper.__wrapped__ = fn
    return wrapper


def fold_steps(step_fn: Callable, steps_per_call: int, *,
               donate_carry: bool = True,
               mesh=None, cache: Optional[ExecutableCache] = None,
               on_retrace: str = "warn") -> Callable:
    """Fold K optimizer steps into one dispatch (opt-in
    ``steps_per_call``).

    ``step_fn(carry, batch) -> (carry, aux)`` becomes
    ``multi(carry, batches) -> (carry, auxes)`` where ``batches`` holds
    K prefetched on-device batches stacked on a leading axis and
    ``auxes`` stacks each step's aux ([K, ...]). The K-step body is one
    ``lax.scan`` inside one cached executable with the carry donated —
    driver cost per K steps is a single dispatch. The staged body is
    subject to raylint's ``jit-purity`` gate: host side effects inside
    ``step_fn`` are baked in at trace time, not executed per step.
    """
    if steps_per_call < 1:
        raise ValueError(f"steps_per_call must be >= 1, "
                         f"got {steps_per_call}")

    def multi_step(carry, batches):
        return lax.scan(step_fn, carry, batches,
                        length=steps_per_call)

    multi_step.__name__ = (
        f"fold_steps({getattr(step_fn, '__name__', 'step')}"
        f"x{steps_per_call})")
    multi_step.__qualname__ = multi_step.__name__
    wrapper = compiled_step(
        multi_step, donate_argnums=(0,) if donate_carry else (),
        mesh=mesh, cache=cache, on_retrace=on_retrace)
    wrapper.steps_per_call = steps_per_call
    return wrapper


def stack_batches(batches, device=None):
    """Stack an iterable of K same-shape batch pytrees into one
    [K, ...] pytree placed on device — the prefetched input block a
    `fold_steps` wrapper consumes."""
    import jax.numpy as jnp

    batches = list(batches)
    if not batches:
        raise ValueError("stack_batches needs at least one batch")
    stacked = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *batches)
    if device is not None:
        stacked = jax.device_put(stacked, device)
    return stacked


# -- a row for every program the process starts ---------------------------

# imported here and not above: see the note at `compiled_step`
import contextlib  # noqa: E402
import weakref  # noqa: E402

#
# jax says through `jax.monitoring` what a program's start was spent on, and
# only on a path that traces or compiles: a call of a cached executable
# records nothing, so a window of steady steps calls no listener. Read off
# jax 0.9.0 (`pjit.py`, `interpreters/pxla.py`, `compiler.py`), each on the
# thread that does the work:
#   jaxpr_trace_duration          tracing the function to a jaxpr; a jitted
#       function called inside it nests a span of its own, and an eager op
#       on concrete values there starts a whole program inside the span
#   jaxpr_to_mlir_module_duration  lowering the jaxpr to an MLIR module
#   backend_compile_duration      ALL of `compile_or_get_cached`: hashing the
#       module and its constants into the persistent cache's key, the probe,
#       and then either the retrieval (read, decompress, deserialize onto
#       the device) or the XLA compile and the entry's write. It CONTAINS
#       `cache_retrieval_time_sec`, which is recorded on a hit only.
# A `program` row runs from its trace's beginning to the backend span's end.
# `trace_s` is the self time of its outermost trace (programs started inside
# it taken out), `load_s` the retrieval on a hit, `compile_s` the backend
# span on a miss, `backend_s` the backend span either way: on a hit what it
# holds beyond `load_s` is the key.

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_USE_CACHE_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
# a stage is its program's when the next one began within this of its end:
# between them lie a few jaxpr passes, never seconds
_STAGE_GAP_NS = 5_000_000_000
_CLOCK_SLACK_NS = 5_000_000     # jax's spans are on the wall clock


class _ThreadPrograms(threading.local):
    """What jax has said on this thread and no program row holds yet."""

    def __init__(self):
        self.traces: List[Tuple[int, int]] = []
        self.lowers: List[Tuple[int, int]] = []
        self.consulted = self.hit = False
        self.load_s = 0.0
        # the thread's last rows that no later row encloses: taken out of
        # an enclosing trace's time
        self.done: List[Tuple[int, int]] = []
        # inside `compiled_step`'s lowering: the candidate rows, of which
        # the last is the step's own
        self.lowering: Optional[List[tuple]] = None


_programs = _ThreadPrograms()
# what `compiled_step(fn, name=)` was told to call a function's programs
_PROGRAM_NAMES: "weakref.WeakKeyDictionary[Callable, str]" = \
    weakref.WeakKeyDictionary()
_totals_lock = threading.Lock()
_totals = {"programs": 0, "trace_s": 0.0, "lower_s": 0.0, "load_s": 0.0,
           "compile_s": 0.0, "persistent_hits": 0, "persistent_misses": 0}
listener_calls = 0      # all three listeners; a steady window adds none


def _last_before(spans: List[Tuple[int, int]], before_ns: int):
    for span in reversed(spans):
        if span[1] <= before_ns + _CLOCK_SLACK_NS:
            return span if before_ns - span[1] <= _STAGE_GAP_NS else None
    return None


def _on_span(event: str, start: float, end: float, **kw) -> None:
    global listener_calls
    listener_calls += 1
    if event not in (_TRACE_EVENT, _LOWER_EVENT, _BACKEND_EVENT):
        return
    st = _programs
    end_ns = time.perf_counter_ns()
    begin_ns = end_ns - int((end - start) * 1e9)
    if event == _TRACE_EVENT:
        st.traces.append((begin_ns, end_ns))
    elif event == _LOWER_EVENT:
        st.lowers.append((begin_ns, end_ns))
    else:
        _program_compiled(st, str(kw.get("fun_name", "?")), begin_ns, end_ns)


def _on_duration(event: str, duration: float, **kw) -> None:
    global listener_calls
    listener_calls += 1
    if event == _RETRIEVAL_EVENT:
        _programs.load_s = duration


def _on_event(event: str, **kw) -> None:
    global listener_calls
    listener_calls += 1
    if event == _USE_CACHE_EVENT:
        _programs.consulted = True
    elif event == _HIT_EVENT:
        _programs.hit = True


def _program_compiled(st: _ThreadPrograms, fn: str, backend_begin: int,
                      backend_end: int) -> None:
    """The backend span ends a program: its row is made of the stages that
    led up to it on this thread."""
    lower = _last_before(st.lowers, backend_begin)
    trace = _last_before(st.traces, lower[0] if lower else backend_begin)
    begin_ns = (trace or lower or (backend_begin,))[0]
    trace_ns = 0
    if trace is not None:
        inside = sum(min(e, trace[1]) - max(b, trace[0])
                     for b, e in st.done if e > trace[0] and b < trace[1])
        trace_ns = max(0, trace[1] - trace[0] - inside)
    backend_s = (backend_end - backend_begin) / 1e9
    hit = st.hit if st.consulted else None
    attrs = {"fn": fn, "door": "jit", "trace_s": trace_ns / 1e9,
             "lower_s": (lower[1] - lower[0]) / 1e9 if lower else 0.0,
             "load_s": min(st.load_s, backend_s) if hit else 0.0,
             "compile_s": 0.0 if hit else backend_s,
             "backend_s": backend_s, "persistent_hit": hit}
    st.traces.clear()
    st.lowers.clear()
    st.consulted = st.hit = False
    st.load_s = 0.0
    st.done = [d for d in st.done[-63:] if d[0] < begin_ns]
    st.done.append((begin_ns, backend_end))
    if st.lowering is None:
        _record_program(begin_ns, backend_end, attrs)
    else:
        # an eager program inside a step's trace is a row of its own; the
        # last candidate is the step's, and waits for the lowering's end
        for earlier in st.lowering:
            _record_program(*earlier)
        st.lowering[:] = [(begin_ns, backend_end, attrs)]


def _record_program(begin_ns: int, end_ns: int, attrs: dict) -> None:
    with _totals_lock:
        _totals["programs"] += 1
        for key in ("trace_s", "lower_s", "load_s", "compile_s"):
            _totals[key] += attrs[key]
        if attrs["persistent_hit"] is not None:
            _totals["persistent_hits" if attrs["persistent_hit"]
                    else "persistent_misses"] += 1
    _tracing.startup_row("program", begin_ns, end_ns, attrs, flush=True)


@contextlib.contextmanager
def _lowering_programs():
    """Around `compiled_step`'s lowering: yields the candidate rows of the
    programs jax compiles inside it, of which the last is the step's own."""
    st = _programs
    outer, st.lowering = st.lowering, []
    try:
        yield st.lowering
    finally:
        st.lowering = outer


def _lowered_program(name: str, lowering, candidates: List[tuple]) -> dict:
    """The row of a `compiled_step` lowering: the `compiled_step.lower`
    phase, with the times of the program jax compiled last inside it (none
    where jax still held the executable and said nothing)."""
    if candidates:
        attrs = candidates[-1][2]
    else:
        attrs = dict.fromkeys(("trace_s", "lower_s", "load_s", "compile_s",
                               "backend_s"), 0.0)
        attrs["persistent_hit"] = None
        _programs.traces.clear()
        _programs.lowers.clear()
    attrs = dict(attrs, fn=name, door="compiled_step")
    _record_program(lowering.begin_ns, lowering.end_ns, attrs)
    return attrs


jax.monitoring.register_event_time_span_listener(_on_span)
jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


def _metrics_text() -> str:
    """Scrape-time exposition of the global executable cache (flight-
    recorder plane: one /metrics scrape sees the dispatch cache state)."""
    s = cache_stats()
    return (
        "# TYPE compile_cache_hits_total counter\n"
        f"compile_cache_hits_total {s['hits']}\n"
        f"compile_cache_misses_total {s['misses']}\n"
        f"compile_cache_retraces_total {s['retraces']}\n"
        "# TYPE compile_cache_entries gauge\n"
        f"compile_cache_entries {s['entries']}\n"
        "# TYPE compile_cache_lowering_ms_total counter\n"
        f"compile_cache_lowering_ms_total {s['lowering_ms']}\n"
        "# TYPE compile_cache_lookup_ms_total counter\n"
        f"compile_cache_lookup_ms_total {s['lookup_ms']}\n"
        f"compile_cache_lookups_total {s['lookups']}\n"
        "# TYPE compile_cache_programs_total counter\n"
        f"compile_cache_programs_total {s['programs']}\n"
        + "".join(
            f'compile_cache_program_ms_total{{stage="{stage}"}} '
            f"{s[f'program_{stage}_ms']}\n"
            for stage in ("trace", "lower", "load", "compile"))
        + f"compile_cache_persistent_hits_total {s['persistent_hits']}\n"
        f"compile_cache_persistent_misses_total {s['persistent_misses']}\n")


_metrics.DEFAULT_REGISTRY.register_callback("compile_cache", _metrics_text)
