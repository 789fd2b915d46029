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
    # total wall time spent in lower()+compile() — not part of as_dict()
    # (counter equality in tests), surfaced via cache_stats()/metrics
    lowering_ms: float = 0.0

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
        # in-process users (no worker_main): executables that outlive
        # this cache's process go to the placed persistent cache
        configure_compile_cache()
        fn_name = getattr(fn, "__name__", "?")
        with self.phases.phase("compiled_step.lower", attrs={
                "fn": fn_name, "retrace": retraced}) as lowering, \
                tracing_for(mesh) as constraints:
            compiled = jax.jit(
                fn, donate_argnums=donate_argnums,
                static_argnums=static_argnums,
            ).lower(*args, **kwargs).compile()
        with self._lock:
            # keep fn alive alongside its executable (id-key safety)
            self._entries[key] = (fn, compiled)
            self.stats.lowering_ms += lowering.ns / 1e6
            self.lowerings.append({
                "fn": fn_name,
                "activation_constraints": constraints.emitted,
                "activation_constraints_skipped": constraints.skipped})
        return compiled


_GLOBAL_CACHE = ExecutableCache()


def global_cache() -> ExecutableCache:
    return _GLOBAL_CACHE


def cache_stats() -> Dict[str, Any]:
    """Process-wide executable-cache counters (bench `dispatch_overhead`
    and the /metrics scrape read these): hits / misses / retraces /
    entries / cumulative lowering ms, what the lookups themselves cost
    (`lookup_ms` over `lookups` calls, compiles not included), and the
    model's activation constraints: `activation_constraints` emitted and
    `activation_constraints_skipped` passed through, summed and, under
    `lowerings`, a row for each lowering."""
    stats = _GLOBAL_CACHE.stats.as_dict()
    stats["entries"] = _GLOBAL_CACHE.size()
    stats["lowering_ms"] = round(_GLOBAL_CACHE.stats.lowering_ms, 3)
    stats["lowerings"] = list(_GLOBAL_CACHE.lowerings)
    for count in ("activation_constraints", "activation_constraints_skipped"):
        stats[count] = sum(row[count] for row in stats["lowerings"])
    stats["lookup_ms"] = round(_GLOBAL_CACHE.phases.ms("cache_lookup"), 3)
    stats["lookups"] = _GLOBAL_CACHE.phases.count("cache_lookup")
    return stats


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
        f"compile_cache_lookups_total {s['lookups']}\n")


_metrics.DEFAULT_REGISTRY.register_callback("compile_cache", _metrics_text)


def compiled_step(fn: Optional[Callable] = None, *,
                  donate_argnums: Tuple[int, ...] = (),
                  static_argnums: Tuple[int, ...] = (),
                  mesh=None, cache: Optional[ExecutableCache] = None,
                  on_retrace: str = "warn") -> Callable:
    """Decorator/wrapper: dispatch ``fn`` through the AOT executable
    cache.

    The first call with a given abstract signature lowers and compiles
    once; later calls invoke the cached executable with no jit-layer
    dispatch. ``donate_argnums`` marks carries (params/opt-state) whose
    buffers XLA reuses in place. ``mesh`` is the mesh the step is traced
    for: part of the key, and the ``sharding.tracing_for`` scope of the
    lowering, so ``logical_constraint`` in the model resolves against it;
    None keeps the scope the caller is in, if any. The
    wrapper exposes ``.cache`` and ``.stats`` for tests and bench
    counters.
    """
    if fn is None:
        return functools.partial(
            compiled_step, donate_argnums=donate_argnums,
            static_argnums=static_argnums, mesh=mesh, cache=cache,
            on_retrace=on_retrace)
    use_cache = cache if cache is not None else _GLOBAL_CACHE

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
