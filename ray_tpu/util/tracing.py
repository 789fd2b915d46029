"""Distributed tracing: spans around task submit/execute with context
propagation through TaskSpec.

Reference: `python/ray/util/tracing/tracing_helper.py:326,450` — the
reference wraps every remote function/actor method in OpenTelemetry
spans and propagates the span context in task metadata so cross-process
traces stitch together. Same design here without the otel dependency:
spans are plain dicts written as JSONL per process (zero deps, zero
cost when disabled), trace/parent ids ride `TaskSpec.trace_ctx`, and
`collect()`/`to_chrome()` merge per-process shards into one
chrome://tracing view.

Enable with `RAY_TPU_TRACE=1` (optionally `RAY_TPU_TRACE_DIR=...`);
every process of the cluster inherits the env through the daemons. The
switch is read once at import (`refresh()` re-reads it; `ray_tpu.init()`
calls that).

Phases (`PhaseTable.phase`) are the spans of a hot loop: the engine's
pump, the train step, the executable cache. A phase always adds its
*self time* to a table its owner keeps, which is what `engine.metrics()`
and `parallel.cache_stats()` publish. Where jax is already imported it
is also a `jax.profiler.TraceAnnotation` named `rt/<name>`, so that any
profiler session (`device_trace`, or an operator's) holds the program's
spans in the `.xplane.pb`, on the clock of the device events. With
`RAY_TPU_TRACE=1` it is written to the JSONL shard like any `span`. This
module never imports jax itself while it is imported: daemons and drivers
that must not touch the chip import it.
"""

from __future__ import annotations

import contextlib
import contextvars
import json
import os
import sys
import tempfile
import threading
import time
import uuid
from typing import Any, Dict, Iterable, Iterator, List, Optional

_current: contextvars.ContextVar[Optional[dict]] = contextvars.ContextVar(
    "ray_tpu_trace_span", default=None)

_lock = threading.Lock()
_file = None


def _reset_writer() -> None:
    """Fork safety: a child inheriting the parent's cached handle would
    append its spans to the PARENT's pid-named shard (and interleave
    writes on a shared file offset). Daemons fork workers, so the cached
    handle is dropped in the child; the next span opens the child's own
    shard. Runs in the just-forked child, which is single-threaded —
    taking the fork-inherited lock here could deadlock on a holder that
    no longer exists in the child."""
    global _file
    _file = None  # raylint: disable=lock-discipline


if hasattr(os, "register_at_fork"):
    os.register_at_fork(after_in_child=_reset_writer)


def _env_enabled() -> bool:
    return os.environ.get("RAY_TPU_TRACE", "") in ("1", "true", "on")


_ENABLED = _env_enabled()


def enabled() -> bool:
    """Cached switch: an attribute read on every span and phase."""
    return _ENABLED


def refresh() -> None:
    """Re-read `RAY_TPU_TRACE` (tests and drivers set it after import)."""
    global _ENABLED
    _ENABLED = _env_enabled()


def trace_dir() -> str:
    return os.environ.get("RAY_TPU_TRACE_DIR", "/tmp/ray_tpu/traces")


def _writer():
    global _file
    if _file is None:
        with _lock:
            if _file is None:
                os.makedirs(trace_dir(), exist_ok=True)
                # opened once per process at the first span; per-span
                # appends are line-buffered local writes (µs-scale), so
                # span exits inside async executors stay loop-safe
                _file = open(  # raylint: disable=async-blocking
                    os.path.join(trace_dir(), f"trace-{os.getpid()}.jsonl"),
                    "a", buffering=1)  # line-buffered: crash-safe
    return _file


def _new_id() -> str:
    return uuid.uuid4().hex[:16]


@contextlib.contextmanager
def span(name: str, kind: str = "internal",
         parent: Optional[Dict[str, str]] = None,
         attrs: Optional[Dict[str, Any]] = None) -> Iterator[dict]:
    """Record one span; nests under the context-local current span
    unless an explicit cross-process `parent` ctx is given."""
    if not enabled():
        yield {}
        return
    cur = _current.get()
    if parent is None and cur is not None:
        parent = {"trace_id": cur["trace_id"], "span_id": cur["span_id"]}
    s = {
        "trace_id": (parent or {}).get("trace_id") or _new_id(),
        "span_id": _new_id(),
        "parent_id": (parent or {}).get("span_id"),
        "name": name,
        "kind": kind,
        "pid": os.getpid(),
        "start": time.time(),
        "attrs": dict(attrs or {}),
    }
    token = _current.set(s)
    try:
        yield s
    except Exception as e:
        s["attrs"]["error"] = type(e).__name__
        raise
    finally:
        _current.reset(token)
        s["end"] = time.time()
        try:
            _writer().write(json.dumps(s) + "\n")
        except OSError:  # tracing must never break the task path
            pass


def current_context() -> Optional[Dict[str, str]]:
    """Wire form of the current span (to stuff into a TaskSpec)."""
    cur = _current.get()
    if cur is None:
        return None
    return {"trace_id": cur["trace_id"], "span_id": cur["span_id"]}


@contextlib.contextmanager
def submit_span(task_name: str, task_type: str):
    """Producer-side span; yields the ctx dict to ship in the spec
    (None when tracing is off — zero wire overhead)."""
    if not enabled():
        yield None
        return
    with span(f"{task_name}.remote", kind="producer",
              attrs={"task_type": task_type}) as s:
        yield {"trace_id": s["trace_id"], "span_id": s["span_id"]}


@contextlib.contextmanager
def execute_span(spec) -> Iterator:
    """Consumer-side span parented on the submitter's ctx."""
    if not enabled():
        yield
        return
    parent = getattr(spec, "trace_ctx", None)
    with span(f"{spec.name}.execute", kind="consumer", parent=parent,
              attrs={"task_type": spec.task_type,
                     "task_id": spec.task_id.hex()}):
        yield


# -- phases --------------------------------------------------------------

_ANNOTATIONS = None  # (TraceAnnotation, StepTraceAnnotation) once jax is in


def _annotations():
    """jax's profiler annotations, only where the process has imported jax
    already: this module must not be what brings it in."""
    global _ANNOTATIONS
    if _ANNOTATIONS is None and "jax" in sys.modules:
        try:
            from jax.profiler import StepTraceAnnotation, TraceAnnotation
        except ImportError:  # another thread is still importing jax
            return None
        _ANNOTATIONS = (TraceAnnotation, StepTraceAnnotation)
    return _ANNOTATIONS


class _ThreadPhases:
    """One thread's open phases in one table, and the instant up to which
    its time has been charged."""

    __slots__ = ("stack", "mark")

    def __init__(self):
        self.stack: List["Phase"] = []
        self.mark = 0


class PhaseTable:
    """Self time and count by phase name, owned by whoever runs the phases
    (an engine, a step runner, an executable cache).

    Time is charged to the innermost open phase of the thread at every
    transition, so while a thread keeps one phase open, every nanosecond of
    it lies in exactly one name's self time and the names sum to the wall
    time. `names` are listed from the start at 0, so a reader finds every
    key before the phase first ran."""

    def __init__(self, names: Iterable[str] = ()):
        self._lock = threading.Lock()
        self._ns: Dict[str, int] = {n: 0 for n in names}
        self._counts: Dict[str, int] = {n: 0 for n in names}
        self._total = 0
        self._tls = threading.local()
        # thread ident -> its open phases, for the live remainder
        self._open: Dict[int, _ThreadPhases] = {}

    def phase(self, name: str, *, step: Optional[int] = None,
              req_id: Optional[str] = None, kind: str = "internal",
              attrs: Optional[Dict[str, Any]] = None) -> "Phase":
        """A context manager. `step` makes it a step of the profiler's
        overview (`StepTraceAnnotation`); `req_id` marks per-request work
        and is inherited by the phases nested in it; `kind` and `attrs` go
        to the JSONL span as `span()` takes them."""
        return Phase(self, name, step, req_id, kind, attrs)

    def _thread(self) -> _ThreadPhases:
        try:
            return self._tls.phases
        except AttributeError:
            st = self._tls.phases = _ThreadPhases()
            return st

    def _charge(self, name: str, ns: int, count: int = 0) -> None:
        with self._lock:
            self._ns[name] = self._ns.get(name, 0) + ns
            self._total += ns
            if count:
                self._counts[name] = self._counts.get(name, 0) + count

    def in_phase(self) -> bool:
        """Whether the calling thread has a phase of this table open."""
        return bool(self._thread().stack)

    def total_ns(self) -> int:
        """Everything charged so far, the calling thread's open phase
        brought up to now: on a thread that keeps a phase open, the
        difference of two readings is the wall time between them."""
        st = self._thread()
        if st.stack:
            now = time.perf_counter_ns()
            self._charge(st.stack[-1].name, now - st.mark)
            st.mark = now
        return self._total

    def snapshot_ns(self) -> Dict[str, int]:
        """Self time by name, with what each thread's open phase has run
        up since its last transition (read from outside those threads, so
        a transition under way can misplace a few microseconds)."""
        with self._lock:
            out = dict(self._ns)
        now = time.perf_counter_ns()
        for st in list(self._open.values()):
            try:
                name, mark = st.stack[-1].name, st.mark
            except IndexError:
                continue
            out[name] = out.get(name, 0) + max(0, now - mark)
        return out

    def ms(self, name: str) -> float:
        with self._lock:
            return self._ns.get(name, 0) / 1e6

    def count(self, name: str) -> int:
        with self._lock:
            return self._counts.get(name, 0)

    def clear(self) -> None:
        with self._lock:
            for name in self._ns:
                self._ns[name] = self._counts[name] = 0
            self._total = 0


class Phase:
    """One span of a `PhaseTable`; see `PhaseTable.phase`. After it ends
    `ns` holds its whole duration, nested phases included."""

    __slots__ = ("table", "name", "step", "req_id", "kind", "attrs", "ns",
                 "_t0", "_annotation", "_span")

    def __init__(self, table, name, step, req_id, kind, attrs):
        self.table = table
        self.name = name
        self.step = step
        self.req_id = req_id
        self.kind = kind
        self.attrs = attrs
        self.ns = 0
        self._annotation = self._span = None

    def elapsed_ns(self) -> int:
        """Duration so far of a phase that is still open."""
        return self.ns or time.perf_counter_ns() - self._t0

    @property
    def begin_ns(self) -> int:
        """`perf_counter_ns` when the phase opened: what its owner stamps
        a transition with, at no clock read of its own."""
        return self._t0

    @property
    def end_ns(self) -> int:
        """`perf_counter_ns` when the phase ended (its begin while it is
        still open)."""
        return self._t0 + self.ns

    def __enter__(self) -> "Phase":
        table = self.table
        st = table._thread()
        now = time.perf_counter_ns()
        if st.stack:
            outer = st.stack[-1]
            table._charge(outer.name, now - st.mark)
            if self.req_id is None:
                self.req_id = outer.req_id
        else:
            table._open[threading.get_ident()] = st
        st.stack.append(self)
        st.mark = self._t0 = now
        annotations = _annotations()
        if annotations is None and not _ENABLED:
            return self
        tags = dict(self.attrs) if self.attrs else {}
        if self.req_id is not None:
            tags.setdefault("req_id", self.req_id)
        if annotations is not None:
            if self.step is not None:
                ann = annotations[1](f"rt/{self.name}", step_num=self.step,
                                     **tags)
            else:
                ann = annotations[0](f"rt/{self.name}", **tags)
            ann.__enter__()
            self._annotation = ann
        if _ENABLED:
            self._span = span(self.name, kind=self.kind, attrs=tags)
            self._span.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        if self._span is not None:
            self._span.__exit__(*exc)
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        table = self.table
        st = table._thread()
        now = time.perf_counter_ns()
        table._charge(self.name, now - st.mark, 1)
        st.mark = now
        st.stack.pop()
        if not st.stack:
            table._open.pop(threading.get_ident(), None)
        self.ns = now - self._t0


class TimedLock:
    """`lock`, with a thread's contended acquisitions as the `lock_wait`
    phase of `table`. An uncontended acquire reads no clock; a thread that
    has no phase of the table open (a caller of `metrics()`, not the pump)
    is not charged."""

    __slots__ = ("_table", "_lock")

    def __init__(self, table: PhaseTable, lock):
        self._table = table
        self._lock = lock

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        if self._lock.acquire(False):
            return True
        if not blocking:
            return False
        if not self._table.in_phase():
            return self._lock.acquire(True, timeout)
        with self._table.phase("lock_wait"):
            return self._lock.acquire(True, timeout)

    def release(self) -> None:
        self._lock.release()

    def locked(self) -> bool:
        return self._lock.locked()

    def __enter__(self) -> "TimedLock":
        self.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self._lock.release()


@contextlib.contextmanager
def device_trace(log_dir: Optional[str] = None) -> Iterator[str]:
    """A `jax.profiler` session around the block, in the process that holds
    the device; yields the directory the `.xplane.pb` is written under
    (`<dir>/plugins/profile/<time>/`). The `rt/` phases that run meanwhile
    are host events of the same file. The session is the light one: no
    Python frames (with jax's default tracer on, stopping a few seconds of
    a serving replica held it 15-32 s and cut the device's line short),
    the host tracer at 1, which keeps the `rt/` annotations, no HLO proto.
    Stopping still costs about 0.1 ms a device event: keep the block to a
    second of a busy device."""
    import jax

    log_dir = log_dir or tempfile.mkdtemp(prefix="rt_device_trace_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    options.enable_hlo_proto = False
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        yield log_dir
    finally:
        jax.profiler.stop_trace()


# -- aggregation ---------------------------------------------------------

def collect(path: Optional[str] = None) -> List[dict]:
    """Merge every process's span shard (sorted by start time)."""
    import glob

    spans = []
    for fn in sorted(glob.glob(os.path.join(path or trace_dir(),
                                            "trace-*.jsonl"))):
        with open(fn) as f:
            for line in f:
                line = line.strip()
                if line:
                    spans.append(json.loads(line))
    spans.sort(key=lambda s: s["start"])
    return spans


def to_chrome(spans: List[dict], filename: Optional[str] = None) -> list:
    """Chrome-trace view: one complete event per span, rows = processes,
    flow arrows producer → consumer (chrome 's'/'f' flow events).

    Two arrow mechanisms: parent/span-id links (the submit→execute task
    path, where the child ships the parent ctx in its TaskSpec), and
    explicit ``flow_id`` attrs for planes where no ctx can ride the
    wire — a channel frame has a fixed raw header, so the producer and
    consumer spans both carry ``flow_id="<channel>:<seq>"`` and the
    arrow is stitched here, at merge time, across processes."""
    events = []
    for s in spans:
        events.append({
            "name": s["name"], "cat": s["kind"], "ph": "X",
            "ts": s["start"] * 1e6,
            "dur": max(1.0, (s.get("end", s["start"]) - s["start"]) * 1e6),
            "pid": s["pid"], "tid": s["trace_id"][:8],
            "args": {k: str(v) for k, v in s.get("attrs", {}).items()},
        })
        if s.get("parent_id"):
            # flow arrow from the parent span's row
            events.append({
                "name": "flow", "cat": "trace", "ph": "f", "bp": "e",
                "id": s["parent_id"], "ts": s["start"] * 1e6,
                "pid": s["pid"], "tid": s["trace_id"][:8],
            })
        if s["kind"] == "producer":
            events.append({
                "name": "flow", "cat": "trace", "ph": "s",
                "id": s["span_id"],
                "ts": s["start"] * 1e6,
                "pid": s["pid"], "tid": s["trace_id"][:8],
            })
        flow_id = s.get("attrs", {}).get("flow_id")
        if flow_id:
            events.append({
                "name": "hop", "cat": "channel",
                "ph": "s" if s["kind"] == "producer" else "f",
                "bp": "e", "id": str(flow_id),
                "ts": s["start"] * 1e6,
                "pid": s["pid"], "tid": s["trace_id"][:8],
            })
    if filename:
        with open(filename, "w") as f:
            json.dump(events, f)
    return events
