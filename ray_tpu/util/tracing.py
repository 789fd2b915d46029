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

Start-up rows (`startup_stage`, `startup_mark`, `startup_row`) are the
ledger of what a process did before it served or trained: a span-shaped
dict that also carries `begin_ns` / `end_ns` of `time.perf_counter_ns()`,
the phases' clock and one clock for every process of a host. They are
recorded whether `RAY_TPU_TRACE` is set or not, since a process writes
some dozens of them and none inside a loop that runs a step: kept in a
bounded list (`startup_rows()`) and appended to
`<session_dir>/logs/startup-<pid>.jsonl`, which `collect_startup` merges.
"""

from __future__ import annotations

import atexit
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

_FLUSH_ROWS = 512
_FLUSH_SECONDS = 1.0


class _Shard:
    """One JSONL file of this process, written in blocks: a row joins a
    buffer that is serialized and goes to the file when it holds
    `_FLUSH_ROWS`, a second after its first row (on a timer's thread),
    when a caller asks (`flush=True`: the rows a reader waits for) and at
    exit, so a crash loses a second at most and a hot loop's span costs
    an append. `path()` names the file when the first row comes; while it
    returns None the rows wait."""

    def __init__(self, path):
        self._path = path
        self._lock = threading.Lock()
        self._rows: List[dict] = []
        self._file = None
        self._named: Optional[str] = None
        self._timer: Optional[threading.Timer] = None

    def write(self, row: dict, flush: bool = False) -> None:
        with self._lock:
            if self._named is None:
                self._named = self._path()
            self._rows.append(row)
            if flush or len(self._rows) >= _FLUSH_ROWS:
                self._flush_locked()
            elif self._timer is None:
                self._timer = threading.Timer(_FLUSH_SECONDS, self.flush)
                self._timer.daemon = True
                self._timer.start()

    def flush(self) -> None:
        with self._lock:
            self._flush_locked()

    def _flush_locked(self) -> None:
        timer, self._timer = self._timer, None
        if timer is not None:
            timer.cancel()
        if not self._rows:
            return
        try:
            if self._file is None:
                path = self._named = self._named or self._path()
                if path is None:
                    return
                os.makedirs(os.path.dirname(path), exist_ok=True)
                # opened once a process at the first block; a block is a
                # local write a second at most, so span exits inside
                # async executors stay loop-safe
                self._file = open(  # raylint: disable=async-blocking
                    path, "a")
            self._file.write("".join(
                json.dumps(row) + "\n" for row in self._rows))
            self._file.flush()
        except (OSError, TypeError, ValueError):
            pass    # tracing must never break the task path
        self._rows = []

    def close(self) -> None:
        """What is buffered goes out and the file is closed: the next
        line opens whatever `path()` names then."""
        with self._lock:
            self._flush_locked()
            if self._file is not None:
                self._file.close()
            self._file = self._named = None

    def discard(self) -> None:
        """Drops the rows that still wait for a file."""
        with self._lock:
            self._rows = []

    def forget(self) -> None:
        """Fork safety: a child inheriting the parent's handle and rows
        would append them to the PARENT's pid-named shard (and interleave
        writes on a shared file offset). Daemons fork workers, so both are
        dropped in the child; its next line opens the child's own shard.
        Runs in the just-forked child, which is single-threaded -- taking
        the fork-inherited lock here could deadlock on a holder that no
        longer exists in the child."""
        self.__init__(self._path)


_trace_shard = _Shard(
    lambda: os.path.join(trace_dir(), f"trace-{os.getpid()}.jsonl"))
_startup_shard = _Shard(lambda: _startup_dir and os.path.join(
    _startup_dir, "logs", f"startup-{os.getpid()}.jsonl"))
atexit.register(_trace_shard.flush)
atexit.register(_startup_shard.flush)


def _reset_writer() -> None:
    """Closes this process's span shard (tests move `RAY_TPU_TRACE_DIR`
    between runs): what was buffered is written first."""
    _trace_shard.close()


def _after_fork() -> None:
    # runs in the just-forked child, which is single-threaded
    global _startup_rows, _startup_dropped
    _trace_shard.forget()
    _startup_shard.forget()
    _startup_rows = []
    _startup_dropped = 0  # raylint: disable=lock-discipline


if hasattr(os, "register_at_fork"):
    os.register_at_fork(after_in_child=_after_fork)


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
        _trace_shard.write(s)


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


# -- the start-up ledger -------------------------------------------------

STARTUP_CAP = 4096      # rows a process keeps and writes; more are counted
_startup_lock = threading.Lock()
_startup_rows: List[dict] = []
_startup_dropped = 0
_startup_dir: Optional[str] = None


def set_startup_dir(session_dir: Optional[str]) -> None:
    """The session whose `logs/` this process's start-up shard lies in
    (a worker's `--session-dir`, the cluster a driver starts). Rows made
    before it is known wait for it; rows of a session this process had
    before are that session's, and leave the list."""
    global _startup_dir, _startup_dropped
    with _startup_lock:
        if session_dir == _startup_dir:
            return
        _startup_shard.close()
        if _startup_dir is not None:
            _startup_rows.clear()
            _startup_dropped = 0
        _startup_dir = session_dir


def startup_row(name: str, begin_ns: int, end_ns: Optional[int] = None,
                attrs: Optional[Dict[str, Any]] = None,
                flush: bool = False) -> dict:
    """Records one row that lasted from `begin_ns` to `end_ns` (now, when
    left out) on `time.perf_counter_ns()`, which may have begun in another
    process of the host. `flush` writes the shard through: the rows a
    reader of a start that hangs would want."""
    global _startup_dropped
    now_ns, now = time.perf_counter_ns(), time.time()
    if end_ns is None:
        end_ns = now_ns
    end = now - (now_ns - end_ns) / 1e9
    row = {"name": name, "pid": os.getpid(), "attrs": dict(attrs or {}),
           "begin_ns": begin_ns, "end_ns": end_ns,
           "start": end - (end_ns - begin_ns) / 1e9, "end": end}
    with _startup_lock:
        if len(_startup_rows) >= STARTUP_CAP:
            _startup_dropped += 1
            return row
        _startup_rows.append(row)
    _startup_shard.write(row, flush=flush)
    return row


def startup_mark(name: str, attrs: Optional[Dict[str, Any]] = None,
                 flush: bool = False) -> dict:
    """A row of no length: an instant the stages are measured between."""
    now_ns = time.perf_counter_ns()
    return startup_row(name, now_ns, now_ns, attrs=attrs, flush=flush)


@contextlib.contextmanager
def startup_stage(name: str, attrs: Optional[Dict[str, Any]] = None,
                  flush: bool = False) -> Iterator[Dict[str, Any]]:
    """A start-up row around the block, and `rt/<name>` in a profiler
    session where jax is imported. Yields the row's `attrs`, for what is
    known only at the end."""
    attrs = dict(attrs or {})
    annotations = _annotations()
    ann = annotations[0](f"rt/{name}") if annotations is not None else None
    begin_ns = time.perf_counter_ns()
    if ann is not None:
        ann.__enter__()
    try:
        yield attrs
    except BaseException as e:
        attrs["error"] = type(e).__name__
        raise
    finally:
        if ann is not None:
            ann.__exit__(None, None, None)
        startup_row(name, begin_ns, attrs=attrs, flush=flush)


def startup_rows() -> List[dict]:
    """This process's rows, in the order they ended."""
    with _startup_lock:
        return list(_startup_rows)


def clear_startup() -> None:
    """Forgets this process's rows; its shard keeps what was written."""
    global _startup_dropped
    with _startup_lock:
        _startup_rows.clear()
        _startup_dropped = 0
        _startup_shard.discard()


def startup_dropped() -> int:
    """Rows past `STARTUP_CAP`, which were counted and not kept."""
    return _startup_dropped


def collect_startup(session_dir: str) -> List[dict]:
    """Every process's start-up rows of one session, by `begin_ns`: one
    clock for the processes of a host."""
    _startup_shard.flush()
    rows = _read_shards(os.path.join(session_dir, "logs", "startup-*.jsonl"))
    rows.sort(key=lambda r: r["begin_ns"])
    return rows


# -- aggregation ---------------------------------------------------------

def _read_shards(pattern: str) -> List[dict]:
    import glob

    rows = []
    for fn in sorted(glob.glob(pattern)):
        with open(fn) as f:
            for line in f:
                line = line.strip()
                if line:
                    rows.append(json.loads(line))
    return rows


def collect(path: Optional[str] = None) -> List[dict]:
    """Merge every process's span shard (sorted by start time)."""
    _trace_shard.flush()
    spans = _read_shards(os.path.join(path or trace_dir(), "trace-*.jsonl"))
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
