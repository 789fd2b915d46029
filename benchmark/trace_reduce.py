"""From a profiler trace to numbers: the one reduction every PR shares, and
the one way a cell takes the trace it reduces.

A trace is handled as plain data, so the reduction can be checked on a small
recorded trace (`tests/data/`) without the profiler:

    [{"name": plane, "lines": [{"name": line, "events": [[name, start_ns,
                                                           duration_ns], ...]}]}]

**Taking it.** `start_session` / `stop_session` are the only calls into the
profiler, made by the process that holds the chip (a replica, a training
worker) and by nothing else. The Python tracer is off: it turned every
frame of 16-64 reader threads into an event, bent the steps it measured and
made `stop_trace` hold the interpreter for longer than the Serve controller
waits for a replica. The host tracer stays at the level that keeps
`jax.profiler.TraceAnnotation`, so the program's `rt/<name>` phases lie on
the device trace's clock and name the device's idle gaps.

**Reducing it.** Never in the process that took it, and the full reduction
never while the window is open: `python3 -m benchmark.trace_reduce <dir>`
is a child of its own under `JAX_PLATFORMS=cpu` (it reads the `.xplane.pb`
with `jax.profiler.ProfileData`, the one import of jax here besides the
session's) and prints the reduced dict as JSON. `--check` prints only what
`refused` needs, which `SliceTaker` asks for inside the window to decide
whether a second slice has to be taken.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import shutil
import subprocess
import sys
import time
from typing import Callable, Dict, List, Optional, Tuple

from benchmark import stage

Interval = Tuple[float, float]

OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast", re.I)
_SUFFIX = re.compile(r"[.\-_]\d+$")
MIN_GAP_NS = 50_000.0       # shorter holes between operations are not gaps
SPAN = "rt/"                # the program's phases (`util/tracing.PhaseTable`)
HOLES, UNCOVERED, OTHER = "holes_under_50us", "no_host_span", "other_spans"

# One rule for every cell: the traced slice is this long (a quarter of the
# window where a test's window is shorter), begins SLICE_AT_S into the
# window, and a second one, if the first is refused, a moment after the
# first was judged. See PERF.md section 3 for how many steps of each cell it
# holds and why it is no longer.
SLICE_S = 1.0
SLICE_AT_S = 3.0
SLICE_AGAIN_S = 1.0         # from a refusal to the second slice
MIN_WINDOW_SHARE = 0.25     # of the slice asked for: under it, not a trace
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def slice_seconds(seconds: float) -> float:
    return min(SLICE_S, seconds / 4)


# -- taking the trace: only the process that holds the chip --------------------

def start_session(trace_dir: str) -> None:
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # no event a Python frame
    options.host_tracer_level = 1       # the lowest that keeps TraceAnnotation
    options.enable_hlo_proto = False    # the operations' names need none
    options.include_dataset_ops = False
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def stop_session() -> None:
    import jax

    jax.profiler.stop_trace()


def load_xplane(trace_dir: str) -> List[dict]:
    """Lists of what `reduce_trace` reads and nothing else: the `XLA Ops`
    line of each device plane and the timed events of the host planes."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        device = is_device_plane(plane.name)
        lines = []
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events]
            if not device:
                events = [e for e in events if e[2] > 0]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return planes


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


_HLO = re.compile(r"^%?(?P<name>[^ ]+) = (?P<type>.*?) (?P<op>[a-z][a-z\-]*)\(")
_LAYOUT = re.compile(r"\{[^}]*\}")


def _strip_suffix(name: str) -> str:
    while True:
        cut = _SUFFIX.sub("", name)
        if cut == name or not cut:
            return name
        name = cut


def op_name(name: str) -> str:
    """The kind of a device operation. The chip names an event by its HLO
    text, `%fusion.123 = bf16[8,1024]{1,0:T(8,128)} fusion(...)`: the kind is
    the name without its number, then the result type without layouts, so
    that `fusion.123` and `fusion.7` of one shape are one kind. A Pallas
    kernel is a `custom-call` whatever flax scope named it (`%h13.3`), so
    its kind starts with `custom-call`. Other names (host spans) only lose
    a trailing number."""
    m = _HLO.match(name)
    if not m:
        return _strip_suffix(name.lstrip("%$"))
    base = "custom-call" if m.group("op") == "custom-call" \
        else _strip_suffix(m.group("name"))
    return f"{base} {_LAYOUT.sub('', m.group('type'))}"[:96]


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: List[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of the (disjoint, sorted) intervals `a` that no interval of the
    (disjoint, sorted) `b` covers."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k, cur = j, lo
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def self_times(events: List[list]) -> Dict[str, float]:
    """Seconds by operation kind, each event charged its own time less the
    events nested inside it (a `while` is not charged its body twice)."""
    out: Dict[str, float] = {}
    stack: List[list] = []  # [end, name, self_ns]

    def close(item):
        out[item[1]] = out.get(item[1], 0.0) + max(item[2], 0.0) / 1e9

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= start:
            close(stack.pop())
        if stack:
            stack[-1][2] -= dur
        stack.append([start + dur, op_name(name), dur])
    while stack:
        close(stack.pop())
    return out


def _device_ops(planes: List[dict]) -> List[List[list]]:
    """The operation events of each device plane."""
    out = []
    for plane in planes:
        if not is_device_plane(plane["name"]):
            continue
        ops = [e for line in plane["lines"] if line["name"] == OPS_LINE
               for e in line["events"]]
        if ops:
            out.append(ops)
    return out


def _host_events(planes: List[dict]) -> Tuple[List[list], List[list]]:
    """The timed host events, in two sorted lists: the program's spans
    (`rt/<name>`) of the thread that has most of them, which is the one that
    feeds the device (the engine's pump, the training loop), and every
    other event: the runtime's threads, and spans of threads that only look
    on (`rt/metrics` of a poll waits for the engine's lock right across the
    pump's steps, and would name their gaps)."""
    lines = [[e for e in line["events"] if e[2] > 0]
             for plane in planes if not is_device_plane(plane["name"])
             for line in plane["lines"]]
    feeder = max(lines, default=[], key=lambda events: sum(
        1 for e in events if e[0].startswith(SPAN)))
    spans = [e for e in feeder if e[0].startswith(SPAN)]
    others = [e for events in lines for e in events
              if events is not feeder or not e[0].startswith(SPAN)]
    return (sorted(spans, key=lambda e: (e[1], -e[2])),    # outer first
            sorted(others, key=lambda e: e[1]))


def _split(lo: float, hi: float, spans: List[list], starts: List[float],
           longest: float) -> Tuple[Dict[str, float], List[Interval]]:
    """The gap [lo, hi] by the feeding thread's spans: they nest
    (`rt/engine_step` holds `rt/admit` holds `rt/lock_wait`), so every
    instant belongs to the innermost one open then, as in the program's
    own ledger of self times. Returns the nanoseconds a span and the
    stretches that none covers."""
    out: Dict[str, float] = {}
    bare: List[Interval] = []
    stack: List[Tuple[float, str]] = []     # (end, name), innermost last
    cur = lo

    def charge(until: float) -> None:
        nonlocal cur
        until = min(until, hi)
        if until <= cur:
            return
        if stack:
            out[stack[-1][1]] = out.get(stack[-1][1], 0.0) + until - cur
        else:
            bare.append((cur, until))
        cur = until

    i = bisect.bisect_left(starts, lo - longest)
    while i < len(spans) and spans[i][1] < hi:
        name, start, dur = spans[i]
        i += 1
        if start + dur <= lo:
            continue
        while stack and stack[-1][0] <= start:
            charge(stack[-1][0])
            stack.pop()
        charge(start)
        stack.append((start + dur, name))
    while stack:
        charge(stack[-1][0])
        stack.pop()
    charge(hi)
    return out, bare


def _name_gaps(gaps: List[Interval], spans: List[list],
               others: List[list]) -> Dict[str, float]:
    """Each idle gap goes to the host events over it. The feeding thread's
    spans come first and share the gap among them (`_split`). Only a
    stretch none of them covers is named by another host event, by the old
    rule: the innermost that covers half of it among events not much
    longer than the stretch (a thread's outermost span covers every gap
    and names none), else the one that covers most of it. No event at
    all: `no_host_span`."""
    out: Dict[str, float] = {}

    def add(name: str, ns: float) -> None:
        out[name] = out.get(name, 0.0) + ns / 1e9

    span_starts = [e[1] for e in spans]
    span_longest = max((e[2] for e in spans), default=0.0)
    starts = [e[1] for e in others]
    longest = max((e[2] for e in others), default=0.0)
    for gap_lo, gap_hi in gaps:
        named, bare = _split(gap_lo, gap_hi, spans, span_starts,
                             span_longest)
        for name, ns in named.items():
            add(op_name(name), ns)
        for lo, hi in bare:
            gap = hi - lo
            best, best_cover, inner, inner_dur = None, 0.25 * gap, None, \
                float("inf")
            i = bisect.bisect_left(starts, lo - min(longest, 8 * gap))
            while i < len(others) and others[i][1] < hi:
                n, s, d = others[i]
                i += 1
                if d > 8 * gap:
                    continue
                cover = min(hi, s + d) - max(lo, s)
                if cover > best_cover:
                    best, best_cover = n, cover
                if cover >= 0.5 * gap and d < inner_dur:
                    inner, inner_dur = n, d
            name = inner or best
            add(op_name(name) if name is not None else UNCOVERED, gap)
    return out


def _top(table: Dict[str, float], n: int = 10) -> List[list]:
    return [[k, v] for k, v in
            sorted(table.items(), key=lambda kv: -kv[1])[:n]]


def _gap_rows(named: Dict[str, float], holes_s: float) -> List[list]:
    """At most ten rows that sum to the device's idle time: the named gaps
    longest first (what does not fit folded into `other_spans`), then the
    two remainders, always there: the holes too short to be gaps, and the
    gaps no host event covers."""
    named = dict(named)
    uncovered = named.pop(UNCOVERED, 0.0)
    rows = _top(named, len(named))
    if len(rows) > 8:
        rows = rows[:7] + [[OTHER, sum(v for _, v in rows[7:])]]
    return rows + [[HOLES, holes_s], [UNCOVERED, uncovered]]


def reduce_trace(planes: List[dict]) -> dict:
    """Busy and idle share, operation time by kind, collective time that no
    compute hides, and idle gaps by host span. The window is the span of the
    device events themselves (the slice the profiler was on for)."""
    devices = _device_ops(planes)
    if not devices:
        return {}
    lo, hi = _span(devices)
    window_s = (hi - lo) / 1e9
    busy_s, coll_s, exposed_s = [], [], []
    op_table: Dict[str, float] = {}
    for ops in devices:
        busy = union([(s, s + d) for _, s, d in ops])
        busy_s.append(total(busy) / 1e9)
        coll = union([(s, s + d) for n, s, d in ops if COLLECTIVE.search(n)])
        other = union([(s, s + d) for n, s, d in ops
                       if not COLLECTIVE.search(n)
                       and not n.lstrip("%").startswith(
                           ("while", "conditional"))])
        coll_s.append(total(coll) / 1e9)
        exposed_s.append(total(subtract(coll, other)) / 1e9)
        for name, sec in self_times(ops).items():
            op_table[name] = op_table.get(name, 0.0) + sec / len(devices)
    first = union([(s, s + d) for _, s, d in devices[0]])
    holes = subtract([(lo, hi)], first)
    gaps = [g for g in holes if g[1] - g[0] >= MIN_GAP_NS]
    spans, others = _host_events(planes)
    n = len(devices)
    return {
        "devices": n,
        "window_s": window_s,
        "busy_s": sum(busy_s) / n,
        "collective_s": sum(coll_s) / n,
        "collective_exposed_s": sum(exposed_s) / n,
        "op_seconds": op_table,
        "device_ops": _top(op_table),
        "idle_gaps": _gap_rows(_name_gaps(gaps, spans, others),
                               (total(holes) - total(gaps)) / 1e9),
        # of the first device, which the rows of `idle_gaps` sum to
        "idle_s": total(holes) / 1e9,
        **_counts(devices, spans + others),
    }


def _span(devices: List[List[list]]) -> Interval:
    return (min(e[1] for ops in devices for e in ops),
            max(e[1] + e[2] for ops in devices for e in ops))


def _counts(devices: List[List[list]], host: List[list]) -> dict:
    return {"device_events": sum(len(ops) for ops in devices),
            "host_events": len(host),
            "span_events": sum(1 for e in host if e[0].startswith(SPAN))}


def check_trace(planes: List[dict]) -> dict:
    """What `refused` reads, without the reduction: asked inside the window."""
    devices = _device_ops(planes)
    if not devices:
        return {}
    lo, hi = _span(devices)
    return {"devices": len(devices), "window_s": (hi - lo) / 1e9,
            **_counts(devices, sum(_host_events(planes), []))}


def refused(reduced: dict, slice_s: float, serving: bool) -> Optional[str]:
    """Why what came back is not a trace, or None. A traced run's line is
    built only on one that is: no device plane, a window of milliseconds
    (`device_idle_pct.generate` once read 89.27 from 12 ms), or a serving
    trace without the program's phases is a loss, whatever else it holds."""
    if reduced.get("error"):
        return str(reduced["error"])
    if not reduced.get("devices"):
        return "no device plane with operation events"
    if reduced["window_s"] < MIN_WINDOW_SHARE * slice_s:
        return (f"the device events span {reduced['window_s'] * 1e3:.1f} ms "
                f"of the {slice_s:g} s slice asked for "
                f"({reduced['device_events']} events)")
    if serving and not reduced.get("span_events"):
        return (f"no {SPAN} host event among {reduced['host_events']} "
                f"(the program's phases are not on the trace's clock)")
    return None


# -- reducing it: a child of its own, after the window ------------------------

def reduce_in_child(trace_dir: str, timeout: float = 240.0) -> dict:
    """`python3 -m benchmark.trace_reduce <dir>` to its dict."""
    return finish_child(start_child(trace_dir, check=False), timeout)


def start_child(trace_dir: str, check: bool) -> subprocess.Popen:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.Popen(
        [sys.executable, "-m", "benchmark.trace_reduce"]
        + (["--check"] if check else []) + [trace_dir],
        cwd=ROOT, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)


def finish_child(proc, timeout: float) -> dict:
    """What the child printed; one that fails, says nothing or outlasts
    its limit gives `error`, which `refused` passes on."""
    try:
        out, err = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        return {"error": f"the reduction's child outlasted its "
                         f"{timeout:g} s"}
    return child_result(proc.returncode, out, err)


def child_result(rc: int, out: str, err: str) -> dict:
    last = out.strip().splitlines()[-1:] or [""]
    try:
        got = json.loads(last[0])
    except ValueError:
        got = None
    if rc or not isinstance(got, dict):
        tail = (err.strip().splitlines() or ["no output"])[-1]
        return {"error": f"the reduction's child ended {rc}: {tail[:300]}"}
    return got


class SliceTaker:
    """One traced slice, judged, and one more if it was no trace.

    `start()` and `stop()` reach the process that holds the session (`stop`
    returns the directory written); `poll(t)` is called with the seconds
    since the window opened, by a driver between naps or by a training loop
    between steps, and never blocks on a reduction: the check runs in a
    child that `poll` only looks at. `tries` holds a dict a slice: `dir`,
    `refused` (None for a trace), `stop_s`."""

    def __init__(self, start: Callable[[], None], stop: Callable[[], str],
                 seconds: float, serving: bool,
                 check: Callable[[str], "subprocess.Popen"] = None):
        self.start, self.stop = start, stop
        self.slice_s = slice_seconds(seconds)
        self.serving = serving
        self.check = check or (lambda d: start_child(d, check=True))
        self.next_at: Optional[float] = min(SLICE_AT_S, seconds / 4)
        self.started_at: Optional[float] = None
        self.child = None
        self.tries: List[dict] = []

    @property
    def tracing(self) -> bool:
        return self.started_at is not None

    @property
    def idle(self) -> bool:
        """Nothing open and nothing more to come."""
        return not self.tracing and self.child is None \
            and self.next_at is None

    def poll(self, t: float) -> None:
        if self.child is not None:
            self._judge(t, wait=False)
        elif self.tracing:
            if t - self.started_at >= self.slice_s:
                self._stop()
        elif self.next_at is not None and t >= self.next_at:
            self.next_at = None
            t0 = time.perf_counter()
            self.start()
            # the slice runs from when the session is on, not from the call
            self.started_at = t + time.perf_counter() - t0

    def _stop(self) -> None:
        t0 = time.perf_counter()
        trace_dir = self.stop()
        self.started_at = None
        self.tries.append({"dir": trace_dir, "refused": None,
                           "stop_s": time.perf_counter() - t0})
        self.child = self.check(trace_dir)

    def _judge(self, t: float, wait: bool) -> None:
        if not wait and self.child.poll() is None:
            return
        got, self.child = finish_child(self.child, 120.0), None
        why = refused(got, self.slice_s, self.serving)
        self.tries[-1]["refused"] = why
        if why is not None and len(self.tries) < 2:
            self.next_at = t + SLICE_AGAIN_S

    def stop_open_slice(self) -> None:
        if self.tracing:
            self._stop()

    def close(self, t: float) -> dict:
        """The window is over: a slice still open is stopped, a check still
        running is waited for. Returns what `reduce_taken` needs, as plain
        data (a training worker hands it to its driver): `dir` of the trace
        to reduce, None when every try was lost and `lost` says how."""
        self.stop_open_slice()
        if self.child is not None:
            self._judge(t, wait=True)
        self.next_at = None
        whole = [tr["dir"] for tr in self.tries if tr["refused"] is None]
        return {"dir": whole[0] if whole else None, "slice_s": self.slice_s,
                "lost": self.lost(), "tries": self.tries}

    def lost(self) -> str:
        if not self.tries:
            return "device trace never taken: the window closed first"
        whys = "; ".join(f"try {i + 1}: {tr['refused']}"
                         for i, tr in enumerate(self.tries))
        if len(self.tries) < 2:
            return (f"device trace lost: {whys}; the window closed before "
                    f"a second slice")
        return f"device trace lost twice: {whys}"


def reduce_taken(obs: dict, taken: dict, serving: bool) -> None:
    """After the window, with the cluster down, in `run.py`'s process: the
    accepted slice goes through the reduction's child into `obs["trace"]`;
    a run whose slices were all lost says so under `failures` and carries no
    `trace` at all, so its line cannot look whole. The directories go."""
    stage.enter("reduce")
    obs["trace_tries"] = [{"refused": t["refused"], "stop_s": t["stop_s"]}
                          for t in taken["tries"]]
    try:
        if obs["platform"] != "tpu":
            return      # a CPU (a test) has no device plane: nothing to read
        if taken["dir"] is None:
            obs["failures"].append(taken["lost"])
            return
        reduced = reduce_in_child(taken["dir"])
        why = refused(reduced, taken["slice_s"], serving)
        if why is None:
            obs["trace"] = reduced
        else:
            obs["failures"].append(
                f"device trace lost in the reduction: {why}")
    finally:
        for t in taken["tries"]:
            shutil.rmtree(t["dir"], ignore_errors=True)


def kernel_seconds(reduced: dict, pattern: str) -> float:
    """Summed device time of the operation kinds whose name matches."""
    rx = re.compile(pattern)
    return sum(v for k, v in reduced.get("op_seconds", {}).items()
               if rx.search(k))


def main(argv=None) -> int:
    args = list(sys.argv[1:] if argv is None else argv)
    check = "--check" in args
    if check:
        args.remove("--check")
    if args[0].endswith(".json"):       # a recorded trace, as plain data
        with open(args[0]) as f:
            planes = json.load(f)
    else:
        planes = load_xplane(args[0])
    print(json.dumps(check_trace(planes) if check else reduce_trace(planes)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
