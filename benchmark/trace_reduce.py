"""From a profiler trace to numbers: the one reduction every PR shares.

A trace is handled as plain data, so the reduction can be checked on a small
recorded trace (`tests/data/`) without the profiler:

    [{"name": plane, "lines": [{"name": line, "events": [[name, start_ns,
                                                           duration_ns], ...]}]}]

`load_xplane` makes that from the `.xplane.pb` the JAX profiler writes; it is
the only function here that imports jax, and only the process that holds the
chip calls it.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Tuple

Interval = Tuple[float, float]

OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast", re.I)
_SUFFIX = re.compile(r"[.\-_]\d+$")
MIN_GAP_NS = 50_000.0       # shorter holes between operations are not gaps


def load_xplane(trace_dir: str) -> List[dict]:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events]
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


def _host_events(planes: List[dict]) -> List[list]:
    out = []
    for plane in planes:
        if is_device_plane(plane["name"]):
            continue
        for line in plane["lines"]:
            out.extend(e for e in line["events"] if e[2] > 0)
    return sorted(out, key=lambda e: e[1])


def _name_gaps(gaps: List[Interval], host: List[list]) -> Dict[str, float]:
    """Each idle gap goes to the host event that covers most of it, among
    events not much longer than the gap (a thread's outermost span covers
    every gap and names none). No such event: `no_host_span`."""
    starts = [e[1] for e in host]
    longest = max((e[2] for e in host), default=0.0)
    out: Dict[str, float] = {}
    for lo, hi in gaps:
        gap = hi - lo
        best, best_cover, inner, inner_dur = "no_host_span", 0.25 * gap, \
            None, float("inf")
        i = bisect.bisect_left(starts, lo - min(longest, 8 * gap))
        while i < len(host) and host[i][1] < hi:
            name, s, d = host[i]
            i += 1
            if d > 8 * gap:
                continue
            cover = min(hi, s + d) - max(lo, s)
            if cover > best_cover:
                best, best_cover = name, cover
            if cover >= 0.5 * gap and d < inner_dur:
                inner, inner_dur = name, d
        best = inner or best  # the innermost span that covers half the gap
        key = op_name(best)
        out[key] = out.get(key, 0.0) + gap / 1e9
    return out


def _top(table: Dict[str, float], n: int = 10) -> List[list]:
    return [[k, v] for k, v in
            sorted(table.items(), key=lambda kv: -kv[1])[:n]]


def reduce_trace(planes: List[dict]) -> dict:
    """Busy and idle share, operation time by kind, collective time that no
    compute hides, and idle gaps by host span. The window is the span of the
    device events themselves (the slice the profiler was on for)."""
    devices = _device_ops(planes)
    if not devices:
        return {}
    lo = min(e[1] for ops in devices for e in ops)
    hi = max(e[1] + e[2] for ops in devices for e in ops)
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
    gaps = [g for g in subtract([(lo, hi)], first)
            if g[1] - g[0] >= MIN_GAP_NS]
    n = len(devices)
    return {
        "devices": n,
        "window_s": window_s,
        "busy_s": sum(busy_s) / n,
        "collective_s": sum(coll_s) / n,
        "collective_exposed_s": sum(exposed_s) / n,
        "op_seconds": op_table,
        "device_ops": _top(op_table),
        "idle_gaps": _top(_name_gaps(gaps, _host_events(planes))),
    }


def kernel_seconds(reduced: dict, pattern: str) -> float:
    """Summed device time of the operation kinds whose name matches."""
    rx = re.compile(pattern)
    return sum(v for k, v in reduced.get("op_seconds", {}).items()
               if rx.search(k))
