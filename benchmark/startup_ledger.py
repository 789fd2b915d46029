"""What `setup_s` was spent on: readers over the program's start-up ledger.

Every process of a run appends span-shaped rows to
`<session_dir>/logs/startup-<pid>.jsonl` (`ray_tpu.util.tracing`): stages
(`cluster_up`, `worker_spawn`, `worker_boot`, `engine_build`), marks
(`deploy_call`, `user_entered`) and one `program` row for every program jax
started. This module finds the run's session as `run.keep_logs` does, merges
the shards and keeps the rows that begin before the window opens. A serving
cell's window is on `time.perf_counter()` (`obs.t_open`), the rows'
`begin_ns` clock; a training cell's is on the wall (`obs.window_open_wall`),
the rows' `start`. The origin is the opening less `obs.setup_s`.

A metric file names one of four readers and its `args`:

    stage_s      the summed length of the rows named `names`
    between_s    from one edge to another: `from` and `to` are "origin",
                 "open", or {"row": name, "edge": "begin" | "end"}
    rows         a count of the rows named `name` whose `attrs` equal `where`;
                 with `sum`, the total of that attribute; `in_window` keeps
                 the rows that begin inside the window instead of before it
    remainder_s  a `from`-`to` span less the union of the rows named `less`
                 that lie in it

`chip_process: true` (in `args`, or in an edge) keeps the rows of the process
whose `worker_boot` row holds chips (a rehearsal on the CPU has none: the
process that has `user_entered`). Each reader returns None where no ledger is
found or an edge is missing, so a program from before the ledger prints a
whole line that merely lacks these metrics.
"""

from __future__ import annotations

import glob
import os
from typing import Any, Dict, List, Optional, Tuple

_KEY = "startup_ledger"     # the loaded ledger, kept in the run's `obs`


def session_dir() -> Optional[str]:
    """This process's newest cluster session (`run.keep_logs`)."""
    sessions = sorted(glob.glob(f"/tmp/ray_tpu/session_*_{os.getpid()}"))
    return sessions[-1] if sessions else None


def load(obs: dict, session: Optional[str] = None) -> Optional[dict]:
    """The run's rows on the window's clock: `rows` as (name, pid, attrs,
    begin, end) in seconds, `origin`, `open`, `close`, `chip_pid`."""
    if _KEY in obs:
        return obs[_KEY]
    obs[_KEY] = ledger = _load(obs, session or session_dir())
    return ledger


def _load(obs: dict, session: Optional[str]) -> Optional[dict]:
    from ray_tpu.util import tracing

    collect = getattr(tracing, "collect_startup", None)
    if collect is None or session is None or "setup_s" not in obs:
        return None
    raw = collect(session)
    if raw and "t_open" in obs:
        opened, closed = obs["t_open"], obs["t_close"]
        edges = [(r["begin_ns"] / 1e9, r["end_ns"] / 1e9) for r in raw]
    elif raw and "window_open_wall" in obs:
        opened = obs["window_open_wall"]
        closed = opened + obs["window_s"]
        edges = [(r["start"], r["end"]) for r in raw]
    else:
        return None
    rows = [(r["name"], r["pid"], r.get("attrs", {}), b, e)
            for r, (b, e) in zip(raw, edges)]
    holders = [pid for name, pid, attrs, _, _ in rows
               if name == "worker_boot" and attrs.get("tpu_chips")] \
        or [pid for name, pid, _, _, _ in rows if name == "user_entered"]
    return {"rows": rows, "origin": opened - obs["setup_s"], "open": opened,
            "close": closed, "chip_pid": holders[-1] if holders else None}


def _select(ledger: dict, names, chip_process: bool = False,
            in_window: bool = False) -> List[tuple]:
    lo, hi = (ledger["open"], ledger["close"]) if in_window \
        else (float("-inf"), ledger["open"])
    return [row for row in ledger["rows"]
            if row[0] in names and lo <= row[3] < hi
            and (not chip_process or row[1] == ledger["chip_pid"])]


def _edge(ledger: dict, spec: Any) -> Optional[float]:
    if isinstance(spec, str):
        return ledger[spec]                 # "origin", "open"
    found = _select(ledger, (spec["row"],), spec.get("chip_process", False))
    if not found:
        return None
    return found[0][4 if spec.get("edge", "begin") == "end" else 3]


def _span(ledger: dict, args: dict) -> Optional[Tuple[float, float]]:
    lo, hi = _edge(ledger, args["from"]), _edge(ledger, args["to"])
    return None if lo is None or hi is None else (lo, hi)


def stage_s(obs: dict, args: dict) -> Optional[float]:
    ledger = load(obs)
    if ledger is None:
        return None
    found = _select(ledger, args["names"], args.get("chip_process", False))
    return sum(row[4] - row[3] for row in found) if found else None


def between_s(obs: dict, args: dict) -> Optional[float]:
    ledger = load(obs)
    span = ledger and _span(ledger, args)
    return span[1] - span[0] if span else None


def rows(obs: dict, args: dict) -> Optional[float]:
    ledger = load(obs)
    if ledger is None:
        return None
    where: Dict[str, Any] = args.get("where", {})
    found = [row for row in _select(
        ledger, (args["name"],), args.get("chip_process", False),
        args.get("in_window", False))
        if all(row[2].get(k) == v for k, v in where.items())]
    if "sum" in args:
        return float(sum(row[2].get(args["sum"]) or 0.0 for row in found))
    return float(len(found))


def remainder_s(obs: dict, args: dict) -> Optional[float]:
    ledger = load(obs)
    span = ledger and _span(ledger, args)
    if not span:
        return None
    lo, hi = span
    covered, reach = 0.0, lo
    for row in sorted(_select(ledger, args["less"],
                              args.get("chip_process", False)),
                      key=lambda row: row[3]):
        begin, end = max(row[3], reach), min(row[4], hi)
        if end > begin:
            covered += end - begin
            reach = end
    return hi - lo - covered
