"""The small reductions that metric files name. A metric is one JSON file
(`end_to_end/<name>.json` or `layer_metrics/<name>.json`):

    {"reader": "ratio", "args": {"num": "engine_delta.prefill_ms",
                                  "den": "engine_delta.prefill_steps"}}

`reader` is a function of this module, or `module:function` for a module
that a later PR adds under `benchmark/` (`resolve`; a configuration file
names its model builder and its required-operations function the same way).
A reader gets the run's
observations (a nested dict, paths written with dots) and its `args`, and
returns a number, or None when what it reads is not there: the harness then
leaves the metric out of the line.
"""

from __future__ import annotations

import importlib
import json
import os
from typing import Any, Optional

from benchmark import yardstick

HERE = os.path.dirname(os.path.abspath(__file__))


def resolve(spec: str):
    """`module:function` to the function, `module` a dotted path under
    `benchmark/`: what lets a later PR bring code as a file of its own."""
    module, fn = spec.split(":")
    return getattr(importlib.import_module(f"benchmark.{module}"), fn)


def lookup(obs: dict, path: str) -> Any:
    cur: Any = obs
    for part in path.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur


def value(obs, args) -> Optional[float]:
    v = lookup(obs, args["key"])
    return None if v is None else float(v) * args.get("scale", 1.0)


def ratio(obs, args) -> Optional[float]:
    """(num - minus) / den * scale; None when the denominator is 0."""
    num, den = lookup(obs, args["num"]), lookup(obs, args["den"])
    if num is None or not den:
        return None
    if "minus" in args:
        num = num - (lookup(obs, args["minus"]) or 0)
    return float(num) / float(den) * args.get("scale", 1.0)


def percentile(obs, args) -> Optional[float]:
    values = lookup(obs, args["key"])
    if not values:
        return None
    return yardstick.percentile(values, args["q"])


def idle_pct(obs, args) -> Optional[float]:
    busy, window = lookup(obs, "trace.busy_s"), lookup(obs, "trace.window_s")
    if busy is None or not window:
        return None
    return 100.0 * (1.0 - busy / window)


def collective_exposed_pct(obs, args) -> Optional[float]:
    t, window = lookup(obs, "trace.collective_exposed_s"), \
        lookup(obs, "trace.window_s")
    if t is None or not window:
        return None
    return 100.0 * t / window


def mfu_pct(obs, args) -> Optional[float]:
    """Required operations per token x steady tokens/s over chips x peak.
    The steady rate is tokens per step over the median step time, which the
    traced run's pause for the profiler does not move."""
    step_ms, per_step = lookup(obs, "step_ms"), lookup(obs, "tokens_per_step")
    flops = lookup(obs, "required_flops_per_token")
    if not step_ms or not per_step or flops is None:
        return None
    rate = per_step / (yardstick.percentile(step_ms, 50) / 1e3)
    return yardstick.mfu_pct(flops, rate, obs["count"], obs["device_kind"])


def kernel_ms_per_step(obs, args) -> Optional[float]:
    """Device time of the operations matching `pattern`, per step of the
    traced slice. None when no event matches: the kernel cannot be told
    apart in the trace as the program stands."""
    from benchmark import trace_reduce

    trace = lookup(obs, "trace")
    step_ms = lookup(obs, "step_ms")
    if not trace or not step_ms:
        return None
    seconds = trace_reduce.kernel_seconds(trace, args["pattern"])
    if not seconds:
        return None
    steps = trace["window_s"] * 1e3 / yardstick.percentile(step_ms, 50)
    return seconds * 1e3 / steps


def load_metric(kind: str, name: str, base: str = HERE) -> dict:
    with open(os.path.join(base, kind, f"{name}.json")) as f:
        return json.load(f)


def read_metric(kind: str, name: str, obs: dict,
                base: str = HERE) -> Optional[float]:
    spec = load_metric(kind, name, base)
    reader = spec["reader"]
    func = resolve(reader) if ":" in reader else globals()[reader]
    return func(obs, spec.get("args", {}))
