"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data: the cell is an entry of
`BENCHMARK.json`'s `workloads`, its configuration is `configs/<config>.json`,
its traffic `traffic/<traffic>.json`, each metric a file under `end_to_end/`
or `layer_metrics/`. This process never imports jax: the worker it starts
through `JaxTrainer.fit()` or `serve.run()` holds the chip. The last line of
standard output is the result; any failure to run prints none and exits
non-zero. A machine without the TPU chips the cell asks for is refused.
"""

from __future__ import annotations

import time

T_START_WALL = time.time()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def local_chips() -> int:
    """One device node per chip (`/dev/accel*`, or a numbered IOMMU group
    under `/dev/vfio/`); asked without jax, which would take the chip."""
    return len(glob.glob("/dev/accel*")) or \
        len(glob.glob("/dev/vfio/[0-9]*"))


def load_cell(workload: str, bench_file: str = "",
              traffic_folder: str = ""):
    """The cell, its configuration and its traffic, found by name. A test
    passes its own `BENCHMARK.json` and folder of tiny traffic mixes."""
    with open(bench_file or os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(has: {sorted(cells)})")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    from benchmark.traffic import load_traffic
    return bench, cell, config, load_traffic(cell["traffic"],
                                             traffic_folder or None)


def metrics_of_cell(bench: dict, cell: dict, kind: str):
    """Names of the cell's metrics of one kind (`end_to_end`, `per_layer`):
    those with no `workloads` key, or whose list names the cell."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def derive_serve(obs: dict, traffic: dict, deck_size: int) -> None:
    """From the callers' samples to the lists the metric files read."""
    t_open, t_close = obs["t_open"], obs["t_close"]
    samples = sorted(obs.pop("samples"), key=lambda s: s.index)
    measured = [s for s in samples if s.due >= t_open]
    # attempted: every request whose stream touched the window
    touched = [s for s in samples if s.due <= t_close and (
        not s.token_times or s.token_times[-1] >= t_open)]
    obs["attempted"] = len(touched)
    obs["failed"] = sum(1 for s in touched if not s.done or s.bad_token)
    obs["errors"] = [s.error for s in samples if s.error][:5]
    if any(s.bad_token for s in samples):
        obs["failures"].append("a streamed token was not an id below the "
                               "vocabulary")
    if obs["errors"]:
        obs["failures"].append(f"requests failed: {obs['errors']}")
    obs["tokens_in_window"] = sum(
        1 for s in samples for t in s.token_times if t_open <= t <= t_close)
    obs["out_tokens_per_s"] = out_tokens_per_s(samples, t_open, t_close)
    obs["itl_ms"] = [
        (b - a) * 1e3 for s in samples
        for a, b in zip(s.token_times, s.token_times[1:])
        if t_open <= b <= t_close]
    if traffic.get("percentiles_over") == "whole_laps":
        laps = {}
        for s in measured:
            laps.setdefault(s.lap, []).append(s)
        timed = [s for lap in laps.values()
                 if len(lap) == deck_size and all(
                     s.done and s.token_times[-1] <= t_close for s in lap)
                 for s in lap]
        obs["whole_laps"] = len(timed) // deck_size
    else:
        timed = [s for s in measured
                 if s.token_times and s.token_times[0] <= t_close]
    obs["ttft_ms"] = [(s.token_times[0] - s.due) * 1e3 for s in timed]
    obs["late_ms"] = [(s.sent - s.due) * 1e3 for s in timed]
    # the front's share: only one caller keeps the engine's order ours
    engine = obs.pop("engine_ttft_ms", [])
    if traffic.get("callers") == 1:
        skip = traffic.get("ramp_requests", 0)
        by_index = {s.index: e for s, e in zip(samples, engine[skip:])
                    if e[0] == s.prompt_len}
        obs["front_ms"] = [
            (s.token_times[0] - s.due) * 1e3 - by_index[s.index][1]
            for s in timed if s.index in by_index]


def out_tokens_per_s(samples, t_open: float, t_close: float) -> float:
    """Tokens streamed per second of the window. A closed loop's callers
    move in step, a decode step hands each one token, and the window holds
    only some hundred steps: counted against its fixed edges, every run
    lands on a lattice a whole step (1%) apart. So each caller's stream is
    taken from its first to its last token inside the window, tokens after
    the first over the time between, and the callers' rates are added: all
    the tokens and all the time but each caller's two broken gaps at the
    edges. An open loop has no callers, and its tokens are counted against
    the window as it stands."""
    if any(s.lane is None for s in samples):
        return sum(1 for s in samples for t in s.token_times
                   if t_open <= t <= t_close) / (t_close - t_open)
    lanes = {}
    for s in samples:
        lanes.setdefault(s.lane, []).extend(
            t for t in s.token_times if t_open <= t <= t_close)
    return sum((len(ts) - 1) / (max(ts) - min(ts))
               for ts in lanes.values() if len(ts) > 1 and max(ts) > min(ts))


def kill_leftovers() -> list:
    """Daemons or workers of this process's clusters that outlived
    shutdown: waited for, then killed, and reported."""
    mine = re.compile(rf"session_\d+_{os.getpid()}\b")
    deadline = time.monotonic() + 30
    while True:
        found = []
        for path in glob.glob("/proc/[0-9]*/cmdline"):
            try:
                with open(path, "rb") as f:
                    cmd = f.read().replace(b"\0", b" ").decode(
                        errors="replace")
            except OSError:
                continue
            if "ray_tpu._private" in cmd and mine.search(cmd):
                found.append((int(path.split("/")[2]), cmd[:160]))
        if not found:
            return []
        if time.monotonic() > deadline:
            for pid, _ in found:
                try:
                    os.kill(pid, signal.SIGKILL)
                except OSError:
                    pass
            return [cmd for _, cmd in found]
        time.sleep(0.5)


def keep_logs(workload: str) -> None:
    """A failed run's daemon and worker logs, where the chip tool brings
    them back from (`chiprun_out/` is in `.gitignore`)."""
    sessions = sorted(glob.glob(f"/tmp/ray_tpu/session_*_{os.getpid()}"))
    if sessions:
        shutil.copytree(
            os.path.join(sessions[-1], "logs"),
            os.path.join(ROOT, "chiprun_out", "benchmark_logs", workload),
            dirs_exist_ok=True)


def result_line(bench, cell, obs, trace: bool) -> dict:
    from benchmark import readers

    kind, folder = ("per_layer", "layer_metrics") if trace \
        else ("end_to_end", "end_to_end")
    metrics = {}
    for m in metrics_of_cell(bench, cell, kind):
        v = readers.read_metric(folder, m["name"], obs)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": obs["platform"], "kind": obs["device_kind"],
              "count": obs["count"],
              "memory_peak_bytes": obs["memory_peak_bytes"]}
    line = {"correct": not obs["failures"], "attempted": obs["attempted"],
            "failed": obs["failed"], "metrics": metrics, "device": device}
    if trace and obs.get("trace"):
        device["busy_s"] = obs["trace"]["busy_s"]
        device["window_s"] = obs["trace"]["window_s"]
        line["breakdown"] = {"device_ops": obs["trace"]["device_ops"],
                             "idle_gaps": obs["trace"]["idle_gaps"]}
    if obs["failures"]:
        line["failures"] = obs["failures"]
    line["notes"] = {k: obs[k] for k in (
        "steps", "whole_laps", "check", "loss_first", "loss_last",
        "warmup_s", "compiled_step_calls", "memory", "tokens_in_window",
        "window_s", "trace_tries") if k in obs}
    return line


def run(args, require_tpu: bool = True, bench_file: str = "",
        traffic_folder: str = "") -> dict:
    bench, cell, config, traffic = load_cell(args.workload, bench_file,
                                             traffic_folder)
    if require_tpu and local_chips() < cell["chips"]:
        raise SystemExit(f"{cell['name']} needs {cell['chips']} TPU chip(s); "
                         f"this machine has {local_chips()}")
    # every program goes to JAX's persistent cache, however quick to compile
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    from benchmark import readers
    from benchmark.traffic import expand_deck

    if traffic["kind"] == "train-steps":
        from benchmark.train_cell import run_train_cell
        obs = run_train_cell(cell, config, traffic, args.seed, args.seconds,
                             bool(args.trace), T_START_WALL, require_tpu)
        obs["attempted"], obs["failed"] = obs.get("steps", 0), 0
        if "required_flops" in config:      # `module:function`, as a reader
            obs["required_flops_per_token"] = readers.resolve(
                config["required_flops"])(config, traffic["seq_len"])
    else:
        from benchmark.serve_cell import run_serve_cell
        obs = run_serve_cell(cell, config, traffic, args.seed, args.seconds,
                             bool(args.trace), T_START_WALL, require_tpu)
        derive_serve(obs, traffic, len(expand_deck(traffic)))
    if require_tpu and (obs["platform"] != "tpu"
                        or obs["count"] < cell["chips"]):
        raise SystemExit(f"the worker computed on {obs['count']} x "
                         f"{obs['platform']}: {obs['failures']}")
    return result_line(bench, cell, obs, bool(args.trace))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    from benchmark import stage

    def failed(what: str) -> int:
        """The last line of standard error of a run that prints no result:
        the driver keeps a failed run's exit code and the end of its
        standard error, so this line is all a later reader has."""
        print(f"benchmark failed: workload={args.workload} "
              f"trace={args.trace} stage={stage.current()} {what}",
              file=sys.stderr, flush=True)
        return 1

    try:
        line = run(args)
    except BaseException as e:
        traceback.print_exc()
        try:
            keep_logs(args.workload)
        except OSError:
            pass
        left = kill_leftovers()
        first = (str(e).strip().splitlines() or [""])[0][:400]
        return failed(f"{type(e).__name__}: {first}" + (
            f" (left running, killed: {left})" if left else ""))
    stage.enter("leftovers")
    left = kill_leftovers()
    if left:
        return failed(f"LeftRunning: after shutdown, killed: {left}")
    if "jax" in sys.modules:
        return failed("ImportedJax: the benchmark's process imported jax")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
