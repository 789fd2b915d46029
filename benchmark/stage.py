"""Where a run is, for the one line a failed run ends with (`run.py:main`).

The driver's side names its stage as it goes; a training worker, whose loop
the driver cannot see into, writes its own to a file the driver reads once
the run has failed. A stage is one of `STAGES`; nothing is timed here."""

from __future__ import annotations

from typing import Optional

STAGES = ("setup", "ramp", "window", "trace_stop", "reduce", "check",
          "shutdown", "leftovers")
_current = "setup"
_write_to: Optional[str] = None     # a worker: every stage goes to this file
_read_from: Optional[str] = None    # the driver: the worker's file


def enter(name: str) -> None:
    global _current
    _current = name
    if _write_to is not None:
        try:
            with open(_write_to, "w") as f:
                f.write(name)
        except OSError:
            pass


def write_to(path: str) -> None:
    global _write_to
    _write_to = path


def read_from(path: Optional[str]) -> None:
    global _read_from
    _read_from = path


def current() -> str:
    """The driver's stage, and behind a slash the worker's where it differs
    (`window/check`: the driver waits in `fit()`, the worker compares)."""
    worker = ""
    if _read_from is not None:
        try:
            with open(_read_from) as f:
                worker = f.read().strip()
        except OSError:
            pass
    return f"{_current}/{worker}" if worker and worker != _current \
        else _current
