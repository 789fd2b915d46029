"""How full the chips got, read in the process that holds them.

The TPU runtime counts the buffers of live arrays (`bytes_in_use`) apart
from what it sets aside for a running program's own temporaries
(`bytes_reserved`): a training step's activations are in the second, so the
first alone reads 4.5 GB for gpt2-medium where the compiler's analysis says
14.7. Each has a lifetime peak, but the two peaks need not fall together
(a scoring run read 17.4 GB for their sum on a chip whose limit is 16.9; my
chip runs, PR 23). So a thread samples the two together and keeps the largest
sum it saw at one moment: a reading from below, never above what was held.
"""

from __future__ import annotations

import threading


class PeakSampler:
    def __init__(self, devices, period_s: float = 0.01):
        self._devices = list(devices)
        self._period = period_s
        self._peak = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="bench-memory")
        self._thread.start()

    def _read(self) -> None:
        for d in self._devices:
            stats = d.memory_stats() or {}
            now = stats.get("bytes_in_use", 0) + stats.get("bytes_reserved", 0)
            if now > self._peak:
                self._peak = now

    def _loop(self) -> None:
        while not self._stop.wait(self._period):
            self._read()

    def report(self) -> dict:
        """The fullest chip so far: the sampled peak, the runtime's own two
        lifetime peaks beside it, and the chip's limit."""
        self._read()
        rows = [d.memory_stats() or {} for d in self._devices]
        return {
            "memory_peak_bytes": int(self._peak),
            "peak_bytes_in_use": max(
                int(r.get("peak_bytes_in_use", 0)) for r in rows),
            "peak_bytes_reserved": max(
                int(r.get("peak_bytes_reserved", 0)) for r in rows),
            "bytes_limit": min(int(r.get("bytes_limit", 0)) for r in rows),
        }

    def stop(self) -> dict:
        self._stop.set()
        self._thread.join()
        return self.report()


def over_limit(report: dict) -> list:
    """A reading above what the chip has is a fault of the reading."""
    limit, peak = report["bytes_limit"], report["memory_peak_bytes"]
    if limit and peak > limit:
        return [f"memory peak {peak} is over the chip's limit {limit}"]
    return []
