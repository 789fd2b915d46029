"""Percentiles of a histogram that the engine keeps as counters.

`engine.metrics()` publishes a fixed histogram as one counter a bucket,
`<prefix><upper edge>` (the edge a number in the histogram's unit, `inf` for
what lies past the last edge); a bucket holds the values in (the edge
before, its edge]. The window's differences of those counters
(`engine_delta`) are the window's histogram.
"""

from __future__ import annotations

from typing import Optional

from benchmark.readers import lookup


def bucket_percentile(obs, args) -> Optional[float]:
    """The `q`th percentile of the buckets `<prefix>*`, `prefix` a dotted
    path whose last part is the counters' common prefix
    (`engine_delta.stream_gap_le_`), interpolated linearly inside its
    bucket (the first from 0); a value in the `inf` bucket reads as that
    bucket's lower edge. None where the program keeps no such counters (an
    older program) or the window holds no value."""
    path, _, prefix = args["prefix"].rpartition(".")
    counters = lookup(obs, path)
    if not isinstance(counters, dict):
        return None
    buckets = sorted((float(key[len(prefix):]), n)
                     for key, n in counters.items()
                     if key.startswith(prefix))
    total = sum(n for _, n in buckets)
    if total <= 0:
        return None
    rank = args["q"] / 100.0 * total
    lower, seen = 0.0, 0.0
    for upper, n in buckets:
        if n and seen + n >= rank:
            if upper == float("inf"):
                return lower
            return lower + (upper - lower) * (rank - seen) / n
        seen += n
        lower = upper
    return lower
