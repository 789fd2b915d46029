"""One general traffic generator, driven by the data files in `traffic/`.

The deck rule: a traffic file fixes the MULTISET of (prompt length, answer
length) pairs. The seed shuffles their order, draws the token ids and, in an
open loop, the arrival gaps; it never changes how many requests of which size
are sent. Token ids of different requests are drawn independently, so they
share no prefix (a 16-token page of random ids never repeats).
"""

from __future__ import annotations

import json
import math
import os
import random
import threading
import time
from typing import Callable, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))


def load_traffic(name: str, folder: Optional[str] = None) -> dict:
    """`traffic/<name>.json`; `folder` is where a test keeps tiny mixes."""
    path = os.path.join(folder or os.path.join(HERE, "traffic"),
                        f"{name}.json")
    with open(path) as f:
        return json.load(f)


def expand_deck(traffic: dict) -> List[Tuple[int, int]]:
    """(prompt_len, new_tokens) for every request of one lap. A group of
    `count` requests spreads its prompt lengths evenly over [from, to] and
    cycles through its `new_tokens` list. No randomness here."""
    deck = []
    for group in traffic["deck"]:
        n, lo, hi = group["count"], group["prompt_from"], group["prompt_to"]
        cycle = group["new_tokens"]
        for i in range(n):
            length = lo if n == 1 else lo + round(i * (hi - lo) / (n - 1))
            deck.append((length, cycle[i % len(cycle)]))
    return deck


def interleaved(deck: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """The deck in a fixed order that mixes its sizes: entry j of the result
    is entry j*stride of the expanded deck (stride about 0.38 of its length
    and coprime to it). No randomness here."""
    n = len(deck)
    stride = max(1, round(0.382 * n))
    while math.gcd(stride, n) != 1:
        stride += 1
    return [deck[(j * stride) % n] for j in range(n)]


class DeckFeeder:
    """Hands out requests, lap after lap, with fresh token ids. Thread-safe.

    `order: shuffled` (the default): each lap is the whole deck in a new
    seeded order, and callers take the next request whoever they are.

    `order: fixed_lanes`: caller i's k-th request is entry (k*callers + i) of
    the interleaved deck, walked round and round. Which sizes are in flight
    together, and which follow which, is then the traffic file's alone: with
    a window that holds about one lap, any shuffle would decide how much
    work falls into it. The seed draws the token ids."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        self.deck = expand_deck(traffic)
        self.vocab = vocab
        self.fixed = traffic.get("order", "shuffled") == "fixed_lanes"
        self.callers = int(traffic.get("callers", 1))
        self._seed = seed
        self._rng = random.Random(seed)
        self._lock = threading.Lock()
        self._lap: List[Tuple[int, int]] = []
        self._sent = 0
        self._lane_sent = [0] * self.callers
        self._fixed_order = interleaved(self.deck)

    def next(self, lane: int = 0) -> Tuple[int, int, List[int], int]:
        """(lap number, index in the send order, token ids, new_tokens)."""
        n = len(self.deck)
        if self.fixed:
            with self._lock:
                k = self._lane_sent[lane]
                self._lane_sent[lane] += 1
            index = k * self.callers + lane
            length, new = self._fixed_order[index % n]
            rng = random.Random(self._seed * 1000003 + index)
            ids = [rng.randrange(self.vocab) for _ in range(length)]
            return index // n, index, ids, new
        with self._lock:
            if not self._lap:
                self._lap = list(self.deck)
                self._rng.shuffle(self._lap)
                self._lap.reverse()
            length, new = self._lap.pop()
            index = self._sent
            self._sent += 1
            ids = [self._rng.randrange(self.vocab) for _ in range(length)]
        return index // n, index, ids, new


class Sample:
    """One request as the caller saw it. Times are `time.perf_counter()`."""

    __slots__ = ("index", "lap", "lane", "prompt_len", "new_tokens", "due",
                 "sent", "token_times", "error", "bad_token")

    def __init__(self, index, lap, prompt_len, new_tokens, due, lane=None):
        self.index, self.lap = index, lap
        self.lane = lane        # the closed-loop caller; None in an open loop
        self.prompt_len, self.new_tokens = prompt_len, new_tokens
        self.due = due          # when the call was due (open loop) or made
        self.sent = due
        self.token_times: List[float] = []
        self.error: Optional[str] = None
        self.bad_token = False

    @property
    def done(self) -> bool:
        return self.error is None and \
            len(self.token_times) == self.new_tokens


def _stream_one(stream: Callable, feeder_item, vocab: int, due: float,
                samples: List[Sample], lock: threading.Lock,
                lane: Optional[int] = None) -> Sample:
    lap, index, ids, new = feeder_item
    s = Sample(index, lap, len(ids), new, due, lane)
    with lock:
        samples.append(s)
    s.sent = time.perf_counter()
    try:
        for token in stream(ids, new):
            s.token_times.append(time.perf_counter())
            if not (isinstance(token, int) and 0 <= token < vocab):
                s.bad_token = True
    except Exception as e:  # noqa: BLE001 — counted in `failed`
        s.error = f"{type(e).__name__}: {e}"[:300]
    return s


def run_closed_loop(stream: Callable, feeder: DeckFeeder, callers: int,
                    stop: threading.Event, serial_start: bool = False
                    ) -> Tuple[List[Sample], List]:
    """`callers` threads; each streams a request and sends the next when the
    last token has arrived, until `stop` is set. With `serial_start`, caller
    i sends its first request when caller i-1 has its first token, so the
    order in which the system meets them is the same in every run. Returns
    the shared sample list (filled as the threads run) and the threads,
    already started."""
    samples: List[Sample] = []
    lock = threading.Lock()
    started = [threading.Event() for _ in range(callers)]

    def caller(lane: int):
        if serial_start and lane:
            started[lane - 1].wait()

        def marking(ids, new):
            for token in stream(ids, new):
                started[lane].set()
                yield token

        while not stop.is_set():
            _stream_one(marking, feeder.next(lane), feeder.vocab,
                        time.perf_counter(), samples, lock, lane)
            started[lane].set()     # a failed first request must not block

    threads = [threading.Thread(target=caller, args=(i,), daemon=True,
                                name=f"caller-{i}") for i in range(callers)]
    for t in threads:
        t.start()
    return samples, threads


def arrival_times(traffic: dict, seed: int, horizon_s: float) -> List[float]:
    """Open loop: offsets from the start at which requests are due. Bursts of
    `burst` arrivals (default 1) come at exponential gaps whose mean keeps
    the long-run rate at `rate_per_s`."""
    rng = random.Random(seed ^ 0x5EED)
    burst = int(traffic.get("burst", 1))
    mean_gap = burst / float(traffic["rate_per_s"])
    out, t = [], 0.0
    while t < horizon_s:
        out.extend([t] * burst)
        t += rng.expovariate(1.0 / mean_gap)
    return out


def run_open_loop(stream: Callable, feeder: DeckFeeder, offsets: List[float],
                  start: float, stop: threading.Event
                  ) -> Tuple[List[Sample], List]:
    """Sends each request when it is due, whatever the system does: one
    thread per request in flight. A request is timed from when it was DUE,
    so a late generator shows as latency and in `late_ms`."""
    samples: List[Sample] = []
    lock = threading.Lock()
    threads: List[threading.Thread] = []

    def dispatcher():
        for off in offsets:
            due = start + off
            delay = due - time.perf_counter()
            if delay > 0 and stop.wait(delay):
                return
            if stop.is_set():
                return
            t = threading.Thread(
                target=_stream_one, daemon=True,
                args=(stream, feeder.next(), feeder.vocab, due, samples,
                      lock))
            t.start()
            threads.append(t)

    d = threading.Thread(target=dispatcher, daemon=True, name="dispatcher")
    d.start()
    threads.append(d)
    return samples, threads
