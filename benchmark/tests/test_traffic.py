"""The deck rule, the closed-loop callers and the open loop, with a fake
stream in place of the handle."""

import collections
import glob
import json
import os
import threading
import time

import pytest

from benchmark import traffic as tg

HERE = os.path.dirname(os.path.abspath(__file__))
MIXES = sorted(glob.glob(os.path.join(HERE, "..", "traffic", "*.json")))
SERVING = [p for p in MIXES if "deck" in json.load(open(p))]


def _multiset(feeder, n, lanes):
    sizes = collections.Counter()
    for i in range(n):
        _, _, ids, new = feeder.next(i % lanes)
        sizes[(len(ids), new)] += 1
    return sizes


@pytest.mark.parametrize("path", SERVING, ids=os.path.basename)
def test_two_seeds_send_the_same_multiset_of_sizes(path):
    with open(path) as f:
        mix = json.load(f)
    deck = tg.expand_deck(mix)
    lanes = mix.get("callers", 1)
    n = len(deck) * lanes        # whole laps in either order
    a = _multiset(tg.DeckFeeder(mix, 32000, 11), n, lanes)
    b = _multiset(tg.DeckFeeder(mix, 32000, 3_000_000_022), n, lanes)
    assert a == b
    assert a == collections.Counter({k: v * lanes for k, v in
                                     collections.Counter(deck).items()})


def test_seed_changes_token_ids_and_requests_share_no_prefix():
    mix = tg.load_traffic("score-serial")
    a, b = tg.DeckFeeder(mix, 32000, 1), tg.DeckFeeder(mix, 32000, 2)
    ids_a = [a.next()[2] for _ in range(50)]
    ids_b = [b.next()[2] for _ in range(50)]
    assert ids_a != ids_b
    heads = [tuple(x[:16]) for x in ids_a]     # one KV page
    assert len(set(heads)) == len(heads)


def test_score_deck_puts_the_median_and_the_tail_inside_their_buckets():
    lengths = sorted(n for n, _ in tg.expand_deck(
        tg.load_traffic("score-serial")))
    assert len(lengths) == 100
    assert 256 < lengths[49] <= 512 and 256 < lengths[50] <= 512
    assert all(1024 < n <= 2047 for n in lengths[90:])
    assert all(new == 1 for _, new in tg.expand_deck(
        tg.load_traffic("score-serial")))


def test_fixed_lanes_give_every_run_the_same_sizes_in_the_same_places():
    mix = tg.load_traffic("generate")
    a, b = tg.DeckFeeder(mix, 32000, 5), tg.DeckFeeder(mix, 32000, 6)
    for lane in (3, 0, 15, 3, 3):
        x, y = a.next(lane), b.next(lane)
        assert (x[0], x[1], len(x[2]), x[3]) == (y[0], y[1], len(y[2]), y[3])
        assert x[2] != y[2]
    assert sorted(tg.interleaved(tg.expand_deck(mix))) == \
        sorted(tg.expand_deck(mix))


def _fake_stream(delay=0.001):
    def stream(ids, new):
        for i in range(new):
            time.sleep(delay)
            yield (len(ids) + i) % 100
    return stream


def test_closed_loop_callers_wait_for_the_last_token():
    mix = {"callers": 3, "deck": [{"count": 4, "prompt_from": 5,
                                   "prompt_to": 9, "new_tokens": [2, 3]}]}
    stop = threading.Event()
    samples, threads = tg.run_closed_loop(
        _fake_stream(), tg.DeckFeeder(mix, 100, 1), 3, stop)
    time.sleep(0.3)
    stop.set()
    for t in threads:
        t.join(timeout=5)
    assert not any(t.is_alive() for t in threads)
    assert len(samples) > 6 and all(s.done for s in samples)
    by_thread = sorted(samples, key=lambda s: s.sent)
    # never more than 3 in flight: the 4th was sent after some 1st ended
    ends = sorted(s.token_times[-1] for s in samples)
    assert by_thread[3].sent >= ends[0]


def test_serial_start_orders_the_first_requests():
    mix = {"callers": 4, "order": "fixed_lanes",
           "deck": [{"count": 4, "prompt_from": 5, "prompt_to": 8,
                     "new_tokens": [3]}]}
    stop = threading.Event()
    samples, threads = tg.run_closed_loop(
        _fake_stream(0.01), tg.DeckFeeder(mix, 100, 1), 4, stop,
        serial_start=True)
    time.sleep(0.3)
    stop.set()
    for t in threads:
        t.join(timeout=5)
    first = sorted((s for s in samples if s.index < 4),
                   key=lambda s: s.index)
    assert [s.index for s in first] == [0, 1, 2, 3]
    for a, b in zip(first, first[1:]):
        assert b.sent >= a.token_times[0]


def test_a_failed_request_is_kept_with_its_error():
    def broken(ids, new):
        yield 1
        raise RuntimeError("boom")

    stop = threading.Event()
    mix = {"callers": 1, "deck": [{"count": 1, "prompt_from": 4,
                                   "prompt_to": 4, "new_tokens": [2]}]}
    samples, threads = tg.run_closed_loop(
        broken, tg.DeckFeeder(mix, 100, 1), 1, stop)
    time.sleep(0.05)
    stop.set()
    threads[0].join(timeout=5)
    assert samples and not samples[0].done and "boom" in samples[0].error


def test_open_loop_keeps_its_rate_and_its_bursts():
    mix = {"rate_per_s": 50.0, "burst": 4}
    a = tg.arrival_times(mix, 7, 200.0)
    assert a == tg.arrival_times(mix, 7, 200.0)
    assert a != tg.arrival_times(mix, 8, 200.0)
    assert len(a) / 200.0 == pytest.approx(50.0, rel=0.1)
    assert all(a[i] == a[i + 1] == a[i + 2] == a[i + 3]
               for i in range(0, len(a) - 3, 4))


def test_open_loop_times_requests_from_when_they_were_due():
    mix = {"deck": [{"count": 2, "prompt_from": 4, "prompt_to": 5,
                     "new_tokens": [2]}]}
    stop = threading.Event()
    start = time.perf_counter()
    samples, threads = tg.run_open_loop(
        _fake_stream(), tg.DeckFeeder(mix, 100, 1), [0.0, 0.05, 0.1], start,
        stop)
    for t in list(threads):
        t.join(timeout=5)
    time.sleep(0.05)
    assert len(samples) == 3 and all(s.done for s in samples)
    assert [round(s.due - start, 2) for s in
            sorted(samples, key=lambda s: s.due)] == [0.0, 0.05, 0.1]
    assert all(s.sent >= s.due for s in samples)
