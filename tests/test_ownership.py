"""Ownership GC + lineage recovery: the distributed ref-counting plane.

The submitting worker owns its returns (reference: `reference_count.h:61`,
ownership design from the NSDI '21 paper): local refs pin the object,
tasks borrow their by-ref args for their lifetime, remote workers that
deserialize a ref register as borrowers, and the owner frees the primary
shm copy the moment every count hits zero. Loss of the primary copy
re-executes the producing task from recorded lineage
(`task_manager.h:208`), recursively for missing upstream inputs, with
`ObjectLostError` on the unreconstructable paths. This suite runs under
lockdep (see conftest `_LOCKDEP_SUITES`): the ref-table lock joins the
order graph in every test.
"""

import gc
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu._private.node import Cluster
from ray_tpu._private.object_ref import get_core_worker

# this machine populates big shm arenas slowly; small stores keep the
# cluster spin-up inside the suite budget without changing semantics
_STORE = 64 * 1024 * 1024


def _poll(pred, timeout=20.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.1)
    return pred()


def _ref_table_empty(cw):
    with cw._ref_lock:
        return (not cw._local_refs and not cw._task_arg_refs
                and not any(cw._borrowers.values())
                and not cw._borrowed_refs)


# ---------------------------------------------------------------------------
# ref-count lifecycle
# ---------------------------------------------------------------------------


def test_local_ref_release_frees_store_copy():
    """Dropping the last local handle drives the owner's count to zero:
    the pin is released and the raylet force-deletes the shm slot (not
    leak-or-LRU — the owner decides)."""
    ray_tpu.init(num_cpus=2, object_store_memory=_STORE)
    try:
        cw = get_core_worker()
        freed_before = cw._stats_objects_freed
        ref = ray_tpu.put(np.arange(1_000_000, dtype=np.uint8))
        oid = ref.binary()
        assert _poll(lambda: oid in cw._pinned_at, 10), \
            "pin never recorded at the owner"
        del ref
        gc.collect()
        assert _poll(lambda: oid not in cw._pinned_at
                     and oid not in cw._local_refs), \
            "owner never released the zero-ref object"
        assert _poll(lambda: cw._stats_objects_freed > freed_before)
    finally:
        ray_tpu.shutdown()


def test_task_return_release_frees_store_copy():
    """Task plasma returns follow the same lifecycle: owner frees the
    executor-pinned copy when the driver's last handle dies."""
    ray_tpu.init(num_cpus=2, object_store_memory=_STORE)
    try:
        cw = get_core_worker()

        @ray_tpu.remote
        def produce():
            return np.full(500_000, 7, np.uint8)

        ref = produce.remote()
        assert ray_tpu.get(ref, timeout=30)[0] == 7
        oid = ref.binary()
        del ref
        gc.collect()
        assert _poll(lambda: oid not in cw._pinned_at
                     and oid not in cw._local_refs), \
            "task-return pin leaked after the last deref"
        # lineage goes with the last reference
        assert _poll(lambda: oid not in cw._lineage_oids)
    finally:
        ray_tpu.shutdown()


def test_borrower_keeps_object_alive_across_worker(ray_start):
    """A ref pickled into another worker's args registers that worker as
    a borrower with the owner; the object survives the owner dropping
    its own handle until the borrower's last deref releases the edge."""
    cw = get_core_worker()

    @ray_tpu.remote
    class Holder:
        def hold(self, refs):
            self.ref = refs[0]  # keep the deserialized borrow alive
            return True

        def read(self):
            return int(ray_tpu.get(self.ref)[123])

        def drop(self):
            self.ref = None
            gc.collect()
            return True

    holder = Holder.remote()
    ref = ray_tpu.put(np.arange(600_000, dtype=np.uint8) % 251)
    oid = ref.binary()
    expected = int((np.arange(600_000, dtype=np.uint8) % 251)[123])
    # nested in a list → rides the borrower protocol, not top-level
    # arg resolution
    assert ray_tpu.get(holder.hold.remote([ref]), timeout=30)
    assert _poll(lambda: cw._borrowers.get(oid), 15), \
        "borrower edge never registered with the owner"

    del ref
    gc.collect()
    time.sleep(1.0)  # give a buggy release a chance to fire
    # the borrow must keep the object readable
    assert ray_tpu.get(holder.read.remote(), timeout=30) == expected

    assert ray_tpu.get(holder.drop.remote(), timeout=30)
    assert _poll(lambda: not cw._borrowers.get(oid)
                 and oid not in cw._pinned_at), \
        "owner never freed after the last borrower released"


def test_zero_leaked_refs_at_quiesce(ray_start):
    """After a workload of puts, ref args, nested refs and chains, the
    owner's entire ref table drains to zero — no leaked counts, no
    stranded pins, no lineage for dead objects."""
    cw = get_core_worker()

    @ray_tpu.remote
    def produce(i):
        return np.full(300_000, i, np.uint8)

    @ray_tpu.remote
    def consume(x):
        return int(x.astype(np.uint64).sum())

    @ray_tpu.remote
    def consume_nested(d):
        return int(ray_tpu.get(d["ref"]).astype(np.uint64).sum())

    puts = [ray_tpu.put(np.full(200_000, i, np.uint8)) for i in range(3)]
    stage1 = [produce.remote(i) for i in range(4)]
    stage2 = [consume.remote(r) for r in stage1]
    nested = [consume_nested.remote({"ref": r}) for r in puts]
    assert ray_tpu.get(stage2, timeout=60) == [300_000 * i
                                               for i in range(4)]
    assert ray_tpu.get(nested, timeout=60) == [200_000 * i
                                               for i in range(3)]
    del puts, stage1, stage2, nested
    gc.collect()
    assert _poll(lambda: _ref_table_empty(cw)), (
        "leaked refs at quiesce: locals=%d task_args=%d borrowers=%d"
        % (len(cw._local_refs), len(cw._task_arg_refs),
           sum(1 for v in cw._borrowers.values() if v)))
    assert _poll(lambda: not cw._pinned_at), "stranded pins at quiesce"
    assert _poll(lambda: not cw._lineage and cw._lineage_bytes == 0), \
        "lineage retained for fully-released objects"


# ---------------------------------------------------------------------------
# loss + reconstruction
# ---------------------------------------------------------------------------


@pytest.fixture
def two_node():
    cluster = Cluster(object_store_memory=_STORE)
    cluster.add_node({"CPU": 2.0})
    victim = cluster.add_node({"CPU": 2.0, "scratch": 1.0})
    ray_tpu.init(address=cluster.gcs_addr)
    yield cluster, victim
    ray_tpu.shutdown()
    cluster.shutdown()


def test_recursive_reconstruction_bit_identical(two_node):
    """Both stages of a chain lived on the dead node: recovering the
    downstream object first re-executes its upstream input, and the
    recovered bytes are identical to a local recompute."""
    cluster, victim = two_node
    affinity = ray_tpu.NodeAffinitySchedulingStrategy(
        victim.node_id_hex, soft=True)

    @ray_tpu.remote(scheduling_strategy=affinity)
    def produce():
        return (np.arange(400_000, dtype=np.uint64) * 2654435761) \
            .astype(np.uint8)

    @ray_tpu.remote(scheduling_strategy=affinity)
    def transform(x):
        return (x.astype(np.uint16) * 3 + 1).astype(np.uint8)

    a = produce.remote()
    b = transform.remote(a)
    ready, _ = ray_tpu.wait([b], timeout=60)  # wait, don't localize
    assert ready

    cluster.remove_node(victim)
    time.sleep(1.0)

    base = (np.arange(400_000, dtype=np.uint64) * 2654435761) \
        .astype(np.uint8)
    expect_b = (base.astype(np.uint16) * 3 + 1).astype(np.uint8)
    out_b = ray_tpu.get(b, timeout=180)
    assert np.array_equal(out_b, expect_b), \
        "reconstructed downstream value is not bit-identical"
    out_a = ray_tpu.get(a, timeout=180)
    assert np.array_equal(out_a, base), \
        "reconstructed upstream value is not bit-identical"
    cw = get_core_worker()
    assert cw._stats_reconstructions >= 2, \
        "chain recovery should have re-executed both stages"


def test_get_lost_object_without_lineage_fails_fast(two_node):
    """Regression (pre-fix: get() on an object whose node died blocked
    until the full timeout with no diagnostic): actor-method returns
    carry no lineage, so loss must raise ObjectLostError promptly —
    well before the caller's timeout — naming why recovery is
    impossible."""
    cluster, victim = two_node
    affinity = ray_tpu.NodeAffinitySchedulingStrategy(
        victim.node_id_hex, soft=False)

    @ray_tpu.remote(scheduling_strategy=affinity)
    class Producer:
        def make(self):
            return np.full(400_000, 5, np.uint8)

    prod = Producer.remote()
    ref = prod.make.remote()
    ready, _ = ray_tpu.wait([ref], timeout=60)
    assert ready

    cluster.remove_node(victim)
    time.sleep(1.0)

    start = time.monotonic()
    with pytest.raises(ray_tpu.ObjectLostError,
                       match="lost|not reconstructable"):
        ray_tpu.get(ref, timeout=120)
    elapsed = time.monotonic() - start
    assert elapsed < 60, (
        f"lost-object get took {elapsed:.0f}s — should fail fast, "
        "not block toward the timeout")


def test_lineage_cap_eviction_marks_unreconstructable():
    """Past max_lineage_bytes the owner evicts oldest lineage and marks
    its returns permanently unreconstructable: loss of such an object
    raises ObjectLostError naming the eviction, while younger objects
    (lineage intact) still recover."""
    cluster = Cluster(object_store_memory=_STORE)
    cluster.add_node({"CPU": 2.0})
    victim = cluster.add_node({"CPU": 2.0, "scratch": 1.0})
    # cap small enough that a handful of specs (~300B each) overflow it
    ray_tpu.init(address=cluster.gcs_addr,
                 _system_config={"max_lineage_bytes": 2048})
    try:
        cw = get_core_worker()
        # hard affinity: a soft one lets a busy run place some of the 16
        # on the other node, and an object made there outlives the victim
        # (the flake of PR 25, 29, 44, 47 and 50: `DID NOT RAISE`)
        affinity = ray_tpu.NodeAffinitySchedulingStrategy(
            victim.node_id_hex, soft=False)

        @ray_tpu.remote(scheduling_strategy=affinity)
        def produce(i):
            return np.full(200_000, i, np.uint8)

        refs = [produce.remote(i) for i in range(16)]
        ready, _ = ray_tpu.wait(refs, num_returns=len(refs), timeout=90)
        assert len(ready) == len(refs)
        # what the test is about to lose lives on the victim and nowhere else
        for ref in refs:
            assert cw.memory_store.locations.get(ref.binary()) \
                == [victim.raylet_addr], ref
        assert cw._stats_lineage_evictions > 0, \
            "16 specs against a 2KB cap must evict"
        assert cw._lineage_bytes <= 2048
        # a spec's lineage is retained when its reply arrives, so "oldest"
        # is by completion: under load a worker that is slow to start can
        # hand back task 0 after eight others, and then `refs[0]` is one of
        # the young (the other half of the flake). Ask the owner which went
        evicted = [i for i, ref in enumerate(refs)
                   if ref.binary() in cw._lineage_evicted]
        kept = [i for i, ref in enumerate(refs)
                if ref.binary() in cw._lineage_oids]
        assert len(evicted) == cw._stats_lineage_evictions and kept
        assert sorted(evicted + kept) == list(range(16))

        cluster.remove_node(victim)
        time.sleep(1.0)

        # an evicted spec → permanent loss, named as such
        with pytest.raises(ray_tpu.ObjectLostError, match="evicted"):
            ray_tpu.get(refs[evicted[0]], timeout=120)
        # one that still has lineage → full recovery
        out = ray_tpu.get(refs[kept[-1]], timeout=180)
        assert out[0] == kept[-1] and out.shape == (200_000,)
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()


def test_reconstruction_metrics_exported():
    """The ownership plane lands on /metrics: owned/borrowed gauges and
    reconstruction counters render with # TYPE lines (tsdb plane keys
    off them)."""
    ray_tpu.init(num_cpus=2, object_store_memory=_STORE)
    try:
        from ray_tpu.util.metrics import DEFAULT_REGISTRY

        keep = ray_tpu.put(np.arange(100_000, dtype=np.uint8))
        text = DEFAULT_REGISTRY.prometheus_text()
        for name in ("ray_tpu_owned_refs", "ray_tpu_lineage_bytes",
                     "ray_tpu_reconstructions_total",
                     "ray_tpu_reconstruction_failures_total",
                     "ray_tpu_objects_freed_total"):
            assert f"# TYPE {name}" in text, f"{name} missing # TYPE"
            assert f"\n{name}" in text or text.startswith(name), \
                f"{name} has no sample row"
        del keep
    finally:
        ray_tpu.shutdown()
