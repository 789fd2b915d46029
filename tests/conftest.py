"""Test configuration.

JAX-facing tests run on a virtual 8-device CPU mesh (the reference's
`ray_start_cluster`-style multi-node-on-one-machine testing mechanism,
adapted to device meshes): set platform/device-count env vars before jax is
imported anywhere.
"""

import os

# Force CPU unconditionally: the environment may point JAX_PLATFORMS at real
# TPU hardware, and jax reads the variable when a test first imports it.
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402

# Suites exercising the lock-heavy planes run under the runtime lock-order
# validator (ray_tpu/_private/lockdep.py): every Lock/RLock created during
# the test joins the order graph, and any A→B / B→A inversion fails the
# test with both witness stacks. Record-only in-process (raise_on_cycle
# off) so the failure is attributed at teardown instead of perturbing
# control flow mid-test; worker daemons self-install via RAY_TPU_LOCKDEP=1
# in their inherited environment and raise in-daemon.
_LOCKDEP_SUITES = ("test_chaos", "test_object_store", "test_rpc_batch",
                   "test_multitenant", "test_ownership",
                   "test_dispatch_ring", "test_slo")


@pytest.fixture(autouse=True)
def _lockdep_gate(request):
    if request.module.__name__ not in _LOCKDEP_SUITES:
        yield
        return
    from ray_tpu._private import lockdep

    already = lockdep.enabled()
    if not already:
        lockdep.install(raise_on_cycle=False)
    os.environ[lockdep.ENV_VAR] = "1"
    try:
        yield
    finally:
        reports = lockdep.cycle_reports()
        os.environ.pop(lockdep.ENV_VAR, None)
        if not already:
            lockdep.uninstall()
        assert not reports, (
            "lockdep: lock-order cycle(s) detected:\n\n"
            + "\n\n".join(reports))


@pytest.fixture
def shm_store():
    """A fresh native shared-memory store, destroyed at teardown."""
    from ray_tpu._private.object_store import ObjectStore

    name = f"/ray_tpu_test_{os.getpid()}_{os.urandom(4).hex()}"
    store = ObjectStore.create(name, capacity=64 * 1024 * 1024, table_size=4096)
    yield store
    store.destroy()


@pytest.fixture
def ray_start():
    """Start a single-node ray_tpu cluster for the duration of a test."""
    import ray_tpu

    ray_tpu.init(num_cpus=4, object_store_memory=128 * 1024 * 1024)
    yield ray_tpu
    ray_tpu.shutdown()
