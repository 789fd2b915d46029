"""Rehearsal of `chip_smoke.py` without the chip (on-chip-measurement guide,
section 2, rehearsals 1 and 2): its phase functions end to end at a tiny
size on the CPU, the four-device phases on virtual devices. Run this before
a chip call after touching `chip_smoke.py` or what it drives:

    JAX_PLATFORMS=cpu python -m pytest tests/test_chip_smoke_rehearsal.py -m slow

Each phase starts its own cluster, so the file is `slow` and outside tier-1.
On the CPU every phase must FAIL — and only for the device: the checks that
say "not the TPU" are the ones a chip run exists to pass.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402
from ray_tpu._private import accelerators  # noqa: E402

pytestmark = pytest.mark.slow

TINY_TRAIN = {"preset": "tiny", "batch": 4, "seq": 128, "steps": 4}
TINY_SERVE = {
    "model_config": {"n_layer": 2, "n_head": 4, "n_kv_head": 2,
                     "d_model": 64, "vocab_size": 512, "max_seq_len": 128},
    "engine_config": {"batch_buckets": (1, 4, 8), "prefill_buckets": (8, 16),
                      "prefill_chunk": 16},
    "prompt_lens": (3, 9, 14, 40),
    "max_new_tokens": 8,
}
# what only a chip can pass
_DEVICE_ONLY = ("not the TPU", "devices, wants", "tpu_custom_call",
                "dense path", "distinct chips")


def _not_about_the_device(failures):
    return [f for f in failures if not any(s in f for s in _DEVICE_ONLY)]


@pytest.fixture
def fake_chips(monkeypatch):
    """`ray_tpu.init()` advertises four chips: workers are granted them and
    still compute on the CPU this environment holds jax to."""
    monkeypatch.setattr(accelerators, "num_local_chips", lambda: 4)


def test_train_phase_tiny(fake_chips):
    rec = chip_smoke.train_phase(TINY_TRAIN, seed=0)
    assert rec["platform"] == "cpu"
    assert any("not the TPU" in f for f in rec["failures"])
    assert any("dense path" in f for f in rec["failures"])
    assert not _not_about_the_device(rec["failures"]), rec
    assert rec["cache_stats"]["retraces"] == 0
    assert rec["losses"][-1] < rec["losses"][0]


def test_sharded_train_phase_on_four_virtual_devices(fake_chips):
    rec = chip_smoke.train_phase(TINY_TRAIN, 0, 4)
    assert not _not_about_the_device(rec["failures"]), rec
    assert rec["mesh"] == {"fsdp": 2, "tp": 2}
    assert rec["carry_arrays_split"] > 0
    assert abs(rec["losses"][0] - rec["one_device_loss"]) < \
        1e-2 * rec["one_device_loss"]


@pytest.mark.parametrize("replicas", [1, 4])
def test_serve_phase_tiny(replicas):
    rec = chip_smoke.serve_phase(TINY_SERVE, 0, replicas)
    assert rec["platform"] == "cpu"
    assert not _not_about_the_device(rec["failures"]), rec
    assert rec["kv_arena_bytes_on_device"] > 0
    if replicas > 1:
        assert rec["replicas_that_answered"] >= 2


def test_replica_beside_a_chip_refuses_the_host(fake_chips):
    """With chips advertised the replica is granted one; held to the CPU
    its constructor raises (`test_chip_startup` holds the message), so the
    phase dies with its replica instead of serving slowly."""
    import ray_tpu

    with pytest.raises(ray_tpu.ActorDiedError):
        chip_smoke.serve_phase(TINY_SERVE, 0, 1)
