"""Test configuration: force an 8-device virtual CPU mesh before jax import.

Mirrors the reference's mock_tsdb_system strategy (SURVEY.md §4: distributed
executor tested without a cluster): sharding/collective logic runs on
xla_force_host_platform_device_count=8 CPU devices.  What only a chip can
show — the TPU backend, x64 off, Mosaic-compiled Pallas — is exercised by
chip_smoke.py and tools/pallas_chip_check.py on hardware.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

# x64 on the CPU test mesh for exact float64/int64 parity with numpy oracles.
# The package never turns x64 on: a server runs with it off (the device
# computes in float32), which tests reach in subprocesses
# (tests/test_bringup.py) or under jax.enable_x64(False)
# (tests/test_served_contract.py holds the served numeric contract so).
# No product path is chosen by the flag: it decides a dtype
# (models/templates.py compute_dtype, jax's own canonicalization), never
# which code reads a column.
jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture(scope="session", autouse=True)
def _lockdep_session_gate():
    """OGT_LOCKDEP=1 turns the whole suite into a deadlock regression
    test: any lock-order cycle or non-annotated blocking-under-hot-lock
    witnessed by ANY test fails the session at teardown."""
    yield
    from opengemini_tpu.utils import lockdep

    if lockdep.enabled():
        lockdep.check()  # raises LockdepError with every report


@pytest.fixture
def encode_pool_on(monkeypatch):
    """Force the encode pool (storage/encodepool.py) live even on
    single/dual-core CI boxes; shuts the forced pool down on teardown so
    tests don't orphan worker threads."""
    from opengemini_tpu.storage import encodepool

    prev = encodepool._pool
    monkeypatch.setattr(encodepool, "WORKERS", 4)
    monkeypatch.setattr(encodepool, "_pool", None)
    yield
    forced = encodepool._pool
    monkeypatch.setattr(encodepool, "_pool", None)
    # never shut down the pre-test process-global pool: a test that
    # reverted the _pool patch mid-test (monkeypatch.undo) could leave
    # it installed here, and shutting it down would poison every later
    # flush in the session
    if forced is not None and forced is not prev:
        forced.shutdown(wait=False)
