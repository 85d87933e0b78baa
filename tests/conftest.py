"""Test configuration.

JAX tests run on a virtual 8-device CPU mesh (multi-chip sharding is
validated without TPU hardware, per the reference's pattern of testing
multi-node semantics on one machine — SURVEY.md §4). These env vars must be
set before jax is imported anywhere in the test process.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long multi-node chaos/drain tests (tier-1 runs -m 'not "
        "slow'; `make chaos` runs them)",
    )


@pytest.fixture
def ray_tpu_start():
    """Boot a real single-node runtime per test (ref analogue: the
    ray_start_regular fixture, python/ray/tests/conftest.py:411)."""
    import ray_tpu

    rt = ray_tpu.init(
        num_cpus=4,
        system_config={
            "num_prestart_workers": 2,
            "refcount_flush_interval_s": 0.1,
            "gc_grace_period_s": 1.0,
        },
    )
    yield rt
    ray_tpu.shutdown()
