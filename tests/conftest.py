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


@pytest.fixture(scope="session")
def naive_greedy():
    """``naive_greedy(params, prompt, cfg, n) -> n`` token ids: greedy
    decoding the plain way, the model's full ``forward`` over the whole
    sequence for every token and nothing cached: the reference the
    serving programs and the engine are compared with. The sequence
    stands in a row of ``pad_to`` tokens: causal attention keeps what
    lies to the right of a position from it, and one shape is one
    compile a model."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import forward

    @functools.lru_cache(maxsize=None)
    def next_token(cfg):
        return jax.jit(lambda params, row, n: jnp.argmax(
            forward(params, row[None], cfg)[0][0, n - 1]))

    def greedy(params, prompt, cfg, n, pad_to=64):
        row = np.zeros(pad_to, np.int32)
        row[:len(prompt)] = prompt
        for at in range(len(prompt), len(prompt) + n):
            row[at] = next_token(cfg)(params, jnp.asarray(row), at)
        return row[len(prompt):len(prompt) + n].tolist()

    return greedy
