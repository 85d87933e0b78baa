"""Test configuration.

JAX tests run on a virtual 8-device CPU mesh (multi-chip sharding is
validated without TPU hardware, per the reference's pattern of testing
multi-node semantics on one machine — SURVEY.md §4). These env vars must be
set before jax is imported anywhere in the test process.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
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


@pytest.hookimpl(optionalhook=True)
def pytest_xdist_make_scheduler(config, log):
    """Tier-1 runs ``-n 6 --dist loadfile`` on eight cores. A compile for
    the described v5e (``test_tpu_compile_<name>.py``) keeps ~3.6 cores
    busy, every other file about one, and xdist deals files by their
    number of cases, which puts the compile files, few long cases each,
    last and side by side: 2-3 times as long a case, 17 GB of host
    memory. So the first worker takes the compile files while there are
    any and the others leave them while there is anything else: one
    compile beside five other files from the run's start, and whoever
    runs out of its own kind takes the other, so no worker waits at the
    end."""
    if config.getvalue("dist") != "loadfile":
        return None
    from xdist.scheduler import LoadFileScheduling

    class CompileLane(LoadFileScheduling):
        def _assign_work_unit(self, node):
            compiles = node is self.nodes[0]
            for scope in self.workqueue:
                if ("test_tpu_compile_" in scope) == compiles:
                    self.workqueue.move_to_end(scope, last=False)
                    break
            super()._assign_work_unit(node)

    return CompileLane(config, log)


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


@pytest.fixture(scope="module")
def v5e_host():
    """The four devices of a described v5e 2x2 host (the
    ``test_tpu_compile_*.py`` files; tests/tpu_rehearsal.py)."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"cannot describe a v5e topology here: {e!r}")
    # A compile for a described device is written to the persistent
    # cache but cannot be read back without the chip (JAX warns and
    # recompiles); keep these out of it.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def v5e(v5e_host):
    """Sharding on one device of that host."""
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e_host[0])


@pytest.fixture
def as_tpu(monkeypatch):
    """The process's default backend is the CPU, so the kernels'
    platform dispatch would take the XLA path; steer it here."""
    import importlib

    # ray_tpu.ops re-exports the function under the module's own name.
    monkeypatch.setattr(
        importlib.import_module("ray_tpu.ops.flash_attention"), "_on_tpu",
        lambda: True)


@pytest.fixture(scope="session")
def tiny_model():
    """``(cfg, params)`` of ``LlamaConfig.tiny()``: what the engine's tests
    (``test_serve_llm*.py``) serve unless they say otherwise."""
    import jax

    from ray_tpu.models import LlamaConfig, init_params

    cfg = LlamaConfig.tiny()
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="session")
def bench_tiny():
    """``bench_tiny(name) -> (config, cfg, params)``: the benchmark's tiny
    model ``tests/bench_harness/<name>_tiny/config.json`` as the benchmark
    builds it, weights from ``PRNGKey(3)``, made once a process."""
    import functools
    import json

    import jax

    from benchmark import arch
    from ray_tpu.models import init_params

    @functools.lru_cache(maxsize=None)
    def build(name):
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "bench_harness", name + "_tiny",
                               "config.json")) as f:
            config = json.load(f)
        cfg = arch.program_config(config)
        return config, cfg, jax.jit(lambda key: init_params(cfg, key))(
            jax.random.PRNGKey(3))

    return build


# The engine's tests of one attention kind (``test_serve_llm_<kind>.py``)
# and ``stats()``'s key tree over all of them (``test_serve_llm.py``) serve
# the same three models.

@pytest.fixture(scope="session")
def window_model(bench_tiny):
    """1 dense + 4 expert layers of kinds S S F S S, window 32: the
    benchmark's tiny Trinity (tests/bench_harness/trinity_tiny)."""
    return bench_tiny("trinity")


@pytest.fixture(scope="session")
def latent_model(bench_tiny):
    """1 dense + 3 expert layers, q.k 24 beside v 12, ranks 24 and 32:
    the benchmark's tiny JoyAI (tests/bench_harness/joyai_tiny)."""
    return bench_tiny("joyai")


@pytest.fixture(scope="session")
def state_model(bench_tiny):
    """3 retention layers, 4 query heads on 2 KV heads of 16: the
    benchmark's tiny Brumby (tests/bench_harness/brumby_tiny)."""
    return bench_tiny("brumby")


@pytest.fixture(scope="session")
def wait_until():
    """``wait_until(predicate, timeout=60.0)``: poll until it holds."""
    import time

    def wait(predicate, timeout=60.0):
        deadline = time.time() + timeout
        while not predicate():
            assert time.time() < deadline, "timed out"
            time.sleep(0.01)

    return wait


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
