"""How workers are launched towards the accelerator: one chip detector,
the platform pin and the compile cache in a worker's environment."""

import os

import pytest

import ray_tpu
from ray_tpu.core import tpu

CACHE = tpu.COMPILE_CACHE_ENV


def _touch(path):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    open(path, "w").close()


@pytest.mark.parametrize("files, want", [
    (["vfio/vfio"], 0),                       # control node only: no chip
    (["vfio/vfio", "vfio/0"], 1),
    (["vfio/vfio", "vfio/0", "vfio/1", "vfio/2", "vfio/3"], 4),
    (["accel0", "accel1", "vfio/vfio", "vfio/7"], 2),  # accel files win
])
def test_local_chip_count(tmp_path, monkeypatch, files, want):
    monkeypatch.delenv("TPU_CHIPS_PER_HOST_OVERRIDE", raising=False)
    monkeypatch.setattr(tpu, "_DEV", str(tmp_path))
    for f in files:
        _touch(str(tmp_path / f))
    assert tpu.local_chip_count() == want


def test_init_advertises_the_detected_chips(tmp_path, monkeypatch):
    """A bare init() uses the same detector: one chip plus the VFIO
    control node is ``TPU: 1``, not 2."""
    monkeypatch.delenv("TPU_CHIPS_PER_HOST_OVERRIDE", raising=False)
    monkeypatch.setattr(tpu, "_DEV", str(tmp_path))
    _touch(str(tmp_path / "vfio" / "vfio"))
    _touch(str(tmp_path / "vfio" / "0"))
    ray_tpu.init(num_cpus=1, system_config={"num_prestart_workers": 0})
    try:
        assert ray_tpu.cluster_resources()["TPU"] == 1
    finally:
        ray_tpu.shutdown()


@pytest.mark.parametrize("worker_type, parent, want", [
    # Nothing set outside: tpu workers must get the chip or fail, and
    # share one fixed cache directory; cpu workers never open the chip.
    ("tpu", {}, {"JAX_PLATFORMS": "tpu,cpu",
                 CACHE: tpu.DEFAULT_COMPILE_CACHE_DIR}),
    ("cpu", {}, {"JAX_PLATFORMS": "cpu"}),
    # The test suite's pin reaches tpu workers.
    ("tpu", {"JAX_PLATFORMS": "cpu"}, {"JAX_PLATFORMS": "cpu"}),
    ("cpu", {"JAX_PLATFORMS": "tpu"}, {"JAX_PLATFORMS": "cpu"}),
    # A cache placed from outside is inherited; no code sets another.
    ("tpu", {CACHE: "/elsewhere"}, {"JAX_PLATFORMS": "tpu,cpu"}),
    ("cpu", {CACHE: "/elsewhere"}, {"JAX_PLATFORMS": "cpu"}),
])
def test_worker_jax_env(worker_type, parent, want):
    assert tpu.worker_jax_env(worker_type, parent) == want
    merged = {**parent, **tpu.worker_jax_env(worker_type, parent)}
    if CACHE in parent:
        assert merged[CACHE] == parent[CACHE]


def test_default_cache_dir_is_fixed_and_ignored():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert tpu.DEFAULT_COMPILE_CACHE_DIR == os.path.join(repo, ".jax_cache")
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


@ray_tpu.remote
def _jax_env():
    return {k: os.environ.get(k) for k in ("JAX_PLATFORMS", CACHE)}


@ray_tpu.remote
def _first_device():
    import jax

    return jax.devices()[0].platform


@pytest.fixture
def node_with_no_pin(monkeypatch):
    """A node whose own environment names no platform, as on a TPU
    host, advertising one chip it does not have."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.delenv(CACHE, raising=False)
    rt = ray_tpu.init(num_cpus=2, num_tpus=1,
                      system_config={"num_prestart_workers": 0})
    yield rt
    ray_tpu.shutdown()


def test_spawned_workers_carry_the_env(node_with_no_pin):
    cpu, chip = ray_tpu.get([
        _jax_env.remote(), _jax_env.options(num_tpus=1).remote(),
    ], timeout=60)
    assert cpu == {"JAX_PLATFORMS": "cpu", CACHE: None}
    assert chip == {"JAX_PLATFORMS": "tpu,cpu",
                    CACHE: tpu.DEFAULT_COMPILE_CACHE_DIR}


def test_tpu_worker_without_a_chip_is_an_error(node_with_no_pin):
    """No chip here: a ``tpu`` worker's jax must fail to initialise, not
    hand back the CPU."""
    with pytest.raises(Exception, match="Unable to initialize backend"):
        ray_tpu.get(_first_device.options(num_tpus=1).remote(), timeout=120)
    assert ray_tpu.get(_first_device.remote(), timeout=120) == "cpu"
