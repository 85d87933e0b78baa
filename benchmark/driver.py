"""The driver's side of a run: bring the system up, tear it down.

This process never imports jax: the worker owns the chip. Copied from
chip_smoke.py (PR 21): ``build_native``, ``wait_chip_released``.
"""

from __future__ import annotations

import contextlib
import os
import shutil
import time

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class NoChip(RuntimeError):
    """The machine does not hold the chips the cell asks for."""


def require_chips(chips: int) -> None:
    from ray_tpu.core.tpu import local_chip_count

    found = local_chip_count()
    if found < chips:
        raise NoChip(
            f"this cell needs {chips} TPU chip(s) and the machine shows "
            f"{found}: the benchmark measures the chip and has no CPU mode"
        )


def build_native() -> None:
    """``make native`` from the tracked sources where there is a
    toolchain; without one the pure-Python store and pump run."""
    from ray_tpu import _native

    if shutil.which("make") and shutil.which("g++"):
        proc = _native.build()
        if proc.returncode != 0:
            raise RuntimeError(
                "make native failed:\n" + (proc.stderr or proc.stdout)[-2000:])
    else:
        os.environ["RAY_TPU_NO_NATIVE_BUILD"] = "1"


@contextlib.contextmanager
def system(chips: int, system_config=None):
    """``ray_tpu.init`` .. ``shutdown`` with the compile cache at a
    fixed path inside the checkout, whatever the machine had set.
    ``system_config`` goes to ``init`` and, as ``RAY_TPU_<KEY>``, into the
    environment: workers read their settings from there, and ``init``'s
    dict does not reach them (PERF.md, open questions)."""
    import ray_tpu
    from ray_tpu.core.tpu import require_driver_off_jax

    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        CHECKOUT, ".jax_cache")
    # No size cap: under the chip machine's cap jax refused to store the
    # serving programs, and every run compiled them again (PR 24).
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    for key, value in (system_config or {}).items():
        os.environ[f"RAY_TPU_{key.upper()}"] = str(value)
    build_native()
    require_driver_off_jax()
    ray_tpu.init(num_cpus=8, num_tpus=chips, system_config={
        "log_to_driver": False, **(system_config or {})})
    try:
        yield
    finally:
        ray_tpu.shutdown()


def _exited(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/stat") as f:
            # "pid (comm) state ...": a zombie has released its devices.
            return f.read().rsplit(")", 1)[1].split()[0] == "Z"
    except FileNotFoundError:
        return True


def wait_chip_released(pid: int, timeout: float = 60.0) -> None:
    """Block until the worker that held the chip has exited and no
    ``tpu`` worker is left in the pool."""
    from ray_tpu.util import state

    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        live = [w for w in state.list_workers(
                    filters=[("worker_type", "=", "tpu")])
                if w["state"] != "dead"]
        if _exited(pid) and not live:
            return
        time.sleep(0.1)
    raise RuntimeError(
        f"tpu worker pid {pid} still holds the chip {timeout}s after the "
        f"run ended (live tpu workers: {live})")
