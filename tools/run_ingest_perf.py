"""Ingest benchmark: shared-memory store -> jax arrays, bytes/s.

Measures the data-plane hand-off VERDICT r4 #10 asks for (SURVEY.md
§5.8's zero-copy host->HBM differentiator):

1. CPU backend: ``iter_jax_batches(zero_copy=True)`` imports the
   store-backed numpy views via dlpack (the jax array ALIASES the store
   pages — no copy) vs the ``jnp.asarray`` copying path.
2. Accelerator (when one is attached): ``device_put`` DMA fed directly
   from the 64-byte-aligned shm views (the store's layout exists for
   this) — the host->HBM ingest rate.

Usage: python tools/run_ingest_perf.py [out.json]
"""

from __future__ import annotations

import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _make_ds(total_mb: int, block_mb: int):
    import numpy as np

    import ray_tpu.data as rd

    rows_per_block = block_mb * 1024 * 1024 // (1024 * 4)
    nblocks = total_mb // block_mb
    arr = np.random.RandomState(0).rand(
        nblocks * rows_per_block, 1024
    ).astype(np.float32)
    return rd.from_numpy(arr, override_num_blocks=nblocks), arr.nbytes


def _consume(ds, *, zero_copy, batch_size, device=None) -> float:
    """Returns seconds to pull every batch onto the jax side (blocking
    on the LAST array only — transfers pipeline like training would)."""
    import jax

    t0 = time.perf_counter()
    last = None
    for batch in ds.iter_jax_batches(batch_size=batch_size,
                                     zero_copy=zero_copy,
                                     device=device,
                                     drop_last=False):
        last = batch
    # Transfers complete in order: the last batch landing closes the
    # window.
    jax.block_until_ready(last)
    return time.perf_counter() - t0


def run(total_mb: int = 512, block_mb: int = 32) -> dict:
    import jax

    out = {}
    backend = jax.default_backend()
    out["backend"] = backend

    import ray_tpu

    # This process has opened the device and consumes the batches
    # itself: the node advertises no TPU, so no worker can ask for it.
    ray_tpu.init(num_cpus=2, num_tpus=0,
                 system_config={"log_to_driver": False})
    try:
        from ray_tpu.data.context import DataContext

        ds, nbytes = _make_ds(total_mb, block_mb)
        ds = ds.materialize()  # blocks in the shm store; measure READS
        # Local consumption: iteration pulls store views directly — the
        # measurement is the store->jax hand-off, not task re-execution.
        DataContext.get_current().use_remote_tasks = False
        batch = block_mb * 1024 * 1024 // (1024 * 4)  # batch == block

        # Warm both paths once (compile/caches out of the window).
        _consume(ds, zero_copy=False, batch_size=batch)
        dt_copy = _consume(ds, zero_copy=False, batch_size=batch)
        out["asarray_copy_gbps"] = nbytes / dt_copy / 1e9
        if backend == "cpu":
            _consume(ds, zero_copy=True, batch_size=batch)
            dt_dl = _consume(ds, zero_copy=True, batch_size=batch)
            out["dlpack_zero_copy_gbps"] = nbytes / dt_dl / 1e9
            out["speedup"] = dt_copy / dt_dl
        else:
            dev = jax.devices()[0]
            _consume(ds, zero_copy=False, batch_size=batch, device=dev)
            dt_dma = _consume(ds, zero_copy=False, batch_size=batch,
                              device=dev)
            out["device_put_hbm_ingest_gbps"] = nbytes / dt_dma / 1e9
        out["total_mb"] = total_mb
        out["block_mb"] = block_mb
    finally:
        ray_tpu.shutdown()
    return out


if __name__ == "__main__":
    res = run()
    print(json.dumps(res, indent=1))
    if len(sys.argv) > 1:
        with open(sys.argv[1], "w") as f:
            json.dump(res, f, indent=1)
