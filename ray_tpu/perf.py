"""Framework microbenchmark harness.

Ref analogue: python/ray/_private/ray_perf.py (task/actor-call/put
throughput) with the timeit runner of ray_microbenchmark_helpers.py:14.
Run as ``python -m ray_tpu.perf`` for the full table, or call
``run_microbenchmarks`` programmatically (tests use reduced iteration
counts).

Each entry reports ops/s (mean of ``repeat`` timed windows). The suite
exercises the real control plane: driver puts/gets through the shm arena,
task submission through the node manager, actor round-trips over the worker
socket protocol, and (when a cluster fixture adds nodes) cross-node object
pulls.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np


def timeit(
    name: str,
    fn: Callable[[], None],
    multiplier: float = 1.0,
    *,
    warmup: int = 1,
    repeat: int = 3,
    min_window_s: float = 0.5,
) -> Tuple[str, float]:
    """Run ``fn`` in timed windows and return (name, ops_per_sec * multiplier)
    (ref analogue: _private/ray_microbenchmark_helpers.py timeit)."""
    for _ in range(warmup):
        fn()
    rates: List[float] = []
    for _ in range(repeat):
        start = time.perf_counter()
        count = 0
        while True:
            fn()
            count += 1
            elapsed = time.perf_counter() - start
            if elapsed >= min_window_s:
                break
        rates.append(count * multiplier / elapsed)
    return name, sum(rates) / len(rates)


def run_microbenchmarks(
    *,
    batch: int = 100,
    payload_mb: int = 10,
    repeat: int = 3,
    min_window_s: float = 0.5,
    include: Optional[List[str]] = None,
) -> Dict[str, float]:
    """Run the suite against the already-initialized runtime. Returns
    {benchmark_name: ops_per_sec}."""
    import ray_tpu

    results: Dict[str, float] = {}

    def record(name, fn, multiplier=1.0):
        if include and not any(pat in name for pat in include):
            return
        n, rate = timeit(
            name, fn, multiplier, repeat=repeat, min_window_s=min_window_s
        )
        results[n] = rate

    # --- object store (small) ---------------------------------------------
    small_ref = ray_tpu.put(b"x")

    def get_small():
        ray_tpu.get(small_ref)

    record("single client get calls", get_small)

    def put_small():
        ray_tpu.put(0)

    record("single client put calls", put_small)

    # --- tasks ------------------------------------------------------------
    @ray_tpu.remote
    def small_value():
        return b"ok"

    def task_batch():
        ray_tpu.get([small_value.remote() for _ in range(batch)])

    record("tasks submit+get throughput", task_batch, batch)

    # --- actors -----------------------------------------------------------
    @ray_tpu.remote
    class Sink:
        def ping(self):
            return b"ok"

        def ping_arg(self, x):
            return b"ok"

    a = Sink.remote()
    ray_tpu.get(a.ping.remote())  # actor creation outside the window

    def actor_sync():
        ray_tpu.get(a.ping.remote())

    record("actor calls sync round-trip", actor_sync)

    def actor_async_batch():
        ray_tpu.get([a.ping.remote() for _ in range(batch)])

    record("actor calls pipelined throughput", actor_async_batch, batch)

    ref = ray_tpu.put(b"payload")

    def actor_arg_batch():
        ray_tpu.get([a.ping_arg.remote(ref) for _ in range(batch)])

    record("actor calls with object arg", actor_arg_batch, batch)

    # --- object store (large) — LAST: the ~GB of dead 10 MiB objects this
    # creates sits at zero refs until the GC grace passes and would spill-
    # thrash every benchmark that ran after it.
    arr = np.zeros(payload_mb * 1024 * 1024 // 8, dtype=np.int64)

    def put_large():
        ray_tpu.put(arr)

    record("single client put gigabytes", put_large, payload_mb / 1024.0)

    return results


def driver_rss_bytes() -> int:
    """Resident set size of this (driver) process. Recorded around the
    queued-task probe so the footprint of a deep queue shows up in the
    perf JSON next to its throughput (delegates to the profiler plane's
    /proc reader rather than growing a second parser)."""
    import os

    from .util.profiler import process_stats

    return int(process_stats(os.getpid()).get("rss_bytes", 0))


def run_envelope_probes(
    *,
    num_args: int = 10_000,
    num_queued: int = 100_000,
    num_returns: int = 3000,
    num_get: int = 10_000,
) -> Dict[str, float]:
    """Scalability-envelope probes at FULL reference magnitude for
    args/returns/get (ref: release/benchmarks/README.md — 10k+ object
    args to one task, 3k+ returns from one task, 10k+ plasma objects in
    one get). The queue probe defaults to 100k for suite runtime; the
    1M+ reference headline is exercised by the dedicated run
    (num_queued=1_000_000 — r5 measured 1M submit 18.7k ops/s, drain
    5.0k ops/s, 4.4 GB RSS on the 1-core sandbox)."""
    import ray_tpu

    results: Dict[str, float] = {}

    # Warm the worker pool first: a cold probe would time worker spawn
    # (~2s/process on hosts with heavy sitecustomize), not the envelope.
    @ray_tpu.remote
    def _warm():
        return None

    ray_tpu.get([_warm.remote() for _ in range(20)])

    # --- N object args to a single task (ref envelope: 10k+) -------------
    refs = [ray_tpu.put(i) for i in range(num_args)]

    @ray_tpu.remote
    def count(*xs):
        return len(xs)

    t0 = time.perf_counter()
    assert ray_tpu.get(count.remote(*refs), timeout=300) == num_args
    results[f"{num_args} object args to one task seconds"] = (
        time.perf_counter() - t0
    )
    del refs

    # --- N tasks queued on one node (ref envelope: 1M+) ------------------
    @ray_tpu.remote
    def noop():
        return None

    rss_before = driver_rss_bytes()
    t0 = time.perf_counter()
    queued = [noop.remote() for _ in range(num_queued)]
    submit_dt = time.perf_counter() - t0
    results[f"{num_queued} queued tasks submit ops/s"] = num_queued / submit_dt
    results[f"{num_queued} queued tasks rss before gb"] = rss_before / 1e9
    results[f"{num_queued} queued tasks rss after submit gb"] = (
        driver_rss_bytes() / 1e9
    )
    ray_tpu.get(queued, timeout=600)
    results[f"{num_queued} queued tasks drain ops/s"] = num_queued / (
        time.perf_counter() - t0
    )
    del queued

    # --- N returns from a single task (ref envelope: 3k+) ----------------
    @ray_tpu.remote(num_returns=num_returns)
    def fan_out():
        return tuple(range(num_returns))

    t0 = time.perf_counter()
    out = ray_tpu.get(list(fan_out.remote()), timeout=300)
    assert len(out) == num_returns
    results[f"{num_returns} returns from one task seconds"] = (
        time.perf_counter() - t0
    )

    # --- N objects in a single get (ref envelope: 10k+) ------------------
    refs = [ray_tpu.put(i) for i in range(num_get)]
    t0 = time.perf_counter()
    vals = ray_tpu.get(refs, timeout=300)
    assert len(vals) == num_get
    results[f"{num_get} objects in one get seconds"] = (
        time.perf_counter() - t0
    )
    return results


def run_cluster_benchmarks(
    cluster, *, payload_mb: int = 10, repeat: int = 3, min_window_s: float = 0.5
) -> Dict[str, float]:
    """Cross-node benchmarks over a cluster fixture with at least one node
    carrying a ``{"gadget": 1}`` resource (object pull over the peer plane)."""
    import ray_tpu

    results: Dict[str, float] = {}
    nbytes = payload_mb * 1024 * 1024

    @ray_tpu.remote(resources={"gadget": 1})
    def produce():
        return np.zeros(nbytes // 8, dtype=np.int64)

    def transfer():
        # New object each window iteration: a cached pull would measure
        # nothing.
        ray_tpu.get(produce.remote(), timeout=120)

    name, rate = timeit(
        "cross-node object transfer gigabytes",
        transfer,
        payload_mb / 1024.0,
        repeat=repeat,
        min_window_s=min_window_s,
    )
    results[name] = rate
    return results


def main():
    import ray_tpu

    ray_tpu.init(ignore_reinit_error=True)
    results = run_microbenchmarks()
    width = max(len(k) for k in results)
    for name, rate in results.items():
        unit = "GB/s" if "gigabytes" in name else "ops/s"
        print(f"{name.ljust(width)}  {rate:12.2f} {unit}")
    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
