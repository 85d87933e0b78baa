"""JAX/TPU device telemetry.

Ref analogue: the reference's per-node metrics agents export GPU/GRAM
gauges from the resource monitor (src/ray/stats/metric_defs.h) — a
TPU-native runtime needs the same visibility into the accelerator plane:
HBM in use/peak/limit per device, jit compile count and cumulative
compile seconds, and collective traffic. Everything publishes through the
util/metrics.py KV pipeline, so ``util/prometheus.render()`` exposes the
series with no extra plumbing, tagged ``{node, device}``.

Sampling is passive and cheap: nothing here imports jax — ``sample()``
is a no-op unless the calling process already imported it (workers that
never touch the accelerator pay nothing). Callers on natural edges
(replica request completion, ``/metrics`` render, ``/api/devices``)
invoke :func:`maybe_sample`, which throttles to one backend query per
``min_interval_s`` per process.
"""

from __future__ import annotations

import sys
import threading
import time
from typing import Any, Dict, List, Optional

from .metrics import Counter, Gauge

DEVICE_COUNT = Gauge(
    "ray_tpu_device_count",
    "Local JAX devices visible to this process.",
    tag_keys=("node", "platform"),
)
MEMORY_IN_USE = Gauge(
    "ray_tpu_device_memory_bytes_in_use",
    "Device (HBM) bytes currently allocated, per device.",
    tag_keys=("node", "device"),
)
MEMORY_PEAK = Gauge(
    "ray_tpu_device_memory_peak_bytes",
    "Peak device (HBM) bytes allocated, per device.",
    tag_keys=("node", "device"),
)
MEMORY_LIMIT = Gauge(
    "ray_tpu_device_memory_limit_bytes",
    "Device (HBM) capacity visible to the allocator, per device.",
    tag_keys=("node", "device"),
)
MEMORY_FRAGMENTATION = Gauge(
    "ray_tpu_device_memory_fragmentation_ratio",
    "Allocator fragmentation per device: reserved-but-not-live fraction "
    "of the arena (1 - live/reserved at peak). High values mean the "
    "allocator holds far more HBM than live buffers need — the failure "
    "mode that OOMs deep scan schedules.",
    tag_keys=("node", "device"),
)
JIT_COMPILES = Counter(
    "ray_tpu_device_jit_compiles_total",
    "XLA compilations observed through instrumented_jit().",
    tag_keys=("node", "fn"),
)
JIT_COMPILE_SECONDS = Counter(
    "ray_tpu_device_jit_compile_seconds_total",
    "Wall seconds spent in calls that triggered an XLA compile.",
    tag_keys=("node", "fn"),
)
COLLECTIVE_CALLS = Counter(
    "ray_tpu_device_collective_calls_total",
    "Collective ops issued through parallel.collectives (in-graph ops "
    "count once per trace, host-level ops once per call).",
    tag_keys=("node", "op"),
)
COLLECTIVE_BYTES = Counter(
    "ray_tpu_device_collective_bytes_total",
    "Payload bytes moved by host-level collectives (barrier/broadcast "
    "over the control-plane KV).",
    tag_keys=("node", "op"),
)

_lock = threading.Lock()
_last_sample = 0.0


def node_tag() -> str:
    """Short hex id of this process's node, or "local" off-cluster."""
    try:
        from ..core import runtime_context

        rt = runtime_context.current_runtime_or_none()
        if rt is not None:
            return rt.node_id.hex()[:8]
    except Exception:
        pass
    return "local"


def _memory_stats(device) -> Optional[Dict[str, Any]]:
    """PJRT allocator stats: a dict on TPU, None on the CPU backend."""
    return device.memory_stats()


def fragmentation_from_stats(stats: Dict[str, Any]) -> Optional[float]:
    """Allocator fragmentation ratio from a PJRT ``memory_stats()`` dict,
    or None when the backend exposes too little. Preference order:

    1. ``peak_bytes_in_use`` vs ``peak_bytes_reserved`` — the reserved
       arena the allocator grew to versus the live bytes it actually
       held at peak (the "43-46% fragmentation" number in XLA's own OOM
       diagnostics).
    2. ``bytes_in_use`` vs ``bytes_reserved`` — the instantaneous pair.
    3. ``largest_free_block_bytes`` vs free bytes under ``bytes_limit``
       — how shattered the remaining arena is.
    """
    peak_live = stats.get("peak_bytes_in_use")
    peak_reserved = stats.get("peak_bytes_reserved")
    if peak_reserved and peak_live is not None and peak_reserved > 0:
        return max(0.0, 1.0 - float(peak_live) / float(peak_reserved))
    live = stats.get("bytes_in_use")
    reserved = stats.get("bytes_reserved")
    if reserved and live is not None and reserved > 0:
        return max(0.0, 1.0 - float(live) / float(reserved))
    limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
    largest_free = stats.get("largest_free_block_bytes")
    if limit and live is not None and largest_free is not None:
        free = float(limit) - float(live)
        if free > 0:
            return max(0.0, 1.0 - float(largest_free) / free)
    return None


def hbm_snapshot(device=None) -> Dict[str, Any]:
    """One device's allocator state as a plain dict — the bench's
    fragmentation probe (recorded into BENCH ab_matrix rows) and the
    payload behind the fragmentation gauge. Empty dict only where the
    backend keeps no memory_stats (CPU); a backend that fails to
    answer raises."""
    if device is None:
        import jax

        device = jax.local_devices()[0]
    stats = _memory_stats(device)
    if not stats:
        return {}
    out: Dict[str, Any] = {}
    for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
                "peak_bytes_reserved", "bytes_limit",
                "bytes_reservable_limit", "largest_free_block_bytes",
                "largest_alloc_size", "num_allocs"):
        if key in stats:
            try:
                out[key] = int(stats[key])
            except (TypeError, ValueError):
                pass
    frag = fragmentation_from_stats(stats)
    if frag is not None:
        out["fragmentation"] = round(frag, 4)
    return out


def sample(force: bool = False) -> List[Dict[str, Any]]:
    """Publish per-device gauges for this process and return the device
    snapshot (also the payload of the dashboard's ``/api/devices``).
    Unless ``force``, does nothing in processes that never imported jax
    — sampling must not be the thing that drags the backend in."""
    if not force and "jax" not in sys.modules:
        return []
    try:
        import jax

        devices = jax.local_devices()
    except Exception:
        return []
    node = node_tag()
    by_platform: Dict[str, int] = {}
    out: List[Dict[str, Any]] = []
    for d in devices:
        platform = getattr(d, "platform", "unknown")
        by_platform[platform] = by_platform.get(platform, 0) + 1
        dev_tag = f"{platform}:{getattr(d, 'id', len(out))}"
        info: Dict[str, Any] = {"device": dev_tag, "platform": platform}
        stats = _memory_stats(d)
        if stats:
            tags = {"node": node, "device": dev_tag}
            in_use = stats.get("bytes_in_use")
            peak = stats.get("peak_bytes_in_use")
            limit = stats.get("bytes_limit") or stats.get(
                "bytes_reservable_limit"
            )
            if in_use is not None:
                MEMORY_IN_USE.set(float(in_use), tags=tags)
                info["bytes_in_use"] = int(in_use)
            if peak is not None:
                MEMORY_PEAK.set(float(peak), tags=tags)
                info["peak_bytes_in_use"] = int(peak)
            if limit is not None:
                MEMORY_LIMIT.set(float(limit), tags=tags)
                info["bytes_limit"] = int(limit)
            frag = fragmentation_from_stats(stats)
            if frag is not None:
                MEMORY_FRAGMENTATION.set(frag, tags=tags)
                info["fragmentation"] = round(frag, 4)
        out.append(info)
    for platform, n in by_platform.items():
        DEVICE_COUNT.set(float(n), tags={"node": node,
                                         "platform": platform})
    return out


def maybe_sample(min_interval_s: float = 5.0) -> None:
    """Throttled :func:`sample` for hot paths (request completion,
    exposition render): at most one backend query per interval."""
    global _last_sample
    now = time.monotonic()
    with _lock:
        if now - _last_sample < min_interval_s:
            return
        _last_sample = now
    try:
        sample()
    except Exception:
        pass


def record_collective(op: str, nbytes: Optional[int] = None) -> None:
    """Count one collective op (and payload bytes when known). Called by
    parallel/collectives.py; in-graph ops fire at trace time."""
    tags = {"node": node_tag(), "op": op}
    COLLECTIVE_CALLS.inc(1, tags=tags)
    if nbytes:
        COLLECTIVE_BYTES.inc(float(nbytes), tags=tags)


def instrumented_jit(fn, *, sample_memory: bool = False,
                     tap_stride: int = 1, **jit_kwargs):
    """``jax.jit`` with compile telemetry: calls that grow the jitted
    function's executable cache (a trace+compile happened) bump the
    compile counter and attribute the call's wall time to cumulative
    compile seconds. This is the runtime-controlled compile path — the
    serving stack jits through here so recompiles (new batch shape, new
    model) are visible in ``/metrics`` instead of silent latency spikes.

    ``sample_memory=True`` additionally publishes the per-device HBM
    gauges (in-use / peak / limit / fragmentation) right after every
    compile and, throttled through :func:`maybe_sample`, on steady-state
    calls — the train-step wiring, so ``rtpu metrics`` shows train
    compile cache behavior AND the step's device footprint. It defaults
    off: the decode hot loop calls this wrapper once per generated token
    and must not pay a lock per call (the 695→652 tok/s regression).

    ``tap_stride=N`` (N>1) batches the per-call tap into a ring flushed
    once every N calls — the decode-loop wiring (ISSUE 12 satellite):
    instead of polling the executable cache around EVERY token step,
    the wrapper accumulates the window's slowest call and polls once
    per flush. A compile inside the window is still detected (cache
    growth is persistent) and its wall time attributed from the
    window's slowest call — which IS the compiling call, orders of
    magnitude over a steady step. ``wrapped.flush_taps()`` forces a
    flush at a burst boundary (the serve engine calls it when the
    decode loop goes idle), so telemetry lags by at most one burst,
    never indefinitely.

    The wrapper sits INSIDE decode hot loops (one call per generated
    token), so the steady-state tap is kept minimal: metric handles and
    tags resolve once (``with_tags`` bound recorders, created lazily on
    the first compile — by then the runtime's node id is known), and the
    executable-cache size is polled against a remembered value instead
    of twice around each call. The serve regression traced to exactly
    this tap (695 -> 652 tok/s when it re-resolved handles per token).
    """
    import functools

    import jax

    jitted = jax.jit(fn, **jit_kwargs)
    name = getattr(fn, "__name__", "jit")
    cache_size = jitted._cache_size

    # [last_seen_cache_size, bound_compiles, bound_seconds, countdown,
    # window_max_dt]; a mutable cell instead of nonlocal keeps the
    # closure allocation-free per call. The flush (stride boundary OR
    # an external stats()/shutdown thread) serializes on _flush_lock so
    # two concurrent flushes cannot double-count a compile against the
    # same stale before-size — the per-call path stays lock-free.
    state = [None, None, None, tap_stride, 0.0]
    _flush_lock = threading.Lock()

    def _flush_taps():
        """Poll the executable cache once for the whole window and
        publish any compile it detected. Safe to call from any thread
        at any burst boundary; resets the ring."""
        with _flush_lock:
            _flush_taps_locked()

    def _flush_taps_locked():
        state[3] = tap_stride
        before = state[0]
        if before is None:
            return
        after = state[0] = cache_size()
        window_dt, state[4] = state[4], 0.0
        if after > before:
            if state[1] is None:
                tags = {"node": node_tag(), "fn": name}
                state[1] = JIT_COMPILES.with_tags(**tags)
                state[2] = JIT_COMPILE_SECONDS.with_tags(**tags)
            state[1].inc(after - before)
            state[2].inc(window_dt)
            if sample_memory:
                sample(force=True)
        elif sample_memory:
            maybe_sample()

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        if state[0] is None:
            state[0] = cache_size()
        t0 = time.perf_counter()
        out = jitted(*args, **kwargs)
        dt = time.perf_counter() - t0
        if dt > state[4]:
            state[4] = dt
        state[3] -= 1
        if state[3] <= 0:
            _flush_taps()
        return out

    wrapped.__wrapped_jit__ = jitted  # AOT API (lower/compile) passthrough
    wrapped.flush_taps = _flush_taps
    return wrapped
