"""What runs inside the process that owns the chip, shared by the jobs.

``CompileLog``, ``device_facts`` and ``zipf_tokens`` are copies of
chip_smoke.py's (PR 21), which ran on the v5e; the benchmark imports
nothing from that script so that a later PR cannot change the yardstick
through it.
"""

from __future__ import annotations

import glob
import os
import shutil
import tempfile
import time
from typing import Dict, Mapping, Optional

from . import arch


def llama_config(config: Mapping):
    """The program's ``LlamaConfig`` from a configuration file's
    published keys and its trainer settings."""
    import jax.numpy as jnp

    from ray_tpu.models import LlamaConfig

    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=arch.counts(config).head_dim(config),
        rope_theta=config["rope_theta"],
        rms_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["dtype"]),
        **{k: v for k, v in (config.get("trainer") or {}).items()
           if k != "learning_rate"},
    )


def device_facts(chips: int) -> Dict:
    """This process's devices as jax reports them; raises unless they
    are TPUs and at least ``chips`` of them."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise RuntimeError(
            f"the cell needs {chips} TPU chip(s); jax found {len(devices)} "
            f"{devices[0].platform!r} device(s) ({devices[0].device_kind})"
        )
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes() -> int:
    """Peak bytes on the fullest chip: this libtpu counts live arrays
    under ``in_use`` and loaded programs' temporaries under
    ``reserved``."""
    import jax

    peaks = []
    for device in jax.local_devices():
        stats = device.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use", 0)
                     + stats.get("peak_bytes_reserved", 0))
    return max(peaks)


class CompileLog:
    """Counts what jax's own monitoring events say about compilation in
    this process: programs compiled or loaded from the persistent cache,
    the seconds that took, and cache hits and misses."""

    def __init__(self):
        import jax

        self.hits = 0
        self.misses = 0
        self.programs = 0
        self.seconds = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += seconds

    def facts(self) -> Dict:
        import jax

        return {"cache_hits": self.hits, "cache_misses": self.misses,
                "programs": self.programs, "programs_loaded_s": self.seconds,
                "cache_dir": jax.config.jax_compilation_cache_dir}


def zipf_tokens(vocab: int, shape, seed: int):
    """Token ids with Zipfian unigram statistics, so a few steps of
    training have something to learn (uniform noise has nothing)."""
    import numpy as np

    p = 1.0 / np.arange(1, vocab + 1)
    return np.random.default_rng(seed).choice(
        vocab, size=shape, p=p / p.sum()
    ).astype(np.int32)


def prng_key(seed: int):
    """The key weights are made from; seeds beyond int32 fold back."""
    import jax

    return jax.random.PRNGKey(seed % (2 ** 31 - 1))


class Tracer:
    """One profiler trace of this process, reduced as soon as it stops.
    The Python tracer is off: it records every function call and slows
    the host loop it is meant to observe. ``stop_trace_s`` is what
    ``jax.profiler.stop_trace()`` itself took: it grows with the events
    in the window, so with the steps a faster loop puts there."""

    def __init__(self):
        self.dir: Optional[str] = None

    def start(self) -> None:
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)

    def stop(self) -> Dict:
        import jax

        from . import trace_reduce

        started = time.perf_counter()
        jax.profiler.stop_trace()
        stop_trace_s = time.perf_counter() - started
        try:
            path, = glob.glob(os.path.join(
                self.dir, "plugins", "profile", "*", "*.xplane.pb"))
            return {**trace_reduce.reduce_trace(trace_reduce.load(path)),
                    "stop_trace_s": stop_trace_s}
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir = None
