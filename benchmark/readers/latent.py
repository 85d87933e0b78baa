"""Readers of a model with latent attention (PR 42): the decode step's
attention over the latent pool, from the device trace and the engine's
counters, and what a token really holds in that pool, from the engine's
gauge.

Every reader returns None where there is nothing to read (an engine
without the gauge or the counters, a configuration whose counts lack the
functions, a run that was not traced, a trace without the kernel) and
never raises for that.

The kernel is found in the trace by the reducer's stable name
(``trace_reduce.stable_name``: ``pallas`` and the shapes a custom call
writes): the latent walk writes a slot's rows [B, H, values] and the one
pool [L, P, page, W], three dimensions and four. The page walk writes
two pools of five dimensions, the flash forward kernel a float32
[B*H, 1, S] of three, the grouped matmul two dimensions in all.
"""

import re

from .. import arch, flops
from .engine import _delta, _per_step

LATENT_WALK = re.compile(
    r"^pallas_[a-z0-9]+_[0-9]+_[0-9]+_[0-9]+_[a-z0-9]+(_[0-9]+){4}$")


def _walk_s(record):
    """Device seconds of the latent walk's calls in the trace."""
    trace = record["trace"]
    if not trace:
        return None
    return sum(s for name, _, s in trace["ops"]
               if LATENT_WALK.match(name)) or None


def latent_walk_time_share(record):
    """The latent walk's share of the device's busy time."""
    walk = _walk_s(record)
    if walk is None:
        return None
    return 100.0 * walk / record["trace"]["busy_s"]


def latent_walk_roofline(record):
    """The least time for the absorbed attention of the decode steps in
    the trace (the rows the engine counted a step, each read once, every
    head's query against a row and its probability against the row's
    latent) over the latent walk's time."""
    walk = _walk_s(record)
    counts = arch.counts(record["config"])
    rows = _per_step(record, _delta(record, "decode_kv_rows_read"))
    if walk is None or rows is None or not all(
            hasattr(counts, f) for f in ("latent_walk_flops",
                                         "latent_walk_bytes")):
        return None
    config = record["config"]
    rows *= len(record["trace"]["modules"].get("decode_step", ()))
    least = flops.roofline_s(
        counts.latent_walk_flops(config, rows),
        counts.latent_walk_bytes(config, rows),
        flops.peaks(record["worker"]["device"]["kind"]))
    return 100.0 * least / walk


def latent_row_bytes(record):
    """What a token holds in one layer of the latent pool: the pool's
    bytes over its tokens and layers, as the engine allocated it."""
    return record["worker"]["engine"].get("kv_row_bytes", {}).get("latent")
