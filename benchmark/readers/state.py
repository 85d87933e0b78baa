"""Readers of a model of retention layers (PR 45): the decode step's
walk over the slots' states and the prefill's chunked scan, from the
device trace and the engine's counters, and what a slot really holds in
the pool of states, from the engine's gauge.

Every reader returns None where there is nothing to read (an engine
without the gauge or the counter, a configuration whose counts lack the
functions, a run that was not traced, a trace without the kernel) and
never raises for that.

The kernels are found in the trace by the reducer's stable names
(``trace_reduce.stable_name``: ``pallas`` and the shapes a custom call
writes). The decode kernel writes the slots' read-outs, float32
[B, Hkv, G, d], and the pool of states, float32 [L, B, Hkv, T, R, d]:
four dimensions and six. The chunked prefill writes the outputs
[Hkv, G, S, d] in the model's dtype and one slot's states, float32
[Hkv, T, R, d]: four and four, so its name carries the bucket S. No
other kernel writes four dimensions first: the page walk and the latent
walk write a slot's rows of three, the flash forward kernel
[B*H, S, D], the grouped matmul two.
"""

import re

from .. import flops
from .engine import _delta, _per_step, _window_rows
from .trace import decode_step_device_s_p50
from .window import _counts, _peak
from .window import _ops_s as _ops

STATE_STEP = re.compile(r"^pallas_f32(_[0-9]+){4}_f32(_[0-9]+){6}$")
CHUNK_SCAN = re.compile(
    r"^pallas_[a-z0-9]+_[0-9]+_[0-9]+_([0-9]+)_[0-9]+_f32(_[0-9]+){4}$")


def _slot_layers(record):
    """States a decode step read and wrote (sequences x retention
    layers), the window's mean."""
    return _per_step(record, _delta(record, "decode_state_slot_layers"))


def state_walk_time_share(record):
    """The decode retention kernel's share of the device's busy time."""
    walks = _ops(record, STATE_STEP)
    if not walks:
        return None
    return 100.0 * sum(s for _, _, s in walks) / record["trace"]["busy_s"]


def state_walk_roofline(record):
    """The least time for the state traffic of the decode steps in the
    trace (each counted state read once and written once, its decay,
    update and read-outs) over the decode retention kernel's time."""
    walks = _ops(record, STATE_STEP)
    counts = _counts(record, "state_walk_flops", "state_walk_bytes")
    slot_layers = _slot_layers(record)
    if not walks or counts is None or not slot_layers:
        return None
    config = record["config"]
    slot_layers *= len(record["trace"]["modules"].get("decode_step", ()))
    least = flops.roofline_s(counts.state_walk_flops(config, slot_layers),
                             counts.state_walk_bytes(config, slot_layers),
                             _peak(record))
    return 100.0 * least / sum(s for _, _, s in walks)


def decode_step_roofline_state(record):
    """The least time for a whole decode step at the window's mean
    sequences and states (every weight once, each counted state read
    and written), over the traced step's median."""
    step = decode_step_device_s_p50(record)
    counts = _counts(record, "decode_step_flops_state",
                     "decode_step_bytes_state")
    sequences = _per_step(record, _delta(record, "decode_slot_steps"))
    slot_layers = _slot_layers(record)
    if not step or counts is None or not sequences or not slot_layers:
        return None
    config = record["config"]
    least = flops.roofline_s(
        counts.decode_step_flops_state(config, sequences, slot_layers),
        counts.decode_step_bytes_state(config, sequences, slot_layers),
        _peak(record))
    return 100.0 * least / step


def prefill_retention_roofline(record):
    """The least time for the retention of the prefills in the trace
    over the chunked kernel's time. The kernel's name carries its
    bucket and it runs once a layer, so the trace says how many prefills
    of each bucket it saw; each counts as the window's mean prompt of
    that bucket: real tokens, not the bucket's."""
    calls = _ops(record, CHUNK_SCAN)
    counts = _counts(record, "retention_prefill_flops",
                     "retention_prefill_bytes")
    rows = _window_rows(record)
    if not calls or counts is None or not rows:
        return None
    config, least = record["config"], 0.0
    for match, n, _ in calls:
        bucket = int(match.group(1))
        prompts = [row[4] for row in rows if row[5] == bucket]
        if not prompts:
            return None
        tokens = round(sum(prompts) / len(prompts))
        least += n / config["num_hidden_layers"] * flops.roofline_s(
            counts.retention_prefill_flops(config, tokens),
            counts.retention_prefill_bytes(config, tokens), _peak(record))
    return 100.0 * least / sum(s for _, _, s in calls)


def state_slot_bytes(record):
    """What a slot holds in one layer of the pool of states, as the
    engine allocated it."""
    return record["worker"]["engine"].get("state_slot_bytes", {}).get("state")
