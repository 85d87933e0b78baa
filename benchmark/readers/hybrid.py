"""Readers of a model whose layers are of two kinds that keep different
things (PR 62): delta-rule layers, a state a slot, among latent layers,
a row a token. The decode step's delta rule, the latent walk and the
whole decode step against their rooflines, from the device trace and the
engine's counters, and what a slot really holds in a delta layer, from
the engine's gauge.

**What the traced steps carried is read where they ran, not from the
window's mean.** ``jax.profiler.stop_trace()`` holds the replica for
53-114 s after this cell's 4 traced seconds (288 decode steps of 15
runs of layers; my chip runs, PR 62), longer than what is left of the
window, and no request is admitted meanwhile: the streams that end are
not replaced, the batch runs down from 16, and a traced run's
``decode_batch_mean.chat`` reads 11.9-12.9 where an untraced run's
reads 15.96, while every traced step carried 16. A roofline that
multiplied the window's mean by the traced steps read 58-60% in three
such runs and 78% in the one whose trace was written in 25 s. So these
readers count the sequences that held a slot while the trace ran
(``_traced_sequences``: from the requests' own stamps) and take each
counter's mean a SEQUENCE a step from the window, which the run-down
does not move: 77.2-77.6% in three runs since.

The prefill's two kernels have no reader here: this cell's traced 4 s
held no prefill in four runs of seven and 1.7, 0.65 and 1.4 of one in
the others (PERF.md section 7, Open after PR 62 d), so the PR that gives
the traffic a trace with whole prefills in it brings
them with their metrics; ``kimi_counts.delta_prefill_*`` and
``flash_prefill_*`` are what they will be held against, a prefill being
one call a layer OF ITS KIND (20 and 7), not of ``num_hidden_layers``.

Every reader returns None where there is nothing to read (an engine
without the gauge or the counter, a configuration whose counts lack the
functions, a run that was not traced, a trace without the kernel) and
never raises for that.

The kernels are found in the trace by the reducer's stable names
(``trace_reduce.stable_name``: ``pallas`` and the shapes a custom call
writes). The decode kernel writes the slots' read-outs, float32
[B, 1, H, d], and the pool of states, float32 [L, B, H, d, d]: four
dimensions and five (the retention step writes four and six, the page
walk three and five). The latent walk is JoyAI's reader's pattern, taken
from it.
"""

import re

from .. import flops
from .engine import _delta
from .latent import _walk_s
from .moe import _delta as _moe_delta
from .trace import decode_step_device_s_p50
from .window import _counts, _ops_s, _peak, _traced_runs

DELTA_STEP = re.compile(r"^pallas_f32(_[0-9]+){4}_f32(_[0-9]+){5}$")


def _traced_sequences(record):
    """Sequences that held a slot while the trace ran, the traced
    interval's mean: a request's row says when it was admitted and when
    it ended, the trace began ``trace_at_s`` into the window and covers
    ``window_s``. A sequence counts from its admission, not from its
    first token: no step runs while its prompt is prefilled, and the
    steps on either side of that carry it or the one it replaced."""
    rows = record["worker"]["engine"].get("requests")
    at = (record.get("traffic") or {}).get("trace_at_s")
    if not rows or at is None or not record["trace"]:
        return None
    start = record["worker"]["window_start"] + at
    end = start + record["trace"]["window_s"]
    held = sum(
        max(0.0, (end if done is None else min(end, done)) - max(start, admit))
        for _, admit, _, done, *_ in rows if admit is not None)
    return held / (end - start)


def _traced_per_step(record, total):
    """A counter's mean a step in the TRACED steps: its mean a sequence
    a step over the window, times the sequences the traced steps
    carried."""
    slot_steps = _delta(record, "decode_slot_steps")
    sequences = _traced_sequences(record)
    if total is None or not slot_steps or not sequences:
        return None
    return total / slot_steps * sequences


def _in_trace(record, key):
    """What the traced decode steps together added to the engine's
    counter ``key``; None without such a step."""
    per_step = _traced_per_step(record, _delta(record, key))
    if not per_step:
        return None
    return per_step * _traced_runs(record, "decode_step") or None


def delta_step_time_share(record):
    """The decode delta-rule kernel's share of the device's busy time."""
    steps = _ops_s(record, DELTA_STEP)
    if not steps:
        return None
    return 100.0 * sum(s for _, _, s in steps) / record["trace"]["busy_s"]


def delta_step_roofline(record):
    """The least time for the state traffic of the decode steps in the
    trace (each counted state read once and written once, its decay,
    correction, write and read-out) over the decode kernel's time."""
    steps = _ops_s(record, DELTA_STEP)
    counts = _counts(record, "delta_step_flops", "delta_step_bytes")
    slot_layers = _in_trace(record, "decode_state_slot_layers")
    if not steps or counts is None or not slot_layers:
        return None
    config = record["config"]
    least = flops.roofline_s(counts.delta_step_flops(config, slot_layers),
                             counts.delta_step_bytes(config, slot_layers),
                             _peak(record))
    return 100.0 * least / sum(s for _, _, s in steps)


def hybrid_latent_walk_roofline(record):
    """The least time for the absorbed attention of the decode steps in
    the trace (the rows the traced steps read, each once, every head's
    query against a row and its probability against the row's latent)
    over the latent walk's time: ``readers/latent.latent_walk_roofline``
    at the traced steps' rows."""
    walk = _walk_s(record)
    counts = _counts(record, "latent_walk_flops", "latent_walk_bytes")
    rows = _in_trace(record, "decode_kv_rows_read")
    if not walk or counts is None or not rows:
        return None
    config = record["config"]
    least = flops.roofline_s(counts.latent_walk_flops(config, rows),
                             counts.latent_walk_bytes(config, rows),
                             _peak(record))
    return 100.0 * least / walk


def decode_step_roofline_hybrid(record):
    """The least time for a whole decode step at the traced steps'
    sequences, latent rows read, states stepped and (layer, held expert)
    pairs reached (every weight held once, each state read and written,
    each latent row read once), over the traced step's median. The
    pairs are scaled with the sequences as the others are: few of the
    held experts are reached, so they go with the assignments."""
    step = decode_step_device_s_p50(record)
    counts = _counts(record, "decode_step_flops_hybrid",
                     "decode_step_bytes_hybrid")
    sequences = _traced_sequences(record)
    rows = _traced_per_step(record, _delta(record, "decode_kv_rows_read"))
    slot_layers = _traced_per_step(
        record, _delta(record, "decode_state_slot_layers"))
    pairs = _traced_per_step(record, _moe_delta(record, "experts_reached"))
    if not step or counts is None or None in (sequences, rows, pairs) \
            or not slot_layers:
        return None
    config = record["config"]
    least = flops.roofline_s(
        counts.decode_step_flops_hybrid(config, sequences, rows, slot_layers),
        counts.decode_step_bytes_hybrid(config, sequences, rows, slot_layers,
                                        pairs), _peak(record))
    return 100.0 * least / step


def delta_slot_bytes(record):
    """What a slot holds in one delta layer, state and convolution
    history, as the engine allocated them."""
    return record["worker"]["engine"].get("state_slot_bytes", {}).get("delta")
