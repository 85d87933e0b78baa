"""Readers of what the engine measures from inside (PR 25):
``LLMEngine.stats()`` taken before and after the window, as the serve
job records it under ``worker.engine_before`` and ``worker.engine``.

Counters and ``phase_s`` are monotonic, so a window's figure is after
minus before. ``requests`` rows are ``[t_submit, t_admit, t_first,
t_done or None, prompt_len, bucket]`` on ``time.time()``, the clock of
``worker.window_start``; the window's rows are those submitted in it.
Every reader returns None where ``stats()`` lacks what it reads (an
engine from before PR 25), and never raises for that.
"""

from .. import arch, flops, stats
from .trace import decode_step_device_s_p50

# The loop thread's time, cut without overlap: ``admit_stalling`` is a
# part of ``admit`` and is left out of the sum.
_LOOP_PHASES = ("admit", "inputs", "decode", "readback", "emit", "idle")
# Where a ``requests`` row keeps each stamp.
_SUBMIT, _ADMIT, _FIRST = 0, 1, 2


def _delta(record, key, group=None):
    """After minus before of a counter (of ``group``'s, for a nested
    one such as ``phase_s``), or None without the counter."""
    worker = record["worker"]
    after, before = worker["engine"], worker["engine_before"]
    if group:
        after, before = after.get(group, {}), before.get(group, {})
    if key not in after or key not in before:
        return None
    return after[key] - before[key]


def _phase_delta(record, phase):
    return _delta(record, phase, "phase_s")


def _per_step(record, total):
    steps = _delta(record, "decode_steps")
    if total is None or not steps:
        return None
    return total / steps


def _window_rows(record):
    """The rows of requests submitted inside the window, or None."""
    worker = record["worker"]
    rows = worker["engine"].get("requests")
    if rows is None:
        return None
    return [row for row in rows if row[0] >= worker["window_start"]]


def _stamp_percentile(record, start, end, q):
    """Percentile of ``row[end] - row[start]`` over the window's rows
    that carry both stamps."""
    rows = _window_rows(record)
    if rows is None:
        return None
    return stats.percentile([row[end] - row[start] for row in rows
                             if row[start] is not None
                             and row[end] is not None], q)


def admit_wait_s_p50(record):
    """Submit to slot and pages assigned: the queue, and the decode step
    or prefill the loop was in when the request came."""
    return _stamp_percentile(record, _SUBMIT, _ADMIT, 50)


def admit_wait_s_p90(record):
    return _stamp_percentile(record, _SUBMIT, _ADMIT, 90)


def engine_prefill_s_p50(record):
    """Admitted to first token: table upload, padding, dispatch and the
    prefill program on the device."""
    return _stamp_percentile(record, _ADMIT, _FIRST, 50)


def engine_ttft_s_p90(record):
    """Submit to first token, inside the engine: the client's TTFT less
    ingress and the hop back."""
    return _stamp_percentile(record, _SUBMIT, _FIRST, 90)


def decode_batch_mean(record):
    """Sequences a decode step carried, averaged over the steps."""
    return _per_step(record, _delta(record, "decode_slot_steps"))


def decode_step_roofline_counted(record):
    """``decode_step_roofline`` at the load the engine counted (mean
    sequences and cached tokens a step, over the window's steps) and not
    the one estimated from the client's samples."""
    step = decode_step_device_s_p50(record)
    sequences = decode_batch_mean(record)
    tokens = _per_step(record, _delta(record, "decode_kv_tokens"))
    if not step or sequences is None or tokens is None:
        return None
    config = record["config"]
    counts = arch.counts(config)
    least = flops.roofline_s(
        counts.decode_step_flops(config, sequences, tokens),
        counts.decode_step_bytes(config, sequences, tokens),
        flops.peaks(record["worker"]["device"]["kind"]))
    return 100.0 * least / step


def engine_host_s_per_step(record):
    """Host time of a decode step the device may idle through: building
    inputs, dispatching, emitting tokens (not the read-back, which is
    the host waiting for the device)."""
    parts = [_phase_delta(record, p) for p in ("inputs", "decode", "emit")]
    if None in parts:
        return None
    return _per_step(record, sum(parts))


def admit_stall_share(record):
    """Share of the loop's time spent in admission rounds entered with a
    stream open: every open stream waits through them."""
    stalling = _phase_delta(record, "admit_stalling")
    parts = [_phase_delta(record, p) for p in _LOOP_PHASES]
    if stalling is None or None in parts or not sum(parts):
        return None
    return 100.0 * stalling / sum(parts)


def prefill_padding_share(record):
    """Share of the prefill programs' tokens that were bucket padding."""
    real = _delta(record, "prefill_tokens")
    padded = _delta(record, "prefill_bucket_tokens")
    if real is None or not padded:
        return None
    return 100.0 * (1.0 - real / padded)
