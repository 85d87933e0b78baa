"""Readers of the reduced profiler trace (``trace_reduce.reduce_trace``).
Each returns None when the run was not traced."""

from .. import arch, flops, stats


def _peak(record):
    return flops.peaks(record["worker"]["device"]["kind"])


def device_idle_share(record):
    trace = record["trace"]
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def _pallas_s(trace):
    return sum(s for name, _, s in trace["ops"] if name.startswith("pallas"))


def flash_time_share(record):
    """The Pallas kernels' share of the device's busy time."""
    trace = record["trace"]
    if not trace:
        return None
    return 100.0 * _pallas_s(trace) / trace["busy_s"]


def flash_roofline(record):
    """The least time the chip could take for the attention the traced
    steps need, over the time the kernels took. Per chip: a mesh splits
    batch and heads evenly."""
    trace = record["trace"]
    if not trace or not _pallas_s(trace):
        return None
    traffic, config = record["traffic"], record["config"]
    steps = len(trace["modules"].get("train_step", ()))
    chips = trace["devices"]
    counts = arch.counts(config)
    args = (config, traffic["batch"], traffic["seqlen"])
    least = flops.roofline_s(counts.flash_train_flops(*args) * steps / chips,
                             counts.flash_train_bytes(*args) * steps / chips,
                             _peak(record))
    return 100.0 * least / _pallas_s(trace)


def collective_exposed_share(record):
    """Time inside collectives during which no other operation ran, as
    a share of the traced window."""
    trace = record["trace"]
    if not trace:
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"]


def _module_p50(record, name):
    trace = record["trace"]
    if not trace or not trace["modules"].get(name):
        return None
    return stats.percentile(trace["modules"][name], 50)


def prefill_device_s_p50(record):
    return _module_p50(record, "prefill")


def decode_step_device_s_p50(record):
    return _module_p50(record, "decode_step")


def _traced_span(record):
    """Start and end of the traced part, seconds from the window's start."""
    traffic = record["traffic"]
    return (traffic["trace_at_s"],
            traffic["trace_at_s"] + traffic["trace_seconds"])


def _traced_load(record):
    """(mean sequences, mean cached tokens) in flight while the trace
    ran, from the client's samples: a request holds its prompt and the
    tokens streamed so far from its first token to its last."""
    start, end = _traced_span(record)
    sequences = tokens = 0.0
    for s in record["client"]["samples"]:
        if not s["token_s"]:
            continue
        overlap = min(end, s["token_s"][-1]) - max(start, s["token_s"][0])
        if overlap > 0:
            share = overlap / (end - start)
            sequences += share
            tokens += share * (s["prompt_len"] + len(s["token_s"]) / 2)
    return sequences, tokens


def decode_step_roofline(record):
    """The least time the chip could take for one decode step at the
    load the trace saw (every weight once, the cached tokens' keys and
    values once), over the median step's device time."""
    step = decode_step_device_s_p50(record)
    if not step:
        return None
    sequences, tokens = _traced_load(record)
    config = record["config"]
    counts = arch.counts(config)
    least = flops.roofline_s(
        counts.decode_step_flops(config, sequences, tokens),
        counts.decode_step_bytes(config, sequences, tokens), _peak(record))
    return 100.0 * least / step
