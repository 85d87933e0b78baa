"""Readers of the training job's own samples."""

from .. import arch, flops, stats


def _summary(record):
    worker = record["worker"]
    return stats.readings_summary(worker["reading_s"],
                                  worker["tokens_per_reading"])


def train_tokens_per_s(record):
    """All tokens of the window over all its time, all chips together:
    a stall, a slow batch or a pause inside the window lowers it."""
    return _summary(record)["tokens_per_s_window"]


def train_tokens_per_s_median(record):
    """Tokens of one reading over the median reading time: the compiled
    step's own speed, which a stalled reading cannot move."""
    return _summary(record)["tokens_per_s_median"]


def step_stall_share(record):
    """The part of the window that the median reading does not account
    for: what separates the two figures above."""
    return 100.0 * _summary(record)["stall_share"]


def step_mfu(record):
    """Required operations per token times the median reading's tokens
    per second (the step's own speed), over the chips' bf16 peak."""
    config = record["config"]
    per_token = arch.counts(config).train_flops_per_token(
        config, record["traffic"]["seqlen"])
    device = record["worker"]["device"]
    peak = flops.peaks(device["kind"])["bf16_flops_per_s"] * device["count"]
    return 100.0 * train_tokens_per_s_median(record) * per_token / peak


def peak_hbm_bytes(record):
    return record["worker"]["memory_peak_bytes"]


def input_wait_share(record):
    """Host time inside ``next(batches)`` as a share of the window."""
    return 100.0 * record["worker"]["input_wait_s"] / _summary(record)["window_s"]
