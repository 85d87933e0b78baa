"""Readers of the flash forward's streamed form (PR 57): a prefill
bucket whose K and V of a head do not fit VMEM whole
(``ray_tpu/ops/flash_attention.py``: 16,384 keys of 128 + 128 in
bfloat16) streams them a block at a time. How many of the window's
prefill bucket tokens took that form, from ``LLMEngine.stats()`` before
and after the window, and the streamed kernel against its roofline,
from the device trace.

Every reader returns None where there is nothing to read (an engine
without the counter, as the parent's; a configuration whose counts lack
the functions; a run that was not traced; a trace without the kernel)
and never raises for that.

The kernel is found in the trace by the reducer's stable name
(``trace_reduce.stable_name``: ``pallas`` and the shapes a custom call
writes). The streamed form writes the float32 log-sum-exp [B*H, 1, S]
FIRST and the output [B*H, S, D] second; the resident form the other way
round (``readers/window.py:FLASH``), so neither pattern matches the
other's name, whatever the dtype.
"""

import re

from .. import flops
from .engine import _delta, _window_rows
from .window import _counts, _ops_s, _peak

STREAMED = re.compile(
    r"^pallas_f32_([0-9]+)_1_([0-9]+)_[a-z0-9]+_\1_\2_([0-9]+)$")


def prefill_streamed_share(record):
    """Prefill bucket tokens whose attention took the streamed form,
    over all prefill bucket tokens of the window."""
    streamed, every = (_delta(record, "prefill_streamed_bucket_tokens"),
                       _delta(record, "prefill_bucket_tokens"))
    if streamed is None or not every:
        return None
    return 100.0 * streamed / every


def prefill_stream_roofline(record):
    """The least time for the causal attention of the traced prefills
    that streamed over the streamed kernel's time. As
    ``prefill_flash_roofline``: the kernel's name carries its bucket and
    it runs once a layer, so the trace says how many prefills of each
    bucket it saw; each counts as the window's mean prompt of that
    bucket, a window layer's attention from its window."""
    calls = _ops_s(record, STREAMED)
    counts = _counts(record, "flash_streamed_flops", "flash_streamed_bytes")
    rows = _window_rows(record)
    if not calls or counts is None or not rows:
        return None
    config, least = record["config"], 0.0
    for match, n, _ in calls:
        bucket = int(match.group(2))
        prompts = [row[4] for row in rows if row[5] == bucket]
        if not prompts:
            return None
        tokens = round(sum(prompts) / len(prompts))
        least += n / config["num_hidden_layers"] * flops.roofline_s(
            counts.flash_streamed_flops(config, tokens),
            counts.flash_streamed_bytes(config, tokens), _peak(record))
    return 100.0 * least / sum(s for _, _, s in calls)
