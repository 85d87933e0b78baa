"""Arithmetic the readers share: percentiles and readings."""

from __future__ import annotations

import math
import statistics
from typing import Dict, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Linear interpolation between closest ranks (numpy's default), on
    plain lists; None for no samples."""
    if not values:
        return None
    ordered = sorted(values)
    pos = (len(ordered) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (pos - lo)


def readings_summary(reading_s: Sequence[float], tokens_per_reading: int
                     ) -> Dict[str, float]:
    """Training throughput from readings of a few steps each.

    ``window``: all tokens over all the time of the window, stalls in:
    what the job's user gets. ``median``: tokens of one reading over
    the median reading time, the step's own speed, which a stalled
    reading cannot move. ``stall_share``: the part of the window that
    the median reading does not account for."""
    window = sum(reading_s)
    median = statistics.median(reading_s)
    n = len(reading_s)
    return {
        "readings": n,
        "window_s": window,
        "median_reading_s": median,
        "tokens_per_s_median": tokens_per_reading / median,
        "tokens_per_s_window": tokens_per_reading * n / window,
        "stall_share": max(0.0, (window - n * median) / window),
    }
