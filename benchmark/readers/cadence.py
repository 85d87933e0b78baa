"""Readers of a gap taken apart where it is made (PR 59): what
``LLMEngine.stats()`` counts of its own emit cadence, of the tails of
the replica's two hops, of its threads' time off the CPU and of what
each decode dispatch found on the device, taken before and after the
window (``worker.engine_before``, ``worker.engine``).

Everything read here is monotonic, so a window's figure is after minus
before: a histogram's count by count. A histogram of
``stats()["stream"]`` is one count more than ``hist_edges_s``, its upper
edges in seconds, 2% apart: ``counts[i]`` holds the samples in
``[edges[i - 1], edges[i])``, the first everything under ``edges[0]``,
the last everything from ``edges[-1]`` on. None of these needs a trace.
Every reader returns None where ``stats()`` lacks what it reads (an
engine from before PR 59) or the window holds no sample, and never
raises for that.
"""

from .engine import _delta, _phase_delta

# The loop's phases that are Python and a dispatch: what is not CPU
# time in them is waiting to run. ``readback`` and ``idle`` wait by
# design, ``admit`` waits for its prefill's first token.
_HOST_PHASES = ("inputs", "decode", "emit")
_STARVED = ("starved_host", "starved_prefill")
_FEEDS = ("fed", "starved_lull") + _STARVED


def hist_quantile(counts, edges, q):
    """The ``q``-th percentile of a histogram's samples: the bucket the
    rank falls in, and inside it as far as the rank lies among the
    bucket's samples, on the logarithm; an open bucket gives the edge
    it has. None for no samples."""
    total = sum(counts)
    if total <= 0:
        return None
    rank, below = q / 100.0 * total, 0
    for i, count in enumerate(counts):
        if count and below + count >= rank:
            if i == 0:
                return edges[0]
            if i == len(edges):
                return edges[-1]
            low, high = edges[i - 1], edges[i]
            return low * (high / low) ** ((rank - below) / count)
        below += count
    return edges[-1]


def _window_quantile(record, name, q):
    """Percentile ``q`` of the samples that ``stream[name]`` counted
    between the two readings."""
    worker = record["worker"]
    after = worker["engine"].get("stream", {})
    before = worker["engine_before"].get("stream", {})
    edges = after.get("hist_edges_s")
    if edges is None or name not in after or name not in before:
        return None
    counts = [a - b for a, b in zip(after[name], before[name])]
    return hist_quantile(counts, edges, q)


def _off_cpu_share(wall, cpu):
    if wall is None or cpu is None or wall <= 0:
        return None
    return 100.0 * (1.0 - cpu / wall)


def emit_gap_s_p50(record):
    """Median of the seconds between two tokens of one request as the
    engine's loop put them: its cadence, before any hop of the way
    back."""
    return _window_quantile(record, "emit_gap_hist", 50)


def emit_gap_s_p90(record):
    """What the loop itself emitted late: the engine's part of the
    client's ``gap_p90_s``."""
    return _window_quantile(record, "emit_gap_hist", 90)


def emit_gap_s_p99(record):
    """A prefill's stand-still in every open stream, as the engine made
    it."""
    return _window_quantile(record, "emit_gap_hist", 99)


def stream_taken_lag_s_p90(record):
    """Tail of emitted to taken by the request's stream thread: its
    wake-up and what queued in front of it in the replica."""
    return _window_quantile(record, "taken_lag_hist", 90)


def stream_seal_s_p90(record):
    """Tail of what a token costs its stream thread from handing it
    over to being asked for the next."""
    return _window_quantile(record, "held_hist", 90)


def engine_loop_offcpu_share(record):
    """Share of the loop thread's time in ``inputs``, ``decode`` and
    ``emit`` in which it was not on the CPU: waiting for the GIL, and in
    ``decode`` for whatever the dispatch blocks on."""
    wall = [_phase_delta(record, p) for p in _HOST_PHASES]
    cpu = [_delta(record, p, "phase_cpu_s") for p in _HOST_PHASES]
    if None in wall or None in cpu:
        return None
    return _off_cpu_share(sum(wall), sum(cpu))


def stream_offcpu_share(record):
    """Share of the stream threads' time between handing a token over
    and being asked for the next in which they were not on the CPU,
    over the holds the engine also timed on the CPU clock (every
    eighth)."""
    return _off_cpu_share(_delta(record, "held_timed_s", "stream"),
                          _delta(record, "held_cpu_s", "stream"))


def decode_starved_share(record):
    """Share of the window's decode dispatches that found the device
    empty with a stream open: the step before had already finished (the
    loop was late) or a prefill had been waited for behind it."""
    found = {feed: _delta(record, feed, "decode_dispatch")
             for feed in _FEEDS}
    if None in found.values() or not sum(found.values()):
        return None
    return 100.0 * sum(found[feed] for feed in _STARVED) / sum(found.values())
