"""Readers of the load generator's samples: what a client saw."""

from .. import stats


def _samples(record):
    return record["client"]["samples"]


def _ttfts(record):
    """Time to first token from when each request was due. A request
    that failed, or had no token when the streams were closed, counts
    as the worst there is: the whole run."""
    ok = [s["token_s"][0] - s["due_s"] for s in _samples(record)
          if s["token_s"]]
    missing = sum(1 for s in _samples(record) if not s["token_s"])
    return ok + [max(ok + [record["client"]["closed_s"]])] * missing


def _gaps(record):
    """Gaps between streamed tokens, pooled over all requests."""
    return [b - a for s in _samples(record)
            for a, b in zip(s["token_s"], s["token_s"][1:])]


def ttft_p50_s(record):
    return stats.percentile(_ttfts(record), 50)


def ttft_p90_s(record):
    return stats.percentile(_ttfts(record), 90)


def gap_p50_s(record):
    return stats.percentile(_gaps(record), 50)


def gap_p90_s(record):
    return stats.percentile(_gaps(record), 90)


def gap_p99_s(record):
    return stats.percentile(_gaps(record), 99)


def gap_slow_share(record):
    """Share of the gaps over 1.5 times the median gap: the gaps that
    hold more than a decode step's cadence (a prefill, an admission, a
    stall). Where it nears 10%, ``gap_p90_s`` sits on their edge."""
    gaps = _gaps(record)
    if not gaps:
        return None
    limit = 1.5 * stats.percentile(gaps, 50)
    return 100.0 * sum(g > limit for g in gaps) / len(gaps)


def loadgen_late_s_max(record):
    """How late the generator sent its latest request."""
    return max(s["sent_s"] - s["due_s"] for s in _samples(record))


def ingress_s_p50(record):
    """Client send to the replica's ``stream`` being entered: proxy,
    handle and actor call."""
    entered = record["worker"]["entered"]
    t0 = record["client"]["t0_wall"]
    return stats.percentile(
        [entered[s["id"]] - (t0 + s["sent_s"]) for s in _samples(record)
         if s["id"] in entered], 50)


def batch_occupancy(record):
    """Tokens the decode steps produced over the slots they had: steps
    from ``LLMEngine.stats()`` before and after the window, tokens
    from the streams (a request's first token comes from prefill)."""
    worker = record["worker"]
    steps = (worker["engine"]["decode_steps"]
             - worker["engine_before"]["decode_steps"])
    if not steps:
        return None
    decoded = sum(max(0, len(s["token_s"]) - 1) for s in _samples(record))
    return 100.0 * decoded / (steps * record["config"]["engine"]["max_batch"])
