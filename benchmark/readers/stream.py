"""Readers of a token's way back from the engine's loop to its client
(PR 40): what ``LLMEngine.stats()`` counts and stamps of it, taken
before and after the window (``worker.engine_before``,
``worker.engine``), against the load generator's stamp of every token.

One clock: ``stats()["t"]``, a ``requests`` row's stamps and
``client.t0_wall`` are ``time.time()`` of one host, and a sample's
``token_s`` are seconds from ``t0_wall``. A row is ``[t_submit, t_admit,
t_first, t_done or None, prompt_len, bucket, id, t_last_put or None]``;
``id`` is the sample's ``id`` (the load generator sends it with the
request; its warm-up's are negative). Every reader returns None where
``stats()`` lacks what it reads (an engine from before PR 40), and
never raises for that.
"""

from .. import stats
from .engine import _delta, _window_rows

# Where a ``requests`` row keeps each field.
_FIRST, _DONE, _ID, _LAST_PUT = 2, 3, 6, 7


def _seconds(record):
    """Seconds between the two readings, by their own ``t``; None where
    a reading carries none or they do not lie apart."""
    return _delta(record, "t") or None


def _per_taken_token(record, key):
    taken = _delta(record, "tokens_taken", "stream")
    total = _delta(record, key, "stream")
    if total is None or not taken:
        return None
    return total / taken


def _joined(record):
    """``(row, sample)`` of every request of the window that the engine
    and the load generator both know by one id, or None where the rows
    carry no id. The warm-up's requests (negative ids, and submitted
    before the window) and rows of callers that gave no id are left out."""
    rows = _window_rows(record)
    if rows is None or any(len(row) <= _LAST_PUT for row in rows):
        return None
    samples = {s["id"]: s for s in record["client"]["samples"]}
    return [(row, samples[row[_ID]]) for row in rows
            if row[_ID] is not None and row[_ID] in samples
            and row[_ID] >= 0]


def _uncut(record):
    """The joined requests whose stream ran to its end on both sides."""
    joined = _joined(record)
    if joined is None:
        return None
    return [(row, s) for row, s in joined
            if row[_DONE] is not None and s["error"] is None
            and not s["cut"] and s["token_s"]]


def tokens_emitted_per_s(record):
    """Tokens the engine's loop put on its requests' live queues,
    between the two readings, over the seconds between them."""
    seconds = _seconds(record)
    emitted = _delta(record, "tokens_emitted", "stream")
    if seconds is None or emitted is None:
        return None
    return emitted / seconds


def tokens_delivered_per_s(record):
    """Tokens that reached a client between the two readings, over the
    same seconds: the way back's rate beside the engine's."""
    seconds = _seconds(record)
    if seconds is None:
        return None
    worker, t0 = record["worker"], record["client"]["t0_wall"]
    first, last = worker["engine_before"]["t"], worker["engine"]["t"]
    delivered = sum(first <= t0 + t <= last
                    for s in record["client"]["samples"]
                    for t in s["token_s"])
    return delivered / seconds


def first_token_lag_s_p50(record):
    """Engine's first token to the client's stamp of it: the way back
    with nothing queued in it."""
    joined = _joined(record)
    if joined is None:
        return None
    t0 = record["client"]["t0_wall"]
    return stats.percentile(
        [t0 + s["token_s"][0] - row[_FIRST] for row, s in joined
         if row[_FIRST] is not None and s["token_s"]], 50)


def last_token_lag_s_p50(record):
    """Engine done to the client's stamp of the last token, over the
    requests that ended uncut: how far a stream fell behind."""
    uncut = _uncut(record)
    if uncut is None:
        return None
    t0 = record["client"]["t0_wall"]
    return stats.percentile(
        [t0 + s["token_s"][-1] - row[_DONE] for row, s in uncut], 50)


def stream_replica_lag_s_p50(record):
    """Engine done to the replica's stream thread having handed the last
    token's seal to the node manager, over the same requests: the part
    of ``last_token_lag_s_p50`` that is still inside the replica."""
    uncut = _uncut(record)
    if uncut is None:
        return None
    return stats.percentile(
        [row[_LAST_PUT] - row[_DONE] for row, _ in uncut
         if row[_LAST_PUT] is not None], 50)


def stream_taken_lag_s_mean(record):
    """Emitted to taken by the request's stream thread, a token: its
    wake-up and what queued in front of it in the replica."""
    return _per_taken_token(record, "taken_lag_s")


def stream_seal_s_mean(record):
    """What a token costs its stream thread from handing it over to
    being asked for the next: ``LLMDeployment.stream``, the executor
    and the seal."""
    return _per_taken_token(record, "held_s")
