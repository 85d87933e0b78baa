"""Reader of how often a mixture of experts' grouped matmuls ran sized
to few rows a group: the two counters ``layer_calls`` and
``small_rows_layer_calls`` of ``LLMEngine.stats()["moe"]`` (PR 47),
taken before and after the window as ``worker.engine_before`` and
``worker.engine``.

Returns None where there is nothing to read (a dense model, an engine
without the counters, a window without a program) and never raises for
that.
"""


def grouped_small_rows_share(record):
    """Expert layers of the window's programs (decode steps and
    prefills) that ran the grouped matmul for few rows a group, over all
    of them."""
    worker = record["worker"]
    after = worker["engine"].get("moe") or {}
    before = worker["engine_before"].get("moe") or {}
    keys = ("layer_calls", "small_rows_layer_calls")
    if any(k not in stats for k in keys for stats in (after, before)):
        return None
    calls, small = (after[k] - before[k] for k in keys)
    if not calls:
        return None
    return 100.0 * small / calls
