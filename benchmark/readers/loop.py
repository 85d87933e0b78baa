"""Readers of a looped model (PR 65): a stack that runs
``total_ut_steps`` times over one set of weights, a KV pool that many
times as deep as the stack and an exit gate behind every pass. All four
read ``LLMEngine.stats()["loop"]`` before and after the window
(``worker.engine_before``, ``worker.engine``), the last one the
configuration's counts besides; none reads the device trace.

Every reader returns None where there is nothing to read (an engine
whose ``stats()`` has no ``loop``, which is every model of one pass and
every program from before PR 65; a ``loop`` without the gauge or the
gate's sums; a window without a decode step; a configuration whose
counts lack the functions) and never raises for that.
"""

from .engine import _delta, _per_step
from .window import _counts


def _loop_delta(record, key):
    return _delta(record, key, "loop")


def loop_passes_per_step(record):
    """Passes over the stack a decode step ran, the window's mean: the
    model's ``total_ut_steps`` while no token leaves early; what an exit
    threshold under 1 would lower."""
    return _per_step(record, _loop_delta(record, "passes"))


def loop_kv_token_bytes(record):
    """What a token holds over all the pools the engine built, from the
    engine's own gauge of them (a row of each pool times the pool's
    layers, as allocated) and not from the configuration."""
    return record["worker"]["engine"].get("loop", {}).get("kv_token_bytes")


def loop_exit_pass_mean(record):
    """The mean pass a generated token would leave at under the gate's
    own distribution, ``sum_t (t + 1) p_t``, from the sums the engine
    keeps of what each program returned: between 1 and the passes."""
    passes, tokens = (_loop_delta(record, "exit_pass_sum"),
                      _loop_delta(record, "exit_tokens"))
    if passes is None or not tokens:
        return None
    return passes / tokens


def loop_weight_bytes_share(record):
    """Of a decode step's counted bytes (the configuration's counts at
    the sequences and KV rows the engine counted a step), the share that
    is layer weights read AGAIN, in the passes after the first: what the
    loop costs a step beyond a model of the same depth without one. The
    rest is the first pass's weights, the head and the rows walked."""
    counts = _counts(record, "decode_step_weight_bytes",
                     "decode_step_bytes_rows")
    sequences = _per_step(record, _delta(record, "decode_slot_steps"))
    rows = _per_step(record, _delta(record, "decode_kv_rows_read"))
    if (counts is None or None in (sequences, rows)
            or _loop_delta(record, "passes") is None):
        return None
    config = record["config"]
    again = counts.decode_step_weight_bytes(config)["later_passes"]
    return 100.0 * again / counts.decode_step_bytes_rows(
        config, sequences, rows)
