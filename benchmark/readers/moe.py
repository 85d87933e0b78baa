"""Readers of a mixture of experts' own layer: the grouped expert
matmuls in the device trace, and the expert-load counters of
``LLMEngine.stats()["moe"]`` (PR 28), taken before and after the window
as ``worker.engine_before`` and ``worker.engine``.

Every reader returns None where there is nothing to read (an engine
without the counters, a dense model, a run that was not traced, a trace
without the operations) and never raises for that.
"""

import re

from .. import arch, flops

# The grouped matmuls under the reducer's stable names: XLA's TPU
# compiler runs ``jax.lax.ragged_dot`` as a custom call, which the
# reducer prints as ``pallas_<dtype>_<rows>_<width>``: the one custom
# call of these programs that writes two dimensions, ``k`` rows a token
# by the expert's or the model's width (the page walk writes three, the
# flash kernel four; looked at by hand in a chat trace of the v5e, PR
# 28: ``pallas_bf16_256_1024`` twice and ``pallas_bf16_256_2048`` once
# a layer of a decode step of 32 slots).
GROUPED = re.compile(r"^pallas_[a-z0-9]+_([0-9]+)_([0-9]+)$")


def _moe(record, which):
    return record["worker"][which].get("moe")


def _delta(record, key):
    after, before = _moe(record, "engine"), _moe(record, "engine_before")
    if not after or not before or key not in after or key not in before:
        return None
    return after[key] - before[key]


def _grouped_s(record):
    """Device seconds of the grouped expert matmuls in the trace."""
    trace = record["trace"]
    if not trace:
        return None
    config = record["config"]
    widths = {str(config["intermediate_size"]), str(config["hidden_size"])}
    matches = ((GROUPED.match(name), s) for name, _, s in trace["ops"])
    return sum(s for m, s in matches if m and m.group(2) in widths) or None


def moe_matmul_time_share(record):
    """The grouped expert matmuls' share of the device's busy time."""
    grouped = _grouped_s(record)
    if grouped is None:
        return None
    return 100.0 * grouped / record["trace"]["busy_s"]


def moe_matmul_roofline(record):
    """The least time the chip could take for the expert matmuls of the
    programs the trace saw, over the time they took. The work is the
    counters': a decode step's assignments and (layer, expert) pairs
    reached, and a prefill's, each the window's mean for that program
    (the counters span the window, the trace a part of it), times the
    runs of that program in the trace; each program against its own
    bound. Weights count once per pair REACHED, from the counter."""
    grouped = _grouped_s(record)
    engine, before = (record["worker"][k] for k in ("engine", "engine_before"))
    deltas = {k: _delta(record, k) for k in (
        "assignments", "decode_assignments", "experts_reached",
        "prefill_experts_reached")}
    if grouped is None or None in deltas.values():
        return None
    runs = {"decode": engine["decode_steps"] - before["decode_steps"],
            "prefill": engine["prefills"] - before["prefills"]}
    work = {"decode": (deltas["decode_assignments"],
                       deltas["experts_reached"]),
            "prefill": (deltas["assignments"] - deltas["decode_assignments"],
                        deltas["prefill_experts_reached"])}
    modules = record["trace"]["modules"]
    traced = {"decode": len(modules.get("decode_step", ())),
              "prefill": len(modules.get("prefill", ()))}
    config = record["config"]
    counts = arch.counts(config)
    peak = flops.peaks(record["worker"]["device"]["kind"])
    least = 0.0
    for program, (assignments, pairs) in work.items():
        if runs[program] and traced[program]:
            least += traced[program] * flops.roofline_s(
                counts.moe_matmul_flops(config, assignments / runs[program]),
                counts.moe_matmul_bytes(config, assignments / runs[program],
                                        pairs / runs[program]), peak)
    return 100.0 * least / grouped


def experts_reached_mean(record):
    """Experts of a layer that a decode step gave at least one token,
    averaged over the window's decode steps and layers."""
    reached, layer_steps = (_delta(record, "experts_reached"),
                            _delta(record, "layer_steps"))
    if reached is None or not layer_steps:
        return None
    return reached / layer_steps


def expert_load_max_over_mean(record):
    """The busiest expert's assignments in the window over the mean
    expert's (prefills and decode steps, all layers together)."""
    after, before = _moe(record, "engine"), _moe(record, "engine_before")
    if not after or not before:
        return None
    load = [a - b for a, b in zip(after["expert_tokens"],
                                  before["expert_tokens"])]
    if not sum(load):
        return None
    return max(load) * len(load) / sum(load)
