"""Readers of a model whose layers are of two attention kinds and whose
routed experts have a width of their own (PR 38): the window's share of
the KV rows read and pages held, from ``LLMEngine.stats()`` before and
after the window, and the routed matmuls, the page walk, the prefill's
flash kernel and the decode step against their rooflines, from the
device trace and the same counters.

Every reader returns None where there is nothing to read (an engine
without the counters, a configuration whose counts lack the functions,
a run that was not traced, a trace without the operations) and never
raises for that.

Kernels are found in the trace by the reducer's stable names
(``trace_reduce.stable_name``: ``pallas`` and the shapes a custom call
writes). The grouped matmul writes two dimensions, rows by a width. The
page walk writes a slot's rows [B, H, D] and both pools (five
dimensions each). The flash forward kernel writes [B*H, S, D] and the
float32 log-sum-exp [B*H, 1, S], so its name carries the bucket.
"""

import re

from .. import arch, flops
from .engine import _delta, _per_step, _window_rows
from .moe import GROUPED
from .moe import _delta as _moe_delta
from .trace import decode_step_device_s_p50

PAGE_WALK = re.compile(
    r"^pallas_[a-z0-9]+_[0-9]+_[0-9]+_[0-9]+_[a-z0-9]+(_[0-9]+){5}")
FLASH = re.compile(
    r"^pallas_[a-z0-9]+_([0-9]+)_([0-9]+)_([0-9]+)_f32_\1_1_\2$")


def _peak(record):
    return flops.peaks(record["worker"]["device"]["kind"])


def _counts(record, *functions):
    """The configuration's counts module if it offers ``functions``."""
    counts = arch.counts(record["config"])
    return counts if all(hasattr(counts, f) for f in functions) else None


def _ops_s(record, pattern):
    """[(match, runs, seconds)] of the traced operations ``pattern``
    matches; None without a trace or a match."""
    trace = record["trace"]
    if not trace:
        return None
    found = [(pattern.match(name), n, s) for name, n, s in trace["ops"]]
    return [f for f in found if f[0]] or None


def _traced_runs(record, program):
    return len(record["trace"]["modules"].get(program, ()))


def window_kv_read_share(record):
    """KV rows the decode steps read over what they would have read had
    every layer attended to the whole context."""
    rows, tokens = (_delta(record, "decode_kv_rows_read"),
                    _delta(record, "decode_kv_tokens"))
    if rows is None or not tokens:
        return None
    return 100.0 * rows / (tokens * record["config"]["num_hidden_layers"])


def kv_held_share(record):
    """Pages the live sequences held, layer by layer, over what one
    table for all layers would have held; summed over decode steps."""
    held, one_table = (_delta(record, "kv_page_steps_held"),
                       _delta(record, "kv_page_steps_one_table"))
    if held is None or not one_table:
        return None
    return 100.0 * held / one_table


def _routed_s(record):
    """Device seconds of the routed experts' grouped matmuls: the
    two-dimensional custom calls as wide as an expert or the model."""
    config = record["config"]
    if "moe_intermediate_size" not in config:
        return None
    widths = {str(config["moe_intermediate_size"]),
              str(config["hidden_size"])}
    found = _ops_s(record, GROUPED)
    if not found:
        return None
    return sum(s for m, _, s in found if m.group(2) in widths) or None


def routed_matmul_time_share(record):
    """The routed experts' grouped matmuls' share of the busy time."""
    routed = _routed_s(record)
    if routed is None:
        return None
    return 100.0 * routed / record["trace"]["busy_s"]


def routed_matmul_roofline(record):
    """As ``moe_matmul_roofline``, by the routed experts' own width: the
    least time for the expert matmuls of the programs the trace saw (a
    decode step's and a prefill's assignments and (layer, expert) pairs
    reached, each the window's mean for that program, times its runs in
    the trace) over the time they took."""
    routed = _routed_s(record)
    counts = _counts(record, "moe_matmul_flops", "moe_matmul_bytes")
    deltas = {k: _moe_delta(record, k) for k in (
        "assignments", "decode_assignments", "experts_reached",
        "prefill_experts_reached")}
    if routed is None or counts is None or None in deltas.values():
        return None
    runs = {"decode_step": _delta(record, "decode_steps"),
            "prefill": _delta(record, "prefills")}
    work = {"decode_step": (deltas["decode_assignments"],
                            deltas["experts_reached"]),
            "prefill": (deltas["assignments"] - deltas["decode_assignments"],
                        deltas["prefill_experts_reached"])}
    config, least = record["config"], 0.0
    for program, (assignments, pairs) in work.items():
        traced = _traced_runs(record, program)
        if runs[program] and traced:
            least += traced * flops.roofline_s(
                counts.moe_matmul_flops(config, assignments / runs[program]),
                counts.moe_matmul_bytes(config, assignments / runs[program],
                                        pairs / runs[program]), _peak(record))
    return 100.0 * least / routed


def page_walk_roofline(record):
    """The least time for the decode steps' attention in the trace (the
    rows the engine counted a step, each a key and a value row of one
    layer, and their two matmuls) over the page walk's time."""
    walks = _ops_s(record, PAGE_WALK)
    counts = _counts(record, "kv_row_bytes")
    rows = _per_step(record, _delta(record, "decode_kv_rows_read"))
    if not walks or counts is None or rows is None:
        return None
    config = record["config"]
    rows *= _traced_runs(record, "decode_step")
    least = flops.roofline_s(
        4 * rows * config["num_attention_heads"] * counts.head_dim(config),
        rows * counts.kv_row_bytes(config), _peak(record))
    return 100.0 * least / sum(s for _, _, s in walks)


def prefill_flash_roofline(record):
    """The least time for the causal attention of the prefills in the
    trace over the flash kernel's time. The kernel's name carries its
    bucket and it runs once a layer, so the trace says how many prefills
    of each bucket it saw; each counts as the window's mean prompt of
    that bucket, a window layer's attention cut at its lower bound."""
    calls = _ops_s(record, FLASH)
    counts = _counts(record, "flash_prefill_flops", "flash_prefill_bytes")
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
            counts.flash_prefill_flops(config, tokens),
            counts.flash_prefill_bytes(config, tokens), _peak(record))
    return 100.0 * least / sum(s for _, _, s in calls)


def decode_step_roofline_rows(record):
    """``decode_step_roofline_counted`` with what a window layer spares
    and the experts really reached: the least time for a decode step at
    the window's mean sequences, KV rows read and (layer, expert) pairs
    reached, over the traced step's median."""
    step = decode_step_device_s_p50(record)
    counts = _counts(record, "decode_step_flops_rows",
                     "decode_step_bytes_rows")
    sequences = _per_step(record, _delta(record, "decode_slot_steps"))
    rows = _per_step(record, _delta(record, "decode_kv_rows_read"))
    pairs = _per_step(record, _moe_delta(record, "experts_reached"))
    if not step or counts is None or None in (sequences, rows, pairs):
        return None
    config = record["config"]
    least = flops.roofline_s(
        counts.decode_step_flops_rows(config, sequences, rows),
        counts.decode_step_bytes_rows(config, sequences, rows, pairs),
        _peak(record))
    return 100.0 * least / step
