"""Readers of a model whose layers are gated short convolutions among
grouped-query layers with heads of 64 (PR 73): what a slot holds in the
conv layers and how many layers write pages at all, from the engine's
own counts; the conv mixers' share of the device's time, the whole
decode step and the prefills' flash kernel at heads of 64 against their
rooflines, from the device trace and the same counters.

``LLMEngine.stats()["conv"]`` (``slot_layers`` shifted, ``slot_bytes``,
``layers`` of ``layers_in_all``) is what the counters are; a program from
before PR 73 has none, and every reader then returns None. So it does
for a configuration whose counts lack the functions, a run that was not
traced, a trace without the operations: never raises for that.

The conv mixers are no kernel of their own: three XLA operations a layer
under the named scopes ``conv.proj``, ``conv.mix`` and ``conv.out``. The
reducer keeps an operation's kind and the shapes it writes
(``trace_reduce.stable_name``), not its scope, so they are found by what
they write and nothing else of such a model does: ``conv.proj``'s
product, the slots' three gates, ``[slots, 3 x hidden]``, and
``conv.mix``'s histories, the pool ``[conv layers, taps - 1, slots,
hidden]`` and a layer's slice of it. ``conv.out``'s product is ``[..,
hidden]`` wide as every other projection's back into the residual is and cannot be told from
them by name (``bitcast_add_fusion_bf16_16_1_2048``, ten a step of this
model: eight conv layers' and two attention layers'; my chip run, PR 73):
the share read here is the first two scopes', three quarters of a
mixer's bytes (``W_in`` 3 hidden^2 of 4 hidden^2), and a floor of the
three.
"""

import re

from .. import flops
from .engine import _delta, _per_step, _window_rows
from .moe import _delta as _moe_delta
from .trace import decode_step_device_s_p50
from .window import _counts, _ops_s, _peak

# The flash forward at heads of 64 writes [heads, bucket, 64] and the
# float32 log-sum-exp [heads, 1, bucket]; the streamed form (the 16,384
# bucket) writes them the other way round. A head of 128's kernel does
# not match, nor the page walk (three dimensions, five and five).
FLASH_H64 = re.compile(
    r"^pallas_(?:[a-z0-9]+_(?P<h>[0-9]+)_(?P<s>[0-9]+)_64_f32_(?P=h)_1_(?P=s)"
    r"|f32_(?P<hs>[0-9]+)_1_(?P<ss>[0-9]+)_[a-z0-9]+_(?P=hs)_(?P=ss)_64)$")


def _group(record):
    return record["worker"]["engine"].get("conv") or {}


def conv_slot_bytes(record):
    """What a slot holds over all the conv layers, as the engine
    allocated them: the last ``taps - 1`` rows a layer."""
    return _group(record).get("slot_bytes")


def kv_layers_share(record):
    """Layers that write pages, of the layers in all: what of the stack
    keeps a row a token."""
    conv = _group(record)
    if not conv.get("layers_in_all"):
        return None
    return 100.0 * (conv["layers_in_all"] - conv["layers"]) / conv[
        "layers_in_all"]


def _conv_patterns(record):
    """The stable names of the operations only a conv mixer's decode
    step writes: the gates' product ``[slots, (1,) 3 hidden]``, the
    histories' pool and a layer's slice of it ``[slots, taps - 1,
    hidden]``. (A prefill writes ``[1, bucket, 3 hidden]`` and the same
    pool: its share of the pool's writes, a layer a prefill, is counted
    with the steps'.)"""
    config, conv = record["config"], _group(record)
    slots = (record["worker"]["engine"].get("active_slots", 0)
             + record["worker"]["engine"].get("free_slots", 0))
    if not conv or not slots or "conv_L_cache" not in config:
        return None
    hidden, rows = config["hidden_size"], config["conv_L_cache"] - 1
    name = r"^[a-z_.-]+_[a-z0-9]+_"
    return (re.compile(rf"^[a-z_.-]*fusion[a-z_.-]*_[a-z0-9]+_{slots}(_1)?_"
                       rf"{3 * hidden}$"),
            re.compile(rf"{name}{conv['layers']}_{rows}_{slots}_{hidden}$"),
            re.compile(rf"{name}{slots}_{rows}_{hidden}$"))


def conv_mix_time_share(record):
    """The conv mixers' operations the reducer can name (the gates'
    product and the taps' histories; the module docstring says why not
    ``conv.out``) as a share of the decode steps' device time."""
    trace = record["trace"]
    patterns = _conv_patterns(record)
    steps = trace and trace["modules"].get("decode_step")
    if not steps or patterns is None:
        return None
    found = sum(s for name, _, s in trace["ops"]
                if any(p.match(name) for p in patterns))
    if not found:
        return None
    return 100.0 * found / sum(steps)


def decode_step_roofline_conv(record):
    """The least time for a whole decode step at the window's mean
    sequences, KV rows read (at 64-wide heads, the slots' own) and
    (layer, expert) pairs reached, every other weight once and each
    sequence's histories read and written, over the traced step's
    median."""
    step = decode_step_device_s_p50(record)
    counts = _counts(record, "decode_step_flops_rows",
                     "decode_step_bytes_rows", "conv_slot_bytes")
    sequences = _per_step(record, _delta(record, "decode_slot_steps"))
    rows = _per_step(record, _delta(record, "decode_kv_rows_read"))
    pairs = _per_step(record, _moe_delta(record, "experts_reached"))
    if (not step or counts is None or not _group(record)
            or None in (sequences, rows, pairs)):
        return None
    config = record["config"]
    least = flops.roofline_s(
        counts.decode_step_flops_rows(config, sequences, rows),
        counts.decode_step_bytes_rows(config, sequences, rows, pairs),
        _peak(record))
    return 100.0 * least / step


def prefill_flash_h64_roofline(record):
    """The least time for the causal attention of the prefills in the
    trace over the flash kernel's time at heads of 64, resident or
    streamed. The kernel's name carries its bucket and it runs once an
    ATTENTION layer (two of this model's ten), so the trace says how many
    prefills of each bucket it saw; each counts as the window's mean
    prompt of that bucket (the kernel works on the whole bucket: what
    the padding costs is in the share)."""
    calls = _ops_s(record, FLASH_H64)
    counts = _counts(record, "flash_prefill_flops", "flash_prefill_bytes",
                     "n_layers", "ATTENTION")
    rows = _window_rows(record)
    if not calls or counts is None or not rows:
        return None
    config, least = record["config"], 0.0
    layers = counts.n_layers(config, counts.ATTENTION)
    for match, n, _ in calls:
        bucket = int(match["s"] or match["ss"])
        prompts = [row[4] for row in rows if row[5] == bucket]
        if not prompts or not layers:
            return None
        tokens = round(sum(prompts) / len(prompts))
        least += n / layers * flops.roofline_s(
            counts.flash_prefill_flops(config, tokens),
            counts.flash_prefill_bytes(config, tokens), _peak(record))
    return 100.0 * least / sum(s for _, _, s in calls)
