"""Readers of a model that selects BLOCKS of its paged k/v pool beside
Lightning linear-attention layers (PR 70): the decode step's selection,
its walk under the selection and its Lightning step, the prefill's
restricted flash attention and chunked Lightning scan, and the whole
decode step against their rooflines, from the device trace and the
engine's counters; what the walk read of the pages held and what a slot
holds in the Lightning layers, from the engine's own counts.

``LLMEngine.stats()["blocks"]`` (``pages_read``, ``pages_held``: (page,
layer) pairs a KV head, summed over decode steps and sequences) and
``stats()["linear"]`` (``slot_layers`` stepped, ``slot_bytes``) are what
the counters are; a program from before PR 70 has neither, and every
reader then returns None. So it does for a configuration whose counts
lack the functions, a run that was not traced, a trace without the
kernel: never raises for that.

What the traced steps carried is read where they ran
(``readers/hybrid.py``'s reason and its ``_traced_sequences``): each
counter's mean a SEQUENCE a step from the window, times the sequences
that held a slot while the trace ran.

The kernels are found in the trace by the reducer's stable names
(``trace_reduce.stable_name``: ``pallas`` and the shapes a custom call
writes), each unlike every older kernel's:

- the block selection writes each (slot, KV head)'s kept pages, int32
  [B, Hkv, 1, pages] alone: one output of four dimensions, int32;
- the block walk writes a (slot, KV head)'s rows [B, Hkv, G, D] and both
  pools, five dimensions each: four, five and five (the page walk
  writes three, five and five);
- the Lightning step writes the read-outs float32 [B, blocks, heads, 1,
  d] and the pool of states float32 [L, B, H, d, d]: five and five (the
  delta step writes four and five, the retention step four and six);
- the Lightning scan writes the outputs [H, 1, S, d] and a slot's states
  float32 [H, d, d]: four and three, its name carries the bucket S;
- the restricted flash attention writes [Hkv, G, S, D] alone, in the
  model's dtype: one output of four dimensions (the selection tiles of
  ``readers/sparse.py`` are int8, the block selection's pages int32).
"""

import re

from .. import flops
from .engine import _delta, _window_rows
from .hybrid import _traced_sequences
from .trace import decode_step_device_s_p50
from .window import _counts, _ops_s, _peak, _traced_runs

_D = r"(_[0-9]+)"
BLOCK_SELECT = re.compile(rf"^pallas_s32{_D}{{4}}$")
BLOCK_WALK = re.compile(
    rf"^pallas_[a-z0-9]+{_D}{{4}}_[a-z0-9]+{_D}{{5}}_[a-z0-9]+{_D}{{5}}$")
LIGHTNING_STEP = re.compile(rf"^pallas_f32{_D}{{5}}_f32{_D}{{5}}$")
LIGHTNING_SCAN = re.compile(
    r"^pallas_[a-z0-9]+_([0-9]+)_1_([0-9]+)_([0-9]+)_f32_\1_\3_\3$")
BLOCK_FLASH = re.compile(
    r"^pallas_(?!s8_|s32_)[a-z0-9]+_([0-9]+)_([0-9]+)_([0-9]+)_([0-9]+)$")


def _group(record, name):
    return record["worker"]["engine"].get(name) or {}


def _traced_per_step(record, group, key):
    """A counter of ``stats()[group]``: its mean a step in the TRACED
    steps (a sequence a step over the window, times the sequences the
    traced steps carried); None without the counter or such a step."""
    total = _delta(record, key, group)
    slot_steps = _delta(record, "decode_slot_steps")
    sequences = _traced_sequences(record)
    if total is None or not slot_steps or not sequences:
        return None
    return total / slot_steps * sequences


def _in_trace(record, group, key):
    """What the traced decode steps together added to that counter."""
    per_step = _traced_per_step(record, group, key)
    if not per_step:
        return None
    return per_step * _traced_runs(record, "decode_step") or None


def _seconds(record, pattern):
    found = _ops_s(record, pattern)
    return sum(s for _, _, s in found) if found else None


def _share(record, seconds):
    if not seconds:
        return None
    return 100.0 * seconds / record["trace"]["busy_s"]


def block_select_time_share(record):
    """The decode selection kernel's (scores of the page means, the
    blocks' scores, the exact top-k, the kept pages in order) share of
    the device's busy time."""
    return _share(record, _seconds(record, BLOCK_SELECT))


def block_walk_roofline(record):
    """The least time for what the selection and the walk under it must
    move in the decode steps of the trace (the SELECTED pages' k and v,
    each once, and a mean key for every page held, with their matmuls)
    over the time of the two kernels that do it."""
    walk = _seconds(record, BLOCK_WALK)
    counts = _counts(record, "block_walk_flops", "block_walk_bytes")
    pages, held = (_in_trace(record, "blocks", key)
                   for key in ("pages_read", "pages_held"))
    if not walk or counts is None or not pages or not held:
        return None
    config = record["config"]
    least = flops.roofline_s(counts.block_walk_flops(config, pages, held),
                             counts.block_walk_bytes(config, pages, held),
                             _peak(record))
    return 100.0 * least / (walk + (_seconds(record, BLOCK_SELECT) or 0.0))


def linear_step_time_share(record):
    """The decode Lightning kernel's share of the device's busy time."""
    return _share(record, _seconds(record, LIGHTNING_STEP))


def linear_step_roofline(record):
    """The least time for the state traffic of the decode steps in the
    trace (each counted state read once and written once, its decay,
    write and read-out) over the decode Lightning kernel's time."""
    steps = _seconds(record, LIGHTNING_STEP)
    counts = _counts(record, "linear_step_flops", "linear_step_bytes")
    slot_layers = _in_trace(record, "linear", "slot_layers")
    if not steps or counts is None or not slot_layers:
        return None
    config = record["config"]
    least = flops.roofline_s(counts.linear_step_flops(config, slot_layers),
                             counts.linear_step_bytes(config, slot_layers),
                             _peak(record))
    return 100.0 * least / steps


def decode_step_roofline_blocks(record):
    """The least time for a whole decode step at the traced steps'
    sequences, selected pages, pages held and states stepped (every
    weight once, the selected pages and every page mean read once, each
    state read and written), over the traced step's median."""
    step = decode_step_device_s_p50(record)
    counts = _counts(record, "decode_step_flops_blocks",
                     "decode_step_bytes_blocks")
    sequences = _traced_sequences(record)
    work = [_traced_per_step(record, group, key) for group, key in (
        ("blocks", "pages_read"), ("blocks", "pages_held"),
        ("linear", "slot_layers"))]
    if not step or counts is None or not sequences or None in work:
        return None
    config = record["config"]
    least = flops.roofline_s(
        counts.decode_step_flops_blocks(config, sequences, *work),
        counts.decode_step_bytes_blocks(config, sequences, *work),
        _peak(record))
    return 100.0 * least / step


def _prefill_roofline(record, calls, layers, flops_of, bytes_of):
    """The least time for one kernel's work in the prefills of the trace
    over its time: ``calls`` [(bucket, runs, seconds)], the kernel runs
    once a layer of its kind (``layers``), each prefill counts as the
    window's mean prompt of its bucket: real tokens, not the bucket's."""
    rows = _window_rows(record)
    if not calls or not rows or not layers:
        return None
    config, least = record["config"], 0.0
    for bucket, n, _ in calls:
        prompts = [row[4] for row in rows if row[5] == bucket]
        if not prompts:
            return None
        tokens = round(sum(prompts) / len(prompts))
        least += n / layers * flops.roofline_s(
            flops_of(config, tokens), bytes_of(config, tokens),
            _peak(record))
    return 100.0 * least / sum(s for _, _, s in calls)


def prefill_block_sparse_roofline(record):
    """The least time for the restricted attention of the prefills in
    the trace (a head's q.k and p.v over the pairs a query attends to:
    causal up to ``dense_len``, ``topk`` blocks after) over the
    restricted flash kernel's time, which computes every causal step of
    keys under the selection as a mask: a kernel that skips nothing
    reads low here."""
    counts = _counts(record, "block_prefill_flops", "block_prefill_bytes",
                     "n_layers", "SPARSE")
    if counts is None or not record["trace"]:
        return None
    config = record["config"]
    shape = (config["num_key_value_heads"],
             config["num_attention_heads"] // config["num_key_value_heads"])
    calls = [(int(m.group(3)), n, s)
             for m, n, s in _ops_s(record, BLOCK_FLASH) or ()
             if (int(m.group(1)), int(m.group(2))) == shape]
    return _prefill_roofline(
        record, calls, counts.n_layers(config, counts.SPARSE),
        counts.block_prefill_flops, counts.block_prefill_bytes)


def prefill_linear_roofline(record):
    """The least time for the Lightning layers of the prefills in the
    trace over the chunked scan kernel's time."""
    counts = _counts(record, "linear_prefill_flops", "linear_prefill_bytes",
                     "n_layers", "LINEAR")
    if counts is None or not record["trace"]:
        return None
    config = record["config"]
    calls = [(int(m.group(2)), n, s)
             for m, n, s in _ops_s(record, LIGHTNING_SCAN) or ()]
    return _prefill_roofline(
        record, calls, counts.n_layers(config, counts.LINEAR),
        counts.linear_prefill_flops, counts.linear_prefill_bytes)


def selected_pages_share(record):
    """Pages the decode steps' walk read, a KV head, over the pages the
    live sequences held in the selecting layers: what the walk that was
    kept really reads."""
    read, held = (_delta(record, key, "blocks")
                  for key in ("pages_read", "pages_held"))
    if read is None or not held:
        return None
    return 100.0 * read / held


def linear_slot_bytes(record):
    """What a slot holds over all the Lightning layers, as the engine
    allocated them."""
    return _group(record, "linear").get("slot_bytes")
