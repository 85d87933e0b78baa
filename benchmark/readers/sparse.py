"""Readers of a model that attends over a learned selection of a latent
pool and holds a share of its experts (PR 54): the decode step's index
walk and its walk under the selection, the prefill's selection and
restricted attention, from the device trace and the engine's counters;
what the selection kept of the rows held, what an indexer's key holds
in its pool, and the share of the router's assignments that fell on
experts held here.

Every reader returns None where there is nothing to read (an engine
without the gauge or the counters, as any program before PR 54; a
configuration whose counts lack the functions; a run that was not
traced; a trace without the kernel) and never raises for that.

The kernels are found in the trace by the reducer's stable names
(``trace_reduce.stable_name``: ``pallas`` and the shapes a custom call
writes). Both decode kernels write four dimensions and four, where the
latent walk writes three and four: the index walk a slot's selection,
float32 [B, 1, blocks, block], and the pool of indexer keys
[Li, P, page, index_head_dim]; the walk under a selection a slot's
rows [B, 1, H, kv_lora_rank] and the latent pool. The prefill's
selection writes int8 tiles [S/bq, S/bk, bq, bk] and nothing else; its
restricted flash attention one [H, S, v_head_dim] and nothing else (the
causal flash kernel writes a float32 log-sum-exp besides).
"""

import re

from .. import flops
from .engine import _delta, _per_step, _window_rows
from .moe import _delta as _moe_delta
from .window import _counts, _ops_s, _peak, _traced_runs

_TYPE = r"([a-z]+[0-9]+[a-z0-9]*)"
FOUR_AND_FOUR = re.compile(
    rf"^pallas_{_TYPE}((?:_[0-9]+){{4}})_{_TYPE}((?:_[0-9]+){{4}})$")
SELECT_TILES = re.compile(r"^pallas_s8_([0-9]+)_([0-9]+)_([0-9]+)_([0-9]+)$")
SPARSE_FLASH = re.compile(rf"^pallas_{_TYPE}_([0-9]+)_([0-9]+)_([0-9]+)$")


def _engine(record):
    return record["worker"]["engine"]


def _decode_kernel_s(record, first_last, second_last):
    """Device seconds of the decode kernels that write four dimensions
    and four, the first ending in ``first_last`` and the second in
    ``second_last`` (None: any)."""
    found = _ops_s(record, FOUR_AND_FOUR)
    if not found:
        return None
    total = 0.0
    for match, _, seconds in found:
        first, second = (match.group(i).split("_")[-1] for i in (2, 4))
        if (first_last in (None, int(first))
                and second_last in (None, int(second))):
            total += seconds
    return total or None


def _index_walk_s(record):
    width = record["config"].get("index_head_dim")
    return width and _decode_kernel_s(record, None, width)


def _sparse_walk_s(record):
    config = record["config"]
    if "index_topk" not in config:
        return None
    return _decode_kernel_s(record, config["kv_lora_rank"], None)


def _share(record, seconds):
    if seconds is None:
        return None
    return 100.0 * seconds / record["trace"]["busy_s"]


def index_score_time_share(record):
    """The index walk's (scoring and selection) share of the device's
    busy time."""
    return _share(record, _index_walk_s(record))


def index_score_roofline(record):
    """The least time for scoring the cached keys of the decode steps in
    the trace (the contexts the engine counted a step, in each indexing
    layer, each key read once and met by every index head's query) over
    the index walk's time, which also selects."""
    walk = _index_walk_s(record)
    counts = _counts(record, "index_score_flops", "index_score_bytes")
    tokens = _per_step(record, _delta(record, "decode_kv_tokens"))
    layers = _engine(record).get("pages", {}).get("index", {}).get("layers")
    if walk is None or counts is None or tokens is None or not layers:
        return None
    config = record["config"]
    keys = tokens * layers * _traced_runs(record, "decode_step")
    least = flops.roofline_s(counts.index_score_flops(config, keys),
                             counts.index_score_bytes(config, keys),
                             _peak(record))
    return 100.0 * least / walk


def sparse_walk_time_share(record):
    """The walk under a selection: its share of the device's busy time."""
    return _share(record, _sparse_walk_s(record))


def sparse_walk_roofline(record):
    """The least time for the absorbed attention over the SELECTED rows
    of the decode steps in the trace (``decode_kv_rows_selected`` a
    step, each read once) over the time of the walk under a selection,
    which reads every row: a walk that reads them all reads low here."""
    walk = _sparse_walk_s(record)
    counts = _counts(record, "sparse_walk_flops", "sparse_walk_bytes")
    rows = _per_step(record, _delta(record, "decode_kv_rows_selected"))
    if walk is None or counts is None or rows is None:
        return None
    config = record["config"]
    rows *= _traced_runs(record, "decode_step")
    least = flops.roofline_s(counts.sparse_walk_flops(config, rows),
                             counts.sparse_walk_bytes(config, rows),
                             _peak(record))
    return 100.0 * least / walk


def prefill_sparse_roofline(record):
    """The least time for the selection and the restricted attention of
    the prefills in the trace (every index head's query against the keys
    before it in the indexing layers, a head's q.k and p.v over
    sum_t min(t + 1, index_topk) pairs in every layer) over the time of
    the two kernels that do them. The restricted flash kernel's name
    carries its bucket and it runs once a layer, so the trace says how
    many prefills of each bucket it saw; each counts as the window's
    mean prompt of that bucket."""
    config = record["config"]
    counts = _counts(record, "prefill_sparse_flops", "prefill_sparse_bytes")
    rows = _window_rows(record)
    if counts is None or not rows or not record["trace"]:
        return None
    heads, width = config["num_attention_heads"], config["v_head_dim"]
    calls = [(int(m.group(3)), n, s)
             for m, n, s in _ops_s(record, SPARSE_FLASH) or ()
             if (int(m.group(2)), int(m.group(4))) == (heads, width)]
    tiles = _ops_s(record, SELECT_TILES) or ()
    if not calls:
        return None
    least = 0.0
    for bucket, n, _ in calls:
        prompts = [row[4] for row in rows if row[5] == bucket]
        if not prompts:
            return None
        tokens = round(sum(prompts) / len(prompts))
        least += n / config["num_hidden_layers"] * flops.roofline_s(
            counts.prefill_sparse_flops(config, tokens),
            counts.prefill_sparse_bytes(config, tokens), _peak(record))
    spent = (sum(s for _, _, s in calls) + sum(s for _, _, s in tiles))
    return 100.0 * least / spent


def selected_rows_share(record):
    """Rows the decode steps' attention took into its softmax over the
    rows the live sequences held, all layers together."""
    if "index" not in _engine(record).get("kv_row_bytes", {}):
        return None
    selected, read = (_delta(record, "decode_kv_rows_selected"),
                      _delta(record, "decode_kv_rows_read"))
    if selected is None or not read:
        return None
    return 100.0 * selected / read


def index_row_bytes(record):
    """What a token holds in one layer of the pool of indexer keys."""
    return _engine(record).get("kv_row_bytes", {}).get("index")


def routed_here_share(record):
    """Of the router's (token, expert) assignments in the window, those
    that fell on experts held here: 100 x held / all if it is even."""
    here, elsewhere = (_moe_delta(record, "assignments"),
                       _moe_delta(record, "assignments_elsewhere"))
    if here is None or elsewhere is None or not here + elsewhere:
        return None
    return 100.0 * here / (here + elsewhere)
