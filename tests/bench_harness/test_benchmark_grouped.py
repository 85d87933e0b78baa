"""``benchmark/readers/grouped.py`` on hand-made records, and its entry
in BENCHMARK.json. CPU, no processes."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import run  # noqa: E402
from benchmark.readers import grouped  # noqa: E402


def _record(before, after):
    engines = [{"decode_steps": 0}, {"decode_steps": 100}]
    for engine, moe in zip(engines, (before, after)):
        if moe is not None:
            engine["moe"] = moe
    return {"worker": {"engine_before": engines[0], "engine": engines[1]}}


def _moe(calls, small):
    return {"assignments": 0, "layer_calls": calls,
            "small_rows_layer_calls": small}


def test_share_of_the_windows_expert_layers_that_ran_the_kernel():
    # 100 decode steps and 10 prefills of 8 layers in the window, all
    # the steps and 3 of the prefills built with the kernel; what ran
    # before the window does not count.
    record = _record(_moe(400, 80), _moe(400 + 880, 80 + 824))
    assert grouped.grouped_small_rows_share(record) == pytest.approx(
        100 * 824 / 880)
    assert grouped.grouped_small_rows_share(
        _record(_moe(0, 0), _moe(880, 0))) == 0.0


@pytest.mark.parametrize("before,after", [
    (None, None),                                    # a dense model
    ({"assignments": 0}, {"assignments": 9}),        # the parent's engine
    ({"assignments": 0}, _moe(8, 8)),
    (_moe(16, 8), _moe(16, 8)),                      # no program ran
], ids=["dense", "no-counters", "counters-after-only", "empty-window"])
def test_nothing_to_read_is_none_and_never_raises(before, after):
    assert grouped.grouped_small_rows_share(_record(before, after)) is None


def test_the_metric_is_the_three_moe_cells_and_finds_its_reader():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"]
              if m["name"] == "grouped_small_rows_share.chat"]
    assert entry == {
        "name": "grouped_small_rows_share.chat", "unit": "%",
        "better": "higher", "source": "program_counter",
        "layer": "expert dispatch", "moves": "gap_p90_s",
        "workloads": ["serve-olmoe-c16", "serve-trinity-c16-long",
                      "serve-joyai-c16-4k"]}
    assert run.find_reader(entry["name"]) is grouped.grouped_small_rows_share
