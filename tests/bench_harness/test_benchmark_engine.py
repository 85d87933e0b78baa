"""The readers of ``LLMEngine.stats()``'s counters and request rows
(``benchmark/readers/engine.py``) on hand-made records: exact values, the
window's filter on ``t_submit``, and None on an engine that has only the
keys of before PR 25. CPU, no processes, no sleeps."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import flops  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark.readers import engine as readers  # noqa: E402

WINDOW_START = 1000.0
OLD_KEYS = {"platform": "tpu", "device_kind": "TPU v5 lite",
            "active_slots": 0, "free_slots": 32, "free_pages": 2048,
            "total_pages": 2048, "page_size": 16}


def _config():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "mistral-7b-v0.3-L16.json")) as f:
        return json.load(f)


def _record(trace=True):
    before = {**OLD_KEYS, "decode_steps": 10, "decode_slot_steps": 15,
              "decode_kv_tokens": 3000, "prefills": 4, "prefill_tokens": 400,
              "prefill_bucket_tokens": 512,
              "phase_s": {"admit": 1.0, "admit_stalling": 0.0, "inputs": 0.5,
                          "decode": 0.25, "readback": 2.0, "emit": 0.25,
                          "idle": 6.0}}
    after = {**OLD_KEYS, "decode_steps": 110, "decode_slot_steps": 1515,
             "decode_kv_tokens": 453000, "prefills": 9,
             "prefill_tokens": 1000, "prefill_bucket_tokens": 1312,
             "phase_s": {"admit": 1.5, "admit_stalling": 0.4, "inputs": 0.7,
                         "decode": 0.35, "readback": 16.0, "emit": 0.35,
                         "idle": 7.1},
             # [t_submit, t_admit, t_first, t_done, prompt_len, bucket]
             "requests": [
                 [990.0, 990.5, 991.0, 992.0, 100, 128],     # warm-up
                 [999.999, 1000.2, 1000.3, 1001.0, 100, 128],  # before it
                 [1000.0, 1000.10, 1000.14, 1003.0, 100, 128],
                 [1001.0, 1001.02, 1001.05, 1004.0, 100, 128],
                 [1002.0, 1002.06, 1002.10, None, 200, 256],
                 [1003.0, 1003.16, 1003.19, None, 200, 256],
                 [1004.0, 1004.08, None, None, 300, 512],    # in prefill
             ]}
    return {
        "worker": {"engine": after, "engine_before": before,
                   "window_start": WINDOW_START,
                   "device": {"kind": "TPU v5 lite", "count": 1}},
        "config": _config(),
        "trace": {"modules": {"decode_step": [0.15, 0.16, 0.14]}}
        if trace else None,
    }


def _roofline_at_15_sequences_4500_tokens():
    config = _config()
    least = flops.roofline_s(flops.decode_step_flops(config, 15.0, 4500.0),
                             flops.decode_step_bytes(config, 15.0, 4500.0),
                             flops.peaks("TPU v5 lite"))
    return 100.0 * least / 0.15


EXPECTED = {
    # Waits of the five rows submitted at or after 1000.0:
    # 0.10, 0.02, 0.06, 0.16, 0.08.
    "admit_wait_s_p50.chat": 0.08,
    "admit_wait_s_p90.chat": 0.10 + 0.6 * 0.06,
    # Admit to first token, of the four that have one: 0.04, 0.03, 0.04, 0.03.
    "engine_prefill_s_p50.chat": 0.035,
    # Submit to first token: 0.14, 0.05, 0.10, 0.19.
    "engine_ttft_s_p90.chat": 0.14 + 0.7 * 0.05,
    "decode_batch_mean.chat": 15.0,
    "decode_step_roofline_counted.chat":
        _roofline_at_15_sequences_4500_tokens(),
    # (0.2 + 0.1 + 0.1) s of inputs, dispatch and emit over 100 steps.
    "engine_host_s_per_step.chat": 0.004,
    # 0.4 s of 0.5 + 0.2 + 0.1 + 14 + 0.1 + 1.1 = 16 s.
    "admit_stall_share.chat": 2.5,
    # 600 real tokens in 800 padded.
    "prefill_padding_share.chat": 25.0,
}
METRICS = [m for m in bench_run.load_benchmark()["per_layer"]
           if m["layer"] == "engine host loop"
           and m["name"] != "batch_occupancy.chat"]


def test_the_nine_metrics_are_the_ones_checked_here():
    assert {m["name"] for m in METRICS} == set(EXPECTED)
    assert all(m["workloads"] == ["serve-mistral7b-chat", "serve-olmoe-c16"]
               and m["moves"] == "gap_p90_s" for m in METRICS)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_a_hand_made_record(name):
    assert bench_run.find_reader(name)(_record()) == pytest.approx(
        EXPECTED[name], rel=1e-9)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_finds_nothing_on_an_engine_without_the_counters(name):
    record = _record()
    record["worker"]["engine"] = {**OLD_KEYS, "decode_steps": 110}
    record["worker"]["engine_before"] = {**OLD_KEYS, "decode_steps": 10}
    assert bench_run.find_reader(name)(record) is None
    # ... and the run's line leaves the metric out, without raising.
    metric, = [m for m in METRICS if m["name"] == name]
    assert bench_run.read_metrics([metric], record) == {}


def test_only_the_traced_metric_needs_a_trace():
    record = _record(trace=False)
    got = bench_run.read_metrics(METRICS, record)
    assert set(got) == set(EXPECTED) - {"decode_step_roofline_counted.chat"}
    needs_trace = [m["name"] for m in METRICS
                   if m["source"] == "device_trace"]
    assert needs_trace == ["decode_step_roofline_counted.chat"]


def test_the_window_takes_the_rows_submitted_in_it():
    record = _record()
    assert readers.admit_wait_s_p50(record) == pytest.approx(0.08)
    # From the engine's start: 0.5 and 0.201 join 0.10, 0.02, 0.06,
    # 0.16, 0.08.
    record["worker"]["window_start"] = 0.0
    assert readers.admit_wait_s_p50(record) == pytest.approx(0.10)
    # A window nothing was submitted in has no percentile.
    record["worker"]["window_start"] = 2000.0
    assert readers.admit_wait_s_p50(record) is None
    assert readers.engine_ttft_s_p90(record) is None


def test_no_decode_step_no_per_step_figure():
    record = _record()
    record["worker"]["engine"]["decode_steps"] = 10
    for reader in (readers.decode_batch_mean, readers.engine_host_s_per_step,
                   readers.decode_step_roofline_counted):
        assert reader(record) is None
    record["worker"]["engine"]["prefill_bucket_tokens"] = 512
    assert readers.prefill_padding_share(record) is None
