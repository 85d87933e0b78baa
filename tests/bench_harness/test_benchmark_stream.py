"""The readers of a token's way back (``benchmark/readers/stream.py``)
on a hand-made record: exact values with the arithmetic beside them, the
join of engine rows and client samples by id, and None on an engine
whose ``stats()`` has no ``t``, no ``stream`` and rows of six fields
(the parent of PR 40). CPU, no processes, no sleeps."""

import copy
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import run as bench_run  # noqa: E402
from benchmark.readers import stream as readers  # noqa: E402

T0 = 1000.0          # client.t0_wall = worker.window_start
LAYER = "stream path: the way back"
CHAT_CELLS = ["serve-mistral7b-chat", "serve-olmoe-c16",
              "serve-trinity-c16-long"]


def _sample(rid, token_s, cut=False, error=None):
    return {"id": rid, "token_s": token_s, "cut": cut, "error": error}


def _record():
    # The readings are 20 s apart: 999.5 (before the load) and 1019.5.
    before = {"t": 999.5, "stream": {
        "tokens_emitted": 12, "tokens_taken": 12, "taken_lag_s": 0.5,
        "held_s": 0.25, "backlog": 0}}
    after = {"t": 1019.5, "stream": {
        "tokens_emitted": 2012, "tokens_taken": 1012, "taken_lag_s": 2.5,
        "held_s": 0.75, "backlog": 1000},
        # [t_submit, t_admit, t_first, t_done, prompt, bucket, id, t_last_put]
        "requests": [
            [990.0, 990.1, 990.2, 990.3, 9, 16, -1, 990.31],   # warm-up
            [1001.0, 1001.1, 1001.20, 1003.0, 9, 16, 0, 1003.04],
            [1002.0, 1002.1, 1002.30, 1005.0, 9, 16, 1, 1005.10],
            [1003.0, 1003.1, 1003.25, 1006.0, 9, 16, 2, 1006.02],
            [1004.0, 1004.1, 1004.20, 1018.0, 9, 16, 3, 1018.01],  # cut
            [1005.0, 1005.1, 1005.21, None, 9, 16, 4, None],   # decoding
            [1006.0, 1006.1, 1006.22, 1009.0, 9, 16, None, 1009.5],  # no id
            [1007.0, 1007.1, 1007.50, 1009.0, 9, 16, 77, 1009.1],  # unknown
        ]}
    samples = [
        _sample(0, [1.21, 2.0, 3.5]),    # first +0.01, last +0.5
        _sample(1, [2.32, 3.0, 5.3]),    # first +0.02, last +0.3
        _sample(2, [3.30, 4.0, 6.9]),    # first +0.05, last +0.9
        _sample(3, [4.23, 5.0], cut=True),    # first +0.03
        _sample(4, [5.25]),              # first +0.04; still decoding
        _sample(5, [], error="HTTP 500"),
        # A token after the second reading does not count as delivered.
        _sample(6, [19.4, 19.6]),
    ]
    return {"worker": {"engine": after, "engine_before": before,
                       "window_start": T0},
            "client": {"t0_wall": T0, "samples": samples}}


EXPECTED = {
    # (2012 - 12) tokens in 20 s.
    "tokens_emitted_per_s.chat": 100.0,
    # 3 + 3 + 3 + 2 + 1 + 1 stamps inside [999.5, 1019.5], in 20 s.
    "tokens_delivered_per_s.chat": 13 / 20,
    # Ids 0..4: 0.01, 0.02, 0.05, 0.03, 0.04.
    "first_token_lag_s_p50.chat": 0.03,
    # Ended uncut, ids 0, 1, 2: 0.5, 0.3, 0.9.
    "last_token_lag_s_p50.chat": 0.5,
    # The same three: 0.04, 0.10, 0.02.
    "stream_replica_lag_s_p50.chat": 0.04,
    # (2.5 - 0.5) s over (1012 - 12) tokens taken.
    "stream_taken_lag_s_mean.chat": 0.002,
    # (0.75 - 0.25) s over the same 1000.
    "stream_seal_s_mean.chat": 0.0005,
}
METRICS = [m for m in bench_run.load_benchmark()["per_layer"]
           if m["layer"] == LAYER]


def test_the_seven_metrics_are_the_ones_checked_here():
    assert {m["name"] for m in METRICS} == set(EXPECTED)
    assert all(m["workloads"] == CHAT_CELLS and m["moves"] == "gap_p90_s"
               and m["source"] != "device_trace" for m in METRICS)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_a_hand_made_record(name):
    assert bench_run.find_reader(name)(_record()) == pytest.approx(
        EXPECTED[name], rel=1e-9)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_finds_nothing_on_an_engine_without_the_keys(name):
    record = _record()
    for reading in ("engine", "engine_before"):
        stats = record["worker"][reading]
        del stats["t"], stats["stream"]
        stats["requests"] = [row[:6] for row in stats.get("requests", [])]
    assert bench_run.find_reader(name)(record) is None
    # ... and the run's line leaves the metric out, without raising.
    metric, = [m for m in METRICS if m["name"] == name]
    assert bench_run.read_metrics([metric], record) == {}


def test_the_join_leaves_out_warm_up_requests_and_cut_streams():
    record = _record()
    joined = readers._joined(record)
    assert [row[6] for row, _ in joined] == [0, 1, 2, 3, 4]
    assert all(row[6] == sample["id"] for row, sample in joined)
    assert [row[6] for row, _ in readers._uncut(record)] == [0, 1, 2]
    # A warm-up request's id that a window's sample happens to share
    # still does not join: its row was submitted before the window.
    shared = copy.deepcopy(record)
    shared["worker"]["engine"]["requests"][0][6] = 6
    assert [row[6] for row, _ in readers._joined(shared)] == [0, 1, 2, 3, 4]
    # A failed stream joins nothing; one that ended with an error on
    # the client's side is not "uncut".
    failed = copy.deepcopy(record)
    failed["client"]["samples"][1]["error"] = "stream ended after 3"
    assert readers.last_token_lag_s_p50(failed) == pytest.approx(0.7)
    assert readers.stream_replica_lag_s_p50(failed) == pytest.approx(0.03)


def test_no_token_taken_no_time_between_readings_no_figure():
    record = _record()
    record["worker"]["engine"]["stream"]["tokens_taken"] = 12
    assert readers.stream_taken_lag_s_mean(record) is None
    assert readers.stream_seal_s_mean(record) is None
    record["worker"]["engine"]["t"] = 999.5
    assert readers.tokens_emitted_per_s(record) is None
    assert readers.tokens_delivered_per_s(record) is None
    # No request of the window ended: no lag to take a median of.
    record["worker"]["window_start"] = 2000.0
    assert readers.first_token_lag_s_p50(record) is None
    assert readers.last_token_lag_s_p50(record) is None
    assert readers.stream_replica_lag_s_p50(record) is None
