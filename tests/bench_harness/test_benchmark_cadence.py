"""The readers of a gap taken apart inside the replica
(``benchmark/readers/cadence.py``) on a hand-made record: exact values
with the arithmetic beside them, the histogram's percentile against the
samples' own, and None on an engine whose ``stats()`` has none of the
keys (the parent of PR 59). CPU, no processes, no sleeps."""

import copy
import os
import random
import statistics
import sys
from bisect import bisect_right

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import run as bench_run  # noqa: E402
from benchmark.readers import cadence as readers  # noqa: E402

# Edges a factor of two apart keep the arithmetic on one line; the
# engine's are 2% apart (``serve/llm.py:HIST_EDGES_S``).
EDGES = [0.001, 0.002, 0.004, 0.008, 0.016]
LAYERS = {"engine cadence": 3, "replica threads": 4, "device feed": 1}


def _hist(**at):
    """Six counts, all zero but ``b<i>=count``."""
    return [at.get(f"b{i}", 0) for i in range(len(EDGES) + 1)]


def _record():
    before = {
        "phase_s": {"admit": 1.0, "inputs": 0.5, "decode": 0.25,
                    "readback": 2.0, "emit": 0.25, "idle": 6.0},
        "phase_cpu_s": {"admit": 0.5, "inputs": 0.5, "decode": 0.25,
                        "readback": 0.0, "emit": 0.25, "idle": 0.0},
        "decode_dispatch": {"fed": 5, "starved_host": 1,
                            "starved_prefill": 1, "starved_lull": 3},
        "stream": {"hist_edges_s": EDGES, "held_s": 9.0,
                   "held_timed_s": 1.0, "held_cpu_s": 0.5,
                   "emit_gap_hist": _hist(b2=10, b5=7),
                   "taken_lag_hist": _hist(b1=4),
                   "held_hist": _hist(b0=2, b1=2)}}
    after = {
        "phase_s": {"admit": 3.0, "inputs": 1.5, "decode": 2.25,
                    "readback": 20.0, "emit": 1.25, "idle": 6.5},
        "phase_cpu_s": {"admit": 0.75, "inputs": 1.25, "decode": 1.0,
                        "readback": 0.5, "emit": 0.75, "idle": 0.25},
        "decode_dispatch": {"fed": 165, "starved_host": 11,
                            "starved_prefill": 31, "starved_lull": 3},
        "stream": {"hist_edges_s": EDGES, "held_s": 41.0,
                   "held_timed_s": 5.0, "held_cpu_s": 1.5,
                   # The window's: 80 in [0.002, 0.004), 15 in
                   # [0.004, 0.008), 5 from 0.016 on.
                   "emit_gap_hist": _hist(b2=90, b3=15, b5=12),
                   # 50 under 0.001, 30 in [0.001, 0.002), 20 in
                   # [0.008, 0.016).
                   "taken_lag_hist": _hist(b0=50, b1=34, b4=20),
                   # 40 in [0.001, 0.002), 60 in [0.002, 0.004).
                   "held_hist": _hist(b0=2, b1=42, b2=60)}}
    return {"worker": {"engine": after, "engine_before": before}}


EXPECTED = {
    # Rank 50 of 100 is the 50th of the 80 in [0.002, 0.004).
    "emit_gap_s_p50.chat": 0.002 * 2 ** (50 / 80),
    # Rank 90: the 10th of the 15 in [0.004, 0.008).
    "emit_gap_s_p90.chat": 0.004 * 2 ** (10 / 15),
    # Rank 99 lies in the open bucket: its edge.
    "emit_gap_s_p99.chat": 0.016,
    # Rank 90 of 100: the 10th of the 20 in [0.008, 0.016).
    "stream_taken_lag_s_p90.chat": 0.008 * 2 ** (10 / 20),
    # Rank 90 of 100: the 50th of the 60 in [0.002, 0.004).
    "stream_seal_s_p90.chat": 0.002 * 2 ** (50 / 60),
    # inputs + decode + emit: 4.0 s of wall, 0.75 + 0.75 + 0.5 on the CPU.
    "engine_loop_offcpu_share.chat": 100 * (1 - 2.0 / 4.0),
    # Of the 32 s held 4.0 were also timed on the CPU clock: 1.0 on it.
    "stream_offcpu_share.chat": 75.0,
    # 160 fed, 10 + 30 starved with a stream open, no lull: 40 of 200.
    "decode_starved_share.chat": 20.0,
}
BENCH = bench_run.load_benchmark()
METRICS = [m for m in BENCH["per_layer"] if m["layer"] in LAYERS]


def test_the_eight_metrics_are_the_ones_checked_here():
    assert {m["name"] for m in METRICS} == set(EXPECTED)
    assert {layer: sum(m["layer"] == layer for m in METRICS)
            for layer in LAYERS} == LAYERS
    assert all(m["moves"] == "gap_p90_s" and m["better"] == "lower"
               and m["source"] == "program_counter" for m in METRICS)
    assert all(m["unit"] == ("%" if "share" in m["name"] else "s")
               for m in METRICS)
    # Which cells: the rule of the host-loop and stream-path metrics.
    # Any cell whose traffic file is of kind serve, in BENCHMARK.json's
    # order, none twice; the seven of PR 59 stay.
    serving = [c["name"] for c in BENCH["workloads"]
               if bench_run.load_cell(BENCH, c["name"])[2]["kind"] == "serve"]
    for metric in METRICS:
        listed = metric["workloads"]
        assert listed == [c for c in serving if c in listed] != []
        assert {"serve-mistral7b-chat", "serve-olmoe-c16",
                "serve-trinity-c16-long", "serve-joyai-c16-4k",
                "serve-brumby-c16-8k", "serve-glm52-c8-16k",
                "serve-smallthinker-c16-8k"} <= set(listed)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_a_hand_made_record(name):
    assert bench_run.find_reader(name)(_record()) == pytest.approx(
        EXPECTED[name], rel=1e-9)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_finds_nothing_on_an_engine_without_the_keys(name):
    record = _record()
    for reading in ("engine", "engine_before"):
        stats = record["worker"][reading]
        del stats["phase_cpu_s"], stats["decode_dispatch"]
        # The parent's ``stream``: sums, no histogram, no CPU time.
        stats["stream"] = {"held_s": stats["stream"]["held_s"]}
    assert bench_run.find_reader(name)(record) is None
    # ... and the run's line leaves the metric out, without raising.
    metric, = [m for m in METRICS if m["name"] == name]
    assert bench_run.read_metrics([metric], record) == {}
    # An engine with no ``stream`` at all (before PR 40) reads the same.
    for reading in ("engine", "engine_before"):
        del record["worker"][reading]["stream"]
    assert bench_run.find_reader(name)(record) is None


def test_an_empty_window_gives_no_figure():
    record = _record()
    record["worker"]["engine"] = copy.deepcopy(
        record["worker"]["engine_before"])
    for name in EXPECTED:
        assert bench_run.find_reader(name)(record) is None


def test_a_lull_is_not_starvation_and_a_late_loop_is():
    record = _record()
    found = record["worker"]["engine"]["decode_dispatch"]
    found["starved_lull"] += 200    # an open loop's idle seconds
    assert readers.decode_starved_share(record) == pytest.approx(10.0)
    found["starved_host"] += 400
    assert readers.decode_starved_share(record) == pytest.approx(
        100 * 440 / 800)


@pytest.mark.parametrize("q", [50, 90, 99])
def test_a_percentile_off_the_engines_edges_is_the_samples_own(q):
    # The engine's rule (serve/llm.py), written out: the reader takes
    # the edges from the record and knows no rule.
    edges = [1e-5 * 1.02 ** i for i in range(792)]
    rng = random.Random(q)
    samples = [0.0098 * rng.uniform(0.99, 1.01)
               + (rng.uniform(0.05, 0.4) if rng.random() < 0.03
                  else rng.expovariate(1 / 0.0005)) for _ in range(20_000)]
    counts = [0] * (len(edges) + 1)
    for sample in samples:
        counts[bisect_right(edges, sample)] += 1
    exact = statistics.quantiles(samples, n=1000)[q * 10 - 1]
    assert readers.hist_quantile(counts, edges, q) == pytest.approx(
        exact, rel=0.025)
