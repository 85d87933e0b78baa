"""The yardstick's arithmetic: percentiles, readings, the schedule, the
operation counts, and the readers on hand-made records. CPU, no
processes, no sleeps."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import arch, flops, loadgen, stats  # noqa: E402
from benchmark.readers import serve as serve_readers  # noqa: E402
from benchmark.readers import train as train_readers  # noqa: E402


def _config(name):
    with open(os.path.join(REPO, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def _traffic(name):
    with open(os.path.join(REPO, "benchmark", "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("values,q,want", [
    ([1.0], 90, 1.0),
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4, 5], 90, 4.6),
    ([5, 1, 4, 2, 3], 100, 5.0),
    ([0.0, 10.0], 25, 2.5),
])
def test_percentile_interpolates_between_ranks(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)


def test_percentile_of_nothing_is_nothing():
    assert stats.percentile([], 50) is None


def test_one_stalled_reading_lowers_the_throughput_and_not_the_median():
    steady = stats.readings_summary([4.0] * 11, 65536)
    stalled = stats.readings_summary([4.0] * 10 + [6.0], 65536)
    # All tokens over all the time: the stall is paid for.
    assert steady["tokens_per_s_window"] == 16384
    assert stalled["tokens_per_s_window"] == pytest.approx(11 * 65536 / 46.0)
    # The step's own speed does not move; the stall share says why.
    assert stalled["tokens_per_s_median"] == steady["tokens_per_s_median"] == 16384
    assert steady["stall_share"] == 0.0
    assert stalled["stall_share"] == pytest.approx(2.0 / 46.0)


def test_train_readers_on_a_hand_made_record():
    record = {
        "worker": {"reading_s": [4.0, 4.0, 5.0], "tokens_per_reading": 65536,
                   "input_wait_s": 0.13, "memory_peak_bytes": 15_000_000_000,
                   "device": {"kind": "TPU v5 lite", "count": 1}},
        "config": _config("mistral-7b-v0.3-L4"),
        "traffic": {"seqlen": 2048},
    }
    # End to end: 3 readings' tokens over the window's 13 s, stall in.
    assert train_readers.train_tokens_per_s(record) == pytest.approx(
        3 * 65536 / 13.0)
    assert train_readers.train_tokens_per_s_median(record) == 16384
    assert train_readers.step_stall_share(record) == pytest.approx(100 / 13)
    assert train_readers.input_wait_share(record) == pytest.approx(1.0)
    assert train_readers.step_mfu(record) == pytest.approx(
        100 * 16384 * 6_241_124_352 / 197e12)
    assert 0 < train_readers.step_mfu(record) < 100


@pytest.mark.parametrize("name,layer,matmul,total,kv_bytes,train_flops", [
    # layer = 2*4096*4096 + 2*4096*1024 + 3*4096*14336
    ("mistral-7b-v0.3-L4", 218_103_808, 1_006_632_960, 1_140_887_552,
     16_384, 6 * 1_006_632_960 + 6 * 4 * 2048 * 4096),
    ("mistral-7b-v0.3-L16", 218_103_808, 3_623_878_656, 3_758_231_552,
     65_536, 6 * 3_623_878_656 + 6 * 16 * 2048 * 4096),
    # layer = 2*5120*4096 + 2*5120*1024 + 3*5120*14336: heads of 128, not 160
    ("mistral-nemo-12b-L8", 272_629_760, 2_852_126_720, 3_523_302_400,
     32_768, 6 * 2_852_126_720 + 6 * 8 * 2048 * 4096),
])
def test_flops_against_hand_worked_counts(name, layer, matmul, total,
                                          kv_bytes, train_flops):
    config = _config(name)
    # Through the resolver, as the readers reach them.
    module = arch.counts(config)
    counts = module.param_counts(config)
    assert counts["layer"] == layer
    assert counts["matmul"] == matmul
    assert counts["total"] == total
    assert module.kv_bytes_per_token(config) == kv_bytes
    assert module.train_flops_per_token(config, 2048) == train_flops
    # The attention kernels' operations are the formula's second term.
    assert module.flash_train_flops(config, 8, 2048) == (
        train_flops - 6 * matmul) * 8 * 2048


def test_decode_step_is_bound_by_weight_bytes():
    config = _config("mistral-7b-v0.3-L16")
    counts = arch.counts(config)
    peak = flops.peaks("TPU v5 lite")
    nbytes = counts.decode_step_bytes(config, 20, 8000)
    assert nbytes == pytest.approx(
        2 * (3_623_878_656 + 135_168) + 8000 * 65_536 + 20 * 4096 * 2)
    least = flops.roofline_s(counts.decode_step_flops(config, 20, 8000),
                             nbytes, peak)
    assert least == pytest.approx(nbytes / 819e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="peaks.json"):
        flops.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("name,config", [
    ("chat-open", "mistral-7b-v0.3-L16"),
    ("chat-closed-c16", "olmoe-1b-7b-0125-L8")])
def test_schedule_is_a_pure_function_of_the_seed(name, config):
    traffic = _traffic(name)
    a = loadgen.schedule(traffic, 2 ** 31 + 5, 40, 32768)
    b = loadgen.schedule(traffic, 2 ** 31 + 5, 40, 32768)
    c = loadgen.schedule(traffic, 7, 40, 32768)
    assert a == b and a != c
    assert [r["id"] for r in a] == list(range(len(a)))
    # Every seed offers the same work, in another order.
    for key in (lambda r: len(r["prompt"]), lambda r: r["max_new_tokens"]):
        assert sorted(map(key, a)) == sorted(map(key, c))
    assert sum(len(r["prompt"]) for r in a) == sum(len(r["prompt"]) for r in c)
    due = [r["due_s"] for r in a]
    if "concurrency" in traffic:
        # A closed loop's list is its own length whatever the window's,
        # and a request is due when a client is free to send it.
        assert len(a) == traffic["requests"] == len(
            loadgen.schedule(traffic, 7, 51, 32768))
        assert due == [None] * len(a)
    else:
        assert len(a) == round(traffic["rate_per_s"] * 40)
        assert due == sorted(due) and 0 < due[0] and due[-1] < 40
    engine = _config(config)["engine"]
    for r in a:
        assert traffic["prompt"]["min"] <= len(r["prompt"]) <= traffic["prompt"]["max"]
        assert len(r["prompt"]) + r["max_new_tokens"] <= engine["max_len"]
        assert all(0 <= t < 32768 for t in r["prompt"][:8])


def test_lengths_follow_the_distribution_and_its_clip():
    spec = {"dist": "lognormal", "median": 256, "sigma": 1.0,
            "min": 32, "max": 1536}
    got = loadgen.lengths(spec, 101)
    assert got == sorted(got) and got[50] == 256
    assert got[0] == 32 and got[-1] == 1536
    uniform = loadgen.lengths({"dist": "loguniform", "min": 2048, "max": 7168}, 3)
    assert uniform == [round(2048 * 3.5 ** q) for q in (1 / 6, 0.5, 5 / 6)]
    with pytest.raises(ValueError):
        loadgen.lengths({"dist": "zipf", "min": 1, "max": 2}, 3)


def _sample(i, due, token_s, error=None, prompt_len=100):
    return {"id": i, "due_s": due, "sent_s": due + 0.001 * (i + 1),
            "prompt_len": prompt_len, "token_s": token_s, "error": error,
            "cut": False, "done_s": token_s[-1] if token_s else due + 1.0}


def test_serve_readers_on_hand_made_samples():
    samples = [
        _sample(0, 0.0, [0.2, 0.3, 0.4, 0.5]),
        _sample(1, 1.0, [1.1, 1.3, 1.5]),
        _sample(2, 2.0, [], error="HTTP 503"),       # failed: worst TTFT
        _sample(3, 9.0, [9.5, 10.5]),                # finishes after the window
    ]
    samples[3]["cut"] = True                         # closed with the window
    record = {
        "client": {"samples": samples, "closed_s": 10.5, "t0_wall": 1000.0},
        "worker": {"entered": {0: 1000.005, 1: 1001.006, 3: 1009.008},
                   "engine": {"decode_steps": 12},
                   "engine_before": {"decode_steps": 2}},
        "config": {"engine": {"max_batch": 2}},
    }
    # TTFTs 0.2, 0.1, 0.5 and the failed one at the worst seen, 10.5.
    assert serve_readers.ttft_p50_s(record) == pytest.approx(0.35)
    assert serve_readers.ttft_p90_s(record) == pytest.approx(
        stats.percentile([0.1, 0.2, 0.5, 10.5], 90))
    # Gaps pooled: 0.1 x3, 0.2 x2, 1.0.
    assert serve_readers.gap_p50_s(record) == pytest.approx(0.15)
    assert serve_readers.gap_p99_s(record) == pytest.approx(0.96)
    # Over 1.5 x 0.15: the gap of 1.0 alone, one of six (0.2 is under).
    assert serve_readers.gap_slow_share(record) == pytest.approx(100 / 6)
    assert serve_readers.loadgen_late_s_max(record) == pytest.approx(0.004)
    assert serve_readers.ingress_s_p50(record) == pytest.approx(0.004)
    # 6 decoded tokens (firsts come from prefill) over 10 steps x 2 slots.
    assert serve_readers.batch_occupancy(record) == pytest.approx(30.0)
