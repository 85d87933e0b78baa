"""trace_reduce.py on a small trace recorded on the chip, and its
parsing and interval arithmetic on hand-made inputs. CPU only."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import trace_reduce as tr  # noqa: E402
from benchmark.readers import trace as trace_readers  # noqa: E402

TRACE = os.path.join(REPO, "benchmark", "testdata", "train_2steps.xplane.pb.gz")


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce_trace(tr.load(TRACE))


def test_recorded_trace_busy_share_and_window(reduced):
    assert reduced["devices"] == 1
    assert reduced["window_s"] == pytest.approx(1.851182646, rel=1e-9)
    assert reduced["busy_s"] == pytest.approx(1.85117386, rel=1e-8)
    assert reduced["collective_s"] == 0.0
    assert len(reduced["modules"]["train_step"]) == 2
    assert sum(reduced["modules"]["train_step"]) == pytest.approx(
        1.851183747, rel=1e-9)


def test_recorded_trace_idle_gap_is_named_by_programs_and_host_span(reduced):
    (name, count, seconds), = reduced["idle_gaps"]
    assert (name, count) == ("train_step>train_step@read_loss", 1)
    assert seconds == pytest.approx(3.266e-06, rel=1e-3)
    spans = reduced["host_spans"]
    assert spans["input_wait"][0] == spans["train_step"][0] == 2
    assert spans["read_loss"][1] == pytest.approx(1.850760013, rel=1e-9)


@pytest.mark.parametrize("name,count,seconds", [
    ("pallas_bf16_256_2048_128_f32_256_1_2048", 16, 0.21777784),
    ("fusion_bf16_1_4096_14336", 16, 0.187831591),
    ("convolution_reduce-precision_fusion_bf16_8_2048_14336", 16, 0.169345091),
    ("pallas_bf16_64_2048_128_bf16_64_2048_128", 8, 0.121502401),
    ("pallas_bf16_256_2048_128", 8, 0.091444559),
])
def test_recorded_trace_time_per_operation(reduced, name, count, seconds):
    row, = [r for r in reduced["ops"] if r[0] == name]
    assert row[1] == count and row[2] == pytest.approx(seconds, rel=1e-8)


def test_breakdown_and_trace_readers_on_the_recorded_trace(reduced):
    out = tr.breakdown(reduced)
    assert len(out["device_ops"]) == 10
    assert out["device_ops"][0][0] == "pallas_bf16_256_2048_128_f32_256_1_2048_x16"
    assert out["idle_gaps"][0][0] == "train_step>train_step@read_loss_x1"
    import json
    with open(os.path.join(REPO, "benchmark/configs/mistral-7b-v0.3-L4.json")) as f:
        config = json.load(f)
    record = {"trace": reduced, "config": config,
              "traffic": {"batch": 8, "seqlen": 2048},
              "worker": {"device": {"kind": "TPU v5 lite", "count": 1}}}
    assert trace_readers.device_idle_share(record) == pytest.approx(4.746e-4, rel=1e-2)
    # Three kernels: 0.2178 + 0.1215 + 0.0914 s of 1.8512 s busy.
    assert trace_readers.flash_time_share(record) == pytest.approx(23.27, abs=0.01)
    # 2 steps x 6*4*8*2048^2*4096 operations at 197 TFLOP/s = 33.5 ms.
    assert trace_readers.flash_roofline(record) == pytest.approx(7.78, abs=0.01)
    assert trace_readers.collective_exposed_share(record) == 0.0
    assert trace_readers.prefill_device_s_p50(record) is None
    assert trace_readers.device_idle_share({"trace": None}) is None


@pytest.mark.parametrize("text,base,opcode,stable", [
    ("%fusion.600 = f32[64]{0:T(128)S(1)} fusion(), kind=kLoop, calls=%fc.1",
     "fusion", "fusion", "fusion_f32_64"),
    ("%checkpoint.20 = (bf16[64,2048,128]{2,1,0:T(8,128)(2,1)S(1)}, "
     "bf16[64,2048,128]{2,1,0}) custom-call(bf16[256,2048,128]{2,1,0} %b)",
     "checkpoint", "custom-call", "pallas_bf16_64_2048_128_bf16_64_2048_128"),
    ("%while.8 = (s32[]{:T(128)}, bf16[4096,32768]{1,0}) while((s32[]) %t)",
     "while", "while", "while_s32_bf16_4096_32768"),
    ("%all-reduce-start.3 = bf16[8,128]{1,0} all-reduce-start(bf16[8,128] %x)",
     "all-reduce-start", "all-reduce-start", "all-reduce-start_bf16_8_128"),
    ("$time sleep", "$time sleep", "", "_time_sleep"),
])
def test_instruction_text_to_stable_name(text, base, opcode, stable):
    assert tr.parse_instruction(text)[:2] == (base, opcode)
    assert tr.stable_name(text) == stable
    assert tr.is_collective(text) == ("all-reduce" in text)


def test_interval_arithmetic():
    merged = tr.merge([(5, 6), (0, 2), (1, 3), (3, 4)])
    assert merged == [(0, 4), (5, 6)] and tr.total(merged) == 5
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == [
        (0, 1), (2, 4), (6, 9)]
    assert tr.subtract([(0, 1), (2, 3)], []) == [(0, 1), (2, 3)]
    assert tr.subtract([(0, 1)], [(0, 1)]) == []
    assert tr.module_name("jit_decode_step(10554430090860190591)") == "decode_step"
