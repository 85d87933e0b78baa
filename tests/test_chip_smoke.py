"""chip_smoke.py off the chip: its phases at LlamaConfig.tiny() sizes on
the CPU backend (rehearsal 1 of the on-chip-measurement guide), and its
refusal to pass without a TPU."""

import json
import os
import subprocess
import sys

import pytest

import chip_smoke

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# LlamaConfig.tiny() as the plain dict the phases take, plus the
# training schedule fields the 8B-shaped dict sets.
TINY = {
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
    "num_layers": 2, "num_heads": 4, "num_kv_heads": 2,
    "rope_theta": 10_000.0, "dtype": "float32",
    "scan_layers": True, "scan_chunk": 2,
}


def _node(num_tpus):
    import ray_tpu

    rt = ray_tpu.init(num_cpus=4, num_tpus=num_tpus,
                      system_config={"num_prestart_workers": 2})
    yield rt
    ray_tpu.shutdown()


@pytest.fixture
def one_tpu_node():
    """A node that advertises one TPU; JAX_PLATFORMS=cpu (conftest)
    reaches its ``tpu`` workers, so the phases run on the CPU backend."""
    yield from _node(1)


@pytest.fixture
def four_tpu_node():
    yield from _node(4)


def test_tiny_dict_is_llama_tiny():
    from ray_tpu.models import LlamaConfig

    import dataclasses

    want = dataclasses.replace(LlamaConfig.tiny(), scan_chunk=2)
    assert chip_smoke._llama_config(TINY) == want


def test_train_phase_tiny_on_cpu(one_tpu_node, capsys):
    device = chip_smoke.train_phase(
        TINY, platform="cpu", batch=4, seqlen=32, steps=4,
        learning_rate=1e-3,
    )
    assert device["platform"] == "cpu"
    gangs = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert [g["gang"] for g in gangs] == [1, 2]
    assert len(gangs[0]["losses"]) == 4 and gangs[0]["executables"] == 1
    # Two processes, one after the other: the second gang got a worker
    # only after the first gang's had exited.
    assert gangs[0]["pid"] != gangs[1]["pid"]
    # CPU-only workers are given no persistent compile cache.
    assert gangs[0]["cache_dir"] is None


def test_serve_phase_tiny_on_cpu(one_tpu_node, capsys):
    device = chip_smoke.serve_phase(
        TINY, platform="cpu", prompt_len=20, max_new_tokens=8,
        max_len=64, total_pages=16,
    )
    assert device["platform"] == "cpu"
    (line,) = capsys.readouterr().out.splitlines()
    facts = json.loads(line)
    # float32: every token the argmax of the plain forward(), which is
    # greedy decoding token for token.
    assert facts["argmax_agreement"] == [8] * 5
    assert facts["max_logit_margin"] == facts["logit_margin_tol"] == 0
    assert facts["new_tokens"] == [8] * 5
    assert facts["prefill_bucket"] == 32
    assert facts["engine"]["platform"] == "cpu"


def test_sharded_phase_tiny_on_cpu(four_tpu_node, capsys):
    """Rehearsal 2: the fsdp=2 x tp=2 step on four of the worker's
    virtual CPU devices against one device."""
    device = chip_smoke.sharded_phase(
        TINY, platform="cpu", batch=4, seqlen=32, steps=3,
        learning_rate=1e-3,
    )
    assert device["platform"] == "cpu"
    (line,) = capsys.readouterr().out.splitlines()
    facts = json.loads(line)
    assert facts["loss_max_rel_diff"] < 1e-4  # float32 here
    assert [m["id"] for m in facts["mesh"]] == [0, 1, 2, 3]
    assert facts["placement"]["params"]["whole_on_one_device"] == []
    assert facts["collectives"]["all-reduce"] > 0


def test_phase_refuses_another_platform(one_tpu_node):
    """What main() passes: the worker must find a TPU or the phase
    fails, before any model is built."""
    with pytest.raises(Exception, match="expected a 'tpu' device"):
        chip_smoke.train_phase(
            TINY, platform="tpu", batch=4, seqlen=32, steps=4,
            learning_rate=1e-3,
        )


def test_main_fails_fast_without_a_chip():
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "expected a 'tpu' device" in proc.stderr


# The main thread leaves by the raw ``exit`` system call, which ends one
# thread and not the group: the leader turns zombie and its other thread
# runs on until its stdin closes (tests/bench_harness/test_benchmark_chips.py
# has the same child for ``benchmark/driver.py``'s copy of the rule).
_ZOMBIE_LEADER = r"""
import ctypes, os, platform, sys, threading

def stay():
    os.write(1, b"ready\n")
    os.read(0, 1)
    os._exit(0)

number = {"x86_64": 60, "aarch64": 93}.get(platform.machine())
if number is None:
    sys.exit(77)
threading.Thread(target=stay).start()
ctypes.CDLL(None).syscall(number, 0)
"""


def test_a_zombie_leader_with_a_thread_left_has_not_exited():
    """What ``_wait_chip_released`` waits for: a worker whose leader is
    a zombie while a thread of it lives still holds its chips."""
    import time

    def until(condition, seconds=10.0):
        deadline = time.monotonic() + seconds
        while time.monotonic() < deadline and not condition():
            time.sleep(0.02)
        return condition()

    def state(pid):
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0]

    assert not chip_smoke._exited(os.getpid())
    child = subprocess.Popen([sys.executable, "-c", _ZOMBIE_LEADER],
                             stdin=subprocess.PIPE, stdout=subprocess.PIPE)
    try:
        if child.stdout.readline() != b"ready\n":
            pytest.skip("no raw exit system call known for this machine")
        if not until(lambda: state(child.pid) == "Z"):
            pytest.skip("this platform shows no zombie leader with a live "
                        "thread")
        assert not chip_smoke._exited(child.pid)
        child.stdin.close()
        # Not reaped yet: a zombie still, but with no thread left.
        assert until(lambda: chip_smoke._exited(child.pid))
        assert state(child.pid) == "Z"
    finally:
        child.stdin.close()
        child.wait(timeout=10)
        child.stdout.close()
    assert chip_smoke._exited(child.pid)  # reaped: gone
