"""reference.py against models/llama.py at a tiny size in float32, with
heads whose size is not hidden/heads (Mistral-Nemo's case), and
benchmark.run's refusal to measure without a chip. CPU only."""

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


@pytest.fixture(scope="module")
def tiny():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import LlamaConfig, init_params

    cfg = LlamaConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                      num_layers=3, num_heads=4, num_kv_heads=2, head_dim=32,
                      rope_theta=1e6, rms_eps=1e-5, dtype=jnp.float32,
                      use_flash=False, loss_chunk=0)
    params = init_params(cfg, jax.random.PRNGKey(3))
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, 256, (2, 1025)), jnp.int32)
    # What the reference reads of a configuration file's dict.
    config = {"rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_eps}
    return cfg, params, tokens, config


def test_reference_loss_equals_the_programs(tiny):
    from benchmark import reference
    from ray_tpu.models.llama import causal_lm_loss

    cfg, params, tokens, config = tiny
    ours = float(reference.loss(params, tokens, config))
    theirs = float(causal_lm_loss(params, tokens, cfg))
    assert abs(ours - theirs) <= reference.LOSS_ATOL["float32"]


def test_reference_margins_equal_the_programs_logits(tiny):
    import jax.numpy as jnp

    from benchmark import reference
    from ray_tpu.models.llama import forward

    cfg, params, tokens, config = tiny
    logits, _ = forward(params, tokens[:, :-1], cfg)
    chosen = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
    theirs = logits.max(-1) - chosen
    ours = reference.logit_margins(params, tokens, config)
    assert ours.shape == (2, 1024)
    assert float(jnp.abs(ours - theirs).max()) <= reference.LOGIT_MARGIN_TOL["float32"]
    # Blocks of queries change nothing: a short sequence is one block.
    short = reference.logit_margins(params, tokens[:, :101], config)
    assert float(jnp.abs(short - theirs[:, :100]).max()) <= 1e-4


def test_llama_config_from_a_configuration_file():
    import json

    from benchmark import arch, worker

    with open(os.path.join(REPO, "benchmark/configs/mistral-nemo-12b-L8.json")) as f:
        config = json.load(f)
    cfg = arch.program_config(config)
    assert (cfg.hidden_size, cfg.dh, cfg.num_heads, cfg.num_kv_heads) == (5120, 128, 32, 8)
    assert (cfg.vocab_size, cfg.num_layers, cfg.rope_theta) == (131072, 8, 1e6)
    assert (cfg.remat_policy, cfg.scan_chunk) == ("dots", 4)
    tokens = worker.zipf_tokens(1000, (4, 9), 2 ** 31 + 3)
    assert tokens.shape == (4, 9) and tokens.dtype == np.int32
    assert (tokens == worker.zipf_tokens(1000, (4, 9), 2 ** 31 + 3)).all()
    assert (tokens < 10).mean() > 0.25  # Zipf: the head of the vocabulary


def test_run_refuses_to_measure_without_a_chip():
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         "train-mistral7b-1chip", "--seed", "1", "--seconds", "1"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 3
    assert proc.stdout == ""
    assert len(proc.stderr.strip().splitlines()) == 1
    assert "no CPU mode" in proc.stderr


def test_the_driver_side_never_imports_jax():
    code = ("import sys, benchmark.run, benchmark.driver, benchmark.loadgen, "
            "benchmark.jobs.train, benchmark.jobs.serve, benchmark.flops, "
            "benchmark.arch, benchmark.readers.engine, "
            "benchmark.trace_reduce, benchmark.readers.trace, "
            "benchmark.readers.serve, benchmark.readers.train; "
            "sys.exit('jax' in sys.modules)")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO, timeout=120)
    assert proc.returncode == 0
