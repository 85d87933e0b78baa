"""A second architecture through ``benchmark/arch.py``, as new files
only: the repo's tiny mixture of experts (``LlamaConfig.tiny(moe=True)``)
with its own program config, dropless float32 reference and counts
under ``tests/bench_harness/moe_tiny/``. No file of ``benchmark/`` knows
it. CPU, no processes, no sleeps."""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import arch  # noqa: E402
from benchmark.readers import train as train_readers  # noqa: E402

FIXTURE = os.path.join(REPO, "tests", "bench_harness", "moe_tiny")


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(FIXTURE, "config.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny(config):
    """(the program's cfg, seeded float32 weights, tokens [2, 130])."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import init_params

    cfg = arch.program_config(config)
    params = init_params(cfg, jax.random.PRNGKey(11))
    tokens = jnp.asarray(np.random.RandomState(5).randint(
        0, config["vocab_size"], (2, 130)), jnp.int32)
    return cfg, params, tokens


def test_the_resolver_returns_the_fixtures_own_modules(config):
    from ray_tpu.models import LlamaConfig

    for role, file in (("reference", "reference.py"), ("counts", "counts.py")):
        module = getattr(arch, role)(config)
        assert module.__file__ == os.path.join(FIXTURE, file)
    cfg = arch.program_config(config)
    assert isinstance(cfg, LlamaConfig)
    assert (cfg.n_experts, cfg.top_k, cfg.capacity_factor) == (4, 2, 2.0)
    # The fixture is the tiny preset itself (its head size spelled out),
    # but for the capacity that makes the program dropless and the two
    # settings no test size needs.
    tiny = LlamaConfig.tiny(moe=True)
    assert cfg == dataclasses.replace(tiny, head_dim=tiny.dh,
                                      **config["program"])


def test_fixture_reference_loss_equals_the_programs(config, tiny):
    from ray_tpu.models.llama import causal_lm_loss

    cfg, params, tokens = tiny
    reference = arch.reference(config)
    ours = float(reference.loss(params, tokens, config))
    theirs = float(causal_lm_loss(
        params, tokens, cfg, aux_weight=config["router_aux_loss_coef"]))
    assert abs(ours - theirs) <= reference.LOSS_ATOL["float32"] == 1e-4
    # The load-balancing term is in the compared loss, and is not small.
    bare = float(causal_lm_loss(params, tokens, cfg, aux_weight=0.0))
    assert theirs - bare > 100 * reference.LOSS_ATOL["float32"]


def test_fixture_reference_margins_equal_the_programs_logits(config, tiny):
    import jax.numpy as jnp

    from ray_tpu.models.llama import forward

    cfg, params, tokens = tiny
    reference = arch.reference(config)
    logits, _ = forward(params, tokens[:, :-1], cfg)
    chosen = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
    ours = reference.logit_margins(params, tokens, config)
    assert ours.shape == (2, 129)
    assert float(jnp.abs(ours - (logits.max(-1) - chosen)).max()) \
        <= reference.LOGIT_MARGIN_TOL["float32"] == 1e-4


def test_the_mistral_reference_fails_on_the_fixtures_parameter_tree(config, tiny):
    """So the dispatch by ``arch`` is what made the two tests above
    pass: the dense reference cannot multiply an [E, M, F] expert
    tensor, whichever configuration dict it is given."""
    from benchmark import reference as dense

    _, params, tokens = tiny
    assert params["layers"]["w_gate"].shape == (2, 4, 64, 128)
    for entry in (dense.loss, dense.logit_margins):
        with pytest.raises((TypeError, ValueError)):
            entry(params, tokens, config)


def test_step_mfu_reads_the_fixtures_counts(config):
    record = {
        "worker": {"reading_s": [4.0, 4.0, 4.0], "tokens_per_reading": 65536,
                   "device": {"kind": "TPU v5 lite", "count": 1}},
        "config": config,
        "traffic": {"seqlen": 128},
    }
    # attention 2*64*64 + 2*64*32, router 64*4, 2 of 4 experts of
    # 3*64*128, in 2 layers; the head 64*256; causal attention's
    # 6 * layers * seqlen * heads * head_dim.
    matmul = 2 * (12_288 + 256 + 2 * 24_576) + 16_384
    per_token = 6 * matmul + 6 * 2 * 128 * 64
    assert arch.counts(config).param_counts(config)["matmul"] == matmul
    assert train_readers.step_mfu(record) == pytest.approx(
        100 * 16384 * per_token / 197e12)
    # The dense counts read the same dict otherwise: one MLP a layer.
    dense = {**config, "arch": {**config["arch"], "counts": "benchmark.flops"}}
    assert train_readers.step_mfu({**record, "config": dense}) == pytest.approx(
        100 * 16384 * (per_token - 6 * 2 * (256 + 24_576)) / 197e12)


def test_fixture_counts_hold_every_expert_and_read_those_reached(config):
    counts = arch.counts(config)
    sizes = counts.param_counts(config)
    assert sizes["layer"] == 12_288 + 256 + 4 * 24_576
    assert sizes["total"] == 2 * sizes["layer"] + 2 * 16_384 + 5 * 64
    weights = 4 * (sizes["total"] - sizes["embed"])
    # One token reaches 2 of a layer's 4 experts; a large batch all.
    assert counts.decode_step_bytes(config, 1, 0) == pytest.approx(
        weights - 4 * 2 * 2 * 24_576 + 64 * 4)
    assert counts.decode_step_bytes(config, 64, 0) == pytest.approx(
        weights + 64 * 64 * 4)
    assert counts.decode_step_flops(config, 1, 100) == (
        2 * sizes["matmul"] + 4 * 2 * 100 * 64)
