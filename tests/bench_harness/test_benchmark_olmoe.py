"""The OLMoE configuration's own modules (``benchmark/olmoe_*.py``) and
the readers of its layer (``benchmark/readers/moe.py``): counts at the
published widths, the readers on hand-made records, the float32
reference against the program at a tiny size. CPU, no processes."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import arch, olmoe_counts  # noqa: E402
from benchmark.readers import moe as readers  # noqa: E402

EXPERT = 3 * 2048 * 1024


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "olmoe-1b-7b-0125-L8.json")) as f:
        return json.load(f)


def test_file_holds_the_catalogs_widths_and_cuts_depth_alone(config):
    published = {"hidden_size": 2048, "intermediate_size": 1024,
                 "num_attention_heads": 16, "num_key_value_heads": 16,
                 "num_experts": 64, "num_experts_per_tok": 8,
                 "vocab_size": 50304, "max_position_embeddings": 4096,
                 "norm_topk_prob": False, "rope_theta": 10000,
                 "rms_norm_eps": 1e-05, "tie_word_embeddings": False,
                 "attention_bias": False, "clip_qkv": None,
                 "hidden_act": "silu", "model_type": "olmoe",
                 "rope_scaling": None}
    assert {k: config[k] for k in published} == published
    assert list(config["reduced"]) == ["num_hidden_layers"]
    assert (config["reduced"]["num_hidden_layers"]["published"],
            config["num_hidden_layers"]) == (16, 8)
    assert config["assumed"]["head_dim"] == 128
    assert config["assumed"]["router_aux_loss_coef"] == 0.01


def test_builder_takes_each_key_by_name(config):
    cfg = arch.program_config(config)
    assert (cfg.n_experts, cfg.top_k, cfg.qk_norm) == (64, 8, True)
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.num_layers,
            cfg.num_heads, cfg.num_kv_heads, cfg.dh, cfg.vocab_size) == (
                2048, 1024, 8, 16, 16, 128, 50304)
    assert cfg.rope_theta == 10000.0 and str(cfg.dtype) == "bfloat16"
    # No trainer door: a key the builder does not name changes nothing.
    assert arch.program_config({**config, "trainer": {"top_k": 1}}) == cfg


def test_counts_at_the_published_widths(config):
    sizes = olmoe_counts.param_counts(config)
    attn = 4 * 2048 * 2048
    assert sizes["expert"] == EXPERT
    assert sizes["layer"] == attn + 2048 * 64 + 64 * EXPERT
    assert sizes["norms"] == 8 * 4 * 2048 + 2048   # two of them QK-norms
    assert sizes["total"] == (8 * sizes["layer"] + 2 * 50304 * 2048
                              + sizes["norms"])
    assert round(sizes["total"] / 1e9, 3) == 3.563
    # A token is multiplied by 8 of a layer's 64 experts.
    assert sizes["matmul"] == (8 * (attn + 2048 * 64 + 8 * EXPERT)
                               + 2048 * 50304)
    assert olmoe_counts.kv_bytes_per_token(config) == 65_536
    assert olmoe_counts.head_dim(config) == 128
    full = {**config, "num_hidden_layers": 16}
    assert round(olmoe_counts.param_counts(full)["total"] / 1e9, 2) == 6.92


def test_decode_bytes_follow_the_experts_reached(config):
    outside = (olmoe_counts.param_counts(config)["total"]
               - 50304 * 2048 - 8 * 64 * EXPERT)
    one = olmoe_counts.decode_step_bytes(config, 1, 0)
    assert one == pytest.approx(2 * (outside + 8 * 8 * EXPERT) + 2048 * 2)
    many = olmoe_counts.decode_step_bytes(config, 512, 0)
    assert many == pytest.approx(2 * (outside + 8 * 64 * EXPERT)
                                 + 512 * 2048 * 2, rel=1e-6)
    assert olmoe_counts.experts_reached_even(config, 5) == pytest.approx(
        64 * (1 - (7 / 8) ** 5))
    assert olmoe_counts.decode_step_flops(config, 2, 100) == (
        4 * olmoe_counts.param_counts(config)["matmul"]
        + 4 * 8 * 100 * 2048)


def test_moe_matmul_work_grows_with_assignments_and_pairs(config):
    assert olmoe_counts.moe_matmul_flops(config, 10) == 2 * EXPERT * 10
    base = olmoe_counts.moe_matmul_bytes(config, 256, 100)
    assert base == 2 * (100 * EXPERT + 256 * 2 * 2048)
    assert olmoe_counts.moe_matmul_bytes(config, 256, 101) - base == 2 * EXPERT
    assert olmoe_counts.moe_matmul_bytes(config, 257, 100) - base == 4 * 2048


def _record(config, moe_before, moe_after, ops, runs=(100, 10),
            traced=(20, 2)):
    before = {"decode_steps": 0, "prefills": 0}
    after = {"decode_steps": runs[0], "prefills": runs[1]}
    if moe_before is not None:
        before["moe"], after["moe"] = moe_before, moe_after
    return {
        "config": config,
        "worker": {"engine_before": before, "engine": after,
                   "device": {"kind": "TPU v5 lite", "count": 1}},
        "trace": None if ops is None else {
            "ops": ops, "busy_s": 4.0, "window_s": 5.0,
            "modules": {"decode_step": [0.04] * traced[0],
                        "prefill": [0.05] * traced[1]}},
    }


def _moe(assignments, decode_assignments, reached, prefill_reached,
         layer_steps, expert_tokens):
    return {"assignments": assignments,
            "decode_assignments": decode_assignments,
            "experts_reached": reached,
            "prefill_experts_reached": prefill_reached,
            "layer_steps": layer_steps, "expert_tokens": expert_tokens}


ZERO = _moe(0, 0, 0, 0, 0, [0] * 64)
READERS = [readers.moe_matmul_time_share, readers.moe_matmul_roofline,
           readers.experts_reached_mean, readers.expert_load_max_over_mean]


@pytest.mark.parametrize("reader", READERS, ids=lambda r: r.__name__)
def test_readers_find_nothing_without_counters_or_trace(config, reader):
    """A dense engine, an engine from before the counters, a run that
    was not traced: None, never an exception."""
    ops = [["fusion_bf16_32_14336", 10, 1.0]]
    assert reader(_record(config, None, None, None)) is None
    if reader in READERS[:2]:
        # A trace without a grouped matmul: the parent's, or a dense one.
        assert reader(_record(config, None, None, ops)) is None
        assert reader(_record(config, ZERO, ZERO, None)) is None
    else:
        assert reader(_record(config, ZERO, ZERO, ops)) is None


def test_readers_on_a_hand_made_window(config):
    # 100 decode steps of 16 sequences, 50 experts a layer reached;
    # 10 prefills of 300 tokens reaching all 64; 8 layers.
    decode = 100 * 16 * 8 * 8
    prefill = 10 * 300 * 8 * 8
    load = [(decode + prefill) // 64] * 64
    load[3] += 640
    load[5] -= 640
    after = _moe(decode + prefill, decode, 100 * 8 * 50, 10 * 8 * 64,
                 100 * 8, load)
    ops = [["pallas_bf16_256_1024", 320, 0.3],
           ["pallas_bf16_256_2048", 160, 0.1],
           ["pallas_bf16_4096_1024", 48, 0.1],
           # Not grouped matmuls: the page walk, a fusion of the same
           # shape, a custom call of another width.
           ["pallas_bf16_32_16_128", 160, 0.2],
           ["fusion_bf16_256_2048", 100, 1.0],
           ["pallas_f32_256_8", 100, 0.3]]
    record = _record(config, ZERO, after, ops)
    assert readers.experts_reached_mean(record) == 50.0
    assert readers.expert_load_max_over_mean(record) == pytest.approx(
        (load[0] + 640) / load[0])
    assert readers.moe_matmul_time_share(record) == pytest.approx(12.5)
    # 20 traced decode steps read 8 x 50 experts each, 2 traced prefills
    # all 8 x 64 for 300 x 8 x 8 rows: each against its own bound (both
    # the weights' bytes here).
    per_step = max(2 * EXPERT * 1024 / 197e12,
                   2 * (400 * EXPERT + 1024 * 4096) / 819e9)
    per_prefill = max(2 * EXPERT * 19200 / 197e12,
                      2 * (512 * EXPERT + 19200 * 4096) / 819e9)
    assert readers.moe_matmul_roofline(record) == pytest.approx(
        100 * (20 * per_step + 2 * per_prefill) / 0.5)
    # The share cannot exceed what the counters allow: with every pair
    # reached in every run it is the most the same time can read.
    full = _moe(decode + prefill, decode, 100 * 8 * 64, 10 * 8 * 64,
                100 * 8, load)
    assert readers.moe_matmul_roofline(_record(config, ZERO, full, ops)) \
        > readers.moe_matmul_roofline(record)


@pytest.fixture(scope="module")
def tiny():
    """(configuration dict, cfg, float32 weights, tokens [2, 130])."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import init_params

    with open(os.path.join(REPO, "tests", "bench_harness", "olmoe_tiny",
                           "config.json")) as f:
        config = json.load(f)
    cfg = arch.program_config(config)
    params = init_params(cfg, jax.random.PRNGKey(11))
    rng = np.random.RandomState(12)
    for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm"):
        leaf = params["layers"][name]
        params["layers"][name] = leaf + jnp.asarray(
            rng.uniform(-0.3, 0.3, leaf.shape), leaf.dtype)
    tokens = jnp.asarray(np.random.RandomState(5).randint(
        0, 256, (2, 130)), jnp.int32)
    return config, cfg, params, tokens


def test_reference_loss_equals_the_programs(tiny):
    from ray_tpu.models.llama import causal_lm_loss

    config, cfg, params, tokens = tiny
    reference = arch.reference(config)
    ours = float(reference.loss(params, tokens, config))
    theirs = float(causal_lm_loss(params, tokens, cfg, aux_weight=0.01))
    assert abs(ours - theirs) <= reference.LOSS_ATOL["float32"] == 1e-4
    bare = float(causal_lm_loss(params, tokens, cfg, aux_weight=0.0))
    assert theirs - bare > 100 * reference.LOSS_ATOL["float32"]


def test_reference_margins_equal_the_programs_logits(tiny):
    import jax.numpy as jnp

    from ray_tpu.models.llama import forward

    config, cfg, params, tokens = tiny
    reference = arch.reference(config)
    logits, _ = forward(params, tokens[:, :-1], cfg)
    chosen = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
    ours = reference.logit_margins(params, tokens, config)
    assert ours.shape == (2, 129)
    assert float(jnp.abs(ours - (logits.max(-1) - chosen)).max()) \
        <= reference.LOGIT_MARGIN_TOL["float32"] == 1e-4


def test_reference_is_dropless_and_does_not_renormalise(tiny):
    """What the tolerance must refuse at this size: gates renormalised
    over the chosen experts, or one assignment in sixteen dropped."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import forward

    config, cfg, params, tokens = tiny
    reference = arch.reference(config)
    x, _ = reference.hidden(params, tokens[:, :-1], config)
    ours = jnp.einsum("bsm,mv->bsv", x, params["lm_head"],
                      precision="highest")
    logits, _ = forward(params, tokens[:, :-1], cfg)
    assert float(jnp.abs(ours - logits).max()) <= 1e-4
    # The same weights with the router's columns scaled change which
    # gates are chosen and their values, far past the tolerance.
    layers = dict(params["layers"], router=params["layers"]["router"] * 2.0)
    scaled, _ = forward(dict(params, layers=layers), tokens[:, :-1], cfg)
    assert float(jnp.abs(scaled - ours).max()) > 100 * 1e-4
