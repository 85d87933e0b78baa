"""The engine on a model with latent attention: one pool of rows (PR 42),
and under a learned selection a pool of indexer keys beside it (PR 54)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from ray_tpu.serve.llm import LLMEngine  # noqa: E402


@pytest.fixture(scope="module")
def selecting_model(bench_tiny):
    """Latent attention under a learned selection of 24 positions, 4 of
    8 experts held: the benchmark's tiny GLM-5.2 share
    (tests/bench_harness/glm52_tiny)."""
    return bench_tiny("glm52")


def test_latent_engine_serves_within_tolerance_of_the_reference(latent_model):
    """Through the engine, four streams at once at different lengths, 60
    tokens each: prefill rebuilds k and v, decode attends absorbed over
    the latent pool. Every served token's logit lies within 1e-4 of the
    plain reference's best at its position (teacher-forced, one full
    forward, no cache, no absorption)."""
    import jax.numpy as jnp

    from benchmark import arch

    config, cfg, params = latent_model
    reference = arch.reference(config)
    engine = LLMEngine(cfg, params, max_batch=4, max_len=256, page_size=16,
                       total_pages=48)
    try:
        rng = np.random.RandomState(0)
        prompts = [list(rng.randint(0, 256, n)) for n in (10, 25, 40, 100)]
        reqs = [engine.submit(p, 60) for p in prompts]
        outs = [r.result(timeout=300) for r in reqs]
    finally:
        engine.shutdown()
    seqs = np.zeros((4, max(len(p) for p in prompts) + 60), np.int32)
    for row, prompt, out in zip(seqs, prompts, outs):
        row[:len(prompt) + 60] = prompt + out
    margins = np.asarray(jax.jit(
        lambda params, seqs: reference.logit_margins(params, seqs, config))(
            params, jnp.asarray(seqs)))
    for row, prompt in zip(margins, prompts):
        assert row[len(prompt) - 1:len(prompt) + 59].max() <= 1e-4


def test_selecting_engine_serves_within_tolerance_of_the_reference(
        selecting_model):
    """Through the engine, four streams at once at different lengths, 60
    tokens each, contexts on both sides of ``index_topk`` 24: every
    served token's logit lies within 1e-4 of the plain reference's best
    at its position. And the engine's account of it: two pools on one
    table, the rows the selection kept beside the rows held, the
    assignments that fell on the other chip's experts."""
    import jax.numpy as jnp

    from benchmark import arch

    config, cfg, params = selecting_model
    reference = arch.reference(config)
    engine = LLMEngine(cfg, params, max_batch=4, max_len=256, page_size=16,
                       total_pages=48)
    try:
        before = engine.stats()
        assert before["pages"] == {
            "latent": {"layers": 4, "total": 48, "free": 48},
            "index": {"layers": 2, "total": 48, "free": 48}}
        assert before["kv_row_bytes"] == {"latent": 160 * 4, "index": 16 * 4}
        assert before["decode_attention"] == "gather"          # on the CPU
        rng = np.random.RandomState(0)
        prompts = [list(rng.randint(0, 256, n)) for n in (10, 25, 40, 100)]
        reqs = [engine.submit(p, 60) for p in prompts]
        outs = [r.result(timeout=300) for r in reqs]
        stats = engine.stats()
    finally:
        engine.shutdown()
    seqs = np.zeros((4, max(len(p) for p in prompts) + 60), np.int32)
    for row, prompt, out in zip(seqs, prompts, outs):
        row[:len(prompt) + 60] = prompt + out
    margins = np.asarray(jax.jit(
        lambda params, seqs: reference.logit_margins(params, seqs, config))(
            params, jnp.asarray(seqs)))
    for row, prompt in zip(margins, prompts):
        assert row[len(prompt) - 1:len(prompt) + 59].max() <= 1e-4
    assert stats["pages"]["index"]["free"] == 48
    assert stats["decode_kv_rows_read"] == 4 * stats["decode_kv_tokens"]
    # A step at context c takes min(c, 24) rows in each of 4 layers.
    assert 0 < stats["decode_kv_rows_selected"] < stats["decode_kv_rows_read"]
    assert stats["decode_kv_rows_selected"] <= 4 * 24 * stats[
        "decode_slot_steps"]
    moe = stats["moe"]
    assert len(moe["expert_tokens"]) == 4
    assert moe["assignments"] == sum(moe["expert_tokens"])
    # Three expert layers, two experts a token, every token of every
    # prompt and every decode step: what was not held went elsewhere.
    tokens = sum(map(len, prompts)) + stats["decode_slot_steps"]
    assert moe["assignments"] + moe["assignments_elsewhere"] == 3 * 2 * tokens
    assert 0.3 < moe["assignments"] / (3 * 2 * tokens) < 0.7


def test_latent_pool_pages_are_held_from_admission_to_finish(
        wait_until, latent_model):
    """A 100-token context (60 + 40) holds 7 pages of the one pool, of
    kind "latent", from admission to its end; they return on finish.
    The counters: a step at context c reads c rows in each of 4 layers;
    a row is 32 + 128 float32 values."""
    _, cfg, params = latent_model
    engine = LLMEngine(cfg, params, max_batch=2, max_len=256, page_size=16,
                       total_pages=20)
    try:
        stats = engine.stats()
        assert stats["pages"] == {
            "latent": {"layers": 4, "total": 20, "free": 20}}
        assert stats["kv_row_bytes"] == {"latent": 160 * 4}
        assert stats["decode_attention"] == "gather"          # on the CPU
        req = engine.submit(list(range(60)), max_new_tokens=40)
        wait_until(lambda: engine.stats()["active_slots"] == 1)
        held = engine.stats()
        assert held["pages"]["latent"]["free"] == held["free_pages"] == 20 - 7
        assert len(req.result(timeout=300)) == 40
        stats = engine.stats()
        assert stats["pages"]["latent"]["free"] == stats["free_pages"] == 20
        contexts = range(61, 100)        # 39 decode steps after the prefill
        assert stats["decode_steps"] == 39
        assert stats["decode_kv_tokens"] == sum(contexts)
        assert stats["decode_kv_rows_read"] == 4 * sum(contexts)
        assert stats["kv_page_steps_held"] == \
            stats["kv_page_steps_one_table"] == 39 * 4 * 7
        assert stats["moe"]["layer_steps"] == 39 * 3
    finally:
        engine.shutdown()
