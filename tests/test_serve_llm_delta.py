"""The engine on a model with delta-rule layers among latent ones
(PR 62): a slot's states and histories beside its latent pages."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from ray_tpu.serve.llm import LLMEngine  # noqa: E402


@pytest.fixture(scope="module")
def hybrid_model(bench_tiny):
    """Delta layers among latent ones, K K K M K K M, the first FFN
    dense, 2 of 32 experts held: the benchmark's tiny Kimi-Linear
    (tests/bench_harness/kimi_tiny)."""
    return bench_tiny("kimi")


def test_hybrid_engine_serves_within_tolerance_of_the_reference(hybrid_model):
    """Through the engine, seven requests over four slots (three slots
    are taken again, one after a longer request), 40 tokens each: a
    prefill lays a slot's delta states, its convolution histories and
    its latent pages from one prompt, decode steps all three. Every
    served token's logit lies within 1e-4 of the plain reference's best
    at its position (teacher-forced, the delta rule token by token, no
    cache)."""
    import jax.numpy as jnp

    from benchmark import arch

    config, cfg, params = hybrid_model
    reference = arch.reference(config)
    engine = LLMEngine(cfg, params, **config["engine"])
    try:
        rng = np.random.RandomState(0)
        prompts = [list(rng.randint(0, 256, n))
                   for n in (10, 25, 150, 100, 17, 64, 3)]
        reqs = [engine.submit(p, 40) for p in prompts]
        outs = [r.result(timeout=300) for r in reqs]
    finally:
        engine.shutdown()
    seqs = np.zeros((len(prompts), 257), np.int32)
    for row, prompt, out in zip(seqs, prompts, outs):
        row[:len(prompt) + 40] = prompt + out
    margins = np.asarray(jax.jit(
        lambda params, seqs: reference.logit_margins(params, seqs, config))(
            params, jnp.asarray(seqs)))
    for row, prompt in zip(margins, prompts):
        assert row[len(prompt) - 1:len(prompt) + 39].max() <= 1e-4


def test_a_hybrid_engine_admits_by_slot_and_latent_pages_together(
        hybrid_model):
    """One admission: a slot (the delta pools have no pages) and the
    latent pool's pages. Two slots and pages for 288 tokens: the third
    request waits for a slot; the gauges say what a token and a slot
    hold, the counters what the steps read of each."""
    _, cfg, params = hybrid_model
    engine = LLMEngine(cfg, params, max_batch=2, max_len=256, page_size=16,
                       total_pages=18)
    try:
        stats = engine.stats()
        assert stats["pages"] == {
            "delta": {"layers": 5, "total": 0, "free": 0},
            "latent": {"layers": 2, "total": 18, "free": 18}}
        assert stats["free_pages"] == 18
        # A latent row: 32 + 8 values on a lane tile of their own.
        assert stats["kv_row_bytes"] == {"latent": (32 + 128) * 4}
        # 4 heads of 16 x 16 float32, and 3 rows of q|k|v of 4 x 16.
        assert stats["state_slot_bytes"] == {
            "delta": 4 * 16 * 16 * 4 + 3 * 3 * 64 * 4}
        assert (stats["decode_attention"], stats["decode_delta"]) == (
            "gather", "xla")                                  # on the CPU
        reqs = [engine.submit(list(range(n)), max_new_tokens=m)
                for n, m in ((200, 40), (9, 30), (60, 20))]
        assert [len(r.result(timeout=300)) for r in reqs] == [40, 30, 20]
        stats = engine.stats()
        assert stats["finished"] == 3 and stats["free_slots"] == 2
        assert stats["pages"]["latent"]["free"] == 18
        steps = 39 + 29 + 19
        assert stats["decode_slot_steps"] == steps
        assert stats["decode_state_slot_layers"] == 5 * steps
        contexts = sum(n + i for n, m in ((200, 40), (9, 30), (60, 20))
                       for i in range(1, m))
        assert stats["decode_kv_tokens"] == contexts
        assert stats["decode_kv_rows_read"] == 2 * contexts
        assert stats["moe"]["assignments_elsewhere"] > 0
    finally:
        engine.shutdown()
