"""The engine on a model of gated short-convolution layers among
grouped-query ones (PR 73): a slot's histories beside its pages of two
layers, under one admission."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from ray_tpu.serve.llm import LLMEngine  # noqa: E402


@pytest.fixture(scope="module")
def conv_model(bench_tiny):
    """Conv layers among attention ones, c c a c c c a c c c, two dense
    FFNs in front of eight expert layers: the benchmark's tiny LFM2
    (tests/bench_harness/lfm2_tiny)."""
    return bench_tiny("lfm2")


def test_conv_engine_serves_within_tolerance_of_the_reference(conv_model):
    """Through the engine, seven requests over four slots (three slots
    are taken again, one by a prompt of one token after a longer
    request), 40 tokens each: a prefill lays a slot's histories and its
    pages from one prompt, decode steps both. Every served token's logit
    lies within 1e-4 of the plain reference's best at its position
    (teacher-forced, the convolution as shifted copies, no cache)."""
    import jax.numpy as jnp

    from benchmark import arch

    config, cfg, params = conv_model
    reference = arch.reference(config)
    engine = LLMEngine(cfg, params, **config["engine"])
    try:
        rng = np.random.RandomState(0)
        prompts = [list(rng.randint(0, 256, n))
                   for n in (10, 25, 150, 100, 1, 64, 2)]
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


def test_a_conv_engine_admits_by_slot_and_pages_together(conv_model):
    """One admission: a slot (the histories have no pages) and the "full"
    pool's pages, which two layers of ten write. The gauges say what a
    token and a slot hold, the counters what the steps read of each."""
    _, cfg, params = conv_model
    engine = LLMEngine(cfg, params, max_batch=2, max_len=256, page_size=16,
                       total_pages=18)
    try:
        stats = engine.stats()
        assert stats["pages"] == {
            "conv": {"layers": 8, "total": 0, "free": 0},
            "full": {"layers": 2, "total": 18, "free": 18}}
        assert stats["free_pages"] == 18
        # k and v of 2 KV heads of 16; two rows of 64 a conv layer.
        assert stats["kv_row_bytes"] == {"full": 2 * 2 * 16 * 4}
        assert stats["state_slot_bytes"] == {"conv": 2 * 64 * 4}
        assert (stats["decode_attention"], stats["decode_delta"],
                stats["decode_linear"]) == ("gather", "none", "none")
        assert stats["conv"] == {"slot_layers": 0,
                                 "slot_bytes": 8 * 2 * 64 * 4,
                                 "layers": 8, "layers_in_all": 10}
        assert stats["weights"]["leaves_turned"] > 0
        reqs = [engine.submit(list(range(n)), max_new_tokens=m)
                for n, m in ((200, 40), (9, 30), (60, 20))]
        assert [len(r.result(timeout=300)) for r in reqs] == [40, 30, 20]
        stats = engine.stats()
        assert stats["finished"] == 3 and stats["free_slots"] == 2
        assert stats["pages"]["full"]["free"] == 18
        steps = 39 + 29 + 19
        assert stats["decode_slot_steps"] == steps
        assert stats["conv"]["slot_layers"] == 8 * steps
        assert stats["decode_state_slot_layers"] == 8 * steps
        contexts = sum(n + i for n, m in ((200, 40), (9, 30), (60, 20))
                       for i in range(1, m))
        assert stats["decode_kv_tokens"] == contexts
        assert stats["decode_kv_rows_read"] == 2 * contexts
        assert stats["moe"]["layer_calls"] > 0
    finally:
        engine.shutdown()
