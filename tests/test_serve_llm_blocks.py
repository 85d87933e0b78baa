"""The engine on a model that selects blocks of its k/v pool beside
Lightning layers (PR 70): a slot's states and its pages and page means
under one admission."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from ray_tpu.serve.llm import LLMEngine  # noqa: E402


@pytest.fixture(scope="module")
def sala_model(bench_tiny):
    """Selected layers among Lightning ones, m m L L L L m L: the
    benchmark's tiny MiniCPM-SALA (tests/bench_harness/minicpm_sala_tiny)."""
    return bench_tiny("minicpm_sala")


def test_selecting_hybrid_engine_serves_within_tolerance_of_the_reference(
        sala_model):
    """Through the engine, six requests over four slots (two slots are
    taken again), 40 tokens each, contexts on both sides of ``dense_len``
    320: a prefill lays a slot's Lightning states, its pages and their
    mean keys from one prompt, decode steps all of them. Every served
    token's logit lies within 1e-4 of the plain reference's best at its
    position (teacher-forced, no cache, no scan)."""
    import jax.numpy as jnp

    from benchmark import arch

    config, cfg, params = sala_model
    reference = arch.reference(config)
    engine = LLMEngine(cfg, params, **config["engine"])
    try:
        rng = np.random.RandomState(0)
        prompts = [list(rng.randint(0, 256, n))
                   for n in (10, 300, 150, 420, 17, 330)]
        reqs = [engine.submit(p, 40) for p in prompts]
        outs = [r.result(timeout=600) for r in reqs]
        stats = engine.stats()
    finally:
        engine.shutdown()
    seqs = np.zeros((len(prompts), 513), np.int32)
    for row, prompt, out in zip(seqs, prompts, outs):
        row[:len(prompt) + 40] = prompt + out
    margins = np.asarray(jax.jit(
        lambda params, seqs: reference.logit_margins(params, seqs, config))(
            params, jnp.asarray(seqs)))
    for row, prompt in zip(margins, prompts):
        assert row[len(prompt) - 1:len(prompt) + 39].max() <= 1e-4
    # Steps on both sides of dense_len, and fewer pages read than held.
    blocks = stats["blocks"]
    assert blocks["steps_dense"] > 0 and blocks["steps_selected"] > 0
    assert blocks["pages_read"] < blocks["pages_held"]


def test_a_selecting_hybrid_engine_admits_by_slot_and_pages_together(
        sala_model):
    """One admission: a slot (the Lightning pool has no pages) and the
    k/v pool's pages, which are the page means' too. The gauges say what
    a token and a slot hold, the counters what the steps read."""
    _, cfg, params = sala_model
    engine = LLMEngine(cfg, params, max_batch=2, max_len=512, page_size=16,
                       total_pages=40)
    try:
        stats = engine.stats()
        assert stats["pages"] == {
            "full": {"layers": 3, "total": 40, "free": 40},
            "linear": {"layers": 5, "total": 0, "free": 0},
            "mean": {"layers": 3, "total": 40, "free": 40}}
        assert stats["free_pages"] == 40
        # 4 heads of 16 x 16 float32 a layer.
        assert stats["state_slot_bytes"] == {"linear": 4 * 16 * 16 * 4}
        assert (stats["decode_attention"], stats["decode_linear"],
                stats["decode_delta"]) == ("gather", "xla", "none")
        assert stats["blocks"] == {
            "pages_read": 0, "pages_held": 0, "copies": 0, "steps_dense": 0,
            "steps_selected": 0, "mean_row_bytes": 2 * 16 * 4, "topk": 4,
            "dense_len": 320}
        assert stats["linear"] == {"slot_layers": 0,
                                   "slot_bytes": 5 * 4 * 16 * 16 * 4}
        reqs = [engine.submit(list(range(n)), max_new_tokens=m)
                for n, m in ((400, 40), (9, 30), (60, 20))]
        assert [len(r.result(timeout=600)) for r in reqs] == [40, 30, 20]
        stats = engine.stats()
        assert stats["finished"] == 3 and stats["free_slots"] == 2
        assert stats["pages"]["full"]["free"] == 40
        steps = 39 + 29 + 19
        assert stats["decode_slot_steps"] == steps
        assert stats["linear"]["slot_layers"] == 5 * steps
        assert stats["decode_state_slot_layers"] == 5 * steps
        # The long request's steps are past dense_len: 4 blocks of 7.
        blocks = stats["blocks"]
        assert (blocks["steps_selected"], blocks["steps_dense"]) == (39, 48)
        held = sum(3 * ((n + i) // 16 + 1) for n, m in (
            (400, 40), (9, 30), (60, 20)) for i in range(1, m))
        assert blocks["pages_held"] == held
        read = sum(3 * (3 * 4 + (400 + i) % 64 // 16 + 1)
                   for i in range(1, 40))
        short = sum(3 * ((n + i) // 16 + 1) for n, m in ((9, 30), (60, 20))
                    for i in range(1, m))
        assert blocks["pages_read"] == read + short
        assert stats["decode_kv_rows_read"] == 16 * (read + short)
        # The walk's copies: a kept block is one (a KV head, a pool, a
        # layer), 4 of them past dense_len, every block before.
        copies = 3 * 4 * 39 + sum(
            3 * ((n + i) // 64 + 1) for n, m in ((9, 30), (60, 20))
            for i in range(1, m))
        assert blocks["copies"] == copies
        # A quarter of the pages, and the last block's copy for 1-4 pages.
        units = 3 * steps
        assert (blocks["pages_read"] / 4 <= copies
                <= blocks["pages_read"] / 4 + units)
    finally:
        engine.shutdown()


def test_a_page_size_other_than_the_stride_is_refused(sala_model):
    _, cfg, params = sala_model
    with pytest.raises(ValueError, match="stride"):
        LLMEngine(cfg, params, max_batch=2, max_len=512, page_size=32,
                  total_pages=40)
