"""The engine on a model with window layers: one allocator, a pool a kind
(PR 38). The model is ``tests/conftest.py``'s ``window_model``."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from ray_tpu.serve.llm import LLMEngine  # noqa: E402


def test_window_engine_serves_within_tolerance_of_the_reference(window_model):
    """Through the engine, four streams at once: prompts under the
    window, crossing it while decoding, over it and far over it, 90
    tokens each. Every served token's logit lies within 1e-4 of the
    plain reference's best at its position (teacher-forced, one full
    forward, no cache, no ring)."""
    import jax.numpy as jnp

    from benchmark import arch

    config, cfg, params = window_model
    reference = arch.reference(config)
    engine = LLMEngine(cfg, params, max_batch=4, max_len=256, page_size=16,
                       total_pages=48)
    try:
        rng = np.random.RandomState(0)
        prompts = [list(rng.randint(0, 256, n)) for n in (10, 25, 40, 100)]
        reqs = [engine.submit(p, 90) for p in prompts]
        outs = [r.result(timeout=300) for r in reqs]
    finally:
        engine.shutdown()
    # One forward of the reference for all four, padded behind their ends.
    seqs = np.zeros((4, max(len(p) for p in prompts) + 90), np.int32)
    for row, prompt, out in zip(seqs, prompts, outs):
        row[:len(prompt) + 90] = prompt + out
    margins = np.asarray(jax.jit(
        lambda params, seqs: reference.logit_margins(params, seqs, config))(
            params, jnp.asarray(seqs)))
    for row, prompt in zip(margins, prompts):
        assert row[len(prompt) - 1:len(prompt) + 89].max() <= 1e-4


def test_window_pool_holds_a_ring_and_the_full_pool_everything(
        wait_until, window_model):
    """A 200-token context (120 + 80) holds window / page + 1 = 3 pages
    in the window pool and 13 in the full one, from admission to its
    end; both return on finish. The counters' arithmetic by hand: the
    step at context c reads c rows in the full layer and min(c, 32) in
    each of 4 window layers, and holds 13 + 4 x 3 pages against 5 x 13
    with one table."""
    _, cfg, params = window_model
    engine = LLMEngine(cfg, params, max_batch=2, max_len=256, page_size=16,
                       total_pages=32)
    try:
        assert engine.stats()["pages"] == {
            "window": {"layers": 4, "total": 2 * 3, "free": 6},
            "full": {"layers": 1, "total": 32, "free": 32}}
        req = engine.submit(list(range(120)), max_new_tokens=80)
        wait_until(lambda: engine.stats()["active_slots"] == 1)
        held = engine.stats()
        assert held["pages"]["window"]["free"] == 6 - 3
        assert held["pages"]["full"]["free"] == 32 - 13
        assert held["free_pages"] == 32 - 13       # the pool that keeps all
        assert len(req.result(timeout=300)) == 80
        stats = engine.stats()
        assert stats["pages"]["window"]["free"] == 6
        assert stats["pages"]["full"]["free"] == stats["free_pages"] == 32
        # 79 decode steps, at contexts 121 .. 199 (the first token came
        # from the prefill).
        steps = stats["decode_steps"]
        assert steps == 79 and stats["decode_slot_steps"] == 79
        contexts = range(121, 200)
        assert stats["decode_kv_tokens"] == sum(contexts)
        assert stats["decode_kv_rows_read"] == sum(
            c + 4 * min(c, 32) for c in contexts)
        assert stats["kv_page_steps_held"] == steps * (13 + 4 * 3)
        assert stats["kv_page_steps_one_table"] == steps * 5 * 13
        # Experts: 4 of the 5 layers have them.
        assert stats["moe"]["layer_steps"] == steps * 4
        assert stats["moe"]["decode_assignments"] == steps * 4 * cfg.top_k
    finally:
        engine.shutdown()


def test_a_pool_that_cannot_hold_a_request_refuses_it_at_submit(window_model):
    _, cfg, params = window_model
    engine = LLMEngine(cfg, params, max_batch=2, max_len=256, page_size=16,
                       total_pages=8)
    try:
        with pytest.raises(ValueError, match="full pool has only 8"):
            engine.submit(list(range(100)), max_new_tokens=100)
        # Its ring it could have had: the window pool refuses nothing
        # that fits a slot.
        assert len(engine.generate(list(range(100)), max_new_tokens=20)) == 20
    finally:
        engine.shutdown()


@pytest.mark.parametrize("short", ["full", "window"])
def test_admission_waits_on_whichever_pool_is_short(window_model, short):
    """Two requests, and one of the pools can hold only one of them at
    a time: the second waits for the first's pages (``page_waits``
    counts the rounds) and both finish. The window pool is sized for
    every slot, so it is short only with pages taken out of it."""
    _, cfg, params = window_model
    engine = LLMEngine(cfg, params, max_batch=2, max_len=128, page_size=16,
                       total_pages=5 if short == "full" else 16)
    try:
        if short == "window":
            del engine.books.free["window"][3:]     # one ring is left
        a = engine.submit(list(range(40)), max_new_tokens=30)   # 5 pages
        b = engine.submit(list(range(40, 80)), max_new_tokens=30)
        assert len(a.result(timeout=300)) == 30
        assert len(b.result(timeout=300)) == 30
        stats = engine.stats()
        assert stats["page_waits"] >= 1
        assert stats["pages"][short]["free"] == (5 if short == "full" else 3)
    finally:
        engine.shutdown()
