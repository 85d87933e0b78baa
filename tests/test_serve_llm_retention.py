"""The engine on a retention model (PR 45): a pool of states, no pages.
The model is ``tests/conftest.py``'s ``state_model``."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from ray_tpu.serve.llm import LLMEngine  # noqa: E402


def test_state_engine_serves_within_tolerance_of_the_reference(state_model):
    """Through the engine, six requests over four slots (two slots are
    taken again, one after a longer request), 40 tokens each: the
    chunked scan lays a state into the slot, decode updates it in place.
    Every served token's logit lies within 1e-3 of the plain reference's
    best at its position (teacher-forced, the attention form, no
    state)."""
    import jax.numpy as jnp

    from benchmark import arch

    config, cfg, params = state_model
    reference = arch.reference(config)
    engine = LLMEngine(cfg, params, **config["engine"])
    try:
        rng = np.random.RandomState(0)
        prompts = [list(rng.randint(0, 256, n))
                   for n in (10, 25, 150, 100, 17, 64)]
        reqs = [engine.submit(p, 40) for p in prompts]
        outs = [r.result(timeout=300) for r in reqs]
    finally:
        engine.shutdown()
    seqs = np.zeros((6, 257), np.int32)
    for row, prompt, out in zip(seqs, prompts, outs):
        row[:len(prompt) + 40] = prompt + out
    margins = np.asarray(jax.jit(
        lambda params, seqs: reference.logit_margins(params, seqs, config))(
            params, jnp.asarray(seqs)))
    for row, prompt in zip(margins, prompts):
        assert row[len(prompt) - 1:len(prompt) + 39].max() <= 1e-3


def test_a_state_engine_admits_by_slots_alone_and_counts_states(state_model):
    """A pool of states has no pages: nothing to reserve, wait for or
    return; two slots admit two requests whatever their lengths and the
    third waits for a slot. The gauge says what a slot holds in a layer,
    the counter how many states the decode steps moved."""
    _, cfg, params = state_model
    engine = LLMEngine(cfg, params, max_batch=2, max_len=256, page_size=16,
                       total_pages=1)
    try:
        stats = engine.stats()
        assert stats["pages"] == {
            "state": {"layers": 3, "total": 0, "free": 0}}
        assert stats["kv_row_bytes"] == {} and stats["free_pages"] == 0
        # 2 KV heads x 9 turns x 24 rows x 16 float32.
        assert stats["state_slot_bytes"] == {"state": 2 * 9 * 24 * 16 * 4}
        assert stats["decode_attention"] == "xla"             # on the CPU
        reqs = [engine.submit(list(range(n)), max_new_tokens=m)
                for n, m in ((200, 40), (9, 30), (60, 20))]
        assert [len(r.result(timeout=300)) for r in reqs] == [40, 30, 20]
        stats = engine.stats()
        assert stats["page_waits"] == 0 and stats["finished"] == 3
        assert stats["free_slots"] == 2
        assert stats["decode_slot_steps"] == 39 + 29 + 19
        assert stats["decode_state_slot_layers"] == 3 * (39 + 29 + 19)
        assert stats["decode_kv_rows_read"] == 0
        assert stats["kv_page_steps_held"] == 0
        # Longer than max_len is still refused: the positions' bound.
        with pytest.raises(ValueError, match="max_len"):
            engine.submit(list(range(250)), max_new_tokens=10)
    finally:
        engine.shutdown()
