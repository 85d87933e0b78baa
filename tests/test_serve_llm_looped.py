"""The engine on a looped model (PR 65): passes, a deeper pool, an exit
gate."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from ray_tpu.serve.llm import LLMEngine  # noqa: E402


@pytest.fixture(scope="module")
def looped_model(bench_tiny):
    """3 layers run 4 times, four norms a layer, an exit gate: the
    benchmark's tiny Ouro (tests/bench_harness/ouro_tiny)."""
    return bench_tiny("ouro")


def test_looped_engine_serves_the_reference_and_counts_its_loop(looped_model):
    """Through the engine, three streams at once over a pool 12 layers
    deep: every served token's logit within 1e-4 of the plain
    reference's best at its position, and ``stats()["loop"]``: four
    passes a decode step, one token counted a prefill and a slot a step,
    the mean exit pass the reference's own exit distribution gives for
    the served tokens, a token's bytes over all 12 pool layers; rows
    read and pages held are counted at that depth."""
    import jax.numpy as jnp

    from benchmark import arch

    config, cfg, params = looped_model
    reference = arch.reference(config)
    engine = LLMEngine(cfg, params, max_batch=4, max_len=128, page_size=16,
                       total_pages=24)
    new = 30
    try:
        rng = np.random.RandomState(0)
        prompts = [list(rng.randint(0, 256, n)) for n in (10, 25, 60)]
        reqs = [engine.submit(p, new) for p in prompts]
        outs = [r.result(timeout=300) for r in reqs]
        stats = engine.stats()
    finally:
        engine.shutdown()
    seqs = np.zeros((3, max(len(p) for p in prompts) + new), np.int32)
    for row, prompt, out in zip(seqs, prompts, outs):
        row[:len(prompt) + new] = prompt + out
    margins, exits = jax.jit(lambda params, seqs: (
        reference.logit_margins(params, seqs, config),
        reference.exit_distribution(params, seqs[:, :-1], config)))(
            params, jnp.asarray(seqs))
    margins, exits = np.asarray(margins), np.asarray(exits)
    expected = 0.0
    for row, p, prompt in zip(margins, exits, prompts):
        made = slice(len(prompt) - 1, len(prompt) + new - 1)
        assert row[made].max() <= 1e-4
        expected += (p[made] * np.arange(1, 5)).sum()
    loop = stats["loop"]
    assert sorted(loop) == ["exit_pass_sum", "exit_tokens", "kv_token_bytes",
                            "passes"]
    assert loop["passes"] == 4 * stats["decode_steps"]
    assert loop["exit_tokens"] == 3 * new == (
        stats["prefills"] + stats["decode_slot_steps"])
    assert loop["exit_pass_sum"] == pytest.approx(expected, rel=1e-4)
    assert 1.0 < loop["exit_pass_sum"] / loop["exit_tokens"] < 4.0
    # 12 pool layers of a key and a value row of 4 heads of 16, float32.
    assert stats["kv_row_bytes"] == {"full": 2 * 4 * 16 * 4}
    assert loop["kv_token_bytes"] == 12 * 512
    assert stats["pages"]["full"]["layers"] == 12
    assert stats["decode_kv_rows_read"] == 12 * stats["decode_kv_tokens"]
    assert stats["kv_page_steps_held"] == stats["kv_page_steps_one_table"]
    assert stats["free_pages"] == 24


def test_an_exit_threshold_under_one_is_refused_by_name(looped_model):
    import dataclasses

    _, cfg, params = looped_model
    with pytest.raises(NotImplementedError, match="stop at different passes"):
        LLMEngine(dataclasses.replace(cfg, exit_threshold=0.9), params,
                  max_batch=2, max_len=64, page_size=16)
