"""The serving tree (PR 69): an engine stores the stacked projections of
``llama.SERVING_ORDER`` with the axis its decode step contracts last,
serves the tokens the published tree's programs serve, hands the
published tree back bit for bit, and counts what it turned; a published
tree goes through ``forward`` and the train step as it always did."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import (  # noqa: E402
    LlamaConfig, causal_lm_loss, forward, init_params, llama)
from ray_tpu.serve.llm import LLMEngine  # noqa: E402

# The tiny configuration of every serving architecture
# (tests/bench_harness/<name>_tiny), and the leaves its engine turns:
# one entry a run of ``llama.layer_runs``.
_QKV = ["wk", "wq", "wv"]
_LATENT = ["wk_b", "wq_b", "wv_b"]
_DELTA = ["wf_b", "wg_b", "wk", "wq", "wv"]
ARCHITECTURES = {
    "olmoe": [_QKV],
    "trinity": [_QKV + ["wg"]] * 4,
    "joyai": [_LATENT] * 2,
    "brumby": [_QKV],
    "glm52": [_LATENT + ["wi_q"], _LATENT, _LATENT + ["wi_q"]],
    "smallthinker": [_QKV] * 4,
    "kimi": [_DELTA, _DELTA, ["wk_b", "wq", "wv_b"], _DELTA,
             ["wk_b", "wq", "wv_b"]],
    "ouro": [_QKV],
}
every_architecture = pytest.mark.parametrize("name", sorted(ARCHITECTURES))


def _engine(config, cfg, params):
    return LLMEngine(cfg, params, **config.get("engine", {
        "max_batch": 4, "max_len": 256, "page_size": 16, "total_pages": 64}))


def _served(engine):
    rng = np.random.RandomState(7)
    prompts = [list(rng.randint(0, 256, n)) for n in (5, 40, 17)]
    return [r.result(timeout=300)
            for r in [engine.submit(p, 12) for p in prompts]]


@every_architecture
def test_an_engine_serves_the_published_trees_tokens(bench_tiny, name):
    """Three requests through an engine, and through one whose programs
    were handed the published tree (what every engine ran before PR 69:
    the same functions traced over the leaves as published): the same
    tokens, and each engine's programs took the tree they were meant
    to."""
    config, cfg, params = bench_tiny(name)
    serving = _engine(config, cfg, params)
    published = _engine(config, cfg, params)
    try:
        published.runner.params = params
        assert _served(serving) == _served(published)
        turned = llama.turning_leaves(serving.runner.params, back=True)
        assert [sorted(stack) for stack in turned] == [
            [leaf + llama.TURNED for leaf in sorted(stack)]
            for stack in ARCHITECTURES[name]]
        assert not any(llama.turning_leaves(serving.runner.params))
    finally:
        serving.shutdown()
        published.shutdown()


@every_architecture
def test_an_engine_hands_back_the_tree_it_was_given(bench_tiny, name):
    """``engine.params`` is the published tree leaf for leaf (names,
    shape, dtype, bits), the leaves that never turned the given arrays
    themselves; the engine's own tree holds each weight once, the turned
    leaves under their serving names in the serving order; and
    ``stats()["weights"]`` counts the leaves turned and their bytes."""
    config, cfg, params = bench_tiny(name)
    engine = _engine(config, cfg, params)
    try:
        back, held, stats = engine.params, engine.runner.params, engine.stats()
    finally:
        engine.shutdown()
    assert jax.tree.structure(back) == jax.tree.structure(params)
    turning = llama.turning_leaves(params)
    assert [sorted(stack) for stack in turning] == [
        sorted(stack) for stack in ARCHITECTURES[name]]
    for given, returned, own, turns in zip(
            llama.layer_stacks(params), llama.layer_stacks(back),
            llama.layer_stacks(held), turning):
        for leaf, w in given.items():
            assert returned[leaf].shape == w.shape
            assert returned[leaf].dtype == w.dtype
            assert np.array_equal(np.asarray(returned[leaf]), np.asarray(w))
            if leaf in turns:
                order = llama.SERVING_ORDER[leaf]
                assert leaf not in own
                assert own[leaf + llama.TURNED].shape == (
                    w.shape[0], *(w.shape[1 + a] for a in order))
            else:
                assert returned[leaf] is w and own[leaf] is w
        assert len(own) == len(given)
    assert back["embed"] is params["embed"]
    weights = stats["weights"]
    assert weights["leaves_turned"] == sum(map(len, ARCHITECTURES[name]))
    assert weights["bytes_turned"] == sum(
        w.nbytes for stack in turning for w in stack.values())
    assert weights["turn_s"] > 0


def _as_before_pr69(lp, name, x, spec):
    """The line every projection was before there was ``llama.project``."""
    return jnp.einsum(spec, x, lp[name])


@pytest.mark.parametrize("program", ["forward", "train_step"])
def test_a_published_tree_traces_what_it_traced_before(monkeypatch, program):
    """``llama.forward`` and the train step's loss and gradients over a
    published tree: the jaxpr through ``llama.project`` is, letter for
    letter, the jaxpr of the plain einsum over the leaf that each of
    those lines was."""
    cfg = LlamaConfig.tiny()
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((2, 16), jnp.int32)

    def traced():
        if program == "forward":
            return str(jax.make_jaxpr(
                lambda p, t: forward(p, t, cfg))(params, tokens))
        return str(jax.make_jaxpr(jax.value_and_grad(
            lambda p, t: causal_lm_loss(p, t, cfg)))(params, tokens))

    now = traced()
    monkeypatch.setattr(llama, "project", _as_before_pr69)
    assert traced() == now


def test_a_serving_tree_is_told_by_what_it_holds():
    """``project`` multiplies by either order, told apart by the leaf's
    name alone: the same product to the last bit in float32 at these
    sizes, whichever tree it is handed; and a retention layer's gate
    ``wg`` [n, M, Hkv], no stack of [M, H, D], never turns."""
    cfg = LlamaConfig.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    serving = llama.serving_tree(params)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, cfg.hidden_size))
    layer = {k: v[0] for k, v in params["layers"].items()}
    turned = {k: v[0] for k, v in serving["layers"].items()}
    for leaf in ("wq", "wk", "wv"):
        np.testing.assert_allclose(
            np.asarray(llama.project(layer, leaf, x, "bsm,mhd->bshd")),
            np.asarray(llama.project(turned, leaf, x, "bsm,mhd->bshd")),
            rtol=1e-6, atol=1e-6)
    gate = {"layers": {**params["layers"],
                       "wg": jnp.zeros((2, cfg.hidden_size, 2))}}
    assert [sorted(stack) for stack in llama.turning_leaves(gate)] == [
        ["wk", "wq", "wv"]]
    back = llama.serving_tree(serving, back=True)
    assert jax.tree.structure(back) == jax.tree.structure(params)
