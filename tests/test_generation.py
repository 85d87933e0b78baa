"""The serving programs over the paged KV cache against the plain full
forward pass: the dense and the mixture-of-experts models. The decode
attention against a reference of its own is tests/test_generation_kernels.py;
an attention kind's programs are tests/test_generation_<kind>.py."""

import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import LlamaConfig, forward, init_params  # noqa: E402
from ray_tpu.models.generation import (  # noqa: E402
    PagedKVCache, paged_decode, paged_prefill)


@pytest.fixture(scope="module")
def tiny_olmoe(bench_tiny):
    """(the configuration dict, the program's cfg, seeded float32
    weights): OLMoE's block at a tiny size, 8 experts top-2, QK-norm,
    built by the benchmark's own builder."""
    config, cfg, params = bench_tiny("olmoe")
    # Norm weights off one, so that a norm left out cannot pass.
    rng = np.random.RandomState(4)
    layers = dict(params["layers"])
    for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm"):
        leaf = layers[name]
        layers[name] = leaf + jnp.asarray(
            rng.uniform(-0.3, 0.3, leaf.shape), leaf.dtype)
    return config, cfg, dict(params, layers=layers)


def _reference_logits(config, params, tokens):
    from benchmark import olmoe_reference as reference

    x, _ = reference.hidden(params, tokens, config)
    return jnp.einsum("bsm,mv->bsv", x, params["lm_head"],
                      precision="highest")


def test_tiny_olmoe_has_the_qk_norm_and_the_training_block_uses_it(
        tiny_olmoe):
    config, cfg, params = tiny_olmoe
    assert cfg.qk_norm and cfg.n_experts == 8 and cfg.top_k == 2
    assert params["layers"]["q_norm"].shape == (2, 64)
    assert params["layers"]["k_norm"].shape == (2, 64)
    tokens = jnp.asarray(np.random.RandomState(5).randint(0, 256, (2, 24)))
    logits, _ = forward(params, tokens, cfg)
    expected = _reference_logits(config, params, tokens)
    # float32 on both sides: the order of sums alone differs.
    np.testing.assert_allclose(np.asarray(logits), np.asarray(expected),
                               atol=1e-4, rtol=1e-4)
    flat = dict(params, layers=dict(
        params["layers"], q_norm=jnp.ones_like(params["layers"]["q_norm"])))
    off, _ = forward(flat, tokens, cfg)
    assert float(jnp.abs(off - expected).max()) > 1e-2


@pytest.fixture(scope="module")
def served(tiny_olmoe):
    """``served(name) -> (cfg, seeded float32 weights, tokens [B, S] ->
    the logits of a full forward pass that caches nothing, prefill,
    decode)``: the tiny dense model and the tiny mixture of experts
    against the program's own ``forward``, the tiny OLMoE against the
    benchmark's reference, which shares no code with the program. The
    two serving programs are jitted, as the engine jits them, once a
    model: its cases that run the same bucket share the prefill's
    compile, and all of them the decode step's (``.__wrapped__`` is the
    program op by op, for the one case a model that runs it so)."""

    @functools.lru_cache(maxsize=None)
    def build(name):
        if name == "olmoe":
            config, cfg, params = tiny_olmoe

            def full_forward(tokens):
                return _reference_logits(config, params, tokens)
        else:
            cfg = LlamaConfig.tiny(moe=name == "moe")
            params = init_params(cfg, jax.random.PRNGKey(0))

            def full_forward(tokens):
                return forward(params, tokens, cfg)[0]

        def prefill(params, tokens, real_len, cache, slot, pages):
            return paged_prefill(params, tokens, real_len, cache, cfg, slot,
                                 pages)

        def decode(params, last, cache, active):
            return paged_decode(params, last, cache, cfg, active=active)

        return cfg, params, full_forward, jax.jit(prefill), jax.jit(decode)

    return build


@pytest.mark.parametrize("real_len", [5, 16, 27])
@pytest.mark.parametrize("model", ["dense", "moe", "olmoe"])
def test_paged_prefill_then_decode_equals_the_full_forward(served, model,
                                                           real_len):
    """A prompt padded to its bucket through ``paged_prefill``, then
    tokens one at a time through ``paged_decode`` beside idle slots,
    against a FULL forward pass of the same sequence at its exact
    length: logits compared, so a padded prefill equals an exact one at
    the last real position and decode continues from what it cached.
    Tolerance 1e-4: float32 on both sides, the sums in another order; a
    bucket's padding or an idle slot reaching an expert, a norm left
    out or a dropped assignment is off by more than 1e-2."""
    cfg, params, full_forward, prefill, decode = served(model)
    if real_len == 5:
        # One case a model op by op: a fault that shows only outside jit.
        prefill, decode = prefill.__wrapped__, decode.__wrapped__
    rng = np.random.RandomState(real_len)
    steps, page, slots, slot = 4, 16, 3, 1
    seq = rng.randint(0, 256, real_len + steps)
    bucket = 16 if real_len <= 16 else 32
    expected = np.asarray(full_forward(jnp.asarray(seq[None])))[0]

    cache = PagedKVCache.create(cfg, slots, 8, page, 4)
    pages = [5, 2, 7]                      # the slot's pages, out of order
    table = np.zeros((slots, 4), np.int32)
    table[slot, :3] = pages
    cache = cache._replace(page_table={"full": jnp.asarray(table)})
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :real_len] = seq[:real_len]
    logits, cache, load = prefill(
        params, jnp.asarray(padded), jnp.asarray(real_len, jnp.int32),
        cache, slot, {"full": jnp.asarray(pages[:bucket // page], jnp.int32)})
    np.testing.assert_allclose(np.asarray(logits)[0], expected[real_len - 1],
                               atol=1e-4, rtol=1e-4)
    assert list(np.asarray(cache.lengths)) == [0, real_len, 0]
    per_token = cfg.top_k * cfg.num_layers
    if cfg.n_experts == 0:
        assert load is None
    else:
        # The bucket's padding reached no expert: real tokens x k x layers.
        assert int(load.expert_tokens.sum()) == real_len * per_token
        # (layer, expert) pairs: at least the experts seen in any layer.
        assert int((np.asarray(load.expert_tokens) > 0).sum()) \
            <= int(load.experts_reached) \
            <= min(cfg.n_experts * cfg.num_layers, real_len * per_token)

    active = jnp.asarray(np.arange(slots) == slot)
    for i in range(steps):
        last = np.zeros(slots, np.int32)
        last[slot] = seq[real_len + i]
        logits, cache, load = decode(params, jnp.asarray(last), cache, active)
        np.testing.assert_allclose(
            np.asarray(logits)[slot], expected[real_len + i],
            atol=1e-4, rtol=1e-4)
        if cfg.n_experts > 0:
            # One live slot: k experts in each layer, whatever idles.
            assert int(load.expert_tokens.sum()) == per_token
            assert int(load.experts_reached) == per_token
    assert list(np.asarray(cache.lengths)) == [0, real_len + steps, 0]


def test_paged_decode_leaves_an_inactive_slot_as_it_was():
    """Two prefilled slots, one decode step with the second inactive:
    its length and every cell of its pages are what they were, the
    first slot's length is one more and its new row is the only cell of
    either pool that changed."""
    cfg = LlamaConfig.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    page, lens, pages = 16, (5, 9), ([3], [1])
    cache = PagedKVCache.create(cfg, 2, 4, page, 2)
    table = np.zeros((2, 2), np.int32)
    table[0, 0], table[1, 0] = pages[0][0], pages[1][0]
    cache = cache._replace(page_table={"full": jnp.asarray(table)})
    rng = np.random.RandomState(0)
    for slot, n in enumerate(lens):
        prompt = np.zeros((1, page), np.int32)
        prompt[0, :n] = rng.randint(0, 256, n)
        _, cache, _ = paged_prefill(
            params, jnp.asarray(prompt), jnp.asarray(n, jnp.int32), cache,
            cfg, slot, {"full": jnp.asarray(pages[slot], jnp.int32)})
    _, after, _ = paged_decode(
        params, jnp.asarray([5, 9], jnp.int32), cache, cfg,
        active=jnp.asarray([True, False]))
    assert list(np.asarray(after.lengths)) == [lens[0] + 1, lens[1]]
    for old, new in ((cache.k["full"], after.k["full"]),
                     (cache.v["full"], after.v["full"])):
        changed = np.argwhere((np.asarray(old) != np.asarray(new)).any(-1))
        # [layer, kv head, page, row]: slot 0's row 5 of page 3 alone.
        assert {tuple(c[2:]) for c in changed} == {(pages[0][0], lens[0])}
        assert len(changed) == cfg.num_layers * cfg.num_kv_heads


def test_dense_programs_return_no_expert_load():
    cfg = LlamaConfig.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    cache = PagedKVCache.create(cfg, 2, 4, 16, 2)
    _, _, load = paged_decode(params, jnp.zeros(2, jnp.int32), cache, cfg,
                              active=jnp.asarray([True, False]))
    assert load is None
