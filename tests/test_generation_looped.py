"""A looped model under the serving programs (PR 65): the stack run
several times over one set of weights, a pool ``passes`` times as deep."""

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import LlamaConfig, forward, init_params  # noqa: E402
from ray_tpu.models import llama  # noqa: E402
from ray_tpu.models.generation import (  # noqa: E402
    PagedKVCache, paged_decode, paged_prefill)


def _looped(kinds, passes):
    """(cfg, weights, the same weights as ONE stack of an all-"full"
    model for ``forward``, which takes no stack in runs)."""
    cfg = dataclasses.replace(
        LlamaConfig.tiny(), num_layers=len(kinds), num_kv_heads=4,
        post_norms=True, passes=passes,
        layer_types=None if set(kinds) == {"full"} else kinds,
        sliding_window=None if set(kinds) == {"full"} else 32)
    params = init_params(cfg, jax.random.PRNGKey(2))
    stacks = llama.layer_stacks(params)
    flat = {**params, "layers": jax.tree.map(
        lambda *leaves: jnp.concatenate(leaves), *stacks)}
    return cfg, params, dataclasses.replace(
        cfg, layer_types=None, sliding_window=None), flat


@pytest.mark.parametrize("kinds,passes", [
    (("full", "full", "full"), 1), (("full", "full", "full"), 2),
    (("full", "full", "full"), 3), (("window", "full", "window"), 2)],
    ids=["one-pass", "two-passes", "three-passes", "window-and-full"])
def test_a_looped_stack_through_the_cache_equals_the_full_forward(kinds,
                                                                  passes):
    """``passes`` walks of the same stacked weights, ``final_norm``
    behind each: prefill then decode through pools ``passes`` times as
    deep as the stack, each pass in layers of its own, against
    ``forward``, which runs the loop and caches nothing. One pass is the
    program it always was: a pool as deep as the stack and one layer
    scan, no scan around it. The window layers' context stays under the
    window (20 + 4 of 32), where a window layer IS a full one and
    ``forward`` (which takes no window) can say what is right: the case
    holds the two pools' places under the loop, not the window's mask."""
    cfg, params, flat_cfg, flat = _looped(kinds, passes)
    per_pass = {kind: kinds.count(kind) for kind in dict.fromkeys(kinds)}
    assert llama.kv_layers_a_pass(cfg) == per_pass
    assert llama.kv_layers(cfg) == {
        kind: n * passes for kind, n in per_pass.items()}
    rng = np.random.RandomState(passes)
    real_len, steps, page, slots, slot = 20, 4, 16, 3, 1
    seq = rng.randint(0, 256, real_len + steps)
    expected = np.asarray(
        forward(flat, jnp.asarray(seq[None]), flat_cfg)[0])[0]

    cache = PagedKVCache.create(cfg, slots, 8, page, 4)
    assert {kind: pool.shape[0] for kind, pool in cache.k.items()} == \
        llama.kv_layers(cfg)
    sizes = PagedKVCache.sizes(cfg, slots, 8, page, 4)
    tables, pages = {}, {}
    for kind, (_, pool_pages, columns) in sizes.items():
        ids = np.arange(pool_pages)[::-1][slot * columns:][:columns]
        tables[kind] = np.zeros((slots, columns), np.int32)
        tables[kind][slot] = ids
        pages[kind] = jnp.asarray(ids[:min(32 // page, columns)], jnp.int32)
    cache = cache._replace(page_table={
        kind: jnp.asarray(table) for kind, table in tables.items()})
    padded = np.zeros((1, 32), np.int32)
    padded[0, :real_len] = seq[:real_len]
    logits, cache, load = paged_prefill(
        params, jnp.asarray(padded), jnp.asarray(real_len, jnp.int32),
        cache, cfg, slot, pages)
    assert load is None
    np.testing.assert_allclose(np.asarray(logits)[0], expected[real_len - 1],
                               atol=1e-4, rtol=1e-4)
    active = jnp.asarray(np.arange(slots) == slot)
    for i in range(steps):
        last = np.zeros(slots, np.int32)
        last[slot] = seq[real_len + i]
        logits, cache, _ = paged_decode(
            params, jnp.asarray(last), cache, cfg, active=active)
        np.testing.assert_allclose(
            np.asarray(logits)[slot], expected[real_len + i],
            atol=1e-4, rtol=1e-4)
    # Every (pass, layer) of a pool kept rows of its own.
    for kind, pool in cache.k.items():
        filled = np.abs(np.asarray(pool)).sum(axis=(1, 2, 3, 4)) > 0
        assert filled.all() and len(filled) == per_pass[kind] * passes
    scans = str(jax.make_jaxpr(lambda params, cache: paged_decode(
        params, jnp.zeros(slots, jnp.int32), cache, cfg, active=active))(
            params, cache)).count("scan[")
    runs = len(llama.layer_runs(cfg))
    assert scans == (runs if passes == 1 else runs + 1)


def test_another_number_of_passes_is_another_model():
    """The same weights walked twice and three times differ by far more
    than the tolerance: the loop is in the arithmetic, not beside it."""
    two, _, flat_cfg, flat = _looped(("full",) * 3, 2)
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 256, (1, 24)))
    a = forward(flat, tokens, flat_cfg)[0]
    b = forward(flat, tokens, dataclasses.replace(flat_cfg, passes=3))[0]
    assert float(jnp.abs(a - b).max()) > 1e-2


@pytest.mark.parametrize("changes,says", [
    ({"layer_types": ("state",) * 2}, "retention state a slot a PASS"),
    ({"layer_types": ("latent", "delta"), "kv_lora_rank": 32,
      "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 16,
      "delta_heads": 2, "delta_head_dim": 16, "delta_conv": 4},
     "delta-rule state"),
    ({"kv_lora_rank": 32, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
      "v_head_dim": 16}, "latent row a token a PASS"),
    ({"n_experts": 4}, "expert-load counters a pass"),
], ids=["state", "delta", "latent", "experts"])
def test_a_loop_over_what_has_no_place_a_pass_is_refused_by_name(changes,
                                                                 says):
    cfg = dataclasses.replace(LlamaConfig.tiny(), passes=2, **changes)
    with pytest.raises(NotImplementedError, match=says):
        llama.layer_runs(cfg)
    with pytest.raises(NotImplementedError, match="passes=2"):
        PagedKVCache.sizes(cfg, 2, 8, 16, 4)


def test_a_loop_is_served_and_not_trained_and_a_gate_needs_two_passes():
    cfg = dataclasses.replace(LlamaConfig.tiny(), passes=2)
    params = init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="looped model"):
        llama.causal_lm_loss(params, jnp.zeros((1, 9), jnp.int32), cfg)
    for bad in ({"passes": 0}, {"passes": 1, "exit_gate": True}):
        with pytest.raises(ValueError, match="passes is"):
            llama.layer_runs(dataclasses.replace(LlamaConfig.tiny(), **bad))
    # The gate's two leaves are drawn last: a seed's other weights are
    # what they are without it.
    gated = init_params(dataclasses.replace(cfg, exit_gate=True),
                        jax.random.PRNGKey(0))
    assert sorted(set(gated) - set(params)) == ["exit_b", "exit_w"]
    assert (gated["exit_w"].shape, gated["exit_b"].shape) == ((64, 1), (1,))
    np.testing.assert_array_equal(np.asarray(gated["lm_head"]),
                                  np.asarray(params["lm_head"]))
    # The distribution, by hand: lambda = 1/2 everywhere.
    p = np.asarray(llama.exit_distribution(jnp.zeros((2, 4))))
    np.testing.assert_allclose(p, [[0.5, 0.25, 0.125, 0.125]] * 2)
