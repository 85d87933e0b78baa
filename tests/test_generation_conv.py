"""Gated short-convolution layers among grouped-query ones under the
serving programs (PR 73): a pool of histories, no pages, beside the
"full" pool; heads of 64 two to a row of that pool; the head tied to the
embedding; and what the kind rules still refuse."""

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import LlamaConfig, init_params  # noqa: E402
from ray_tpu.models import llama  # noqa: E402
from ray_tpu.models.generation import (  # noqa: E402
    SLOT_KINDS, KVBooks, PagedKVCache, kv_pool_row, paged_decode,
    paged_prefill)

KINDS = ("conv", "full", "conv", "conv")


def _cfg(**over):
    return LlamaConfig(**{**dict(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=4,
        num_heads=4, num_kv_heads=2, dtype=jnp.float32, layer_types=KINDS,
        qk_norm=True, qk_norm_per_head=True, conv_taps=3, tied_head=True),
        **over})


@pytest.fixture(scope="module")
def conv_programs():
    """``(cfg, params, prefill, decode)``: conv layers round a "full"
    one, the programs jitted as the engine jits them."""
    cfg = _cfg()

    @jax.jit
    def prefill(params, tokens, real_len, cache, slot, pages):
        return paged_prefill(params, tokens, real_len, cache, cfg, slot,
                             pages)

    @jax.jit
    def decode(params, tokens, cache, active):
        return paged_decode(params, tokens, cache, cfg, active=active)

    return cfg, init_params(cfg, jax.random.PRNGKey(2)), prefill, decode


def _prefill(prefill, params, cache, prompt, bucket, slot, pages):
    padded = np.full((1, bucket), 9, np.int32)
    padded[0, :len(prompt)] = prompt
    return prefill(
        params, jnp.asarray(padded), jnp.int32(len(prompt)), cache,
        jnp.int32(slot), {"full": jnp.asarray(pages, jnp.int32),
                          "conv": jnp.zeros((0,), jnp.int32)})


def test_conv_cache_is_a_pool_of_histories_beside_the_full_pool(
        conv_programs):
    cfg = conv_programs[0]
    assert "conv" in SLOT_KINDS
    assert PagedKVCache.sizes(cfg, 4, 99, 16, 8) == {
        "conv": (3, 0, 0), "full": (1, 99, 8)}
    cache = PagedKVCache.create(cfg, 4, 99, 16, 8)
    assert set(cache.k) == {"conv", "full"} and set(cache.v) == {"full"}
    # Histories [L, taps - 1, B, hidden] in the model's dtype: no state.
    assert cache.k["conv"].shape == (3, 2, 4, 64)
    assert cache.k["conv"].dtype == cfg.dtype
    assert cache.k["full"].shape == (1, 2, 99, 16, 16)
    assert cache.page_table["conv"].shape == (4, 0)
    assert cache.page_size == 16
    books = KVBooks(cfg, 4, 99, 16, 8, cache).reading()
    assert books["pages"]["conv"] == {"layers": 3, "total": 0, "free": 0}
    assert books["state_slot_bytes"] == {"conv": 2 * 64 * 4}
    assert books["kv_row_bytes"] == {"full": 2 * 2 * 16 * 4}
    assert books["conv"] == {"slot_layers": 0, "slot_bytes": 3 * 2 * 64 * 4,
                             "layers": 3, "layers_in_all": 4}
    # The tree has one array for the embedding and the head.
    assert "lm_head" not in conv_programs[1]
    assert set(conv_programs[1]["layers"][0]) == {
        "attn_norm", "w_in", "conv_w", "w_out", "mlp_norm", "w_gate", "w_up",
        "w_down"}


@pytest.mark.parametrize("prompt_len", [1, 2, 9, 31])
def test_a_prompt_leaves_the_same_histories_in_any_bucket(conv_programs,
                                                          prompt_len):
    """The history is taken at the last REAL token, not at the bucket's
    end: the same prompt in a bucket of 32, 64 and 128 leaves the same
    two rows a layer and the same logits, zeros in front of a prompt of
    one token, and touches no other slot."""
    cfg, params, prefill, _ = conv_programs
    prompt = np.random.default_rng(prompt_len).integers(0, 256, prompt_len)
    got = []
    for bucket in (32, 64, 128):
        cache = PagedKVCache.create(cfg, 2, 16, 16, 8)
        # A slot taken again: what the request before left is overwritten.
        cache = cache._replace(k={**cache.k, "conv": cache.k["conv"] + 7.0})
        out, cache, _ = _prefill(prefill, params, cache, prompt, bucket, 1,
                                 np.arange(bucket // 16))
        assert int(cache.lengths[1]) == prompt_len
        assert (np.asarray(cache.k["conv"])[:, :, 0] == 7.0).all()
        got.append([np.asarray(x) for x in (cache.k["conv"][:, :, 1], out)])
    for history, logits in got[1:]:
        assert np.abs(history - got[0][0]).max() < 1e-5
        assert np.abs(logits - got[0][1]).max() < 1e-5
    history = got[0][0]                              # [L, taps - 1, M]
    assert history[:, -min(prompt_len, 2):].any()
    assert not history[:, :max(2 - prompt_len, 0)].any()


def test_a_decode_step_shifts_the_history_by_the_tokens_row(conv_programs):
    """Prefill of n tokens then a decode step equals a prefill of n + 1:
    the same histories (the older row out, the token's in), the same
    logits; an idle slot's histories stay as they are."""
    cfg, params, prefill, decode = conv_programs
    seq = np.random.default_rng(0).integers(0, 256, 21)
    cache = PagedKVCache.create(cfg, 2, 16, 16, 8)
    table = np.zeros((2, 8), np.int32)
    table[1, :2] = (3, 5)
    cache = cache._replace(page_table={
        "full": jnp.asarray(table), "conv": cache.page_table["conv"]})
    _, stepped, _ = _prefill(prefill, params, cache, seq[:20], 32, 1, (3, 5))
    idle = np.asarray(stepped.k["conv"])[:, :, 0].copy()
    logits, stepped, _ = decode(
        params, jnp.asarray([0, seq[20]], jnp.int32), stepped,
        jnp.asarray([False, True]))
    want, whole, _ = _prefill(prefill, params, cache, seq, 32, 1, (3, 5))
    assert np.abs(np.asarray(logits[1]) - np.asarray(want[0])).max() < 1e-5
    assert np.abs(np.asarray(stepped.k["conv"][:, :, 1])
                  - np.asarray(whole.k["conv"][:, :, 1])).max() < 1e-5
    np.testing.assert_array_equal(np.asarray(stepped.k["conv"])[:, :, 0],
                                  idle)
    assert stepped.lengths.tolist() == [0, 21]


def test_heads_of_64_lie_two_to_a_row_through_both_programs():
    """A model with heads of 64 on an even number of KV heads keeps its
    k/v pool ``[L, Hkv / 2, P, page, 128]``: a token's bytes are the
    model's own. Its prefill lays rows so and its decode step (the
    gather here, the walk on a TPU) reads them so: a decode step behind
    a prefill equals the longer prefill."""
    cfg = _cfg(hidden_size=256, num_heads=4, num_kv_heads=2, head_dim=64)
    assert kv_pool_row(cfg) == (1, 128)
    assert kv_pool_row(_cfg(num_kv_heads=1, num_heads=4, head_dim=64,
                            hidden_size=256)) == (1, 64)
    assert kv_pool_row(_cfg()) == (2, 16)
    params = init_params(cfg, jax.random.PRNGKey(1))
    cache = PagedKVCache.create(cfg, 2, 8, 16, 4)
    assert cache.k["full"].shape == cache.v["full"].shape == (
        1, 1, 8, 16, 128)
    books = KVBooks(cfg, 2, 8, 16, 4, cache).reading()
    assert books["kv_row_bytes"] == {"full": 2 * 2 * 64 * 4}
    table = np.zeros((2, 4), np.int32)
    table[1, :2] = (6, 2)
    cache = cache._replace(page_table={
        "full": jnp.asarray(table), "conv": cache.page_table["conv"]})
    seq = np.random.default_rng(3).integers(0, 256, 19)
    prefill = jax.jit(lambda p, t, n, c, pg: paged_prefill(
        p, t, n, c, cfg, 1, pg))

    def run(n):
        padded = np.zeros((1, 32), np.int32)
        padded[0, :n] = seq[:n]
        return prefill(params, jnp.asarray(padded), jnp.int32(n), cache,
                       {"full": jnp.asarray((6, 2), jnp.int32),
                        "conv": jnp.zeros((0,), jnp.int32)})

    _, short, _ = run(18)
    logits, stepped, _ = jax.jit(lambda p, t, c, a: paged_decode(
        p, t, c, cfg, active=a))(
            params, jnp.asarray([0, seq[18]], jnp.int32), short,
            jnp.asarray([False, True]))
    want, whole, _ = run(19)
    assert np.abs(np.asarray(logits[1]) - np.asarray(want[0])).max() < 1e-5
    for got, kept in zip(stepped.pools("full"), whole.pools("full")):
        # Page 6 whole, and the token's row, KV head 0 | 1 side by side.
        assert np.abs(np.asarray(got[:, :, 6]) - np.asarray(kept[:, :, 6])
                      ).max() < 1e-5
        assert np.abs(np.asarray(got[:, :, 2, :3])
                      - np.asarray(kept[:, :, 2, :3])).max() < 1e-5


@pytest.mark.parametrize("change,says", [
    (dict(conv_taps=1), "needs conv_taps of two or more"),
    (dict(conv_taps=0), "needs conv_taps"),
    (dict(layer_types=("conv", "window", "conv", "conv"),
          sliding_window=32), "stands beside"),
    (dict(layer_types=("conv", "linear", "conv", "full"), linear_heads=4,
          linear_head_dim=16, linear_decay_layers=(0, 4)), "stands beside"),
    (dict(layer_types=("conv", "state", "conv", "conv")), "stands beside"),
    (dict(layer_types=("conv", "latent", "conv", "conv"), kv_lora_rank=32,
          qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16,
          head_dim=16), "kv_lora_rank"),
])
def test_the_kind_rules_refuse_what_cannot_be_run(change, says):
    with pytest.raises(ValueError, match=says):
        llama.layer_runs(_cfg(**change))


def test_what_is_refused_is_refused_by_name():
    cfg = _cfg()
    with pytest.raises(NotImplementedError, match="tied_head"):
        llama.require_uniform(cfg, "train")
    with pytest.raises(NotImplementedError,
                       match="correlation of conv_taps taps"):
        llama.require_uniform(dataclasses.replace(cfg, tied_head=False),
                              "train")
    with pytest.raises(NotImplementedError,
                       match="a convolution history a slot a PASS"):
        llama.layer_runs(dataclasses.replace(cfg, passes=2))


def test_the_taps_are_one_function_for_both_kinds():
    """``causal_taps``: a delta layer's convolution and a conv layer's
    share the shifted sum; ``y_t = sum_i w_i x_{t - taps + 1 + i}``."""
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(3, 8)), jnp.float32)
    tokens = jnp.asarray(rng.normal(size=(2, 5, 8)), jnp.float32)
    history = jnp.asarray(rng.normal(size=(2, 2, 8)), jnp.float32)
    y, rows = llama.causal_taps(w, tokens, history)
    assert rows.shape == (2, 7, 8)
    want = np.zeros((2, 5, 8))
    whole = np.concatenate([np.asarray(history), np.asarray(tokens)], 1)
    for t in range(5):
        want[:, t] = sum(np.asarray(w)[i] * whole[:, t + i] for i in range(3))
    assert np.abs(np.asarray(y) - want).max() < 1e-5
