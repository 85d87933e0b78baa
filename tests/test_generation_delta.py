"""Delta-rule layers among latent ones under the serving programs
(PR 62): two pools of slots beside the latent pool."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import LlamaConfig, init_params  # noqa: E402
from ray_tpu.models.generation import (  # noqa: E402
    PagedKVCache, paged_prefill)


@pytest.fixture(scope="module")
def hybrid_programs():
    """``(cfg, params, prefill)``: delta layers round a latent one, and
    the prefill over them jitted as the engine jits it: the cases here
    run the same three buckets, and compile each once."""
    cfg = LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=4,
        num_heads=4, num_kv_heads=4, head_dim=24, dtype=jnp.float32,
        q_lora_rank=0, latent_rope=False, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        layer_types=("delta", "delta", "latent", "delta"),
        delta_heads=4, delta_head_dim=16, delta_conv=4)

    @jax.jit
    def prefill(params, tokens, real_len, cache, slot, pages):
        return paged_prefill(params, tokens, real_len, cache, cfg, slot,
                             pages)

    return cfg, init_params(cfg, jax.random.PRNGKey(2)), prefill


def _hybrid_prefill(prefill, params, cache, prompt, bucket, slot, pages):
    padded = np.full((1, bucket), 9, np.int32)
    padded[0, :len(prompt)] = prompt
    return prefill(
        params, jnp.asarray(padded), jnp.int32(len(prompt)), cache,
        jnp.int32(slot), {"latent": jnp.asarray(pages, jnp.int32),
                          "delta": jnp.zeros((0,), jnp.int32)})


def test_delta_cache_is_two_pools_of_slots_beside_the_latent_pool(
        hybrid_programs):
    cfg = hybrid_programs[0]
    assert PagedKVCache.sizes(cfg, 4, 99, 16, 8) == {
        "delta": (3, 0, 0), "latent": (1, 99, 8)}
    cache = PagedKVCache.create(cfg, 4, 99, 16, 8)
    assert set(cache.k) == {"delta", "latent"} and set(cache.v) == {"delta"}
    # States [L, B, H, D, D] float32; histories [L, taps - 1, B, 3 H D].
    assert cache.k["delta"].shape == (3, 4, 4, 16, 16)
    assert cache.k["delta"].dtype == jnp.float32
    assert cache.v["delta"].shape == (3, 3, 4, 3 * 64)
    assert cache.k["latent"].shape == (1, 99, 16, 32 + 128)
    assert cache.page_table["delta"].shape == (4, 0)
    assert cache.page_table["latent"].shape == (4, 8)
    assert cache.page_size == 16
    assert cache.pools("delta") == (cache.k["delta"], cache.v["delta"])


@pytest.mark.parametrize("prompt_len", [2, 9, 31])
def test_a_prompt_leaves_the_same_delta_pools_in_any_bucket(hybrid_programs,
                                                            prompt_len):
    """Padding must reach neither the state nor the convolution's
    history: the same prompt in a bucket of 32, 64 and 128 (the last a
    whole chunk of the delta prefill) leaves the same states, the same
    three history rows, those of the last REAL tokens (zeros where the
    prompt is shorter than the history), and the same logits, and
    touches no other slot."""
    cfg, params, prefill = hybrid_programs
    if prompt_len == 2:
        # One case op by op: a fault that shows only outside jit.
        prefill = prefill.__wrapped__
    prompt = np.random.default_rng(prompt_len).integers(0, 256, prompt_len)
    got = []
    for bucket in (32, 64, 128):
        cache = PagedKVCache.create(cfg, 2, 16, 16, 8)
        out, cache, _ = _hybrid_prefill(prefill, params, cache, prompt,
                                        bucket, 1, np.arange(bucket // 16))
        assert int(cache.lengths[1]) == prompt_len
        assert not np.asarray(cache.k["delta"])[:, 0].any()
        assert not np.asarray(cache.v["delta"])[:, :, 0].any()
        got.append([np.asarray(x) for x in (
            cache.k["delta"][:, 1], cache.v["delta"][:, :, 1], out)])
    for states, history, logits in got[1:]:
        assert np.abs(states - got[0][0]).max() < 1e-5
        assert np.abs(history - got[0][1]).max() < 1e-5
        assert np.abs(logits - got[0][2]).max() < 1e-5
    history = got[0][1]
    assert history[:, -min(prompt_len, 3):].any()
    assert not history[:, :max(3 - prompt_len, 0)].any()
