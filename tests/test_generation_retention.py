"""Retention layers under the serving programs (PR 45): a state a slot,
no pages, and the two kernels against their XLA paths."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import LlamaConfig, init_params  # noqa: E402
from ray_tpu.models.generation import (  # noqa: E402
    PagedKVCache, paged_decode, paged_prefill)


@pytest.fixture(scope="module")
def state_programs():
    """``(cfg, params, prefill, decode)``: three retention layers and
    the two serving programs over them, jitted as the engine jits them:
    the cases here run the same buckets, and compile each once."""
    cfg = LlamaConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                      num_layers=3, num_heads=4, num_kv_heads=2, head_dim=16,
                      rope_theta=10_000.0, dtype=jnp.float32,
                      layer_types=("state",) * 3, qk_norm=True,
                      qk_norm_per_head=True)

    @jax.jit
    def prefill(params, tokens, real_len, cache, slot):
        return paged_prefill(params, tokens, real_len, cache, cfg, slot,
                             {"state": jnp.zeros((0,), jnp.int32)})

    @jax.jit
    def decode(params, last, cache, active):
        return paged_decode(params, last, cache, cfg, active=active)

    return cfg, init_params(cfg, jax.random.PRNGKey(2)), prefill, decode


def _state_prefill(prefill, params, cache, prompt, bucket, slot):
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :len(prompt)] = prompt
    return prefill(params, jnp.asarray(padded), jnp.int32(len(prompt)),
                   cache, jnp.int32(slot))


def test_state_cache_is_one_pool_of_slots_with_no_pages(state_programs):
    from ray_tpu.ops import retention

    cfg = state_programs[0]
    assert PagedKVCache.sizes(cfg, 4, 99, 16, 8) == {"state": (3, 0, 0)}
    cache = PagedKVCache.create(cfg, 4, 99, 16, 8)
    assert set(cache.k) == {"state"} and cache.v == {}
    # [L, B, Hkv, d/2 + 1 turns, d + 8 rows, d] float32.
    assert cache.k["state"].shape == (3, 4, 2, 9, 24, 16) == \
        retention.state_shape(3, 4, 2, 16)
    assert cache.k["state"].dtype == jnp.float32
    assert cache.page_table["state"].shape == (4, 0)
    assert cache.page_size is None and cache.pools("state") == (
        cache.k["state"],)


@pytest.mark.parametrize("prompt_len", [9, 16, 31])
def test_a_prompt_leaves_the_same_state_in_any_bucket(state_programs,
                                                      prompt_len):
    """Padding must not reach the state. The retention itself: the same
    real tokens, the padding masked as ``paged_prefill`` masks it (a
    gate of 1, a key of 0), in a bucket of their own, one twice and one
    four times as long leave the same state and outputs BIT FOR BIT.
    Through the whole model the same to float32's rounding (what differs
    there is XLA's own matmul of 32 rows against one of 64)."""
    from ray_tpu.ops import retention

    ks = jax.random.split(jax.random.PRNGKey(prompt_len), 4)
    real = jnp.arange(128) < prompt_len
    q = jax.random.normal(ks[0], (128, 4, 16))
    k = jnp.where(real[:, None, None],
                  jax.random.normal(ks[1], (128, 2, 16)), 0)
    v = jax.random.normal(ks[2], (128, 2, 16))
    log_g = jnp.where(real[:, None], jax.nn.log_sigmoid(
        jax.random.normal(ks[3], (128, 2)) + 3.0), 0.0)
    outs = [retention.retention_prefill(q[:n], k[:n], v[:n], log_g[:n])
            for n in (32, 64, 128)]
    assert np.asarray(outs[0][1]).any()
    for y, state in outs[1:]:
        assert np.array_equal(np.asarray(state), np.asarray(outs[0][1]))
        assert np.array_equal(np.asarray(y[:prompt_len]),
                              np.asarray(outs[0][0][:prompt_len]))

    cfg, params, prefill, _ = state_programs
    if prompt_len == 9:
        # One case op by op: a fault that shows only outside jit.
        prefill = prefill.__wrapped__
    prompt = np.random.default_rng(prompt_len).integers(0, 256, prompt_len)
    states, logits = [], []
    for bucket in (32, 64, 128):
        cache = PagedKVCache.create(cfg, 2, 1, 16, 8)
        out, cache, _ = _state_prefill(prefill, params, cache, prompt,
                                       bucket, 1)
        states.append(np.asarray(cache.k["state"]))
        logits.append(np.asarray(out))
        assert int(cache.lengths[1]) == prompt_len
        assert not states[-1][:, 0].any()         # the other slot untouched
    scale = np.abs(states[0]).max()
    assert all(np.abs(states[0] - s).max() < 1e-5 * scale
               for s in states[1:])
    assert all(np.abs(logits[0] - x).max() < 1e-5 for x in logits[1:])


def test_a_slot_reused_after_a_longer_request_carries_nothing_over(
        state_programs):
    """Nothing is zeroed at release: the next prefill overwrites the
    slot's state whole. A short request in a slot that just held a long
    one decodes exactly as in a fresh cache."""
    cfg, params, prefill, decode_step = state_programs
    rng = np.random.default_rng(0)
    long, short = rng.integers(0, 256, 100), rng.integers(0, 256, 11)
    active = jnp.asarray([True, False])

    def decode(cache, n=5):
        rows = []
        for tok in range(n):
            out, cache, _ = decode_step(
                params, jnp.full((2,), tok, jnp.int32), cache, active)
            rows.append(np.asarray(out[0]))
        return np.stack(rows), cache

    used = PagedKVCache.create(cfg, 2, 1, 16, 8)
    _, used, _ = _state_prefill(prefill, params, used, long, 128, 0)
    _, used = decode(used, 7)
    first_used, used, _ = _state_prefill(prefill, params, used, short, 16, 0)
    rows_used, used = decode(used)
    fresh = PagedKVCache.create(cfg, 2, 1, 16, 8)
    first_fresh, fresh, _ = _state_prefill(prefill, params, fresh, short, 16,
                                           0)
    rows_fresh, fresh = decode(fresh)
    assert np.array_equal(np.asarray(first_used), np.asarray(first_fresh))
    assert np.array_equal(rows_used, rows_fresh)
    assert np.array_equal(np.asarray(used.k["state"]),
                          np.asarray(fresh.k["state"]))


def _kernel_inputs(S, H, Hkv, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (S, H, 128)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (S, Hkv, 128)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (S, Hkv, 128)).astype(jnp.bfloat16)
    log_g = jax.nn.log_sigmoid(jax.random.normal(ks[3], (S, Hkv)) + 4.0)
    return q, k, v, log_g


def test_chunk_scan_kernel_matches_the_xla_path():
    """The Pallas prefill kernel, interpreted: two chunks of a head, so
    the second reads the state the first left. bfloat16 operands to the
    MXU, float32 state: within bfloat16's rounding of the XLA path."""
    from ray_tpu.ops import retention

    q, k, v, log_g = _kernel_inputs(2 * retention.CHUNK, 2, 1)
    want, state = retention.xla_retention_prefill(q, k, v, log_g)
    got, got_state = retention.chunk_scan(q, k, v, log_g, interpret=True)
    scale = float(jnp.abs(want.astype(jnp.float32)).max())
    assert float(jnp.abs(got.astype(jnp.float32)
                         - want.astype(jnp.float32)).max()) < 0.02 * scale
    assert float(jnp.abs(got_state - state).max()) < 0.01 * float(
        jnp.abs(state).max())


@pytest.mark.parametrize("active", [
    (True, True, True), (False, True, False), (True, False, True),
    (False, False, True), (True, False, False), (False, False, False)],
    ids=lambda a: "".join("x" if s else "-" for s in a))
@pytest.mark.parametrize("group", [2, 5], ids=lambda g: f"g{g}")
def test_state_step_kernel_matches_the_xla_path_and_skips_idle_slots(
        active, group):
    """The Pallas decode kernel, interpreted: every pattern of idle
    slots before, between and after active ones, and nobody active, at
    two and at the cell's five query heads a KV head (which divide
    nothing a pass over the turns is cut by). An idle slot's state is
    what it was, bit for bit, and so is every other layer."""
    from ray_tpu.ops import retention

    q, k, v, log_g = _kernel_inputs(3, 2 * group, 2, seed=1)
    pool = jax.random.normal(jax.random.PRNGKey(7),
                             retention.state_shape(2, 3, 2, 128)) + 3.0
    on = jnp.asarray(active)
    want, want_pool = retention.xla_retention_decode(q, k, v, log_g, pool,
                                                     1, on)
    got, got_pool = retention.state_step(q, k, v, log_g, pool, 1, on,
                                         interpret=True)
    idle = ~np.asarray(active)
    assert np.array_equal(np.asarray(got_pool[0]), np.asarray(pool[0]))
    assert np.array_equal(np.asarray(got_pool[1])[idle],
                          np.asarray(pool[1])[idle])
    assert np.allclose(got_pool, want_pool, rtol=1e-6, atol=1e-6)
    busy = np.asarray(active)
    assert np.allclose(np.asarray(got, np.float32)[busy],
                       np.asarray(want, np.float32)[busy], rtol=0.02,
                       atol=0.02)
