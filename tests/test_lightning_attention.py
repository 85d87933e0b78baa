"""ops/lightning_attention.py: the XLA paths against the recurrence a
token at a time, the Pallas kernels interpreted against the XLA paths."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops import lightning_attention as la  # noqa: E402


def _inputs(seed, S, H, d, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    q, k, v = (jnp.asarray(rng.normal(size=(S, H, d)) / d ** 0.25, dtype)
               for _ in range(3))
    return q, k, v


def _recurrence(q, k, v, log_g, scale):
    """S_t = g_t S_{t-1} + k_t v_t^T, o_t = scale S_t^T q_t, in float64."""
    q, k, v, log_g = (np.asarray(x, np.float64) for x in (q, k, v, log_g))
    S, H, d = q.shape
    state = np.zeros((H, d, d))
    out = np.zeros((S, H, d))
    for t in range(S):
        state = (np.exp(log_g[t])[:, None, None] * state
                 + k[t][:, :, None] * v[t][:, None, :])
        out[t] = scale * np.einsum("hde,hd->he", state, q[t])
    return out, state


def test_decays_are_the_published_formula():
    got = np.asarray(la.log_decays(32, 16, 32))
    want = -(2.0 ** (-8 * np.arange(1, 33) / 32)) * (1 - 16 / 31 + 1e-5)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # The deepest layer forgets slowest; head 0 of any layer fastest.
    assert np.all(np.asarray(la.log_decays(32, 31, 32)) > got)
    assert got[0] == got.min()


@pytest.mark.parametrize("S,real", [(40, 40), (300, 300), (512, 389)])
def test_xla_prefill_is_the_recurrence(S, real):
    """Whole chunks and a tail, and a bucket's padding masked as the
    caller masks it (log gate 0, key 0): the state is the one after the
    last real token."""
    H, d, scale = 3, 16, 16 ** -0.5
    q, k, v = _inputs(S, S, H, d)
    decays = la.log_decays(H, 5, 32)
    is_real = jnp.arange(S) < real
    log_g = jnp.where(is_real[:, None], decays[None], 0.0)
    out, state = la.xla_lightning_prefill(
        q, jnp.where(is_real[:, None, None], k, 0), v, log_g, scale=scale)
    want_out, want_state = _recurrence(q[:real], k[:real], v[:real],
                                       log_g[:real], scale)
    np.testing.assert_allclose(out[:real], want_out, atol=2e-5)
    np.testing.assert_allclose(state, want_state, atol=2e-5)


def test_xla_decode_steps_the_state_and_leaves_idle_slots():
    B, H, d, scale = 4, 3, 16, 0.25
    q, k, v = _inputs(1, B, H, d)
    rng = np.random.default_rng(2)
    pool = jnp.asarray(rng.normal(size=la.state_shape(2, B, H, d)),
                       jnp.float32)
    log_g = jnp.broadcast_to(la.log_decays(H, 3, 32), (B, H))
    active = jnp.asarray([True, False, True, True])
    out, new = la.xla_lightning_decode(q, k, v, log_g, pool, 1, active,
                                       scale=scale)
    want = (np.exp(np.asarray(log_g))[..., None, None] * np.asarray(pool[1])
            + np.asarray(k)[..., :, None] * np.asarray(v)[..., None, :])
    np.testing.assert_allclose(new[1][active], want[np.asarray(active)],
                               atol=1e-6)
    np.testing.assert_array_equal(new[1][1], pool[1][1])
    np.testing.assert_array_equal(new[0], pool[0])
    np.testing.assert_allclose(
        out[0], scale * np.einsum("hde,hd->he", want[0], np.asarray(q[0])),
        atol=1e-5)


@pytest.mark.parametrize("active", [
    [True, True, True], [False, True, False], [False, False, False],
    [True, False, True]])
def test_step_kernel_is_the_xla_path(active):
    """Interpreted at heads of 128, 32 heads in two blocks: active slots
    step, idle ones keep their state whichever neighbour's block their
    steps were pointed at."""
    B, H, d, scale = 3, 32, 128, 128 ** -0.5
    q, k, v = _inputs(3, B, H, d)
    pool = jnp.asarray(np.random.default_rng(4).normal(
        size=la.state_shape(2, B, H, d)), jnp.float32)
    log_g = jnp.broadcast_to(la.log_decays(H, 20, 32), (B, H))
    active = jnp.asarray(active)
    want_out, want_pool = la.xla_lightning_decode(
        q, k, v, log_g, pool, 1, active, scale=scale)
    out, new = la.lightning_step(q, k, v, log_g, pool, jnp.asarray(1),
                                 active, scale=scale, interpret=True)
    np.testing.assert_allclose(new, want_pool, atol=1e-5)
    np.testing.assert_allclose(out[active], want_out[active], atol=1e-4)
    assert not np.asarray(out)[~np.asarray(active)].any()


def test_scan_kernel_is_the_xla_path():
    S, H, d, scale = 768, 2, 128, 128 ** -0.5
    q, k, v = _inputs(5, S, H, d)
    real = jnp.arange(S) < 700
    log_g = jnp.where(real[:, None], la.log_decays(H, 16, 32)[None], 0.0)
    k = jnp.where(real[:, None, None], k, 0)
    want_out, want_state = la.xla_lightning_prefill(q, k, v, log_g,
                                                    scale=scale)
    out, state = la.lightning_scan(q, k, v, log_g, scale=scale,
                                   interpret=True)
    np.testing.assert_allclose(out, want_out, atol=2e-4)
    np.testing.assert_allclose(state, want_state, atol=2e-4)


def test_the_path_follows_platform_and_shape():
    assert la.lightning_path(128, 32) == "xla"      # the CPU
    assert la.state_shape(9, 16, 32, 128) == (9, 16, 32, 128, 128)
