"""Pallas flash attention kernel tests (interpret mode on the CPU mesh;
the same kernel compiles for real on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import flash_attention, mha_attention


def _rand(shape, key):
    return jax.random.normal(jax.random.PRNGKey(key), shape,
                             dtype=jnp.float32)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    B, S, H, D = 2, 256, 4, 64
    q, k, v = (_rand((B, S, H, D), i) for i in range(3))
    ref = mha_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, interpret=True,
                          block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_gqa():
    B, S, H, Hkv, D = 2, 256, 8, 2, 64
    q = _rand((B, S, H, D), 0)
    k = _rand((B, S, Hkv, D), 1)
    v = _rand((B, S, Hkv, D), 2)
    ref = mha_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_decode_offsets():
    """Global-coordinate masking: a single query block at q_offset against
    a long KV prefix (the decode/ring-attention case)."""
    B, H, D = 1, 4, 64
    Skv, Sq, q_off = 512, 128, 384
    q = _rand((B, Sq, H, D), 0)
    k = _rand((B, Skv, H, D), 1)
    v = _rand((B, Skv, H, D), 2)
    ref = mha_attention(q, k, v, causal=True, q_offset=q_off)
    out = flash_attention(q, k, v, causal=True, q_offset=q_off,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_gradients_match():
    B, S, H, D = 1, 128, 2, 32
    q, k, v = (_rand((B, S, H, D), i) for i in range(3))

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               interpret=True).sum()

    def loss_ref(q, k, v):
        return mha_attention(q, k, v, causal=True).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)


def test_flash_fallback_for_odd_shapes():
    # Non-tileable sequence length silently takes the XLA path.
    B, S, H, D = 1, 100, 2, 32
    q, k, v = (_rand((B, S, H, D), i) for i in range(3))
    out = flash_attention(q, k, v, causal=True)
    ref = mha_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-6)


def test_flash_gqa_gradients_match():
    """Backward with grouped KV heads: dK/dV must sum each group's query
    heads (reduced inside the grouped dkv kernel)."""
    B, S, H, Hkv, D = 1, 128, 4, 2, 32
    q = _rand((B, S, H, D), 0)
    k = _rand((B, S, Hkv, D), 1)
    v = _rand((B, S, Hkv, D), 2)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True,
                                interpret=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (mha_attention(q, k, v, causal=True) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5, rtol=3e-5)


def test_flash_decode_offset_gradients():
    """Fused backward with nonzero q_offset (the block-bound math must
    stay consistent with the forward's)."""
    B, S, Skv, H, D = 1, 128, 256, 2, 32
    q = _rand((B, S, H, D), 3)
    k = _rand((B, Skv, H, D), 4)
    v = _rand((B, Skv, H, D), 5)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=True, q_offset=128,
                               interpret=True).sum()

    def loss_ref(q, k, v):
        return mha_attention(q, k, v, causal=True, q_offset=128).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)


def test_flash_gqa_gradients_perhead_fallback(monkeypatch):
    """Shapes whose grouped [rep, Sq, D] Q/dO block would overflow VMEM
    use the per-query-head dkv kernel + external group sum; force that
    path by zeroing the VMEM budget and check grads still match XLA."""
    import importlib

    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "_DKV_GROUP_VMEM_BUDGET", 0)
    B, S, H, Hkv, D = 1, 128, 4, 2, 32
    q = _rand((B, S, H, D), 0)
    k = _rand((B, S, Hkv, D), 1)
    v = _rand((B, S, Hkv, D), 2)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True,
                                interpret=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (mha_attention(q, k, v, causal=True) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("window", [1, 100, 128, 300, 4096])
def test_flash_window_matches_the_masked_einsum(window):
    """The forward kernel with a lower bound: token t attends to
    ``t - window < j <= t``, for windows under a block, of a block, over
    several and over the whole sequence, with GQA; against the XLA
    einsum with the same mask, which itself is checked by hand."""
    B, S, H, Hkv, D = 1, 512, 4, 2, 64
    q = _rand((B, S, H, D), 0)
    k = _rand((B, S, Hkv, D), 1)
    v = _rand((B, S, Hkv, D), 2)
    ref = mha_attention(q, k, v, causal=True, window=window)
    out = flash_attention(q, k, v, causal=True, window=window,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # The einsum's mask by hand, at one query row.
    t = 400
    lo = max(0, t + 1 - window)
    s = np.einsum("d,td->t", np.asarray(q)[0, t, 3],
                  np.asarray(k)[0, lo:t + 1, 1]) * D ** -0.5
    p = np.exp(s - s.max())
    want = (p / p.sum()) @ np.asarray(v)[0, lo:t + 1, 1]
    np.testing.assert_allclose(np.asarray(ref)[0, t, 3], want, atol=2e-5,
                               rtol=2e-5)


def test_flash_window_none_is_todays_kernel_and_a_window_has_no_grad():
    """``window=None`` traces the program it always did; under
    ``jax.grad`` a window raises by name instead of giving the causal
    kernels' gradient."""
    B, S, H, D = 1, 256, 2, 64
    q, k, v = (_rand((B, S, H, D), i) for i in range(3))

    def flash(window, **named):
        return lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=True, **named,
            **({"window": window} if window != "unnamed" else {}))

    assert str(jax.make_jaxpr(flash("unnamed"))(q, k, v)) == \
        str(jax.make_jaxpr(flash(None))(q, k, v))
    np.testing.assert_array_equal(
        np.asarray(flash(None)(q, k, v)), np.asarray(flash("unnamed")(q, k, v)))
    with pytest.raises(NotImplementedError, match="no backward kernel"):
        jax.grad(lambda q: flash(64)(q, k, v).sum())(q)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=64, interpret=True)
    with pytest.raises(ValueError, match="causal"):
        mha_attention(q, k, v, causal=False, window=64)


@pytest.mark.parametrize("S", [128, 512])
def test_flash_forward_takes_a_v_narrower_than_q_and_k(S):
    """Latent attention rebuilt: q and k 192 wide, v 128, the scale
    192 ** -0.5; the output is v's width. Against the XLA einsum. The
    backward kernels take one width: a gradient raises by name."""
    B, H = 1, 4
    q, k = _rand((B, S, H, 192), 0), _rand((B, S, H, 192), 1)
    v = _rand((B, S, H, 128), 2)
    ref = mha_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    assert out.shape == (B, S, H, 128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    with pytest.raises(NotImplementedError, match="narrower than q and k"):
        jax.grad(lambda q: flash_attention(
            q, k, v, causal=True, interpret=True).sum())(q)
