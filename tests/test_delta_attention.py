"""The gated delta rule with a decay a channel (``ray_tpu/ops/
delta_attention.py``): both programs, each on its XLA path and as its
Pallas kernel interpreted, against the recurrence written out a token at
a time. Decays at both ends of what a configuration's gates give (a
channel that remembers a thousand tokens, one that forgets inside one),
a chunk in which the naive ``K / G`` would overflow float32, keys that
repeat (an inverse that no series of powers could give), a bucket's
padding, idle slots. CPU, float32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import delta_attention as da

HI = jax.lax.Precision.HIGHEST


def token_by_token(q, k, v, log_a, beta, state=None):
    """The module docstring's three lines, a token at a time. q, k, v,
    log_a [S, H, d]; beta [S, H]. Returns (outputs, last state)."""
    S, H, d = q.shape
    state = jnp.zeros((H, d, d), jnp.float32) if state is None else state

    def step(s, xs):
        qt, kt, vt, la, bt = xs
        decayed = jnp.exp(la)[..., None] * s
        seen = jnp.einsum("hk,hkv->hv", kt, decayed, precision=HI)
        s = decayed + kt[..., None] * (bt[:, None] * (vt - seen))[:, None, :]
        return s, jnp.einsum("hk,hkv->hv", qt, s, precision=HI)

    state, out = jax.lax.scan(step, state, (q, k, v, log_a, beta))
    return out, state


def inputs(seed, S, H, d, strength, spread=1.0):
    """Unit keys, queries of length d ** -0.5, log decays ``-strength *
    exp(spread * normal)`` a channel a token, beta in (0, 1)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (S, H, d))
    k = jax.random.normal(ks[1], (S, H, d))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (S, H, d))
    log_a = -strength * jnp.exp(spread * jax.random.normal(ks[3], (S, H, d)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (S, H)))
    return q, k, v, log_a, beta


def worst(got, want):
    return max(float(jnp.abs(g - w).max()) for g, w in zip(got, want))


PREFILLS = {"xla": jax.jit(da.xla_delta_prefill),
            "kernel": lambda *a: da.delta_scan(*a, interpret=True)}


@pytest.mark.parametrize("strength", [1e-3, 0.5, 30.0],
                         ids=["remembers-1000", "forgets-in-2",
                              "forgets-in-one"])
@pytest.mark.parametrize("path", sorted(PREFILLS))
def test_chunked_prefill_is_the_recurrence(path, strength):
    """Three chunks at a head width the kernel takes, decays from a
    thousandth a token to e^-30 and beyond a token: outputs and the last
    state within float32 rounding of the token loop."""
    args = inputs(2, 3 * da.CHUNK, 2, 128, strength)
    got = PREFILLS[path](*args)
    assert worst(got, token_by_token(*args)) < 5e-6


@pytest.mark.parametrize("path", sorted(PREFILLS))
def test_a_chunk_whose_naive_factors_overflow(path):
    """One token decays every channel by e^-120 in the middle of a
    chunk, and the tokens behind it hardly at all: ``exp(-g_i)`` for a
    key after it is e^+120, over float32's range, and ``exp(g_t)`` for
    every later query 0, so the product of the two sides referred to the
    chunk's start is inf x 0. Referred to the levels' midpoints every
    factor is at most 1 and the pairs behind the token come out as the
    recurrence gives them."""
    q, k, v, log_a, beta = inputs(3, 2 * da.CHUNK, 2, 128, 1e-3)
    log_a = log_a.at[da.CHUNK // 2 + 3].set(-120.0)
    log_a = log_a.at[da.CHUNK + 1, :, ::2].set(-95.0)
    naive = jnp.exp(-jnp.cumsum(log_a[:da.CHUNK], axis=0))
    assert not bool(jnp.isfinite(naive).all())
    got = PREFILLS[path](q, k, v, log_a, beta)
    want = token_by_token(q, k, v, log_a, beta)
    assert bool(jnp.isfinite(got[0]).all())
    assert worst(got, want) < 3e-5         # on states of size ~1
    # The tokens behind the strong one still see each other.
    assert float(jnp.abs(want[0][da.CHUNK // 2 + 10:]).max()) > 0.05


@pytest.mark.parametrize("path", sorted(PREFILLS))
def test_repeated_keys_invert_exactly(path):
    """Every key the same and beta 1: ``A`` is all ones below the
    diagonal, whose powers grow as binomials (a Neumann series would
    cancel 1e18 against 1e18); the inverse built from its halves is the
    bidiagonal one."""
    q, k, v, log_a, _ = inputs(4, 2 * da.CHUNK, 1, 128, 1e-3)
    k = jnp.broadcast_to(k[:1], k.shape)
    beta = jnp.ones(k.shape[:2])
    got = PREFILLS[path](q, k, v, log_a, beta)
    assert worst(got, token_by_token(q, k, v, log_a, beta)) < 2e-5


@pytest.mark.parametrize("path", sorted(PREFILLS))
def test_padding_leaves_the_state_exactly_as_it_was(path):
    """Tokens behind the real ones with no decay and no write: the state
    is bit for bit the state after the last real token, whether the
    padding fills the real tokens' chunk or adds chunks, and the real
    tokens' outputs do not move."""
    real = da.CHUNK + 17
    q, k, v, log_a, beta = inputs(5, 3 * da.CHUNK, 2, 128, 0.05)
    mask = jnp.arange(3 * da.CHUNK) < real
    log_a = jnp.where(mask[:, None, None], log_a, 0.0)
    beta = jnp.where(mask[:, None], beta, 0.0)
    out, state = PREFILLS[path](q, k, v, log_a, beta)
    short = 2 * da.CHUNK
    out2, state2 = PREFILLS[path](q[:short], k[:short], v[:short],
                                  log_a[:short], beta[:short])
    np.testing.assert_array_equal(np.asarray(state), np.asarray(state2))
    np.testing.assert_array_equal(np.asarray(out[:real]),
                                  np.asarray(out2[:real]))
    want, want_state = token_by_token(q[:real], k[:real], v[:real],
                                      log_a[:real], beta[:real])
    assert worst((out[:real], state), (want, want_state)) < 5e-6


def test_any_length_and_width_on_the_xla_path():
    """Heads of 16 and a length that is no multiple of the chunk: the
    XLA path pads to whole chunks itself."""
    args = inputs(6, 200, 3, 16, 0.1)
    assert worst(jax.jit(da.xla_delta_prefill)(*args),
                 token_by_token(*args)) < 5e-6


DECODES = {"xla": da.xla_delta_decode,
           "kernel": lambda *a: da.delta_step(*a, interpret=True)}


@pytest.mark.parametrize("active", [
    [False, True, False, True, False], [True] * 5, [False] * 5,
    [False, False, True, False, False], [True, False, False, False, True]],
    ids=["two", "all", "none", "middle", "ends"])
@pytest.mark.parametrize("path", sorted(DECODES))
def test_decode_step_moves_the_active_slots_alone(path, active):
    """One token a slot at layer 1 of 3, 32 heads of 128: an active
    slot's state is the recurrence's next and its read-out the
    recurrence's, an idle slot's state and the other layers are bit for
    bit what they were."""
    B, H, d = 5, 32, 128
    q, k, v, log_a, beta = inputs(7, B, H, d, 0.5)
    pool = jax.random.normal(jax.random.PRNGKey(9), (3, B, H, d, d))
    active = jnp.asarray(active)
    out, new = DECODES[path](q, k, v, log_a, beta, pool, jnp.int32(1), active)
    for b in range(B):
        if not active[b]:
            np.testing.assert_array_equal(np.asarray(new[1, b]),
                                          np.asarray(pool[1, b]))
            continue
        want, state = token_by_token(q[b:b + 1], k[b:b + 1], v[b:b + 1],
                                     log_a[b:b + 1], beta[b:b + 1],
                                     pool[1, b])
        assert worst((out[b], new[1, b]), (want[0], state)) < 2e-5
    for layer in (0, 2):
        np.testing.assert_array_equal(np.asarray(new[layer]),
                                      np.asarray(pool[layer]))


def test_prefill_then_decode_continue_one_recurrence():
    """A prompt through the chunked prefill, its state laid into a slot,
    then the decode step a token at a time: the outputs are those of the
    token loop over the whole sequence."""
    S, more, H, d = 2 * da.CHUNK, 5, 32, 128
    q, k, v, log_a, beta = inputs(8, S + more, H, d, 0.02)
    want, _ = token_by_token(q, k, v, log_a, beta)
    out, state = da.delta_scan(q[:S], k[:S], v[:S], log_a[:S], beta[:S],
                               interpret=True)
    assert float(jnp.abs(out - want[:S]).max()) < 5e-6
    pool = jnp.zeros((1, 2, H, d, d)).at[0, 1].set(state)
    active = jnp.asarray([False, True])
    for t in range(S, S + more):
        step = [jnp.stack([jnp.zeros_like(x[t]), x[t]])
                for x in (q, k, v, log_a, beta)]
        out, pool = da.delta_step(*step, pool, jnp.int32(0), active,
                                  interpret=True)
        assert float(jnp.abs(out[1] - want[t]).max()) < 5e-6


def test_the_path_follows_platform_and_shape(monkeypatch):
    import importlib

    flash = importlib.import_module("ray_tpu.ops.flash_attention")
    assert da.delta_path(128, 32, 4096) == "xla"          # the CPU
    monkeypatch.setattr(flash, "_on_tpu", lambda: True)
    assert da.delta_path(128, 32, 4096) == "delta_kernel"
    assert da.delta_path(128, 64) == "delta_kernel"
    assert da.delta_path(64, 32) == "xla"                 # the head's width
    assert da.delta_path(128, 4) == "xla"                 # columns of 4 x 4
    assert da.delta_path(128, 32, 100) == "xla"           # no whole chunks
    with pytest.raises(ValueError, match="power of two"):
        da._levels(48)
