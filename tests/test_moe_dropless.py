"""parallel/moe.py: dropless sorted/grouped dispatch against a per-token
Python-loop float32 reference. CPU, seeded, small."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.parallel.moe import moe_ffn  # noqa: E402

M, F = 16, 24
# (experts, experts a token, tokens): fewer tokens than experts; k * T
# no multiple of E; OLMoE's 64 top-8 at a decode batch; one expert only.
CASES = [(8, 2, 5), (4, 3, 7), (64, 8, 6), (4, 1, 16)]
IDS = ["T-lt-E", "kT-not-multiple-of-E", "64-top-8", "top-1"]


def _weights(E, seed):
    """(router [M, E], w_up, w_gate [E, M, F], w_down [E, F, M])."""
    rng = np.random.RandomState(seed)

    def normal(*shape, scale=0.3):
        return jnp.asarray(rng.randn(*shape) * scale, jnp.float32)

    return (normal(M, E, scale=1.0), normal(E, M, F), normal(E, M, F),
            normal(E, F, M))


def _tokens(T, seed):
    return jnp.asarray(np.random.RandomState(seed).randn(1, T, M) * 0.7,
                       jnp.float32)


def _choices(x, router, k):
    """Each token's top-k experts, by NumPy."""
    logits = np.asarray(x, np.float64).reshape(-1, M) @ np.asarray(
        router, np.float64)
    return np.argsort(-logits, axis=-1, kind="stable")[:, :k]


def _per_token(x, router, w_up, w_gate, w_down, choices, mask=None):
    """For each token, for each expert it chose: its softmax gate (not
    renormalised) times down(silu(gate(x)) * up(x)). A Python loop over
    tokens in float32 jnp, so it differentiates."""
    xt = x.reshape(-1, M)
    probs = jax.nn.softmax(xt @ router, axis=-1)
    rows = []
    for t in range(xt.shape[0]):
        total = jnp.zeros((M,), jnp.float32)
        if mask is None or mask[t]:
            for e in choices[t]:
                h = jax.nn.silu(xt[t] @ w_gate[e]) * (xt[t] @ w_up[e])
                total = total + probs[t, e] * (h @ w_down[e])
        rows.append(total)
    return jnp.stack(rows).reshape(x.shape)


@pytest.mark.parametrize("E,k,T", CASES, ids=IDS)
def test_values_equal_the_per_token_loop(E, k, T):
    x = _tokens(T, 1)
    router, w_up, w_gate, w_down = _weights(E, 2)
    out, aux, load = jax.jit(lambda *a: moe_ffn(
        a[0], a[1], a[2], a[4], k=k, w_gate=a[3]))(
            x, router, w_up, w_gate, w_down)
    choices = _choices(x, router, k)
    expected = _per_token(x, router, w_up, w_gate, w_down, choices)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-6, rtol=1e-5)
    # The load counter is a NumPy count of the choices: nothing dropped.
    np.testing.assert_array_equal(
        np.asarray(load), np.bincount(choices.ravel(), minlength=E))
    assert int(load.sum()) == k * T and load.dtype == jnp.int32
    # Switch load-balancing term: E * sum_e share_e * mean prob_e.
    probs = np.asarray(jax.nn.softmax(x.reshape(-1, M) @ router, -1))
    share = np.bincount(choices.ravel(), minlength=E) / (k * T)
    assert float(aux) == pytest.approx(E * (share * probs.mean(0)).sum(),
                                       rel=1e-5)


@pytest.mark.parametrize("E,k,T", CASES, ids=IDS)
def test_gradients_equal_the_per_token_loops(E, k, T):
    x = _tokens(T, 3)
    weights = _weights(E, 4)
    choices = _choices(x, weights[0], k)

    def ours(x, router, w_up, w_gate, w_down):
        out, aux, _ = moe_ffn(x, router, w_up, w_down, k=k, w_gate=w_gate)
        return (out ** 2).sum() + 0.01 * aux

    def theirs(x, router, w_up, w_gate, w_down):
        out = _per_token(x, router, w_up, w_gate, w_down, choices)
        probs = jax.nn.softmax(x.reshape(-1, M) @ router, -1)
        share = np.bincount(choices.ravel(), minlength=E) / (k * T)
        return (out ** 2).sum() + 0.01 * E * (share * probs.mean(0)).sum()

    got = jax.jit(jax.grad(ours, argnums=(0, 1, 2, 3, 4)))(x, *weights)
    want = jax.grad(theirs, argnums=(0, 1, 2, 3, 4))(x, *weights)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=2e-5, rtol=1e-4)


def test_all_tokens_on_one_expert():
    """One group holds every row and the other experts are empty."""
    E, k, T = 4, 1, 9
    router, w_up, w_gate, w_down = _weights(E, 5)
    x = jnp.abs(_tokens(T, 6))
    router = jnp.zeros((M, E)).at[:, 2].set(5.0)   # positive x: expert 2
    out, _, load = moe_ffn(x, router, w_up, w_down, k=k, w_gate=w_gate)
    assert np.asarray(load).tolist() == [0, 0, T, 0]
    expected = _per_token(x, router, w_up, w_gate, w_down,
                          np.full((T, 1), 2))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-6, rtol=1e-5)


@pytest.mark.parametrize("E,k,T", CASES[:3], ids=IDS[:3])
def test_masked_rows_reach_no_expert_and_leave_the_rest_bit_equal(E, k, T):
    x = _tokens(T, 7)
    router, w_up, w_gate, w_down = _weights(E, 8)
    mask = np.ones(T, bool)
    mask[[0, T // 2]] = False
    run = jax.jit(lambda x, m: moe_ffn(x, router, w_up, w_down, k=k,
                                       w_gate=w_gate, token_mask=m))
    out, aux, load = run(x, jnp.asarray(mask)[None])
    full, _, full_load = run(x, jnp.ones((1, T), bool))
    out, full = np.asarray(out)[0], np.asarray(full)[0]
    assert (out[~mask] == 0).all()
    np.testing.assert_array_equal(out[mask], full[mask])
    choices = _choices(x, router, k)
    np.testing.assert_array_equal(
        np.asarray(load), np.bincount(choices[mask].ravel(), minlength=E))
    assert int(load.sum()) == k * mask.sum() < int(full_load.sum())
    assert np.isfinite(float(aux))
    # The masked rows' gradient is zero, the others' the unmasked run's.
    grad = jax.grad(lambda x, m: (run(x, m)[0] ** 2).sum())
    g = np.asarray(grad(x, jnp.asarray(mask)[None]))[0]
    g_full = np.asarray(grad(x, jnp.ones((1, T), bool)))[0]
    assert (g[~mask] == 0).all()
    np.testing.assert_allclose(g[mask], g_full[mask], atol=1e-6, rtol=1e-5)


def test_a_decode_step_reads_the_experts_of_its_live_slots_only():
    """32 slots of one token, 3 live: at most 3 * k experts are given a
    row, and an all-idle step gives none."""
    E, k, B = 64, 8, 32
    router, w_up, w_gate, w_down = _weights(E, 9)
    x = _tokens(B, 10).reshape(B, 1, M)
    active = np.zeros(B, bool)
    active[[1, 17, 30]] = True
    run = jax.jit(lambda a: moe_ffn(x, router, w_up, w_down, k=k,
                                    w_gate=w_gate, token_mask=a[:, None]))
    out, _, load = run(jnp.asarray(active))
    assert int(load.sum()) == 3 * k and int((load > 0).sum()) <= 3 * k
    assert (np.asarray(out)[~active] == 0).all()
    assert np.abs(np.asarray(out)[active]).min() > 0
    out, _, load = run(jnp.zeros(B, bool))
    assert int(load.sum()) == 0 and not np.asarray(out).any()


def test_ungated_experts_and_bfloat16():
    E, k, T = 8, 2, 12
    router, w_up, _, w_down = _weights(E, 11)
    x = _tokens(T, 12)
    out, _, _ = moe_ffn(x, router, w_up, w_down, k=k,
                        activation=jax.nn.gelu)
    probs = jax.nn.softmax(x.reshape(-1, M) @ router, -1)
    choices = _choices(x, router, k)
    want = np.stack([sum(
        np.asarray(probs[t, e] * (jax.nn.gelu(x[0, t] @ w_up[e]) @ w_down[e]))
        for e in choices[t]) for t in range(T)])
    np.testing.assert_allclose(np.asarray(out)[0], want, atol=2e-6, rtol=1e-5)
    half = [a.astype(jnp.bfloat16) for a in (x, w_up, w_down)]
    out16, _, load = moe_ffn(half[0], router, half[1], half[2], k=k,
                             activation=jax.nn.gelu)
    assert out16.dtype == jnp.bfloat16 and int(load.sum()) == k * T
    np.testing.assert_allclose(np.asarray(out16, np.float32)[0], want,
                               atol=0.05, rtol=0.05)


def test_nothing_has_a_capacity_axis():
    """No intermediate is larger than the k * T rows times the widest of
    hidden, expert width and experts: no [T, E, C] one-hot anywhere."""
    E, k, T = 64, 8, 128
    router, w_up, w_gate, w_down = _weights(E, 13)
    x = _tokens(T, 14)
    jaxpr = jax.make_jaxpr(lambda x: moe_ffn(
        x, router, w_up, w_down, k=k, w_gate=w_gate,
        token_mask=jnp.ones((1, T), bool)))(x)

    def sizes(jaxpr):
        for eqn in jaxpr.eqns:
            for var in eqn.outvars:
                yield int(np.prod(var.aval.shape))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from sizes(sub)

    assert max(sizes(jaxpr.jaxpr)) <= k * T * max(M, F, E)
    assert any(e.primitive.name.startswith("ragged_dot")
               for e in jaxpr.jaxpr.eqns)


# ---- the router's other arguments (PR 38) -----------------------------------

@pytest.mark.parametrize("E,k,T", CASES[:3], ids=IDS[:3])
def test_sigmoid_routing_with_a_selection_bias_equals_the_per_token_loop(
        E, k, T):
    """Sigmoid scores, the k experts of the largest score + bias, each
    gated by its score alone, renormalised and scaled: the same sorted
    grouped path as ever, against a per-token loop that selects and
    weights by NumPy. The bias is large enough to change who is chosen."""
    router, w_up, w_gate, w_down = _weights(E, 11)
    x = _tokens(T, 12)
    bias = jnp.asarray(np.random.RandomState(13).randn(E) * 0.5, jnp.float32)
    scale = 2.826
    out, _, expert_tokens = moe_ffn(
        x, router, w_up, w_down, k=k, w_gate=w_gate, score="sigmoid",
        select_bias=bias, renormalize=True, scale=scale)
    xt = np.asarray(x, np.float64).reshape(-1, M)
    scores = 1 / (1 + np.exp(-(xt @ np.asarray(router, np.float64))))
    chosen = np.argsort(-(scores + np.asarray(bias, np.float64)), axis=-1,
                        kind="stable")[:, :k]
    unbiased = np.argsort(-scores, axis=-1, kind="stable")[:, :k]
    assert (np.sort(chosen) != np.sort(unbiased)).any()
    rows = []
    for t in range(T):
        gates = scores[t, chosen[t]]
        gates = gates / gates.sum() * scale
        total = np.zeros(M)
        for gate, e in zip(gates, chosen[t]):
            a = xt[t] @ np.asarray(w_gate[e], np.float64)
            h = a / (1 + np.exp(-a)) * (xt[t] @ np.asarray(w_up[e], np.float64))
            total += gate * (h @ np.asarray(w_down[e], np.float64))
        rows.append(total)
    np.testing.assert_allclose(np.asarray(out)[0], np.stack(rows),
                               atol=2e-5, rtol=2e-5)
    assert list(np.asarray(expert_tokens)) == list(
        np.bincount(chosen.reshape(-1), minlength=E))


def test_the_default_routing_is_the_program_it_was():
    """Softmax, no bias, gates as they fall, scale 1: naming the
    defaults traces the same program as not naming them, so OLMoE's
    compiled programs do not change."""
    router, w_up, w_gate, w_down = _weights(8, 1)
    x = _tokens(5, 2)

    def plain(x):
        return moe_ffn(x, router, w_up, w_down, k=2, w_gate=w_gate)

    def named(x):
        return moe_ffn(x, router, w_up, w_down, k=2, w_gate=w_gate,
                       score="softmax", select_bias=None, renormalize=False,
                       scale=1.0)

    assert str(jax.make_jaxpr(plain)(x)) == str(jax.make_jaxpr(named)(x))
