"""Model tests: tiny configs, forward/loss/grad, sharded execution."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import (  # noqa: E402
    LlamaConfig,
    causal_lm_loss,
    forward,
    init_params,
    param_logical_axes,
    resnet18,
)


def test_llama_tiny_forward():
    cfg = LlamaConfig.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 256, (2, 16)))
    logits, aux = jax.jit(lambda p, t: forward(p, t, cfg))(params, tokens)
    assert logits.shape == (2, 16, 256)
    assert np.isfinite(np.asarray(logits)).all()


def test_llama_tiny_loss_and_grad():
    cfg = LlamaConfig.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 256, (2, 17)))
    loss, grads = jax.jit(
        jax.value_and_grad(lambda p: causal_lm_loss(p, tokens, cfg))
    )(params)
    assert np.isfinite(float(loss))
    flat = jax.tree.leaves(grads)
    assert all(np.isfinite(np.asarray(g)).all() for g in flat)
    assert float(loss) > 0


def test_llama_moe_tiny():
    cfg = LlamaConfig.tiny(moe=True)
    params = init_params(cfg, jax.random.PRNGKey(1))
    tokens = jnp.asarray(np.random.RandomState(1).randint(0, 256, (2, 9)))
    loss = jax.jit(lambda p: causal_lm_loss(p, tokens, cfg))(params)
    assert np.isfinite(float(loss))


def test_llama_causality():
    """Changing future tokens must not change past logits."""
    cfg = LlamaConfig.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(2)
    t1 = rng.randint(0, 256, (1, 12))
    t2 = t1.copy()
    t2[0, -1] = (t2[0, -1] + 7) % 256
    l1, _ = forward(params, jnp.asarray(t1), cfg)
    l2, _ = forward(params, jnp.asarray(t2), cfg)
    np.testing.assert_allclose(
        np.asarray(l1[0, :-1]), np.asarray(l2[0, :-1]), atol=1e-5
    )


def test_llama_sharded_matches_single_device():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    from ray_tpu.parallel import make_mesh
    from ray_tpu.parallel.sharding import shard_pytree

    cfg = LlamaConfig.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.asarray(np.random.RandomState(3).randint(0, 256, (4, 16)))
    expected, _ = forward(params, tokens, cfg)

    mesh = make_mesh(dp=2, sp=2, tp=2)
    sharded_params = shard_pytree(params, mesh, param_logical_axes(cfg))

    @jax.jit
    def f(p, t):
        return forward(p, t, cfg, mesh)[0]

    got = f(sharded_params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=3e-4, rtol=3e-4)


@pytest.mark.slow
def test_resnet18_forward_and_grad():
    import optax

    model = resnet18(num_classes=10, dtype=jnp.float32)
    rng = jax.random.PRNGKey(0)
    x = jnp.asarray(np.random.RandomState(0).rand(4, 32, 32, 3), jnp.float32)
    y = jnp.asarray([0, 1, 2, 3])
    variables = model.init(rng, x, train=True)

    def loss_fn(params):
        logits, updates = model.apply(
            {"params": params, "batch_stats": variables["batch_stats"]},
            x, train=True, mutable=["batch_stats"],
        )
        return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

    loss, g = jax.value_and_grad(loss_fn)(variables["params"])
    assert np.isfinite(float(loss))
    assert all(np.isfinite(np.asarray(l)).all() for l in jax.tree.leaves(g))


def test_rope_interleave_turns_adjacent_pairs():
    """A hand-made case: 4 dims, theta 100, position 3. Pair 0 turns by
    3 rad, pair 1 by 3 / 10 rad; interleaved the pairs are dims (0, 1)
    and (2, 3), by halves they are (0, 2) and (1, 3). Position 0 turns
    nothing."""
    import numpy as np

    from ray_tpu.models.llama import rope

    x = jnp.asarray([[[[1.0, 2.0, 3.0, 4.0]]] * 2])       # [1, 2, 1, 4]
    got = np.asarray(rope(x, jnp.asarray([0, 3]), 100.0, interleave=True))
    np.testing.assert_allclose(got[0, 0, 0], [1, 2, 3, 4], atol=1e-6)
    c0, s0, c1, s1 = np.cos(3.0), np.sin(3.0), np.cos(0.3), np.sin(0.3)
    np.testing.assert_allclose(
        got[0, 1, 0],
        [1 * c0 - 2 * s0, 1 * s0 + 2 * c0, 3 * c1 - 4 * s1, 3 * s1 + 4 * c1],
        rtol=1e-5)
    halves = np.asarray(rope(x, jnp.asarray([0, 3]), 100.0))
    np.testing.assert_allclose(
        halves[0, 1, 0],
        [1 * c0 - 3 * s0, 2 * c1 - 4 * s1, 1 * s0 + 3 * c0, 2 * s1 + 4 * c1],
        rtol=1e-5)


# ---- power retention (ops/retention.py) ------------------------------------


def _retention_attention_form(q, k, v, log_g):
    """The layer as a sum over the past, nothing kept: q [S, H, d]; k, v
    [S, Hkv, d]; log_g [S, Hkv]."""
    S, H, d = q.shape
    Hkv = k.shape[1]
    cum = jnp.cumsum(log_g, axis=0).T                      # [Hkv, S]
    s = jnp.einsum("tngd,jnd->ngtj", q.reshape(S, Hkv, H // Hkv, d), k,
                   precision="highest")
    past = jnp.tril(jnp.ones((S, S), bool))
    decay = jnp.where(past, jnp.exp(jnp.where(
        past, cum[:, :, None] - cum[:, None, :], 0.0)), 0.0)
    a = s * s / d * decay[:, None]
    out = jnp.einsum("ngtj,jnd->tngd", a, v, precision="highest")
    total = a.sum(-1).transpose(2, 0, 1)[..., None]
    return (out / (total + 1e-6)).reshape(S, H, d)


def _retention_inputs(gate_bias, S=96, H=4, Hkv=2, d=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (S, H, d))
    k = jax.random.normal(ks[1], (S, Hkv, d))
    v = jax.random.normal(ks[2], (S, Hkv, d))
    log_g = jax.nn.log_sigmoid(
        jax.random.normal(ks[3], (S, Hkv)) + jnp.asarray(gate_bias))
    return q, k, v, log_g


@pytest.mark.parametrize("gate_bias,tol", [
    (-6.0, 2e-3), (8.0, 2e-5), ((-3.0, 6.0), 2e-4)],
    ids=["gates-near-0", "gates-near-1", "mixed"])
def test_retention_chunked_recurrent_and_attention_forms_agree(gate_bias,
                                                               tol):
    """One layer three ways: the chunked scan (chunks of 32 and the
    sequence whole), the recurrence a token at a time through the
    decode step, and the sum over the past. With gates near 0 a token
    sees little but itself and the normaliser can be as small as its
    own (q.k)^2 / d: the quotient is then less well conditioned."""
    from ray_tpu.ops import retention

    q, k, v, log_g = _retention_inputs(gate_bias)
    want = np.asarray(_retention_attention_form(q, k, v, log_g))
    scale = tol / 2e-5 * np.abs(want).max()
    chunked, state = retention.xla_retention_prefill(q, k, v, log_g,
                                                     chunk=32)
    whole, state_whole = retention.xla_retention_prefill(q, k, v, log_g,
                                                         chunk=96)
    assert np.abs(np.asarray(chunked) - want).max() < 2e-5 * scale
    assert np.abs(np.asarray(whole) - want).max() < 2e-5 * scale
    assert np.allclose(state, state_whole, rtol=1e-4,
                       atol=1e-5 * float(jnp.abs(state).max()))
    # The recurrence, from an empty state in slot 1 of 2; slot 0 idle.
    pool = jnp.zeros(retention.state_shape(1, 2, 2, 16))
    active = jnp.asarray([False, True])
    step = jax.jit(retention.xla_retention_decode)
    rows = []
    for t in range(q.shape[0]):
        out, pool = step(*(jnp.stack([x[t] * 0, x[t]])
                           for x in (q, k, v, log_g)), pool, 0, active)
        rows.append(out[1])
    assert np.abs(np.stack(rows) - want).max() < 2e-5 * scale
    assert np.allclose(pool[0, 1], state, rtol=1e-4,
                       atol=1e-5 * float(jnp.abs(state).max()))
    assert not np.asarray(pool[0, 0]).any()


@pytest.mark.parametrize("d", [16, 128])
def test_the_symmetric_state_is_the_square_of_the_dot_product(d):
    """phi(x).phi(y) = (x.y)^2 / d against the full d^2 outer product,
    with every unordered pair held once: d/2 + 1 turns of d, where turn
    d/2 holds its d/2 pairs twice at half the weight."""
    from ray_tpu.ops import retention

    x, y = jax.random.normal(jax.random.PRNGKey(d), (2, 7, d))
    px, py = retention.phi(x), retention.phi(y)
    assert px.shape == (7, d // 2 + 1, d)
    got = jnp.einsum("nta,nta->n", px, py, precision="highest")
    full = jnp.einsum("na,nb,na,nb->n", x, x, y, y,
                      precision="highest") / d
    assert np.allclose(got, full, rtol=1e-4, atol=1e-6)
    assert np.allclose(got, jnp.einsum("na,na->n", x, y) ** 2 / d,
                       rtol=1e-4, atol=1e-6)
    # Every unordered pair (a, b) once: turn t pairs a with a - t.
    pairs = {}
    for t in range(d // 2 + 1):
        for a in range(d):
            pair = frozenset((a, (a - t) % d))
            pairs[pair] = pairs.get(pair, 0) + 1
    assert len(pairs) == d * (d + 1) // 2
    assert {n for p, n in pairs.items()
            if len(p) == 2 and max(p) - min(p) == d // 2} == {2}
    assert {n for p, n in pairs.items()
            if len(p) == 1 or max(p) - min(p) != d // 2} == {1}


def test_a_retention_stack_is_one_run_of_kind_state_and_inits_its_gate():
    from ray_tpu.models.llama import kv_layers, layer_runs

    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=48,
                      num_layers=3, num_heads=4, num_kv_heads=2, head_dim=8,
                      dtype=jnp.float32, layer_types=("state",) * 3,
                      qk_norm=True, qk_norm_per_head=True)
    (run,) = layer_runs(cfg)
    assert (run.n, run.kind, run.moe, run.kv_offset) == (3, "state", False, 0)
    assert kv_layers(cfg) == {"state": 3}
    layers = init_params(cfg, jax.random.PRNGKey(0))["layers"]
    assert layers["wg"].shape == (3, 32, 2) and layers["bg"].shape == (3, 2)
    # The gates remember 32 and 4,096 tokens: sigmoid(bg) = 1 - 1/tau.
    assert np.allclose(1 / (1 - jax.nn.sigmoid(layers["bg"][0])),
                       [32.0, 4096.0], rtol=1e-3)
    with pytest.raises(ValueError, match="power retention beside another"):
        layer_runs(LlamaConfig(num_layers=3,
                               layer_types=("state", "full", "state")))
