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
