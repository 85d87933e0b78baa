"""Pipeline parallelism + MoE tests on the virtual 8-device CPU mesh."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.parallel import make_mesh  # noqa: E402
from ray_tpu.parallel.pipeline import pipeline_apply  # noqa: E402
from ray_tpu.parallel.moe import moe_ffn  # noqa: E402


def _require_8():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")


def test_pipeline_matches_sequential():
    _require_8()
    mesh = make_mesh(dp=1, pp=4)
    n_stages, B, D = 4, 8, 16
    rng = np.random.RandomState(0)
    # Each stage: x @ W + b, tanh.
    Ws = jnp.asarray(rng.randn(n_stages, D, D) * 0.1, dtype=jnp.float32)
    bs = jnp.asarray(rng.randn(n_stages, D) * 0.1, dtype=jnp.float32)
    x = jnp.asarray(rng.randn(B, D), dtype=jnp.float32)

    def stage_fn(params, h):
        W, b = params
        return jnp.tanh(h @ W + b)

    expected = x
    for i in range(n_stages):
        expected = stage_fn((Ws[i], bs[i]), expected)

    got = pipeline_apply(
        stage_fn, (Ws, bs), x, mesh, n_microbatches=4
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=1e-5, rtol=1e-5)


def test_pipeline_grad_flows():
    _require_8()
    mesh = make_mesh(dp=1, pp=4)
    n_stages, B, D = 4, 4, 8
    rng = np.random.RandomState(1)
    Ws = jnp.asarray(rng.randn(n_stages, D, D) * 0.1, dtype=jnp.float32)
    bs = jnp.zeros((n_stages, D), dtype=jnp.float32)
    x = jnp.asarray(rng.randn(B, D), dtype=jnp.float32)

    def stage_fn(params, h):
        W, b = params
        return jnp.tanh(h @ W + b)

    def loss(params):
        out = pipeline_apply(stage_fn, params, x, mesh, n_microbatches=2)
        return (out ** 2).mean()

    g = jax.grad(loss)((Ws, bs))
    assert np.isfinite(np.asarray(g[0])).all()
    assert float(jnp.abs(g[0]).sum()) > 0


def test_moe_ffn_runs_and_differentiates():
    B, S, M, E, F = 2, 8, 16, 4, 32
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(B, S, M) * 0.1, dtype=jnp.float32)
    router_w = jnp.asarray(rng.randn(M, E) * 0.1, dtype=jnp.float32)
    w_in = jnp.asarray(rng.randn(E, M, F) * 0.1, dtype=jnp.float32)
    w_gate = jnp.asarray(rng.randn(E, M, F) * 0.1, dtype=jnp.float32)
    w_out = jnp.asarray(rng.randn(E, F, M) * 0.1, dtype=jnp.float32)

    def loss(ws):
        out, aux, _ = moe_ffn(x, ws[0], ws[1], ws[3], k=2, w_gate=ws[2])
        return (out ** 2).mean() + 0.01 * aux

    val, g = jax.value_and_grad(loss)((router_w, w_in, w_gate, w_out))
    assert np.isfinite(float(val))
    for gi in g:
        assert np.isfinite(np.asarray(gi)).all()


def test_moe_sharded_on_mesh():
    _require_8()
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_mesh(dp=2, ep=4)
    B, S, M, E, F = 4, 8, 16, 4, 32
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(B, S, M) * 0.1, dtype=jnp.float32)
    router_w = jnp.asarray(rng.randn(M, E) * 0.1, dtype=jnp.float32)
    w_in = jnp.asarray(rng.randn(E, M, F) * 0.1, dtype=jnp.float32)
    w_out = jnp.asarray(rng.randn(E, F, M) * 0.1, dtype=jnp.float32)
    expected, _, _ = moe_ffn(x, router_w, w_in, w_out, k=1)

    with jax.set_mesh(mesh) if hasattr(jax, "set_mesh") else mesh:
        xs = jax.device_put(x, NamedSharding(mesh, P("dp")))
        wi = jax.device_put(w_in, NamedSharding(mesh, P("ep")))
        wo = jax.device_put(w_out, NamedSharding(mesh, P("ep")))

        @jax.jit
        def f(x, rw, wi, wo):
            out, aux, _ = moe_ffn(x, rw, wi, wo, k=1)
            return out

        got = f(xs, router_w, wi, wo)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=1e-5, rtol=1e-5)
