"""ops/grouped_matmul.py: the kernel for few rows a group (interpret
mode on the CPU) against ``jax.lax.ragged_dot`` followed by today's
activation and against a float64 loop a row; the rule that chooses it;
what ``moe_ffn`` traces here."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops import grouped_matmul as gm  # noqa: E402
from ray_tpu.parallel.moe import moe_ffn  # noqa: E402

K = 64
TILE = 16        # of the walks made by hand below


def _gated(h, g):
    """``moe_ffn``'s activation of gated experts, as it was before the
    kernel: silu of the gate rounded before the product."""
    return jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * h


def _case(sizes, m, N, seed=0, dtype=jnp.bfloat16):
    rng = np.random.RandomState(seed)
    G = len(sizes)
    normal = lambda *shape: jnp.asarray(  # noqa: E731
        rng.randn(*shape) * shape[-2] ** -0.5, dtype)
    return (jnp.asarray(rng.randn(m, K), dtype), normal(G, K, N),
            normal(G, K, N), jnp.asarray(sizes, jnp.int32))


def _per_row(x, w_up, w_gate, sizes):
    """float64, one row at a time; rows behind the last group zero."""
    x, w_up, w_gate = (np.asarray(a, np.float64) for a in (x, w_up, w_gate))
    out = np.zeros((x.shape[0], w_up.shape[2]))
    group = np.repeat(np.arange(len(sizes)), sizes)
    for r, g in enumerate(group):
        gate = x[r] @ w_gate[g]
        out[r] = gate / (1 + np.exp(-gate)) * (x[r] @ w_up[g])
    return out


def _stack(layers, experts, layer, sizes):
    """A ``[L * E]`` stack's groups with ``layer``'s alone non-empty."""
    out = np.zeros((layers, experts), np.int64)
    out[layer, :len(sizes)] = sizes
    return list(out.reshape(-1))


# (group sizes, rows, expert width)
CASES = {
    "stack-every-other-layer-empty": (_stack(4, 8, 2, [3, 0, 5, 1, 0, 7]),
                                      16, 128),
    "groups-of-0-1-2-40": ([0, 1, 2, 40, 0, 5], 48, 128),
    "a-group-straddles-a-row-tile": ([10, 12, 10], 48, 128),
    "rows-behind-the-last-group": ([2, 3, 0, 1], 48, 128),
    "width-768-by-8": ([4, 0, 9, 3], 16, 96),
    "width-1024-by-8": ([4, 0, 9, 3], 16, 128),
    "width-2048-by-8": ([4, 0, 9, 3], 16, 256),
}


@pytest.mark.parametrize("sizes,m,N", CASES.values(), ids=CASES.keys())
def test_the_kernel_equals_ragged_dot_and_a_loop_a_row(sizes, m, N):
    x, w_up, w_gate, group_sizes = _case(sizes, m, N)
    routed = sum(sizes)
    got = gm.small_rows_grouped_matmul(
        x, (w_up, w_gate), group_sizes, None, _gated, None, True)
    want = gm.ragged_grouped_matmul(x, (w_up, w_gate), group_sizes, _gated)
    assert got.shape == (m, N) and got.dtype == jnp.bfloat16
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    # The same products, rounded where an array is bfloat16; between
    # them the kernel keeps the activation in float32, as XLA's fusion
    # does on the TPU and not on the CPU: one bfloat16 step at the most.
    np.testing.assert_allclose(got[:routed], want[:routed], atol=1e-3,
                               rtol=2 ** -7)
    assert not got[routed:].any()
    np.testing.assert_allclose(
        got, _per_row(x, w_up, w_gate, sizes), atol=0.03, rtol=0.03)
    # In float32 the loop is met closely: the kernel's sums are whole.
    x, w_up, w_gate, _ = _case(sizes, m, N, dtype=jnp.float32)
    exact = gm.small_rows_grouped_matmul(
        x, (w_up, w_gate), group_sizes, None, _gated, None, True)
    np.testing.assert_allclose(np.asarray(exact), _per_row(
        x, w_up, w_gate, sizes), atol=2e-5, rtol=2e-5)
    # One stack and no epilogue (the down projection), column blocks of
    # their own: ragged_dot itself.
    down = gm.small_rows_grouped_matmul(
        x, (w_up,), group_sizes, None, None,
        N // 2 if N % 256 == 0 else N, True)
    np.testing.assert_allclose(
        np.asarray(down)[:routed],
        np.asarray(jax.lax.ragged_dot(x, w_up, group_sizes))[:routed],
        atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("sizes,m", [
    ([0, 1, 2, 40, 0, 5], 48), ([10, 12, 10], 32), ([0, 0, 0], 32),
    (_stack(3, 4, 1, [16, 0, 17, 1]), 64), ([64], 64)])
def test_the_walk_visits_each_group_in_each_tile_it_has_a_row_in(sizes, m):
    """The scalar kernel's walk against the same walk by hand: a visit
    for every (row tile, group) pair that shares a row, in row order;
    none for an empty group or a tile behind the last."""
    walk = [np.asarray(a) for a in gm.visits(
        jnp.asarray(sizes, jnp.int32), m, TILE, interpret=True)]
    ends = np.cumsum(sizes)
    by_hand = [(g, t, ends[g] - sizes[g], ends[g])
               for g in range(len(sizes)) if sizes[g]
               for t in range((ends[g] - sizes[g]) // TILE,
                              (ends[g] - 1) // TILE + 1)]
    n = len(by_hand)
    assert list(walk[4]) == [n, sum(sizes)]
    assert len(walk[0]) == m // TILE + min(len(sizes), m) - 1 >= n
    assert list(zip(*(a[:n] for a in walk[:4]))) == by_hand


def test_a_gradient_through_the_kernel_is_ragged_dots():
    """The kernel has no transpose rule: its ``custom_vjp`` hands the
    cotangent to the XLA path's own backward."""
    x, w_up, w_gate, group_sizes = _case([3, 0, 9, 4], 16, 128,
                                         dtype=jnp.float32)

    def loss(path):
        return lambda x, w_up, w_gate: (
            path(x, (w_up, w_gate)) ** 2).sum()

    kernel = loss(lambda x, w: gm.small_rows_grouped_matmul(
        x, w, group_sizes, None, _gated, None, True))
    plain = loss(lambda x, w: gm.ragged_grouped_matmul(
        x, w, group_sizes, _gated))
    got = jax.grad(kernel, argnums=(0, 1, 2))(x, w_up, w_gate)
    want = jax.grad(plain, argnums=(0, 1, 2))(x, w_up, w_gate)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


class _Mesh:
    def __init__(self, size):
        self.size = size


@pytest.mark.parametrize("on_tpu,rows,experts,mesh,path", [
    # The three cells' decode steps: 32 slots x 8 experts a token.
    (True, 256, 64, None, "small_rows"),
    (True, 256, 128, None, "small_rows"),
    (True, 256, 256, None, "small_rows"),
    (True, 256, 64, _Mesh(1), "small_rows"),
    # c16's shortest prefill buckets; a median prompt's bucket is not.
    (True, 8 * 32, 64, None, "small_rows"),
    (True, 8 * 2048, 64, None, "ragged_dot"),
    (True, 8 * 8192, 256, None, "ragged_dot"),
    # Past what was measured by one row tile, by rows an expert and by
    # rows; rows that are no whole tiles.
    (True, gm.ROWS_PER_EXPERT * 32 + 128, 32, None, "ragged_dot"),
    (True, gm.MAX_ROWS, 256, None, "small_rows"),
    (True, gm.MAX_ROWS + 128, 256, None, "ragged_dot"),
    (True, 8 * 5, 64, None, "ragged_dot"),
    # Any mesh of more than one device (ep > 1 among them); any CPU.
    (True, 256, 64, _Mesh(2), "ragged_dot"),
    (False, 256, 64, None, "ragged_dot"),
])
def test_the_rule_that_chooses_the_kernel(monkeypatch, on_tpu, rows, experts,
                                          mesh, path):
    import importlib

    flash_mod = importlib.import_module("ray_tpu.ops.flash_attention")
    monkeypatch.setattr(flash_mod, "_on_tpu", lambda: on_tpu)
    assert gm.grouped_path(rows, experts, mesh) == path


def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


def test_moe_ffn_traces_ragged_dot_here_and_the_kernel_as_a_tpu(monkeypatch):
    """On the CPU, three ``ragged_dot`` and no kernel, as ever; steered
    to the TPU's side of the rule, the same call at a decode step's
    shape traces two kernel calls (and one walk) and no
    ``ragged_dot``, a mesh of two devices or a long prefill three
    ``ragged_dot`` again."""
    import importlib

    E, k, M, F = 8, 2, 16, 128
    rng = np.random.RandomState(0)
    normal = lambda *shape: jnp.asarray(rng.randn(*shape) * 0.3,  # noqa: E731
                                        jnp.bfloat16)
    router, w_up, w_gate, w_down = (
        normal(M, E).astype(jnp.float32), normal(2, E, M, F),
        normal(2, E, M, F), normal(2, E, F, M))

    def names(tokens, **kwargs):
        x = normal(tokens, 1, M)
        return list(_primitives(jax.make_jaxpr(lambda x: moe_ffn(
            x, router, w_up, w_down, k=k, w_gate=w_gate,
            token_mask=jnp.ones((tokens, 1), bool),
            layer=jnp.int32(1), **kwargs))(x).jaxpr))

    here = names(16)
    assert here.count("ragged_dot_general") + here.count("ragged_dot") == 3
    assert "pallas_call" not in here
    flash_mod = importlib.import_module("ray_tpu.ops.flash_attention")
    monkeypatch.setattr(flash_mod, "_on_tpu", lambda: True)
    steered = names(16)
    assert steered.count("pallas_call") == 3      # the walk, two matmuls
    assert not any(n.startswith("ragged_dot") for n in steered)
    for other in (names(16, mesh=_Mesh(2)), names(16 * 64)):
        assert sum(n.startswith("ragged_dot") for n in other) == 3
        assert "pallas_call" not in other
