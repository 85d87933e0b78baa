"""Two kernels at shapes no configuration had before PR 73: the flash
forward at heads of 64 on grouped KV heads, and the few-rows grouped
matmul over 64 groups of width 1,536 (12 lane tiles, no whole number of
1,024). Interpreted against their XLA paths."""

import importlib

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops import grouped_matmul as gm  # noqa: E402
from ray_tpu.ops.attention import mha_attention  # noqa: E402

# ray_tpu.ops re-exports the function under the module's own name.
fa = importlib.import_module("ray_tpu.ops.flash_attention")


@pytest.mark.parametrize("seq,heads,kv_heads,dtype,tol", [
    (256, 32, 8, "float32", 2e-5), (512, 32, 8, "bfloat16", 2e-2),
    (384, 8, 2, "float32", 2e-5)])
def test_flash_forward_at_heads_of_64_matches_the_einsum(seq, heads,
                                                         kv_heads, dtype,
                                                         tol):
    rng = np.random.RandomState(seq)
    q = jnp.asarray(rng.randn(1, seq, heads, 64), dtype)
    k = jnp.asarray(rng.randn(1, seq, kv_heads, 64), dtype)
    v = jnp.asarray(rng.randn(1, seq, kv_heads, 64), dtype)
    got = fa.flash_attention(q, k, v, causal=True, interpret=True)
    want = mha_attention(q, k, v, causal=True)
    assert got.shape == want.shape == (1, seq, heads, 64)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)


def test_flash_blocks_at_heads_of_64_are_reckoned_at_128_lanes(monkeypatch):
    """A head of 64 fills half a lane tile in VMEM and is counted as a
    whole one: the choice of blocks and of the streamed form at 64 is
    that of 128, bucket for bucket, and the kernel runs each of the
    cell's buckets (never the einsum)."""
    for bucket in (4096, 8192, 16384):
        assert (fa.choose_blocks(bucket, bucket, 64, 64, 4, 2)
                == fa.choose_blocks(bucket, bucket, 128, 128, 4, 2))
    assert fa.choose_blocks(8192, 8192, 64, 64, 4, 2).streamed is False
    assert fa.choose_blocks(16384, 16384, 64, 64, 4, 2).streamed is True
    assert fa._vmem_bytes(512, 512, 8192, 0, 4, 64, 64, 2) == fa._vmem_bytes(
        512, 512, 8192, 0, 4, 128, 128, 2)
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    assert [fa.forward_path(s, s, 64, 64, 32, 8, 2)
            for s in (4096, 8192, 16384)] == [
                "resident", "resident", "streamed"]


@pytest.mark.parametrize("rows", [16, 32, 64])
def test_grouped_matmul_over_64_groups_of_width_1536(rows):
    """A decode step's 4 to 16 streams x top-4 rows: the kernel's tiles
    take the whole width both ways, and its two calls equal
    ``ragged_dot``'s."""
    K, F, G = 256, 1536, 64
    assert gm.row_tile(rows) == rows
    assert gm.choose_block_n(2048, F, 2, 2) == F
    assert gm.choose_block_n(F, 2048, 1, 2) == 2048
    rng = np.random.RandomState(rows)
    x = jnp.asarray(rng.randn(rows, K), jnp.float32)
    w_gate = jnp.asarray(rng.randn(G, K, F) * K ** -0.5, jnp.float32)
    w_up = jnp.asarray(rng.randn(G, K, F) * K ** -0.5, jnp.float32)
    w_down = jnp.asarray(rng.randn(G, F, K) * F ** -0.5, jnp.float32)
    sizes = jnp.asarray(np.bincount(rng.randint(0, G, rows), minlength=G),
                        jnp.int32)

    def act(g, u):
        return jax.nn.silu(g) * u

    h = gm.small_rows_grouped_matmul(x, (w_gate, w_up), sizes, None, act,
                                     None, True)
    got = gm.small_rows_grouped_matmul(h, (w_down,), sizes, None, None, None,
                                       True)
    want = gm.ragged_grouped_matmul(
        gm.ragged_grouped_matmul(x, (w_gate, w_up), sizes, act), (w_down,),
        sizes)
    assert h.shape == (rows, F) and got.shape == (rows, K)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4,
                               rtol=2e-4)


def test_grouped_path_takes_the_kernel_for_these_rows(monkeypatch):
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    assert [gm.grouped_path(rows, 64) for rows in (16, 32, 64, 4096)] == [
        "small_rows"] * 4
    # Past 64 rows an expert, and rows no tile divides: ragged_dot.
    assert gm.grouped_path(8192, 64) == "ragged_dot"
    assert gm.grouped_path(24, 64) == "ragged_dot"
