"""The page walk over a pool of heads of 64, two to a lane tile
(ops/paged_attention.py ``pool_row``): the Pallas kernel interpreted and
the XLA gather against a plain float32 attention over the unpacked
heads, the token's row through the aliased output, a window's ring
over such a pool, and heads of 128 left as they were."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops import paged_attention as pa  # noqa: E402

PAGE, D = 16, 64


def _unpacked(pool):
    """[rows, P, page, 128] -> [2 * rows, P, page, 64]: KV head 2j and
    2j + 1 are the two halves of row j."""
    rows, P, page, lanes = pool.shape
    return pool.reshape(rows, P, page, 2, D).transpose(0, 3, 1, 2, 4).reshape(
        2 * rows, P, page, D)


def _reference(q, ck, cv, table, lengths, window=None):
    """Plain float32 over one layer's unpacked pool [Hkv, P, page, 64]:
    position t lies in column ``(t // page) % columns`` of the slot's row
    of the table (a ring under a ``window``, which attends to the last
    ``window`` positions)."""
    q, ck, cv = (np.asarray(x, np.float32) for x in (q, ck, cv))
    B, H, _ = q.shape
    rep = H // ck.shape[0]
    out = np.zeros_like(q)
    for b in range(B):
        n = int(lengths[b])
        t = np.arange(max(0, n + 1 - window) if window else 0, n + 1)
        cells = (table[b, (t // PAGE) % table.shape[1]], t % PAGE)
        for h in range(H):
            k, v = ck[h // rep][cells], cv[h // rep][cells]
            s = k @ q[b, h] * D ** -0.5
            p = np.exp(s - s.max())
            out[b, h] = (p / p.sum()) @ v
    return out


# name: (H, Hkv, dtype, lengths, active, pages a slot). A step of the
# walk is a token's bytes' (``walk_step_tokens`` of 2 x Hkv x 64): 512
# tokens at 8 KV heads of 64 in bfloat16 (a row of 4 x 128, as 4 heads
# of 128), 256 in float32; 15 fills a page, 16 opens one.
_CASES = {
    "page_fills_and_opens": (32, 8, "bfloat16", [15, 16, 0], [True] * 3, 4),
    "step_edges_bf16": (32, 8, "bfloat16", [511, 512, 513, 1030],
                        [True] * 4, 65),
    "step_edges_f32": (32, 8, "float32", [255, 256, 257], [True] * 3, 33),
    "an_idle_slot_between": (32, 8, "bfloat16", [3, 700, 1500],
                             [True, False, True], 96),
    "groups_of_2_on_2": (4, 2, "float32", [40, 150], [True, True], 10),
    "mha_4_on_4": (4, 4, "float32", [33, 64], [True, True], 10),
    # A window of 40 over a ring of ``ring_pages`` = 4 columns: a slot
    # under the window, one crossing it, one whose ring wrapped.
    "a_window_over_a_ring": (8, 4, "float32", [7, 45, 300], [True] * 3, 4,
                             40),
}


@pytest.mark.parametrize("case", list(_CASES))
@pytest.mark.parametrize("path", ["page_walk", "gather"])
def test_heads_of_64_two_to_a_row(path, case):
    H, Hkv, dtype, lengths, active, pmax, window = (*_CASES[case], None)[:7]
    if window:
        assert pmax == pa.ring_pages(window, PAGE, 4096)
    rows, lanes = pa.pool_row(Hkv, D)
    assert (rows, lanes) == (Hkv // 2, 128)
    B, n_layers, layer = len(lengths), 2, 1
    n_pool = B * pmax
    rng = np.random.RandomState(len(case))
    q = jnp.asarray(rng.randn(B, H, D), dtype)
    k_new = jnp.asarray(rng.randn(B, Hkv, D), dtype)
    v_new = jnp.asarray(rng.randn(B, Hkv, D), dtype)
    ck = jnp.asarray(rng.randn(n_layers, rows, n_pool, PAGE, lanes), dtype)
    cv = jnp.asarray(rng.randn(n_layers, rows, n_pool, PAGE, lanes), dtype)
    order = rng.permutation(n_pool)
    table = np.zeros((B, pmax), np.int32)
    for b, n in enumerate(lengths):
        used = min(n // PAGE + 1, pmax)
        table[b, :used] = order[b * pmax:b * pmax + used]
    active = np.asarray(active)
    # The token's row: KV heads 2j | 2j + 1 side by side in row j, at
    # the slot's current page and cell; every other cell as it was.
    want_k, want_v = np.array(ck), np.array(cv)
    for b in np.flatnonzero(active):
        cell = (layer, slice(None), table[b, lengths[b] // PAGE % pmax],
                lengths[b] % PAGE)
        want_k[cell] = np.asarray(k_new[b]).reshape(rows, lanes)
        want_v[cell] = np.asarray(v_new[b]).reshape(rows, lanes)
    args = (q, k_new, v_new, ck, cv, jnp.asarray(layer, jnp.int32),
            jnp.asarray(table), jnp.asarray(lengths, jnp.int32),
            jnp.asarray(active))
    if path == "page_walk":
        out, got_k, got_v = pa.paged_decode_attention(
            *args, window=window, interpret=True)
        assert not np.asarray(out, np.float32)[~active].any()
    else:
        out, got_k, got_v = pa.gather_decode_attention(*args, window=window)
    np.testing.assert_array_equal(np.asarray(got_k), want_k)
    np.testing.assert_array_equal(np.asarray(got_v), want_v)
    assert out.shape == q.shape and out.dtype == q.dtype
    ref = _reference(q, _unpacked(want_k[layer]), _unpacked(want_v[layer]),
                     table, lengths, window)
    tol = 2e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32)[active],
                               ref[active], atol=tol, rtol=tol)


def test_the_pool_rows_and_the_path_follow_the_heads(monkeypatch):
    """Heads of 64 pair up where their number is even; an odd number of
    them, and every other width, lies a head a row. The walk takes a
    pool by its rows: 128 lanes and a page of 16."""
    import importlib

    # ray_tpu.ops re-exports the function under the module's own name.
    flash_attention = importlib.import_module("ray_tpu.ops.flash_attention")
    assert pa.pool_row(8, 64) == (4, 128)
    assert pa.pool_row(3, 64) == (3, 64)
    assert pa.pool_row(8, 128) == (8, 128)
    assert pa.pool_row(2, 16) == (2, 16)
    assert pa.pageable(16, pa.pool_row(8, 64)[1])
    assert not pa.pageable(16, pa.pool_row(3, 64)[1])
    assert not pa.pageable(8, pa.pool_row(8, 64)[1])
    monkeypatch.setattr(flash_attention, "_on_tpu", lambda: True)
    assert pa.decode_attention_path(16, pa.pool_row(8, 64)[1]) == "page_walk"
    assert pa.decode_attention_path(16, pa.pool_row(3, 64)[1]) == "gather"
    # ``walk_step_tokens`` sizes the step by the row's bytes: 8 heads of
    # 64 are 4 of 128.
    assert (pa.walk_step_tokens(2 * 8 * 64 * 2, 16, 1024)
            == pa.walk_step_tokens(2 * 4 * 128 * 2, 16, 1024) == 512)


def test_heads_of_128_lower_to_the_program_they_were():
    """The lanes' helpers add nothing to a pool whose rows are its heads:
    queries, rows and output are the arguments themselves."""
    q = jnp.ones((2, 8, 128), jnp.bfloat16)
    kv = jnp.ones((2, 4, 128), jnp.bfloat16)
    got = pa._to_lanes(q, kv, kv, 4, 128)
    assert got[0] is q and got[1] is kv and got[2] is kv
    out = jnp.ones((2, 8, 128), jnp.bfloat16)
    assert pa._from_lanes(out, 128, 4) is out
