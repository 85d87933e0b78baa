"""Latent attention (PR 42) and its selection (PR 54) under the serving
programs: one row a token for all heads, the latent walk against its
reference, the pool of indexer keys on the latent pool's page table."""

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import LlamaConfig, init_params  # noqa: E402
from ray_tpu.models import llama  # noqa: E402
from ray_tpu.models.generation import PagedKVCache  # noqa: E402


_LATENT_CASES = {
    # lengths, active, pages a slot, layers, layer[, pool dtype]. A
    # compute step is as long as the row's bytes and the table's columns
    # say (``walk_step_tokens``): of these float32 rows of 256, 256
    # tokens under 16 columns, 512 under 32, 1,024 under 64 or more.
    "mid_page_and_page_ends": ([37, 0, 255, 16], [True, True, True, True],
                               16, 2, 1),
    "an_inactive_slot": ([37, 200, 90], [True, False, True], 16, 3, 0),
    "all_inactive": ([5, 70], [False, False], 8, 1, 0),
    "over_a_block": ([300, 511, 256], [True, True, True], 32, 2, 1),
    # Under a step, idle, a step to the row, idle, two steps and a part,
    # a step and the first row of the next, idle: the list of steps runs
    # on from a slot's last step to the next walking slot's first.
    "mixed_steps_and_idle_slots": (
        [100, 700, 1023, 5, 2600, 1024, 33],
        [True, False, True, False, True, True, False], 170, 2, 1),
    # The new row's page is the first of its step (the write-back's row
    # offset is 0 in a step that holds one page), its last row and its
    # first; 511 closes a step.
    "last_page_opens_a_step": ([512, 527, 511, 1039], [True] * 4, 66, 1, 0),
    "first_and_last_slot_idle": ([900, 64, 1500, 2047, 10],
                                 [False, True, True, True, False], 128, 3,
                                 2),
    "one_walking_slot_of_many": ([0, 0, 1300, 0, 0],
                                 [False, False, True, False, False], 96, 2,
                                 0),
    # The cells' own row: 640 of bfloat16, steps of 1,024 tokens.
    "bf16_rows_of_640": ([3, 1023, 700, 1024, 2100],
                         [True, True, False, True, True], 140, 2, 1,
                         "bfloat16"),
}


@pytest.mark.parametrize("case", list(_LATENT_CASES))
@pytest.mark.parametrize("path", ["latent_walk", "gather"])
def test_latent_decode_attention_matches_reference(path, case):
    """Both decode attentions over a latent pool (the Pallas latent walk
    in interpret mode, the XLA gather): the new row of each active slot
    lands in ``[layer, page_table[b, len // page], len % page]`` and
    every other cell of the pool is bit-identical (an inactive slot
    writes nothing); the attention is, by hand, every head's softmax of
    ``scale * q . row`` over rows ``0 .. len`` times the rows' first
    ``values``. Contexts end mid-page, on a page's last row, on a
    step's last row and past several steps; pages are walked out of
    order; an inactive slot keeps the length its last request left."""
    from ray_tpu.ops import paged_attention as pa

    lengths, active, pmax, n_layers, layer, *dtype = _LATENT_CASES[case]
    dtype = jnp.dtype(*dtype or ["float32"])
    B, H, page, scale = len(lengths), 8, 16, 0.07
    W, values, tol = (256, 128, 2e-5) if dtype == jnp.float32 else (
        640, 512, 2e-2)
    n_pool = B * pmax
    rng = np.random.RandomState(len(case))
    q = jnp.asarray(rng.randn(B, H, W), dtype)
    new = jnp.asarray(rng.randn(B, W), dtype)
    pool = jnp.asarray(rng.randn(n_layers, n_pool, page, W), dtype)
    table = rng.permutation(n_pool).reshape(B, pmax).astype(np.int32)
    active = np.asarray(active)
    want = np.array(pool)
    for b in np.flatnonzero(active):
        want[layer, table[b, lengths[b] // page], lengths[b] % page] = new[b]
    args = (q, new, pool, jnp.asarray(layer, jnp.int32), jnp.asarray(table),
            jnp.asarray(lengths, jnp.int32), jnp.asarray(active))
    if path == "latent_walk":
        out, got = pa.paged_latent_decode_attention(
            *args, scale=scale, values=values, interpret=True)
        assert not np.asarray(out, np.float32)[~active].any()
    else:
        out, got = pa.gather_latent_decode_attention(
            *args, scale=scale, values=values)
    np.testing.assert_array_equal(np.asarray(got), want)
    assert out.shape == (B, H, values) and got.dtype == pool.dtype
    for b in np.flatnonzero(active):
        rows = want[layer][table[b]].reshape(pmax * page, W)[
            :lengths[b] + 1].astype(np.float32)
        s = np.asarray(q, np.float32)[b] @ rows.T * scale
        p = np.exp(s - s.max(-1, keepdims=True))
        ref = (p / p.sum(-1, keepdims=True)) @ rows[:, :values]
        np.testing.assert_allclose(np.asarray(out, np.float32)[b], ref,
                                   atol=tol, rtol=tol)


def _latent_cfg(**changes):
    return LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=4, num_kv_heads=4, head_dim=24, rope_theta=10_000.0,
        dtype=jnp.float32, q_lora_rank=24, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12,
        rope_interleave=True, **changes)


def test_absorbed_attention_is_the_rebuilt_one_on_the_same_weights():
    """One latent layer's weights, 50 tokens. Rebuilt: k and v of every
    head from the rows, causal attention (a prefill's). Absorbed: W_UK
    into the query, every head over the rows themselves, W_UV behind (a
    decode step's), here for the last token over a pool that holds the
    49 before it. The same [H, v_head_dim], to float32's rounding."""
    from ray_tpu.ops import paged_attention as pa
    from ray_tpu.ops.attention import mha_attention

    cfg = _latent_cfg()
    assert cfg.latent and cfg.latent_row == 32 + 128
    lp = jax.tree.map(lambda p: p[1],
                      init_params(cfg, jax.random.PRNGKey(2))["layers"])
    T, page = 50, 16
    x = jnp.asarray(np.random.RandomState(0).randn(1, T, 32), jnp.float32)
    q, rows = llama.latent_proj(cfg, lp, x, jnp.arange(T))
    assert q.shape == (1, T, 4, 24) and rows.shape == (1, T, 160)
    assert not np.asarray(rows)[..., 40:].any()          # the lane padding
    k, v = llama.latent_kv(cfg, lp, rows)
    assert k.shape == (1, T, 4, 24) and v.shape == (1, T, 4, 12)
    rebuilt = mha_attention(q, k, v, causal=True)[0, -1]
    # The pool: the first 49 rows in pages 3, 1, 0, 2; the 50th is new.
    table = jnp.asarray([[3, 1, 0, 2]], jnp.int32)
    held = jnp.zeros((64, 160)).at[:T - 1].set(rows[0, :T - 1])
    pool = jnp.zeros((1, 4, page, 160)).at[0, table[0]].set(
        held.reshape(4, page, 160))
    q_lat = llama.latent_absorb_q(cfg, lp, q[:, -1:])
    assert q_lat.shape == (1, 1, 4, 160)
    out, pool = pa.gather_latent_decode_attention(
        q_lat[:, 0], rows[:, -1], pool, jnp.asarray(0), table,
        jnp.asarray([T - 1]), jnp.asarray([True]), scale=24 ** -0.5,
        values=32)
    absorbed = llama.latent_absorb_out(cfg, lp, out[:, None])[0, 0]
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(rebuilt),
                               atol=2e-6, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(pool[0, 2, 1]),  # 49 = 3 * 16 + 1
                                  np.asarray(rows[0, -1]))


def test_latent_cache_is_one_pool_of_rows_and_training_raises_by_name():
    from ray_tpu.models import causal_lm_loss
    from ray_tpu.models.llama import kv_layers, layer_runs

    cfg = _latent_cfg()
    assert [tuple(r) for r in layer_runs(cfg)] == [(0, 2, False, "latent", 0)]
    assert kv_layers(cfg) == {"latent": 2}
    cache = PagedKVCache.create(cfg, 3, 12, 16, 4)
    assert {k: v.shape for k, v in cache.k.items()} == {
        "latent": (2, 12, 16, 160)}
    assert cache.v == {} and cache.page_size == 16
    assert cache.page_table["latent"].shape == (3, 4)
    assert PagedKVCache.sizes(cfg, 3, 12, 16, 4) == {"latent": (2, 12, 4)}
    params = init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="attention is latent"):
        causal_lm_loss(params, jnp.zeros((1, 9), jnp.int32), cfg)
    with pytest.raises(ValueError, match="q.k width"):
        layer_runs(_latent_cfg(layer_types=("full", "full")))


def _selecting_cfg():
    """Latent attention under a selection: an indexing layer, a layer
    that shares its selection, an indexing layer."""
    return dataclasses.replace(
        _latent_cfg(), num_layers=3, index_topk=24, index_n_heads=2,
        index_head_dim=16, indexer_types=("full", "shared", "full"))


def test_the_pool_of_indexer_keys_rides_on_the_latent_pools_page_table():
    """A second pool, of the indexing layers' keys, beside the latent
    rows: as many pages at the same ids, no table and no free list of
    its own. One reservation a slot covers both; released pages are the
    next slot's in both; a slot reused finds the table row zeroed and
    re-laid; what a page weighs counts both pools' layers."""
    from ray_tpu.models.generation import KVBooks
    from ray_tpu.models.llama import index_offsets, kv_layers, layer_runs

    cfg = _selecting_cfg()
    assert [tuple(r) for r in layer_runs(cfg)] == [
        (0, 1, False, "latent_index", 0), (1, 1, False, "latent_shared", 1),
        (2, 1, False, "latent_index", 2)]
    assert index_offsets(cfg) == (0, 1, 1)
    assert kv_layers(cfg) == {"latent": 3, "index": 2}
    geometry = (cfg, 3, 12, 16, 8)
    assert PagedKVCache.sizes(*geometry) == {"latent": (3, 12, 8),
                                             "index": (2, 12, 0)}
    cache = PagedKVCache.create(*geometry)
    assert {k: v.shape for k, v in cache.k.items()} == {
        "latent": (3, 12, 16, 160), "index": (2, 12, 16, 16)}
    assert cache.v == {} and cache.page_size == 16
    assert set(cache.page_table) == {"latent"}
    assert cache.pools("index") == (cache.k["index"],)
    books = KVBooks(*geometry, cache)
    assert set(books.free) == set(books.tables) == {"latent"}
    reading = books.reading()
    assert reading["kv_row_bytes"] == {"latent": 160 * 4, "index": 16 * 4}
    assert reading["pages"] == {
        "latent": {"layers": 3, "total": 12, "free": 12},
        "index": {"layers": 2, "total": 12, "free": 12}}
    pages, tables = books.reserve(0, 40, 32)            # 3 pages
    assert set(pages) == set(tables) == {"latent"} and len(pages["latent"]) == 2
    first = tables["latent"][0].copy()
    assert np.count_nonzero(first) >= 2 and books.reserve(1, 64, 64)
    assert books.reading()["pages"]["index"]["free"] == 12 - 3 - 4
    # The third slot's 6 pages are not there: nothing is taken.
    assert books.reserve(2, 96, 64) is None
    assert books.reading()["pages"]["latent"]["free"] == 5
    books.account([0, 1], [40, 60])
    counts = books.counts
    assert counts["decode_kv_rows_read"] == 3 * 100
    assert counts["decode_kv_rows_selected"] == 3 * (24 + 24)
    assert counts["kv_page_steps_held"] == (3 + 2) * (3 + 4)
    books.release(0)
    assert not books.tables["latent"][0].any()
    assert books.reading()["pages"]["index"]["free"] == 8
    pages, tables = books.reserve(0, 96, 64)            # the slot reused
    assert len(pages["latent"]) == 4
    assert set(first[:3]) <= set(tables["latent"][0][:6].tolist())
    assert books.reading()["pages"]["latent"]["free"] == 2
