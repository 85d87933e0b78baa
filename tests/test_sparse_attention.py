"""ops/sparse_attention.py: the exact selection against a stable sort,
ties and all, and each of the four Pallas kernels in interpret mode
against its XLA path (the index walk and the walk under a selection over
paged pools; a prefill's selection in tiles and its restricted flash
attention). CPU."""

import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops import paged_attention as pa  # noqa: E402
from ray_tpu.ops import sparse_attention as sa  # noqa: E402


def _stable_topk(scores, k, valid):
    """By hand: the k largest valid entries of a row, equal ones in the
    order of their positions."""
    out = np.zeros(scores.shape, bool)
    for row, (s, v) in enumerate(zip(scores, valid)):
        order = sorted(np.flatnonzero(v), key=lambda i: (-s[i], i))
        out[row, order[:k]] = True
    return out


@pytest.mark.parametrize("k", [1, 5, 24, 64])
def test_select_topk_is_exact_and_breaks_ties_towards_the_lower_position(k):
    """Scores drawn from a handful of values (so that most are tied),
    signed zeros among them, rows of every length from none to all: the
    selection is the stable sort's, entry for entry."""
    rng = np.random.RandomState(k)
    T = 50
    scores = rng.choice(np.asarray(
        [-2.5, -0.0, 0.0, 1e-30, 0.5, 0.5000001, 3.0], np.float32), (12, T))
    scores[6:] = rng.randn(6, T).astype(np.float32)
    lengths = np.asarray([0, 1, 2, 7, 24, 50] * 2)
    valid = np.arange(T)[None] < lengths[:, None]
    got = np.asarray(sa.select_topk(jnp.asarray(scores), k,
                                    jnp.asarray(valid)))
    np.testing.assert_array_equal(got, _stable_topk(scores, k, valid))
    assert (got.sum(-1) == np.minimum(k, lengths)).all()


_INDEX_CASES = {
    # lengths, active, pages a slot, layers, layer, topk
    "under_and_over_topk": ([37, 5, 255, 16], [True] * 4, 16, 2, 1, 24),
    "an_inactive_slot": ([37, 200, 90], [True, False, True], 16, 3, 0, 64),
    "over_a_block": ([300, 511, 256], [True] * 3, 32, 2, 1, 100),
}


@pytest.mark.parametrize("case", list(_INDEX_CASES))
def test_the_index_walk_is_the_gather(case):
    """The index walk (interpreted) and the XLA gather on the same
    arguments: the same pool, every active slot's new key at ``[layer,
    page_table[b, len // page], len % page]`` and nothing else touched;
    the same selection, which is by hand the ``min(topk, len + 1)`` best
    of ``sum_j w_j relu(q_j . k)`` over positions ``0 .. len``; an
    inactive slot selects nothing. Scores are made to tie: keys repeat."""
    lengths, active, pmax, n_layers, layer, topk = _INDEX_CASES[case]
    B, H, D, page = len(lengths), 4, 128, 16
    n_pool = B * pmax
    rng = np.random.RandomState(len(case))
    q = jnp.asarray(rng.randn(B, H, D), jnp.float32)
    w = jnp.asarray(rng.randn(B, H), jnp.float32)
    new = jnp.asarray(rng.randn(B, D), jnp.float32)
    pool = rng.randn(n_layers, n_pool, page, D).astype(np.float32)
    pool[:, 3::3] = pool[:, 1:-2:3][:, :len(pool[0, 3::3])]  # repeated pages
    table = rng.permutation(n_pool).reshape(B, pmax).astype(np.int32)
    active = np.asarray(active)
    want = pool.copy()
    for b in np.flatnonzero(active):
        want[layer, table[b, lengths[b] // page], lengths[b] % page] = new[b]
    args = (q, w, new, jnp.asarray(pool), jnp.asarray(layer, jnp.int32),
            jnp.asarray(table), jnp.asarray(lengths, jnp.int32),
            jnp.asarray(active))
    walked, walked_pool = sa.paged_index_select(*args, topk=topk,
                                                interpret=True)
    gathered, gathered_pool = sa.gather_index_select(*args, topk=topk)
    np.testing.assert_array_equal(np.asarray(walked_pool), want)
    np.testing.assert_array_equal(np.asarray(gathered_pool), want)
    assert walked.shape == gathered.shape == (B, pmax * page)
    assert not np.asarray(walked)[~active].any()
    for b in np.flatnonzero(active):
        n = lengths[b] + 1
        keys = want[layer][table[b]].reshape(pmax * page, D)[:n]
        scores = (np.maximum(np.asarray(q)[b] @ keys.T, 0)
                  * np.asarray(w)[b][:, None]).sum(0)
        for got in (walked, gathered):
            got = np.asarray(got)[b] != 0
            assert got.sum() == min(topk, n) and not got[n:].any()
            # The kept scores are the best: none left out beats one kept
            # by more than the order of the sums can move it.
            if got[:n].all():
                continue
            assert scores[:n][got[:n]].min() >= (
                scores[:n][~got[:n]].max() - 1e-4)
        np.testing.assert_array_equal(np.asarray(walked)[b],
                                      np.asarray(gathered)[b])


_SELECTED_WALK_CASES = {
    **{name: case[:5] for name, case in _INDEX_CASES.items()},
    # lengths, active, pages a slot, layers, layer. Under 64 columns or
    # more a compute step of these rows is 1,024 tokens: under a step,
    # a step to the row, steps and a part, the first row of a step, and
    # idle slots between walking ones.
    "mixed_steps_and_idle_slots": (
        [100, 700, 1023, 5, 2600, 1024, 33],
        [True, False, True, False, True, True, False], 170, 2, 1),
    "last_page_opens_a_step": ([512, 527, 511, 1039], [True] * 4, 66, 1, 0),
    "first_and_last_slot_idle": ([900, 64, 1500, 2047, 10],
                                 [False, True, True, True, False], 128, 3,
                                 2),
}


@pytest.mark.parametrize("case", list(_SELECTED_WALK_CASES))
def test_the_walk_under_a_selection_is_the_gather_under_it(case):
    """``paged_latent_decode_attention(selected=)`` interpreted against
    the XLA gather with the same selection and against a softmax by hand
    over the selected rows alone; the pool written as without one. One
    slot selects its new row alone, the longest selects nothing in its
    first compute step nor, if it has three, in its second."""
    lengths, active, pmax, n_layers, layer = _SELECTED_WALK_CASES[case]
    B, H, W, values, page, scale = len(lengths), 8, 256, 128, 16, 0.07
    n_pool = B * pmax
    rng = np.random.RandomState(len(case) + 1)
    q = jnp.asarray(rng.randn(B, H, W), jnp.float32)
    new = jnp.asarray(rng.randn(B, W), jnp.float32)
    pool = jnp.asarray(rng.randn(n_layers, n_pool, page, W), jnp.float32)
    table = rng.permutation(n_pool).reshape(B, pmax).astype(np.int32)
    active = np.asarray(active)
    T = pmax * page
    step = pa.walk_step_tokens(W * 4, page, pmax)
    selected = rng.rand(B, T) < 0.3
    alone, longest = np.flatnonzero(active)[0], np.argmax(
        np.where(active, lengths, -1))
    selected[alone, :] = False
    selected[alone, lengths[alone]] = True      # the new row alone
    selected[longest, :step] = False            # a whole step unselected
    if lengths[longest] >= 2 * step:
        selected[longest, step:2 * step] = False
    selected[longest, lengths[longest] - 3] = True
    args = (q, new, pool, jnp.asarray(layer, jnp.int32), jnp.asarray(table),
            jnp.asarray(lengths, jnp.int32), jnp.asarray(active))
    sel = jnp.asarray(selected, jnp.float32)
    walked, walked_pool = pa.paged_latent_decode_attention(
        *args, scale=scale, values=values, selected=sel, interpret=True)
    gathered, gathered_pool = pa.gather_latent_decode_attention(
        *args, scale=scale, values=values, selected=sel)
    plain, plain_pool = pa.gather_latent_decode_attention(
        *args, scale=scale, values=values)
    np.testing.assert_array_equal(np.asarray(walked_pool),
                                  np.asarray(plain_pool))
    np.testing.assert_array_equal(np.asarray(gathered_pool),
                                  np.asarray(plain_pool))
    assert walked.shape == (B, H, values)
    assert not np.asarray(walked)[~active].any()
    for b in np.flatnonzero(active):
        n = lengths[b] + 1
        rows = np.asarray(plain_pool)[layer][table[b]].reshape(T, W)[:n]
        rows = rows[selected[b, :n]]
        s = np.asarray(q)[b] @ rows.T * scale
        p = np.exp(s - s.max(-1, keepdims=True))
        ref = (p / p.sum(-1, keepdims=True)) @ rows[:, :values]
        for got in (walked, gathered):
            np.testing.assert_allclose(np.asarray(got)[b], ref, atol=2e-5,
                                       rtol=2e-5)
    # Everything selected is the walk without a selection.
    everything, _ = pa.paged_latent_decode_attention(
        *args, scale=scale, values=values,
        selected=jnp.ones((B, T), jnp.float32), interpret=True)
    np.testing.assert_allclose(
        np.asarray(everything)[active], np.asarray(plain)[active],
        atol=2e-5, rtol=2e-5)


def _tiles_to_square(tiles):
    nq, nk, bq, bk = tiles.shape
    return np.asarray(tiles).transpose(0, 2, 1, 3).reshape(nq * bq, nk * bk)


def test_a_prefills_selection_in_tiles_is_the_one_in_blocks():
    """1,024 tokens, 4 index heads, ``index_topk`` 100: the kernel's
    int8 tiles (interpreted) are the XLA path's boolean [S, S], which is
    by hand the stable top-100 of each token's scores of the tokens
    before it and itself. Keys repeat, so scores tie."""
    S, H, D, topk = 1024, 4, 128, 100
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(S, H, D), jnp.float32)
    k = rng.randn(S, D).astype(np.float32)
    k[::5] = k[3]
    w = jnp.asarray(rng.randn(S, H), jnp.float32)
    square = np.asarray(sa._select_xla(q, jnp.asarray(k), w, topk))
    tiles = sa._select_kernel(q, jnp.asarray(k), w, topk, interpret=True)
    assert tiles.shape == (S // 128, S // 512, 128, 512)
    assert tiles.dtype == jnp.int8
    np.testing.assert_array_equal(_tiles_to_square(tiles) != 0, square)
    causal = np.tril(np.ones((S, S), bool))
    assert not (square & ~causal).any()
    assert (square.sum(-1) == np.minimum(topk, np.arange(S) + 1)).all()
    scores = np.asarray(sa.index_scores(q, jnp.asarray(k)[None], w))
    rows = [5, 99, 100, 101, 640, 1023]
    np.testing.assert_array_equal(
        square[rows], _stable_topk(scores[rows], topk, causal[rows]))


def test_the_restricted_flash_attention_is_the_softmax_over_the_selected():
    """The forward flash kernel under a selection's tiles (interpreted)
    against the XLA path under the same selection as booleans, and both
    against a softmax by hand; q.k 256 wide beside a v of 128."""
    S, H, D, Dv, topk, scale = 1024, 2, 256, 128, 64, 0.06
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(S, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(S, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(S, H, Dv), jnp.float32)
    qi = jnp.asarray(rng.randn(S, 2, 128), jnp.float32)
    ki = jnp.asarray(rng.randn(S, 128), jnp.float32)
    wi = jnp.asarray(rng.randn(S, 2), jnp.float32)
    tiles = sa._select_kernel(qi, ki, wi, topk, interpret=True)
    square = _tiles_to_square(tiles) != 0
    got = np.asarray(sa._attention_kernel(q, k, v, tiles, scale,
                                          interpret=True))
    blocks = np.asarray(sa._attention_xla(q, k, v, jnp.asarray(square),
                                          scale))
    assert got.shape == (S, H, Dv)
    s = np.einsum("qhd,thd->hqt", np.asarray(q), np.asarray(k)) * scale
    s = np.where(square[None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("hqt,thd->qhd", p / p.sum(-1, keepdims=True),
                     np.asarray(v))
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)
    np.testing.assert_allclose(blocks, want, atol=3e-5, rtol=3e-5)
