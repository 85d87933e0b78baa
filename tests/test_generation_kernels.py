"""The decode attention over paged pools, kernel against reference: the
Pallas page walk interpreted and the XLA gather, full and window layers,
and the rules that choose a path and a step from shapes."""

import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402


def _reference_decode_attention(q, ck, cv, page_table, lengths):
    """Plain float32 over one layer's pool [Hkv, P, page, D]: a slot's
    real tokens, positions 0..lengths[b], sliced out of its pages in
    table order; one softmax a query row."""
    q, ck, cv = (jnp.asarray(x, jnp.float32) for x in (q, ck, cv))
    B, H, D = q.shape
    rep = H // ck.shape[0]
    rows = []
    for b in range(B):
        n = int(lengths[b]) + 1
        heads = []
        for h in range(H):
            k = jnp.concatenate([ck[h // rep, p] for p in page_table[b]])[:n]
            v = jnp.concatenate([cv[h // rep, p] for p in page_table[b]])[:n]
            prob = jax.nn.softmax(k @ q[b, h] * D ** -0.5)
            heads.append(prob @ v)
        rows.append(jnp.stack(heads))
    return np.asarray(jnp.stack(rows))


# name: (H, Hkv, pool dtype, lengths, active, pages a slot, layers, the
# layer decoded). Pages are 16 tokens and, under a table of 10 or 17
# columns, the kernel's step 8 or 16 pages (``walk_step_tokens``), so
# 10 pages a slot make a second, partly filled step. ``lengths[b]`` is
# where the new row goes: 16 is the first row of a fresh page, 128 (of
# 10 pages) and 256 (of 17) the first of a fresh step, 159 of 10 pages
# the last cell of the slot's last page.
_PAGED_CASES = {
    "length_0": (4, 2, "float32", [0], [True], 4, 2, 1),
    "length_15_page_end": (4, 2, "float32", [15], [True], 4, 2, 0),
    "length_16_page_start": (4, 2, "float32", [16], [True], 4, 2, 1),
    "length_17": (4, 2, "float32", [17], [True], 4, 2, 0),
    "last_cell_of_last_page": (4, 2, "float32", [159, 127, 128], [True] * 3,
                               10, 2, 1),
    "inactive_slot_stale_row": (4, 2, "float32", [40, 150, 3],
                                [True, False, True], 10, 2, 0),
    "gqa_rep4_hkv8": (32, 8, "float32", [5, 131], [True, True], 10, 2, 1),
    "mha_rep1": (4, 4, "float32", [33, 64], [True, True], 10, 2, 0),
    "bf16_pool": (32, 8, "bfloat16", [0, 100, 159], [True] * 3, 10, 2, 1),
    "block_boundaries": (4, 2, "float32", [127, 128, 256, 255], [True] * 4,
                         17, 1, 0),
    "all_slots_inactive": (4, 2, "float32", [16, 130], [False, False], 10,
                           2, 1),
    "layer_0_of_3": (4, 2, "float32", [20, 143], [True, True], 10, 3, 0),
    "layer_last_of_3": (4, 2, "float32", [20, 143], [True, True], 10, 3, 2),
    "mha_hkv16_bf16": (16, 16, "bfloat16", [31, 144, 7],
                       [True, True, False], 10, 2, 1),
    # 28 rows of queries: no whole number of sublane tiles (8 of
    # float32, 16 of bfloat16), and groups of 7 (SmallThinker).
    "gqa_rep7_hkv4": (28, 4, "float32", [5, 131, 159], [True] * 3, 10, 2, 1),
    "gqa_rep7_hkv4_bf16": (28, 4, "bfloat16", [0, 100, 159, 16],
                           [True, True, True, False], 10, 2, 0),
    # A compute step is as long as its bytes say (``walk_step_tokens``):
    # 512 tokens at 4 KV heads of bfloat16 or 2 of float32, 256 at 8,
    # 128 at 16. 511 is the last row of a step, 512 the first of the
    # next, which then holds one page; 1024 opens a third step.
    "step_edges_hkv2": (4, 2, "float32", [511, 512, 513, 1023, 1024],
                        [True] * 5, 65, 1, 0),
    "step_edges_hkv4_bf16": (28, 4, "bfloat16", [511, 512, 513, 1023, 1024],
                             [True] * 5, 65, 2, 1),
    "step_edges_hkv8_bf16": (32, 8, "bfloat16", [255, 256, 257, 511, 512],
                             [True] * 5, 33, 2, 0),
    # A slot of 3 tokens beside one of 1,500, between them an idle slot
    # with the row and the length its last request left: no step of the
    # short slot reads past its one page, nothing of the idle one moves.
    "short_idle_long_hkv4_bf16": (28, 4, "bfloat16", [3, 700, 1500],
                                  [True, False, True], 96, 2, 1),
    "short_idle_long_hkv8_bf16": (32, 8, "bfloat16", [3, 700, 1500],
                                  [True, False, True], 96, 2, 0),
    "short_idle_long_hkv16_bf16": (16, 16, "bfloat16", [3, 700, 1500],
                                   [True, False, True], 96, 1, 0),
    "short_idle_long_hkv2": (4, 2, "float32", [3, 700, 1500],
                             [True, False, True], 96, 1, 0),
}


@pytest.mark.parametrize("case", list(_PAGED_CASES))
@pytest.mark.parametrize("path", ["page_walk", "gather"])
def test_paged_decode_attention_matches_reference(path, case):
    """Both decode attentions (the Pallas page walk in interpret mode,
    the XLA gather): the new K/V row of each active slot lands in
    ``[layer, :, page_table[b, len // page], len % page]`` and every
    other cell of both pools is bit-identical (an inactive slot writes
    nothing); the attention equals the float32 reference above over the
    pool so written. Every case walks pages out of order; a slot's
    unused table cells hold 0, the id of a page another slot uses; an
    inactive slot keeps the row and the length its last request left."""
    from ray_tpu.ops import paged_attention as pa

    H, Hkv, dtype, lengths, active, pmax, n_layers, layer = \
        _PAGED_CASES[case]
    B, D, page = len(lengths), 128, 16
    n_pool = B * pmax
    rng = np.random.RandomState(len(case))
    q = jnp.asarray(rng.randn(B, H, D), dtype)
    k_new = jnp.asarray(rng.randn(B, Hkv, D), dtype)
    v_new = jnp.asarray(rng.randn(B, Hkv, D), dtype)
    ck = jnp.asarray(rng.randn(n_layers, Hkv, n_pool, page, D), dtype)
    cv = jnp.asarray(rng.randn(n_layers, Hkv, n_pool, page, D), dtype)
    order = rng.permutation(n_pool)
    order[np.argmin(order)], order[0] = order[0], 0  # slot 0 owns page 0
    table = np.zeros((B, pmax), np.int32)
    for b, n in enumerate(lengths):
        used = n // page + 1
        table[b, :used] = order[b * pmax:b * pmax + used]
    active = np.asarray(active)
    want_k, want_v = np.array(ck), np.array(cv)
    for b in np.flatnonzero(active):
        cell = (layer, slice(None), table[b, lengths[b] // page],
                lengths[b] % page)
        want_k[cell], want_v[cell] = k_new[b], v_new[b]
    lengths = jnp.asarray(lengths, jnp.int32)
    args = (q, k_new, v_new, ck, cv, jnp.asarray(layer, jnp.int32),
            jnp.asarray(table), lengths, jnp.asarray(active))
    if path == "page_walk":
        out, got_k, got_v = pa.paged_decode_attention(*args, interpret=True)
        assert not np.asarray(out, np.float32)[~active].any()
    else:
        out, got_k, got_v = pa.gather_decode_attention(*args)
    np.testing.assert_array_equal(np.asarray(got_k), want_k)
    np.testing.assert_array_equal(np.asarray(got_v), want_v)
    assert got_k.dtype == ck.dtype and got_v.dtype == cv.dtype
    assert out.shape == q.shape and out.dtype == q.dtype
    ref = _reference_decode_attention(q, want_k[layer], want_v[layer], table,
                                      lengths)
    tol = 2e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32)[active],
                               ref[active], atol=tol, rtol=tol)


_WINDOW_CASES = {
    # lengths (position of the new row), window; page 16
    "under_the_window": ([5, 20], 32),
    "crossing_it": ([31, 32, 33], 32),
    "far_over_wrapping": ([47, 48, 200, 1000], 32),
    "window_no_page_multiple": ([70, 129, 7], 40),
    "an_idle_slot": ([300, 90], 64),
    # 28 query heads on 4 (the third and fourth entries: H, Hkv).
    "groups_of_7": ([47, 300, 33], 32, 28, 4),
    # A window of 64 pages under steps of 32 (float32, 2 KV heads): rings
    # of 65 columns that wrapped four times and once, walks of 65 pages
    # (two steps and one of a single page) from columns 53 and 1 and of
    # 64 from column 54, so each step crosses what a step was on the
    # ring's first turn, and the first two the ring's end.
    "wrapped_ring_of_steps": ([5000, 5007, 2090, 40], 1024),
}


@pytest.mark.parametrize("case", list(_WINDOW_CASES))
@pytest.mark.parametrize("path", ["page_walk", "gather"])
def test_window_decode_attention_matches_the_masked_einsum(path, case):
    """A window layer's decode attention over a RING of ``ring_pages``
    columns, on both paths: each slot's whole history is laid into its
    ring the way prefill and earlier steps would have left it (position t
    in column ``(t // page) % columns``, later pages over earlier ones);
    the new row lands in the ring's cell for ``lengths[b]`` and nowhere
    else, and the output equals a plain softmax over positions
    ``lengths[b] - window < t <= lengths[b]`` of the history, on float32
    values, whether the slot is under the window, crosses it or has
    wrapped its ring many times. A slot's unused columns hold page 0."""
    from ray_tpu.ops import paged_attention as pa

    lengths, window, H, Hkv = (*_WINDOW_CASES[case], 4, 2)[:4]
    B, D, page, layer = len(lengths), 128, 16, 1
    columns = pa.ring_pages(window, page, 4096)
    assert columns == -(-window // page) + 1
    active = np.ones(B, bool)
    if case == "an_idle_slot":
        active[1] = False
    rng = np.random.RandomState(len(case))
    n_pool = B * columns + 1
    q = rng.randn(B, H, D).astype(np.float32)
    hist_k = [rng.randn(n + 1, Hkv, D).astype(np.float32) for n in lengths]
    hist_v = [rng.randn(n + 1, Hkv, D).astype(np.float32) for n in lengths]
    ck = rng.randn(2, Hkv, n_pool, page, D).astype(np.float32)
    cv = rng.randn(2, Hkv, n_pool, page, D).astype(np.float32)
    order = 1 + rng.permutation(B * columns)          # page 0 is no one's
    table = np.zeros((B, columns), np.int32)
    for b, n in enumerate(lengths):
        used = min(n // page + 1, columns)
        table[b, :used] = order[b * columns:b * columns + used]
        for t in range(n):                             # the rows before
            cell = (layer, slice(None), table[b, (t // page) % columns],
                    t % page)
            ck[cell], cv[cell] = hist_k[b][t], hist_v[b][t]
    want_k, want_v = ck.copy(), cv.copy()
    for b in np.flatnonzero(active):
        n = lengths[b]
        cell = (layer, slice(None), table[b, (n // page) % columns], n % page)
        want_k[cell], want_v[cell] = hist_k[b][n], hist_v[b][n]
    args = (jnp.asarray(q), jnp.asarray(np.stack([h[-1] for h in hist_k])),
            jnp.asarray(np.stack([h[-1] for h in hist_v])), jnp.asarray(ck),
            jnp.asarray(cv), jnp.asarray(layer, jnp.int32),
            jnp.asarray(table), jnp.asarray(lengths, jnp.int32),
            jnp.asarray(active))
    if path == "page_walk":
        out, got_k, got_v = pa.paged_decode_attention(
            *args, window=window, interpret=True)
    else:
        out, got_k, got_v = pa.gather_decode_attention(*args, window=window)
    np.testing.assert_array_equal(np.asarray(got_k), want_k)
    np.testing.assert_array_equal(np.asarray(got_v), want_v)
    for b in np.flatnonzero(active):
        n = lengths[b]
        lo = max(0, n + 1 - window)
        k, v = hist_k[b][lo:n + 1], hist_v[b][lo:n + 1]   # [T, Hkv, D]
        qg = q[b].reshape(Hkv, H // Hkv, D)
        s = np.einsum("hgd,thd->hgt", qg, k) * D ** -0.5
        prob = np.exp(s - s.max(-1, keepdims=True))
        prob /= prob.sum(-1, keepdims=True)
        ref = np.einsum("hgt,thd->hgd", prob, v).reshape(H, D)
        np.testing.assert_allclose(np.asarray(out)[b], ref, atol=2e-5,
                                   rtol=2e-5)


def test_a_window_of_none_is_the_walk_over_everything():
    """``window=None`` changes nothing: the same jaxpr as a call that
    does not name it, on both paths, and a window wider than the
    context gives the same numbers over a table that holds it all."""
    from ray_tpu.ops import paged_attention as pa

    B, H, Hkv, D, page, pmax = 2, 4, 2, 128, 16, 4
    rng = np.random.RandomState(0)
    args = (jnp.asarray(rng.randn(B, H, D), jnp.float32),
            jnp.asarray(rng.randn(B, Hkv, D), jnp.float32),
            jnp.asarray(rng.randn(B, Hkv, D), jnp.float32),
            jnp.asarray(rng.randn(1, Hkv, B * pmax, page, D), jnp.float32),
            jnp.asarray(rng.randn(1, Hkv, B * pmax, page, D), jnp.float32),
            jnp.asarray(0, jnp.int32),
            jnp.asarray(rng.permutation(B * pmax).reshape(B, pmax), jnp.int32),
            jnp.asarray([37, 9], jnp.int32), jnp.asarray([True, True]))
    for fn in (functools.partial(pa.paged_decode_attention, interpret=True),
               pa.gather_decode_attention):
        plain = jax.make_jaxpr(fn)(*args)
        named = jax.make_jaxpr(functools.partial(fn, window=None))(*args)
        assert str(plain) == str(named)
        wide = fn(*args, window=4096)
        for a, b in zip(fn(*args), wide):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6, rtol=1e-6)


def test_decode_attention_path_follows_platform_and_shape(monkeypatch):
    """One choice, from what the code can see: the page walk on a TPU
    for shapes it tiles, the gather everywhere else."""
    import importlib

    from ray_tpu.ops import paged_attention as pa

    # ray_tpu.ops re-exports the function under the module's own name.
    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    assert pa.decode_attention_path(16, 128) == "gather"  # this is a CPU
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    assert pa.decode_attention_path(16, 128) == "page_walk"
    assert pa.decode_attention_path(16, 64) == "gather"
    assert pa.decode_attention_path(8, 128) == "gather"


@pytest.mark.parametrize("elements,dtype,columns,tokens", [
    # a k and a v row of every KV head of 128: 2 * kv_heads * 128
    (1024, "bfloat16", 257, 512),  # SmallThinker's rings (28 on 4)
    (1024, "bfloat16", 1024, 512),  # ... and its tables of 16k
    (1024, "bfloat16", 129, 512),  # Trinity's rings (32 on 4)
    (1024, "bfloat16", 512, 512),
    (2048, "bfloat16", 128, 256),  # Mistral (32 on 8)
    (4096, "bfloat16", 128, 128),  # OLMoE (16 on 16): what it had
    (8192, "bfloat16", 128, 128),  # never under a lane tile of scores
    (512, "float32", 65, 512),     # the float32 pools of these tests
    (1024, "bfloat16", 20, 256),   # a table shorter than a step:
    (1024, "bfloat16", 8, 128),    # whole pages, a power of two of them,
    (1024, "bfloat16", 4, 64),     # never more than the columns
    (1024, "bfloat16", 1, 16),
    # one latent row for all heads (512 of latent, the rotary key's tile)
    # (51 pages to the megabyte: 64, the power of two nearest in ratio)
    (640, "bfloat16", 1024, 1024),  # Kimi-Linear's and GLM-5.2's 16k
    (640, "bfloat16", 512, 1024),  # JoyAI's 8k
    (640, "bfloat16", 40, 512),    # a table shorter than a step
    (256, "float32", 170, 1024),   # the float32 pools of these tests
    (720, "bfloat16", 1024, 512),  # 45 pages to the megabyte: 32
    (736, "bfloat16", 1024, 512),  # 44 pages
    (704, "bfloat16", 1024, 1024), # 46 pages: 64
])
def test_walk_step_follows_the_bytes_of_a_token(elements, dtype, columns,
                                                tokens):
    """A walk's compute step, from shapes alone: about a megabyte of the
    pool (``elements * itemsize`` bytes a token in one layer), at least
    the 128 lanes of a score tile, a power of two of pages, never longer
    than the table's columns."""
    from ray_tpu.ops import paged_attention as pa

    got = pa.walk_step_tokens(elements * jnp.dtype(dtype).itemsize, 16,
                              columns)
    assert got == tokens
    assert got <= columns * 16 and got % 16 == 0
    assert (got // 16) & (got // 16 - 1) == 0
