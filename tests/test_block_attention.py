"""ops/block_attention.py: the selection against a direct form in numpy
(ties, fewer blocks than ``topk``, every forced block), a page's mean
written exactly when its page fills, the XLA attention paths against a
direct softmax, and both Pallas kernels interpreted against the XLA
paths, the walk over tables whose blocks are aligned ascending runs of
page ids, as ``generation.KVBooks`` hands them out."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops import block_attention as ba  # noqa: E402

PAGE = 16
SIZES = ba.BlockSizes(kernel=32, stride=16, block=64, init=1, window=128,
                      topk=5, dense_len=256)


def _direct_selection(q, k, t, sizes):
    """The module docstring's steps 1-4 for one query at position ``t``:
    q [H, d], k [S, Hkv, d] -> [Hkv, blocks] bool, loops and no tricks."""
    H, d = q.shape
    S, Hkv, _ = k.shape
    G = H // Hkv
    n_blocks = S // sizes.block
    own = t // sizes.block
    out = np.zeros((Hkv, n_blocks), bool)
    if t < sizes.dense_len:
        out[:, :own + 1] = True
        return out
    keys = [j for j in range((S - sizes.kernel) // sizes.stride + 1)
            if j * sizes.stride + sizes.kernel - 1 <= t]
    for g in range(Hkv):
        c = np.stack([k[j * sizes.stride:j * sizes.stride + sizes.kernel, g]
                      .mean(0) for j in keys])
        r = np.zeros(len(keys))
        for h in range(g * G, (g + 1) * G):
            s = c @ q[h] / np.sqrt(d)
            p = np.exp(s - s.max())
            r += p / p.sum()
        score = np.full(n_blocks, -np.inf)
        for b in range(own + 1):
            over = [r[i] for i, j in enumerate(keys)
                    if j * sizes.stride + sizes.kernel > b * sizes.block
                    and j * sizes.stride < (b + 1) * sizes.block]
            score[b] = max(over) if over else -1.0
            if b < sizes.init or b > own - sizes.local:
                score[b] = np.inf
        # The best ``topk``, ties towards the lower block.
        order = sorted(range(own + 1), key=lambda b: (-score[b], b))
        out[g, order[:sizes.topk]] = True
    return out


@pytest.mark.parametrize("t", [100, 255, 256, 300, 447, 448, 511])
def test_selection_is_the_direct_form(t):
    """Contexts on both sides of ``dense_len``, with fewer blocks than
    ``topk`` (t = 256: five seen, all kept) and more, at a page's last
    token and its first."""
    rng = np.random.default_rng(t)
    S, H, Hkv, d = 512, 4, 2, 16
    q = rng.normal(size=(H, d)).astype(np.float32)
    k = rng.normal(size=(S, Hkv, d)).astype(np.float32)
    means = ba.page_means(jnp.asarray(k), PAGE)[None]
    got = ba.select_blocks(jnp.asarray(q)[None], means, jnp.asarray([t]),
                           SIZES)[0]
    np.testing.assert_array_equal(got, _direct_selection(q, k, t, SIZES))
    assert got[:, t // 64].all() and got[:, 0].all()
    assert (got.sum(-1) == min(SIZES.topk, t // 64 + 1)).all() or t < 256


def test_ties_go_to_the_lower_block():
    """Every key the same: every block scores alike, and the selection
    takes the forced blocks and then the LOWEST of the rest."""
    S, H, Hkv, d, t = 1024, 4, 2, 16, 1000
    k = np.ones((S, Hkv, d), np.float32)
    q = np.ones((H, d), np.float32)
    got = np.asarray(ba.select_blocks(
        jnp.asarray(q)[None], ba.page_means(jnp.asarray(k), PAGE)[None],
        jnp.asarray([t]), SIZES))[0]
    # Own block 15; forced 0 and 14, 15; topk 5: blocks 1 and 2 besides.
    assert [list(np.flatnonzero(row)) for row in got] == [[0, 1, 2, 14, 15]] * 2


def _pools(rng, layers, hkv, pages, d, dtype=jnp.float32):
    k_pool = jnp.asarray(rng.normal(size=(layers, hkv, pages, PAGE, d)), dtype)
    v_pool = jnp.asarray(rng.normal(size=(layers, hkv, pages, PAGE, d)), dtype)
    return k_pool, v_pool


def test_a_page_mean_is_written_exactly_when_its_page_fills():
    """Sixteen decode steps of one slot from the first token of a page:
    its row of the mean pool is untouched until the token that fills the
    page arrives, then it is the mean of the page's sixteen keys; the
    running sum starts afresh at the page's first token; an idle slot
    writes nothing."""
    rng = np.random.default_rng(0)
    B, H, Hkv, d, pages = 2, 4, 2, 16, 12
    table = jnp.asarray([[3, 7, 1, 0], [2, 4, 5, 6]], jnp.int32)
    means = jnp.full((2, pages, Hkv * d), 9.0, jnp.float32)
    sums = jnp.asarray(rng.normal(size=(2, B, Hkv * d)), jnp.float32)
    keys = rng.normal(size=(PAGE, B, Hkv, d)).astype(np.float32)
    active = jnp.asarray([True, False])
    for i in range(PAGE):
        before = means
        lengths = jnp.asarray([PAGE + i, 5], jnp.int32)
        q = jnp.asarray(rng.normal(size=(B, H, d)), jnp.float32)
        _, means, sums = ba.block_select_decode(
            q, jnp.asarray(keys[i]), means, sums, 1, table, lengths, active,
            sizes=SIZES)
        if i < PAGE - 1:
            np.testing.assert_array_equal(means, before)
    want = keys[:, 0].mean(0).reshape(-1)
    np.testing.assert_allclose(means[1, 7], want, atol=1e-6)
    changed = np.asarray((means != 9.0).any(-1))
    assert changed.sum() == 1 and changed[1, 7]
    np.testing.assert_allclose(sums[1, 0], keys[:, 0].sum(0).reshape(-1),
                               atol=1e-5)


def _run_table(rng, slots, columns, pages, ratio=4):
    """A table as ``KVBooks`` fills one for a pool that selects blocks:
    a permutation of the pool's RUNS, each block's pages one aligned
    ascending run of ids."""
    runs = rng.permutation(pages // ratio)[:slots * columns // ratio]
    return jnp.asarray((runs[:, None] * ratio + np.arange(ratio)).reshape(
        slots, columns), jnp.int32)


def _decode_case(seed, dtype=jnp.float32, d=16, H=4, Hkv=2,
                 lengths=(300, 37, 511), pmax=32, keep=0.4):
    rng = np.random.default_rng(seed)
    B, pages = 3, 3 * pmax + 32
    k_pool, v_pool = _pools(rng, 2, Hkv, pages, d, dtype)
    table = _run_table(rng, B, pmax, pages)
    lengths = jnp.asarray(lengths, jnp.int32)
    active = jnp.asarray([True, True, True])
    q = jnp.asarray(rng.normal(size=(B, H, d)), dtype)
    k_new = jnp.asarray(rng.normal(size=(B, Hkv, d)), dtype)
    v_new = jnp.asarray(rng.normal(size=(B, Hkv, d)), dtype)
    chosen = jnp.asarray(rng.random((B, Hkv, pmax * PAGE // 64)) < keep)
    own = (lengths // 64)[:, None, None]
    blocks = jnp.arange(chosen.shape[-1])[None, None]
    chosen = (chosen | (blocks == own) | (blocks == 0)) & (blocks <= own)
    return q, k_new, v_new, k_pool, v_pool, table, lengths, active, chosen


def test_gather_attends_to_the_kept_blocks_alone():
    q, k_new, v_new, k_pool, v_pool, table, lengths, active, chosen = \
        _decode_case(0)
    out, k_out, v_out = ba.gather_block_decode_attention(
        q, k_new, v_new, k_pool, v_pool, 1, table, lengths, active,
        ba.pages_of(chosen, table.shape[1], lengths, SIZES), sizes=SIZES)
    H, Hkv = q.shape[1], k_new.shape[1]
    for b in range(q.shape[0]):
        t = int(lengths[b])
        np.testing.assert_array_equal(
            k_out[1, :, table[b, t // PAGE], t % PAGE], k_new[b])
        rows_k = np.asarray(k_out[1][:, table[b]]).reshape(Hkv, -1, 16)
        rows_v = np.asarray(v_out[1][:, table[b]]).reshape(Hkv, -1, 16)
        for h in range(H):
            g = h // (H // Hkv)
            keep = np.repeat(np.asarray(chosen[b, g]), 64)
            keep &= np.arange(keep.size) <= t
            s = rows_k[g][keep] @ np.asarray(q[b, h]) / 4.0
            p = np.exp(s - s.max())
            np.testing.assert_allclose(
                out[b, h], (p / p.sum()) @ rows_v[g][keep], atol=1e-5)


@pytest.mark.parametrize("case", [
    dict(), dict(idle=1),
    # The unit's last block holds 1, 2 and 3 pages; 4, 1 (a unit of one
    # page) and 2.
    dict(lengths=(261, 280, 300)), dict(lengths=(319, 5, 90)),
    # Rows an earlier request left behind the token's own, in its page
    # and in the pages of its block behind it: finite, and masked.
    dict(lengths=(261, 280, 300), stale=1e4),
    # A table of 128 pages, a step of 64: units of two steps, one whose
    # second step holds its last block alone, and one of one step.
    dict(lengths=(2047, 1040, 70), pmax=128, keep=0.95, topk=32,
         dense_len=2048),
], ids=["all", "idle", "last-1-2-3", "last-4-1-2", "stale", "steps"])
def test_block_walk_is_the_gather(case):
    """The walk interpreted, heads of 128, over tables of runs: the kept
    blocks copied a head a block, the unit's last block whole, the new
    row written as one page, an idle slot left alone."""
    idle, stale = case.get("idle"), case.get("stale")
    q, k_new, v_new, k_pool, v_pool, table, lengths, active, chosen = \
        _decode_case(1, d=128, **{k: case[k] for k in
                                  ("lengths", "pmax", "keep") if k in case})
    if idle is not None:
        active = active.at[idle].set(False)
    if stale:
        def soiled(pool):
            for b, t in enumerate(np.asarray(lengths)):
                behind = table[b, t // PAGE + 1:(t // 64 + 1) * 4]
                assert len(behind) == 3 - t // PAGE % 4
                pool = pool.at[:, :, behind].set(stale)
                pool = pool.at[:, :, table[b, t // PAGE], t % PAGE:].set(stale)
            return pool
        k_pool, v_pool = soiled(k_pool), soiled(v_pool)
    sizes = SIZES._replace(topk=case.get("topk", 24),
                           dense_len=case.get("dense_len", 512))
    chosen = ba.pages_of(chosen, table.shape[1], lengths, sizes)
    want, want_k, want_v = ba.gather_block_decode_attention(
        q, k_new, v_new, k_pool, v_pool, 1, table, lengths, active, chosen,
        sizes=sizes)
    out, k_out, v_out = ba.paged_block_decode_attention(
        q, k_new, v_new, k_pool, v_pool, jnp.asarray(1), table, lengths,
        active, chosen, sizes=sizes, interpret=True)
    np.testing.assert_allclose(out[active], want[active], atol=2e-5)
    np.testing.assert_array_equal(k_out, want_k)
    np.testing.assert_array_equal(v_out, want_v)
    assert not np.asarray(out)[~np.asarray(active)].any()


@pytest.mark.parametrize("lengths", [[100, 2047, 600], [255, 256, 1040]])
def test_select_kernel_is_the_xla_path(lengths):
    """The selection kernel interpreted at heads of 128 over a table of
    128 pages: contexts before ``dense_len``, past it with fewer blocks
    than ``topk`` and with more, at the table's end."""
    rng = np.random.default_rng(7)
    B, H, Hkv, d, P = 3, 4, 2, 128, 128
    q = jnp.asarray(rng.normal(size=(B, H, d)), jnp.float32)
    rows = jnp.asarray(rng.normal(size=(B, P, Hkv * d)), jnp.float32)
    lengths = jnp.asarray(lengths, jnp.int32)
    sizes = SIZES._replace(topk=6)
    want = ba.pages_of(ba.select_blocks(q, rows.reshape(B, P, Hkv, d),
                                        lengths, sizes), P, lengths, sizes)
    got = ba.paged_block_select(q, rows, lengths, sizes=sizes,
                                interpret=True)
    np.testing.assert_array_equal(got, want)


def test_selected_pages_are_in_order_and_end_at_the_tokens_own():
    """A kept block is its FIRST page's id, in the order of the
    sequence; the last is the token's own block, of which the pages up
    to the token's own count."""
    *_, table, lengths, active, chosen = _decode_case(2)
    sizes = SIZES._replace(topk=8)       # as many as ``chosen`` may keep
    kept = ba.pages_of(chosen, table.shape[1], lengths, sizes)
    first, n, last = ba.selected_pages(kept, table, lengths, active, sizes)
    assert first.shape == (3, 2, 8)
    for b in range(3):
        for g in range(2):
            blocks = np.flatnonzero(chosen[b, g])
            assert int(n[b, g]) == len(blocks)
            assert blocks[-1] == int(lengths[b]) // 64
            np.testing.assert_array_equal(first[b, g, :len(blocks)],
                                          np.asarray(table[b])[4 * blocks])
            assert int(last[b, g]) == int(lengths[b]) // 16 % 4 + 1
            # What ``pages_of`` keeps, counted through the blocks.
            assert int(kept[b, g].sum()) == 4 * (len(blocks) - 1) + int(
                last[b, g])
    _, none, no_last = ba.selected_pages(kept, table, lengths,
                                         jnp.zeros(3, bool), sizes)
    assert not np.asarray(none).any() and not np.asarray(no_last).any()


def _prefill_case(seed, S, H, Hkv, d, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(S, H, d)), dtype)
    k = jnp.asarray(rng.normal(size=(S, Hkv, d)), dtype)
    v = jnp.asarray(rng.normal(size=(S, Hkv, d)), dtype)
    return q, k, v


def test_prefill_selects_a_token_as_a_decode_step_does():
    S, H, Hkv, d = 512, 4, 2, 16
    q, k, _ = _prefill_case(0, S, H, Hkv, d)
    means = ba.page_means(k, PAGE)
    chosen = ba.prefill_block_select(q, means, sizes=SIZES)
    assert chosen.shape == (Hkv, S, S // 64)
    for t in (3, 255, 256, 389, 511):
        np.testing.assert_array_equal(
            chosen[:, t], _direct_selection(np.asarray(q[t]), np.asarray(k),
                                            t, SIZES))


def test_prefill_attention_xla_is_the_masked_softmax():
    S, H, Hkv, d = 256, 4, 2, 16
    q, k, v = _prefill_case(1, S, H, Hkv, d)
    sizes = SIZES._replace(dense_len=64, topk=2, window=64)
    chosen = ba.prefill_block_select(q, ba.page_means(k, PAGE), sizes=sizes)
    out = ba.block_prefill_attention(q, k, v, chosen, sizes=sizes)
    for t in (0, 70, 200, 255):
        for h in range(H):
            g = h // 2
            keep = np.repeat(np.asarray(chosen[g, t]), 64)
            keep &= np.arange(S) <= t
            s = np.asarray(k[:, g])[keep] @ np.asarray(q[t, h]) / 4.0
            p = np.exp(s - s.max())
            np.testing.assert_allclose(
                out[t, h], (p / p.sum()) @ np.asarray(v[:, g])[keep],
                atol=1e-5)


def test_block_flash_is_the_xla_path():
    """The kernel interpreted at heads of 128: two steps of keys a
    query block, the diagonal inside a step, the selection widened from
    blocks to tokens in the kernel."""
    S, H, Hkv, d = 4096, 4, 2, 128
    q, k, v = _prefill_case(2, S, H, Hkv, d)
    sizes = ba.BlockSizes(32, 16, 64, 1, 128, 6, 1024)
    chosen = ba.prefill_block_select(q, ba.page_means(k, PAGE), sizes=sizes)
    want = ba._attention_xla(q, k, v, chosen, d ** -0.5, sizes)
    got = ba._attention_kernel(q, k, v, chosen, d ** -0.5, sizes,
                               interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_sizes_are_held_to_the_page():
    SIZES.check(16)
    with pytest.raises(ValueError, match="stride"):
        SIZES.check(32)
    assert (SIZES.span, SIZES.ratio, SIZES.local) == (2, 4, 2)
    assert ba.BlockSizes(32, 16, 64, 1, 2048, 64, 8192).most_pages() == 512
    assert ba.block_walk_path(16, 128) == "gather"   # the CPU
