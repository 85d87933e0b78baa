"""A learned selection over a latent pool (DSA): score, select, attend.

A layer with an indexer (``llama.index_proj``) scores every cached
token ``s`` for the token ``t`` that asks,

    I[t, s] = sum_j w[t, j] * relu(q[t, j] . k[s])        (s <= t)

over ``index_n_heads`` queries of ``index_head_dim`` against the ONE key
a token, q and k in the model's dtype and the dot products summed in
float32, and keeps the ``min(index_topk, t + 1)`` positions of the
largest I, ties towards the lower position. That layer's latent
attention, and that of the layers that share its selection, is the
softmax over the kept positions alone.

**The selection is exact** (:func:`_select`): no sort and no
approximate top-k, but the k-th largest score found bit by bit (32
counts of "how many are at least this", over an order-preserving int32
image of the float32 scores) and, among scores equal to it, the lowest
positions found the same way. The same function runs under XLA and
inside both kernels.

**Decode** (:func:`index_select_decode`, then
``paged_attention.latent_decode_attention(selected=)``). The indexer's
keys live in a pool of their own, ``[Li, P, page, index_head_dim]``,
addressed by the latent pool's page table. ``index_walk``, a Pallas TPU
kernel, one program a slot: it walks the slot's pages of keys as the
latent walk walks rows (double-buffered copies, the new key set in VMEM
and its page copied back through the aliased output), scores a block of
tokens at a time into VMEM, selects there and writes the slot's
selection, 1.0 or 0.0 a position, [B, T]. The attention then walks
every page of latent rows under that selection (``sparse_walk``, the
latent walk with one more operand): it reads every row and attends to
the selected ones. Reading the selected rows alone would take a copy a
row (2,048 of 1,280 B a slot a layer), against one a page of sixteen.
``gather`` is both steps in plain XLA, for any platform.

**Prefill** (:func:`prefill_select`, :func:`sparse_prefill_attention`).
``index_block`` scores and selects for a block of ``_Q_BLOCK`` queries
at a time, the block's scores over all keys held in VMEM and never in
HBM, and writes the selection as int8 tiles ``[S / bq, S / bk, bq, bk]``
(268 MB at 16,384 tokens, where float32 scores would be 1.07 GB);
``sparse_flash`` is a forward flash attention that takes those tiles as
a mask beside causality. The XLA paths do the same in blocks of queries
with a boolean ``[S, S]``. What form a selection has is this module's
business: a caller hands :func:`prefill_select`'s result to
:func:`sparse_prefill_attention` and to nothing else.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_NEG_INF = -1e30
_INT_MIN = -(2 ** 31)
# Tokens a compute step of the index walk covers.
_INDEX_BLOCK_TOKENS = 256
# A prefill's blocks: queries that are scored, selected and attended
# together, and keys a step.
_Q_BLOCK, _K_BLOCK = 128, 512
# Queries a step of the restricted flash attention: whole tiles of the
# selection.
_FLASH_Q_BLOCK = 512
_VMEM_LIMIT = 96 * 1024 * 1024


def _sortable(x):
    """float32 -> int32, order-preserving (and -0.0 as +0.0)."""
    x = jnp.where(x == 0.0, 0.0, x.astype(jnp.float32))
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ (jnp.right_shift(bits, 31) & 0x7FFFFFFF)


def _select(key, want, pos, count, position_bits: int):
    """Which entries of ``key`` (int32, ``_INT_MIN`` where there is
    nothing to select) are among the ``want`` largest of their row, ties
    towards the lower ``pos``. ``count(mask)`` counts a row's true
    entries, shaped as ``want``; a row is whatever ``count`` sums over.
    Exact: the ``want``-th largest key is built from its sign bit down,
    31 + 1 counts, then the number of positions that its equals may
    take, ``position_bits`` counts more."""
    one = jnp.int32(1)
    thr = jnp.where(count(key >= 0) >= want, 0, _INT_MIN).astype(jnp.int32)

    def value_bit(i, thr):
        cand = thr | jnp.left_shift(one, 30 - i)
        return jnp.where(count(key >= cand) >= want, cand, thr)

    thr = jax.lax.fori_loop(0, 31, value_bit, thr)
    above, tie = key > thr, key == thr
    need = want - count(above)

    def position_bit(i, upto):
        cand = upto | jnp.left_shift(one, position_bits - 1 - i)
        return jnp.where(count(tie & (pos < cand)) <= need, cand, upto)

    upto = jax.lax.fori_loop(0, position_bits, position_bit,
                             jnp.zeros_like(thr))
    return above | (tie & (pos < upto))


def _count_last(mask):
    return mask.sum(axis=-1, keepdims=True, dtype=jnp.int32)


def select_topk(scores, k: int, valid):
    """[.., T] bool: the ``min(k, valid entries)`` largest of each row
    of float32 ``scores`` among the ``valid``, ties towards the lower
    position. The XLA path of both selections, and a test's handle."""
    T = scores.shape[-1]
    key = jnp.where(valid, _sortable(scores), _INT_MIN)
    want = jnp.minimum(k, _count_last(valid))
    pos = jnp.arange(T, dtype=jnp.int32)
    return _select(key, want, pos, _count_last, T.bit_length())


def index_scores(q, k, w):
    """I [.., T] float32 of queries q [.., H, D] with weights w [.., H]
    against keys k [.., T, D]: sum_j w_j relu(q_j . k)."""
    s = jnp.einsum("...hd,...td->...ht", q, k,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("...ht,...h->...t", jax.nn.relu(s),
                      w.astype(jnp.float32))


# ---------------------------------------------------------------- decode


def gather_index_select(q, w, k_new, pool, layer, page_table, lengths,
                        active, *, topk: int):
    """The XLA path of :func:`index_select_decode`. The new key goes in
    as its whole page (a page is a tile; a row of one is not)."""
    B = q.shape[0]
    _, n_pool, page, D = pool.shape
    T = page_table.shape[1] * page
    # Inactive slots aim past the pool (``gather_decode_attention``).
    pid = jnp.where(active, page_table[jnp.arange(B), lengths // page],
                    n_pool)
    held = pool[layer, jnp.minimum(pid, n_pool - 1)]        # [B, page, D]
    is_new = (jnp.arange(page)[None, :, None]
              == (lengths % page)[:, None, None])
    pool = pool.at[layer, pid].set(
        jnp.where(is_new, k_new.astype(pool.dtype)[:, None], held),
        mode="drop")
    keys = jnp.take(pool[layer], page_table, axis=0).reshape(B, T, D)
    with jax.named_scope("index.score"):
        scores = index_scores(q.astype(pool.dtype), keys, w)
    with jax.named_scope("index.select"):
        valid = jnp.arange(T)[None, :] <= lengths[:, None]
        selected = select_topk(scores, topk, valid)
    return selected.astype(jnp.float32), pool


def _index_walk_kernel(pt_ref, np_ref, len_ref, layer_ref, q_ref, w_ref,
                       new_ref, pool_hbm, sel_ref, pool_out, buf, keys, sems,
                       *, pmax: int, topk: int):
    """Grid (B,). pt_ref [B * Pmax], np_ref [B] (pages to walk), len_ref
    [B], layer_ref [1] in SMEM; q_ref [H, D] this slot's index queries,
    w_ref [H, 1] float32 their weights, new_ref [1, D] its new key;
    pool_hbm the key pool [Li, P, page, D] left in HBM and pool_out the
    same buffer as an output; sel_ref [blocks, block] float32, the
    slot's selection; buf [2, block, D] and keys [blocks, block] int32
    VMEM; sems [2, 2] DMA (keys in by buffer, the new key's page back)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    n_all, block = keys.shape
    page = pool_hbm.shape[2]
    pages_per_block = block // page
    layer = layer_ref[0]
    n_pages = np_ref[b]
    n_blocks = (n_pages + pages_per_block - 1) // pages_per_block
    length = len_ref[b]

    def copies(i, at):
        out = []
        for j in range(pages_per_block):
            p = jnp.minimum(i * pages_per_block + j, n_pages - 1)
            out.append(pltpu.make_async_copy(
                pool_hbm.at[layer, pt_ref[b * pmax + p]],
                buf.at[at, pl.ds(j * page, page), :], sems.at[0, at]))
        return out

    last = n_blocks - 1
    pid_new = pt_ref[b * pmax + jnp.maximum(n_pages - 1, 0)]
    rows_new = pl.ds(pl.multiple_of(
        (n_pages - 1 - last * pages_per_block) * page, page), page)

    def write_back():
        return pltpu.make_async_copy(buf.at[last % 2, rows_new, :],
                                     pool_out.at[layer, pid_new],
                                     sems.at[1, 0])

    @pl.when(n_blocks > 0)
    def _first():
        for c in copies(0, 0):
            c.start()

    keys[...] = jnp.full(keys.shape, _INT_MIN, jnp.int32)
    q, w = q_ref[...], w_ref[...]

    def body(i, carry):
        at = i % 2

        @pl.when(i + 1 < n_blocks)
        def _next():
            for c in copies(i + 1, 1 - at):
                c.start()

        for c in copies(i, at):
            c.wait()

        @pl.when(i == last)
        def _new_key():
            held = buf[at, rows_new, :]
            is_new = jax.lax.broadcasted_iota(
                jnp.int32, held.shape, 0) == length % page
            buf[at, rows_new, :] = jnp.where(is_new, new_ref[...], held)
            write_back().start()

        s = jax.lax.dot_general(
            q, buf[at], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # [H, block]
        score = (jnp.maximum(s, 0.0) * w).sum(axis=0, keepdims=True)
        t = i * block + jax.lax.broadcasted_iota(jnp.int32, score.shape, 1)
        keys[pl.ds(i, 1), :] = jnp.where(t <= length, _sortable(score),
                                         _INT_MIN)
        return carry

    jax.lax.fori_loop(0, n_blocks, body, 0)

    def count(mask):
        return mask.astype(jnp.int32).sum(axis=1, keepdims=True).sum(
            axis=0, keepdims=True)

    held = keys[...]
    pos = (jax.lax.broadcasted_iota(jnp.int32, held.shape, 0) * block
           + jax.lax.broadcasted_iota(jnp.int32, held.shape, 1))
    want = jnp.minimum(topk, count(held > _INT_MIN))
    selected = _select(held, want, pos, count,
                       (n_all * block).bit_length())
    sel_ref[...] = selected.astype(jnp.float32)

    @pl.when(n_blocks > 0)
    def _written():
        write_back().wait()


def paged_index_select(q, w, k_new, pool, layer, page_table, lengths, active,
                       *, topk: int, interpret: bool = False):
    """The index walk. Returns ([B, T] float32, pool): a slot's
    selection over its table's ``T = Pmax * page`` positions (an
    inactive slot's is all zeros), and the pool, the argument's buffer
    with the active slots' keys written."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, D = q.shape
    page = pool.shape[2]
    Pmax = page_table.shape[1]
    block = max(1, _INDEX_BLOCK_TOKENS // page) * page
    blocks = -(-Pmax * page // block)
    n_pages = jnp.where(active, jnp.minimum(lengths // page + 1, Pmax),
                        0).astype(jnp.int32)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    dtype = pool.dtype
    kernel = functools.partial(_index_walk_kernel, pmax=Pmax, topk=topk)
    selected, pool = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B,),
            in_specs=[pl.BlockSpec((None, H, D), lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec((None, H, 1), lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec((None, 1, D), lambda b, *_: (b, 0, 0)),
                      hbm],
            # Four dimensions, so that the reducer's name for this call
            # is not the latent walk's (three and four).
            out_specs=[pl.BlockSpec((None, None, blocks, block),
                                    lambda b, *_: (b, 0, 0, 0)), hbm],
            scratch_shapes=[
                pltpu.VMEM((2, block, D), dtype),
                pltpu.VMEM((blocks, block), jnp.int32),
                pltpu.SemaphoreType.DMA((2, 2))],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, 1, blocks, block), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, dtype)],
        # Operands count the four prefetched scalars: the pool is 7.
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(page_table.reshape(-1).astype(jnp.int32), n_pages,
      lengths.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
      q.astype(dtype), w.astype(jnp.float32)[:, :, None],
      k_new.astype(dtype)[:, None], pool)
    return selected.reshape(B, blocks * block)[:, :Pmax * page], pool


def index_path(page: int, head_dim: int) -> str:
    """``"index_walk"`` or ``"gather"``: what
    :func:`index_select_decode` runs for this pool here."""
    from .flash_attention import _on_tpu
    from .paged_attention import pageable

    return ("index_walk" if _on_tpu() and pageable(page, head_dim)
            else "gather")


def index_select_decode(q, w, k_new, pool, layer, page_table, lengths,
                        active, *, topk: int):
    """Write each active slot's new index key ``k_new`` [B, D] into the
    key pool [Li, P, page, D] at ``layer`` and position ``lengths[b]``
    (the pages are the latent pool's: ``page_table`` is its table),
    score positions ``0 .. lengths[b]`` with the slot's index queries
    ``q`` [B, H, D] and weights ``w`` [B, H], and select the
    ``min(topk, lengths[b] + 1)`` best. Returns (selection [B, T]
    float32, 1.0 where selected, and the pool), by the path
    :func:`index_path` names."""
    page, D = pool.shape[2:]
    path = (paged_index_select if index_path(page, D) == "index_walk"
            else gather_index_select)
    return path(q, w, k_new, pool, layer, page_table, lengths, active,
                topk=topk)


# --------------------------------------------------------------- prefill


def prefill_path(tokens: int, head_dim: int) -> str:
    """``"kernel"`` or ``"xla"``: how a prefill of ``tokens`` selects
    and attends here (both functions below ask this)."""
    from .flash_attention import _on_tpu

    return ("kernel" if _on_tpu() and tokens % _K_BLOCK == 0
            and head_dim % 128 == 0 else "xla")


def _blocks(x, block: int):
    """[S, ..] -> [S / block, block, ..]."""
    return x.reshape((x.shape[0] // block, block) + x.shape[1:])


def _select_xla(q, k, w, topk: int):
    """[S, S] bool, a block of queries at a time."""
    S = q.shape[0]
    block = min(S, _Q_BLOCK)
    pos = jnp.arange(S, dtype=jnp.int32)

    def one(args):
        qb, wb, start = args
        q_pos = start + jnp.arange(block, dtype=jnp.int32)
        with jax.named_scope("index.score"):
            scores = index_scores(qb, k[None], wb)
        with jax.named_scope("index.select"):
            return select_topk(scores, topk, pos[None, :] <= q_pos[:, None])

    return jax.lax.map(one, (_blocks(q, block), _blocks(w, block),
                             jnp.arange(S // block) * block)).reshape(S, S)


def _index_block_kernel(q_ref, w_ref, k_ref, o_ref, keys, *, topk: int):
    """Grid (S / bq,). q_ref [H, bq, D] the block's index queries, w_ref
    [bq, H] float32 their weights, k_ref [S, D] every key; o_ref
    [S / bk, bq, bk] int8, the block's selection over every key; keys
    the same shape int32, VMEM."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(0)
    heads, bq, _ = q_ref.shape
    nkb, _, bk = keys.shape
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    # The last block of keys that any of these queries may see.
    diagonal = (qi * bq + bq - 1) // bk
    w = w_ref[...]

    def fill(kb, carry):
        @pl.when(kb <= diagonal)
        def _scored():
            k = k_ref[pl.ds(pl.multiple_of(kb * bk, bk), bk), :]
            score = jnp.zeros((bq, bk), jnp.float32)
            for j in range(heads):
                s = jax.lax.dot_general(
                    q_ref[j], k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                score = score + jnp.maximum(s, 0.0) * w[:, j:j + 1]
            k_pos = kb * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            keys[kb] = jnp.where(k_pos <= q_pos, _sortable(score), _INT_MIN)

        @pl.when(kb > diagonal)
        def _unseen():
            keys[kb] = jnp.full((bq, bk), _INT_MIN, jnp.int32)

        return carry

    jax.lax.fori_loop(0, nkb, fill, 0)

    def count(mask):
        return mask.astype(jnp.int32).sum(axis=0, keepdims=True).sum(
            axis=2, keepdims=True)

    held = keys[...]
    pos = (jax.lax.broadcasted_iota(jnp.int32, held.shape, 0) * bk
           + jax.lax.broadcasted_iota(jnp.int32, held.shape, 2))
    rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (1, bq, 1), 1)
    want = jnp.minimum(topk, rows + 1)
    selected = _select(held, want, pos, count, (nkb * bk).bit_length())
    o_ref[...] = selected.astype(jnp.int8)


def _select_kernel(q, k, w, topk: int, interpret: bool = False):
    """int8 [S / bq, S / bk, bq, bk]: the selection in tiles."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, D = q.shape
    bq, bk = _Q_BLOCK, _K_BLOCK
    return pl.pallas_call(
        functools.partial(_index_block_kernel, topk=topk),
        grid=(S // bq,),
        in_specs=[pl.BlockSpec((H, bq, D), lambda i: (0, i, 0)),
                  pl.BlockSpec((bq, H), lambda i: (i, 0)),
                  pl.BlockSpec((S, D), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((None, S // bk, bq, bk),
                               lambda i: (i, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((S // bk, bq, bk), jnp.int32)],
        out_shape=jax.ShapeDtypeStruct((S // bq, S // bk, bq, bk), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(q.transpose(1, 0, 2), w.astype(jnp.float32), k)


def prefill_select(q, k, w, *, topk: int):
    """The selection of every token of one prompt over the tokens
    before it and itself: index queries q [S, H, D], keys k [S, D],
    weights w [S, H]. What comes back is for
    :func:`sparse_prefill_attention` alone."""
    S, _, D = q.shape
    if prefill_path(S, D) == "kernel":
        return _select_kernel(q, k, w, topk)
    return _select_xla(q, k, w, topk)


def empty_selection(tokens: int, head_dim: int):
    """A selection's shape and dtype, for a layer scan's first carry."""
    if prefill_path(tokens, head_dim) == "kernel":
        bq, bk = _Q_BLOCK, _K_BLOCK
        return jnp.zeros((tokens // bq, tokens // bk, bq, bk), jnp.int8)
    return jnp.zeros((tokens, tokens), bool)


def _attention_xla(q, k, v, selected, scale: float):
    """q, k [S, H, D], v [S, H, Dv], selected [S, S] bool."""
    S = q.shape[0]
    block = min(S, _Q_BLOCK)

    def one(args):
        qb, sel = args
        s = jnp.einsum("qhd,thd->hqt", qb, k,
                       preferred_element_type=jnp.float32) * scale
        p = jax.nn.softmax(jnp.where(sel[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqt,thd->qhd", p.astype(v.dtype), v)

    out = jax.lax.map(one, (_blocks(q, block), _blocks(selected, block)))
    return out.reshape((S,) + out.shape[2:])


def _sparse_flash_kernel(q_ref, k_ref, v_ref, sel_ref, o_ref, m_s, l_s,
                         acc_s, *, scale: float):
    """Grid (H, S / bq, S / bk), the keys innermost. q_ref [bq, D],
    k_ref [bk, D], v_ref [bk, Dv], sel_ref [bq / 128, 128, bk] int8 (the
    selection's tiles of these queries), o_ref [bq, Dv]; running max,
    denominator and accumulator float32 VMEM."""
    from jax.experimental import pallas as pl

    qi, kj = pl.program_id(1), pl.program_id(2)
    bq, bk = q_ref.shape[0], k_ref.shape[0]

    @pl.when(kj == 0)
    def _start():
        m_s[...] = jnp.full(m_s.shape, _NEG_INF, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    # A block of keys wholly behind the causal diagonal holds nothing
    # selected (and was not fetched: the index maps stop at the diagonal).
    @pl.when(kj * bk <= qi * bq + bq - 1)
    def _block():
        s = jax.lax.dot_general(
            q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        keep = sel_ref[...].astype(jnp.int32).reshape(bq, bk) != 0
        s = jnp.where(keep, s, _NEG_INF)
        m = m_s[...]
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        # A row with nothing selected so far has m_new = -1e30 and
        # exp(0) = 1 for every masked entry: hence the second mask.
        p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        l_s[...] = alpha * l_s[...] + p.sum(axis=1, keepdims=True)
        acc_s[...] = alpha * acc_s[...] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[...],
            preferred_element_type=jnp.float32)
        m_s[...] = m_new

    @pl.when(kj == pl.num_programs(2) - 1)
    def _done():
        l = l_s[...]
        o_ref[...] = (acc_s[...] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


def _attention_kernel(q, k, v, selected, scale: float,
                      interpret: bool = False):
    """q, k [S, H, D], v [S, H, Dv], selected the int8 tiles."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, D = q.shape
    Dv = v.shape[-1]
    # Queries a step: four of the selection's tiles (blocks of 128 x 512
    # ran the MXU a quarter full: chip run, PR 54; PR 44 found the same
    # of the causal kernels).
    bq, bk = _FLASH_Q_BLOCK, _K_BLOCK
    tiles = bq // _Q_BLOCK

    def upto(i, j):
        # Past the diagonal the last block needed again: no new copy.
        return jnp.minimum(j, (i * bq + bq - 1) // bk)

    out = pl.pallas_call(
        functools.partial(_sparse_flash_kernel, scale=scale),
        grid=(H, S // bq, S // bk),
        in_specs=[
            pl.BlockSpec((None, bq, D), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((None, bk, D), lambda h, i, j: (h, upto(i, j), 0)),
            pl.BlockSpec((None, bk, Dv), lambda h, i, j: (h, upto(i, j), 0)),
            pl.BlockSpec((tiles, None, _Q_BLOCK, bk),
                         lambda h, i, j: (i, upto(i, j), 0, 0))],
        out_specs=pl.BlockSpec((None, bq, Dv), lambda h, i, j: (h, i, 0)),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, Dv), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((H, S, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q.transpose(1, 0, 2), k.transpose(1, 0, 2), v.transpose(1, 0, 2),
      selected)
    return out.transpose(1, 0, 2)


def sparse_prefill_attention(q, k, v, selected, *, scale: float):
    """Attention of one prompt's q [S, H, D] over k [S, H, D] and v
    [S, H, Dv] restricted to ``selected`` (:func:`prefill_select`'s, of
    this layer or of the one whose selection it shares); the selection
    is causal by construction. Returns [S, H, Dv]."""
    if selected.dtype == jnp.int8:
        return _attention_kernel(q, k, v, selected, scale)
    return _attention_xla(q, k, v, selected, scale)
