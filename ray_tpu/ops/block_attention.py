"""Block-selected attention over a paged k/v pool (InfLLM-V2's scheme):
compressed keys, a score a block, the best blocks, attention over those.

A layer of kind "blocks" is a grouped-query attention whose token ``t``
(0-based), once ``t >= dense_len``, attends over a SELECTION of blocks of
``block`` tokens, each KV head its own (:class:`BlockSizes` names the
sizes; ``G`` query heads a KV head ``g``, width ``d``):

1. compressed keys ``C_j = mean(k_i, i = stride j .. stride j + kernel -
   1)`` for every ``j`` whose last token is ``<= t``. ``stride`` is the
   pool's page and ``kernel`` a whole number of pages, so ``C_j`` is the
   mean of ``kernel / stride`` adjacent PAGE MEANS, which is all that is
   kept of them (generation.PagedKVCache's "mean" pool, a row a page);
2. ``p_h = softmax_j(q_h . C_j / sqrt(d))`` in float32 for each head of
   the group, ``r_j = sum_h p_h[j]``;
3. block ``b`` (tokens ``block b .. block b + block - 1``) scores ``R_b =
   max r_j`` over the ``j`` whose window overlaps it, ``j = ratio b -
   (span - 1) .. ratio b + ratio - 1`` (``ratio`` pages a block, ``span``
   pages a compressed key), those that exist;
4. kept: the first ``init`` blocks, the last ``window / block`` blocks up
   to the token's own, and of the rest those of largest ``R_b``, ``topk``
   blocks in all, ties towards the lower block
   (``sparse_attention.select_topk``: exact);
5. a softmax over the tokens ``i <= t`` of the kept blocks with the real
   k and v. Before ``dense_len`` every block up to the token's own is
   kept: causal attention.

**Decode.** :func:`block_select_decode` keeps the running sum of each
slot's open page, writes a page's mean when the token that fills it
arrives, gathers the slot's page means by its table and selects:
``block_select``, a Pallas TPU kernel, everything laid along the lanes
as the pages are (a slot a grid step scores its page means by one matmul
a KV head, ``q . C_j`` their mean over a key's pages; the last step
takes every (slot, KV head)'s top-k at once, a unit a row, a block's
score and its selection at the lane of its first page), or the same
steps in plain XLA.
:func:`block_decode_attention` then writes the token's k and v and
attends under that selection: ``block_walk``, a Pallas TPU kernel by the
page walk's scheme (one program, one list of compute steps, a step sized
by its bytes) whose unit is a (slot, KV head) and which COPIES THE
SELECTED BLOCKS ONLY, a block of one head a copy, or ``gather``, plain
XLA over every page with the selection as a mask.

**The pool's contract.** *A block's pages are one aligned ascending
run*: columns ``ratio b .. ratio b + ratio - 1`` of every slot's table
hold the ids ``p, p + 1, .., p + ratio - 1`` with ``p % ratio == 0``
(``generation.KVBooks`` hands the "full" pool of a model that selects
blocks out a run at a time). The pool is head-major, ``[L, Hkv, P, page,
d]``, so a block of one head is ONE region of it, ``[ratio, page, d]``
from its first page on, and the walk reads it with one descriptor where
a page at a time took ``ratio`` (a layer's call of 16 slots is its
descriptors' issue on the scalar core: PERF.md section 6, PR 71). The
walk knows a block by its first page alone; ``gather`` reads the table
column by column and holds for any table, which is why the tests
compare the two on tables of runs.

**Prefill.** :func:`prefill_block_select` selects for a block of queries
at a time (XLA); :func:`block_prefill_attention` is ``block_flash``, a
forward flash attention a KV head's group at a time that takes the
selection block by block beside causality and widens it to tokens inside
the kernel (one small matmul a step for the whole group), or the same in
plain XLA a block of queries at a time.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .paged_attention import (
    _NEG_INF, _step_list, gather_decode_attention, pageable, walk_step_tokens,
)
from .sparse_attention import _INT_MIN, _blocks, _select, _sortable, select_topk

# A prefill's blocks: queries that are scored and selected together
# (XLA), and the restricted flash attention's queries and keys a step.
_SELECT_Q_BLOCK = 512
_FLASH_Q_BLOCK, _FLASH_K_BLOCK = 256, 2048
_XLA_Q_BLOCK = 128
_FORCED = 1e30
_VMEM_LIMIT = 96 * 1024 * 1024


class BlockSizes(NamedTuple):
    """The selection's sizes, in tokens but ``init`` and ``topk``
    (blocks): a compressed key covers ``kernel`` tokens and the next
    starts ``stride`` on; a block is ``block`` tokens; the first ``init``
    blocks and the last ``window`` tokens' blocks are always kept, ``topk``
    blocks in all; a token before ``dense_len`` attends to everything."""

    kernel: int
    stride: int
    block: int
    init: int
    window: int
    topk: int
    dense_len: int

    @property
    def span(self) -> int:
        """Pages a compressed key covers."""
        return self.kernel // self.stride

    @property
    def ratio(self) -> int:
        """Pages a block holds."""
        return self.block // self.stride

    @property
    def local(self) -> int:
        """Blocks of the window that is always kept."""
        return self.window // self.block

    def most_pages(self) -> int:
        """The most pages a token attends over in one KV head: the
        selection's, or before ``dense_len`` every page."""
        return max(self.topk * self.ratio, -(-self.dense_len // self.stride))

    def check(self, page: int) -> None:
        if (self.stride != page or self.kernel % page or self.block % page
                or self.window % self.block or self.topk < 1):
            raise ValueError(
                f"block selection {self}: the compressed keys' stride is "
                f"the pool's page ({page}), a key and a block are whole "
                f"pages and the window whole blocks")


def page_means(k, page: int):
    """k [S, Hkv, d] -> [S // page, Hkv, d] float32, each page's mean."""
    S = k.shape[0]
    return k.astype(jnp.float32).reshape(
        (S // page, page) + k.shape[1:]).mean(axis=1)


def select_blocks(q, means, t, sizes: BlockSizes):
    """[N, Hkv, blocks] bool: the blocks each of N queries keeps, a KV
    head its own. q [N, H, d]; means [N, P, Hkv, d] each query's page
    means in the order of its sequence, or [1, P, Hkv, d] one sequence's
    for all of them; t [N] each query's position. Steps 1-4 of the
    module docstring; ``blocks = ceil(P / ratio)``."""
    N, H, d = q.shape
    P, Hkv = means.shape[1:3]
    span, ratio = sizes.span, sizes.ratio
    n_blocks = -(-P // ratio)
    qg = q.astype(jnp.float32).reshape(N, Hkv, H // Hkv, d)
    with jax.named_scope("blocks.score"):
        mf = means.astype(jnp.float32)
        n_keys = P - span + 1
        keys = sum(mf[:, i:i + n_keys] for i in range(span)) / span
        if means.shape[0] == 1:
            s = jnp.einsum("nkgd,pkd->nkgp", qg, keys[0])
        else:
            s = jnp.einsum("nkgd,npkd->nkgp", qg, keys)
        # Key j exists for a token that has seen its last page whole.
        exists = (jnp.arange(n_keys)[None, :]
                  < ((t + 1) // sizes.stride - (span - 1))[:, None])
        exists = exists[:, None, None, :]
        p = jax.nn.softmax(jnp.where(exists, s * d ** -0.5, _NEG_INF), axis=-1)
        r = jnp.where(exists[:, :, 0], p.sum(axis=2), -1.0)   # [N, Hkv, keys]
        # Block b's keys are j = ratio b - (span - 1) .. ratio b + ratio - 1.
        reach = ratio + span - 1
        padded = jnp.pad(
            r, ((0, 0), (0, 0),
                (span - 1, ratio * n_blocks + reach - (span - 1) - n_keys)),
            constant_values=-1.0)
        score = functools.reduce(jnp.maximum, (
            padded[..., i::ratio][..., :n_blocks] for i in range(reach)))
    with jax.named_scope("blocks.select"):
        own = (t // sizes.block)[:, None, None]
        b = jnp.arange(n_blocks)[None, None, :]
        seen = jnp.broadcast_to(b <= own, score.shape)
        forced = (b < sizes.init) | (b > own - sizes.local)
        chosen = select_topk(jnp.where(forced, _FORCED, score), sizes.topk,
                             seen)
        return jnp.where((t < sizes.dense_len)[:, None, None], seen, chosen)


# ---------------------------------------------------------------- decode


def block_select_decode(q, k_new, means, sums, layer, page_table, lengths,
                        active, *, sizes: BlockSizes):
    """The selection of each slot's new token at position ``lengths[b]``.
    q [B, H, d] its queries and k_new [B, Hkv, d] its key; ``means`` [Lm,
    P, Hkv * d] the pool of page means (the k/v pool's pages, at the same
    ids) and ``sums`` [Lm, B, Hkv * d] float32 the running sum of each
    slot's open page, both at ``layer``. An active slot's sum takes the
    key (a page's first token starts it afresh) and, where the token
    fills its page, the page's mean is written: a page that is not full
    has no row that anything reads. Returns ([B, Hkv, Pmax] bool, the
    pages of the slot's table each KV head keeps, up to the token's own;
    means; sums), the selection by the path :func:`block_select_path`
    names."""
    B, H, d = q.shape
    Hkv = k_new.shape[1]
    page = sizes.stride
    n_pool = means.shape[1]
    with jax.named_scope("blocks.mean"):
        at = lengths % page
        key = k_new.astype(jnp.float32).reshape(B, Hkv * d)
        held = sums[layer]
        total = jnp.where((at == 0)[:, None], key, held + key)
        sums = sums.at[layer].set(jnp.where(active[:, None], total, held))
        # Slots whose page is not filled aim past the pool: dropped.
        fills = active & (at == page - 1)
        pid = jnp.where(fills, page_table[jnp.arange(B), lengths // page],
                        n_pool)
        means = means.at[layer, pid].set(
            (total / page).astype(means.dtype), mode="drop")
    # A layer's slice, then the slot's rows of it: one gather out of the
    # whole pool (rows at ``layer * P + page``) spares the slice's copy
    # and measured 0.2 ms a layer SLOWER on the chip (PERF.md section 6).
    rows = jnp.take(means[layer], page_table, axis=0)     # [B, Pmax, Hkv*d]
    if block_select_path(page, d, page_table.shape[1],
                         sizes) == "block_select":
        kept = paged_block_select(q, rows, lengths, sizes=sizes)
    else:
        kept = pages_of(select_blocks(
            q, rows.reshape(B, page_table.shape[1], Hkv, d), lengths, sizes),
            page_table.shape[1], lengths, sizes)
    return kept, means, sums


def pages_of(selected, pages: int, lengths, sizes: BlockSizes):
    """[B, Hkv, blocks] bool, the blocks each (slot, KV head) keeps ->
    [B, Hkv, pages] bool, their pages up to the new token's own."""
    kept = jnp.repeat(selected, sizes.ratio, axis=-1)[..., :pages]
    return kept & (jnp.arange(pages)[None, None, :]
                   <= (lengths // sizes.stride)[:, None, None])


def _block_select_kernel(len_ref, q_ref, m_ref, t_ref, o_ref, r_s, *,
                         sizes: BlockSizes):
    """Grid (B,). len_ref [B] in SMEM; q_ref [Hkv, G, D] this slot's
    queries; m_ref [P, Hkv * D] its page means in the order of its
    sequence; t_ref [B * Hkv, 1] int32 every (slot, KV head)'s position;
    o_ref [B * Hkv, P] int32, 1 at the pages each keeps; r_s [B * Hkv,
    P] float32 VMEM, every unit's summed probabilities. Everything lies
    along the lanes as pages do: a block's score and its selection at
    the lane of its first page. A slot's step scores its keys; the LAST
    step selects for every unit at once, a unit a row: the top-k is 43
    counts each waiting for the one before, and 32 rows cost what one
    does."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Hkv, G, D = q_ref.shape
    P = m_ref.shape[0]
    span, ratio = sizes.span, sizes.ratio
    b = pl.program_id(0)

    def shifted(x, by, fill):
        """x[.., j + by] at lane j; ``fill`` where that is off the end."""
        if by == 0:
            return x
        at = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1) + by
        return jnp.where((at >= 0) & (at < P),
                         pltpu.roll(x, (-by) % P, x.ndim - 1), fill)

    def exists(t, lane):
        """Key j exists for a token that has seen its last page whole."""
        return lane < (t + 1) // sizes.stride - (span - 1)

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, P), 1)
    seen_keys = exists(len_ref[b], lane)
    for g in range(Hkv):
        # q . C_j is the mean of q . M_j .. q . M_(j + span - 1).
        s = jax.lax.dot_general(
            q_ref[g], m_ref[:, g * D:(g + 1) * D], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # [G, P]
        s = sum(shifted(s, i, 0.0) for i in range(span)) * (
            D ** -0.5 / span)
        s = jnp.where(seen_keys, s, _NEG_INF)
        e = jnp.exp(s - s.max(axis=1, keepdims=True))
        p = e / e.sum(axis=1, keepdims=True)
        r_s[pl.ds(b * Hkv + g, 1), :] = p.sum(axis=0, keepdims=True)

    @pl.when(b == pl.num_programs(0) - 1)
    def _select_all():
        t = t_ref[...]                                    # [U, 1]
        lanes = jax.lax.broadcasted_iota(jnp.int32, r_s.shape, 1)
        r = jnp.where(exists(t, lanes), r_s[...], -1.0)
        score = functools.reduce(jnp.maximum, (
            shifted(r, i, -1.0) for i in range(1 - span, ratio)))
        own = t // sizes.block
        # ``ratio`` is a power of two (``selectable``).
        block = jnp.right_shift(lanes, ratio.bit_length() - 1)
        seen = ((lanes & (ratio - 1)) == 0) & (block <= own)
        forced = (block < sizes.init) | (block > own - sizes.local)
        key = jnp.where(seen, _sortable(jnp.where(forced, _FORCED, score)),
                        _INT_MIN)

        def count(mask):
            return mask.astype(jnp.int32).sum(axis=1, keepdims=True)

        chosen = _select(key, jnp.minimum(sizes.topk, own + 1), lanes, count,
                         P.bit_length())
        # (int32: a select between two masks does not lower.)
        chosen = jnp.where(t < sizes.dense_len, seen.astype(jnp.int32),
                           chosen.astype(jnp.int32))
        kept = functools.reduce(jnp.maximum, (
            shifted(chosen, -i, 0) for i in range(ratio)))
        o_ref[...] = jnp.where(lanes <= t // sizes.stride, kept, 0)


def paged_block_select(q, rows, lengths, *, sizes: BlockSizes,
                       interpret: bool = False):
    """The selection kernel: q [B, H, d], ``rows`` [B, P, Hkv * d] each
    slot's page means by its table, ``lengths`` [B] -> [B, Hkv, P] bool,
    the pages each (slot, KV head) keeps."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, D = q.shape
    P = rows.shape[1]
    Hkv = rows.shape[2] // D
    lengths = lengths.astype(jnp.int32)
    out = pl.pallas_call(
        functools.partial(_block_select_kernel, sizes=sizes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((None, Hkv, H // Hkv, D),
                             lambda b, *_: (b, 0, 0, 0)),
                pl.BlockSpec((None, P, Hkv * D), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec((B * Hkv, 1), lambda b, *_: (0, 0))],
            out_specs=pl.BlockSpec((None, None, B * Hkv, P),
                                   lambda b, *_: (0, 0, 0, 0)),
            scratch_shapes=[pltpu.VMEM((B * Hkv, P), jnp.float32)],
        ),
        # One int32 output of four dimensions: no other kernel's name in
        # a trace.
        out_shape=jax.ShapeDtypeStruct((1, 1, B * Hkv, P), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 2 ** 20),
        interpret=interpret,
    )(lengths, q.astype(rows.dtype).reshape(B, Hkv, H // Hkv, D), rows,
      jnp.repeat(lengths, Hkv)[:, None])
    return out.reshape(B, Hkv, P) != 0


def block_select_path(page: int, head_dim: int, pages: int,
                      sizes: BlockSizes) -> str:
    """``"block_select"`` or ``"xla"``: how a decode step selects here."""
    from .flash_attention import _on_tpu

    return "block_select" if _on_tpu() and selectable(
        page, head_dim, pages, sizes) else "xla"


def selectable(page: int, head_dim: int, pages: int,
               sizes: BlockSizes) -> bool:
    """Whether the selection kernel tiles these shapes: the pool's own
    condition, a table of whole lane tiles of pages, and blocks of a
    power of two of pages (a block is found by a shift)."""
    return (pageable(page, head_dim) and pages % 128 == 0
            and sizes.ratio & (sizes.ratio - 1) == 0)


def _block_walk_kernel(pid_ref, nb_ref, last_ref, len_ref, layer_ref,
                       unit_ref, at_ref, total_ref, q_ref, kn_ref, vn_ref,
                       k_hbm, v_hbm, o_ref, k_out, v_out, k_buf, v_buf, sems,
                       *, cap: int, ratio: int, scale: float):
    """One program for every (slot, KV head): a unit. In SMEM: pid_ref
    [U * cap] the id in the pool of the first page of each block a unit
    keeps, in the order of the sequence (the last is the block of the
    new token); nb_ref [U] how many blocks, last_ref [U] the pages of
    the last one up to the token's own; len_ref [B]; layer_ref [1];
    unit_ref [steps] (the unit of each compute step, every unit's steps
    in one list), at_ref [U] (where a unit's steps start in it),
    total_ref [1]. q_ref/o_ref [B, Hkv, G, D] a unit's group of query
    rows; kn_ref/vn_ref [B, Hkv, 1, D] its new K/V row; k_hbm/v_hbm the
    pools [L, Hkv, P, page, D] left in HBM and k_out/v_out the same
    buffers as outputs; k_buf/v_buf [2, step's pages, page, D] VMEM, a
    block ``ratio`` pages of it; sems [3, 2] DMA (k and v in by buffer,
    then k and v back)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, Hkv, G, D = q_ref.shape
    _, step_pages, page, _ = k_buf.shape
    step_blocks = step_pages // ratio
    layer = layer_ref[0]
    total = total_ref[0]

    # The page walk's reason: rows no copy has written meet a
    # probability of 0, and 0 times a NaN is a NaN. A unit's last block
    # is copied whole, the pages behind the token's own with it: they
    # are the slot's (a block is one run, reserved whole), hold zeros or
    # an earlier request's finite rows, and lie behind ``attends``.
    v_buf[...] = jnp.zeros(v_buf.shape, v_buf.dtype)
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    def step(g):
        """The list's step g: (its unit, which of the unit's steps it
        is, the blocks it holds, where they start in ``pid_ref``)."""
        u = unit_ref[g]
        i = g - at_ref[u]
        held = jnp.minimum(step_blocks, nb_ref[u] - i * step_blocks)
        return u, i, held, u * cap + i * step_blocks

    def block_copies(head, pid, j, buf):
        """The block of ``head`` whose first page is ``pid``, in both
        pools, into block j of ``buf``: its pages are one aligned
        ascending run of ids (generation.KVBooks), so one region of
        the head-major pool and ONE descriptor a pool."""
        run = pl.ds(pl.multiple_of(pid, ratio), ratio)
        pages = pl.ds(pl.multiple_of(j * ratio, ratio), ratio)
        return (pltpu.make_async_copy(
                    k_hbm.at[layer, head, run], k_buf.at[buf, pages],
                    sems.at[0, buf]),
                pltpu.make_async_copy(
                    v_hbm.at[layer, head, run], v_buf.at[buf, pages],
                    sems.at[1, buf]))

    def start(g, unrolled=True):
        inside = g < total
        u, _, held, first = step(jnp.where(inside, g, 0))
        held = jnp.where(inside, held, 0)
        head = jax.lax.rem(u, Hkv)
        buf = g % 2

        def start_block(j, _):
            for copy in block_copies(head, pid_ref[first + j], j, buf):
                copy.start()
            return 0

        if not unrolled:
            jax.lax.fori_loop(0, held, start_block, 0)
            return

        @pl.when(held == step_blocks)
        def _whole():
            # Runs of eight descriptors: a whole step's (32 blocks of a
            # head, k and v) in one straight run would be the program's
            # length for nothing.
            run = 8 if step_blocks % 8 == 0 else 1

            def start_run(r, _):
                for j in range(run):
                    start_block(r * run + j, 0)
                return 0

            jax.lax.fori_loop(0, step_blocks // run, start_run, 0)

        @pl.when(held < step_blocks)
        def _part():
            jax.lax.fori_loop(0, held, start_block, 0)

    def wait(held, buf):
        @pl.when(held == step_blocks)
        def _whole():
            # One wait a pool for all the step's copies: a semaphore
            # counts bytes, whichever copies brought them.
            for ref, sem in ((k_buf, 0), (v_buf, 1)):
                pltpu.make_async_copy(ref.at[buf], ref.at[buf],
                                      sems.at[sem, buf]).wait()

        @pl.when(held < step_blocks)
        def _part():
            def wait_block(j, _):
                for copy in block_copies(0, 0, j, buf):
                    copy.wait()
                return 0
            jax.lax.fori_loop(0, held, wait_block, 0)

    start(0, unrolled=False)

    def body(g, carry):
        u, i, held, first = step(g)
        b, head = jax.lax.div(u, jnp.int32(Hkv)), jax.lax.rem(u, Hkv)
        buf = g % 2
        length = len_ref[b]
        start(g + 1)
        wait(held, buf)

        # The unit's last page, in its last step, takes the new row and
        # goes back to the pool while the step computes: one page, as
        # the token's own is one.
        block_new = nb_ref[u] - 1 - i * step_blocks
        last = block_new < step_blocks
        block_new = jnp.where(last, block_new, 0)
        page_new = block_new * ratio + last_ref[u] - 1
        pid_new = pid_ref[first + block_new] + last_ref[u] - 1
        write_back = [
            pltpu.make_async_copy(k_buf.at[buf, page_new],
                                  k_out.at[layer, head, pid_new],
                                  sems.at[2, 0]),
            pltpu.make_async_copy(v_buf.at[buf, page_new],
                                  v_out.at[layer, head, pid_new],
                                  sems.at[2, 1]),
        ]

        @pl.when(last)
        def _new_row():
            is_new = jax.lax.broadcasted_iota(
                jnp.int32, (page, D), 0) == length % page
            for ref, new in ((k_buf, kn_ref), (v_buf, vn_ref)):
                rows = ref[buf, page_new]
                ref[buf, page_new] = jnp.where(is_new, new[b, head], rows)
            for copy in write_back:
                copy.start()

        m, l, acc = carry
        m = jnp.where(i == 0, _NEG_INF, m)
        l = jnp.where(i == 0, 0.0, l)
        acc = jnp.where(i == 0, 0.0, acc)
        # A page is whole tiles of the buffer: its rows one after another.
        k = k_buf[buf].reshape(step_pages * page, D)
        v = v_buf[buf].reshape(step_pages * page, D)
        s = jax.lax.dot_general(
            q_ref[b, head], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # [G, step]
        # Every page but the unit's last is full and before the token.
        at = i * step_pages * page + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        pages = (nb_ref[u] - 1) * ratio + last_ref[u]
        attends = at < (pages - 1) * page + length % page + 1
        s = jnp.where(attends, s * scale, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        prob = jnp.exp(s - m_new)
        l = alpha * l + prob.sum(axis=1, keepdims=True)
        acc = acc * alpha + jnp.dot(prob.astype(v.dtype), v,
                                    preferred_element_type=jnp.float32)

        @pl.when(last)
        def _done():
            o_ref[b, head] = (acc / l).astype(o_ref.dtype)
            for copy in write_back:
                copy.wait()

        return m_new, l, acc

    m0 = jnp.full((G, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((G, 1), jnp.float32)
    acc0 = jnp.zeros((G, D), jnp.float32)
    jax.lax.fori_loop(0, total, body, (m0, l0, acc0))


def selected_pages(kept, page_table, lengths, active, sizes: BlockSizes):
    """From the pages each (slot, KV head) keeps, [B, Hkv, Pmax] bool
    (:func:`block_select_decode`'s: whole blocks up to the new token's
    own page), the kept blocks in the order of the sequence: (the id in
    the pool of each block's FIRST page [B, Hkv, cap], how many blocks
    [B, Hkv], the pages of the last one up to the token's own [B, Hkv]);
    ``cap`` is ``sizes.most_pages()`` in blocks. A block's pages are one
    aligned ascending run of ids (generation.KVBooks), so its first says
    where all of it lies. None of an inactive slot. A block's first page
    says whether it is kept, so what is put in order is blocks, a
    quarter as many."""
    B, Hkv, pages = kept.shape
    ratio = sizes.ratio
    blocks = kept[..., ::ratio]
    n_blocks = blocks.shape[-1]
    cap = min(-(-sizes.most_pages() // ratio), n_blocks)
    held = jnp.sort(jnp.where(blocks & active[:, None, None],
                              jnp.arange(n_blocks, dtype=jnp.int32),
                              n_blocks), axis=-1)[..., :cap]
    count = (held < n_blocks).sum(axis=-1)
    # The last kept block is the token's own (always kept).
    last = jnp.where(count > 0,
                     (lengths // sizes.stride % ratio + 1)[:, None], 0)
    first = jnp.take_along_axis(
        page_table[:, None, :], jnp.minimum(held * ratio, pages - 1), axis=-1)
    return (first.astype(jnp.int32), count.astype(jnp.int32),
            last.astype(jnp.int32))


def paged_block_decode_attention(q, k_new, v_new, k_pool, v_pool, layer,
                                 page_table, lengths, active, selected, *,
                                 sizes: BlockSizes, interpret: bool = False):
    """The block walk. Arguments and results as
    :func:`block_decode_attention`; ``page_table`` holds each block's
    pages as one aligned ascending run of ids."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, D = q.shape
    _, Hkv, _, page, _ = k_pool.shape
    dtype = k_pool.dtype
    ratio = sizes.ratio
    first, n_blocks, last = selected_pages(selected, page_table, lengths,
                                           active, sizes)
    cap = first.shape[-1]
    # A step is sized by its bytes and holds whole blocks.
    step_blocks = max(walk_step_tokens(
        2 * D * jnp.dtype(dtype).itemsize, page, cap * ratio)
        // sizes.block, 1)
    scalars = [last.reshape(-1), lengths.astype(jnp.int32),
               jnp.reshape(layer, (1,)).astype(jnp.int32),
               *_step_list(n_blocks.reshape(-1), step_blocks, cap)]
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    kv_buf = pltpu.VMEM((2, step_blocks * ratio, page, D), dtype)
    n_scalars = 2 + len(scalars)
    out, k_pool, v_pool = pl.pallas_call(
        functools.partial(_block_walk_kernel, cap=cap, ratio=ratio,
                          scale=D ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_scalars,
            grid=(1,),
            in_specs=[whole, whole, whole, hbm, hbm],
            out_specs=[whole, hbm, hbm],
            scratch_shapes=[kv_buf, kv_buf, pltpu.SemaphoreType.DMA((3, 2))],
        ),
        # A unit's rows in four dimensions beside the pools' five: the
        # page walk writes three and five.
        out_shape=[jax.ShapeDtypeStruct((B, Hkv, H // Hkv, D), q.dtype),
                   jax.ShapeDtypeStruct(k_pool.shape, dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, dtype)],
        # Operands count the eight prefetched scalars: the pools are 11
        # and 12.
        input_output_aliases={n_scalars + 3: 1, n_scalars + 4: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(first.reshape(-1), n_blocks.reshape(-1), *scalars,
      q.astype(dtype).reshape(B, Hkv, H // Hkv, D),
      k_new.astype(dtype)[:, :, None], v_new.astype(dtype)[:, :, None],
      k_pool, v_pool)
    return out.reshape(B, H, D), k_pool, v_pool


def block_tokens(selected, tokens: int, sizes: BlockSizes):
    """[.., blocks] bool -> [.., tokens] bool: each block's tokens."""
    return jnp.repeat(selected, sizes.block, axis=-1)[..., :tokens]


def gather_block_decode_attention(q, k_new, v_new, k_pool, v_pool, layer,
                                  page_table, lengths, active, selected, *,
                                  sizes: BlockSizes):
    """The XLA path: every page gathered, the selection a mask."""
    return gather_decode_attention(
        q, k_new, v_new, k_pool, v_pool, layer, page_table, lengths, active,
        selected=jnp.repeat(selected, k_pool.shape[3], axis=-1))


def block_walk_path(page: int, head_dim: int) -> str:
    """``"block_walk"`` or ``"gather"``: what
    :func:`block_decode_attention` runs for this pool here."""
    from .flash_attention import _on_tpu

    return ("block_walk" if _on_tpu() and pageable(page, head_dim)
            else "gather")


def block_decode_attention(q, k_new, v_new, k_pool, v_pool, layer,
                           page_table, lengths, active, selected, *,
                           sizes: BlockSizes):
    """Write each active slot's ``k_new``/``v_new`` row [B, Hkv, D] into
    the pools [L, Hkv, P, page, D] at ``layer`` and position
    ``lengths[b]``, and attend the queries [B, H, D], a KV head's group
    over the tokens ``<= lengths[b]`` of the pages that ``selected`` [B,
    Hkv, Pmax] keeps for it (:func:`block_select_decode`'s): (attention
    [B, H, D], k_pool, v_pool), by the path :func:`block_walk_path`
    names."""
    page, D = k_pool.shape[3:]
    path = (paged_block_decode_attention
            if block_walk_path(page, D) == "block_walk"
            else gather_block_decode_attention)
    return path(q, k_new, v_new, k_pool, v_pool, layer, page_table, lengths,
                active, selected, sizes=sizes)


# --------------------------------------------------------------- prefill


def prefill_block_select(q, means, *, sizes: BlockSizes):
    """The selection of every token of one prompt: q [S, H, d] and the
    prompt's :func:`page_means` [S / page, Hkv, d] -> [Hkv, S, blocks]
    bool (``blocks = S / block``), a block of queries at a time. For
    :func:`block_prefill_attention` alone."""
    S = q.shape[0]
    means = means[None]                                   # [1, P, Hkv, d]
    block = min(S, _SELECT_Q_BLOCK)

    def one(args):
        qb, start = args
        return select_blocks(qb, means,
                             start + jnp.arange(block, dtype=jnp.int32), sizes)

    out = jax.lax.map(one, (_blocks(q, block), jnp.arange(S // block) * block))
    return out.reshape((S,) + out.shape[2:]).transpose(1, 0, 2)


def _attention_xla(q, k, v, selected, scale: float, sizes: BlockSizes):
    """q [S, H, D], k, v [S, Hkv, D], selected [Hkv, S, blocks] bool."""
    S, H, D = q.shape
    Hkv = k.shape[1]
    block = min(S, _XLA_Q_BLOCK)
    key_at = jnp.arange(S)

    def one(args):
        qb, sel, start = args                             # sel [Hkv, block, nb]
        keep = (block_tokens(sel, S, sizes)
                & (key_at[None, :] <= (start + jnp.arange(block))[:, None]))
        s = jnp.einsum("qkgd,tkd->kgqt", qb.reshape(block, Hkv, H // Hkv, D),
                       k, preferred_element_type=jnp.float32) * scale
        p = jax.nn.softmax(jnp.where(keep[:, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p.astype(v.dtype), v)

    out = jax.lax.map(one, (
        _blocks(q, block),
        selected.reshape(Hkv, S // block, block, -1).transpose(1, 0, 2, 3),
        jnp.arange(S // block) * block))
    return out.reshape(S, H, D)


def _block_flash_kernel(q_ref, k_ref, v_ref, sel_ref, o_ref, m_s, l_s, acc_s,
                        *, scale: float, block: int):
    """Grid (Hkv, S / bq, S / bk), the keys innermost. q_ref/o_ref [G,
    bq, D] a KV head's group of query heads; k_ref, v_ref [bk, D];
    sel_ref [bq, bk / block] int8, which of this step's blocks each
    query keeps; running max and denominator [G, bq, 1] and accumulator
    [G, bq, D] float32 VMEM."""
    from jax.experimental import pallas as pl

    qi, kj = pl.program_id(1), pl.program_id(2)
    G, bq, _ = q_ref.shape
    bk = k_ref.shape[0]
    n_blocks = sel_ref.shape[1]

    @pl.when(kj == 0)
    def _start():
        m_s[...] = jnp.full(m_s.shape, _NEG_INF, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    # A step of keys wholly behind the causal diagonal holds nothing
    # (and was not fetched: the index maps stop at the diagonal).
    @pl.when(kj * bk <= qi * bq + bq - 1)
    def _step():
        # The blocks widened to tokens, once for the whole group: block
        # c's column of the selection to the tokens c block .. c block +
        # block - 1 of the step, by one matmul.
        first = block * jax.lax.broadcasted_iota(
            jnp.int32, (n_blocks, bk), 0)
        token = jax.lax.broadcasted_iota(jnp.int32, (n_blocks, bk), 1)
        widen = ((token >= first) & (token < first + block)).astype(
            jnp.bfloat16)
        kept = sel_ref[...].astype(jnp.int32).astype(jnp.float32)
        keep = jnp.dot(kept.astype(jnp.bfloat16), widen,
                       preferred_element_type=jnp.float32) > 0.5
        q_at = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_at = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        keep &= k_at <= q_at
        k, v = k_ref[...], v_ref[...]
        for g in range(G):
            s = jax.lax.dot_general(
                q_ref[g], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(keep, s, _NEG_INF)
            m = m_s[g]
            m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            # A row with nothing kept so far has m_new = -1e30 and
            # exp(0) = 1 for every masked entry: hence the second mask.
            p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
            l_s[g] = alpha * l_s[g] + p.sum(axis=1, keepdims=True)
            acc_s[g] = alpha * acc_s[g] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_s[g] = m_new

    @pl.when(kj == pl.num_programs(2) - 1)
    def _done():
        l = l_s[...]
        o_ref[...] = (acc_s[...] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


def _attention_kernel(q, k, v, selected, scale: float, sizes: BlockSizes,
                      interpret: bool = False):
    """q [S, H, D], k, v [S, Hkv, D], selected [Hkv, S, blocks] bool."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, D = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    bq, bk = min(S, _FLASH_Q_BLOCK), min(S, _FLASH_K_BLOCK)
    n_blocks = bk // sizes.block
    # [Hkv, S / bk, S, bk / block]: a step's blocks last, whole.
    tiles = selected.reshape(Hkv, S, S // bk, n_blocks).transpose(
        0, 2, 1, 3).astype(jnp.int8)

    def upto(i, j):
        # Past the diagonal the last step needed again: no new copy.
        return jnp.minimum(j, (i * bq + bq - 1) // bk)

    out = pl.pallas_call(
        functools.partial(_block_flash_kernel, scale=scale,
                          block=sizes.block),
        grid=(Hkv, S // bq, S // bk),
        in_specs=[
            pl.BlockSpec((None, G, bq, D), lambda h, i, j: (h, 0, i, 0)),
            pl.BlockSpec((None, bk, D), lambda h, i, j: (h, upto(i, j), 0)),
            pl.BlockSpec((None, bk, D), lambda h, i, j: (h, upto(i, j), 0)),
            pl.BlockSpec((None, None, bq, n_blocks),
                         lambda h, i, j: (h, upto(i, j), i, 0))],
        out_specs=pl.BlockSpec((None, G, bq, D), lambda h, i, j: (h, 0, i, 0)),
        scratch_shapes=[pltpu.VMEM((G, bq, 1), jnp.float32),
                        pltpu.VMEM((G, bq, 1), jnp.float32),
                        pltpu.VMEM((G, bq, D), jnp.float32)],
        # One output of four dimensions: no other kernel's name in a
        # trace (the sparse flash writes three, the selection's tiles
        # are int8).
        out_shape=jax.ShapeDtypeStruct((Hkv, G, S, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(q.reshape(S, Hkv, G, D).transpose(1, 2, 0, 3), k.transpose(1, 0, 2),
      v.transpose(1, 0, 2), tiles)
    return out.transpose(2, 0, 1, 3).reshape(S, H, D)


def prefill_path(tokens: int, head_dim: int, sizes: BlockSizes) -> str:
    """``"block_flash"`` or ``"xla"``: how a prefill of ``tokens``
    attends under its selection here."""
    from .flash_attention import _on_tpu

    step = min(tokens, _FLASH_K_BLOCK)
    return ("block_flash" if _on_tpu() and head_dim % 128 == 0
            and tokens % step == 0 and tokens % min(tokens, _FLASH_Q_BLOCK) == 0
            and step % sizes.block == 0 else "xla")


def block_prefill_attention(q, k, v, selected, *, sizes: BlockSizes):
    """Causal attention of one prompt's q [S, H, D] over k, v [S, Hkv,
    D], a KV head's group over the tokens of the blocks ``selected``
    (:func:`prefill_block_select`'s) keeps for each query. Returns [S,
    H, D]."""
    S, _, D = q.shape
    path = (_attention_kernel if prefill_path(S, D, sizes) == "block_flash"
            else _attention_xla)
    return path(q, k, v, selected, D ** -0.5, sizes)
