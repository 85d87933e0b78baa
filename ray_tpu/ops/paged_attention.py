"""Single-token decode over a paged KV pool: write the token, attend.

The pool (models/generation.py PagedKVCache, the one KV cache) is
head-major and whole, ``[L, Hkv, P, page, Dh]``; a slot's tokens live in
the pages its row of ``page_table`` names. :func:`decode_attention` is
what ``paged_decode`` hands the one transformer block (``llama.block``)
as its ``attend``, and the one place a decode step touches the pool:
at layer ``layer`` it puts each active slot's new K/V row at position
``lengths[b]`` (page ``page_table[b, lengths[b] // page]``, row
``lengths[b] % page``), attends over positions ``0 .. lengths[b]`` and
hands both pools on.

A layer with an attention ``window`` keeps no more than the window: a
slot's row of that pool's ``page_table`` is a RING of
:func:`ring_pages` columns, page ``p`` of the sequence lying in column
``p % columns``, so the page a new row opens is the one whose rows all
left the window. Such a step attends over positions ``lengths[b] + 1 -
window .. lengths[b]``: it starts at the page that holds the first of
them, and masks a row by its position in the sequence. The host never
touches the ring while a slot decodes.

Two implementations, one chosen by :func:`decode_attention_path` from
what the code can see (platform and shape), never by a user:

``page_walk`` — a Pallas TPU kernel, one program for all slots. Both
pools stay in HBM and come back as outputs aliased to the inputs; the
program copies each walking slot's own pages at ``layer`` into VMEM, a
COMPUTE STEP at a time and double-buffered, and runs an online softmax
over them. A step is as long as its bytes say, about a megabyte of K and
V (:func:`walk_step_tokens`: 512 tokens at 4 KV heads of 128, 256 at 8,
128 at 16), so that what a step costs beside its bytes (the turn of the
loop, the waits, one serial softmax) is paid once a megabyte whatever
the heads. The copies stay a page each and follow ``lengths``: a slot
copies ``lengths[b] // page + 1`` pages (under a window, the pages its
window touches), each once, an inactive slot none; a step that is not
full copies what it holds and the mask covers the rest of its buffer.
The slots' steps form ONE list that the program's one loop runs down, so
while a slot's last step computes, the next walking slot's first copies
are in flight, and a slot that walks nothing costs nothing. The last of
a slot's pages is the one the new row belongs to:
the program sets the row in VMEM before the scores and copies that one
page (``[Hkv, page, Dh]``, a whole tile a head) back to the pool. That
is a decode step's only write to the pool, and an inactive slot makes
none. It is made here because no XLA write of one row leaves the pool
where it is: a row is a sixteenth of a bf16 ``(16, 128)`` tile, and for
a scatter or a ``dynamic_update_slice`` of it XLA re-lays the whole
pool so that a row is a tile, and back (compiled for a v5e: four
pool-sized copies a step; with the pool sliced by the layer scan, 70%
of the step: PERF.md §6, PR 29). Slots own disjoint pages and the list
runs in order, so one slot's write meets no other slot's reads. All KV
heads of a slot are served from the slot's ``[H, Dh]``
queries: per KV head one bf16 matmul of all H query rows against that
head's step, of which the rows of its own GQA group are kept — so K
and V are never repeated, and no operand is narrower than a tile.
Operands go to the MXU in the pool's dtype with float32 scores; running
max, denominator and accumulator are float32.

Heads of 64 (PR 73). A page of one head of 64 is ``(16, 64)``, half a
lane tile, which the device pads to a whole one: the pool, and every
copy of the walk, at twice the bytes the model states. Where the KV
heads pair up the pool therefore lays two to a row (:func:`pool_row`:
``[L, Hkv / 2, P, page, 128]``, heads ``2j | 2j + 1`` side by side), a
page of a pair is the tile a page of one head of 128 is, and the walk
runs the SAME program over it, copies, buffers and step length those of
``Hkv / 2`` heads of 128. Only the operands are laid for it, outside the
kernel (``_to_lanes``, ``_from_lanes``): a query goes to its own KV
head's half of a 128-wide row, zeros in the other, so that its product
with a pair's row is its product with its own head's key; the
probabilities multiply the pair's whole value rows and each head keeps
its half. The MXU multiplies the zeros too, half its work wasted, in a
kernel that waits for its copies; the bytes are the model's, 2 x Hkv x
64 a token, no row padded. Heads of 128 take the program they always
did.

``gather`` — plain XLA, for any platform and shape (tier-1 runs it on
CPU): scatter the rows into the whole pool, gather every slot's
``Pmax`` pages of the layer, attend densely with the GQA group as a
dimension of the queries, mask by length. It copies the pool, and its
work is in proportion to ``B * Pmax * page`` whatever ``lengths`` says.

A LATENT pool (latent attention, ``llama.latent_proj``) holds one row a
token for all heads, ``[L, P, page, W]``: the latent, the shared rotary
key, zeros up to whole lane tiles. :func:`latent_decode_attention`
attends every head's absorbed query, laid as such a row, over the
slot's rows; the scores are ``q . row`` over all W and the values the
rows' first ``values``, so a row is read once for both. ``latent_walk``
walks such a pool as the page walk walks k and v, by the same scheme:
one program for all slots down one list of compute steps
(:func:`_step_list`), a step as long as its bytes say
(:func:`walk_step_tokens` of a row's bytes: 1,024 tokens of a bfloat16
row of 640), one copy a page with every "head" in it and each page
once, one wait a whole step, the next walking slot's first copies in
flight while a slot's last step computes, the new row set in VMEM in
the slot's last step and its page copied back through the aliased
output. A step is two matmuls of all H query rows, no GQA group to
pick: 2 H (W + values) operations a row of 2 W bytes, some 60 a byte at
32 heads over 576 + 512, where a k/v walk does 2. With ``selected`` a
slot walks the same pages and attends to the selected positions alone
(``sparse_walk``). ``gather`` is its XLA path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

_NEG_INF = -1e30
# Bytes of K and V a compute step of the page walk covers
# (``walk_step_tokens``): at 128 tokens a step whatever the heads the
# walk paid a step's fixed costs (the turn of its loop, the waits, a
# serial softmax) every 262 KB at 4 KV heads and ran at 40% of HBM's
# rate (PERF.md §6, PR 58).
_STEP_BYTES = 1 << 20


def _page_walk_kernel(pt_ref, np_ref, len_ref, layer_ref, slot_ref, at_ref,
                      total_ref, *refs, pmax: int, scale: float, window=None):
    """One program for every slot. In SMEM: pt_ref [B * Pmax], np_ref
    [B] (pages to walk), len_ref [B], layer_ref [1], slot_ref [G] (the
    slot of each compute step, every walking slot's steps in one list),
    at_ref [B] (where a slot's steps start in that list), total_ref [1]
    (the steps in all) and, with a ``window``, first_ref [B] (the page a
    walk starts at). q_ref/o_ref [B, H, D] every slot's rows;
    kn_ref/vn_ref [B, Hkv, 1, D] their new K/V rows; k_hbm/v_hbm the
    pools [L, Hkv, P, page, D] left in HBM and k_out/v_out the same
    buffers as outputs; k_buf/v_buf [2, Hkv, step, D] VMEM; sems [3, 2]
    DMA (k and v in by buffer, then k and v back)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    first_ref = None
    if window is not None:
        first_ref, *refs = refs
    (q_ref, kn_ref, vn_ref, k_hbm, v_hbm, o_ref, k_out, v_out,
     k_buf, v_buf, sems) = refs
    _, H, D = q_ref.shape
    _, Hkv, block, _ = k_buf.shape
    page = k_hbm.shape[3]
    step_pages = block // page
    layer = layer_ref[0]
    total = total_ref[0]

    # A step copies the pages it holds and no other, so rows of its
    # buffer may be what no copy has written, and a probability of 0
    # times a NaN is a NaN: v's buffers start as zeros (k's rows there
    # only make scores that the mask replaces). A slot that walks
    # nothing (inactive) gets zeros.
    v_buf[...] = jnp.zeros(v_buf.shape, v_buf.dtype)
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    def step(g):
        """The list's step g: (its slot, which of the slot's steps it
        is, the pages it holds, the table column of the first)."""
        b = slot_ref[g]
        i = g - at_ref[b]
        held = jnp.minimum(step_pages, np_ref[b] - i * step_pages)
        column = i * step_pages
        if window is not None:
            # The slot's row of the table is a ring: page p of the
            # sequence lies in column p % Pmax, and the walk starts at
            # the page that holds the oldest position the new token
            # still attends to.
            column = column + jax.lax.rem(first_ref[b], pmax)
        return b, i, held, column

    def table_cell(b, column):
        if window is not None:
            column = jnp.where(column >= pmax, column - pmax, column)
        return pt_ref[b * pmax + column]

    def page_copies(pid, j, buf):
        """Page ``pid`` of both pools into page j of buffer ``buf``."""
        rows = pl.ds(pl.multiple_of(j * page, page), page)
        return (pltpu.make_async_copy(
                    k_hbm.at[layer, :, pid], k_buf.at[buf, :, rows, :],
                    sems.at[0, buf]),
                pltpu.make_async_copy(
                    v_hbm.at[layer, :, pid], v_buf.at[buf, :, rows, :],
                    sems.at[1, buf]))

    def start(g, unrolled=True):
        """Start the copies of the list's step g, if it has one: the
        pages the step holds, each once. A whole step's are ``unrolled``
        (one straight run of descriptors), a part's are a loop."""
        inside = g < total
        b, _, held, column = step(jnp.where(inside, g, 0))
        held = jnp.where(inside, held, 0)
        buf = g % 2

        def start_page(j, _):
            for copy in page_copies(table_cell(b, column + j), j, buf):
                copy.start()
            return 0

        if not unrolled:
            jax.lax.fori_loop(0, held, start_page, 0)
            return

        @pl.when(held == step_pages)
        def _whole():
            jax.lax.fori_loop(0, step_pages, start_page, 0, unroll=True)

        @pl.when(held < step_pages)
        def _part():
            jax.lax.fori_loop(0, held, start_page, 0)

    def wait(held, buf):
        @pl.when(held == step_pages)
        def _whole():
            # One wait a pool for all the step's copies: a semaphore
            # counts bytes, whichever copies brought them.
            for ref, sem in ((k_buf, 0), (v_buf, 1)):
                pltpu.make_async_copy(ref.at[buf], ref.at[buf],
                                      sems.at[sem, buf]).wait()

        @pl.when(held < step_pages)
        def _part():
            def wait_page(j, _):
                for copy in page_copies(0, j, buf):
                    copy.wait()
                return 0
            jax.lax.fori_loop(0, held, wait_page, 0)

    # own[h]: the query rows of KV head h's GQA group.
    head = jax.lax.broadcasted_iota(jnp.int32, (H, 1), 0) // (H // Hkv)
    own = [head == h for h in range(Hkv)]

    # Once a call, so a plain loop: unrolled here as well it would only
    # lengthen the program's lowering (a set-up cost, PERF.md §6, PR 58).
    start(0, unrolled=False)

    def body(g, carry):
        b, i, held, column = step(g)
        buf = g % 2
        length = len_ref[b]
        # The list runs on over the slots: while this step computes,
        # the copies of the next are in flight, be it the next slot's
        # first.
        start(g + 1)
        wait(held, buf)

        # The walk's last page, in its last step, takes the new row and
        # goes back to the pool while the step computes.
        page_new = np_ref[b] - 1 - i * step_pages
        last = page_new < step_pages
        page_new = jnp.where(last, page_new, 0)
        rows_new = pl.ds(pl.multiple_of(page_new * page, page), page)
        pid_new = table_cell(b, column + page_new)
        write_back = [
            pltpu.make_async_copy(k_buf.at[buf, :, rows_new, :],
                                  k_out.at[layer, :, pid_new],
                                  sems.at[2, 0]),
            pltpu.make_async_copy(v_buf.at[buf, :, rows_new, :],
                                  v_out.at[layer, :, pid_new],
                                  sems.at[2, 1]),
        ]

        @pl.when(last)
        def _new_row():
            is_new = jax.lax.broadcasted_iota(
                jnp.int32, (Hkv, page, D), 1) == length % page
            for ref, new in ((k_buf, kn_ref), (v_buf, vn_ref)):
                rows = ref[buf, :, rows_new, :]
                ref[buf, :, rows_new, :] = jnp.where(is_new, new[b], rows)
            for copy in write_back:
                copy.start()

        # A slot's first step starts its softmax afresh.
        m, l, acc = carry
        m = jnp.where(i == 0, _NEG_INF, m)
        l = jnp.where(i == 0, 0.0, l)
        acc = jnp.where(i == 0, 0.0, acc)
        q = q_ref[b]
        s = jnp.zeros((H, block), jnp.float32)
        for h in range(Hkv):
            s_h = jax.lax.dot_general(
                q, k_buf[buf, h], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)   # [H, block]
            s = jnp.where(own[h], s_h, s)
        t = i * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if window is None:
            attends = t <= length
        else:
            t = first_ref[b] * page + t
            attends = (t <= length) & (t > length - window)
        s = jnp.where(attends, s * scale, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        prob = jnp.exp(s - m_new)
        l = alpha * l + prob.sum(axis=1, keepdims=True)
        prob = prob.astype(v_buf.dtype)
        pv = jnp.zeros((H, D), jnp.float32)
        for h in range(Hkv):
            pv_h = jnp.dot(prob, v_buf[buf, h],
                           preferred_element_type=jnp.float32)  # [H, D]
            pv = jnp.where(own[h], pv_h, pv)
        acc = acc * alpha + pv

        @pl.when(last)
        def _done():
            o_ref[b] = (acc / l).astype(o_ref.dtype)
            for copy in write_back:
                copy.wait()

        return m_new, l, acc

    m0 = jnp.full((H, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((H, 1), jnp.float32)
    acc0 = jnp.zeros((H, D), jnp.float32)
    jax.lax.fori_loop(0, total, body, (m0, l0, acc0))


def paged_decode_attention(
    q: jax.Array,           # [B, H, D] one query row per slot
    k_new: jax.Array,       # [B, Hkv, D] the token's K row per slot
    v_new: jax.Array,
    k_pool: jax.Array,      # [L, Hkv, P, page, D]
    v_pool: jax.Array,
    layer: jax.Array,       # [] int32 — the pools' layer to use
    page_table: jax.Array,  # [B, Pmax] int32
    lengths: jax.Array,     # [B] int32 — the new row's position
    active: jax.Array,      # [B] bool — an inactive slot walks no page
    *,
    window: Optional[int] = None,
    interpret: bool = False,
):
    """The page-walk kernel. Returns ([B, H, D], k_pool, v_pool): rows
    of inactive slots are zeros, the pools are the arguments' buffers
    with the active slots' rows written. The pools' rows may hold two
    heads of 64 side by side (``pool_row``), q, k_new and v_new then
    being 64 wide; scores are scaled by the heads' own width. With
    ``window`` a slot's row of the table is a ring (module docstring)
    and the walk starts at the page of position ``lengths[b] + 1 - window``, masking the rows
    before it; ``window=None`` is the walk over everything."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, width = q.shape
    _, Hkv, _, page, D = k_pool.shape
    # Heads narrower than the pool's rows lie side by side in them
    # (``pool_row``): the queries go to their own head's lanes.
    q, k_new, v_new = _to_lanes(q, k_new, v_new, Hkv, D)
    Pmax = page_table.shape[1]
    dtype = k_pool.dtype
    block = walk_step_tokens(
        2 * Hkv * D * jnp.dtype(dtype).itemsize, page, Pmax)
    step_pages = block // page
    if window is None:
        n_pages, windowed = jnp.minimum(lengths // page + 1, Pmax), []
    else:
        first = jnp.maximum(lengths + 1 - window, 0) // page
        n_pages, windowed = lengths // page + 1 - first, [
            first.astype(jnp.int32)]
    n_pages = jnp.where(active, n_pages, 0).astype(jnp.int32)
    scalars = [lengths.astype(jnp.int32),
               jnp.reshape(layer, (1,)).astype(jnp.int32),
               *_step_list(n_pages, step_pages, Pmax), *windowed]
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    kv_buf = pltpu.VMEM((2, Hkv, block, D), dtype)
    kernel = functools.partial(
        _page_walk_kernel, pmax=Pmax, scale=width ** -0.5, window=window)
    n_scalars = 2 + len(scalars)
    out, k_pool, v_pool = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_scalars,
            grid=(1,),
            in_specs=[whole, whole, whole, hbm, hbm],
            out_specs=[whole, hbm, hbm],
            scratch_shapes=[kv_buf, kv_buf, pltpu.SemaphoreType.DMA((3, 2))],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, H, D), q.dtype),
                   jax.ShapeDtypeStruct(k_pool.shape, dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, dtype)],
        # Operands count the prefetched scalars: with seven of them the
        # pools are 10 and 11.
        input_output_aliases={n_scalars + 3: 1, n_scalars + 4: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(page_table.reshape(-1).astype(jnp.int32), n_pages, *scalars,
      q.astype(dtype), k_new.astype(dtype)[:, :, None],
      v_new.astype(dtype)[:, :, None], k_pool, v_pool)
    return _from_lanes(out, width, Hkv), k_pool, v_pool


def pool_row(kv_heads: int, head_dim: int):
    """(heads, width) of a token's k (or v) row as a pool lays it,
    ``[L, heads, P, page, width]``: the model's KV heads of ``head_dim``,
    but heads of 64 two to a row of 128 where they pair up, KV heads
    ``2j`` and ``2j + 1`` side by side (module docstring: why). An odd
    number of KV heads of 64, and every other width, lies as it is."""
    pack = 2 if head_dim == 64 and kv_heads % 2 == 0 else 1
    return kv_heads // pack, head_dim * pack


def _lane_of(H: int, rows: int, pack: int) -> jax.Array:
    """[H, pack] bool: the part of its KV head's row of the pool that
    query head h's own KV head lies in (``rows`` rows of ``pack``)."""
    kv_head = jnp.arange(H) // (H // (rows * pack))
    return (kv_head % pack)[:, None] == jnp.arange(pack)


def _to_lanes(q, k_new, v_new, rows: int, lanes: int):
    """The walk's operands for a pool whose rows hold ``lanes // D``
    heads side by side (``pool_row``): each query [B, H, D] in its own KV
    head's part of a row of ``lanes`` and zeros in the rest, so that its
    product with the row is its product with its own head's key; the new
    k and v rows [B, Hkv, D] as the pool lays them, [B, rows, lanes].
    Heads as wide as the pool's rows come back as they are."""
    B, H, D = q.shape
    pack = lanes // D
    if pack == 1:
        return q, k_new, v_new
    q = jnp.where(_lane_of(H, rows, pack)[None, :, :, None], q[:, :, None],
                  0).reshape(B, H, lanes)
    return q, k_new.reshape(B, rows, lanes), v_new.reshape(B, rows, lanes)


def _from_lanes(out, D: int, rows: int):
    """Of the walk's [B, H, lanes], probabilities times whole rows, each
    head's own part [B, H, D]."""
    B, H, lanes = out.shape
    pack = lanes // D
    if pack == 1:
        return out
    return jnp.where(_lane_of(H, rows, pack)[None, :, :, None],
                     out.reshape(B, H, pack, D), 0).sum(axis=2)


def _latent_walk_kernel(pt_ref, np_ref, len_ref, layer_ref, slot_ref, at_ref,
                        total_ref, q_ref, new_ref, *refs, pmax: int,
                        scale: float, selected: bool = False):
    """One program for every slot, the page walk's scheme over one pool
    of rows. In SMEM: pt_ref [B * Pmax], np_ref [B] (pages to walk),
    len_ref [B], layer_ref [1], slot_ref [G], at_ref [B], total_ref [1]
    (the list of compute steps, :func:`_step_list`). q_ref [B, H, W]
    every slot's absorbed queries, laid as rows; new_ref [B, 1, W] their
    new rows; with ``selected`` one more input behind new_ref, sel_ref
    [B, steps, step] float32: a slot attends to the positions where it
    is not 0 (ops/sparse_attention.py). pool_hbm the pool [L, P, page,
    W] left in HBM and pool_out the same buffer as an output; o_ref
    [B, H, values] (under a selection [B, 1, H, values]); buf [2, step,
    W] VMEM; sems [2, 2] DMA (rows in by buffer, then the new row's
    page back)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    sel_ref = None
    if selected:
        sel_ref, *refs = refs
    pool_hbm, o_ref, pool_out, buf, sems = refs
    _, H, _ = q_ref.shape
    values = o_ref.shape[-1]
    _, block, _ = buf.shape
    page = pool_hbm.shape[2]
    step_pages = block // page
    layer = layer_ref[0]
    total = total_ref[0]

    # A step copies the pages it holds and no other, and a row is both
    # key and value: the buffers start as zeros, so that a masked
    # probability of 0 meets a pool's row or a zero, never a NaN. A slot
    # that walks nothing (inactive) gets zeros.
    buf[...] = jnp.zeros(buf.shape, buf.dtype)
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    def step(g):
        """The list's step g: (its slot, which of the slot's steps it
        is, the pages it holds)."""
        b = slot_ref[g]
        i = g - at_ref[b]
        return b, i, jnp.minimum(step_pages, np_ref[b] - i * step_pages)

    def page_copy(pid, j, at):
        """Page ``pid`` of the pool into page j of buffer ``at``."""
        rows = pl.ds(pl.multiple_of(j * page, page), page)
        return pltpu.make_async_copy(
            pool_hbm.at[layer, pid], buf.at[at, rows, :], sems.at[0, at])

    def start(g, unrolled=True):
        """Start the copies of the list's step g, if it has one: the
        pages the step holds, each once. A whole step's are ``unrolled``
        (one straight run of descriptors), a part's are a loop."""
        inside = g < total
        b, i, held = step(jnp.where(inside, g, 0))
        held = jnp.where(inside, held, 0)
        first = b * pmax + i * step_pages

        def start_page(j, _):
            page_copy(pt_ref[first + j], j, g % 2).start()
            return 0

        if not unrolled:
            jax.lax.fori_loop(0, held, start_page, 0)
            return

        @pl.when(held == step_pages)
        def _whole():
            jax.lax.fori_loop(0, step_pages, start_page, 0, unroll=True)

        @pl.when(held < step_pages)
        def _part():
            jax.lax.fori_loop(0, held, start_page, 0)

    def wait(held, at):
        @pl.when(held == step_pages)
        def _whole():
            # One wait for all the step's copies: a semaphore counts
            # bytes, whichever copies brought them.
            pltpu.make_async_copy(buf.at[at], buf.at[at],
                                  sems.at[0, at]).wait()

        @pl.when(held < step_pages)
        def _part():
            def wait_page(j, _):
                page_copy(0, j, at).wait()
                return 0
            jax.lax.fori_loop(0, held, wait_page, 0)

    # Once a call, so a plain loop (the page walk's reason).
    start(0, unrolled=False)

    def body(g, carry):
        b, i, held = step(g)
        at = g % 2
        length = len_ref[b]
        # The list runs on over the slots: while this step computes,
        # the copies of the next are in flight, be it the next slot's
        # first.
        start(g + 1)
        wait(held, at)

        # The walk's last page, in its last step, takes the new row and
        # goes back to the pool while the step computes.
        page_new = np_ref[b] - 1 - i * step_pages
        last = page_new < step_pages
        page_new = jnp.where(last, page_new, 0)
        rows_new = pl.ds(pl.multiple_of(page_new * page, page), page)
        write_back = pltpu.make_async_copy(
            buf.at[at, rows_new, :],
            pool_out.at[layer, pt_ref[b * pmax + i * step_pages + page_new]],
            sems.at[1, 0])

        @pl.when(last)
        def _new_row():
            held_rows = buf[at, rows_new, :]
            is_new = jax.lax.broadcasted_iota(
                jnp.int32, held_rows.shape, 0) == length % page
            buf[at, rows_new, :] = jnp.where(is_new, new_ref[b], held_rows)
            write_back.start()

        # A slot's first step starts its softmax afresh.
        m, l, acc = carry
        m = jnp.where(i == 0, _NEG_INF, m)
        l = jnp.where(i == 0, 0.0, l)
        acc = jnp.where(i == 0, 0.0, acc)
        rows = buf[at]                                    # [step, W]
        s = jax.lax.dot_general(
            q_ref[b], rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # [H, step]
        t = i * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        attends = t <= length
        if sel_ref is not None:
            attends &= sel_ref[b, pl.ds(i, 1), :] != 0.0
        s = jnp.where(attends, s * scale, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        prob = jnp.exp(s - m_new)
        if sel_ref is not None:
            # A step with nothing selected leaves m_new at -1e30 and
            # exp(0) = 1 for what was masked.
            prob = jnp.where(attends, prob, 0.0)
        l = alpha * l + prob.sum(axis=1, keepdims=True)
        pv = jnp.dot(prob.astype(rows.dtype), rows[:, :values],
                     preferred_element_type=jnp.float32)  # [H, values]
        acc = acc * alpha + pv

        @pl.when(last)
        def _done():
            # Under a selection that selects nothing the slot gets zeros.
            out = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)
            o_ref[b] = out.reshape(o_ref.shape[1:])
            write_back.wait()

        return m_new, l, acc

    m0 = jnp.full((H, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((H, 1), jnp.float32)
    acc0 = jnp.zeros((H, values), jnp.float32)
    jax.lax.fori_loop(0, total, body, (m0, l0, acc0))


def paged_latent_decode_attention(
    q: jax.Array,           # [B, H, W] absorbed queries, laid as rows
    row_new: jax.Array,     # [B, W] the token's row per slot
    pool: jax.Array,        # [L, P, page, W]
    layer: jax.Array,       # [] int32
    page_table: jax.Array,  # [B, Pmax] int32
    lengths: jax.Array,     # [B] int32: the new row's position
    active: jax.Array,      # [B] bool: an inactive slot walks no page
    *,
    scale: float,
    values: int,
    selected: Optional[jax.Array] = None,   # [B, Pmax * page] float32
    interpret: bool = False,
):
    """The latent walk. Returns ([B, H, values], pool): rows of inactive
    slots are zeros, the pool is the argument's buffer with the active
    slots' rows written. With ``selected`` (``sparse_walk``) a slot
    walks the same pages and attends only to the positions where its
    row of ``selected`` is not 0."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, W = q.shape
    page = pool.shape[2]
    Pmax = page_table.shape[1]
    dtype = pool.dtype
    block = walk_step_tokens(W * jnp.dtype(dtype).itemsize, page, Pmax)
    n_pages = jnp.where(active, jnp.minimum(lengths // page + 1, Pmax),
                        0).astype(jnp.int32)
    scalars = [lengths.astype(jnp.int32),
               jnp.reshape(layer, (1,)).astype(jnp.int32),
               *_step_list(n_pages, block // page, Pmax)]
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    kernel = functools.partial(_latent_walk_kernel, pmax=Pmax, scale=scale,
                               selected=selected is not None)
    out_rows = jax.ShapeDtypeStruct((B, H, values), q.dtype)
    more = []
    if selected is not None:
        steps = -(-Pmax * page // block)
        more.append(jnp.pad(
            selected, ((0, 0), (0, steps * block - Pmax * page))
        ).reshape(B, steps, block))
        # Four dimensions, so that the reducer's name for this call is
        # not the latent walk's (three and four).
        out_rows = jax.ShapeDtypeStruct((B, 1, H, values), q.dtype)
    n_scalars = 2 + len(scalars)
    out, pool = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_scalars,
            grid=(1,),
            in_specs=[whole] * (2 + len(more)) + [hbm],
            out_specs=[whole, hbm],
            scratch_shapes=[
                pltpu.VMEM((2, block, W), dtype),
                pltpu.SemaphoreType.DMA((2, 2))],
        ),
        out_shape=[out_rows, jax.ShapeDtypeStruct(pool.shape, dtype)],
        # Operands count the seven prefetched scalars: the pool is 9,
        # behind a selection 10.
        input_output_aliases={n_scalars + 2 + len(more): 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(page_table.reshape(-1).astype(jnp.int32), n_pages, *scalars,
      q.astype(dtype), row_new.astype(dtype)[:, None], *more, pool)
    return out.reshape(B, H, values), pool


def gather_latent_decode_attention(q, row_new, pool, layer, page_table,
                                   lengths, active, *, scale, values,
                                   selected=None):
    """The XLA path: the kernel's arguments and results, bar that an
    inactive slot's row of the attention is computed (and discarded by
    the caller)."""
    B, _, W = q.shape
    _, n_pool, page, _ = pool.shape
    T = page_table.shape[1] * page
    # Inactive slots aim past the pool (``gather_decode_attention``).
    drop = jnp.where(active, page_table[jnp.arange(B), lengths // page],
                     n_pool)
    pool = pool.at[layer, drop, lengths % page].set(
        row_new.astype(pool.dtype), mode="drop")
    rows = jnp.take(pool[layer], page_table, axis=0).reshape(B, T, W)
    s = jnp.einsum("bhw,btw->bht", q, rows,
                   preferred_element_type=jnp.float32) * scale
    attends = jnp.arange(T)[None, :] <= lengths[:, None]
    if selected is not None:
        attends &= selected != 0.0
    s = jnp.where(attends[:, None], s, -jnp.inf)
    prob = jax.nn.softmax(s, axis=-1).astype(rows.dtype)
    return jnp.einsum("bht,btr->bhr", prob, rows[..., :values]), pool


def gather_decode_attention(q, k_new, v_new, k_pool, v_pool, layer,
                            page_table, lengths, active, *, window=None,
                            selected=None):
    """The XLA path: the kernel's arguments and results, bar that an
    inactive slot's row of the attention is computed (and discarded by
    the caller). With ``selected`` [B, Hkv, T] bool a KV head's group
    attends only to the positions where it is true
    (ops/block_attention.py)."""
    B, H, D = q.shape
    _, rows, n_pool, page, lanes = k_pool.shape
    # A pool's row may hold several heads side by side (``pool_row``).
    pack = lanes // D
    Hkv = rows * pack
    if pack > 1:
        k_new = k_new.reshape(B, rows, lanes)
        v_new = v_new.reshape(B, rows, lanes)
    Pmax = page_table.shape[1]
    T = Pmax * page
    last = lengths // page                     # the new row's page
    if window is None:
        column, position = last, jnp.arange(T)[None, :]
    else:
        # The ring: column c holds the newest page p <= last with
        # p % Pmax == c (negative: never written).
        column = last % Pmax
        held = last[:, None] - (last[:, None] - jnp.arange(Pmax)) % Pmax
        position = (held[:, :, None] * page
                    + jnp.arange(page)).reshape(B, T)
    # Inactive slots aim past the pool: -1 would WRAP to the last page
    # (NumPy semantics) and corrupt it; only >= n is truly dropped.
    drop = jnp.where(active, page_table[jnp.arange(B), column], n_pool)
    # A scalar beside index arrays across a slice: the cells are [B, Hkv, D].
    at = (layer, slice(None), drop, lengths % page)
    k_pool = k_pool.at[at].set(k_new.astype(k_pool.dtype), mode="drop")
    v_pool = v_pool.at[at].set(v_new.astype(v_pool.dtype), mode="drop")
    k = jnp.take(k_pool[layer], page_table, axis=1).reshape(rows, B, T, lanes)
    v = jnp.take(v_pool[layer], page_table, axis=1).reshape(rows, B, T, lanes)
    if pack > 1:
        k, v = (x.reshape(rows, B, T, pack, D).transpose(0, 3, 1, 2, 4)
                .reshape(Hkv, B, T, D) for x in (k, v))
    qg = q.reshape(B, Hkv, H // Hkv, D)
    s = jnp.einsum("bhgd,hbtd->bhgt", qg, k,
                   preferred_element_type=jnp.float32) * (D ** -0.5)
    attends = position <= lengths[:, None]                    # [B, T]
    if window is not None:
        attends &= (position > lengths[:, None] - window) & (position >= 0)
    attends = attends[:, None]                                # [B, 1|Hkv, T]
    if selected is not None:
        attends &= selected
    s = jnp.where(attends[:, :, None], s, -jnp.inf)
    prob = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    attn = jnp.einsum("bhgt,hbtd->bhgd", prob, v).reshape(B, H, D)
    return attn, k_pool, v_pool


def pageable(page: int, head_dim: int) -> bool:
    """Whether the kernel tiles these shapes: head_dim, the width of a
    pool's row (``pool_row``: heads of 64 lie two to a row of 128 where
    they pair up), a multiple of the 128 lanes, and a page a whole number
    of bf16 sublane tiles (so a page's copy lands on tile boundaries of
    the block buffer)."""
    return head_dim % 128 == 0 and page % 16 == 0


def walk_step_tokens(token_bytes: int, page: int, columns: int) -> int:
    """Tokens one compute step of a walk covers, from what the code can
    see of a pool and its table: about ``_STEP_BYTES`` of it, a token
    being ``token_bytes`` in one layer (a k and a v row of every KV
    head, ``2 * kv_heads * head_dim`` elements; one latent row, ``W``),
    so that a step's fixed costs are spread over as many bytes whatever
    the pool; never under the 128 lanes of one tile of scores; whole
    pages, the power of two of them nearest in ratio, and no more than
    the table's ``columns``, so that no step is longer than the longest
    walk. 512 tokens at 4 KV heads of 128 in bfloat16, 256 at 8, 128 at
    16, a megabyte each; 1,024 for a latent row of 640 (51 pages to the
    megabyte: 64, 1.3 MB, and not 32: the latent walk measured 9-14%
    faster at 1,024 than at 512 and no faster at 2,048, where the score
    tile ``[H, step]`` and its ``exp`` grow with the step; PERF.md §6,
    PR 63). ``LLMEngine.stats()`` reports it as
    ``page_walk_step_tokens`` and ``latent_walk_step_tokens``."""
    pages = max(_STEP_BYTES // (token_bytes * page), 128 // page, 1)
    power = 1 << (pages.bit_length() - 1)
    if pages * pages >= 2 * power * power:
        power *= 2
    return page * min(power, 1 << (columns.bit_length() - 1))


def _step_list(n_pages: jax.Array, step_pages: int, columns: int):
    """Every slot's compute steps in one list, slot after slot, for a
    walk's one loop to run down: (the slot of each step [G], where each
    slot's steps start in the list [B], the steps in all [1]), from the
    pages each slot walks ``n_pages`` [B]. A slot that walks nothing
    (inactive) is not in it."""
    B = n_pages.shape[0]
    n_steps = (n_pages + step_pages - 1) // step_pages
    ends = jnp.cumsum(n_steps)
    most = B * -(-columns // step_pages)
    slot_of = jnp.minimum(
        (ends[None, :] <= jnp.arange(most)[:, None]).sum(axis=1), B - 1)
    return [slot_of.astype(jnp.int32), (ends - n_steps).astype(jnp.int32),
            ends[-1:].astype(jnp.int32)]


def decode_attention_path(page: int, head_dim: int,
                          values: Optional[int] = None) -> str:
    """``"page_walk"`` or ``"gather"``: what :func:`decode_attention`
    runs here for a pool whose rows are ``head_dim`` wide (``pool_row``
    says how a model's heads lie in them); for a latent pool, whose rows
    are ``head_dim`` wide and hold ``values`` of latent,
    ``"latent_walk"`` or ``"gather"``: what
    :func:`latent_decode_attention` runs. ``LLMEngine.stats()`` reports
    it."""
    from .flash_attention import _on_tpu

    if not (_on_tpu() and pageable(page, head_dim)):
        return "gather"
    if values is None:
        return "page_walk"
    return "latent_walk" if values % 128 == 0 else "gather"


def ring_pages(window: int, page: int, max_pages: int) -> int:
    """Pages a slot holds in a window layer's pool, the columns of that
    pool's page table: the ``window`` positions a token attends to span
    at most ``ceil(window / page) + 1`` pages, and never more than the
    ``max_pages`` of the longest sequence."""
    return min(-(-window // page) + 1, max_pages)


def decode_attention(q, k_new, v_new, k_pool, v_pool, layer, page_table,
                     lengths, active, *, window=None):
    """Write each active slot's ``k_new``/``v_new`` row [B, Hkv, D] into
    the pools [L, Hkv, P, page, D] at ``layer`` and position
    ``lengths[b]``, and attend the queries [B, H, D] over positions ``0
    .. lengths[b]``, or with ``window`` over the last ``window`` of
    them: (attention [B, H, D], k_pool, v_pool), by the path
    :func:`decode_attention_path` names."""
    page, lanes = k_pool.shape[3:]
    path = (paged_decode_attention
            if decode_attention_path(page, lanes) == "page_walk"
            else gather_decode_attention)
    return path(q, k_new, v_new, k_pool, v_pool, layer, page_table,
                lengths, active, window=window)


def latent_decode_attention(q, row_new, pool, layer, page_table, lengths,
                            active, *, scale: float, values: int,
                            selected=None):
    """Write each active slot's ``row_new`` [B, W] into the latent pool
    [L, P, page, W] at ``layer`` and position ``lengths[b]``, and attend
    the absorbed queries [B, H, W] over rows ``0 .. lengths[b]``: scores
    ``scale * q . row``, values the rows' first ``values``. Returns
    (attention [B, H, values], pool), by the path
    :func:`decode_attention_path` names. With ``selected`` [B, T]
    float32 (ops/sparse_attention.index_select_decode's) a slot attends
    only to the positions where it is not 0: the same walk over every
    page, ``"sparse_walk"``."""
    page, W = pool.shape[2:]
    path = (paged_latent_decode_attention
            if decode_attention_path(page, W, values) == "latent_walk"
            else gather_latent_decode_attention)
    return path(q, row_new, pool, layer, page_table, lengths, active,
                scale=scale, values=values, selected=selected)
