"""Single-token decode attention over a paged KV pool.

The pool (models/generation.py PagedKVCache) is head-major,
``[Hkv, P, page, Dh]`` a layer; a slot's tokens live in the pages its
row of ``page_table`` names, position ``lengths[b]`` holding the token
being decoded (the caller has scattered it in, so it attends).

Two implementations, one chosen by :func:`decode_attention_path` from
what the code can see (platform and shape), never by a user:

``page_walk`` — a Pallas TPU kernel, one program a slot. The pool stays
in HBM; the program copies the slot's own pages, ``_BLOCK_TOKENS`` at a
time and double-buffered, into VMEM and runs an online softmax over
them. Its reads and arithmetic follow ``lengths``: a slot walks
``lengths[b] // page + 1`` pages (rounded up to a block), an inactive
slot none. All KV heads of a slot are served by one program from the
slot's ``[H, Dh]`` queries: per KV head one bf16 matmul of all H query
rows against that head's block, of which the rows of its own GQA group
are kept — so K and V are never repeated, and no operand is narrower
than a tile. Operands go to the MXU in the pool's dtype with float32
scores; running max, denominator and accumulator are float32.

``gather`` — plain XLA, for any platform and shape (tier-1 runs it on
CPU): gather every slot's ``Pmax`` pages, attend densely with the GQA
group as a dimension of the queries, mask by length. Its work is in
proportion to ``B * Pmax * page`` whatever ``lengths`` says.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_NEG_INF = -1e30
# Tokens a compute step covers: long enough that a layer is tens of
# grid steps and some hundreds of copies, short enough that a chat
# context of a few hundred tokens is not mostly padding.
_BLOCK_TOKENS = 128


def _page_walk_kernel(pt_ref, np_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
                      k_buf, v_buf, sems, *, pmax: int, scale: float):
    """Grid (B,). pt_ref [B * Pmax], np_ref [B] (pages to walk), len_ref
    [B] in SMEM; q_ref/o_ref [H, D] this slot's rows; k_hbm/v_hbm the
    pool [Hkv, P, page, D] left in HBM; k_buf/v_buf [2, Hkv, block, D]
    VMEM; sems [2, 2] DMA (k/v, buffer)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    H, D = q_ref.shape
    _, Hkv, block, _ = k_buf.shape
    page = k_hbm.shape[2]
    pages_per_block = block // page
    n_pages = np_ref[b]
    n_blocks = (n_pages + pages_per_block - 1) // pages_per_block
    length = len_ref[b]

    def copies(i, buf):
        """The block's page copies. Past the slot's last page the last
        one is read again: the buffer then never holds anything but
        pool rows, so a masked probability of 0 meets no stale NaN."""
        out = []
        for j in range(pages_per_block):
            p = jnp.minimum(i * pages_per_block + j, n_pages - 1)
            pid = pt_ref[b * pmax + p]
            rows = pl.ds(j * page, page)
            out.append(pltpu.make_async_copy(
                k_hbm.at[:, pid], k_buf.at[buf, :, rows, :],
                sems.at[0, buf]))
            out.append(pltpu.make_async_copy(
                v_hbm.at[:, pid], v_buf.at[buf, :, rows, :],
                sems.at[1, buf]))
        return out

    @pl.when(n_blocks > 0)
    def _first():
        for c in copies(0, 0):
            c.start()

    q = q_ref[...]
    # own[h]: the query rows of KV head h's GQA group.
    head = jax.lax.broadcasted_iota(jnp.int32, (H, 1), 0) // (H // Hkv)
    own = [head == h for h in range(Hkv)]

    def body(i, carry):
        m, l, acc = carry
        buf = i % 2

        @pl.when(i + 1 < n_blocks)
        def _next():
            for c in copies(i + 1, 1 - buf):
                c.start()

        for c in copies(i, buf):
            c.wait()
        k = k_buf[buf]                                # [Hkv, block, D]
        v = v_buf[buf]
        s = jnp.zeros((H, block), jnp.float32)
        for h in range(Hkv):
            s_h = jax.lax.dot_general(
                q, k[h], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)   # [H, block]
            s = jnp.where(own[h], s_h, s)
        t = i * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(t <= length, s * scale, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        prob = jnp.exp(s - m_new)
        l = alpha * l + prob.sum(axis=1, keepdims=True)
        prob = prob.astype(v.dtype)
        pv = jnp.zeros((H, D), jnp.float32)
        for h in range(Hkv):
            pv_h = jnp.dot(prob, v[h],
                           preferred_element_type=jnp.float32)  # [H, D]
            pv = jnp.where(own[h], pv_h, pv)
        return m_new, l, acc * alpha + pv

    m0 = jnp.full((H, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((H, 1), jnp.float32)
    acc0 = jnp.zeros((H, D), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, acc0))
    # A slot that walked nothing (inactive) writes zeros.
    o_ref[...] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def paged_decode_attention(
    q: jax.Array,           # [B, H, D] one query row per slot
    k_pool: jax.Array,      # [Hkv, P, page, D]
    v_pool: jax.Array,
    page_table: jax.Array,  # [B, Pmax] int32
    lengths: jax.Array,     # [B] int32 — key positions <= lengths[b] attend
    active: jax.Array,      # [B] bool — an inactive slot walks no page
    *,
    interpret: bool = False,
) -> jax.Array:
    """The page-walk kernel. Returns [B, H, D]; rows of inactive slots
    are zeros."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, D = q.shape
    Hkv, _, page, _ = k_pool.shape
    Pmax = page_table.shape[1]
    pages_per_block = max(1, _BLOCK_TOKENS // page)
    n_pages = jnp.where(
        active, jnp.minimum(lengths // page + 1, Pmax), 0
    ).astype(jnp.int32)
    slot_rows = pl.BlockSpec((None, H, D), lambda b, *_: (b, 0, 0))
    kv_buf = pltpu.VMEM((2, Hkv, pages_per_block * page, D), k_pool.dtype)
    kernel = functools.partial(
        _page_walk_kernel, pmax=Pmax, scale=D ** -0.5)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[
                slot_rows,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=slot_rows,
            scratch_shapes=[kv_buf, kv_buf, pltpu.SemaphoreType.DMA((2, 2))],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(page_table.reshape(-1).astype(jnp.int32), n_pages,
      lengths.astype(jnp.int32), q.astype(k_pool.dtype), k_pool, v_pool)


def gather_decode_attention(q, k_pool, v_pool, page_table, lengths):
    """The XLA path. Same arguments and result as the kernel, bar
    ``active`` (an inactive slot's row is computed and discarded)."""
    B, H, D = q.shape
    Hkv, _, page, _ = k_pool.shape
    T = page_table.shape[1] * page
    k = jnp.take(k_pool, page_table, axis=1).reshape(Hkv, B, T, D)
    v = jnp.take(v_pool, page_table, axis=1).reshape(Hkv, B, T, D)
    qg = q.reshape(B, Hkv, H // Hkv, D)
    s = jnp.einsum("bhgd,hbtd->bhgt", qg, k,
                   preferred_element_type=jnp.float32) * (D ** -0.5)
    attends = jnp.arange(T)[None, :] <= lengths[:, None]      # [B, T]
    s = jnp.where(attends[:, None, None], s, -jnp.inf)
    prob = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhgt,hbtd->bhgd", prob, v).reshape(B, H, D)


def pageable(page: int, head_dim: int) -> bool:
    """Whether the kernel tiles these shapes: head_dim a multiple of the
    128 lanes, and a page a whole number of bf16 sublane tiles (so a
    page's copy lands on tile boundaries of the block buffer)."""
    return head_dim % 128 == 0 and page % 16 == 0


def decode_attention_path(page: int, head_dim: int) -> str:
    """``"page_walk"`` or ``"gather"``: what :func:`decode_attention`
    runs for this pool here. ``LLMEngine.stats()`` reports it."""
    from .flash_attention import _on_tpu

    return "page_walk" if _on_tpu() and pageable(page, head_dim) \
        else "gather"


def decode_attention(q, k_pool, v_pool, page_table, lengths, active):
    """[B, H, D] queries against the paged pool, by the path
    :func:`decode_attention_path` names."""
    _, _, page, D = k_pool.shape
    if decode_attention_path(page, D) == "page_walk":
        return paged_decode_attention(
            q, k_pool, v_pool, page_table, lengths, active)
    return gather_decode_attention(q, k_pool, v_pool, page_table, lengths)
