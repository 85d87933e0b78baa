"""Flash attention as a Pallas TPU kernel.

The HBM-optimal attention path (SURVEY.md §5.7): QK^T logits never
materialize in HBM — each query block streams KV blocks through VMEM with
online-softmax accumulation (flash attention v2 schedule), so memory is
O(S·D) instead of O(S²) and both matmuls hit the MXU back-to-back. Causal
masking skips fully-masked KV blocks (the loop's bounds are computed per
query block), recovering the ~2x causal FLOP saving.

No counterpart exists in the reference — it delegates attention to user
frameworks; this framework owns its compute path. Falls back to the XLA
einsum implementation (ops/attention.py) off-TPU or for shapes the kernel
doesn't tile.

Training: the backward is a fused Pallas kernel pair (flash attention v2
backward schedule): the forward additionally emits the per-row logsumexp,
and two kernels recompute P block-wise in VMEM — one accumulating dQ over
KV blocks, one accumulating dK/dV over Q blocks — so the S^2 probability
matrix never hits HBM in either direction. The dK/dV kernel runs on a
KV-HEAD grid: all ``rep`` query heads of a GQA group stay resident in
VMEM and the group reduction happens in the f32 accumulator, so dK/dV is
written to HBM once per KV head (not per query head + external sum). A
per-query-head fallback kernel covers shapes whose grouped Q block would
not fit VMEM.

Precision (PR 44). Operands go to the MXU in the dtype they arrive in
and every matmul accumulates in float32 (``dot_general`` with
``preferred_element_type=float32``, contracting the last dimension of
both operands or the first of the right one: no explicit transpose). A
bfloat16 product is exact in float32, so the scores ``q·k`` and ``dO·v``
are what a float32 kernel would read from the same inputs up to the
order of summation; ``scale`` multiplies the float32 scores inside the
exponent, never a bfloat16 q. The left operand of each second matmul (the
probabilities ``p`` before ``p·v`` and ``pᵀ·dO``, ``ds`` before ``ds·k``
and ``dsᵀ·q``) is rounded to the value's dtype, as ops/attention.py does
before its second einsum. Running max, denominator, accumulators, ``lse``
and ``delta`` are float32. A float32 caller is computed in float32
throughout.

Masks. A kernel's loop is cut into the blocks that lie wholly inside the
causal (and window) region, which take no iota, compare or select, and
the blocks that the diagonal (or a window's lower edge) crosses, which
are masked in global coordinates (``q_offset``, ``kv_offset``).

Blocks follow the shape: ``choose_blocks`` picks each kernel's
(block_q, block_k) from the sequence lengths, widths, GQA group and
itemsize — the largest of 128..``_MAX_BLOCK`` that divide the sequence
and keep the kernel inside the VMEM a kernel gets without asking
(``_VMEM_BUDGET``: K and V of one head stay whole in VMEM,
double-buffered; for dK/dV the group's Q and dO), or None where 128 does
not divide (the einsum's shapes). Where one head's K and V alone are
over that budget (16,384 keys of 128 + 128 in bfloat16 are 16.8 MB
double-buffered) the FORWARD is the streamed form (``Blocks.streamed``,
``_flash_fwd_streamed``, PR 57): the grid's second dimension walks the
(query block, key block) pairs in which a query attends to a key
(``stream_visits``, made when the kernel is built), a visit brings one
key block into VMEM, and a window layer's query block is given only the
key blocks its window touches. Every shape that fits keeps the resident
form and its blocks. The sizes were read off a v5e (PERF.md
§6, PR 44): at b8 x 2048 the forward takes 13.9 ms at 128 x 128 and 4.0
at 512 x 512, dQ 11.5 and 3.9, dK/dV 15.4 and 5.1; what pays is the
step of the kernel's loop. ``block_q`` / ``block_k`` arguments override
the choice for all three kernels (the tests').
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

_NEG_INF = -1e30
_MIN_BLOCK = 128
# The largest block either way: past it a causal query block's diagonal
# blocks waste more matmul than the longer MXU runs win (PERF.md §6).
_MAX_BLOCK = 512
# What a kernel is counted to need (``_vmem_bytes``) stays under the
# 16 MiB of VMEM that a kernel gets without asking. Asking for more
# (``vmem_limit_bytes``) is not free outside the kernel: XLA then lays
# out the whole train step differently, and its peak HBM rises by one
# [8, 2048, 14336] activation, 234 MB (compiled for a v5e, PR 44).
_VMEM_BUDGET = int(15.5 * 1024 * 1024)

# dot_general dimension numbers: a·bᵀ (both contracted on their last
# dimension) and a·b.
_NT = (((1,), (1,)), ((), ()))
_NN = (((1,), (0,)), ((), ()))


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


class Blocks(NamedTuple):
    """(block_q, block_k) of the forward, the dQ and the dK/dV kernel,
    and whether the forward is the streamed form (``_flash_fwd_streamed``:
    K and V come a block at a time) and not the resident one."""
    fwd: Tuple[int, int]
    dq: Tuple[int, int]
    dkv: Tuple[int, int]
    streamed: bool = False


def _largest_block(n: int) -> int:
    """The largest of 128, 256, .. ``_MAX_BLOCK`` that divides ``n``
    (which 128 divides)."""
    b = _MIN_BLOCK
    while b * 2 <= _MAX_BLOCK and n % (b * 2) == 0:
        b *= 2
    return b


def _vmem_bytes(grid_block: int, loop_block: int, resident_rows: int,
                stat_rows: int, score_copies: float, d: int, dv: int,
                itemsize: int) -> int:
    """What a kernel is counted to hold in VMEM, within a MiB or so of
    what the compiler asks for at the benchmark's shapes and not under
    it (found by compiling for a v5e under a falling limit, PR 44): the
    rows that stay for a whole head (K and V, or the group's Q and dO)
    and the blocks that move with the grid (in and out), both
    double-buffered by the pipeline, a width padded to the 128 lanes;
    the float32 rows of lse and delta where they stay whole (dK/dV),
    which a [.., 1, rows] block pads to 8 sublanes; ``score_copies`` float32 temporaries of the scores'
    shape (s, p, dp, ds and the rounded copies: 4 are counted for the
    forward and dQ, 5.5 for dK/dV); the float32 accumulators."""
    lanes = -(-d // 128) * 128 + -(-dv // 128) * 128
    resident = 2 * resident_rows * lanes * itemsize
    stats = 2 * 2 * 8 * stat_rows * 4
    moving = 2 * 2 * grid_block * lanes * itemsize
    temporaries = int(score_copies * grid_block * loop_block * 4)
    accumulators = grid_block * lanes * 4
    return resident + stats + moving + temporaries + accumulators


def choose_blocks(sq: int, skv: int, d: int, dv: int, rep: int,
                  itemsize: int) -> Optional[Blocks]:
    """Each kernel's blocks from what ``flash_attention`` can see, or
    None where the kernel does not tile the shape. A pure function of its
    arguments (tests/test_flash_attention.py pins its table). Both blocks
    start at the largest that divides their sequence; over the budget,
    the block the GRID steps by halves first and the block the kernel's
    LOOP steps by stays long (on the chip the loop's step is what pays:
    forward 512 x 512 4.75 ms, 256 x 512 4.74, 512 x 256 6.33 at b8 x
    2048), then that one halves too."""
    if sq % _MIN_BLOCK or skv % _MIN_BLOCK:
        return None

    def fit(grid_rows, loop_rows, resident_rows, stat_rows, score_copies):
        grid, loop = _largest_block(grid_rows), _largest_block(loop_rows)
        while _vmem_bytes(grid, loop, resident_rows, stat_rows,
                          score_copies, d, dv, itemsize) > _VMEM_BUDGET:
            if grid > _MIN_BLOCK:
                grid //= 2
            elif loop > _MIN_BLOCK:
                loop //= 2
            else:
                break  # K and V alone are over: 128 x 128, as ever
        return grid, loop

    # The forward and dQ step their grid by query blocks and loop over
    # one head's K and V, which stay whole; dK/dV steps its grid by key
    # blocks and loops over the query blocks of the group's heads, whose
    # Q, dO, lse and delta stay whole.
    bq, bk = fit(sq, skv, skv, 0, 4)
    group = rep if _dkv_grouped(rep, sq, d, itemsize) else 1
    dkv_bk, dkv_bq = fit(skv, sq, group * sq, group * sq, 5.5)
    # One head's K and V whole, double-buffered, beside the smallest
    # blocks are over the budget (16,384 keys of 128 + 128 in bfloat16
    # are 16.8 MB): the forward streams them, and what stays in VMEM is
    # a key block where the resident form counts the head.
    streamed = _vmem_bytes(_MIN_BLOCK, _MIN_BLOCK, skv, 0, 4, d, dv,
                           itemsize) > _VMEM_BUDGET
    fwd = (bq, bk)
    if streamed:
        fwd = _largest_block(sq), _largest_block(skv)
        while _vmem_bytes(*fwd, fwd[1], 0, 4, d, dv,
                          itemsize) > _VMEM_BUDGET and fwd[0] > _MIN_BLOCK:
            fwd = fwd[0] // 2, fwd[1]
    return Blocks(fwd=fwd, dq=(bq, bk), dkv=(dkv_bq, dkv_bk),
                  streamed=streamed)


def _dkv_grouped(rep: int, sq: int, d: int, itemsize: int) -> bool:
    """Whether Q, dO, lse and delta of all ``rep`` query heads of a KV
    group fit in VMEM beside the smallest blocks: the dK/dV kernel then
    runs on a KV-head grid; else once a query head, summed outside."""
    return _vmem_bytes(_MIN_BLOCK, _MIN_BLOCK, rep * sq, rep * sq, 5.5, d,
                       d, itemsize) <= _VMEM_BUDGET


def _clip(x, lo, hi):
    """``jnp.clip``, or for plain ints a plain int (``stream_visits``
    reckons the same bounds when the kernel is built)."""
    if all(isinstance(i, int) for i in (x, lo, hi)):
        return max(lo, min(x, hi))
    return jnp.clip(x, lo, hi)


def _loop_bounds(q_first, block_q, kv_offset, block_k, nk, causal, window):
    """Of one query block (its first global position ``q_first``) the KV
    blocks to visit, [lo, hi), and inside them the run [a, b) that needs
    no mask: a block j holds keys kv_offset + j·block_k .. + block_k − 1;
    it needs no causal mask when its last key is at or before the first
    query, and no window mask when its first key is inside the last
    query's window."""
    if not causal:
        return 0, nk, 0, nk
    q_last = q_first + block_q - 1
    hi = _clip((q_last - kv_offset) // block_k + 1, 0, nk)
    unmasked_hi = (q_first - kv_offset + 1) // block_k
    if window is None:
        return 0, hi, 0, _clip(unmasked_hi, 0, hi)
    lo = _clip((q_first - window + 1 - kv_offset) // block_k, 0, hi)
    unmasked_lo = -((-(q_last - window + 1 - kv_offset)) // block_k)
    a = _clip(unmasked_lo, lo, hi)
    return lo, hi, a, _clip(unmasked_hi, a, hi)


def _attends(shape, q_axis, first_q, first_k, window):
    """The mask of one block in global coordinates: key position <= query
    position, and inside the window where there is one. ``q_axis`` says
    which axis of ``shape`` the queries lie on (the dK/dV kernel's scores
    are transposed)."""
    q_pos = first_q + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_pos = first_k + jax.lax.broadcasted_iota(jnp.int32, shape,
                                               1 - q_axis)
    attends = k_pos <= q_pos
    if window is not None:
        attends = attends & (k_pos > q_pos - window)
    return attends


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale: float,
                      causal: bool, q_offset: int, kv_offset: int,
                      block_k: int, window: Optional[int] = None):
    from jax.experimental import pallas as pl

    block_q = q_ref.shape[1]
    head_dim = v_ref.shape[2]  # the output's width is v's
    nk = k_ref.shape[1] // block_k
    q = q_ref[0]  # [Bq, D], the caller's dtype
    q_start = q_offset + pl.program_id(1) * block_q  # global position
    lo, hi, a, b = _loop_bounds(q_start, block_q, kv_offset, block_k, nk,
                                causal, window)

    def body(masked):
        def step(j, carry):
            m, l, acc = carry  # of the UNSCALED scores: scale > 0
            k = k_ref[0, pl.ds(j * block_k, block_k), :]
            v = v_ref[0, pl.ds(j * block_k, block_k), :]
            s = _dot(q, k, _NT)  # [Bq, Bk] float32 on the MXU
            if masked:
                s = jnp.where(
                    _attends(s.shape, 0, q_start,
                             kv_offset + j * block_k, window),
                    s, _NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
            p = jnp.exp((s - m_new) * scale)
            alpha = jnp.exp((m - m_new) * scale)
            l_new = alpha * l + p.sum(axis=1, keepdims=True)
            acc_new = acc * alpha + _dot(p.astype(v.dtype), v, _NN)
            return m_new, l_new, acc_new
        return step

    carry = (jnp.full((block_q, 1), _NEG_INF, dtype=jnp.float32),
             jnp.zeros((block_q, 1), dtype=jnp.float32),
             jnp.zeros((block_q, head_dim), dtype=jnp.float32))
    if window is not None:  # the blocks a window's lower edge crosses
        carry = jax.lax.fori_loop(lo, a, body(True), carry)
    carry = jax.lax.fori_loop(a, b, body(False), carry)
    if causal:  # the blocks the diagonal crosses
        carry = jax.lax.fori_loop(b, hi, body(True), carry)
    m, l, acc = carry
    # Guard the all-masked case (possible when kv_offset > q positions).
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    # Per-row logsumexp: the backward recomputes P = exp(S - lse) from it.
    # The statistics are columns (a row's value on its sublane) and lse is
    # stored as a row: a 2-D transpose is the relayout the chip does well
    # (as ``[:, 0]`` it took a fifth of the kernel: PERF.md §6, PR 44).
    lse_ref[0] = jnp.transpose(m * scale + jnp.log(l_safe))


def _flash_fwd(q3, k3, v3, *, heads: int, kv_heads: int, scale: float,
               causal: bool, q_offset: int, kv_offset: int,
               block_q: int, block_k: int, interpret: bool = False,
               window: Optional[int] = None):
    """q3: [B*H, Sq, D]; k3: [B*Hkv, Skv, D]; v3: [B*Hkv, Skv, Dv] →
    [B*H, Sq, Dv] and the float32 logsumexp [B*H, 1, Sq]: these two
    outputs, in this order, are how the benchmark's trace reader knows a
    prefill's forward call (benchmark/readers/window.py:FLASH)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, sq, d = q3.shape
    skv, dv = k3.shape[1], v3.shape[2]
    rep = heads // kv_heads
    grid = (bh, sq // block_q)

    def kv_index(i, j):
        # GQA: query head h reads kv head h // rep of the same batch.
        b = i // heads
        h = i % heads
        return (b * kv_heads + h // rep, 0, 0)

    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale, causal=causal,
        q_offset=q_offset, kv_offset=kv_offset, block_k=block_k,
        window=window,
    )
    return pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((bh, sq, dv), q3.dtype),
            # [bh, 1, sq]: a (1, 1, block) tile satisfies the TPU
            # (8, 128)-divisible-or-full block rule; flat [bh, sq] can't.
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, skv, d), kv_index,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, skv, dv), kv_index,
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, dv), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j),
                         memory_space=pltpu.VMEM),
        ),
        interpret=interpret,
    )(q3, k3, v3)


# What a visit of the streamed forward is, as bits of its flag: the
# first or the last of its query block, one the diagonal or a window's
# edge crosses (masked), or the one visit of a query block that attends
# to no key at all (nothing to multiply: zeros are written).
_FIRST, _LAST, _MASKED, _EMPTY = 1, 2, 4, 8


def stream_visits(sq: int, skv: int, block_q: int, block_k: int, *,
                  causal: bool, q_offset: int = 0, kv_offset: int = 0,
                  window: Optional[int] = None):
    """The streamed forward's walk, made when the kernel is built: for
    every (query block, key block) pair in which a query attends to a
    key, in the order the grid visits them (a query block's key blocks
    running), its query block, its key block and its flag, each a list
    of ints. A causal query block is given the key blocks up to its
    diagonal, a window layer's only those that ``t - window < j <= t``
    touches, ``_loop_bounds``' [lo, hi); nothing else is ever copied
    into VMEM. A pure function of its arguments (the benchmark's counts
    and tests/test_flash_attention.py read it)."""
    nk = skv // block_k
    q_blocks, k_blocks, flags = [], [], []
    for i in range(sq // block_q):
        lo, hi, a, b = _loop_bounds(q_offset + i * block_q, block_q,
                                    kv_offset, block_k, nk, causal, window)
        row = [(j, 0 if a <= j < b else _MASKED) for j in range(lo, hi)]
        row = row or [(0, _EMPTY)]
        row[0] = (row[0][0], row[0][1] | _FIRST)
        row[-1] = (row[-1][0], row[-1][1] | _LAST)
        q_blocks += [i] * len(row)
        k_blocks += [j for j, _ in row]
        flags += [f for _, f in row]
    return q_blocks, k_blocks, flags


def _flash_fwd_streamed_kernel(qi_ref, kj_ref, flag_ref, q_ref, k_ref,
                               v_ref, lse_ref, o_ref, m_ref, l_ref, acc_ref,
                               *, scale: float, q_offset: int,
                               kv_offset: int, window: Optional[int]):
    """Grid (B*H, visits). qi_ref, kj_ref, flag_ref: ``stream_visits``,
    in SMEM. q_ref [1, Bq, D] and the outputs stay while the visits stay
    in the query block; k_ref / v_ref [1, Bk, D] are the visit's key
    block, the only keys in VMEM. m_ref, l_ref [Bq, 1] and acc_ref
    [Bq, Dv] float32 carry the online softmax from visit to visit."""
    from jax.experimental import pallas as pl

    p = pl.program_id(1)
    flag = flag_ref[p]
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]

    @pl.when(flag & _FIRST != 0)
    def _first_visit_of_the_query_block():
        m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def visit(masked):
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        s = _dot(q, k, _NT)  # [Bq, Bk] float32, UNSCALED: scale > 0
        if masked:
            s = jnp.where(
                _attends(s.shape, 0, q_offset + qi_ref[p] * block_q,
                         kv_offset + kj_ref[p] * block_k, window),
                s, _NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        prob = jnp.exp((s - m_new) * scale)
        alpha = jnp.exp((m - m_new) * scale)
        l_ref[...] = alpha * l_ref[...] + prob.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + _dot(prob.astype(v.dtype), v,
                                                   _NN)
        m_ref[...] = m_new

    pl.when(flag & _MASKED != 0)(lambda: visit(True))
    pl.when(flag & (_MASKED | _EMPTY) == 0)(lambda: visit(False))

    @pl.when(flag & _LAST != 0)
    def _last_visit_of_the_query_block():
        l = l_ref[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = jnp.transpose(m_ref[...] * scale + jnp.log(l_safe))


def _flash_fwd_streamed(q3, k3, v3, *, heads: int, kv_heads: int,
                        scale: float, causal: bool, q_offset: int,
                        kv_offset: int, block_q: int, block_k: int,
                        interpret: bool = False,
                        window: Optional[int] = None):
    """``_flash_fwd`` for a head whose K and V do not fit VMEM whole
    (``Blocks.streamed``): the same arguments and results. The grid's
    second dimension walks ``stream_visits`` and each visit brings ONE
    key block in, so VMEM holds a query block, a key and a value block
    (double-buffered by the pipeline) and the float32 carry, whatever
    the sequence; what the resident form saves, the K and V of a head
    read once for all its query blocks, is given up. The kernel is told
    the log-sum-exp first and the output second, [B*H, 1, Sq] float32
    then [B*H, Sq, Dv]: by that order, the reverse of the resident
    form's, the benchmark's trace reader knows this form
    (benchmark/readers/smallthinker.py:STREAMED) and the resident
    form's reader does not. The walk is three int32 a visit in SMEM
    (528 visits at 16,384 x 16,384 in blocks of 512)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, sq, d = q3.shape
    skv, dv = k3.shape[1], v3.shape[2]
    rep = heads // kv_heads
    walk = stream_visits(sq, skv, block_q, block_k, causal=causal,
                         q_offset=q_offset, kv_offset=kv_offset,
                         window=window)

    def q_index(i, p, qi, kj, flag):
        return (i, qi[p], 0)

    def kv_index(i, p, qi, kj, flag):
        return ((i // heads) * kv_heads + (i % heads) // rep, kj[p], 0)

    kernel = functools.partial(
        _flash_fwd_streamed_kernel, scale=scale, q_offset=q_offset,
        kv_offset=kv_offset, window=window)
    lse, o3 = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(bh, len(walk[0])),
            in_specs=[pl.BlockSpec((1, block_q, d), q_index),
                      pl.BlockSpec((1, block_k, d), kv_index),
                      pl.BlockSpec((1, block_k, dv), kv_index)],
            out_specs=(
                pl.BlockSpec((1, 1, block_q),
                             lambda i, p, qi, kj, flag: (i, 0, qi[p])),
                pl.BlockSpec((1, block_q, dv), q_index)),
            scratch_shapes=[pltpu.VMEM((block_q, 1), jnp.float32),
                            pltpu.VMEM((block_q, 1), jnp.float32),
                            pltpu.VMEM((block_q, dv), jnp.float32)],
        ),
        out_shape=(jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
                   jax.ShapeDtypeStruct((bh, sq, dv), q3.dtype)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="flash_fwd_streamed",
        interpret=interpret,
    )(*(jnp.asarray(w, jnp.int32) for w in walk), q3, k3, v3)
    return o3, lse


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, scale: float, causal: bool,
                         q_offset: int, kv_offset: int, block_k: int):
    from jax.experimental import pallas as pl

    block_q = q_ref.shape[1]
    nk = k_ref.shape[1] // block_k
    q = q_ref[0]                            # [Bq, D]
    do = do_ref[0]                          # [Bq, D]
    lse = jnp.transpose(lse_ref[0])         # [1, Bq] as stored -> [Bq, 1]
    delta = jnp.transpose(delta_ref[0])     # [Bq, 1] = rowsum(dO * O)
    q_start = q_offset + pl.program_id(1) * block_q
    _, hi, _, b = _loop_bounds(q_start, block_q, kv_offset, block_k, nk,
                               causal, None)

    def body(masked):
        def step(j, dq):
            k = k_ref[0, pl.ds(j * block_k, block_k), :]
            v = v_ref[0, pl.ds(j * block_k, block_k), :]
            s = _dot(q, k, _NT)
            if masked:
                s = jnp.where(
                    _attends(s.shape, 0, q_start,
                             kv_offset + j * block_k, None),
                    s, _NEG_INF)
            p = jnp.exp(s * scale - lse)    # masked entries -> 0
            dp = _dot(do, v, _NT)           # [Bq, Bk]
            ds = p * (dp - delta)
            return dq + _dot(ds.astype(k.dtype), k, _NN)
        return step

    dq = jnp.zeros(q.shape, dtype=jnp.float32)
    dq = jax.lax.fori_loop(0, b, body(False), dq)
    if causal:
        dq = jax.lax.fori_loop(b, hi, body(True), dq)
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, scale: float, causal: bool,
                          q_offset: int, kv_offset: int, block_q: int):
    """dK/dV on a KV-head grid. q_ref/do_ref hold ALL ``rep`` query heads
    of this KV group ([rep, Sq, D]); the GQA reduction happens in the f32
    accumulator so each dK/dV block is written to HBM exactly once. The
    scores are computed transposed, [Bk, Bq] = k·qᵀ, so that pᵀ·dO and
    dsᵀ·q are plain matmuls and ``lse`` / ``delta`` are read as the rows
    they are stored as."""
    from jax.experimental import pallas as pl

    rep = q_ref.shape[0]
    block_k = k_ref.shape[1]
    nq = q_ref.shape[1] // block_q
    k = k_ref[0]                            # [Bk, D]
    v = v_ref[0]                            # [Bk, D]
    k_start = kv_offset + pl.program_id(1) * block_k
    if causal:
        # First q block whose LAST position reaches this kv block, and
        # the first whose FIRST position is at or past its last key: from
        # there on no mask.
        lo = jnp.clip((k_start - q_offset) // block_q, 0, nq)
        a = jnp.clip(-((-(k_start + block_k - 1 - q_offset)) // block_q),
                     lo, nq)
    else:
        lo = a = 0

    def body(r, masked):
        def step(j, carry):
            dk, dv = carry
            rows = pl.ds(j * block_q, block_q)
            q = q_ref[r, rows, :]
            do = do_ref[r, rows, :]
            lse = lse_ref[r, :, rows]       # [1, Bq]
            delta = delta_ref[r, :, rows]
            st = _dot(k, q, _NT)            # [Bk, Bq]
            if masked:
                st = jnp.where(
                    _attends(st.shape, 1, q_offset + j * block_q, k_start,
                             None),
                    st, _NEG_INF)
            pt = jnp.exp(st * scale - lse)
            dv = dv + _dot(pt.astype(do.dtype), do, _NN)
            dpt = _dot(v, do, _NT)
            dst = pt * (dpt - delta)
            dk = dk + _dot(dst.astype(q.dtype), q, _NN)
            return dk, dv
        return step

    carry = (jnp.zeros(k.shape, dtype=jnp.float32),
             jnp.zeros(v.shape, dtype=jnp.float32))
    for r in range(rep):  # static unroll over the group's query heads
        if causal:
            carry = jax.lax.fori_loop(lo, a, body(r, True), carry)
        carry = jax.lax.fori_loop(a, nq, body(r, False), carry)
    dk, dv = carry
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd(q3, k3, v3, do3, lse, delta, *, heads: int, kv_heads: int,
               scale: float, causal: bool, q_offset: int, kv_offset: int,
               dq_blocks: Tuple[int, int], dkv_blocks: Tuple[int, int],
               interpret: bool = False):
    """Fused backward. q3/do3: [B*H, Sq, D]; k3/v3: [B*Hkv, Skv, D];
    lse/delta: [B*H, 1, Sq]. Returns (dq3 [B*H, Sq, D],
    dk3/dv3 [B*Hkv, Skv, D] — already reduced over each KV group)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, sq, d = q3.shape
    skv = k3.shape[1]
    rep = heads // kv_heads
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)

    def kv_index(i, j):
        b = i // heads
        h = i % heads
        return (b * kv_heads + h // rep, 0, 0)

    block_q, block_k = dq_blocks
    dq_kernel = functools.partial(
        _flash_bwd_dq_kernel, scale=scale, causal=causal,
        q_offset=q_offset, kv_offset=kv_offset, block_k=block_k,
    )
    dq3 = pl.pallas_call(
        dq_kernel,
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q3.dtype),
        grid=(bh, sq // block_q),
        in_specs=[
            vmem((1, block_q, d), lambda i, j: (i, j, 0)),
            vmem((1, skv, d), kv_index),
            vmem((1, skv, d), kv_index),
            vmem((1, block_q, d), lambda i, j: (i, j, 0)),
            vmem((1, 1, block_q), lambda i, j: (i, 0, j)),
            vmem((1, 1, block_q), lambda i, j: (i, 0, j)),
        ],
        out_specs=vmem((1, block_q, d), lambda i, j: (i, j, 0)),
        interpret=interpret,
    )(q3, k3, v3, do3, lse, delta)

    block_q, block_k = dkv_blocks
    bkv = (bh // heads) * kv_heads
    dkv_kernel = functools.partial(
        _flash_bwd_dkv_kernel, scale=scale, causal=causal,
        q_offset=q_offset, kv_offset=kv_offset, block_q=block_q,
    )

    def dkv_call(rows, group, kv_block_index):
        """dK/dV of ``rows`` KV rows (heads), each against the ``group``
        query heads at block-row i of q3."""
        q_spec = vmem((group, sq, d), lambda i, j: (i, 0, 0))
        stat_spec = vmem((group, 1, sq), lambda i, j: (i, 0, 0))
        kv_spec = vmem((1, block_k, d), kv_block_index)
        out_spec = vmem((1, block_k, d), lambda i, j: (i, j, 0))
        return pl.pallas_call(
            dkv_kernel,
            out_shape=(
                jax.ShapeDtypeStruct((rows, skv, d), k3.dtype),
                jax.ShapeDtypeStruct((rows, skv, d), v3.dtype),
            ),
            grid=(rows, skv // block_k),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, stat_spec,
                      stat_spec],
            out_specs=(out_spec, out_spec),
                interpret=interpret,
        )(q3, k3, v3, do3, lse, delta)

    if _dkv_grouped(rep, sq, d, q3.dtype.itemsize):
        # KV-head grid: q3 rows of group g are contiguous ([g*rep,
        # (g+1)*rep) since g = b*kv_heads + hk and H = kv_heads*rep), so a
        # [rep, Sq, D] block at block-row g picks exactly the group. The
        # index maps are constant in j — Q/dO stay VMEM-resident across
        # the whole KV sweep of a group.
        dk3, dv3 = dkv_call(bkv, rep, lambda i, j: (i, j, 0))
        return dq3, dk3, dv3

    def kv_blk_index(i, j):
        b = i // heads
        h = i % heads
        return (b * kv_heads + h // rep, j, 0)

    # Per-query-head fallback: the grouped kernel with rep=1 blocks
    # (q_ref.shape[0] == 1) is exactly the per-head computation; the
    # GQA group sum happens outside.
    dk3h, dv3h = dkv_call(bh, 1, kv_blk_index)
    b = bh // heads
    dk3 = dk3h.reshape(b, kv_heads, rep, skv, d).sum(
        axis=2).reshape(bkv, skv, d).astype(k3.dtype)
    dv3 = dv3h.reshape(b, kv_heads, rep, skv, d).sum(
        axis=2).reshape(bkv, skv, d).astype(v3.dtype)
    return dq3, dk3, dv3


def _reference(q, k, v, *, causal, scale, q_offset, kv_offset, window=None):
    from .attention import mha_attention

    return mha_attention(q, k, v, causal=causal, scale=scale,
                         q_offset=q_offset, kv_offset=kv_offset,
                         window=window)


def _forward_kernel(blocks: Blocks):
    return _flash_fwd_streamed if blocks.streamed else _flash_fwd


def _to_heads3(x):
    """[B, S, H, D] -> [B*H, S, D]."""
    B, S, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9)
)
def _flash_attention_core(q, k, v, causal, scale, q_offset, kv_offset,
                          blocks, interpret=False, window=None):
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    o3, _lse = _forward_kernel(blocks)(
        _to_heads3(q), _to_heads3(k), _to_heads3(v),
        heads=H, kv_heads=Hkv, scale=scale, causal=causal,
        q_offset=q_offset, kv_offset=kv_offset,
        block_q=blocks.fwd[0], block_k=blocks.fwd[1], interpret=interpret,
        window=window,
    )
    return o3.reshape(B, H, Sq, v.shape[3]).transpose(0, 2, 1, 3)


# What the forward kernel hands the backward ones is named: a remat
# policy that saves the name (models/llama.py's "dots") keeps ``o3`` and
# ``lse``, ``out`` is a re-laying of the kept ``o3``, and the forward
# kernel is dead code in the rematerialised forward. To a policy that
# does not, a Pallas call is no dot and the kernel runs a second time.
# Kernel, names and re-laying are ONE jitted call: ``jax.checkpoint``
# hands a kept value that another equation of the forward reads on
# through a ``reduce_precision`` to its own precision (against XLA's
# excess precision), which behind a custom call is a pass of its own
# over ``o`` (0.41 ms a layer at b8 x 2048 x 32 x 128 on a v5e;
# PERF.md, PR 74). A call's outputs that only the backward reads get
# none, and the kernel has rounded already.
FLASH_SAVED = "flash_fwd_residuals"


def _core_fwd(q, k, v, causal, scale, q_offset, kv_offset, blocks,
              interpret=False, window=None):
    if window is not None:
        # The two backward kernels mask causally and no further: a
        # window's gradient through them would be wrong without a word.
        raise NotImplementedError(
            "flash_attention(window=...) has no backward kernel: the "
            "forward kernel alone takes a window (serving's prefill)")
    if v.shape[3] != q.shape[3]:
        raise NotImplementedError(
            "flash_attention with a v narrower than q and k (latent "
            "attention's 192/128) has no backward kernel: the forward "
            "kernel alone takes unequal widths (serving's prefill)")
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    q3, k3, v3 = _to_heads3(q), _to_heads3(k), _to_heads3(v)

    @jax.jit
    def forward(q3, k3, v3):
        o3, lse = _forward_kernel(blocks)(
            q3, k3, v3, heads=H, kv_heads=Hkv, scale=scale, causal=causal,
            q_offset=q_offset, kv_offset=kv_offset,
            block_q=blocks.fwd[0], block_k=blocks.fwd[1],
            interpret=interpret,
        )
        o3 = checkpoint_name(o3, FLASH_SAVED)
        lse = checkpoint_name(lse, FLASH_SAVED)
        return o3.reshape(B, H, Sq, D).transpose(0, 2, 1, 3), o3, lse

    out, o3, lse = forward(q3, k3, v3)
    return out, (q3, k3, v3, o3, lse, B, H, Hkv)


def _core_bwd(causal, scale, q_offset, kv_offset, blocks, interpret,
              window, res, g):
    """Fused flash backward: P recomputed block-wise in VMEM from the
    saved logsumexp; dK/dV reduced over each GQA group inside the kernel
    (KV-head grid). ``_core_fwd`` has refused a window."""
    q3, k3, v3, o3, lse, B, H, Hkv = res
    Sq, D = q3.shape[1], q3.shape[2]
    do3 = _to_heads3(g)
    delta = (do3.astype(jnp.float32) * o3.astype(jnp.float32)).sum(
        -1
    )[:, None, :]  # [bh, 1, sq] to match the lse tiling
    dq3, dk3, dv3 = _flash_bwd(
        q3, k3, v3, do3, lse, delta, heads=H, kv_heads=Hkv, scale=scale,
        causal=causal, q_offset=q_offset, kv_offset=kv_offset,
        dq_blocks=blocks.dq, dkv_blocks=blocks.dkv, interpret=interpret,
    )
    Skv = k3.shape[1]
    dq = dq3.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
    dk = dk3.reshape(B, Hkv, Skv, D).transpose(0, 2, 1, 3)
    dv = dv3.reshape(B, Hkv, Skv, D).transpose(0, 2, 1, 3)
    return dq, dk, dv


_flash_attention_core.defvjp(_core_fwd, _core_bwd)


def flash_attention(
    q: jax.Array,  # [B, Sq, H, D]
    k: jax.Array,  # [B, Skv, Hkv, D]
    v: jax.Array,  # [B, Skv, Hkv, D]
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    q_offset: int = 0,
    kv_offset: int = 0,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
    window: Optional[int] = None,
) -> jax.Array:
    """Flash attention with GQA and global-coordinate causal masking
    (same signature as ops.attention.mha_attention), of the last
    ``window`` positions where one is given (causal only; forward only:
    under ``jax.grad`` it raises). v may have a width of its own, [B,
    Skv, Hkv, Dv] (latent attention rebuilt: q and k 192, v 128), which
    is then the output's; forward only likewise. The one place that
    says when the Pallas kernel runs: on a TPU, for sequences that are
    whole blocks (``choose_blocks``: of 128 rows at the least, unless the
    caller names a size, which all three kernels then take: a serving
    bucket of 16, 32 or 64 tokens is no block, and no shorter one has
    run on the chip), heads of at most 256 and whole GQA groups.
    Everything else takes the XLA einsum path."""
    B, Sq, H, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    scale = scale if scale is not None else D ** -0.5

    blocks = None
    if block_q is None and block_k is None:
        blocks = _blocks_of(Sq, Skv, D, v.shape[3], H, Hkv,
                            q.dtype.itemsize)
    elif D > 256 or H % Hkv:
        pass
    else:
        named = (block_q or _MIN_BLOCK, block_k or _MIN_BLOCK)
        if Sq % named[0] == 0 and Skv % named[1] == 0:
            blocks = Blocks(named, named, named)
    if blocks is None or (not _on_tpu() and not interpret):
        return _reference(q, k, v, causal=causal, scale=scale,
                          q_offset=q_offset, kv_offset=kv_offset,
                          window=window)
    if window is not None and not causal:
        raise ValueError("a window is a causal attention's lower bound")
    return _flash_attention_core(
        q, k, v, causal, scale, q_offset, kv_offset, blocks, interpret,
        window,
    )


def _blocks_of(sq, skv, d, dv, heads, kv_heads, itemsize):
    """``choose_blocks`` for the heads the kernel takes: of at most 256
    and whole GQA groups; None for the others."""
    if d > 256 or heads % kv_heads:
        return None
    return choose_blocks(sq, skv, d, dv, heads // kv_heads, itemsize)


def forward_path(sq: int, skv: int, d: int, dv: int, heads: int,
                 kv_heads: int, itemsize: int) -> str:
    """What ``flash_attention`` runs forward for these shapes where no
    caller names a block: ``"resident"`` or ``"streamed"`` (the two
    forms of the Pallas kernel), or ``"einsum"`` (off a TPU, and the
    shapes the kernel does not tile). ``LLMEngine.stats()`` counts a
    prefill's bucket by it."""
    blocks = _blocks_of(sq, skv, d, dv, heads, kv_heads, itemsize)
    if blocks is None or not _on_tpu():
        return "einsum"
    return "streamed" if blocks.streamed else "resident"


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"
