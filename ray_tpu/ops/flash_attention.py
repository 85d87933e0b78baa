"""Flash attention as a Pallas TPU kernel.

The HBM-optimal attention path (SURVEY.md §5.7): QK^T logits never
materialize in HBM — each query block streams KV blocks through VMEM with
online-softmax accumulation (flash attention v2 schedule), so memory is
O(S·D) instead of O(S²) and both matmuls hit the MXU back-to-back. Causal
masking skips fully-masked KV blocks (the loop's upper bound is computed
per query block), recovering the ~2x causal FLOP saving.

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
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
_NEG_INF = -1e30


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale: float,
                      causal: bool, q_offset: int, kv_offset: int,
                      block_k: int, window: Optional[int] = None):
    from jax.experimental import pallas as pl

    block_q = q_ref.shape[1]
    head_dim = v_ref.shape[2]  # the output's width is v's
    skv = k_ref.shape[1]
    nk = skv // block_k
    qi = pl.program_id(1)

    q = q_ref[0].astype(jnp.float32) * scale  # [Bq, D]

    q_start = q_offset + qi * block_q  # global position of this q block

    if causal:
        # KV blocks whose first position exceeds this q block's last
        # position are fully masked: bound the loop instead of masking.
        last_q = q_start + block_q - 1
        hi = jnp.clip((last_q - kv_offset) // block_k + 1, 0, nk)
    else:
        hi = nk
    # With a window, KV blocks whose last position lies before the first
    # query's window are fully masked too: the loop gets a lower bound.
    lo = 0 if window is None else jnp.clip(
        (q_start - window + 1 - kv_offset) // block_k, 0, nk)

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = q @ k.T  # [Bq, Bk] on the MXU
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = kv_offset + j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            attends = k_pos <= q_pos
            if window is not None:
                attends = attends & (k_pos > q_pos - window)
            s = jnp.where(attends, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + p.sum(axis=1)
        acc_new = acc * alpha[:, None] + p @ v  # second MXU matmul
        return m_new, l_new, acc_new

    m0 = jnp.full((block_q,), _NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((block_q,), dtype=jnp.float32)
    acc0 = jnp.zeros((block_q, head_dim), dtype=jnp.float32)
    m, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, acc0))
    # Guard the all-masked case (possible when kv_offset > q positions).
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = acc / l_safe[:, None]
    o_ref[0] = out.astype(o_ref.dtype)
    # Per-row logsumexp: the backward recomputes P = exp(S - lse) from it.
    lse_ref[0, 0] = m + jnp.log(l_safe)


def _flash_fwd(q3, k3, v3, *, heads: int, kv_heads: int, scale: float,
               causal: bool, q_offset: int, kv_offset: int,
               block_q: int, block_k: int, interpret: bool = False,
               window: Optional[int] = None):
    """q3: [B*H, Sq, D]; k3: [B*Hkv, Skv, D]; v3: [B*Hkv, Skv, Dv] →
    [B*H, Sq, Dv]."""
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


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, scale: float, causal: bool,
                         q_offset: int, kv_offset: int, block_k: int):
    from jax.experimental import pallas as pl

    block_q = q_ref.shape[1]
    skv = k_ref.shape[1]
    nk = skv // block_k
    qi = pl.program_id(1)

    q = q_ref[0].astype(jnp.float32)        # [Bq, D] (unscaled)
    do = do_ref[0].astype(jnp.float32)      # [Bq, D]
    lse = lse_ref[0, 0]                     # [Bq]
    delta = delta_ref[0, 0]                 # [Bq] = rowsum(dO * O)
    q_start = q_offset + qi * block_q
    if causal:
        last_q = q_start + block_q - 1
        hi = jnp.clip((last_q - kv_offset) // block_k + 1, 0, nk)
    else:
        hi = nk

    def body(j, dq):
        k = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = (q @ k.T) * scale
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = kv_offset + j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
        p = jnp.exp(s - lse[:, None])       # masked entries -> 0
        dp = do @ v.T                       # [Bq, Bk]
        ds = p * (dp - delta[:, None])
        return dq + (ds @ k) * scale

    dq0 = jnp.zeros_like(q)
    dq = jax.lax.fori_loop(0, hi, body, dq0)
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, scale: float, causal: bool,
                          q_offset: int, kv_offset: int, block_q: int):
    """dK/dV on a KV-head grid. q_ref/do_ref hold ALL ``rep`` query heads
    of this KV group ([rep, Sq, D]); the GQA reduction happens in the f32
    accumulator so each dK/dV block is written to HBM exactly once."""
    from jax.experimental import pallas as pl

    rep = q_ref.shape[0]
    block_k = k_ref.shape[1]
    sq = q_ref.shape[1]
    nq = sq // block_q
    ki = pl.program_id(1)
    head_dim = q_ref.shape[2]

    k = k_ref[0].astype(jnp.float32)        # [Bk, D]
    v = v_ref[0].astype(jnp.float32)        # [Bk, D]
    k_start = kv_offset + ki * block_k
    if causal:
        # First q block whose LAST position reaches this kv block.
        lo = jnp.clip((k_start - q_offset) // block_q, 0, nq)
    else:
        lo = 0

    def body_for_head(r):
        def body(j, carry):
            dk, dv = carry
            q = q_ref[r, pl.ds(j * block_q, block_q), :].astype(jnp.float32)
            do = do_ref[r, pl.ds(j * block_q, block_q), :].astype(
                jnp.float32)
            lse = lse_ref[r, 0, pl.ds(j * block_q, block_q)]
            delta = delta_ref[r, 0, pl.ds(j * block_q, block_q)]
            s = (q @ k.T) * scale               # [Bq, Bk]
            if causal:
                q_pos = q_offset + j * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0
                )
                k_pos = k_start + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1
                )
                s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
            p = jnp.exp(s - lse[:, None])
            dv = dv + p.T @ do
            dp = do @ v.T
            ds = p * (dp - delta[:, None])
            dk = dk + (ds.T @ q) * scale
            return dk, dv
        return body

    dk = jnp.zeros((block_k, head_dim), dtype=jnp.float32)
    dv = jnp.zeros((block_k, head_dim), dtype=jnp.float32)
    for r in range(rep):  # static unroll over the group's query heads
        dk, dv = jax.lax.fori_loop(lo, nq, body_for_head(r), (dk, dv))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


# Grouped Q/dO blocks larger than this fall back to the per-head kernel
# (VMEM is ~16 MiB/core; leave room for K/V blocks, f32 casts and the
# accumulators).
_DKV_GROUP_VMEM_BUDGET = 10 * 1024 * 1024


def _flash_bwd(q3, k3, v3, do3, lse, delta, *, heads: int, kv_heads: int,
               scale: float, causal: bool, q_offset: int, kv_offset: int,
               block_q: int, block_k: int, interpret: bool = False):
    """Fused backward. q3/do3: [B*H, Sq, D]; k3/v3: [B*Hkv, Skv, D];
    lse/delta: [B*H, 1, Sq]. Returns (dq3 [B*H, Sq, D],
    dk3/dv3 [B*Hkv, Skv, D] — already reduced over each KV group)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, sq, d = q3.shape
    skv = k3.shape[1]
    rep = heads // kv_heads

    def kv_index(i, j):
        b = i // heads
        h = i % heads
        return (b * kv_heads + h // rep, 0, 0)

    dq_kernel = functools.partial(
        _flash_bwd_dq_kernel, scale=scale, causal=causal,
        q_offset=q_offset, kv_offset=kv_offset, block_k=block_k,
    )
    dq3 = pl.pallas_call(
        dq_kernel,
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q3.dtype),
        grid=(bh, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, skv, d), kv_index, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, skv, d), kv_index, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(q3, k3, v3, do3, lse, delta)

    bkv = (bh // heads) * kv_heads
    dkv_kernel = functools.partial(
        _flash_bwd_dkv_kernel, scale=scale, causal=causal,
        q_offset=q_offset, kv_offset=kv_offset, block_q=block_q,
    )
    grouped_bytes = 2 * rep * sq * d * q3.dtype.itemsize  # q + do resident
    if grouped_bytes <= _DKV_GROUP_VMEM_BUDGET:
        # KV-head grid: q3 rows of group g are contiguous ([g*rep,
        # (g+1)*rep) since g = b*kv_heads + hk and H = kv_heads*rep), so a
        # [rep, Sq, D] block at block-row g picks exactly the group. The
        # index maps are constant in j — Q/dO stay VMEM-resident across
        # the whole KV sweep of a group.
        dk3, dv3 = pl.pallas_call(
            dkv_kernel,
            out_shape=(
                jax.ShapeDtypeStruct((bkv, skv, d), k3.dtype),
                jax.ShapeDtypeStruct((bkv, skv, d), v3.dtype),
            ),
            grid=(bkv, skv // block_k),
            in_specs=[
                pl.BlockSpec((rep, sq, d), lambda i, j: (i, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((rep, sq, d), lambda i, j: (i, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((rep, 1, sq), lambda i, j: (i, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((rep, 1, sq), lambda i, j: (i, 0, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=(
                pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0),
                             memory_space=pltpu.VMEM),
            ),
            interpret=interpret,
        )(q3, k3, v3, do3, lse, delta)
        return dq3, dk3, dv3

    def kv_blk_index(i, j):
        b = i // heads
        h = i % heads
        return (b * kv_heads + h // rep, j, 0)

    # Per-query-head fallback: the grouped kernel with rep=1 blocks
    # (q_ref.shape[0] == 1) is exactly the per-head computation; the
    # GQA group sum happens outside.
    dk3h, dv3h = pl.pallas_call(
        dkv_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((bh, skv, d), k3.dtype),
            jax.ShapeDtypeStruct((bh, skv, d), v3.dtype),
        ),
        grid=(bh, skv // block_k),
        in_specs=[
            pl.BlockSpec((1, sq, d), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), kv_blk_index,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), kv_blk_index,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, sq, d), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, sq), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, sq), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
        ),
        interpret=interpret,
    )(q3, k3, v3, do3, lse, delta)
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


def _to_heads3(x):
    """[B, S, H, D] -> [B*H, S, D]."""
    B, S, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10)
)
def _flash_attention_core(q, k, v, causal, scale, q_offset, kv_offset,
                          block_q, block_k, interpret=False, window=None):
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    o3, _lse = _flash_fwd(
        _to_heads3(q), _to_heads3(k), _to_heads3(v),
        heads=H, kv_heads=Hkv, scale=scale, causal=causal,
        q_offset=q_offset, kv_offset=kv_offset,
        block_q=block_q, block_k=block_k, interpret=interpret,
        window=window,
    )
    return o3.reshape(B, H, Sq, v.shape[3]).transpose(0, 2, 1, 3)


def _core_fwd(q, k, v, causal, scale, q_offset, kv_offset, block_q,
              block_k, interpret=False, window=None):
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
    o3, lse = _flash_fwd(
        q3, k3, v3, heads=H, kv_heads=Hkv, scale=scale, causal=causal,
        q_offset=q_offset, kv_offset=kv_offset,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    out = o3.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
    return out, (q3, k3, v3, o3, lse, B, H, Hkv)


def _core_bwd(causal, scale, q_offset, kv_offset, block_q, block_k,
              interpret, window, res, g):
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
        block_q=block_q, block_k=block_k, interpret=interpret,
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
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
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
    whole blocks (128 rows unless the caller names another size: a
    serving bucket of 16, 32 or 64 tokens is no block, and no shorter
    one has run on the chip), heads of at most 256 and whole GQA
    groups. Everything else takes the XLA einsum path."""
    B, Sq, H, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    scale = scale if scale is not None else D ** -0.5

    tileable = (
        Sq % block_q == 0
        and Skv % block_k == 0
        and D <= 256
        and H % Hkv == 0
    )
    if not tileable or (not _on_tpu() and not interpret):
        return _reference(q, k, v, causal=causal, scale=scale,
                          q_offset=q_offset, kv_offset=kv_offset,
                          window=window)
    if window is not None and not causal:
        raise ValueError("a window is a causal attention's lower bound")
    return _flash_attention_core(
        q, k, v, causal, scale, q_offset, kv_offset, block_q, block_k,
        interpret, window,
    )


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"
