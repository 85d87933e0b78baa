"""Attention ops (local/single-shard).

The XLA fallback path: einsum attention with numerically-stable softmax.
XLA fuses this well on TPU (the MXU does the two einsums; the softmax is
fused elementwise); the Pallas flash kernel (ops/flash_attention.py) is the
HBM-optimal path for long sequences. Both share this signature.

No counterpart exists in the reference — it delegates attention to user
frameworks; this framework owns its compute path (SURVEY.md §5.7).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def mha_attention(
    q: jax.Array,  # [B, Sq, H, D]
    k: jax.Array,  # [B, Skv, Hkv, D]
    v: jax.Array,  # [B, Skv, Hkv, D]
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    q_offset: int = 0,
    kv_offset: int = 0,
    bias: Optional[jax.Array] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Multi-head attention with optional GQA (Hkv divides H) and causal
    masking in *global* coordinates: query position i is q_offset + i,
    key position j is kv_offset + j — offsets make the same kernel correct
    for sharded sequence blocks (ring attention) and decode steps. With
    ``window`` (causal only) a query attends to the last ``window``
    positions, its own among them: q_pos - window < k_pos <= q_pos."""
    if window is not None and not causal:
        raise ValueError("a window is a causal attention's lower bound")
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    scale = scale if scale is not None else D ** -0.5
    if Hkv != H:
        assert H % Hkv == 0, f"GQA requires H % Hkv == 0, got {H=} {Hkv=}"
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if bias is not None:
        scores = scores + bias
    if causal:
        q_pos = q_offset + jnp.arange(Sq)[:, None]
        k_pos = kv_offset + jnp.arange(k.shape[1])[None, :]
        mask = k_pos <= q_pos  # [Sq, Skv]
        if window is not None:
            mask = mask & (k_pos > q_pos - window)
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    out = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", out, v)


def attention_block_accumulate(
    q: jax.Array,        # [B, Sq, H, D]
    k: jax.Array,        # [B, Skv, H, D]
    v: jax.Array,        # [B, Skv, H, D]
    m: jax.Array,        # [B, H, Sq]   running max (start: -inf)
    l: jax.Array,        # [B, H, Sq]   running denominator (start: 0)
    acc: jax.Array,      # [B, Sq, H, D] running numerator (start: 0)
    *,
    scale: float,
    mask: Optional[jax.Array] = None,  # [Sq, Skv] True = attend
):
    """One online-softmax (flash) accumulation step against a KV block.
    This is the inner update of both ring attention (block = remote KV
    shard) and the Pallas flash kernel (block = VMEM tile)."""
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if mask is not None:
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    m_new = jnp.maximum(m, scores.max(axis=-1))
    # Correction guards: fully-masked-so-far rows have m == -inf.
    safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    correction = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
    p = jnp.where(
        jnp.isfinite(scores), jnp.exp(scores - safe_m[..., None]), 0.0
    )  # [B,H,Sq,Skv]
    l_new = l * correction + p.sum(axis=-1)
    acc_new = (
        acc * correction.transpose(0, 2, 1)[..., None]
        + jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    )
    return m_new, l_new, acc_new


def attention_finalize(l: jax.Array, acc: jax.Array) -> jax.Array:
    """Divide the numerator by the accumulated denominator."""
    denom = jnp.maximum(l, 1e-37).transpose(0, 2, 1)[..., None]
    return (acc / denom).astype(acc.dtype)
