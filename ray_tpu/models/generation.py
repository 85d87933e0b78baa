"""The serving programs of the Llama family over the paged KV cache.

The engine's cache is the paged one (``PagedKVCache``): a shared pool of
token pages and a page table a slot. Two jitted programs use it, both
built on the one transformer block (``llama.block``) with an attention
of their own, and all their shapes are static:

``paged_prefill`` — one request's prompt, padded to a bucket. A fresh
prompt attends to nothing but itself, so this is training's causal
self-attention (``llama.causal_attention``); what the block's
``attend`` keeps is each layer's k and v, which the layer scan stacks
and which are then laid into the slot's pages.

``paged_decode`` — one token for every slot. The layer scan carries the
pool whole; ``attend`` is ``ops/paged_attention.decode_attention``,
which writes the token's k and v into the slot's current page and
attends over the slot's pages.

No reference counterpart — Ray delegates model serving compute to user
code; this framework owns it (continuous batching sits on top in
ray_tpu.serve.llm).
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.paged_attention import decode_attention
from .llama import (
    LlamaConfig, block, causal_attention, rms_norm, split_expert_stack,
)


class MoeLoad(NamedTuple):
    """What the experts of one program run were given, summed over its
    layers: the serving engine's expert-load counters."""

    expert_tokens: jax.Array    # [E] int32 (token, expert) assignments
    experts_reached: jax.Array  # [] int32 (layer, expert) pairs with any

    @staticmethod
    def of_layers(expert_tokens) -> Optional["MoeLoad"]:
        """From the layer scan's stacked [L, E] counts; None for None."""
        if expert_tokens is None:
            return None
        return MoeLoad(expert_tokens.sum(axis=0),
                       (expert_tokens > 0).sum().astype(jnp.int32))


class PagedKVCache(NamedTuple):
    """Paged KV cache: a SHARED pool of fixed-size token pages plus a
    per-slot page table (the TPU-static analogue of vLLM's PagedAttention
    — no reference counterpart; Ray stops at request batching). Memory is
    bounded by ``total_pages * page_size`` tokens ACROSS requests instead
    of ``max_batch * max_len`` each, so one long-context request coexists
    with many short ones; pages recycle the moment a request finishes.
    All shapes static for XLA. The pool is HEAD-MAJOR
    ([L, Hkv, P_total, page, Dh]): one copy brings a page of every KV
    head to the decode attention (ops/paged_attention.py), which reads a
    slot's own pages where they lie and, on a TPU, is also what writes a
    decode step's token: one page a slot a layer, through an output
    aliased to the pool. A decode step never slices, re-stacks or
    re-lays the pool. XLA cannot write one token's row in place (a row
    is a sixteenth of a bf16 tile; a scatter or ``dynamic_update_slice``
    of it compiles to pool-sized re-layouts, and the pool as the layer
    scan's xs/ys to a slice and a re-stack a layer: 28 ms of a 39 ms
    step before PR 29, PERF.md §6)."""

    k: jax.Array            # [L, Hkv, P_total, page, Dh] shared pool
    v: jax.Array            # [L, Hkv, P_total, page, Dh]
    page_table: jax.Array   # [B, P_max] int32 page ids per slot
    lengths: jax.Array      # [B] int32 valid tokens per slot

    @property
    def page_size(self) -> int:
        return self.k.shape[3]

    @staticmethod
    def create(cfg: LlamaConfig, batch: int, total_pages: int,
               page_size: int, max_pages_per_seq: int) -> "PagedKVCache":
        shape = (cfg.num_layers, cfg.num_kv_heads, total_pages,
                 page_size, cfg.dh)
        return PagedKVCache(
            k=jnp.zeros(shape, dtype=cfg.dtype),
            v=jnp.zeros(shape, dtype=cfg.dtype),
            page_table=jnp.zeros((batch, max_pages_per_seq),
                                 dtype=jnp.int32),
            lengths=jnp.zeros((batch,), dtype=jnp.int32),
        )


def paged_decode(
    params: Dict[str, Any],
    tokens: jax.Array,          # [B] one token per slot
    cache: PagedKVCache,
    cfg: LlamaConfig,
    *,
    active: jax.Array,          # [B] bool
) -> Tuple[jax.Array, PagedKVCache, Optional[MoeLoad]]:
    """One decode step over the paged pool: write each active slot's
    token into its current page cell, attend over its pages, return
    [B, V] logits, the updated cache and the step's ``MoeLoad`` (None
    for a dense model). The layer scan CARRIES the pools whole: as its
    xs and ys they would be sliced and re-stacked, pool-sized copies
    every step (PagedKVCache). An inactive slot's pages and length stay
    as they are, and it reaches no expert: the experts a step reads
    follow the live sequences."""
    x = params["embed"][tokens][:, None].astype(cfg.dtype)
    layers, expert_stack = split_expert_stack(cfg, params["layers"])

    def body(carry, lp):
        x, k_pool, v_pool = carry

        def attend(q, k, v):
            # The token's K/V row goes to ``decode_attention``, which
            # writes it at position ``lengths[b]`` of each active slot
            # and attends. Nothing else in the step reads or writes the
            # pools (a second reader of what goes into the kernel's
            # aliased call would make XLA copy them): the block never
            # sees them.
            out, k_new, v_new = decode_attention(
                q[:, 0], k[:, 0], v[:, 0], k_pool, v_pool, lp["index"],
                cache.page_table, cache.lengths, active)
            return out[:, None], (k_new, v_new)

        # The load-balancing loss is a training-only term: dropped.
        x, (k_pool, v_pool), _aux, expert_tokens = block(
            cfg, lp, x, cache.lengths[:, None], attend,
            token_mask=active[:, None], expert_stack=expert_stack)
        return (x, k_pool, v_pool), expert_tokens

    (x, new_k, new_v), expert_tokens = jax.lax.scan(
        body, (x, cache.k, cache.v), layers
    )
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    logits = jnp.einsum("bm,mv->bv", x[:, 0], params["lm_head"])
    lengths = jnp.where(active, cache.lengths + 1, cache.lengths)
    return logits.astype(jnp.float32), PagedKVCache(
        new_k, new_v, cache.page_table, lengths
    ), MoeLoad.of_layers(expert_tokens)


def paged_prefill(
    params: Dict[str, Any],
    tokens: jax.Array,          # [1, S_bucket] padded prompt
    real_len: jax.Array,        # [] int32 true prompt length
    cache: PagedKVCache,
    cfg: LlamaConfig,
    slot: int | jax.Array,
    pages: jax.Array,           # [S_bucket // page] page ids for this slot
) -> Tuple[jax.Array, PagedKVCache, Optional[MoeLoad]]:
    """Prefill one request: causal self-attention over the padded prompt,
    logits [1, V] at its last real token, each layer's k and v laid
    into the slot's pool pages, the slot's length set to ``real_len``.
    Rows behind ``real_len`` are the bucket's padding: causal masking
    keeps them from the real rows, they reach no expert of a MoE model,
    and what they leave in the pages lies behind the slot's length,
    where decode writes before it reads. The bucket length must be a
    multiple of the page size (buckets are powers of two >= page).
    Returns the run's ``MoeLoad`` too (None for a dense model)."""
    S = tokens.shape[1]
    page = cache.page_size
    x = params["embed"][tokens].astype(cfg.dtype)
    positions = jnp.arange(S)
    token_mask = positions[None] < real_len if cfg.n_experts > 0 else None
    layers, expert_stack = split_expert_stack(cfg, params["layers"])

    def attend(q, k, v):
        return causal_attention(cfg, None, q, k, v), (k, v)

    def body(x, lp):
        x, kv, _aux, expert_tokens = block(
            cfg, lp, x, positions, attend, token_mask=token_mask,
            expert_stack=expert_stack)
        return x, (kv, expert_tokens)

    x, ((k, v), expert_tokens) = jax.lax.scan(body, x, layers)
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    logits = jnp.einsum("bm,mv->bv", x[:, real_len - 1], params["lm_head"])

    def to_pages(rows, pool):
        """[L, 1, S, Hkv, Dh] -> [L, Hkv, S // page, page, Dh], the
        pool's layout, set at the slot's page ids."""
        paged = rows[:, 0].reshape(
            cfg.num_layers, S // page, page, cfg.num_kv_heads, cfg.dh
        ).transpose(0, 3, 1, 2, 4)
        return pool.at[:, :, pages].set(paged.astype(pool.dtype))

    lengths = cache.lengths.at[slot].set(real_len)
    return logits.astype(jnp.float32), PagedKVCache(
        to_pages(k, cache.k), to_pages(v, cache.v), cache.page_table, lengths
    ), MoeLoad.of_layers(expert_tokens)


def sample_logits(logits: jax.Array, rng: jax.Array, *,
                  temperature: float = 0.0, top_k: int = 0) -> jax.Array:
    """Greedy (temperature 0) or temperature/top-k sampling. [B,V] → [B]."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k > 0:
        vals, _ = jax.lax.top_k(logits, top_k)
        kth = vals[:, -1][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)
