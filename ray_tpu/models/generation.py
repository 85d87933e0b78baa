"""Autoregressive generation with a static KV cache.

TPU-first decode path for the Llama family: all shapes static (XLA traces
once) — the cache is a fixed [L, B, T_max, Hkv, Dh] buffer updated with
dynamic_update_slice; per-slot lengths mask attention. Prefill and decode
are separate jitted programs (the standard TPU serving split: prefill is
compute-bound on the MXU, decode is HBM-bandwidth-bound).

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
    LlamaConfig, ffn, qkv_proj, rms_norm, rope, split_expert_stack,
)


class KVCache(NamedTuple):
    k: jax.Array  # [L, B, T, Hkv, Dh]
    v: jax.Array  # [L, B, T, Hkv, Dh]
    lengths: jax.Array  # [B] int32 — valid tokens per slot

    @staticmethod
    def create(cfg: LlamaConfig, batch: int, max_len: int) -> "KVCache":
        shape = (cfg.num_layers, batch, max_len, cfg.num_kv_heads, cfg.dh)
        return KVCache(
            k=jnp.zeros(shape, dtype=cfg.dtype),
            v=jnp.zeros(shape, dtype=cfg.dtype),
            lengths=jnp.zeros((batch,), dtype=jnp.int32),
        )


def _attend_cached(q, ck, cv, q_pos, lengths, cfg):
    """q [B,S,H,D] against cache ck/cv [B,T,Hkv,D]; positions of q rows are
    q_pos [B,S]; cache rows >= lengths[b] (post-update) are masked."""
    B, S, H, D = q.shape
    T = ck.shape[1]
    if S == T and S % 128 == 0 and cfg.use_flash:
        # Fresh prefill (appending S tokens to an S-long cache implies
        # start position 0): pure causal self-attention — route through
        # the flash kernel (GQA handled natively; ~1.5x the XLA einsum
        # on TPU and O(S) memory). VERDICT r3 ask #7b.
        from ..ops.flash_attention import flash_attention

        return flash_attention(q, ck, cv, causal=True)
    rep = H // ck.shape[2]
    k = jnp.repeat(ck, rep, axis=2)
    v = jnp.repeat(cv, rep, axis=2)
    scores = jnp.einsum("bshd,bthd->bhst", q, k,
                        preferred_element_type=jnp.float32) * (D ** -0.5)
    t_idx = jnp.arange(T)[None, None, :]  # [1,1,T]
    causal = t_idx <= q_pos[:, :, None]  # [B,S,T]
    scores = jnp.where(causal[:, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhst,bthd->bshd", probs, v)


def _layer_cached(cfg, lp, x, cache_k, cache_v, start_pos, q_pos,
                  token_mask=None, expert_stack=None):
    """One block over cached KV. x [B,S,M]; start_pos [B] write offset;
    ``token_mask`` [B,S] keeps rows (inactive decode slots, a bucket's
    padding) out of MoE routing: they reach no expert. Returns the
    tokens assigned to each expert beside x and the cache (None for a
    dense model)."""
    B, S, M = x.shape
    q, k, v = qkv_proj(cfg, lp, x)

    # Rotary with per-slot positions.
    def rope_rows(x_b, pos_b):
        return rope(x_b[None], pos_b, cfg.rope_theta)[0]

    q = jax.vmap(rope_rows)(q, q_pos)
    k = jax.vmap(rope_rows)(k, q_pos)

    # Scatter new KV rows into the cache at start_pos per slot.
    def upd(cache_b, new_b, start_b):
        return jax.lax.dynamic_update_slice(
            cache_b, new_b.astype(cache_b.dtype), (start_b, 0, 0)
        )

    cache_k = jax.vmap(upd)(cache_k, k, start_pos)
    cache_v = jax.vmap(upd)(cache_v, v, start_pos)
    attn = _attend_cached(q, cache_k, cache_v, q_pos,
                          start_pos + S, cfg)
    x = x + jnp.einsum("bshd,hdm->bsm", attn.astype(x.dtype), lp["wo"])
    # The load-balancing loss is a training-only term: dropped here.
    x, _aux, expert_tokens = ffn(cfg, lp, x, token_mask=token_mask,
                                 expert_stack=expert_stack)
    return x, cache_k, cache_v, expert_tokens


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


def forward_with_cache(
    params: Dict[str, Any],
    tokens: jax.Array,      # [B, S] — S tokens appended to each slot
    cache: KVCache,
    cfg: LlamaConfig,
    *,
    active: Optional[jax.Array] = None,  # [B] bool — rows to update
    last_index: Optional[jax.Array] = None,  # [B] logits position override
    append_len: Optional[jax.Array] = None,  # [B] real (unpadded) length
) -> Tuple[jax.Array, KVCache]:
    """Append ``tokens`` to each slot's sequence and return logits for the
    final appended position [B, V] plus the updated cache. Works for both
    prefill (S = prompt length, lengths 0) and decode (S = 1).

    ``last_index``/``append_len`` support BUCKETED prefill: tokens padded
    to a bucket length S still produce logits at the true final position
    and advance each slot's length by its true prompt length (padded cache
    rows beyond the length are never attended — masking is by length;
    padded rows and inactive slots reach no expert of a MoE model)."""
    logits, cache, _load = _forward_with_cache(
        params, tokens, cache, cfg, active=active, last_index=last_index,
        append_len=append_len)
    return logits, cache


def _forward_with_cache(params, tokens, cache, cfg, *, active=None,
                        last_index=None, append_len=None):
    """``forward_with_cache`` plus the run's ``MoeLoad`` (None for a
    dense model)."""
    B, S = tokens.shape
    start = cache.lengths
    q_pos = start[:, None] + jnp.arange(S)[None, :]
    x = params["embed"][tokens].astype(cfg.dtype)
    token_mask = None
    if cfg.n_experts > 0 and (active is not None or append_len is not None):
        token_mask = jnp.ones((B, S), bool)
        if active is not None:
            token_mask &= active[:, None]
        if append_len is not None:
            token_mask &= (jnp.arange(S)[None, :]
                           < jnp.reshape(append_len, (-1, 1)))

    layers, expert_stack = split_expert_stack(cfg, params["layers"])

    def body(carry, layer_in):
        x = carry
        lp, ck, cv = layer_in
        x, ck, cv, expert_tokens = _layer_cached(
            cfg, lp, x, ck, cv, start, q_pos, token_mask=token_mask,
            expert_stack=expert_stack)
        return x, (ck, cv, expert_tokens)

    x, (new_k, new_v, expert_tokens) = jax.lax.scan(
        body, x, (layers, cache.k, cache.v)
    )
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    if last_index is None:
        last = x[:, -1]
    else:
        last = x[jnp.arange(B), last_index]
    logits = jnp.einsum("bm,mv->bv", last, params["lm_head"])
    active = jnp.ones((B,), bool) if active is None else active
    advance = append_len if append_len is not None else S
    lengths = jnp.where(active, cache.lengths + advance, cache.lengths)
    keep = active[:, None, None, None]
    new_k = jnp.where(keep[None], new_k, cache.k)
    new_v = jnp.where(keep[None], new_v, cache.v)
    return (logits.astype(jnp.float32), KVCache(new_k, new_v, lengths),
            MoeLoad.of_layers(expert_tokens))


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


def _layer_paged_decode(cfg, lp, x, k_pool, v_pool, page_table, lengths,
                        active, expert_stack=None):
    """One block, single-token decode against the paged pool. x [B,1,M];
    k_pool/v_pool the WHOLE pools [L, Hkv, P, page, Dh], carried by the
    layer scan and used at ``lp["index"]``. The token's K/V row goes to
    ``decode_attention``, which writes it at position ``lengths[b]`` of
    each active slot and attends; nothing else here reads or writes the
    pools (a second reader of what goes into the kernel's aliased call
    would make XLA copy them). Returns x, the pools and the tokens
    assigned to each expert (None for a dense model)."""
    q, k, v = qkv_proj(cfg, lp, x)
    q_pos = lengths[:, None]

    def rope_rows(x_b, pos_b):
        return rope(x_b[None], pos_b, cfg.rope_theta)[0]

    q = jax.vmap(rope_rows)(q, q_pos)
    k = jax.vmap(rope_rows)(k, q_pos)
    attn, k_pool, v_pool = decode_attention(
        q[:, 0], k[:, 0], v[:, 0], k_pool, v_pool, lp["index"],
        page_table, lengths, active)
    x = x + jnp.einsum("bshd,hdm->bsm", attn[:, None].astype(x.dtype),
                       lp["wo"])
    # Inactive slots reach no expert: the experts a step reads follow
    # the live sequences.
    x, _aux, expert_tokens = ffn(cfg, lp, x, token_mask=active[:, None],
                                 expert_stack=expert_stack)
    return x, k_pool, v_pool, expert_tokens


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
    every step (PagedKVCache)."""
    x = params["embed"][tokens][:, None].astype(cfg.dtype)
    layers, expert_stack = split_expert_stack(cfg, params["layers"])

    def body(carry, lp):
        x, k_pool, v_pool = carry
        x, k_pool, v_pool, expert_tokens = _layer_paged_decode(
            cfg, lp, x, k_pool, v_pool, cache.page_table, cache.lengths,
            active, expert_stack=expert_stack,
        )
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
    """Prefill one request through the dense single-row path, then scatter
    the resulting rows into the slot's pool pages. The bucket length must
    be a multiple of the page size (buckets are powers of two >= page).
    Returns the run's ``MoeLoad`` too (None for a dense model)."""
    S = tokens.shape[1]
    page = cache.page_size
    small = KVCache.create(cfg, 1, S)
    logits, small, load = _forward_with_cache(
        params, tokens, small, cfg,
        last_index=real_len[None] - 1, append_len=real_len[None],
    )
    n = S // page
    # [L, 1, S, Hkv, Dh] -> [L, Hkv, n, page, Dh] -> scatter at page ids.
    k_pages = small.k[:, 0].reshape(
        cfg.num_layers, n, page, cfg.num_kv_heads, cfg.dh
    ).transpose(0, 3, 1, 2, 4)
    v_pages = small.v[:, 0].reshape(
        cfg.num_layers, n, page, cfg.num_kv_heads, cfg.dh
    ).transpose(0, 3, 1, 2, 4)
    k = cache.k.at[:, :, pages].set(k_pages.astype(cache.k.dtype))
    v = cache.v.at[:, :, pages].set(v_pages.astype(cache.v.dtype))
    lengths = cache.lengths.at[slot].set(real_len)
    return logits, PagedKVCache(k, v, cache.page_table, lengths), load


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


def generate(
    params: Dict[str, Any],
    prompt: jax.Array,       # [B, S_prompt]
    cfg: LlamaConfig,
    *,
    max_new_tokens: int,
    max_len: Optional[int] = None,
    temperature: float = 0.0,
    rng: Optional[jax.Array] = None,
    eos_token: Optional[int] = None,
) -> jax.Array:
    """Simple batch generation (prefill + scan decode). Returns
    [B, max_new_tokens]."""
    B, S = prompt.shape
    max_len = max_len or (S + max_new_tokens)
    cache = KVCache.create(cfg, B, max_len)
    rng = rng if rng is not None else jax.random.PRNGKey(0)

    logits, cache = forward_with_cache(params, prompt, cache, cfg)
    first = sample_logits(logits, rng, temperature=temperature)
    if max_new_tokens == 1:
        return first[:, None]

    def step(carry, key):
        tok, cache = carry
        logits, cache = forward_with_cache(params, tok[:, None], cache, cfg)
        nxt = sample_logits(logits, key, temperature=temperature)
        return (nxt, cache), nxt

    keys = jax.random.split(rng, max_new_tokens - 1)
    (_, _), rest = jax.lax.scan(step, (first, cache), keys)
    return jnp.concatenate([first[:, None], rest.T], axis=1)
