"""The serving programs of the Llama family over the paged KV cache.

The engine's cache is the paged one (``PagedKVCache``): for each of the
seven kinds of layer a model may have ("full", "window", "latent",
"state", "delta", "linear", "conv") a pool, and for the three that keep
a row a token a shared pool of token pages and a page table a slot.
Two jitted programs use it, both built on the one transformer block (``llama.block``) with an attention
of their own, both a layer scan for each run of alike layers
(``llama.layer_runs``: one run for a uniform model), and all their
shapes are static:

``paged_prefill`` — one request's prompt, padded to a bucket. A fresh
prompt attends to nothing but itself, so this is training's causal
self-attention (``llama.causal_attention``); what the block's
``attend`` keeps is each layer's k and v, which the layer scan stacks
and which are then laid into the slot's pages.

``paged_decode`` — one token for every slot. The layer scan carries the
pool whole; ``attend`` is ``ops/paged_attention.decode_attention``,
which writes the token's k and v into the slot's current page and
attends over the slot's pages.

A latent-attention layer (``llama.latent_proj``) caches one row a token,
the latent and the shared rotary key, in a pool of its own kind and has
two attends: the prefill rebuilds k and v of every head from the rows
and attends causally as any other (q and k 192 wide beside a v of 128
through the flash kernel), the decode step absorbs the up-projections
into the query and behind the attention and attends every head over the
rows themselves (``ops/paged_attention.latent_decode_attention``). The
same function of the same weights, at the cost each phase can bear.

A latent layer may attend over a learned SELECTION of the cached tokens
(``cfg.index_topk``; ops/sparse_attention.py): an indexing layer (kind
"latent_index") caches one more key a token, in the "index" pool that
rides on the latent pool's page table, scores the slot's keys with it,
keeps the best ``index_topk`` and attends over those rows alone; the
layers behind it of kind "latent_shared" attend over the same selection,
which the layer loop hands on from run to run and from layer to layer
(the block passes nothing but the residual). A context no longer than
``index_topk`` selects everything, and is the latent layer's attention.

A retention layer (kind "state", ops/retention.py) keeps no row a token
but a state of fixed size a slot, in a pool that has no pages: the
prefill runs the chunked scan from an empty state, with the bucket's
padding masked out of it (a gate of 1, a key of 0), and lays the state
it is left with into the slot; the decode step updates every active
slot's state in place and reads the token's output from it.

A delta layer (kind "delta", ops/delta_attention.py) keeps a state a
slot as well, [heads, width, width] float32, and beside it the last
``delta_conv - 1`` input rows of its short convolution; its layers stand
AMONG latent ones, so one request holds a slot of the two "delta" pools
and pages of the latent pool, under one admission and one release. The
prefill lays both from one prompt: the chunked delta rule from an empty
state with the bucket's padding masked out of it (no decay, no write),
the convolution's history taken at the last real token, not at the
bucket's end. The decode step shifts the history by the token's row and
updates each active slot's state in place.

A Lightning layer (kind "linear", ops/lightning_attention.py) keeps a
state a slot too, [heads, width, width] float32, decayed by a constant a
head; its layers stand AMONG "full" ones, so one request holds a slot of
the "linear" pool and pages of the "full" pool under one admission. A
model may select BLOCKS of its "full" pool (``cfg.block_select``, kind
"blocks", ops/block_attention.py): beside each page's k and v it keeps
the page's mean key, in the "mean" pool that rides on the "full" pool's
page table, with each slot's open page as a running sum; a decode step
scores the slot's page means, keeps the best blocks a KV head and copies
those alone, a block of one head a copy (its pages are one aligned run
of ids: ``KVBooks``); a prefill selects and attends a block of queries at a
time, and lays the means of the pages it fills beside their rows.

A gated short convolution (kind "conv", ``llama.conv_proj`` /
``conv_mix``) attends to nothing and keeps no state: all a later token
needs of the request is the last ``conv_taps - 1`` rows of one
hidden-wide product, the layer's whole cache, a slot and no pages
(``k["conv"]``). Its layers stand AMONG "full" ones, so one request holds
a slot of histories and pages of the few layers that attend, under one
admission. The prefill lays the history taken at the last real token,
not at the bucket's end, and overwrites the slot's whole (a slot taken
again needs no zeroing; a prompt shorter than the taps reach leaves
zeros in front); the decode step shifts it by the token's row
(``_shifted``, a delta layer's too). The "full" pool of a model with
heads of 64 lays two heads to a row (``kv_pool_row``), so that a token
holds the bytes the model states.

A looped model (``cfg.passes`` > 1, Ouro) runs its stack that many
times over ONE set of weights, and each (pass, layer) attends over keys
and values of its own: its pools are ``passes`` times as deep as the
stack (``llama.kv_layers``), pass ``t`` of a pool's layer ``l`` at ``t *
llama.kv_layers_a_pass + l``, and both programs walk the runs once a
pass (``_passes``), the final norm behind every pass. Pages, tables and
admission are what they are for any model: a page is simply that much
deeper. A model with an exit gate returns the exit distribution over
the passes beside its logits; every token runs every pass.

What the pools hold for whom is kept on the host by ``KVBooks``; the
serving engine reserves and releases through it and names no kind.

No reference counterpart — Ray delegates model serving compute to user
code; this framework owns it (continuous batching sits on top in
ray_tpu.serve.llm).
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.paged_attention import (
    decode_attention, decode_attention_path, latent_decode_attention,
    pool_row, ring_pages, walk_step_tokens,
)
from ..ops.block_attention import (
    block_decode_attention, block_prefill_attention, block_select_decode,
    block_walk_path, page_means, prefill_block_select,
)
from ..ops.delta_attention import (
    delta_decode, delta_path, delta_prefill,
    state_shape as delta_state_shape,
)
from ..ops.lightning_attention import (
    lightning_decode, lightning_path, lightning_prefill,
    state_shape as linear_state_shape,
)
from ..ops.retention import (
    retention_decode, retention_path, retention_prefill, state_shape,
)
from ..ops.sparse_attention import (
    empty_selection, index_select_decode, prefill_select,
    sparse_prefill_attention,
)
from .llama import (
    LlamaConfig, block, causal_attention, conv_mix, delta_mix, embed_tokens,
    exit_distribution, exit_gate_logit, head_input, head_logits,
    index_offsets, kv_layers, kv_layers_a_pass, latent_absorb_out,
    latent_absorb_q, latent_kv, layer_runs, layer_stacks, pool_kind,
    rms_norm, split_expert_stack,
)

# The kinds whose pools hold a slot's state and no row a token: no pages,
# no table column, admission by slot alone.
SLOT_KINDS = ("state", "delta", "linear", "conv")


def kv_pool_row(cfg: LlamaConfig) -> Tuple[int, int]:
    """(heads, width) of a token's row in this model's k/v pools
    (ops/paged_attention.py ``pool_row``: heads of 64 two to a row). A
    model that selects blocks keeps a head a row: its kernels and its
    page means read a page a KV head (ops/block_attention.py)."""
    if cfg.block_select:
        return cfg.num_kv_heads, cfg.dh
    return pool_row(cfg.num_kv_heads, cfg.dh)


class MoeLoad(NamedTuple):
    """What the experts of one program run were given, summed over its
    layers: the serving engine's expert-load counters."""

    expert_tokens: jax.Array    # [E] int32 (token, expert) assignments
    experts_reached: jax.Array  # [] int32 (layer, expert) pairs with any
    # [] int32 assignments to experts that other chips hold; None for a
    # model that holds all of its experts.
    elsewhere: Optional[jax.Array] = None

    @staticmethod
    def of_layers(expert_tokens, share: bool = False) -> Optional["MoeLoad"]:
        """From the layer scans' stacked [n, E] counts, one entry a run
        with experts; None for none. Of a model that holds a ``share``
        of its experts the counts are [n, held + 1], the last column
        the assignments that went elsewhere (``moe_ffn``)."""
        if not expert_tokens:
            return None
        counts = jnp.concatenate(expert_tokens)
        elsewhere = None
        if share:
            counts, elsewhere = counts[:, :-1], counts[:, -1].sum()
        return MoeLoad(counts.sum(axis=0),
                       (counts > 0).sum().astype(jnp.int32), elsewhere)


class PagedKVCache(NamedTuple):
    """Paged KV cache: a SHARED pool of fixed-size token pages plus a
    per-slot page table (the TPU-static analogue of vLLM's PagedAttention
    — no reference counterpart; Ray stops at request batching). Memory is
    bounded by ``total_pages * page_size`` tokens ACROSS requests instead
    of ``max_batch * max_len`` each, so one long-context request coexists
    with many short ones; pages recycle the moment a request finishes.
    All shapes static for XLA.

    One manager, a pool and a page table for each attention KIND the
    model has (``llama.kv_layers``: a pool's layers are the stack's
    layers of that kind, once for every pass of a looped model): ``k``,
    ``v`` and ``page_table`` are dicts by kind, ``{"full": ...}`` alone
    for a model without window layers. A "full" layer keeps every
    token, so its table has a column
    for every page of the longest sequence and its pool as many pages as
    the caller gives it. A "window" layer keeps the last
    ``sliding_window`` tokens: its table's row is a ring of
    ``ring_pages`` columns (ops/paged_attention.py) and its pool holds a
    ring for every slot, whatever the context lengths. A "latent" layer
    keeps every token as a "full" one does, but ONE row for all heads
    (``llama.latent_proj``): its pool is ``k["latent"]``,
    [L, P, page, ``cfg.latent_row``], and ``v`` has no such entry.
    A latent model with a learned selection (``cfg.index_topk``) keeps
    besides one indexer key a token an indexing layer, in ``k["index"]``,
    [Li, P, page, ``cfg.index_head_dim``]: a pool that RIDES on the
    latent pool's page table (``RIDES``), page for page, so that one
    reservation a slot covers both and there is no second allocator: it
    has no table and no free list of its own.
    A "state" layer (power retention) keeps no token at all but a state
    of fixed size a slot: its pool is ``k["state"]``,
    ``ops/retention.state_shape`` [L, B, Hkv, T, R, Dh] float32, again
    without a ``v``; it has NO pages (``sizes`` gives it none, its page
    table has no column), a slot is all a request needs of it, a prefill
    overwrites the slot's state whole and nothing is zeroed at release.
    The class keeps its name though such a model pages nothing.
    A "delta" layer (the delta rule) keeps a state a slot likewise,
    ``k["delta"]`` [L, B, H, D, D] float32, and under ``v["delta"]`` the
    convolution's history, [L, taps - 1, B, ``cfg.delta_row``] in the
    model's dtype (the slots second to last: a layer's slice of a tap is
    whole tiles); no pages either. Its layers lie among latent ones, so
    such a model has these two pools BESIDE the latent pool and its table.
    A "linear" layer (Lightning attention) keeps a state a slot, ``k["linear"]``
    [L, B, H, D, D] float32, no pages, beside the "full" pool of the
    layers it stands among. A model that selects blocks of its "full"
    pool (``cfg.block_select``) keeps besides ``k["mean"]`` [L, P, Hkv *
    Dh], a page's mean key a KV head, at the page's own id (it RIDES on
    the "full" table as "index" rides on "latent"), and ``v["mean"]``
    [L, B, Hkv * Dh] float32, the running sum of each slot's open page.
    The "full" pool and table of such a model keep one contract more: *a
    block's pages are one aligned ascending run* (columns ``ratio b ..
    ratio b + ratio - 1`` of a slot hold ids ``p .. p + ratio - 1``, ``p
    % ratio == 0``; ``KVBooks`` allocates so), by which the decode walk
    copies a block of one head as one region (ops/block_attention.py).
    The mean pool, at the same ids, gets the runs for free.
    A "conv" layer (a gated short convolution) keeps the last
    ``conv_taps - 1`` rows of its gated product a slot, ``k["conv"]``
    [L, taps - 1, B, hidden] in the model's dtype, no ``v``, no pages,
    beside the "full" pool of the layers it stands among.

    A k/v pool is HEAD-MAJOR
    ([L_kind, Hkv, P_kind, page, Dh]; heads of 64 two to a row, [L_kind,
    Hkv / 2, P_kind, page, 128], ``kv_pool_row``): one copy brings a page
    of every KV head to the decode attention (ops/paged_attention.py),
    which reads a slot's own pages where they lie and, on a TPU, is also what writes a
    decode step's token: one page a slot a layer, through an output
    aliased to the pool. A decode step never slices, re-stacks or
    re-lays the pool. XLA cannot write one token's row in place (a row
    is a sixteenth of a bf16 tile; a scatter or ``dynamic_update_slice``
    of it compiles to pool-sized re-layouts, and the pool as the layer
    scan's xs/ys to a slice and a re-stack a layer: 28 ms of a 39 ms
    step before PR 29, PERF.md §6)."""

    k: Dict[str, jax.Array]            # kind -> [L_kind, Hkv, P, page, Dh]
    v: Dict[str, jax.Array]            # ("latent", "index", "state", "linear",
    #                                    "conv": k alone; "delta": the
    #                                    convolution's history; "mean": the
    #                                    open pages' sums)
    page_table: Dict[str, jax.Array]   # kind -> [B, columns] int32 page ids
    lengths: jax.Array                 # [B] int32 valid tokens per slot

    # A pool whose pages are another pool's, at the same ids.
    RIDES = {"index": "latent", "mean": "full"}

    @property
    def page_size(self) -> Optional[int]:
        """Tokens a page holds; None for a model that pages nothing."""
        return next((pool.shape[-2] for kind, pool in self.k.items()
                     if kind not in SLOT_KINDS), None)

    def pools(self, kind: str) -> Tuple[jax.Array, ...]:
        """The pools of ``kind``: (k, v), or the one of latent rows, or
        the one of states, or (states, convolution histories)."""
        return tuple(d[kind] for d in (self.k, self.v) if kind in d)

    @staticmethod
    def sizes(cfg: LlamaConfig, batch: int, total_pages: int,
              page_size: int, max_pages_per_seq: int) -> Dict[str, Tuple]:
        """{kind: (layers, pool pages, table columns)}: what ``create``
        builds, and what the engine's allocator counts in."""
        out = {}
        for kind, layers in kv_layers(cfg).items():
            if kind == "window":
                ring = ring_pages(cfg.sliding_window, page_size,
                                  max_pages_per_seq)
                out[kind] = (layers, batch * ring, ring)
            elif kind in SLOT_KINDS:
                out[kind] = (layers, 0, 0)
            elif kind in PagedKVCache.RIDES:
                out[kind] = (layers, total_pages, 0)
            else:
                out[kind] = (layers, total_pages, max_pages_per_seq)
        return out

    @staticmethod
    def create(cfg: LlamaConfig, batch: int, total_pages: int,
               page_size: int, max_pages_per_seq: int) -> "PagedKVCache":
        """``total_pages`` and ``max_pages_per_seq`` are the "full"
        pool's; a window pool's size follows from the window."""
        sizes = PagedKVCache.sizes(cfg, batch, total_pages, page_size,
                                   max_pages_per_seq)

        def pool(kind, layers, pages):
            if kind == "state":
                return jnp.zeros(state_shape(layers, batch, cfg.num_kv_heads,
                                             cfg.dh), dtype=jnp.float32)
            if kind == "delta":
                return jnp.zeros(delta_state_shape(
                    layers, batch, cfg.delta_heads, cfg.delta_head_dim),
                    dtype=jnp.float32)
            if kind == "linear":
                return jnp.zeros(linear_state_shape(
                    layers, batch, cfg.linear_heads, cfg.linear_head_dim),
                    dtype=jnp.float32)
            if kind == "conv":
                # The last ``conv_taps - 1`` rows of z, the slots second
                # to last as a delta layer's histories are.
                return jnp.zeros((layers, cfg.conv_taps - 1, batch,
                                  cfg.hidden_size), dtype=cfg.dtype)
            if kind == "mean":
                return jnp.zeros((layers, pages, cfg.num_kv_heads * cfg.dh),
                                 dtype=cfg.dtype)
            if kind == "latent":
                return jnp.zeros((layers, pages, page_size, cfg.latent_row),
                                 dtype=cfg.dtype)
            if kind == "index":
                return jnp.zeros(
                    (layers, pages, page_size, cfg.index_head_dim),
                    dtype=cfg.dtype)
            heads, width = kv_pool_row(cfg)
            return jnp.zeros((layers, heads, pages, page_size, width),
                             dtype=cfg.dtype)

        first = {kind: pool(kind, layers, pages)
                 for kind, (layers, pages, _) in sizes.items()}
        # A v pool beside a k pool of rows a head; beside the delta
        # states the convolution's histories.
        second = {kind: jnp.zeros_like(first[kind])
                  for kind in first if kind in ("full", "window")}
        if "delta" in sizes:
            second["delta"] = jnp.zeros(
                (sizes["delta"][0], cfg.delta_conv - 1, batch,
                 cfg.delta_row), dtype=cfg.dtype)
        if "mean" in sizes:
            # Beside the page means each slot's open page: the running
            # sum of its keys, which becomes a mean when the page fills.
            second["mean"] = jnp.zeros(
                (sizes["mean"][0], batch, cfg.num_kv_heads * cfg.dh),
                dtype=jnp.float32)
        return PagedKVCache(
            k=first, v=second,
            page_table={kind: jnp.zeros((batch, columns), dtype=jnp.int32)
                        for kind, (_, _, columns) in sizes.items()
                        if kind not in PagedKVCache.RIDES},
            lengths=jnp.zeros((batch,), dtype=jnp.int32),
        )


class KVBooks:
    """The accounts of one ``PagedKVCache``, on the host: numpy and
    ints, no request and no program. For every pool the free pages and
    the host's copy of the page table, for every slot what it holds: a
    request reserves at admission, in every pool, all it holds to its
    end (every page of its context, of a ring at most the table's
    columns, of a pool of states nothing: admission is then by slot
    alone), and nothing is allocated or freed in between. A pool that
    rides on another's pages (``PagedKVCache.RIDES``) has no account of
    its own: what is reserved in the pool it rides on is its too.

    A pool's unit of allocation is its model's: pages are handed out,
    counted and given back in RUNS, a run one page but in the "full"
    pool of a model that selects blocks (``cfg.block_select``), where it
    is a block's ``ratio`` pages. A run's first id is a multiple of the
    run and its ids go into the slot's table in ascending order: *a
    block's pages are one aligned ascending run*, so columns ``ratio b
    .. ratio b + ratio - 1`` of every slot hold ``p, p + 1, ..`` with
    ``p % ratio == 0`` and the block walk copies a block of one head as
    ONE region of the pool (ops/block_attention.py). A request's need is
    rounded up to whole runs; pages of the pool beyond its last whole
    run are never handed out (``refusal`` says so).

    Built from the five arguments ``cache`` was created with, and
    ``cache`` (or its shapes) for what its pools weigh. One thread
    writes, the engine's loop; a ``reading`` on another holds the
    engine's lock."""

    def __init__(self, cfg: LlamaConfig, batch: int, total_pages: int,
                 page_size: int, max_pages_per_seq: int,
                 cache: PagedKVCache):
        self.page_size, self.total_pages = page_size, total_pages
        # Layers a token is kept by: the stack's, once for every pass.
        self._batch, self._layers = batch, cfg.num_layers * cfg.passes
        # {kind: (layers, pool pages, table columns)}
        self.pools = PagedKVCache.sizes(cfg, batch, total_pages, page_size,
                                        max_pages_per_seq)
        rides = PagedKVCache.RIDES
        # The pools that have a free list and a table of their own, and
        # the layers that hold a page of each (its riders' too).
        self._own = {kind: sizes for kind, sizes in self.pools.items()
                     if kind not in rides}
        # A model that selects blocks: its "full" layers read the pages
        # of the kept blocks (``account`` counts them from the contexts),
        # and its "full" pool is handed out a block's run at a time.
        self._blocks = cfg.block_select
        if self._blocks:
            self._blocks.check(page_size)
        self._run = {kind: self._blocks.ratio
                     if self._blocks and kind == "full" else 1
                     for kind in self._own}
        self._page_layers = {
            kind: layers + sum(self.pools[r][0] for r, on in rides.items()
                               if on == kind and r in self.pools)
            for kind, (layers, _, _) in self._own.items()}
        held = {kind: sum(pool.size * pool.dtype.itemsize
                          for pool in cache.pools(kind))
                for kind in self.pools}
        # What a token holds in one layer of each pool that has pages,
        # and what a slot holds in one layer of one that has none, as
        # allocated.
        self._row_bytes = {
            kind: held[kind] // (layers * pages * page_size)
            for kind, (layers, pages, _) in self.pools.items() if pages}
        self._slot_bytes = {
            kind: held[kind] // (layers * batch)
            for kind, (layers, pages, _) in self.pools.items() if not pages}
        # What a decode step reads of a sequence in each pool of rows,
        # and what it attends to of that: (layers, the window or None
        # for every token, the most a selection keeps or None).
        selecting = sum(run.n for run in layer_runs(cfg)
                        if run.kind in ("latent_index", "latent_shared"))
        self._reads = [(layers, cfg.window(kind), None)
                       for kind, (layers, pages, _) in self._own.items()
                       if pages and not (kind == "full" and cfg.block_select)]
        if selecting:
            (layers, _, _), = self._reads
            self._reads = [(layers - selecting, None, None),
                           (selecting, None, cfg.index_topk)]
        self._state_layers = sum(
            layers for layers, pages, _ in self.pools.values() if not pages)
        # ``free_pages``: of the pool that keeps everything, or the only.
        self._gauge = "full" if "full" in self.pools else next(
            (kind for kind, (_, pages, _) in self._own.items() if pages),
            next(iter(self.pools)))
        # What the decode program is built with: the same call
        # paged_decode's attention makes when the program is traced.
        # A model's delta layers: ops/delta_attention.py's path; "none"
        # for a model that has none.
        self.decode_delta = (
            delta_path(cfg.delta_head_dim, cfg.delta_heads)
            if "delta" in self.pools else "none")
        self.decode_linear = (
            lightning_path(cfg.linear_head_dim, cfg.linear_heads)
            if "linear" in self.pools else "none")
        if self._blocks:
            self.decode_attention = block_walk_path(page_size, cfg.dh)
        elif cfg.retention:
            self.decode_attention = retention_path(cfg.dh)
        elif cfg.latent:
            self.decode_attention = decode_attention_path(
                page_size, cfg.latent_row, cfg.kv_lora_rank)
            if selecting and self.decode_attention == "latent_walk":
                self.decode_attention = "sparse_walk"
        else:
            self.decode_attention = decode_attention_path(
                page_size, kv_pool_row(cfg)[1])
        # The tokens a compute step of the walk covers in each pool it
        # walks: what its buffers were sized by. A token is a k and a v
        # row of every KV head, or one latent row.
        def step_tokens(walks: bool, elements: int) -> Dict[str, int]:
            return {
                kind: walk_step_tokens(
                    elements * cache.k[kind].dtype.itemsize, page_size,
                    columns)
                for kind, (_, pages, columns) in self._own.items() if pages
            } if walks else {}

        self.page_walk_step_tokens = step_tokens(
            self.decode_attention == "page_walk",
            2 * cfg.num_kv_heads * cfg.dh)
        self.latent_walk_step_tokens = step_tokens(
            self.decode_attention in ("latent_walk", "sparse_walk"),
            cfg.latent_row)
        # What the decode steps read and held (LLMEngine.stats() says
        # what each means), summed as the steps are read.
        self.counts = dict.fromkeys((
            "decode_kv_tokens", "decode_kv_rows_read",
            "decode_kv_rows_selected", "decode_state_slot_layers",
            "kv_page_steps_held",
            "kv_page_steps_one_table"), 0)
        # ``stats()["blocks"]`` and ``stats()["linear"]``: what the
        # steps of a model that selects blocks read of what they held,
        # and the Lightning states they stepped; None for a model
        # without such layers.
        self.block_counts = dict.fromkeys(
            ("pages_read", "pages_held", "copies", "steps_dense",
             "steps_selected"), 0) if self._blocks else None
        self.linear_counts = ({"slot_layers": 0}
                              if "linear" in self.pools else None)
        # ``stats()["conv"]``: the convolution histories the steps
        # shifted; None for a model without such layers.
        self.conv_counts = ({"slot_layers": 0}
                            if "conv" in self.pools else None)
        self._mean_row_bytes = (
            cache.k["mean"].shape[-1] * cache.k["mean"].dtype.itemsize
            if "mean" in cache.k else 0)
        self.reset()

    def reset(self) -> None:
        """Every run free, every table zero, no slot holding."""
        # A pool's free runs, each by its first page: whole runs only.
        self.free: Dict[str, List[int]] = {
            kind: list(range(0, pages - pages % self._run[kind],
                             self._run[kind]))
            for kind, (_, pages, _) in self._own.items()}
        self.tables: Dict[str, np.ndarray] = {
            kind: np.zeros((self._batch, columns), dtype=np.int32)
            for kind, (_, _, columns) in self._own.items()}
        self._pages: Dict[int, Dict[str, List[int]]] = {}
        # Per slot, fixed from ``reserve`` to ``release`` so that a
        # decode step only adds them up: pages held, each times its
        # pool's layers; what one table for every layer would hold.
        self._held: Dict[int, int] = {}
        self._one_table: Dict[int, int] = {}

    def _need(self, tokens: int, bucket: int) -> Dict[str, int]:
        """Runs of each pool a context of ``tokens``, prefilled in
        ``bucket``, holds to its end: the bucket's pages or the
        context's, whichever is more, of a ring no more than the ring,
        in whole runs; of a pool of states, whose table has no column,
        none."""
        span = max(bucket // self.page_size, -(-tokens // self.page_size))
        return {kind: -(-min(span, columns) // self._run[kind])
                for kind, (_, _, columns) in self._own.items()}

    def refusal(self, tokens: int, bucket: int) -> Optional[str]:
        """Why such a context could never be held, whatever is released;
        None where it could."""
        for kind, need in self._need(tokens, bucket).items():
            run, pages = self._run[kind], self.pools[kind][1]
            if need > pages // run:
                return (f"request needs {need * run} pages but the {kind} "
                        f"pool has only {pages - pages % run} "
                        f"(page_size={self.page_size})" + (
                            f" in whole runs of {run}: {pages % run} of "
                            f"its {pages} are never handed out"
                            if pages % run else ""))
        return None

    def reserve(self, slot: int, tokens: int,
                bucket: int) -> Optional[tuple]:
        """Hold for ``slot`` what ``_need`` says, or None where a pool
        is short (nothing is then taken). What a prefill gets: the
        slot's pages of each pool that take the bucket (of a ring no
        more than the ring has), and every table whole, to upload."""
        need = self._need(tokens, bucket)
        if any(n > len(self.free[kind]) for kind, n in need.items()):
            return None
        pages = {kind: [first + i
                        for first in (self.free[kind].pop() for _ in range(n))
                        for i in range(self._run[kind])]
                 for kind, n in need.items()}
        self._pages[slot] = pages
        self._held[slot] = sum(
            self._page_layers[kind] * len(ids) for kind, ids in pages.items())
        self._one_table[slot] = self._layers * max(map(len, pages.values()))
        for kind, ids in pages.items():
            # (A table whose columns are no whole runs cuts the last.)
            ids = ids[:self.tables[kind].shape[1]]
            self.tables[kind][slot, :] = 0
            self.tables[kind][slot, :len(ids)] = ids
        return ({kind: ids[: bucket // self.page_size]
                 for kind, ids in pages.items()}, self.tables)

    def release(self, slot: int) -> None:
        self._held.pop(slot, None)
        self._one_table.pop(slot, None)
        for kind, ids in self._pages.pop(slot, {}).items():
            self.free[kind].extend(ids[::self._run[kind]])
            self.tables[kind][slot, :] = 0

    def account(self, slots: Iterable[int], contexts: List[int]) -> None:
        """A decode step, once read: ``slots`` decoded in it, at
        ``contexts`` (each the prompt and every token generated before
        this one). The step attended to all of them, in a window layer
        to no more than the window."""
        counts = self.counts
        tokens = sum(contexts)
        counts["decode_kv_tokens"] += tokens
        for layers, window, most in self._reads:
            read = (tokens if window is None
                    else sum(min(c, window) for c in contexts))
            counts["decode_kv_rows_read"] += layers * read
            counts["decode_kv_rows_selected"] += layers * (
                read if most is None
                else sum(min(c, most) for c in contexts))
        if self._blocks:
            self._account_blocks(contexts)
        if self.linear_counts:
            self.linear_counts["slot_layers"] += (
                len(contexts) * self.pools["linear"][0])
        if self.conv_counts:
            self.conv_counts["slot_layers"] += (
                len(contexts) * self.pools["conv"][0])
        counts["decode_state_slot_layers"] += (
            len(contexts) * self._state_layers)
        counts["kv_page_steps_held"] += sum(
            map(self._held.__getitem__, slots))
        counts["kv_page_steps_one_table"] += sum(
            map(self._one_table.__getitem__, slots))

    def _account_blocks(self, contexts: List[int]) -> None:
        """A decode step of a model that selects blocks: in each "full"
        layer a token at position ``t`` (its context) reads, a KV head,
        the pages up to its own of the blocks it keeps (all of them
        before ``dense_len``, ``topk`` after), of the ``t // page + 1``
        the slot holds there, and the walk issues a copy a kept block
        (a KV head, a pool): a block is one run."""
        sizes, counts = self._blocks, self.block_counts
        layers = self.pools["full"][0]
        for t in contexts:
            blocks = t // sizes.block + 1
            dense = t < sizes.dense_len
            kept = blocks if dense else min(sizes.topk, blocks)
            pages = ((kept - 1) * sizes.ratio
                     + t % sizes.block // sizes.stride + 1)
            counts["pages_read"] += layers * pages
            counts["pages_held"] += layers * (t // sizes.stride + 1)
            counts["copies"] += layers * kept
            counts["steps_dense" if dense else "steps_selected"] += 1
            for key in ("decode_kv_rows_read", "decode_kv_rows_selected"):
                self.counts[key] += layers * pages * sizes.stride

    @property
    def kv_token_bytes(self) -> int:
        """What a token holds over all the pools that keep a row a
        token, while every one of them keeps it: each pool's row times
        its layers, as allocated."""
        return sum(row * self.pools[kind][0]
                   for kind, row in self._row_bytes.items())

    def _free_pages(self, kind: str) -> int:
        return len(self.free[kind]) * self._run[kind]

    def reading(self) -> Dict[str, Any]:
        """The counts and the gauges, as ``LLMEngine.stats()`` shows
        and documents them."""
        return {
            **self.counts,
            "free_pages": self._free_pages(self._gauge),
            "pages": {kind: {"layers": layers, "total": total,
                             "free": self._free_pages(
                                 PagedKVCache.RIDES.get(kind, kind))}
                      for kind, (layers, total, _) in self.pools.items()},
            "kv_row_bytes": dict(self._row_bytes),
            "state_slot_bytes": dict(self._slot_bytes),
            "total_pages": self.total_pages,
            "page_size": self.page_size,
            "decode_attention": self.decode_attention,
            "decode_delta": self.decode_delta,
            "decode_linear": self.decode_linear,
            **({"blocks": {**self.block_counts,
                           "mean_row_bytes": self._mean_row_bytes,
                           "topk": self._blocks.topk,
                           "dense_len": self._blocks.dense_len}}
               if self._blocks else {}),
            **({"linear": {**self.linear_counts,
                           "slot_bytes": self._slot_bytes["linear"]
                           * self.pools["linear"][0]}}
               if self.linear_counts else {}),
            **({"conv": {**self.conv_counts,
                         "slot_bytes": self._slot_bytes["conv"]
                         * self.pools["conv"][0],
                         "layers": self.pools["conv"][0],
                         "layers_in_all": self._layers}}
               if self.conv_counts else {}),
            "page_walk_step_tokens": dict(self.page_walk_step_tokens),
            "latent_walk_step_tokens": dict(self.latent_walk_step_tokens),
        }


def _shifted(histories, layer, history, rows, active):
    """A decode step's write of a convolution's histories [L, taps - 1,
    B, C]: at ``layer`` each active slot's becomes the last ``taps - 1``
    of ``rows`` [B, taps, C], its ``history`` [taps - 1, B, C] and the
    token's row behind it; an idle slot's stays as it is. A delta
    layer's and a "conv" layer's alike."""
    return histories.at[layer].set(jnp.where(
        active[None, :, None], rows[:, 1:].transpose(1, 0, 2), history))


def _with_pools(cache: PagedKVCache, pools, lengths) -> PagedKVCache:
    """``cache`` with each kind's pools (``PagedKVCache.pools``' tuples)
    and the slots' lengths replaced."""
    return PagedKVCache(
        {kind: held[0] for kind, held in pools.items()},
        {kind: held[1] for kind, held in pools.items() if len(held) > 1},
        cache.page_table, lengths)


def _exit_gate(cfg: LlamaConfig, params, h):
    """The exit gate's logit of the pass whose normed output of one
    token a sequence is ``h`` [B, M]; None for a model without a gate."""
    return exit_gate_logit(params, h) if cfg.exit_gate else None


def _passes(cfg: LlamaConfig, one_pass, x, pools):
    """A program's walk of the stack: ``one_pass(t, x, pools)`` ->
    ``(x, pools, expert_tokens, gate)`` once for a model of one pass,
    which is then the program it always was; for a looped model an
    outer ``lax.scan`` over the passes around it, the residual and the
    pools its carry (the pools whole, as in the layer scans inside),
    the gates its ys: one body to compile whatever the passes. (Either
    way, this or four scans in a row, XLA re-lays the stacked q, k and
    v weights once a program run, outside the loops that share them:
    1.1 GiB of temporaries, tests/test_tpu_compile_ouro.py; PERF.md section
    7, Open after PR 65.) Returns ``(x, pools, expert_tokens, gates)``,
    the gates [B, passes] or None."""
    if cfg.passes == 1:
        return one_pass(0, x, pools)[:3] + (None,)

    def body(carry, t):
        with jax.named_scope("pass"):
            x, pools, _, gate = one_pass(t, *carry)
        return (x, pools), gate

    (x, pools), gates = jax.lax.scan(body, (x, pools),
                                     jnp.arange(cfg.passes))
    return x, pools, [], None if gates is None else gates.T


def _with_exit(gates, *out):
    """A program's results, and behind them the exit distribution
    [B, passes] of a model with an exit gate."""
    if gates is None:
        return out
    return out + (exit_distribution(gates),)


def paged_decode(
    params: Dict[str, Any],
    tokens: jax.Array,          # [B] one token per slot
    cache: PagedKVCache,
    cfg: LlamaConfig,
    *,
    active: jax.Array,          # [B] bool
) -> Tuple[jax.Array, PagedKVCache, Optional[MoeLoad]]:
    """One decode step over the paged pools: write each active slot's
    token into its current page cell, attend over its pages, return
    [B, V] logits, the updated cache and the step's ``MoeLoad`` (None
    for a dense model); of a model with an exit gate a fourth, the
    token's exit distribution over the passes [B, passes] float32
    (``llama.exit_distribution``). One layer scan a run of alike layers
    (``llama.layer_runs``), each over the pool of its attention kind,
    which it CARRIES whole: as its
    xs and ys a pool would be sliced and re-stacked, pool-sized copies
    every step (PagedKVCache). An inactive slot's pages and length stay
    as they are, and it reaches no expert: the experts a step reads
    follow the live sequences. A "state" layer's pool is carried the
    same way; its step updates each active slot's state and leaves an
    inactive slot's as it is; a "delta" layer's two pools likewise, so
    that a model of delta layers among latent ones steps all three.

    A looped model (``cfg.passes``) walks the runs that many times over
    the same stacked weights, the scans of one pass behind those of the
    pass before in one program: pass ``t`` writes and walks its OWN
    layers of each pool, ``t`` times a pass's layers further in, the
    final norm stands behind every pass and its output is the next
    pass's input. The pools are carried whole through every scan of
    every pass, the decode kernel their only reader and writer."""
    x = embed_tokens(params, tokens, cfg)[:, None]
    pools = {kind: cache.pools(kind) for kind in cache.k}
    runs = layer_runs(cfg)
    # The pools that ride on another's table, carried beside every run's
    # own: a model has the indexers' keys, the page means, or neither.
    riding = next((kind for kind in PagedKVCache.RIDES if kind in pools), None)

    def one_pass(t, x, pools):
        """The runs walked once, as pass ``t``, and the final norm."""
        pools, expert_tokens = dict(pools), []
        # The last selection made, [B, T] float32 (``index_select_decode``):
        # an indexing layer replaces it, the layers that share it take it
        # as the layer loop hands it on.
        selected = None
        for run, stack, index_at in zip(runs, layer_stacks(params),
                                        index_offsets(cfg)):
            layers, expert_stack = split_expert_stack(stack)
            kind, pool = run.kind, pool_kind(run.kind)
            table = cache.page_table[pool]
            # Where the run's layers begin in the pool, in this pass.
            kv_at = t * kv_layers_a_pass(cfg)[pool] + run.kv_offset
            if kind == "latent_index" and selected is None:
                selected = jnp.zeros(
                    (tokens.shape[0], table.shape[1] * cache.page_size),
                    jnp.float32)

            def body(carry, lp):
                # ``keys``: the pools that ride on this model's table,
                # the indexers' keys or the page means and open sums.
                x, held, keys, selected = carry

                def attend(q, k, v):
                    # The token's K/V row goes to ``decode_attention``, which
                    # writes it at position ``lengths[b]`` of each active slot
                    # and attends. Nothing else in the step reads or writes
                    # the pools (a second reader of what goes into the
                    # kernel's aliased call would make XLA copy them): the
                    # block never sees them.
                    with jax.named_scope(f"attn.{kind}"):
                        out, *new = decode_attention(
                            q[:, 0], k[:, 0], v[:, 0], *held,
                            lp["index"] + kv_at, table, cache.lengths,
                            active, window=cfg.window(kind))
                    return out[:, None], (tuple(new), keys, selected)

                def attend_latent(q, row, index, selected=selected, keys=keys):
                    # Absorbed: every head's query against the rows as they
                    # are cached, the values the rows' own latent part; of a
                    # layer with a selection, against the selected rows.
                    if index is not None:
                        q_i, k_i, w_i = index
                        with jax.named_scope("index.score"):
                            selected, key_pool = index_select_decode(
                                q_i[:, 0], w_i[:, 0], k_i[:, 0], *keys,
                                lp["index"] + index_at, table, cache.lengths,
                                active, topk=cfg.index_topk)
                        keys = (key_pool,)
                    sparse = kind != "latent"
                    q_lat = latent_absorb_q(cfg, lp, q)
                    with jax.named_scope(
                            "attn.sparse" if sparse else "attn.latent"):
                        out, pool = latent_decode_attention(
                            q_lat[:, 0], row[:, 0], *held,
                            lp["index"] + kv_at, table, cache.lengths,
                            active, scale=cfg.dh ** -0.5,
                            values=cfg.kv_lora_rank,
                            selected=selected if sparse else None)
                    return (latent_absorb_out(cfg, lp, out[:, None]),
                            ((pool,), keys, selected))

                def attend_state(q, k, v_gate):
                    v, log_g = v_gate
                    with jax.named_scope("attn.state"):
                        out, pool = retention_decode(
                            q[:, 0], k[:, 0], v[:, 0], log_g[:, 0], *held,
                            lp["index"] + kv_at, active)
                    return out[:, None], ((pool,), keys, selected)

                def attend_blocks(q, k, v, keys=keys):
                    # The open page's sum and, where the token fills it,
                    # the page's mean; the selection from the slot's page
                    # means; then the token's k and v written and the
                    # attention over the pages of the kept blocks alone.
                    layer = lp["index"] + kv_at
                    with jax.named_scope("attn.blocks"):
                        chosen, *keys = block_select_decode(
                            q[:, 0], k[:, 0], *keys, layer, table,
                            cache.lengths, active, sizes=cfg.block_select)
                        out, *new = block_decode_attention(
                            q[:, 0], k[:, 0], v[:, 0], *held, layer, table,
                            cache.lengths, active, chosen,
                            sizes=cfg.block_select)
                    return out[:, None], (tuple(new), tuple(keys), selected)

                def attend_linear(q, k, v):
                    log_g = jnp.broadcast_to(lp["log_decay"], q.shape[:1]
                                             + lp["log_decay"].shape)
                    with jax.named_scope("attn.linear"):
                        out, pool = lightning_decode(
                            q[:, 0], k[:, 0], v[:, 0], log_g, *held,
                            lp["index"] + kv_at, active,
                            scale=cfg.linear_head_dim ** -0.5)
                    return out[:, None], ((pool,), keys, selected)

                def attend_delta(q, k, packed):
                    # The convolution's history shifted by the token's row,
                    # then the state's step: each its own pool, both at the
                    # layer, an idle slot's left as they are.
                    v, log_a, beta = packed
                    states, histories = held
                    layer = lp["index"] + kv_at
                    history = histories[layer]            # [taps-1, B, row]
                    q, k, v, rows = delta_mix(cfg, lp, q, k, v,
                                              history.transpose(1, 0, 2))
                    with jax.named_scope("kda.conv"):
                        histories = _shifted(histories, layer, history, rows,
                                             active)
                    with jax.named_scope("attn.delta"):
                        out, states = delta_decode(
                            q[:, 0], k[:, 0], v[:, 0], log_a[:, 0], beta[:, 0],
                            states, layer, active)
                    return out[:, None], ((states, histories), keys, selected)

                def attend_conv(z, _k, _v):
                    # The taps over the slot's history and the token's
                    # row, then the history shifted by that row: the
                    # layer's whole cache, an idle slot's left as it is.
                    (histories,) = held
                    layer = lp["index"] + kv_at
                    history = histories[layer]            # [taps-1, B, M]
                    with jax.named_scope("attn.conv"):
                        y, rows = conv_mix(lp, z, history.transpose(1, 0, 2))
                        with jax.named_scope("conv.mix"):
                            histories = _shifted(histories, layer, history,
                                                 rows, active)
                    return y, ((histories,), keys, selected)

                # The load-balancing loss is a training-only term: dropped.
                x, kept, _aux, load = block(
                    cfg, lp, x, cache.lengths[:, None],
                    attend_blocks if kind == "blocks" else
                    {"latent": attend_latent, "state": attend_state,
                     "delta": attend_delta, "linear": attend_linear,
                     "conv": attend_conv}.get(pool, attend),
                    token_mask=active[:, None], expert_stack=expert_stack,
                    kind=kind)
                return (x,) + kept, load

            (x, pools[pool], keys, selected), load = jax.lax.scan(
                body, (x, pools[pool], pools.get(riding, ()), selected),
                layers)
            if keys:
                pools[riding] = keys
            if load is not None:
                expert_tokens.append(load)
        x = rms_norm(x, params["final_norm"], cfg.rms_eps)
        return x, pools, expert_tokens, _exit_gate(cfg, params, x[:, 0])

    x, pools, expert_tokens, gates = _passes(cfg, one_pass, x, pools)
    logits = head_logits(params, head_input(cfg, x[:, 0]))
    lengths = jnp.where(active, cache.lengths + 1, cache.lengths)
    return _with_exit(
        gates, logits.astype(jnp.float32),
        _with_pools(cache, pools, lengths),
        MoeLoad.of_layers(expert_tokens, cfg.experts_held is not None))


def paged_prefill(
    params: Dict[str, Any],
    tokens: jax.Array,          # [1, S_bucket] padded prompt
    real_len: jax.Array,        # [] int32 true prompt length
    cache: PagedKVCache,
    cfg: LlamaConfig,
    slot: int | jax.Array,
    pages: Dict[str, jax.Array],  # kind -> page ids for this slot
) -> Tuple[jax.Array, PagedKVCache, Optional[MoeLoad]]:
    """Prefill one request: causal self-attention over the padded prompt,
    logits [1, V] at its last real token, each layer's k and v laid
    into the slot's pages of the layer's pool, the slot's length set to
    ``real_len``.
    Rows behind ``real_len`` are the bucket's padding: causal masking
    keeps them from the real rows, they reach no expert of a MoE model,
    and what they leave in the pages lies behind the slot's length,
    where decode writes before it reads. The bucket length must be a
    multiple of the page size (buckets are powers of two >= page).

    ``pages``: for "full" the ``S_bucket // page`` pages that take the
    bucket; for "window" the first ``min(S_bucket // page, columns)``
    columns of the slot's ring, into which go only the pages a later
    token can still attend to, the last of them the one that holds
    ``real_len - 1``; for "state" none: what a retention layer keeps
    is the state after token ``real_len - 1``, laid into the slot whole,
    and the padding never reaches it (a padded token's gate is 1 and
    its key 0), so that a prompt leaves the same state in any bucket;
    for "delta" none either: the state after token ``real_len - 1`` and
    the convolution's last ``delta_conv - 1`` real input rows, both laid
    into the slot whole, beside the latent layers' rows in their pages.
    Returns the run's ``MoeLoad`` too (None for a dense model), and of
    a model with an exit gate the last real token's exit distribution
    [1, passes]. A looped model's passes are walked as ``paged_decode``
    walks them, each pass's k and v laid into its own layers of the
    pool."""
    S = tokens.shape[1]
    page = cache.page_size
    x = embed_tokens(params, tokens, cfg)
    positions = jnp.arange(S)
    token_mask = positions[None] < real_len if cfg.n_experts > 0 else None
    pools = {kind: cache.pools(kind) for kind in cache.k}

    def to_pages(rows, pool, run, offset):
        """[n, 1, S, Hkv, Dh] -> [n, Hkv, S // page, page, Dh], the
        pool's layout, set at the run's layers (from ``offset`` of the
        pool's: a number, or in a pass of a looped model a traced one)
        and the slot's page ids; latent rows and indexer keys
        [n, 1, S, W] -> [n, S // page, page, W] likewise."""
        if run.kind == "state":
            # [n, Hkv, T, R, Dh]: the slot's states of the run's layers.
            return jax.lax.dynamic_update_slice(
                pool, rows[:, None], (offset, slot, 0, 0, 0, 0))
        if run.kind in ("delta", "linear", "conv"):
            # The slot's states [n, H, D, D], whole, or its convolution
            # histories [n, taps - 1, row], the slots second to last.
            rows, at = ((rows[:, None], (offset, slot, 0, 0, 0))
                        if rows.ndim == 4 else
                        (rows[:, :, None], (offset, 0, slot, 0)))
            return jax.lax.dynamic_update_slice(
                pool, rows.astype(pool.dtype), at)
        ids = pages[pool_kind(run.kind)]
        whole = run.n == pool.shape[0]
        looped = not isinstance(offset, int)
        at = slice(None) if whole else slice(offset, offset + run.n)
        if rows.ndim == 4:
            # One row a token for all heads: latent rows, indexer keys.
            paged = rows[:, 0].reshape(run.n, S // page, page, -1)
            return pool.at[at, ids].set(paged.astype(pool.dtype))
        # A token's row as the pool lays it (``kv_pool_row``: the KV
        # heads, or heads of 64 two to a row).
        paged = rows[:, 0].reshape(
            run.n, S // page, page, pool.shape[1], pool.shape[-1]
        ).transpose(0, 3, 1, 2, 4)
        if run.kind == "window":
            # The newest ``len(ids)`` pages up to the last real token's
            # (all of a bucket that fits the ring), each to its column.
            first = jnp.clip((real_len - 1) // page + 1 - len(ids), 0,
                             S // page - len(ids))
            paged = jax.lax.dynamic_slice_in_dim(paged, first, len(ids), 2)
            ids = ids[(first + jnp.arange(len(ids))) % len(ids)]
        if looped:
            # A pass of a looped model, its number known when the
            # program runs: the layers are indices as the pages are, and
            # the two index arrays come first in what is set.
            at = (offset + jnp.arange(run.n))[:, None]
            return pool.at[at, :, ids[None]].set(
                paged.transpose(0, 2, 1, 3, 4).astype(pool.dtype))
        return pool.at[at, :, ids].set(paged.astype(pool.dtype))

    def to_means(means, sums, pool, open_sums, run, offset):
        """A run's page means [n, S // page, W] set at the slot's pages
        of the "mean" pool (the k/v pool's own ids) and the sums of the
        page the prompt leaves open [n, W] at the slot."""
        at = slice(offset, offset + run.n)
        return (pool.at[at, pages["full"]].set(means.astype(pool.dtype)),
                jax.lax.dynamic_update_slice(
                    open_sums, sums[:, None].astype(open_sums.dtype),
                    (offset, slot, 0)))

    runs = layer_runs(cfg)

    def one_pass(t, x, pools):
        """The runs walked once, as pass ``t``, each run's rows laid
        into the pass's layers of its pool, and the final norm."""
        pools, expert_tokens = dict(pools), []
        # A prompt no longer than the selection keeps selects every token
        # before it: None, and the attention is the causal one of a latent
        # layer without an indexer. Else the last selection made
        # (``prefill_select``'s), handed on by the layer loop.
        selects = cfg.index_topk and S > cfg.index_topk
        selected = (empty_selection(S, cfg.index_head_dim) if selects
                    else None)
        for run, stack, index_at in zip(runs, layer_stacks(params),
                                        index_offsets(cfg)):
            layers, expert_stack = split_expert_stack(stack)
            kind, pool = run.kind, pool_kind(run.kind)

            def body(carry, lp):
                x, selected = carry

                def attend(q, k, v):
                    with jax.named_scope(f"attn.{kind}"):
                        out = causal_attention(cfg, None, q, k, v,
                                               window=cfg.window(kind))
                    return out, ((k, v), selected)

                def attend_latent(q, row, index, selected=selected):
                    # Rebuilt: k and v of every head from the rows, for this
                    # attention alone; what is kept is the rows, and of an
                    # indexing layer its keys.
                    kept = (row,)
                    if index is not None:
                        q_i, k_i, w_i = index
                        kept = (row, k_i)
                        if selects:
                            selected = prefill_select(
                                q_i[0], k_i[0], w_i[0], topk=cfg.index_topk)
                    k, v = latent_kv(cfg, lp, row)
                    if kind == "latent" or not selects:
                        with jax.named_scope("attn.latent"):
                            out = causal_attention(cfg, None, q, k, v)
                    else:
                        with jax.named_scope("attn.sparse"):
                            out = sparse_prefill_attention(
                                q[0], k[0], v[0], selected,
                                scale=cfg.dh ** -0.5)[None]
                    return out, (kept, selected)

                def attend_state(q, k, v_gate):
                    v, log_g = v_gate
                    real = positions < real_len
                    with jax.named_scope("attn.state"):
                        out, state = retention_prefill(
                            q[0], jnp.where(real[:, None, None], k[0], 0),
                            v[0], jnp.where(real[:, None], log_g[0], 0.0))
                    return out[None], ((state,), selected)

                def attend_blocks(q, k, v):
                    # Kept beside k and v: every page's mean key, and the
                    # sum of the keys of the page the prompt leaves open.
                    sizes = cfg.block_select
                    means = page_means(k[0], page)
                    opened = ((positions >= real_len // page * page)
                              & (positions < real_len))
                    sums = jnp.where(opened[:, None, None],
                                     k[0].astype(jnp.float32), 0.0).sum(axis=0)
                    with jax.named_scope("attn.blocks"):
                        if S <= sizes.dense_len:
                            out = causal_attention(cfg, None, q, k, v)
                        else:
                            chosen = prefill_block_select(q[0], means,
                                                          sizes=sizes)
                            out = block_prefill_attention(
                                q[0], k[0], v[0], chosen, sizes=sizes)[None]
                    return out, ((k, v, means.reshape(S // page, -1),
                                  sums.reshape(-1)), selected)

                def attend_linear(q, k, v):
                    # From nothing before the prompt; the padding neither
                    # decays nor writes.
                    real = positions < real_len
                    with jax.named_scope("attn.linear"):
                        out, state = lightning_prefill(
                            q[0], jnp.where(real[:, None, None], k[0], 0),
                            v[0], jnp.where(real[:, None],
                                            lp["log_decay"][None], 0.0),
                            scale=cfg.linear_head_dim ** -0.5)
                    return out[None], ((state,), selected)

                def attend_delta(q, k, packed):
                    # From nothing before the prompt; the padding neither
                    # decays nor writes, and the history kept is the last
                    # real tokens' rows (token t lies at row t + taps - 1).
                    v, log_a, beta = packed
                    real = positions < real_len
                    taps = cfg.delta_conv
                    q, k, v, rows = delta_mix(
                        cfg, lp, q, k, v,
                        jnp.zeros((1, taps - 1, cfg.delta_row), q.dtype))
                    history = jax.lax.dynamic_slice_in_dim(
                        rows[0], real_len, taps - 1)
                    with jax.named_scope("attn.delta"):
                        out, state = delta_prefill(
                            q[0], k[0], v[0],
                            jnp.where(real[:, None, None], log_a[0], 0.0),
                            jnp.where(real[:, None], beta[0], 0.0))
                    return out[None], ((state, history), selected)

                def attend_conv(z, _k, _v):
                    # From zeros before the prompt; the history kept is
                    # the last real tokens' rows, not the bucket's end
                    # (token t lies at row t + taps - 1), zeros in front
                    # of a prompt shorter than the taps reach.
                    taps = cfg.conv_taps
                    with jax.named_scope("attn.conv"):
                        y, rows = conv_mix(lp, z, jnp.zeros(
                            (1, taps - 1, z.shape[-1]), z.dtype))
                        history = jax.lax.dynamic_slice_in_dim(
                            rows[0], real_len, taps - 1)
                    return y, ((history,), selected)

                x, (kept, selected), _aux, load = block(
                    cfg, lp, x, positions,
                    attend_blocks if kind == "blocks" else
                    {"latent": attend_latent, "state": attend_state,
                     "delta": attend_delta, "linear": attend_linear,
                     "conv": attend_conv}.get(pool, attend),
                    token_mask=token_mask, expert_stack=expert_stack,
                    kind=kind)
                return (x, selected), (kept, load)

            (x, selected), (kept, load) = jax.lax.scan(body, (x, selected),
                                                       layers)
            kept, keys, riders = kept[:len(pools[pool])], kept[-1], kept[2:]
            kv_at = t * kv_layers_a_pass(cfg)[pool] + run.kv_offset
            pools[pool] = tuple(to_pages(rows, held, run, kv_at)
                                for rows, held in zip(kept, pools[pool]))
            if kind == "latent_index":
                pools["index"] = (to_pages(keys, *pools["index"], run,
                                           index_at),)
            if kind == "blocks":
                pools["mean"] = to_means(*riders, *pools["mean"], run, kv_at)
            if load is not None:
                expert_tokens.append(load)
        x = rms_norm(x, params["final_norm"], cfg.rms_eps)
        return x, pools, expert_tokens, _exit_gate(
            cfg, params, x[:, real_len - 1])

    x, pools, expert_tokens, gates = _passes(cfg, one_pass, x, pools)
    logits = head_logits(params, head_input(cfg, x[:, real_len - 1]))
    lengths = cache.lengths.at[slot].set(real_len)
    return _with_exit(
        gates, logits.astype(jnp.float32),
        _with_pools(cache, pools, lengths),
        MoeLoad.of_layers(expert_tokens, cfg.experts_held is not None))


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
