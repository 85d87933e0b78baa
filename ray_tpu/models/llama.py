"""Llama-family transformer, TPU-first.

A mixture of experts via ``n_experts``, routed as OLMoE routes
(parallel/moe.py). Design choices for TPU/XLA:

- Pure-functional: params are a pytree of arrays; sharding is declared as a
  matching pytree of logical axes (parallel/sharding.py rules) — pjit/GSPMD
  inserts the collectives for dp/fsdp/tp; ring attention (sp) is an explicit
  shard_map island inside the jitted program.
- Layers are *stacked* ([L, ...] leaves) and applied with lax.scan: one
  layer gets compiled once regardless of depth (compile-time O(1) in L),
  and the "layers" leading axis is what pipeline parallelism shards. A
  model whose layers are not all alike is an ordered list of such stacks,
  one a run of alike layers (``layer_runs``), each scanned by the one
  block; what kind a layer is, is known when a program is traced.
- bfloat16 activations/weights with float32 RMSNorm/softmax/rope, the
  standard TPU mixed-precision recipe (MXU eats bf16; norms need f32).
- jax.checkpoint around each layer body for rematerialization.

The reference has no model zoo — it orchestrates user models; this
framework owns its compute path (SURVEY.md §7 phase 7).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..parallel.sharding import prune_spec, with_logical_constraint
from ..parallel.mesh import mesh_axis_size
from ..parallel.collective_matmul import (
    gather_matmul, matmul_scatter, ring_size,
)
from ..parallel.ring_attention import ring_attention
from ..parallel.moe import dispatch, moe_ffn
from ..ops.attention import mha_attention
from ..ops.block_attention import BlockSizes


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32_000
    hidden_size: int = 4096
    intermediate_size: int = 14_336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: Optional[int] = None
    rope_theta: float = 500_000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # MoE when n_experts > 0: softmax over all experts, top_k, gates not
    # renormalised over the chosen ones (parallel/moe.py; dropless).
    n_experts: int = 0
    top_k: int = 2
    # RMSNorm over the whole q and the whole k projection, before the
    # split into heads and before rotary (OLMoE's q_norm / k_norm).
    qk_norm: bool = False
    # What an architecture states beyond Llama's block, each off by
    # default; with all of them off the programs are what they were.
    # A stack that is not uniform: the first ``num_dense_layers`` layers
    # of a model with experts have a dense FFN of ``intermediate_size``,
    # the others experts of ``moe_intermediate_size`` (None: an expert is
    # ``intermediate_size`` wide) beside ``n_shared_experts`` dense ones
    # of the same width, always on.
    num_dense_layers: int = 0
    moe_intermediate_size: Optional[int] = None
    n_shared_experts: int = 0
    # The router (parallel/moe.route): "softmax" | "sigmoid" scores, a
    # learned selection bias that is not in the gate, the chosen gates
    # renormalised, and a scale. What it reads: "ffn", the FFN's normed
    # input (the residual behind the attention under ``mlp_norm``), or
    # "attention", the attention's own normed input: ``block`` then
    # routes and sorts BEFORE the attention (parallel/moe.dispatch), the
    # experts still multiply the FFN's input, and nothing of the route
    # waits for the attention (SmallThinker, PR 57).
    router_score: str = "softmax"
    router_bias: bool = False
    route_norm: bool = False
    route_scale: float = 1.0
    router_input: str = "ffn"
    # The routed experts' gate activation: "silu" (SwiGLU) or "relu"
    # (ReGLU: relu(gate) * up).
    expert_act: str = "silu"
    # A layer's attention kind, "window" or "full" (None: all full). A
    # window layer's token t attends to t - sliding_window < j <= t.
    # Or "state" for every layer: power retention of degree 2
    # (ops/retention.py), a gated linear attention whose weights are
    # (q.k)^2 / dh, decayed by a gate a KV head a token,
    # log g = log_sigmoid(h wg + bg) (``wg`` [hidden, Hkv], ``bg`` [Hkv]
    # float32). What is kept is a state of fixed size a slot and no row
    # a token (generation.PagedKVCache's "state" pool).
    layer_types: Optional[Tuple[str, ...]] = None
    sliding_window: Optional[int] = None
    # Rotary on every layer, or on window layers alone.
    rope_full_layers: bool = True
    # ``qk_norm`` over each head's ``dh`` instead of the whole projection.
    qk_norm_per_head: bool = False
    # What ``init_params`` draws the weights of ``q_norm`` and ``k_norm``
    # around: 1.0 makes them ones, as every other norm's. A trained
    # model's grow well above 1 (normed q and k bound a score by
    # ``sqrt(dh)``, and the weights are what let a softmax over thousands
    # of keys peak); seeded weights with ones attend almost evenly, and
    # an attention layer's output then hardly reaches the logits. With g
    # here each weight is ``g (1 + 0.2 n)``, n normal from the seed, so
    # that scores are about ``N(0, g**4)`` and a weight differs a channel.
    qk_norm_init: float = 1.0
    # The attention output times sigmoid(h wg), before ``wo``.
    attn_gate: bool = False
    # A norm behind the attention and one behind the FFN, on what each
    # adds to the residual stream (four norms a layer).
    post_norms: bool = False
    # The embedding rows times this (muP: sqrt(hidden_size)).
    embed_scale: float = 1.0
    # Latent attention (MLA) when ``kv_lora_rank`` > 0, every layer of
    # kind "latent": q through a bottleneck of ``q_lora_rank`` (normed),
    # k and v rebuilt from one latent row of ``kv_lora_rank`` a token
    # (normed) and one rotary key of ``qk_rope_head_dim`` shared by all
    # heads. A head's q and k are ``qk_nope_head_dim`` + ``qk_rope_head_dim``
    # wide (that sum is ``dh``; only the second part is rotated), its v
    # ``v_head_dim``. What is cached is the latent row and the rotary key
    # (generation.PagedKVCache's "latent" pool); ``num_kv_heads`` is unread.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # ``q_lora_rank`` 0: no q bottleneck, one ``wq`` from the layer's
    # input. ``latent_rope`` off: the ``qk_rope_head_dim`` values of q and
    # of the shared key are left unrotated (a model whose positions come
    # from its other layers).
    latent_rope: bool = True
    # Rotary over adjacent pairs (2i, 2i+1) instead of the two halves.
    rope_interleave: bool = False
    # Layers of kind "delta" among latent ones (``layer_types`` names
    # each layer "latent" or "delta"): a gated delta rule with a decay a
    # channel (ops/delta_attention.py) behind a causal depthwise
    # convolution of ``delta_conv`` taps on q, k and v, ``delta_heads``
    # heads of ``delta_head_dim``, no rotary. What such a layer keeps is
    # a state [heads, width, width] float32 a slot and the convolution's
    # last ``delta_conv - 1`` input rows, no row a token
    # (generation.PagedKVCache's "delta" pools, beside the "latent" one).
    delta_heads: int = 0
    delta_head_dim: int = 0
    delta_conv: int = 0
    # A learned selection over the latent pool (DSA) when ``index_topk``
    # > 0: a layer that ``indexer_types`` calls "full" scores every
    # cached token with an indexer of its own (``index_n_heads`` queries
    # of ``index_head_dim`` from the q bottleneck, one key a token from
    # the layer's input, cached in the "index" pool; the first
    # ``qk_rope_head_dim`` of both rotated; I = sum_j w_j relu(q_j . k)),
    # keeps the ``index_topk`` best positions (``ops/sparse_attention``)
    # and attends over those alone; a "shared" layer has no indexer and
    # attends over the selection of the nearest "full" layer before it.
    index_topk: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_rope_interleave: bool = False
    indexer_types: Optional[Tuple[str, ...]] = None
    # (first, count): the routed experts held here, of ``n_experts`` that
    # the router scores; one chip's share of a layer that ``n_experts /
    # count`` chips hold between them (parallel/moe.py ``held``). None:
    # all of them.
    experts_held: Optional[Tuple[int, int]] = None
    # What each sub-block adds to the residual stream times this, and
    # the head's input divided by ``logit_divisor`` (muP: ``scale_depth /
    # sqrt(layers)`` and ``hidden_size / dim_model_base``).
    residual_scale: float = 1.0
    logit_divisor: float = 1.0
    # Layers of kind "linear" among "full" ones (``layer_types`` names
    # each): Lightning attention (ops/lightning_attention.py), a linear
    # attention of ``linear_heads`` heads of ``linear_head_dim``, a key
    # and a value head for every query head, decayed by a constant a
    # head, ``lightning_attention.log_decays`` of the layer's PUBLISHED
    # index: ``linear_decay_layers`` is (this stack's first layer's
    # published index, the published stack's depth), so that a cut of
    # the depth keeps each layer's own decays. Rotated as a window layer
    # is (``rope_full_layers`` off: on these layers alone), an RMSNorm a
    # head on the output (``o_norm``). What such a layer keeps is a
    # state [heads, width, width] float32 a slot, no row a token
    # (generation.PagedKVCache's "linear" pool, beside the "full" one).
    linear_heads: int = 0
    linear_head_dim: int = 0
    linear_decay_layers: Optional[Tuple[int, int]] = None
    # A selection of BLOCKS over the paged k/v pool
    # (ops/block_attention.py, whose ``BlockSizes`` this is): every
    # "full" layer is then of kind "blocks", scores the mean keys of its
    # pages (the "mean" pool, which rides on the "full" pool's page
    # table), keeps ``topk`` blocks a KV head and attends over those;
    # before ``dense_len`` over everything.
    block_select: Optional[BlockSizes] = None
    # Layers of kind "conv" among "full" ones (``layer_types`` names
    # each): a gated short convolution and no attention. ``w_in`` makes
    # three gates of the hidden size from the layer's normed input, (B,
    # C, X) in this order; z = B * X goes through a causal depthwise
    # convolution of ``conv_taps`` taps (``conv_w`` [taps, hidden], no
    # bias, no activation), and ``w_out`` multiplies C times its output.
    # No rotary, no k and v: what such a layer keeps of a request is the
    # last ``conv_taps - 1`` rows of z, a slot, no row a token and no
    # state either (generation.PagedKVCache's "conv" pool, beside the
    # "full" one).
    conv_taps: int = 0
    # The head is the embedding's transpose: ``params`` has no
    # ``lm_head`` and the logits are ``h . embed^T`` (``head_logits``).
    # Served only: the training programs read ``lm_head``.
    tied_head: bool = False
    # A looped model (Ouro): the whole stack runs ``passes`` times over
    # the ONE set of layer weights, ``final_norm`` behind every pass and
    # its output the next pass's input; the last pass's goes to the head.
    # Each (pass, layer) attends over keys and values of its own, so a
    # paged pool is ``passes`` times as deep as the stack
    # (``kv_layers``): pass t of a pool's layer l lies at ``t *
    # kv_layers_a_pass + l``. ``exit_gate``: behind every pass's norm a
    # gate ``h . exit_w + exit_b`` says how likely a token is to leave
    # there (``exit_distribution``); a token leaves at the first pass
    # whose cumulative probability reaches ``exit_threshold``, at 1.0
    # the last one, always: the only threshold the programs are built
    # for (serve/llm.py ``serving_programs`` says what another needs).
    passes: int = 1
    exit_gate: bool = False
    exit_threshold: float = 1.0
    remat: bool = True
    # "full" (save only layer inputs), "dots" (save matmul outputs and
    # the flash forward's o and lse, recompute elementwise), "mlp" (save
    # the up and gate matmuls), or "save_all" (save every intermediate —
    # no backward recompute). "dots"/"save_all" trade HBM for less
    # backward recompute where memory allows.
    remat_policy: str = "full"
    # Pallas flash attention kernel on TPU (ops/flash_attention.py, which
    # also says for which shapes); the XLA einsum path off-TPU and for
    # the other shapes. Every cell of the benchmark runs it on.
    use_flash: bool = True
    # Cross-entropy sequence chunk: the loss streams over S/chunk slices
    # so the [B, S, V] float32 logits (4.3 GB at B=16, S=2k, V=32k — and
    # the backward saves log-softmax residuals of the same size) never
    # materialize; peak is one [B, chunk, V] slice, recomputed in the
    # backward (jax.checkpoint per chunk). 0 disables chunking.
    loss_chunk: int = 512
    # lax.scan over layers (compile-time O(1) in depth) vs an unrolled
    # python loop. Unrolled avoids the scan's stacked [L, ...] residual
    # buffers — at shallow depth that removes the large contiguous
    # allocations behind the allocator fragmentation that OOMs the
    # selective-remat policies.
    scan_layers: bool = True
    # Layers per scan step (the full-depth schedule). 0/1 scans one layer
    # at a time (the classic stacked-scan path). K>1 scans over L/K
    # chunks of K layers, unrolled inside the chunk body with ONE
    # jax.checkpoint (remat_policy) around the chunk: the scan's stacked
    # residual buffers shrink from [L, ...] to [L/K, ...] — the
    # allocation that drove the 43-46% allocator fragmentation OOMs on
    # selective-remat policies at real depth — while the per-chunk
    # unroll keeps the remat policy's save-set (dots/mlp outputs) local
    # to one chunk. K must divide num_layers.
    scan_chunk: int = 0

    @property
    def dh(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def expert_size(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def latent(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def latent_row(self) -> int:
        """Values a token's row of the latent pool holds: the latent and
        the rotary key, the key on a lane tile (128) of its own so that
        the latent is whole tiles and both are read by one copy."""
        return self.kv_lora_rank + -(-self.qk_rope_head_dim // 128) * 128

    @property
    def experts_here(self) -> int:
        """Routed experts whose weights this program holds."""
        return self.experts_held[1] if self.experts_held else self.n_experts

    @property
    def retention(self) -> bool:
        return bool(self.layer_types) and "state" in self.layer_types

    @property
    def delta_row(self) -> int:
        """Values of a token's row into a delta layer's convolution:
        its q, k and v projections side by side."""
        return 3 * self.delta_heads * self.delta_head_dim

    def window(self, kind: str) -> Optional[int]:
        """The attention window of a layer of ``kind``; None for none."""
        return self.sliding_window if kind == "window" else None

    @staticmethod
    def tiny(vocab: int = 256, moe: bool = False) -> "LlamaConfig":
        return LlamaConfig(
            vocab_size=vocab, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, num_kv_heads=2, rope_theta=10_000.0,
            dtype=jnp.float32, n_experts=4 if moe else 0, top_k=2,
        )


class LayerRun(NamedTuple):
    """Consecutive layers that are alike, so that one body scans their
    stacked weights: the same FFN (dense or experts) and the same
    attention kind. ``kv_offset`` is where the run's layers begin in
    the KV pool of their kind (generation.PagedKVCache)."""

    start: int
    n: int
    moe: bool
    # "full" | "window" | "latent" | "state" | "delta", or a latent
    # layer that attends over a selection: "latent_index" makes one,
    # "latent_shared" takes the last one made. Both keep their rows in
    # the "latent" pool. "linear": a Lightning state a slot. "blocks": a
    # "full" layer that attends over a selection of blocks; its rows lie
    # in the "full" pool. "conv": a gated short convolution, its last
    # inputs a slot.
    kind: str
    kv_offset: int


def pool_kind(kind: str) -> str:
    """The KV pool a layer of ``kind`` keeps its rows in."""
    if kind == "blocks":
        return "full"
    return "latent" if kind.startswith("latent") else kind


def _kind_rules(cfg: LlamaConfig) -> Dict[str, Tuple[Any, str, frozenset]]:
    """For each kind ``layer_types`` may name: (whether the config has
    what such a layer needs, what that is in words, the kinds it may
    stand beside in one model). What no rule lets through cannot be
    run: power retention ("state", degree 2) beside any other kind (its
    ``wg`` is the retention gate's name, its programs scan one pool of
    states: ROADMAP R7), a delta-rule layer beside k/v rows ("delta"
    beside "full": the delta layers' convolution histories and the
    latent pool are what generation.py lays together), a gated short
    convolution ("conv") beside anything but "full" layers and itself
    (beside "window" the pools would lie together as they do beside
    "full", and no configuration has asked; beside "latent" or a kind
    that keeps a state, "state", "delta", "linear", generation.py's
    layer loop hands one run's ``attend`` the pools of one kind and the
    decode programs' tests cover none of those pairs), and a selective
    scan, which is no kind at all (a state whose decay and write are
    functions of the token needs an ``attend`` and a pool of its own)."""
    rows = not cfg.latent
    return {
        "full": (rows, "keeps k and v rows a head, and this model's "
                 "attention is latent (kv_lora_rank > 0: each layer is "
                 "'latent' or 'delta', head_dim the q.k width, "
                 "qk_nope_head_dim + qk_rope_head_dim)",
                 frozenset(("full", "window", "linear", "conv"))),
        "window": (rows and bool(cfg.sliding_window),
                   "needs a sliding_window and k and v rows a head (no "
                   "kv_lora_rank)", frozenset(("full", "window"))),
        "latent": (cfg.latent and cfg.dh == cfg.qk_nope_head_dim
                   + cfg.qk_rope_head_dim,
                   f"needs latent attention (kv_lora_rank > 0) with "
                   f"head_dim ({cfg.dh}) the q.k width, qk_nope_head_dim + "
                   f"qk_rope_head_dim; without it a layer is 'full' or "
                   f"'window', 'linear' or 'state'",
                   frozenset(("latent", "delta"))),
        "delta": (cfg.latent and bool(cfg.delta_heads and cfg.delta_head_dim)
                  and cfg.delta_conv > 1 and not cfg.index_topk,
                  "stands among latent layers (kv_lora_rank > 0; without "
                  "it a layer is 'full' or 'window', 'linear' or 'state': "
                  "a delta-rule state beside k/v rows is not implemented) "
                  "and needs delta_heads, delta_head_dim and a delta_conv "
                  "of two taps or more, and no selection (index_topk)",
                  frozenset(("latent", "delta"))),
        "state": (rows and not cfg.attn_gate
                  and cfg.num_heads % cfg.num_kv_heads == 0,
                  "is power retention: whole groups of query heads a KV "
                  "head and no attn_gate (its wg is the retention gate's "
                  "name)", frozenset(("state",))),
        "linear": (rows and bool(cfg.linear_heads and cfg.linear_head_dim
                                 and cfg.linear_decay_layers),
                   "needs linear_heads, linear_head_dim and "
                   "linear_decay_layers, and k and v rows a head in the "
                   "layers beside it (no kv_lora_rank)",
                   frozenset(("full", "linear"))),
        "conv": (rows and cfg.conv_taps > 1,
                 "needs conv_taps of two or more, and k and v rows a head "
                 "in the layers beside it (no kv_lora_rank)",
                 frozenset(("full", "conv"))),
    }


def layer_runs(cfg: LlamaConfig) -> Tuple[LayerRun, ...]:
    """The model's layers as an ordered list of uniform runs, from what
    the config states; everything about a layer that a program needs to
    know when it is traced. ``layer_types`` names each layer's kind
    (none: every layer "latent" for a model with latent attention, else
    "full"), and ONE rule reads each (``_kind_rules``): a kind needs
    what it needs of the config and may stand beside the kinds it names.
    Llama, Mistral, OLMoE: one run, "full". "window" beside "full";
    "delta" (a state a slot) among "latent"; "linear" (a state a slot)
    among "full"; "conv" (a convolution's history a slot) among "full";
    every layer "state". Under a selection a "latent"
    layer is "latent_index" or "latent_shared" (``index_topk``), a
    "full" one "blocks" (``block_select``)."""
    kinds = cfg.layer_types or (
        ("latent" if cfg.latent else "full",) * cfg.num_layers)
    rules = _kind_rules(cfg)
    if len(kinds) != cfg.num_layers or set(kinds) - set(rules):
        raise ValueError(
            f"layer_types {kinds} must name one of {sorted(rules)} for each "
            f"of {cfg.num_layers} layers")
    for kind in dict.fromkeys(kinds):
        has, needs, beside = rules[kind]
        if not has:
            raise ValueError(f"layer_types {kinds}: a '{kind}' layer {needs}")
        if set(kinds) - beside:
            raise ValueError(
                f"layer_types {kinds}: a '{kind}' layer stands beside "
                f"{sorted(beside - {kind}) or 'no other kind'} only; "
                f"{sorted(set(kinds) - beside)} beside it in one model is "
                f"not implemented (ROADMAP R7: power retention beside "
                f"another kind, a delta-rule layer beside k/v rows, a short "
                f"convolution beside a window, a latent pool or a state)")
    if cfg.index_topk:
        types = cfg.indexer_types or ()
        if (not cfg.latent or len(types) != cfg.num_layers
                or types[0] != "full" or set(types) - {"full", "shared"}):
            raise ValueError(
                f"a selection (index_topk {cfg.index_topk}) is over a "
                f"latent pool: indexer_types {types} must name 'full' or "
                f"'shared' for each of {cfg.num_layers} layers, the first "
                f"'full' (a shared layer takes the selection of a full "
                f"one before it)")
        kinds = tuple("latent_index" if t == "full" else "latent_shared"
                      for t in types)
    if cfg.block_select:
        if "full" not in kinds:
            raise ValueError(
                f"a selection of blocks (block_select) is over the k/v "
                f"pool of 'full' layers; layer_types {kinds} has none")
        kinds = tuple("blocks" if k == "full" else k for k in kinds)
    _check_loop(cfg, kinds)
    if (cfg.router_input not in ("ffn", "attention")
            or cfg.expert_act not in _EXPERT_ACTS):
        raise ValueError(
            f"router_input {cfg.router_input!r} is 'ffn' or 'attention' "
            f"and expert_act {cfg.expert_act!r} one of "
            f"{sorted(_EXPERT_ACTS)}")
    alike = [(cfg.n_experts > 0 and i >= cfg.num_dense_layers, kind)
             for i, kind in enumerate(kinds)]
    runs, seen = [], dict.fromkeys(
        ("full", "window", "latent", "state", "delta", "linear", "conv"), 0)
    for i, (moe, kind) in enumerate(alike):
        if runs and alike[i - 1] == (moe, kind):
            runs[-1] = runs[-1]._replace(n=runs[-1].n + 1)
        else:
            runs.append(LayerRun(i, 1, moe, kind, seen[pool_kind(kind)]))
        seen[pool_kind(kind)] += 1
    return tuple(runs)


def _check_loop(cfg: LlamaConfig, kinds) -> None:
    """What a stack that runs ``cfg.passes`` times may be made of: the
    kinds whose rows lie in pages, "full" and "window", where a pass
    more is layers more of the same pool. Anything else is refused by
    name, with what would have to exist."""
    if cfg.passes < 1 or (cfg.exit_gate and cfg.passes == 1):
        raise ValueError(
            f"passes is {cfg.passes}: a stack runs once or more, and an "
            f"exit gate chooses among two passes or more")
    if cfg.passes == 1:
        return
    missing = [why for kind, why in (
        ("state", "a retention state a slot a PASS"),
        ("delta", "a delta-rule state and a convolution history a slot a "
                  "PASS"),
        ("latent", "a latent row a token a PASS, and an absorbed decode "
                   "that walks the pass's layers of the latent pool"),
        ("latent_index", "a selection a pass: indexer keys a token a PASS"),
        ("linear", "a Lightning state a slot a PASS"),
        ("conv", "a convolution history a slot a PASS"),
        ("blocks", "a selection a pass: page means a PASS"),
    ) if kind in kinds]
    if cfg.n_experts > 0:
        missing.append("the expert-load counters a pass (MoeLoad sums one "
                       "walk of the stack)")
    if missing:
        raise NotImplementedError(
            f"passes={cfg.passes} over layers of kinds {sorted(set(kinds))}"
            f"{' with experts' if cfg.n_experts > 0 else ''} is not "
            f"implemented: a looped stack is 'full' and 'window' layers "
            f"with dense FFNs; it would need " + "; ".join(missing))


def index_offsets(cfg: LlamaConfig) -> Tuple[int, ...]:
    """For each of ``layer_runs``, where its layers begin in the "index"
    pool (the indexing layers before it)."""
    out, seen = [], 0
    for run in layer_runs(cfg):
        out.append(seen)
        seen += run.n * (run.kind == "latent_index")
    return tuple(out)


def kv_layers(cfg: LlamaConfig) -> Dict[str, int]:
    """Layers each KV pool holds, {kind: count}, in the order of first
    use: the layers of that kind, once for every pass of a looped model
    (each (pass, layer) keeps rows of its own)."""
    return {kind: n * cfg.passes
            for kind, n in kv_layers_a_pass(cfg).items()}


def kv_layers_a_pass(cfg: LlamaConfig) -> Dict[str, int]:
    """Layers of each attention kind present, {kind: count}: the KV
    pools a model needs and what one walk of the stack fills of each.
    Pass t of a looped model keeps its rows ``t`` times this further
    into the pool (``LayerRun.kv_offset`` is a layer's place in a pass)."""
    out: Dict[str, int] = {}
    runs = layer_runs(cfg)
    for run in runs:
        kind = pool_kind(run.kind)
        out[kind] = out.get(kind, 0) + run.n
    indexing = out.get("latent") and sum(
        run.n for run in runs if run.kind == "latent_index")
    if indexing:
        # The indexers' keys, one a token an indexing layer: a pool of
        # its own that the latent pool's page table addresses.
        out["index"] = indexing
    if cfg.block_select:
        # A mean key a page a KV head, of every layer that selects
        # blocks: all the "full" pool's, layer for layer, on its table.
        out["mean"] = out["full"]
    return out


def layer_stacks(params) -> Tuple[Dict[str, Any], ...]:
    """``params["layers"]`` as one stacked tree a run: a uniform model
    keeps the single ``[L, ...]`` tree it always had, another holds a
    tuple of them in ``layer_runs``' order."""
    layers = params["layers"]
    return tuple(layers) if isinstance(layers, (tuple, list)) else (layers,)


def require_uniform(cfg: LlamaConfig, what: str) -> None:
    """Training scans ONE stack with ONE causal attention, and the flash
    backward kernels take no window and one width for q, k and v: a
    stack in runs, a window layer, a latent-attention layer, a
    retention, delta-rule or Lightning layer (kinds "state", "delta",
    "linear": their chunked scans have no backward) or a short
    convolution (kind "conv": its backward is a correlation of
    ``conv_taps`` taps, the cheapest of these to bring, but its layers
    stand among "full" ones in runs) trains nowhere yet (ROADMAP R3, R5,
    R7), and says so by name; nor does a tied head (``tied_head``: the
    loss reads ``lm_head``)."""
    if cfg.tied_head:
        raise NotImplementedError(
            f"{what}: training a model whose head is its embedding's "
            f"transpose (tied_head) is not implemented: the loss reads "
            f"params['lm_head'] and would need the embedding's gradient "
            f"summed from both uses; it is served only "
            f"(models/generation.py)")
    if len(layer_runs(cfg)) > 1 or set(kv_layers_a_pass(cfg)) - {"full"}:
        raise NotImplementedError(
            f"{what}: training a model whose layer stack is not uniform "
            f"(dense layers before expert layers, window, linear or conv "
            f"beside full attention), whose attention is latent (q.k and v "
            f"of unequal widths) or over a selection of blocks, or whose "
            f"layers are of kind 'state', 'delta', 'linear' or 'conv' "
            f"(power retention, the delta rule, Lightning attention: the "
            f"chunked scans have no backward pass; a short convolution: "
            f"its backward, a correlation of conv_taps taps, is not "
            f"written) is not implemented; it is served only "
            f"(models/generation.py)")


# Logical axes for each parameter leaf (maps through DEFAULT_RULES:
# embed→fsdp, heads/mlp/vocab→tp, expert→ep, layers→pp-or-scan).
def param_logical_axes(cfg: LlamaConfig) -> Dict[str, Any]:
    require_uniform(cfg, "param_logical_axes")
    layer = {
        "attn_norm": ("layers", "norm"),
        "wq": ("layers", "embed", "heads", "head_dim"),
        "wk": ("layers", "embed", "kv_heads", "head_dim"),
        "wv": ("layers", "embed", "kv_heads", "head_dim"),
        "wo": ("layers", "heads", "head_dim", "embed"),
        "mlp_norm": ("layers", "norm"),
    }
    if cfg.qk_norm:
        layer.update(q_norm=("layers", "norm"), k_norm=("layers", "norm"))
    if cfg.attn_gate:
        layer["wg"] = ("layers", "embed", "heads", "head_dim")
    if cfg.post_norms:
        layer.update(post_attn_norm=("layers", "norm"),
                     post_mlp_norm=("layers", "norm"))
    if cfg.n_experts > 0:
        layer.update(
            router=("layers", "embed", None),
            w_gate=("layers", "expert", "embed", "mlp"),
            w_up=("layers", "expert", "embed", "mlp"),
            w_down=("layers", "expert", "mlp", "embed"),
        )
        if cfg.n_shared_experts:
            layer.update(ws_gate=("layers", "embed", "mlp"),
                         ws_up=("layers", "embed", "mlp"),
                         ws_down=("layers", "mlp", "embed"))
        if cfg.router_bias:
            layer["expert_bias"] = ("layers", None)
    else:
        layer.update(
            w_gate=("layers", "embed", "mlp"),
            w_up=("layers", "embed", "mlp"),
            w_down=("layers", "mlp", "embed"),
        )
    return {
        "embed": ("vocab", "embed"),
        "layers": layer,
        "final_norm": ("norm",),
        "lm_head": ("embed", "vocab"),
        **({"exit_w": ("embed", None), "exit_b": (None,)}
           if cfg.exit_gate else {}),
    }


# A leaf whose float32 form is larger than this is drawn a slice of its
# leading axis at a time (``_normal``): 2**30 elements, the largest leaf
# any configuration had before there was a larger one (OLMoE's experts,
# [8, 64, 2048, 1024]), which are therefore drawn whole as ever.
_WHOLE_LEAF_ELEMENTS = 2 ** 30


def _normal(key, shape, scale: float, dtype):
    """A leaf of normal values times ``scale`` in ``dtype``, drawn in
    float32. A leaf is drawn whole, as it always was, unless it is over
    ``_WHOLE_LEAF_ELEMENTS``: then a slice of its leading axis at a
    time, each from a key of its own, so that the float32 temporaries
    are a slice's (256 experts of a layer, 1.6 GB, where four layers'
    whole would be 6.4 GB beside 11 GB of weights)."""
    def draw(key, shape):
        return (jax.random.normal(key, shape, dtype=jnp.float32)
                * scale).astype(dtype)

    if math.prod(shape) <= _WHOLE_LEAF_ELEMENTS:
        return draw(key, shape)
    return jax.lax.map(lambda k: draw(k, shape[1:]),
                       jax.random.split(key, shape[0]))


def _retention_gate_bias(n: int, kv_heads: int) -> jax.Array:
    """``bg`` [n, Hkv] float32: KV head i's gate remembers about
    ``tau_i`` tokens, 32 to 4,096 in equal ratios over the heads, so
    sigmoid(bg) = 1 - 1/tau. A gate without a bias is 1/2 in the median
    for any weights drawn around zero, a memory of two tokens: neither
    a long context nor the state's precision would be exercised."""
    span = jnp.arange(kv_heads, dtype=jnp.float32) / max(kv_heads - 1, 1)
    tau = 32.0 * 128.0 ** span
    return jnp.broadcast_to(jnp.log(tau - 1.0), (n, kv_heads))


def _init_delta(cfg: LlamaConfig, k, n: int) -> Dict[str, Any]:
    """The attention half of ``n`` delta layers: q, k, v and o, the
    convolution's taps (``conv_w`` [taps, q|k|v channels]), the decay
    gate through a bottleneck of the head's width (``wf_a``, ``wf_b``,
    ``a_log`` a head, ``dt_bias`` a channel), the write strength
    (``wb``), the output gate through the same bottleneck (``wg_a``,
    ``wg_b``) and the norm on each head's output.

    log a = -exp(a_log) softplus(f + dt_bias): ``a_log`` is log U(1, 16)
    and ``dt_bias`` the inverse softplus of a step log-uniform in
    [1e-3, 0.1], so that with f = 0 a channel forgets over between a few
    tokens and a thousand (a decay drawn around 1/2 would exercise
    neither a long context nor the float32 state)."""
    M, H, D, dt = (cfg.hidden_size, cfg.delta_heads, cfg.delta_head_dim,
                   cfg.dtype)

    def winit(shape, fan_in):
        return _normal(next(k), shape, fan_in ** -0.5, dt)

    layers = {
        "attn_norm": jnp.ones((n, M), jnp.float32),
        "wq": winit((n, M, H, D), M),
        "wk": winit((n, M, H, D), M),
        "wv": winit((n, M, H, D), M),
        "wo": winit((n, H, D, M), H * D),
        "mlp_norm": jnp.ones((n, M), jnp.float32),
        "conv_w": winit((n, cfg.delta_conv, cfg.delta_row), cfg.delta_conv),
        "wf_a": winit((n, M, D), M),
        "wf_b": winit((n, D, H, D), D),
        "a_log": jnp.log(jax.random.uniform(
            next(k), (n, H), jnp.float32, 1.0, 16.0)),
        "wb": winit((n, M, H), M),
        "wg_a": winit((n, M, D), M),
        "wg_b": winit((n, D, H, D), D),
        "o_norm": jnp.ones((n, D), jnp.float32),
    }
    step = jnp.exp(jax.random.uniform(
        next(k), (n, H, D), jnp.float32, math.log(1e-3), math.log(0.1)))
    layers["dt_bias"] = step + jnp.log(-jnp.expm1(-step))
    return layers


def _init_stack(cfg: LlamaConfig, k, n: int, moe: bool,
                kind: str = "full", start: int = 0) -> Dict[str, Any]:
    """``n`` alike layers' weights, stacked ``[n, ...]``, drawing keys
    from the iterator ``k`` in an order that never changes for a leaf
    that is there (new leaves draw last): a seed's weights stay what
    they were."""
    M, H, Hkv, Dh, dt = (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                         cfg.dh, cfg.dtype)
    if kind == "linear":
        # A key and a value head for every query head, of its own width.
        H = Hkv = cfg.linear_heads
        Dh = cfg.linear_head_dim

    def norm_init(shape):
        return jnp.ones(shape, dtype=jnp.float32)

    def winit(key, shape, fan_in):
        return _normal(key, shape, fan_in ** -0.5, dt)

    if kind == "delta":
        layers = _init_delta(cfg, k, n)
    elif kind == "conv":
        # The three gates' projection (B | C | X), the taps a channel
        # and the output projection: no q, k, v, no norm a head.
        layers = {
            "attn_norm": norm_init((n, M)),
            "w_in": winit(next(k), (n, M, 3 * M), M),
            "conv_w": winit(next(k), (n, cfg.conv_taps, M), cfg.conv_taps),
            "w_out": winit(next(k), (n, M, M), M),
            "mlp_norm": norm_init((n, M)),
        }
    elif cfg.latent:
        # The down-projections and their norms, the up-projections a
        # head (``wk_b`` and ``wv_b`` are the two halves of the
        # published kv_b_proj, W_UK and W_UV), the output projection.
        # Without a q bottleneck (``q_lora_rank`` 0) one ``wq``.
        Rq, Rkv = cfg.q_lora_rank, cfg.kv_lora_rank
        layers: Dict[str, Any] = {
            "attn_norm": norm_init((n, M)),
            **({"wq_a": winit(next(k), (n, M, Rq), M),
                "q_a_norm": norm_init((n, Rq)),
                "wq_b": winit(next(k), (n, Rq, H, Dh), Rq)} if Rq else
               {"wq": winit(next(k), (n, M, H, Dh), M)}),
            "wkv_a": winit(next(k), (n, M, Rkv + cfg.qk_rope_head_dim), M),
            "kv_a_norm": norm_init((n, Rkv)),
            "wk_b": winit(next(k), (n, Rkv, H, cfg.qk_nope_head_dim), Rkv),
            "wv_b": winit(next(k), (n, Rkv, H, cfg.v_head_dim), Rkv),
            "wo": winit(next(k), (n, H, cfg.v_head_dim, M),
                        H * cfg.v_head_dim),
            "mlp_norm": norm_init((n, M)),
        }
    else:
        layers = {
            "attn_norm": norm_init((n, M)),
            "wq": winit(next(k), (n, M, H, Dh), M),
            "wk": winit(next(k), (n, M, Hkv, Dh), M),
            "wv": winit(next(k), (n, M, Hkv, Dh), M),
            "wo": winit(next(k), (n, H, Dh, M), H * Dh),
            "mlp_norm": norm_init((n, M)),
        }
    if cfg.qk_norm and kind != "conv":
        per_head = cfg.qk_norm_per_head
        layers.update(q_norm=norm_init((n, Dh if per_head else H * Dh)),
                      k_norm=norm_init((n, Dh if per_head else Hkv * Dh)))
    if moe:
        # The router scores all of them; the stacks hold those held here.
        R, E, F = cfg.n_experts, cfg.experts_here, cfg.expert_size
        layers.update(
            router=winit(next(k), (n, M, R), M).astype(jnp.float32),
            w_gate=winit(next(k), (n, E, M, F), M),
            w_up=winit(next(k), (n, E, M, F), M),
            w_down=winit(next(k), (n, E, F, M), F),
        )
        if cfg.n_shared_experts:
            Fs = F * cfg.n_shared_experts
            layers.update(ws_gate=winit(next(k), (n, M, Fs), M),
                          ws_up=winit(next(k), (n, M, Fs), M),
                          ws_down=winit(next(k), (n, Fs, M), Fs))
        if cfg.router_bias:
            # Nonzero, so that "selects, but is not in the gate" shows.
            layers["expert_bias"] = 0.05 * jax.random.normal(
                next(k), (n, R), dtype=jnp.float32)
    else:
        F = cfg.intermediate_size
        layers.update(
            w_gate=winit(next(k), (n, M, F), M),
            w_up=winit(next(k), (n, M, F), M),
            w_down=winit(next(k), (n, F, M), F),
        )
    if cfg.qk_norm and cfg.qk_norm_init != 1.0 and kind != "conv":
        for name in ("q_norm", "k_norm"):
            layers[name] = cfg.qk_norm_init * (1 + 0.2 * jax.random.normal(
                next(k), layers[name].shape, dtype=jnp.float32))
    if cfg.attn_gate and kind != "conv":
        layers["wg"] = winit(next(k), (n, M, H, Dh), M)
    if cfg.post_norms:
        layers.update(post_attn_norm=norm_init((n, M)),
                      post_mlp_norm=norm_init((n, M)))
    if kind == "state":
        layers.update(wg=winit(next(k), (n, M, Hkv), M),
                      bg=_retention_gate_bias(n, Hkv))
    if kind == "linear":
        # The norm on each head's output, and each layer's decays a
        # head, from the layer's published index: no learned value, kept
        # with the layer so that the layer scan slices them.
        from ..ops.lightning_attention import log_decays

        first, published = cfg.linear_decay_layers
        layers.update(
            o_norm=norm_init((n, Dh)),
            log_decay=jnp.stack([log_decays(H, first + start + i, published)
                                 for i in range(n)]))
    if kind == "latent_index":
        # The indexer: queries from the q bottleneck, one key a token
        # and the heads' weights from the layer's input, a LayerNorm
        # (weight and bias) on the key.
        Hi, Di = cfg.index_n_heads, cfg.index_head_dim
        layers.update(
            wi_q=winit(next(k), (n, cfg.q_lora_rank, Hi, Di),
                       cfg.q_lora_rank),
            wi_k=winit(next(k), (n, M, Di), M),
            wi_w=winit(next(k), (n, M, Hi), M),
            i_k_norm=norm_init((n, Di)),
            i_k_bias=jnp.zeros((n, Di), dtype=jnp.float32))
    return layers


def init_params(cfg: LlamaConfig, key: jax.Array) -> Dict[str, Any]:
    """``layers`` is one stacked tree for a uniform model and a tuple of
    them, one a run (``layer_runs``), for any other."""
    M, V = cfg.hidden_size, cfg.vocab_size
    runs = layer_runs(cfg)

    def keys(key):
        # Sixteen as ever, so that a seed's weights stay what they were,
        # and sixteen more for a stack with more leaves than that.
        yield from jax.random.split(key, 16)
        yield from jax.random.split(jax.random.fold_in(key, 16), 16)

    k = keys(key)
    if len(runs) == 1:
        layers = _init_stack(cfg, k, cfg.num_layers, runs[0].moe,
                             runs[0].kind, 0)
    else:
        layers = tuple(
            _init_stack(cfg, keys(jax.random.fold_in(key, run.start)),
                        run.n, run.moe, run.kind, run.start)
            for run in runs)

    def winit(key, shape):
        return _normal(key, shape, M ** -0.5, cfg.dtype)

    params = {
        "embed": winit(next(k), (V, M)),
        "layers": layers,
        "final_norm": jnp.ones((M,), dtype=jnp.float32),
    }
    if not cfg.tied_head:
        params["lm_head"] = winit(next(k), (M, V))
    if cfg.exit_gate:
        # A Linear(hidden, 1) with a bias. Against a normed state of
        # unit RMS the gate's logit is then about N(0, 1): sigmoid of it
        # spread about 1/2, and the exit distribution not degenerate.
        params.update(exit_w=winit(next(k), (M, 1)),
                      exit_b=jnp.zeros((1,), dtype=jnp.float32))
    return params


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale * w).astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float,
         interleave: bool = False) -> jax.Array:
    """Rotary embedding; x [B, S, H, D]; positions [S], the same for every
    row (global indices, so sequence-sharded blocks stay correct), or
    [B, S], each row's own (serving slots at different lengths). Pair i
    of a head, turned by ``positions * theta ** (-i / (D/2))``, is
    dimensions (i, i + D/2), or with ``interleave`` (2i, 2i + 1)."""
    D = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, D // 2, dtype=jnp.float32) / (D // 2))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [(B,) S, D/2]
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    xf = x.astype(jnp.float32)
    if interleave:
        pairs = xf.reshape(x.shape[:-1] + (D // 2, 2))
        x1, x2 = pairs[..., 0], pairs[..., 1]
        out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                        axis=-1).reshape(x.shape)
    else:
        x1, x2 = jnp.split(xf, 2, axis=-1)
        out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                              axis=-1)
    return out.astype(x.dtype)


def causal_attention(cfg: LlamaConfig, mesh, q, k, v, window=None):
    """Causal self-attention of q [B,S,H,Dh] over k, v [B,S,Hkv,Dh] of
    the same S tokens: training's, and a prefill's; with ``window``, of
    each token over the last ``window`` tokens, itself among them (a
    prefill's only). The one place that
    chooses among ring attention (an ``sp`` axis), the flash kernel and
    the XLA einsum; for which platform and shapes the kernel itself runs
    is ops/flash_attention.py's to say."""
    if mesh is not None and mesh_axis_size(mesh, "sp") > 1:
        if window is not None:
            raise NotImplementedError("ring attention takes no window")
        return ring_attention(q, k, v, mesh, causal=True)
    if cfg.use_flash:
        from ..ops.flash_attention import flash_attention

        attend = functools.partial(flash_attention, causal=True,
                                   window=window)
        if mesh is not None:
            # GSPMD cannot partition a Mosaic kernel ("wrap the call in
            # a shard_map"): hand each device its (batch, heads) shard.
            # GQA groups stay whole because heads and kv_heads split
            # over the same tp axis in the same order.
            spec = prune_spec(mesh, P(("dp", "fsdp"), None, "tp", None))
            attend = jax.shard_map(
                attend, mesh=mesh, in_specs=(spec, spec, spec),
                out_specs=spec, check_vma=False,
            )
        return attend(q, k, v)
    return mha_attention(q, k, v, causal=True, window=window)


def prefill_attention_path(cfg: LlamaConfig, tokens: int) -> Optional[str]:
    """What ``causal_attention`` runs in a prefill of ``tokens`` (one
    bucket, no mesh) for this model's layers that attend over k and v
    rows: ops/flash_attention.py's ``forward_path``, ``"einsum"`` with
    ``use_flash`` off; None where no layer calls it for such a prompt
    (retention layers; a selection, of rows or of blocks, over a longer
    prompt)."""
    from ..ops.flash_attention import forward_path

    if (cfg.retention or (cfg.index_topk and tokens > cfg.index_topk)
            or (cfg.block_select and tokens > cfg.block_select.dense_len)):
        return None
    if not cfg.use_flash:
        return "einsum"
    kv_heads, dv = ((cfg.num_heads, cfg.v_head_dim) if cfg.latent
                    else (cfg.num_kv_heads, cfg.dh))
    return forward_path(tokens, tokens, cfg.dh, dv, cfg.num_heads, kv_heads,
                        jnp.dtype(cfg.dtype).itemsize)


# What a ring matmul hands back is named, and the "dots" remat policy
# keeps it as it keeps a plain matmul's output (to the policy the island
# is no dot): the backward runs no ring a second time.
_RING_SAVED = "tp_ring_matmul"


def _ring_saved(out):
    return jax.tree.map(lambda y: checkpoint_name(y, _RING_SAVED), out)


# The stacked projections a serving engine keeps in another order than
# the published one (serve/llm.py ``_Runner``), each with the order: the
# published layer's axes as the serving tree lays them, the heads first
# and LAST the axis the decode step contracts. Given so the TPU compiler
# reads a layer's slice where it lies; given as published ([M, H, D]) it
# copies every one of these leaves into that order inside every decode
# step (Ouro: 3 x 1.22 ms of a 40 ms step; tests/tpu_rehearsal.py
# ``assert_projections_stay_in_place`` holds each leaf to it). ``wk_b``
# is the one leaf two programs contract differently: the decode step's
# absorbed form contracts the head's width, a prefill ``kv_lora_rank``;
# the decode step decides. A leaf turns only as a stack of [M, H, D]
# layers (a retention layer's ``wg`` [M, Hkv] stays as it is), and lies
# in the serving tree under its name and ``TURNED``.
SERVING_ORDER = {
    "wq": (1, 2, 0), "wk": (1, 2, 0), "wv": (1, 2, 0), "wg": (1, 2, 0),
    "wq_b": (1, 2, 0), "wi_q": (1, 2, 0), "wv_b": (1, 2, 0),
    "wk_b": (1, 0, 2), "wf_b": (1, 2, 0), "wg_b": (1, 2, 0),
}
TURNED = "_t"


def turning_leaves(params, back: bool = False):
    """Of each of ``layer_stacks(params)`` the leaves that the serving
    tree keeps in another order, ``{name: leaf}``: of a published tree
    those that turn, with ``back`` of a serving tree those that were
    turned."""
    def turns(name, w):
        if back:
            return name.endswith(TURNED) and name[:-len(TURNED)] in SERVING_ORDER
        return name in SERVING_ORDER and w.ndim == 4

    return tuple({name: w for name, w in stack.items() if turns(name, w)}
                 for stack in layer_stacks(params))


def turn_leaves(leaves, back: bool = False):
    """``turning_leaves``' dicts with every leaf transposed into the
    serving order and named ``name + TURNED``, or ``back`` into the
    published one under its published name. Traced: the caller jits it,
    one program whatever the stacks and leaves."""
    def turned(name, w):
        if back:
            name = name[:-len(TURNED)]
            order = SERVING_ORDER[name]
            order = tuple(order.index(a) for a in range(len(order)))
            new = name
        else:
            order, new = SERVING_ORDER[name], name + TURNED
        return new, jnp.transpose(w, (0, *(a + 1 for a in order)))

    return tuple(dict(turned(name, w) for name, w in stack.items())
                 for stack in leaves)


def serving_tree(params, turn=turn_leaves, back: bool = False):
    """The published tree ``params`` as a serving engine keeps it, or
    with ``back`` such a tree as it was published: the leaves of
    ``SERVING_ORDER`` through ``turn`` (``turn_leaves``, or the caller's
    jitted form of it); every other leaf is the same array, shared and
    not copied."""
    old = turning_leaves(params, back)
    new = turn(old, back) if any(old) else old
    stacks = tuple({**{k: v for k, v in stack.items() if k not in gone},
                    **come}
                   for stack, gone, come in zip(layer_stacks(params), old, new))
    uniform = not isinstance(params["layers"], (tuple, list))
    return {**params, "layers": stacks[0] if uniform else stacks}


def project(lp, name, x, spec):
    """``einsum(spec, x, w)`` with this layer's slice ``w`` of the stacked
    projection ``name``, ``spec`` written for the published order of its
    axes: the one place that reads a leaf of ``SERVING_ORDER``. A
    published tree holds it under ``name`` and is multiplied as it
    always was; a serving tree holds it turned under ``name + TURNED``,
    and the same product is asked for with the leaf's letters in that
    order."""
    if name in lp:
        return jnp.einsum(spec, x, lp[name])
    operands, out = spec.split("->")
    xs, ws = operands.split(",")
    ws = "".join(ws[a] for a in SERVING_ORDER[name])
    return jnp.einsum(f"{xs},{ws}->{out}", x, lp[name + TURNED])


def qkv_proj(cfg: LlamaConfig, lp, x, *, mesh=None):
    """The block's first half up to rotary: attention norm, then q
    [B,S,H,Dh] and k, v [B,S,Hkv,Dh], q and k normed where the model has
    a QK-norm (over their whole projection, or over each head's ``dh``),
    and the output gate sigmoid(h wg) [B,S,H,Dh] where it has one (else
    None); for a retention layer the fourth is the tokens' log gates
    log_sigmoid(h wg + bg) [B,S,Hkv] float32, which go to its attention
    and not behind it. Under a mesh whose ``tp`` ring runs
    (``ring_size``) x comes with its rows over ``tp`` and q, k, v leave
    whole along them: the gather rides beside the three matmuls."""
    B, S, _ = x.shape
    h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
    if ring_size(mesh, S) > 1:
        q, k, v = _ring_saved(
            gather_matmul(mesh, h, (lp["wq"], lp["wk"], lp["wv"])))
    else:
        q = project(lp, "wq", h, "bsm,mhd->bshd")
        k = project(lp, "wk", h, "bsm,mhd->bshd")
        v = project(lp, "wv", h, "bsm,mhd->bshd")
    if cfg.qk_norm and cfg.qk_norm_per_head:
        q = rms_norm(q, lp["q_norm"], cfg.rms_eps)
        k = rms_norm(k, lp["k_norm"], cfg.rms_eps)
    elif cfg.qk_norm:
        q = rms_norm(q.reshape(B, S, -1), lp["q_norm"],
                     cfg.rms_eps).reshape(q.shape)
        k = rms_norm(k.reshape(B, S, -1), lp["k_norm"],
                     cfg.rms_eps).reshape(k.shape)
    gate = None
    if cfg.attn_gate:
        g = project(lp, "wg", h, "bsm,mhd->bshd")
        gate = jax.nn.sigmoid(g.astype(jnp.float32)).astype(g.dtype)
    elif cfg.retention:
        with jax.named_scope("ret.gate"):
            g = jnp.einsum("bsm,mn->bsn", h, lp["wg"])
            gate = jax.nn.log_sigmoid(g.astype(jnp.float32) + lp["bg"])
    return q, k, v, gate


def _latent_row(cfg: LlamaConfig, latent, rotary):
    """[.., kv_lora_rank] ‖ [.., qk_rope_head_dim] ‖ zeros up to
    ``cfg.latent_row``: how a cached row is laid, and a query against it."""
    pad = jnp.zeros(latent.shape[:-1] + (
        cfg.latent_row - latent.shape[-1] - rotary.shape[-1],), latent.dtype)
    return jnp.concatenate([latent, rotary, pad], axis=-1)


def latent_proj(cfg: LlamaConfig, lp, x, positions):
    """A latent layer's first half: attention norm, then the rotated
    queries [B,S,H,dh] (each head ``qk_nope_head_dim`` unrotated values
    and ``qk_rope_head_dim`` rotated ones) through the normed bottleneck
    of ``q_lora_rank``, and the token's latent row [B,S,``latent_row``]:
    the normed latent of ``kv_lora_rank``, then the one rotated key all
    heads share, then zeros up to the lane tile. The row is what a cache
    keeps (generation.PagedKVCache) and all that a later token needs."""
    return _latent_parts(cfg, lp, x, positions)[:2]


def _latent_parts(cfg: LlamaConfig, lp, x, positions):
    """``latent_proj``'s (q, row), and behind them what an indexer reads
    besides: the normed input ``h`` and the normed q bottleneck ``c_q``."""
    nope, rank = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)

    def turned(x):
        if not cfg.latent_rope:
            return x
        return rope(x, positions, cfg.rope_theta, cfg.rope_interleave)

    with jax.named_scope("mla.q"):
        c_q = None
        if cfg.q_lora_rank:
            c_q = rms_norm(jnp.einsum("bsm,mr->bsr", h, lp["wq_a"]),
                           lp["q_a_norm"], cfg.rms_eps)
            q = project(lp, "wq_b", c_q, "bsr,rhd->bshd")
        else:
            q = project(lp, "wq", h, "bsm,mhd->bshd")
        q = jnp.concatenate([q[..., :nope], turned(q[..., nope:])], axis=-1)
    with jax.named_scope("mla.kv"):
        kv = jnp.einsum("bsm,mr->bsr", h, lp["wkv_a"])
        c = rms_norm(kv[..., :rank], lp["kv_a_norm"], cfg.rms_eps)
        k_rope = turned(kv[..., None, rank:])[..., 0, :]
        row = _latent_row(cfg, c, k_rope)
    return q, row, h, c_q


def causal_taps(w, tokens, history):
    """A causal depthwise convolution of ``tokens`` [B, S, C] behind
    ``history`` [B, taps - 1, C], the rows of the tokens just before
    them (zeros before a prompt's first): ``y_t = sum_i w_i
    x_{t-taps+1+i}`` with ``w`` [taps, C], no bias, summed in float32.
    Returns (y [B, S, C] float32, the rows [B, taps - 1 + S, C] of
    history and tokens together, of which a cache keeps the last ``taps
    - 1`` real ones): what a delta layer's and a "conv" layer's mix
    share."""
    S = tokens.shape[1]
    rows = jnp.concatenate([history.astype(tokens.dtype), tokens], axis=1)
    w = w.astype(jnp.float32)
    y = sum(rows[:, i:i + S].astype(jnp.float32) * w[i]
            for i in range(w.shape[0]))
    return y, rows


def conv_proj(cfg: LlamaConfig, lp, x):
    """A "conv" layer's first half: attention norm, ``w_in`` to the
    three gates (B, C, X), each ``hidden_size`` wide and in this order,
    and z = B * X [B, S, M], what the convolution mixes and all that a
    later token needs of this one. Returns (z, C)."""
    h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
    with jax.named_scope("conv.proj"):
        b, c, xg = jnp.split(jnp.einsum("bsm,mn->bsn", h, lp["w_in"]), 3,
                             axis=-1)
        return b * xg, c


def conv_mix(lp, z, history):
    """The ``conv_taps`` taps over z [B, S, M] behind ``history`` [B,
    taps - 1, M] (``causal_taps``; no activation). Returns (y [B, S, M]
    in z's dtype, the rows of history and tokens together)."""
    with jax.named_scope("conv.mix"):
        y, rows = causal_taps(lp["conv_w"], z, history)
        return y.astype(z.dtype), rows


def delta_proj(cfg: LlamaConfig, lp, x):
    """A delta layer's first half up to its convolution: attention norm,
    then q, k and v [B,S,H,D] as projected (``delta_mix`` takes them on),
    the token's log decays [B,S,H,D] float32, a channel of k each,
    ``-exp(a_log) softplus((h wf_a) wf_b + dt_bias)``, its write strength
    sigmoid(h wb) [B,S,H] float32, and the output gate
    sigmoid((h wg_a) wg_b) [B,S,H,D]. Returns (q, k, (v, log decays,
    write strength), gate): what ``block`` hands to ``attend``, and the
    gate for behind it."""
    h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
    with jax.named_scope("kda.proj"):
        q = project(lp, "wq", h, "bsm,mhd->bshd")
        k = project(lp, "wk", h, "bsm,mhd->bshd")
        v = project(lp, "wv", h, "bsm,mhd->bshd")
    with jax.named_scope("kda.gate"):
        f = project(lp, "wf_b", jnp.einsum("bsm,mr->bsr", h, lp["wf_a"]),
                    "bsr,rhd->bshd")
        log_a = -jnp.exp(lp["a_log"])[:, None] * jax.nn.softplus(
            f.astype(jnp.float32) + lp["dt_bias"])
        beta = jax.nn.sigmoid(
            jnp.einsum("bsm,mh->bsh", h, lp["wb"]).astype(jnp.float32))
        g = project(lp, "wg_b", jnp.einsum("bsm,mr->bsr", h, lp["wg_a"]),
                    "bsr,rhd->bshd")
        gate = jax.nn.sigmoid(g.astype(jnp.float32)).astype(g.dtype)
    return q, k, (v, log_a, beta), gate


def delta_mix(cfg: LlamaConfig, lp, q, k, v, history):
    """The convolution and what follows it, on ``delta_proj``'s q, k, v
    [B,S,H,D]: each channel's causal convolution of ``delta_conv`` taps
    (y_t = sum_i w_i x_{t-taps+1+i}, no bias), SiLU, then q and k
    normed to length 1 a head and q scaled by D ** -0.5. ``history``
    [B, taps-1, ``delta_row``]: the rows (q|k|v as projected) of the
    tokens just before these, zeros before a prompt's first. Returns
    (q, k, v) for the delta rule and the rows [B, taps-1+S, delta_row]
    of history and tokens together, of which a cache keeps the last
    ``taps - 1`` real ones."""
    B, S, H, D = q.shape
    with jax.named_scope("kda.conv"):
        tokens = jnp.concatenate(
            [x.reshape(B, S, H * D) for x in (q, k, v)], axis=-1)
        y, rows = causal_taps(lp["conv_w"], tokens, history)
        y = jax.nn.silu(y).reshape(B, S, 3, H, D)
        q, k, v = y[:, :, 0], y[:, :, 1], y[:, :, 2]

        def unit(x):
            return x * jax.lax.rsqrt(
                jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

        q, k = unit(q) * D ** -0.5, unit(k)
    return (q.astype(rows.dtype), k.astype(rows.dtype),
            v.astype(rows.dtype), rows)


def _rope_head(cfg: LlamaConfig, x, positions):
    """Rotary on the first ``qk_rope_head_dim`` of an indexer's head
    [B,S,H,D], the rest left as it is."""
    rot = cfg.qk_rope_head_dim
    return jnp.concatenate(
        [rope(x[..., :rot], positions, cfg.rope_theta,
              cfg.index_rope_interleave), x[..., rot:]], axis=-1)


def index_proj(cfg: LlamaConfig, lp, h, c_q, positions):
    """An indexing layer's indexer, from the layer's normed input ``h``
    [B,S,M] and its normed q bottleneck ``c_q``: the queries
    [B,S,``index_n_heads``,``index_head_dim``] and the token's one key
    [B,S,``index_head_dim``] (a LayerNorm with bias on it), both rotated
    in their first ``qk_rope_head_dim`` and in the model's dtype, and the
    heads' weights [B,S,``index_n_heads``] float32, scaled by
    ``index_n_heads ** -0.5 * index_head_dim ** -0.5``. The key is what
    the "index" pool keeps; a token's score of a cached one is ``sum_j
    w_j relu(q_j . k)`` (ops/sparse_attention.py)."""
    with jax.named_scope("index.proj"):
        q = project(lp, "wi_q", c_q, "bsr,rhd->bshd")
        k = jnp.einsum("bsm,md->bsd", h, lp["wi_k"])
        kf = k.astype(jnp.float32)
        kf = kf - kf.mean(axis=-1, keepdims=True)
        kf = kf * jax.lax.rsqrt(
            jnp.mean(kf * kf, axis=-1, keepdims=True) + cfg.rms_eps)
        k = (kf * lp["i_k_norm"] + lp["i_k_bias"]).astype(k.dtype)
        w = jnp.einsum("bsm,mh->bsh", h, lp["wi_w"]).astype(jnp.float32) * (
            cfg.index_n_heads ** -0.5 * cfg.index_head_dim ** -0.5)
        return (_rope_head(cfg, q, positions),
                _rope_head(cfg, k[..., None, :], positions)[..., 0, :], w)


def latent_kv(cfg: LlamaConfig, lp, row):
    """k [B,S,H,dh] and v [B,S,H,``v_head_dim``] rebuilt from the latent
    rows [B,S,``latent_row``]: what a prefill attends with. A head's k
    is its own up-projection of the latent beside the shared rotary
    key."""
    rank, rot = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    with jax.named_scope("mla.kv"):
        c = row[..., :rank]
        k_nope = project(lp, "wk_b", c, "bsr,rhd->bshd")
        v = project(lp, "wv_b", c, "bsr,rhd->bshd")
        k_rope = jnp.broadcast_to(
            row[..., None, rank:rank + rot],
            k_nope.shape[:-1] + (rot,))
        return jnp.concatenate([k_nope, k_rope], axis=-1), v


def latent_absorb_q(cfg: LlamaConfig, lp, q):
    """Queries [B,S,H,dh] against latent rows instead of rebuilt keys:
    W_UK goes into the query, ``q_nope_h wk_b_h^T`` [kv_lora_rank], so
    that ``q . row`` is ``q_nope . k_nope + q_rope . k_rope`` without a
    k_nope ever made. Returns [B,S,H,``latent_row``], laid as a row."""
    nope = cfg.qk_nope_head_dim
    with jax.named_scope("mla.absorb"):
        q_lat = project(lp, "wk_b", q[..., :nope], "bshd,rhd->bshr")
        return _latent_row(cfg, q_lat, q[..., nope:])


def latent_absorb_out(cfg: LlamaConfig, lp, attn):
    """W_UV behind the attention: probabilities times latent rows
    [B,S,H,kv_lora_rank] to a head's [B,S,H,``v_head_dim``]."""
    with jax.named_scope("mla.absorb"):
        return project(lp, "wv_b", attn, "bshr,rhd->bshd")


EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def split_expert_stack(layers):
    """(what a layer scan slices, what it must read in place) of one
    run's stacked layers: the scanned layers carry their ``index`` in
    the stack, and the expert weights of layers that have experts stay
    whole beside the scan, read at that index (``ffn``'s
    ``expert_stack``; why: parallel/moe.py); None for dense layers.
    ``paged_decode`` reads the KV pool at ``LayerRun.kv_offset`` plus
    the same index."""
    experts = EXPERT_WEIGHTS if "router" in layers else ()
    scanned = {k: v for k, v in layers.items() if k not in experts}
    scanned["index"] = jnp.arange(layers["attn_norm"].shape[0])
    return scanned, ({k: layers[k] for k in experts} or None)


def swiglu(h, w_gate, w_up, w_down, *, mesh=None):
    """The SiLU-gated MLP of h [B,S,M]: a dense layer's, a shared
    expert's. Where the mesh's ``tp`` ring runs (``ring_size``) h comes
    and the result leaves with its rows over ``tp``: the gather rides
    beside ``w_up|w_gate``, the reduction beside ``w_down``."""
    ring = ring_size(mesh, h.shape[1]) > 1
    if ring:
        up, gate = _ring_saved(gather_matmul(mesh, h, (w_up, w_gate)))
    else:
        up = jnp.einsum("bsm,mf->bsf", h, w_up)
        gate = jnp.einsum("bsm,mf->bsf", h, w_gate)
    # Named for the selective "mlp" remat policy: saving these two
    # outputs (the widest matmuls — ~45% of a layer's forward FLOPs)
    # removes their backward recompute at a fraction of checkpoint_dots'
    # footprint (which also saves attention/down/norm outputs).
    up = checkpoint_name(up, "mlp_up")
    gate = checkpoint_name(gate, "mlp_gate")
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(up.dtype) * up
    h = with_logical_constraint(h, ("batch", "seq", "mlp"), mesh=mesh)
    if ring:
        return _ring_saved(matmul_scatter(mesh, h, w_down))
    return jnp.einsum("bsf,fm->bsm", h, w_down)


_EXPERT_ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def route_tokens(cfg: LlamaConfig, lp, h, *, mesh=None, token_mask=None,
                 expert_stack=None):
    """The expert layer's route (parallel/moe.dispatch) of the normed
    rows ``h`` [B,S,M] that the model's router reads: ``ffn`` makes it
    from its own input, ``block`` from the attention's for a model of
    ``router_input="attention"``."""
    stacked = expert_stack is not None
    return dispatch(
        h, lp["router"], k=cfg.top_k, token_mask=token_mask,
        layer=lp["index"] if stacked else None,
        stack_layers=expert_stack["w_up"].shape[0] if stacked else None,
        mesh=mesh, held=cfg.experts_held,
        score=cfg.router_score, select_bias=lp.get("expert_bias"),
        renormalize=cfg.route_norm, scale=cfg.route_scale)


def _residual(cfg: LlamaConfig, out):
    """What a sub-block adds to the residual stream: its output, times
    the model's ``residual_scale`` where it has one."""
    if cfg.residual_scale == 1.0:
        return out
    return out * jnp.asarray(cfg.residual_scale, out.dtype)


def head_input(cfg: LlamaConfig, h):
    """The normed hidden state as the head multiplies it: divided by the
    model's ``logit_divisor`` where it has one."""
    if cfg.logit_divisor == 1.0:
        return h
    return h * jnp.asarray(1.0 / cfg.logit_divisor, h.dtype)


def ffn(cfg: LlamaConfig, lp, x, *, mesh=None, token_mask=None,
        expert_stack=None, routed=None):
    """The block's second half: MLP norm, then the
    SiLU-gated MLP or, for a layer with a ``router``, the mixture of
    experts (and the shared expert beside it), normed again where the
    model has post-norms, added to x [B,S,M].
    Returns (x, load-balancing loss, tokens assigned to each expert [E]
    or None for a dense layer; of a model that holds a share of the
    experts, ``cfg.experts_held``, the held ones' and behind them the
    assignments that went elsewhere: ``moe_ffn``). ``token_mask`` [B,S]
    keeps rows (inactive
    decode slots, bucket padding) away from every expert. The experts'
    weights are ``lp``'s own, or with ``expert_stack`` (the second half
    of ``split_expert_stack``) the whole run's, read at ``lp["index"]``.
    ``routed``: the route, where ``block`` made it ahead of the attention
    (``route_tokens``); None: the router reads this half's normed input."""
    h = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
    if "router" in lp:
        if ring_size(mesh, x.shape[1]) > 1:
            # The experts' dispatch takes whole rows: gathered by the
            # partitioner here, and ``x + out`` below keeps x's layout.
            h = with_logical_constraint(h, ("batch", "seq", "embed"),
                                        mesh=mesh)
        w = lp if expert_stack is None else expert_stack
        if routed is None:
            routed = route_tokens(cfg, lp, h, mesh=mesh,
                                  token_mask=token_mask,
                                  expert_stack=expert_stack)
        out, aux, expert_tokens = moe_ffn(
            h, lp["router"], w["w_up"], w["w_down"], k=cfg.top_k,
            w_gate=w["w_gate"], activation=_EXPERT_ACTS[cfg.expert_act],
            layer=None if expert_stack is None else lp["index"],
            routed=routed,
        )
        if "ws_up" in lp:
            with jax.named_scope("moe.shared"):
                out = out + swiglu(h, lp["ws_gate"], lp["ws_up"],
                                   lp["ws_down"], mesh=mesh)
    else:
        out = swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"], mesh=mesh)
        aux, expert_tokens = jnp.zeros((), dtype=jnp.float32), None
    if cfg.post_norms:
        out = rms_norm(out, lp["post_mlp_norm"], cfg.rms_eps)
    return x + _residual(cfg, out), aux, expert_tokens


def block(cfg: LlamaConfig, lp, x, positions, attend, *, mesh=None,
          token_mask=None, expert_stack=None, kind: str = "full"):
    """One transformer block over x [B,S,M]: the only place where
    projections, rotary, attention, ``wo`` and the FFN are put in order.
    Training, prefill and decode differ in what they pass:

    ``positions`` [S] or [B,S], each row's place in its sequence (``rope``).
    ``attend(q, k, v)`` owns the attention and whatever state it keeps,
    and returns (output [B,S,H,Dh], that state): training attends
    causally and keeps nothing, a prefill does the same and keeps k and
    v, a decode step writes them into the KV pool it carries and attends
    over the pool. The block hands the state on unread, so another
    attention (a window, a latent cache) is another ``attend``.
    ``mesh``, ``token_mask``, ``expert_stack``: ``ffn``'s. ``kind``,
    the layer's attention kind, is the caller's ``attend`` to honour;
    here it decides whether q and k are rotated, and for "latent" what
    the projections are: ``attend`` is then given the rotated queries,
    the tokens' latent rows in k's place and no v (``latent_proj``), and
    returns [B,S,H,``v_head_dim``]; whether it rebuilds k and v
    (``latent_kv``) or absorbs the up-projections (``latent_absorb_q``,
    ``latent_absorb_out``) is its own to choose. A latent layer that
    attends over a selection is "latent_index", whose ``attend`` is given
    the indexer's (queries, key, weights) in v's place (``index_proj``)
    and makes the selection, or "latent_shared", whose ``attend`` is
    given no v and brings the selection it was handed: the block passes
    nothing from layer to layer but the residual, the caller's layer loop
    carries the selection in ``attend``'s state. For "state" ``attend``
    is given ``(v, log gates)`` in v's place (``qkv_proj``). For "delta"
    ``attend`` is given q and k as projected and ``(v, log decays, write
    strength)`` in v's place (``delta_proj``), owns the convolution and
    its history with the rest of its state (``delta_mix``), and returns
    [B,S,``delta_heads``,``delta_head_dim``], which is normed a head and
    gated here. For "linear" ``attend`` is given rotated q, k and v of
    ``linear_heads`` heads each and the layer's decays are its own to
    read (``lp["log_decay"]``); its output is normed a head and gated
    here. For "conv" there is no attention: ``attend`` is given z = B *
    X [B,S,M] in q's place and nothing else (``conv_proj``), owns the
    taps and their history (``conv_mix``) and returns y [B,S,M], which
    is gated by C and goes through ``w_out`` here. For "blocks"
    ``attend`` is given what a "full" layer's is,
    unrotated where the model rotates its other layers alone, and owns
    the selection and the page means it is made from.

    A model whose router reads the attention's input
    (``router_input="attention"``) is routed here, first: the route, the
    sort and the grouped matmul's walk are then in the program ahead of
    the attention and depend on nothing it produces.

    Returns (x, attend's state, load-balancing loss, tokens assigned to
    each expert or None)."""
    routed = None
    if cfg.router_input == "attention" and "router" in lp:
        # ``attn_norm`` of x a second time in the text (``qkv_proj``
        # norms it too): one operation once compiled.
        routed = route_tokens(
            cfg, lp, rms_norm(x, lp["attn_norm"], cfg.rms_eps), mesh=mesh,
            token_mask=token_mask, expert_stack=expert_stack)
    if kind == "latent":
        (q, k), v, gate = latent_proj(cfg, lp, x, positions), None, None
    elif kind in ("latent_index", "latent_shared"):
        q, k, h, c_q = _latent_parts(cfg, lp, x, positions)
        v = gate = None
        if kind == "latent_index":
            v = index_proj(cfg, lp, h, c_q, positions)
    elif kind == "delta":
        q, k, v, gate = delta_proj(cfg, lp, x)
    elif kind == "conv":
        (q, gate), k, v = conv_proj(cfg, lp, x), None, None
    else:
        q, k, v, gate = qkv_proj(cfg, lp, x, mesh=mesh)
        if cfg.rope_full_layers or kind not in ("full", "blocks"):
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
    if kind == "conv":
        # z in q's place; what comes back is gated and goes through
        # ``w_out``, which stands where another layer's ``wo`` does.
        y, state = attend(q, k, v)
        with jax.named_scope("conv.out"):
            attn = jnp.einsum("bsm,mn->bsn", gate * y, lp["w_out"])
    else:
        q = with_logical_constraint(
            q, ("batch", "seq", "heads", "head_dim"), mesh=mesh)
        if kind == "state":
            attn, state = attend(q, k, (v, gate))
        else:
            attn, state = attend(q, k, v)
            if kind in ("delta", "linear"):
                attn = rms_norm(attn, lp["o_norm"], cfg.rms_eps)
            if gate is not None:
                attn = attn * gate
        if ring_size(mesh, x.shape[1]) > 1:
            attn = _ring_saved(matmul_scatter(mesh, attn, lp["wo"]))
        else:
            attn = jnp.einsum("bshd,hdm->bsm", attn, lp["wo"])
    if cfg.post_norms:
        attn = rms_norm(attn, lp["post_attn_norm"], cfg.rms_eps)
    x = x + _residual(cfg, attn)
    x, aux, expert_tokens = ffn(cfg, lp, x, mesh=mesh, token_mask=token_mask,
                                expert_stack=expert_stack, routed=routed)
    return x, state, aux, expert_tokens


def exit_gate_logit(params, h: jax.Array) -> jax.Array:
    """The exit gate of a pass, from the pass's normed output ``h``
    [..., M]: ``h . exit_w + exit_b`` [...] float32."""
    with jax.named_scope("exit.gate"):
        g = jnp.einsum("...m,mo->...o", h, params["exit_w"],
                       preferred_element_type=jnp.float32)
        return (g + params["exit_b"])[..., 0]


def exit_distribution(gates: jax.Array) -> jax.Array:
    """From the passes' gate logits [..., T] the probability of leaving
    at each pass, [..., T] float32: with ``lambda_t = sigmoid(g_t)``,
    ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` for t < T - 1 and what
    is left for the last pass, ``p_{T-1} = prod_{j<T-1} (1 - lambda_j)``
    (the last pass's own gate decides nothing)."""
    lam = jax.nn.sigmoid(gates.astype(jnp.float32))[..., :-1]
    stay = jnp.cumprod(1.0 - lam, axis=-1)        # prod_{j<=t}, t < T - 1
    before = jnp.concatenate(
        [jnp.ones_like(stay[..., :1]), stay[..., :-1]], axis=-1)
    return jnp.concatenate([lam * before, stay[..., -1:]], axis=-1)


def embed_tokens(params, tokens: jax.Array, cfg: LlamaConfig) -> jax.Array:
    """The residual stream's first value: the tokens' embedding rows in
    the model's dtype, scaled where the model scales them."""
    x = params["embed"][tokens].astype(cfg.dtype)
    if cfg.embed_scale != 1.0:
        x = x * jnp.asarray(cfg.embed_scale, cfg.dtype)
    return x


def head_logits(params, h: jax.Array) -> jax.Array:
    """The logits [B, V] of the normed hidden rows ``h`` [B, M] (behind
    ``head_input``), in h's dtype: ``h . lm_head``, or for a model whose
    head is tied (``tied_head``: the tree has no ``lm_head``) ``h .
    embed^T``, the embedding contracted along its last axis, where it
    lies."""
    if "lm_head" in params:
        return jnp.einsum("bm,mv->bv", h, params["lm_head"])
    return jnp.einsum("bm,vm->bv", h, params["embed"])


def forward(
    params: Dict[str, Any],
    tokens: jax.Array,  # [B, S] int32
    cfg: LlamaConfig,
    mesh=None,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (logits [B, S, V] float32, moe_aux_loss scalar)."""
    x, aux = hidden_forward(params, tokens, cfg, mesh)
    logits = jnp.einsum("bsm,mv->bsv", x, params["lm_head"])
    return logits.astype(jnp.float32), aux


def remat_policy(cfg: LlamaConfig):
    """The jax.checkpoint policy selected by cfg.remat_policy."""
    if cfg.remat_policy == "dots":
        # Save ALL matmul outputs, and what stands for one: a ring
        # matmul's result and the flash forward kernel's ``o`` and
        # ``lse`` — least recompute, largest footprint (PERF.md
        # section 5 has the two train cells' peaks beside the chip's
        # 15.75 GiB).
        from ..ops.flash_attention import FLASH_SAVED

        return jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.checkpoint_dots,
            jax.checkpoint_policies.save_only_these_names(
                _RING_SAVED, FLASH_SAVED))
    if cfg.remat_policy == "mlp":
        # Selective (scaling-playbook style): save only the two
        # widest matmuls' outputs (up/gate, ~45% of forward
        # FLOPs) and recompute the rest — the best
        # recompute-per-byte trade on one chip.
        return jax.checkpoint_policies.save_only_these_names(
            "mlp_up", "mlp_gate"
        )
    if cfg.remat_policy == "save_all":
        return jax.checkpoint_policies.everything_saveable
    return None


def scan_chunks(cfg: LlamaConfig) -> Tuple[int, int]:
    """(layers_per_chunk, num_chunks) for the scan schedule. Validates
    that scan_chunk divides num_layers — a ragged final chunk would need
    its own compiled body, defeating the scan's O(1)-in-depth compile."""
    K = max(1, cfg.scan_chunk or 1)
    if cfg.num_layers % K:
        raise ValueError(
            f"scan_chunk={K} must divide num_layers={cfg.num_layers}"
        )
    return K, cfg.num_layers // K


def hidden_forward(
    params: Dict[str, Any],
    tokens: jax.Array,  # [B, S] int32
    cfg: LlamaConfig,
    mesh=None,
) -> Tuple[jax.Array, jax.Array]:
    """Transformer trunk WITHOUT the lm_head projection: returns
    (hidden [B, S, M] after final_norm, moe_aux_loss scalar); of a
    looped model the last pass's (``cfg.passes``)."""
    require_uniform(cfg, "hidden_forward")
    B, S = tokens.shape
    # Between the layers' matmul pairs the residual's rows lie over ``tp``
    # where its ring runs (parallel/collective_matmul.py), else as ever.
    ring = ring_size(mesh, S) > 1
    residual = ("batch", "seq_tp" if ring else "seq", "embed")
    x = embed_tokens(params, tokens, cfg)
    x = with_logical_constraint(x, residual, mesh=mesh)
    positions = jnp.arange(S)
    policy = remat_policy(cfg)

    def attend(q, k, v):
        return causal_attention(cfg, mesh, q, k, v), None

    def layer(x, lp):
        # A layer's own slice of the experts (expert_stack=None): why,
        # parallel/moe.py.
        x, _, aux, _ = block(cfg, lp, x, positions, attend, mesh=mesh)
        return x, aux

    def body(x, lp):
        if cfg.remat:
            out, aux = jax.checkpoint(layer, policy=policy)(x, lp)
        else:
            out, aux = layer(x, lp)
        out = with_logical_constraint(out, residual, mesh=mesh)
        return out, aux

    def layers(x):
        """One walk of the stack."""
        if cfg.scan_layers:
            K, n_chunks = scan_chunks(cfg)
            if K == 1:
                x, aux = jax.lax.scan(body, x, params["layers"])
            else:
                # Layer-chunked schedule: scan over [L/K, ...] stacks of
                # K-layer chunks. ONE checkpoint per chunk (the policy's
                # save-set covers the whole unrolled chunk body), and the
                # carry re-annotated each step so GSPMD keeps the scan
                # body's layout resident instead of resharding per
                # iteration.
                chunked = jax.tree.map(
                    lambda p: p.reshape((n_chunks, K) + p.shape[1:]),
                    params["layers"],
                )

                def chunk_fn(x_, cp):
                    aux = jnp.zeros((), dtype=jnp.float32)
                    for k in range(K):
                        lp = jax.tree.map(lambda p: p[k], cp)
                        x_, a = layer(x_, lp)
                        aux = aux + a
                    return x_, aux

                if cfg.remat:
                    chunk_fn = jax.checkpoint(chunk_fn, policy=policy)

                def chunk_body(x_, cp):
                    x_ = with_logical_constraint(x_, residual, mesh=mesh)
                    out, aux = chunk_fn(x_, cp)
                    out = with_logical_constraint(out, residual, mesh=mesh)
                    return out, aux

                x, aux = jax.lax.scan(chunk_body, x, chunked)
            return x, aux.sum()
        aux = jnp.zeros((), jnp.float32)
        for i in range(cfg.num_layers):
            lp = jax.tree.map(lambda p: p[i], params["layers"])
            x, a = body(x, lp)
            aux = aux + a
        return x, aux

    # A looped model walks the same weights ``passes`` times, the final
    # norm behind every walk and its output the next walk's input (it
    # has no experts, ``_check_loop``: every walk's ``aux`` is zero).
    for _ in range(cfg.passes):
        x, aux = layers(x)
        x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    if ring:
        # The head reads whole rows: gathered once, not once a loss chunk.
        x = with_logical_constraint(x, ("batch", "seq", "embed"), mesh=mesh)
    return head_input(cfg, x), aux


def _chunked_nll_sum(x: jax.Array, lm_head: jax.Array,
                     targets: jax.Array, chunk: int) -> jax.Array:
    """Total next-token NLL over [B, S] positions, streaming the lm_head
    projection + log-sum-exp over S/chunk slices so no [B, S, V] tensor
    ever materializes (the memory cliff behind the batch-16 collapse:
    the monolithic loss kept logits + log-softmax residuals, ~8.6 GB at
    B=16). Each chunk is rematerialized in the backward.

    ``lm_head`` is the same array in every iteration of both scans (this
    one, and the backward one that re-runs each chunk), so it comes here
    as every iteration needs it: whole along ``embed``
    (causal_lm_loss gathers it over ``fsdp`` once, outside). A head that
    came in sharded over ``fsdp`` would be carried into both ``while``
    bodies as it lies, gathered there once a chunk, and its gradient
    reduce-scattered once a chunk into an accumulator of the shard's
    shape. Whole, the gradient's accumulator has the gathered layout
    too: each chip adds its own batch rows' dW through the backward
    scan, and the sum over ``fsdp`` is taken once, after it."""
    B, S, M = x.shape
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
    nchunks = (S + pad) // chunk
    # [n, B, C, M] / [n, B, C] views for the scan.
    xs = x.reshape(B, nchunks, chunk, M).transpose(1, 0, 2, 3)
    ts = targets.reshape(B, nchunks, chunk).transpose(1, 0, 2)
    valid = jnp.arange(nchunks * chunk).reshape(nchunks, chunk) < S

    def body(total, inp):
        xc, tc, mask = inp
        logits = jnp.einsum(
            "bcm,mv->bcv", xc, lm_head
        ).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(
            logits, tc[..., None], axis=-1
        )[..., 0]
        nll = (lse - tgt) * mask[None, :]
        return total + nll.sum(), None

    total, _ = jax.lax.scan(
        jax.checkpoint(body), jnp.zeros((), jnp.float32),
        (xs, ts, valid),
    )
    return total


def causal_lm_loss(
    params: Dict[str, Any],
    tokens: jax.Array,       # [B, S]
    cfg: LlamaConfig,
    mesh=None,
    *,
    aux_weight: float = 0.01,
) -> jax.Array:
    """Next-token cross entropy (tokens shifted internally). With
    cfg.loss_chunk > 0 the head projection + softmax stream over
    sequence chunks (identical math, a fraction of the peak memory)."""
    if cfg.passes > 1:
        raise NotImplementedError(
            f"causal_lm_loss: training a looped model (passes="
            f"{cfg.passes}) is not implemented: its objective weights "
            f"EVERY pass's next-token loss by the exit distribution "
            f"(llama.exit_distribution) less an entropy term, and needs "
            f"the head behind every pass; the last pass's loss alone "
            f"would train another model. It is served only "
            f"(models/generation.py)")
    targets = tokens[:, 1:]
    chunk = cfg.loss_chunk
    if chunk and chunk > 0 and targets.shape[1] > chunk:
        x, aux = hidden_forward(params, tokens[:, :-1], cfg, mesh)
        # Once a step, not once a loss chunk (_chunked_nll_sum): where
        # the rules shard `embed` (fsdp > 1) this is the head's one
        # all-gather and its gradient's one reduction; where they do not
        # (no mesh, fsdp = 1) the head lies so already.
        head = with_logical_constraint(
            params["lm_head"], (None, "vocab"), mesh=mesh)
        total = _chunked_nll_sum(x, head, targets, chunk)
        return total / targets.size + aux_weight * aux
    logits, aux = forward(params, tokens[:, :-1], cfg, mesh)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean() + aux_weight * aux


def num_params(params) -> int:
    return sum(int(p.size) for p in jax.tree.leaves(params))
