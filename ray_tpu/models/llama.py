"""Llama-family transformer, TPU-first.

The flagship model (BASELINE.json configs: Llama-3 8B/70B; a mixture of
experts via ``n_experts``, routed as OLMoE routes: parallel/moe.py).
Design choices for TPU/XLA:

- Pure-functional: params are a pytree of arrays; sharding is declared as a
  matching pytree of logical axes (parallel/sharding.py rules) — pjit/GSPMD
  inserts the collectives for dp/fsdp/tp; ring attention (sp) is an explicit
  shard_map island inside the jitted program.
- Layers are *stacked* ([L, ...] leaves) and applied with lax.scan: one
  layer gets compiled once regardless of depth (compile-time O(1) in L),
  and the "layers" leading axis is what pipeline parallelism shards.
- bfloat16 activations/weights with float32 RMSNorm/softmax/rope, the
  standard TPU mixed-precision recipe (MXU eats bf16; norms need f32).
- jax.checkpoint around each layer body for rematerialization.

The reference has no model zoo — it orchestrates user models; this
framework owns its compute path (SURVEY.md §7 phase 7).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..parallel.sharding import prune_spec, with_logical_constraint
from ..parallel.mesh import mesh_axis_size
from ..parallel.ring_attention import ring_attention
from ..parallel.moe import moe_ffn
from ..ops.attention import mha_attention


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
    # Read by nothing since the dropless dispatch (PR 28). It stays only
    # because tests/bench_harness/moe_tiny pins it and a model_config PR
    # may not edit the benchmark's files; ROADMAP D5 removes both.
    capacity_factor: float = 1.25
    # RMSNorm over the whole q and the whole k projection, before the
    # split into heads and before rotary (OLMoE's q_norm / k_norm).
    qk_norm: bool = False
    remat: bool = True
    # "full" (save only layer inputs), "dots" (save matmul outputs,
    # recompute elementwise), or "save_all" (save every intermediate —
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

    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        return LlamaConfig()

    @staticmethod
    def llama3_70b() -> "LlamaConfig":
        return LlamaConfig(
            hidden_size=8192, intermediate_size=28_672, num_layers=80,
            num_heads=64, num_kv_heads=8,
        )

    @staticmethod
    def tiny(vocab: int = 256, moe: bool = False) -> "LlamaConfig":
        return LlamaConfig(
            vocab_size=vocab, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, num_kv_heads=2, rope_theta=10_000.0,
            dtype=jnp.float32, n_experts=4 if moe else 0, top_k=2,
        )


# Logical axes for each parameter leaf (maps through DEFAULT_RULES:
# embed→fsdp, heads/mlp/vocab→tp, expert→ep, layers→pp-or-scan).
def param_logical_axes(cfg: LlamaConfig) -> Dict[str, Any]:
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
    if cfg.n_experts > 0:
        layer.update(
            router=("layers", "embed", None),
            w_gate=("layers", "expert", "embed", "mlp"),
            w_up=("layers", "expert", "embed", "mlp"),
            w_down=("layers", "expert", "mlp", "embed"),
        )
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
    }


def init_params(cfg: LlamaConfig, key: jax.Array) -> Dict[str, Any]:
    k = iter(jax.random.split(key, 16))
    M, F, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    H, Hkv, Dh, V = cfg.num_heads, cfg.num_kv_heads, cfg.dh, cfg.vocab_size
    dt = cfg.dtype

    def norm_init(shape):
        return jnp.ones(shape, dtype=jnp.float32)

    def winit(key, shape, fan_in):
        return (jax.random.normal(key, shape, dtype=jnp.float32)
                * (fan_in ** -0.5)).astype(dt)

    layers: Dict[str, Any] = {
        "attn_norm": norm_init((L, M)),
        "wq": winit(next(k), (L, M, H, Dh), M),
        "wk": winit(next(k), (L, M, Hkv, Dh), M),
        "wv": winit(next(k), (L, M, Hkv, Dh), M),
        "wo": winit(next(k), (L, H, Dh, M), H * Dh),
        "mlp_norm": norm_init((L, M)),
    }
    if cfg.qk_norm:
        layers.update(q_norm=norm_init((L, H * Dh)),
                      k_norm=norm_init((L, Hkv * Dh)))
    if cfg.n_experts > 0:
        E = cfg.n_experts
        layers.update(
            router=winit(next(k), (L, M, E), M).astype(jnp.float32),
            w_gate=winit(next(k), (L, E, M, F), M),
            w_up=winit(next(k), (L, E, M, F), M),
            w_down=winit(next(k), (L, E, F, M), F),
        )
    else:
        layers.update(
            w_gate=winit(next(k), (L, M, F), M),
            w_up=winit(next(k), (L, M, F), M),
            w_down=winit(next(k), (L, F, M), F),
        )
    return {
        "embed": winit(next(k), (V, M), M),
        "layers": layers,
        "final_norm": norm_init((M,)),
        "lm_head": winit(next(k), (M, V), M),
    }


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale * w).astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding; x [B, S, H, D]; positions [S], the same for every
    row (global indices, so sequence-sharded blocks stay correct), or
    [B, S], each row's own (serving slots at different lengths)."""
    D = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, D // 2, dtype=jnp.float32) / (D // 2))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [(B,) S, D/2]
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def causal_attention(cfg: LlamaConfig, mesh, q, k, v):
    """Causal self-attention of q [B,S,H,Dh] over k, v [B,S,Hkv,Dh] of
    the same S tokens: training's, and a prefill's. The one place that
    chooses among ring attention (an ``sp`` axis), the flash kernel and
    the XLA einsum; for which platform and shapes the kernel itself runs
    is ops/flash_attention.py's to say."""
    if mesh is not None and mesh_axis_size(mesh, "sp") > 1:
        return ring_attention(q, k, v, mesh, causal=True)
    if cfg.use_flash:
        from ..ops.flash_attention import flash_attention

        attend = functools.partial(flash_attention, causal=True)
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
    return mha_attention(q, k, v, causal=True)


def qkv_proj(cfg: LlamaConfig, lp, x):
    """The block's first half up to rotary: attention norm, then q
    [B,S,H,Dh] and k, v [B,S,Hkv,Dh], q and k normed over their whole
    projection where the model has a QK-norm."""
    B, S, _ = x.shape
    h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
    q = jnp.einsum("bsm,mhd->bshd", h, lp["wq"])
    k = jnp.einsum("bsm,mhd->bshd", h, lp["wk"])
    v = jnp.einsum("bsm,mhd->bshd", h, lp["wv"])
    if cfg.qk_norm:
        q = rms_norm(q.reshape(B, S, -1), lp["q_norm"],
                     cfg.rms_eps).reshape(q.shape)
        k = rms_norm(k.reshape(B, S, -1), lp["k_norm"],
                     cfg.rms_eps).reshape(k.shape)
    return q, k, v


EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def split_expert_stack(cfg: LlamaConfig, layers):
    """(what a layer scan slices, what it must read in place): the
    scanned layers carry their ``index``, and a MoE model's expert
    weights stay whole beside the scan, read at that index (``ffn``'s
    ``expert_stack``; why: parallel/moe.py); None for a dense model.
    ``paged_decode`` reads the KV pool at the same index."""
    experts = EXPERT_WEIGHTS if cfg.n_experts > 0 else ()
    scanned = {k: v for k, v in layers.items() if k not in experts}
    scanned["index"] = jnp.arange(cfg.num_layers)
    return scanned, ({k: layers[k] for k in experts} or None)


def ffn(cfg: LlamaConfig, lp, x, *, mesh=None, token_mask=None,
        expert_stack=None):
    """The block's second half: MLP norm, then the
    SiLU-gated MLP or the mixture of experts, added to x [B,S,M].
    Returns (x, load-balancing loss, tokens assigned to each expert [E]
    or None for a dense model). ``token_mask`` [B,S] keeps rows (inactive
    decode slots, bucket padding) away from every expert. The experts'
    weights are ``lp``'s own, or with ``expert_stack`` (the second half
    of ``split_expert_stack``) all layers', read at ``lp["index"]``."""
    h = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
    if cfg.n_experts > 0:
        w = lp if expert_stack is None else expert_stack
        out, aux, expert_tokens = moe_ffn(
            h, lp["router"], w["w_up"], w["w_down"], k=cfg.top_k,
            w_gate=w["w_gate"], token_mask=token_mask,
            layer=None if expert_stack is None else lp["index"],
        )
        return x + out, aux, expert_tokens
    up = jnp.einsum("bsm,mf->bsf", h, lp["w_up"])
    gate = jnp.einsum("bsm,mf->bsf", h, lp["w_gate"])
    # Named for the selective "mlp" remat policy: saving these two
    # outputs (the widest matmuls — ~45% of a layer's forward FLOPs)
    # removes their backward recompute at a fraction of checkpoint_dots'
    # footprint (which also saves attention/down/norm outputs).
    up = checkpoint_name(up, "mlp_up")
    gate = checkpoint_name(gate, "mlp_gate")
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(up.dtype) * up
    h = with_logical_constraint(h, ("batch", "seq", "mlp"), mesh=mesh)
    x = x + jnp.einsum("bsf,fm->bsm", h, lp["w_down"])
    return x, jnp.zeros((), dtype=jnp.float32), None


def block(cfg: LlamaConfig, lp, x, positions, attend, *, mesh=None,
          token_mask=None, expert_stack=None):
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
    ``mesh``, ``token_mask``, ``expert_stack``: ``ffn``'s.

    Returns (x, attend's state, load-balancing loss, tokens assigned to
    each expert or None)."""
    q, k, v = qkv_proj(cfg, lp, x)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    q = with_logical_constraint(q, ("batch", "seq", "heads", "head_dim"),
                                mesh=mesh)
    attn, state = attend(q, k, v)
    x = x + jnp.einsum("bshd,hdm->bsm", attn, lp["wo"])
    x, aux, expert_tokens = ffn(cfg, lp, x, mesh=mesh, token_mask=token_mask,
                                expert_stack=expert_stack)
    return x, state, aux, expert_tokens


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
        # Save ALL matmul outputs — least recompute, largest
        # footprint (OOMs the 8B-shaped bench: ~10 G HLO temp).
        return jax.checkpoint_policies.checkpoint_dots
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
    (hidden [B, S, M] after final_norm, moe_aux_loss scalar)."""
    B, S = tokens.shape
    x = params["embed"][tokens].astype(cfg.dtype)
    x = with_logical_constraint(x, ("batch", "seq", "embed"), mesh=mesh)
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
        out = with_logical_constraint(out, ("batch", "seq", "embed"), mesh=mesh)
        return out, aux

    if cfg.scan_layers:
        K, n_chunks = scan_chunks(cfg)
        if K == 1:
            x, aux = jax.lax.scan(body, x, params["layers"])
        else:
            # Layer-chunked schedule: scan over [L/K, ...] stacks of
            # K-layer chunks. ONE checkpoint per chunk (the policy's
            # save-set covers the whole unrolled chunk body), and the
            # carry re-annotated each step so GSPMD keeps the scan body's
            # layout resident instead of resharding per iteration.
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
                x_ = with_logical_constraint(
                    x_, ("batch", "seq", "embed"), mesh=mesh
                )
                out, aux = chunk_fn(x_, cp)
                out = with_logical_constraint(
                    out, ("batch", "seq", "embed"), mesh=mesh
                )
                return out, aux

            x, aux = jax.lax.scan(chunk_body, x, chunked)
        aux = aux.sum()
    else:
        aux = jnp.zeros((), jnp.float32)
        for i in range(cfg.num_layers):
            lp = jax.tree.map(lambda p: p[i], params["layers"])
            x, a = body(x, lp)
            aux = aux + a
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    return x, aux


def _chunked_nll_sum(x: jax.Array, lm_head: jax.Array,
                     targets: jax.Array, chunk: int) -> jax.Array:
    """Total next-token NLL over [B, S] positions, streaming the lm_head
    projection + log-sum-exp over S/chunk slices so no [B, S, V] tensor
    ever materializes (the memory cliff behind the batch-16 collapse:
    the monolithic loss kept logits + log-softmax residuals, ~8.6 GB at
    B=16). Each chunk is rematerialized in the backward."""
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
    targets = tokens[:, 1:]
    chunk = cfg.loss_chunk
    if chunk and chunk > 0 and targets.shape[1] > chunk:
        x, aux = hidden_forward(params, tokens[:, :-1], cfg, mesh)
        total = _chunked_nll_sum(x, params["lm_head"], targets, chunk)
        return total / targets.size + aux_weight * aux
    logits, aux = forward(params, tokens[:, :-1], cfg, mesh)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean() + aux_weight * aux


def num_params(params) -> int:
    return sum(int(p.size) for p in jax.tree.leaves(params))
