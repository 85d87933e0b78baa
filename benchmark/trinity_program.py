"""``program_config`` of the Trinity (AFMoE) configurations: the
program's ``LlamaConfig`` from the configuration file's keys, each by
its name. Nothing of the file reaches the program but what is spelled
out here; what the released implementation does without a key in
``config.json`` (the file's ``assumed.block``) is switched on by name."""

from __future__ import annotations

from typing import Mapping

from . import trinity_counts

_KINDS = {"sliding_attention": "window", "full_attention": "full"}


def trinity_config(config: Mapping):
    import jax.numpy as jnp

    from ray_tpu.models import LlamaConfig

    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],  # the dense layers'
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=trinity_counts.head_dim(config),
        rope_theta=float(config["rope_theta"]),
        rms_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["dtype"]),
        n_experts=config["num_experts"],
        top_k=config["num_experts_per_tok"],
        num_dense_layers=config["num_dense_layers"],
        moe_intermediate_size=config["moe_intermediate_size"],
        n_shared_experts=config["num_shared_experts"],
        router_score=config["score_func"],
        router_bias=True,
        route_norm=config["route_norm"],
        route_scale=config["route_scale"],
        layer_types=tuple(_KINDS[t]
                          for t in trinity_counts.layer_types(config)),
        sliding_window=config["sliding_window"],
        rope_full_layers=False,
        qk_norm=True,
        qk_norm_per_head=True,
        attn_gate=True,
        post_norms=True,
        embed_scale=(config["hidden_size"] ** 0.5
                     if config["mup_enabled"] else 1.0),
    )
