"""``program_config`` of the JoyAI-LLM-Flash configurations: the
program's ``LlamaConfig`` from the configuration file's keys, each by
its name. Nothing of the file reaches the program but what is spelled
out here; what the released implementation does without a key in
``config.json`` (the file's ``assumed``: the two latent norms, the
selection bias) comes with the program's latent attention and its
``router_bias``."""

from __future__ import annotations

from typing import Mapping

from . import joyai_counts


def joyai_config(config: Mapping):
    import jax.numpy as jnp

    from ray_tpu.models import LlamaConfig

    if (config["n_group"], config["topk_group"]) != (1, 1):
        raise NotImplementedError(
            "grouped top-k (n_group > 1): the router has no group step")
    if config["rope_scaling"] is not None or config["moe_layer_freq"] != 1:
        raise NotImplementedError("rope_scaling, moe_layer_freq != 1")
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],  # the dense layer's
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],     # unread: latent
        head_dim=joyai_counts.head_dim(config),         # q.k: 128 + 64
        rope_theta=float(config["rope_theta"]),
        rms_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["dtype"]),
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        rope_interleave=config["rope_interleave"],
        n_experts=config["n_routed_experts"],
        top_k=config["num_experts_per_tok"],
        num_dense_layers=config["first_k_dense_replace"],
        moe_intermediate_size=config["moe_intermediate_size"],
        n_shared_experts=config["n_shared_experts"],
        router_score=config["scoring_func"],
        router_bias=config["topk_method"] == "noaux_tc",
        route_norm=config["norm_topk_prob"],
        route_scale=config["routed_scaling_factor"],
    )
