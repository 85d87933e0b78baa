"""``program_config`` of the Kimi-Linear configurations: the program's
``LlamaConfig`` from the configuration file's keys, each by its name.
Nothing of the file reaches the program but what is spelled out here;
what the released implementation does without a key in ``config.json``
(the file's ``assumed``: the KDA layer's gate, convolution and norms,
the latent norm, the selection bias) comes with the program's delta
layers, its latent attention and its ``router_bias``.

The file is one chip's share of a deployment (its ``deployment`` and
``reduced`` say of what): every layer, the router at
``reduced.num_experts.published`` columns, the file's ``num_experts``
the experts held here, the first of them, and the file's ``vocab_size``
rows of the vocabulary. ``linear_attn_config`` counts layers from 1."""

from __future__ import annotations

from typing import Mapping

from . import kimi_counts


def kimi_config(config: Mapping):
    import jax.numpy as jnp

    from ray_tpu.models import LlamaConfig

    if (config["num_expert_group"], config["topk_group"]) != (1, 1):
        raise NotImplementedError(
            "grouped top-k (num_expert_group > 1): the router has no "
            "group step")
    if config["rope_scaling"] is not None or config["moe_layer_freq"] != 1:
        raise NotImplementedError("rope_scaling, moe_layer_freq != 1")
    if config["q_lora_rank"] is not None or not config["mla_use_nope"]:
        raise NotImplementedError(
            "a q bottleneck or rotated latent layers: this family has "
            "neither (joyai_program builds a model that has both)")
    if (config["tie_word_embeddings"] or config["hidden_act"] != "silu"
            or config["num_nextn_predict_layers"]):
        raise NotImplementedError(
            "tie_word_embeddings, hidden_act other than silu, a "
            "multi-token-prediction module")
    linear = config["linear_attn_config"]
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],  # the dense layer's
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],     # unread: latent
        head_dim=kimi_counts.head_dim(config),          # q.k: 128 + 64
        rope_theta=float(config["rope_theta"]),         # unread: no rotary
        rms_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["dtype"]),
        layer_types=kimi_counts.layer_kinds(config),
        delta_heads=linear["num_heads"],
        delta_head_dim=linear["head_dim"],
        delta_conv=linear["short_conv_kernel_size"],
        q_lora_rank=0,
        latent_rope=False,
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        n_experts=kimi_counts.router_experts(config),
        experts_held=(0, config["num_experts"]),
        top_k=config["num_experts_per_token"],
        num_dense_layers=config["first_k_dense_replace"],
        moe_intermediate_size=config["moe_intermediate_size"],
        n_shared_experts=config["num_shared_experts"],
        router_score=config["moe_router_activation_func"],
        router_bias=True,
        route_norm=config["moe_renormalize"],
        route_scale=config["routed_scaling_factor"],
    )
