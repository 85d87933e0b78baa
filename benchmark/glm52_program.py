"""``program_config`` of the GLM-5.2 configurations: the program's
``LlamaConfig`` from the configuration file's keys, each by its name.
Nothing of the file reaches the program but what is spelled out here;
what the released implementation does without a key in ``config.json``
(the file's ``assumed``) comes with the program's latent attention, its
indexer and its ``router_bias``.

The file is one chip's share of a deployment (its ``deployment`` and
``reduced`` say of what): ``num_hidden_layers`` consecutive published
layers from ``layers_from``, at which ``mlp_layer_types`` and
``indexer_types``, kept whole as published, are read; the router at
``reduced.n_routed_experts.published`` columns, the file's
``n_routed_experts`` the experts held here, the first of them; and the
file's ``vocab_size`` rows of the vocabulary."""

from __future__ import annotations

from typing import Mapping

from . import glm52_counts


def glm52_config(config: Mapping):
    import jax.numpy as jnp

    from ray_tpu.models import LlamaConfig

    if (config["n_group"], config["topk_group"]) != (1, 1):
        raise NotImplementedError(
            "grouped top-k (n_group > 1): the router has no group step")
    rope = config["rope_parameters"]
    if rope["rope_type"] != "default" or config["moe_layer_freq"] != 1:
        raise NotImplementedError("rope scaling, moe_layer_freq != 1")
    if config["index_topk_pattern"] is not None:
        raise NotImplementedError("index_topk_pattern: indexer_types decides")
    mlp, indexers = glm52_counts.layer_kinds(config)
    dense = mlp.count("dense")
    if mlp != ("dense",) * dense + ("sparse",) * (len(mlp) - dense):
        raise NotImplementedError(
            f"dense layers behind expert layers: {mlp}")
    held = config["n_routed_experts"]
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],  # the dense layer's
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],     # unread: latent
        head_dim=glm52_counts.head_dim(config),         # q.k: 192 + 64
        rope_theta=float(rope["rope_theta"]),
        rms_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["dtype"]),
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        rope_interleave=config["rope_interleave"],
        index_topk=config["index_topk"],
        index_n_heads=config["index_n_heads"],
        index_head_dim=config["index_head_dim"],
        index_rope_interleave=config["indexer_rope_interleave"],
        indexer_types=indexers,
        n_experts=glm52_counts.router_experts(config),
        experts_held=(0, held),
        top_k=config["num_experts_per_tok"],
        num_dense_layers=dense,
        moe_intermediate_size=config["moe_intermediate_size"],
        n_shared_experts=config["n_shared_experts"],
        router_score=config["scoring_func"],
        router_bias=config["topk_method"] == "noaux_tc",
        route_norm=config["norm_topk_prob"],
        route_scale=config["routed_scaling_factor"],
    )
