"""``program_config`` of the SmallThinker configurations: the program's
``LlamaConfig`` from the configuration file's keys, each by its name.
Nothing of the file reaches the program but what is spelled out here;
what the model does without a key in ``config.json`` (the file's
``assumed``: where the router reads, ReGLU, no rotary on the layers that
attend to everything) is switched on by name."""

from __future__ import annotations

from typing import Mapping

from . import smallthinker_counts


def smallthinker_config(config: Mapping):
    import jax.numpy as jnp

    from ray_tpu.models import LlamaConfig

    windows = smallthinker_counts.layer_windows(config)
    rotary = smallthinker_counts.layer_rotary(config)
    if rotary != [w is not None for w in windows]:
        raise NotImplementedError(
            "rope_layout and sliding_window_layout differ: the program "
            "rotates a window layer and leaves a full one (rope_full_layers)")
    if not (config["moe_primary_router_apply_softmax"]
            and config["norm_topk_prob"]):
        raise NotImplementedError(
            "a router without its softmax or without norm_topk_prob")
    if config["rope_scaling"] is not None or config["tie_word_embeddings"]:
        raise NotImplementedError("rope scaling, tied embeddings")
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["moe_ffn_hidden_size"],  # no dense layer
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=smallthinker_counts.head_dim(config),
        rope_theta=float(config["rope_theta"]),
        rms_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["dtype"]),
        n_experts=config["moe_num_primary_experts"],
        top_k=config["moe_num_active_primary_experts"],
        moe_intermediate_size=config["moe_ffn_hidden_size"],
        # Top-6 of the logits then a softmax over the six = a softmax
        # over all 64, the six largest, renormalised.
        router_score="softmax",
        route_norm=True,
        router_input="attention",
        expert_act="relu",
        layer_types=tuple("full" if w is None else "window"
                          for w in windows),
        sliding_window=config["sliding_window_size"],
        rope_full_layers=False,
    )
