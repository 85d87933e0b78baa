"""``program_config`` of the OLMoE configurations: the program's
``LlamaConfig`` from the configuration file's keys, each by its name.
Nothing of the file reaches the program but what is spelled out here."""

from __future__ import annotations

from typing import Mapping

from . import olmoe_counts


def olmoe_config(config: Mapping):
    import jax.numpy as jnp

    from ray_tpu.models import LlamaConfig

    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],  # one expert's width
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=olmoe_counts.head_dim(config),
        rope_theta=float(config["rope_theta"]),
        rms_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["dtype"]),
        n_experts=config["num_experts"],
        top_k=config["num_experts_per_tok"],
        qk_norm=True,
    )
