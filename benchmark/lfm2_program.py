"""``program_config`` of the LFM2 (``lfm2_moe``) configurations: the
program's ``LlamaConfig`` from the configuration file's keys, each by its
name. Nothing of the file reaches the program but what is spelled out
here; what the released implementation does without a key in
``config.json`` (the file's ``assumed``: heads of ``hidden_size /
num_attention_heads``, the tied head, the order of the convolution's
gates and that it has no activation, the per-head norms, the router in
float32) comes with the program's "conv" layers, ``tied_head`` and
``qk_norm_per_head``. One key is of the weights and not of the
architecture: ``assumed.qk_norm_init``, what the seed draws the per-head
norms' weights around (the file says why it is not 1).

The file holds ``num_hidden_layers`` layers of the published stack,
``layers_kept`` (first, last) of ``layer_types``."""

from __future__ import annotations

from typing import Mapping

from . import lfm2_counts as counts

_KINDS = {counts.CONV: "conv", counts.ATTENTION: "full"}


def lfm2_config(config: Mapping):
    import jax.numpy as jnp

    from ray_tpu.models import LlamaConfig

    assumed = config["assumed"]
    if config["conv_bias"] or not assumed["tie_word_embeddings"]:
        raise NotImplementedError(
            "conv_bias, or a head of its own: the program's convolution "
            "and projections have no biases, and this family's head is its "
            "embedding (LlamaConfig.tied_head)")
    if not (config["norm_topk_prob"] and config["use_expert_bias"]):
        raise NotImplementedError(
            "norm_topk_prob, use_expert_bias: each is on as published, and "
            "built so")
    rope = config["rope_parameters"]
    if rope["rope_type"] != "default":
        raise NotImplementedError(
            f"rope_type {rope['rope_type']!r}: the program rotates the two "
            f"halves of a head at rope_theta, unscaled")
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],  # the dense layers'
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=counts.head_dim(config),
        rope_theta=float(rope["rope_theta"]),
        rms_eps=config["norm_eps"],
        dtype=jnp.dtype(config["dtype"]),
        n_experts=config["num_experts"],
        top_k=config["num_experts_per_tok"],
        num_dense_layers=config["num_dense_layers"],
        moe_intermediate_size=config["moe_intermediate_size"],
        router_score="sigmoid",
        router_bias=True,
        route_norm=True,
        route_scale=float(config["routed_scaling_factor"]),
        layer_types=tuple(_KINDS[kind]
                          for kind in counts.layer_types(config)),
        qk_norm=True,
        qk_norm_per_head=True,
        qk_norm_init=float(assumed["qk_norm_init"]),
        conv_taps=config["conv_L_cache"],
        tied_head=True,
    )
