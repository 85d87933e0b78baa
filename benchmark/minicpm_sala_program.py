"""``program_config`` of the MiniCPM-SALA configurations: the program's
``LlamaConfig`` from the configuration file's keys, each by its name.
Nothing of the file reaches the program but what is spelled out here;
what the released implementation does without a key in ``config.json``
(the file's ``assumed``: the block selection's sizes and its switch by
query position, the per-head norms, the decays' formula, the float32
state) comes with the program's ``block_select``, ``qk_norm_per_head``
and "linear" layers.

The file holds ``num_hidden_layers`` layers of the published stack,
``layers_kept`` (first, last) of ``mixer_types``; the muP constants and
each lightning layer's decays are those of the PUBLISHED depth and
index (``reduced.num_hidden_layers.published``)."""

from __future__ import annotations

from typing import Mapping

from . import minicpm_sala_counts as counts

_KINDS = {counts.SPARSE: "full", counts.LINEAR: "linear"}


def sala_config(config: Mapping):
    import jax.numpy as jnp

    from ray_tpu.models import LlamaConfig
    from ray_tpu.ops.block_attention import BlockSizes

    if (config["attention_bias"] or config["hidden_act"] != "silu"
            or config["tie_word_embeddings"]):
        raise NotImplementedError(
            "attention_bias, hidden_act other than silu, "
            "tie_word_embeddings: the program's attention has no biases, "
            "its FFN is SwiGLU and its head a matrix of its own")
    if config["attn_use_rope"] or not config["lightning_use_rope"]:
        raise NotImplementedError(
            "rotary on the minicpm4 layers or none on the lightning ones: "
            "the program rotates its linear layers alone "
            "(rope_full_layers off)")
    if not (config["qk_norm"] and config["use_output_gate"]
            and config["use_output_norm"] and config["attn_use_output_gate"]):
        raise NotImplementedError(
            "qk_norm, use_output_gate, use_output_norm, "
            "attn_use_output_gate: each is on as published, and built so")
    if (config["lightning_nkv"] != config["lightning_nh"]
            or config["lightning_scale"] != "1/sqrt(d)"):
        raise NotImplementedError(
            "lightning_nkv != lightning_nh or a lightning_scale other than "
            "1/sqrt(d): a lightning layer is MHA, read out at d ** -0.5")
    sparse = counts.sparse(config)
    published = config["reduced"]["num_hidden_layers"]["published"] \
        if "num_hidden_layers" in config.get("reduced", {}) \
        else config["num_hidden_layers"]
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=counts.head_dim(config),
        rope_theta=float(config["rope_theta"]),
        rms_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["dtype"]),
        layer_types=tuple(_KINDS[kind] for kind in counts.mixers(config)),
        rope_full_layers=False,
        qk_norm=True,
        qk_norm_per_head=True,
        attn_gate=True,
        embed_scale=float(config["scale_emb"]),
        residual_scale=config["scale_depth"] / published ** 0.5,
        logit_divisor=config["hidden_size"] / config["dim_model_base"],
        linear_heads=config["lightning_nh"],
        linear_head_dim=config["lightning_head_dim"],
        linear_decay_layers=(config["layers_kept"][0], published),
        block_select=BlockSizes(
            kernel=sparse["kernel_size"], stride=sparse["kernel_stride"],
            block=sparse["block_size"], init=sparse["init_blocks"],
            window=sparse["window_size"], topk=sparse["topk"],
            dense_len=sparse["dense_len"]),
    )
