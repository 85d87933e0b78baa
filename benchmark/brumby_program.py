"""``program_config`` of the Brumby configurations: the program's
``LlamaConfig`` from the configuration file's keys, each by its name.
Nothing of the file reaches the program but what is spelled out here.
``config.json`` holds the widths (Qwen3-14B's) and not one key of the
retention: what the program does beyond them is the file's ``assumed``
and is switched on by name here (every layer of kind "state", an
RMSNorm on each head's q and k, the gate's bias, which comes with the
kind). ``model_type``, ``max_window_layers``, ``use_sliding_window`` and
``max_position_embeddings`` are kept as published and read by nothing:
no layer has a window, and the engine's ``max_len`` bounds the
positions."""

from __future__ import annotations

from typing import Mapping

from . import brumby_counts


def brumby_config(config: Mapping):
    import jax.numpy as jnp

    from ray_tpu.models import LlamaConfig

    if config["rope_scaling"] is not None:
        raise NotImplementedError("rope_scaling: the rotary is unscaled")
    if config["sliding_window"] is not None or config["use_sliding_window"]:
        raise NotImplementedError(
            "sliding_window: a retention layer has no window")
    if (config["attention_bias"] or config["tie_word_embeddings"]
            or config["hidden_act"] != "silu"):
        raise NotImplementedError(
            "attention_bias, tie_word_embeddings, hidden_act other than silu")
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=brumby_counts.head_dim(config),
        rope_theta=float(config["rope_theta"]),
        rms_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["dtype"]),
        layer_types=("state",) * config["num_hidden_layers"],
        qk_norm=True,
        qk_norm_per_head=True,
    )
