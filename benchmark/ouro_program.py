"""``program_config`` of the Ouro (LoopLM) configurations: the program's
``LlamaConfig`` from the configuration file's keys, each by its name.
Nothing of the file reaches the program but what is spelled out here;
what the released implementation does without a key in ``config.json``
(the file's ``assumed``: four norms a layer, the final norm behind every
pass, a K/V cache a (pass, layer), the exit gate and its distribution)
comes with the program's ``post_norms``, ``passes`` and ``exit_gate``.

What the program cannot build is refused here, by name, before a weight
is made."""

from __future__ import annotations

from typing import Mapping

from . import ouro_counts


def ouro_config(config: Mapping):
    import jax.numpy as jnp

    from ray_tpu.models import LlamaConfig

    if config["rope_scaling"] is not None:
        raise NotImplementedError(
            f"rope_scaling {config['rope_scaling']!r}: the program's rotary "
            f"has no scaling (the published Ouro-2.6B has none)")
    if (config["use_sliding_window"] or config["sliding_window"] is not None
            or set(config["layer_types"]) != {"full_attention"}):
        raise NotImplementedError(
            "a sliding window (use_sliding_window, sliding_window, a "
            "layer_types entry other than full_attention): the published "
            "model attends over everything in every layer, and a window "
            "layer's ring under a loop has no reference here")
    if config["tie_word_embeddings"]:
        raise NotImplementedError(
            "tie_word_embeddings: the program's head is a matrix of its own")
    if config["hidden_act"] != "silu":
        raise NotImplementedError(
            f"hidden_act {config['hidden_act']!r}: the dense FFN is SwiGLU")
    if config["early_exit_threshold"] != 1.0:
        raise NotImplementedError(
            f"early_exit_threshold {config['early_exit_threshold']}: only "
            f"1.0 is built, every token through every pass (a lower one "
            f"needs a batch whose sequences stop at different passes and "
            f"the K/V of the passes a token skipped: serve/llm.py "
            f"serving_programs)")
    if len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError("layer_types names another number of layers than "
                         "num_hidden_layers")
    assumed = config["assumed"]
    if assumed["attention_bias"] or not assumed["gate_bias"]:
        raise NotImplementedError(
            "the program's attention has no biases and its exit gate has "
            "one: assumed.attention_bias false, assumed.gate_bias true")
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=ouro_counts.head_dim(config),
        rope_theta=float(config["rope_theta"]),
        rms_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["dtype"]),
        post_norms=True,
        passes=config["total_ut_steps"],
        exit_gate=True,
        exit_threshold=float(config["early_exit_threshold"]),
    )
