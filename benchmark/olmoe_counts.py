"""OLMoE's operations and bytes from shapes; never imports jax.

A token is multiplied by ``num_experts_per_tok`` of a layer's experts
and by its router; ``total`` and ``layer`` hold every expert, and the
two QK-norm vectors are among the norms. Attention is the dense
model's, so those counts are ``benchmark/flops.py``'s own."""

from __future__ import annotations

from typing import Dict, Mapping

from .flops import (  # noqa: F401  (part of the counts interface)
    _BYTES, flash_train_bytes, flash_train_flops, head_dim,
    kv_bytes_per_token,
)


def param_counts(cfg: Mapping) -> Dict[str, int]:
    """``matmul`` is what one token is multiplied by: attention, the
    router, ``num_experts_per_tok`` experts, the head."""
    m, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  head_dim(cfg))
    layers, vocab = cfg["num_hidden_layers"], cfg["vocab_size"]
    experts, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    attn = m * h * dh + 2 * m * hkv * dh + h * dh * m
    expert, router = 3 * m * f, m * experts
    layer = attn + router + experts * expert
    # attn_norm, mlp_norm, q_norm, k_norm a layer, and the final norm.
    norms = layers * (2 * m + h * dh + hkv * dh) + m
    return {
        "layer": layer,
        "expert": expert,
        "embed": vocab * m,
        "lm_head": m * vocab,
        "norms": norms,
        "matmul": layers * (attn + router + k * expert) + m * vocab,
        "total": layers * layer + 2 * vocab * m + norms,
    }


def train_flops_per_token(cfg: Mapping, seqlen: int) -> int:
    attn = 6 * cfg["num_hidden_layers"] * seqlen * (
        cfg["num_attention_heads"] * head_dim(cfg))
    return 6 * param_counts(cfg)["matmul"] + attn


def decode_step_flops(cfg: Mapping, sequences: float,
                      context_tokens: float) -> float:
    attn = 4 * cfg["num_hidden_layers"] * context_tokens * (
        cfg["num_attention_heads"] * head_dim(cfg))
    return 2 * param_counts(cfg)["matmul"] * sequences + attn


def experts_reached_even(cfg: Mapping, sequences: float) -> float:
    """Experts of a layer that ``sequences`` tokens reach when the
    router is even: E (1 - (1 - k/E) ** sequences)."""
    experts, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    return experts * (1 - (1 - k / experts) ** sequences)


def decode_step_bytes(cfg: Mapping, sequences: float,
                      context_tokens: float) -> float:
    """Every weight outside the experts once, of each layer's experts
    those reached under even routing, the cached tokens' keys and
    values, one embedding row a sequence. (The engine counts the experts
    really reached: ``moe_matmul_bytes`` takes that count.)"""
    counts = param_counts(cfg)
    idle = cfg["num_experts"] - experts_reached_even(cfg, sequences)
    weights = (counts["total"] - counts["embed"]
               - cfg["num_hidden_layers"] * idle * counts["expert"])
    size = _BYTES[cfg["dtype"]]
    rows = sequences * cfg["hidden_size"] * size
    return weights * size + context_tokens * kv_bytes_per_token(cfg) + rows


def moe_matmul_flops(cfg: Mapping, assignments: float) -> float:
    """The three expert matmuls of ``assignments`` (token, expert)
    pairs: gate, up and down, each hidden x expert width."""
    return (2 * 3 * cfg["hidden_size"] * cfg["intermediate_size"]
            * assignments)


def moe_matmul_bytes(cfg: Mapping, assignments: float,
                     pairs_reached: float) -> float:
    """The weights of the ``pairs_reached`` (layer, expert) pairs that
    were given a token, once each, and a hidden-wide row in and out for
    every assignment (the expert-wide intermediate can stay on chip)."""
    size = _BYTES[cfg["dtype"]]
    weights = pairs_reached * param_counts(cfg)["expert"]
    rows = assignments * 2 * cfg["hidden_size"]
    return (weights + rows) * size
