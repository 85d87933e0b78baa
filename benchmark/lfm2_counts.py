"""LFM2's (``lfm2_moe``'s) operations and bytes from shapes; never
imports jax.

The stack is not uniform, so every count sums over the layers the
configuration runs, ``layers_kept`` (first, last) of the published
``layer_types``: a ``conv`` layer's mixer is ``in_proj`` (hidden x 3
hidden), ``conv_L_cache`` taps a channel and ``out_proj`` (hidden x
hidden); a ``full_attention`` layer's is q, o (hidden x hidden) and k, v
(hidden x KV heads x ``head_dim``) with a norm a head on q and k. The
first ``num_dense_layers`` layers have a dense FFN of
``intermediate_size``, the others ``num_experts`` routed experts of
``moe_intermediate_size`` (a token is multiplied by
``num_experts_per_tok`` of them and the router; no shared expert). The
head is the embedding (``assumed.tie_word_embeddings``): one array,
counted once in ``total`` and once, as the head, in ``matmul``.

What is kept of a request: a k and a v row a token in each
``full_attention`` layer, heads of 64 (``kv_row_bytes``: 2 x 8 x 64 x 2
B, no row padded to a lane tile), and in each ``conv`` layer the last
``conv_L_cache - 1`` rows of one hidden-wide product a SLOT, whatever
the context (``conv_slot_bytes``).

The interface's ``decode_step_flops`` / ``decode_step_bytes`` take the
sequences and their cached tokens in all and count the experts REACHED
by an even router; what a step really reached is the engine's to count
(``experts_reached``), and ``decode_step_flops_rows`` /
``decode_step_bytes_rows`` take it (``rows_read``: cached rows attended
to, summed over sequences and attention layers, the engine's
``decode_kv_rows_read`` a step).
"""

from __future__ import annotations

from typing import Dict, List, Mapping

from .flops import _BYTES, head_dim  # noqa: F401  (part of the interface)

CONV, ATTENTION = "conv", "full_attention"


def layer_types(cfg: Mapping) -> List[str]:
    """The kind of each layer that runs: ``layers_kept`` (first, last)
    of the published list."""
    first, last = cfg["layers_kept"]
    kinds = cfg["layer_types"][first:last + 1]
    if (len(kinds) != cfg["num_hidden_layers"]
            or set(kinds) - {CONV, ATTENTION}):
        raise ValueError(
            f"layers_kept {cfg['layers_kept']} of layer_types gives {kinds}: "
            f"not {cfg['num_hidden_layers']} layers of {CONV} or {ATTENTION}")
    if first:
        raise ValueError(
            "layers_kept starts past layer 0: the leading num_dense_layers "
            "are counted from the stack's first layer")
    return kinds


def n_layers(cfg: Mapping, kind: str) -> int:
    return layer_types(cfg).count(kind)


def _expert_layers(cfg: Mapping) -> int:
    return cfg["num_hidden_layers"] - cfg["num_dense_layers"]


def param_counts(cfg: Mapping) -> Dict[str, int]:
    """``matmul`` is what one token is multiplied by: every layer's
    mixer, the dense layers' FFN, in an expert layer the router and
    ``num_experts_per_tok`` experts, then the head. ``layer`` is an
    expert layer's FFN whole with a conv mixer, ``dense_layer`` a dense
    one with a conv mixer (the two leading layers are conv layers)."""
    m, vocab = cfg["hidden_size"], cfg["vocab_size"]
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  head_dim(cfg))
    layers, dense = cfg["num_hidden_layers"], cfg["num_dense_layers"]
    experts, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    conv = 3 * m * m + m * m + cfg["conv_L_cache"] * m
    attn = 2 * m * h * dh + 2 * m * hkv * dh
    expert = 3 * m * cfg["moe_intermediate_size"]
    router = m * experts
    dense_ffn = 3 * m * cfg["intermediate_size"]
    n_conv, n_attn = n_layers(cfg, CONV), n_layers(cfg, ATTENTION)
    sparse = layers - dense
    mixers = n_conv * conv + n_attn * attn
    # Two norms of hidden width a layer, two of a head's an attention
    # layer, the final norm, and an expert layer's selection bias.
    norms = layers * 2 * m + n_attn * 2 * dh + m + sparse * experts
    return {
        "layer": conv + router + experts * expert,
        "dense_layer": conv + dense_ffn,
        "conv": conv,
        "attn": attn,
        "expert": expert,
        "embed": vocab * m,
        "lm_head": m * vocab,
        "norms": norms,
        "matmul": (mixers + dense * dense_ffn
                   + sparse * (router + k * expert) + m * vocab),
        "total": (mixers + dense * dense_ffn
                  + sparse * (router + experts * expert) + vocab * m + norms),
    }


def _causal_pairs(tokens: int) -> int:
    return tokens * (tokens + 1) // 2


def flash_prefill_flops(cfg: Mapping, tokens: int) -> int:
    """The attention kernel's work in a prefill of ``tokens``: two
    matmuls over the causal pairs, in the attention layers alone."""
    return (4 * n_layers(cfg, ATTENTION) * cfg["num_attention_heads"]
            * head_dim(cfg) * _causal_pairs(tokens))


def flash_prefill_bytes(cfg: Mapping, tokens: int) -> int:
    """q, k, v in and o out, once an attention layer."""
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return (n_layers(cfg, ATTENTION) * (2 * h + 2 * hkv) * tokens
            * head_dim(cfg) * _BYTES[cfg["dtype"]])


def train_flops_per_token(cfg: Mapping, seqlen: int) -> float:
    """6 per matmul weight, the attention's three passes over the causal
    pairs and the taps' (2 a tap a channel, three passes). (No cell
    trains this architecture.)"""
    taps = 6 * n_layers(cfg, CONV) * cfg["conv_L_cache"] * cfg["hidden_size"]
    return (6 * param_counts(cfg)["matmul"]
            + 3 * flash_prefill_flops(cfg, seqlen) / seqlen + taps)


def flash_train_flops(cfg: Mapping, batch: int, seqlen: int) -> int:
    return 3 * batch * flash_prefill_flops(cfg, seqlen)


def flash_train_bytes(cfg: Mapping, batch: int, seqlen: int) -> int:
    return 3 * batch * flash_prefill_bytes(cfg, seqlen)


def kv_row_bytes(cfg: Mapping) -> int:
    """One token's key and value in ONE attention layer, every KV head
    at its own ``head_dim``: nothing is padded to a lane tile."""
    return (2 * cfg["num_key_value_heads"] * head_dim(cfg)
            * _BYTES[cfg["dtype"]])


def kv_bytes_per_token(cfg: Mapping) -> int:
    """A token is kept by the attention layers alone."""
    return n_layers(cfg, ATTENTION) * kv_row_bytes(cfg)


def conv_slot_bytes(cfg: Mapping) -> int:
    """What a slot holds over all the conv layers: the last
    ``conv_L_cache - 1`` hidden-wide rows a layer, in the model's dtype
    (``assumed.conv_history``)."""
    return (n_layers(cfg, CONV) * (cfg["conv_L_cache"] - 1)
            * cfg["hidden_size"] * _BYTES[cfg["dtype"]])


def experts_reached_even(cfg: Mapping, sequences: float) -> float:
    """Experts of a layer that ``sequences`` tokens reach when the
    router is even: E (1 - (1 - 1/E) ** (k sequences)), each of the k
    sequences assignments an independent draw (~41 of 64 at 16)."""
    experts, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    return experts * (1 - (1 - 1 / experts) ** (k * sequences))


def decode_step_flops_rows(cfg: Mapping, sequences: float,
                           rows_read: float) -> float:
    """One token for each of ``sequences``: every weight a token is
    multiplied by, the attention over ``rows_read`` cached rows (summed
    over sequences and attention layers), the taps."""
    attn = 4 * rows_read * cfg["num_attention_heads"] * head_dim(cfg)
    taps = (2 * n_layers(cfg, CONV) * cfg["conv_L_cache"]
            * cfg["hidden_size"] * sequences)
    return 2 * param_counts(cfg)["matmul"] * sequences + attn + taps


def decode_step_bytes_rows(cfg: Mapping, sequences: float, rows_read: float,
                           pairs_reached: float) -> float:
    """What a decode step must move: every weight outside the routed
    experts once (the embedding once, as the head), the ``pairs_reached``
    (layer, expert) pairs that were given a token once each, the k and v
    rows read at 64-wide heads, each sequence's histories read and
    written, one embedding row a sequence."""
    counts = param_counts(cfg)
    size = _BYTES[cfg["dtype"]]
    routed = _expert_layers(cfg) * cfg["num_experts"] * counts["expert"]
    weights = counts["total"] - routed + pairs_reached * counts["expert"]
    rows = sequences * cfg["hidden_size"] * size
    return (weights * size + rows_read * kv_row_bytes(cfg)
            + 2 * sequences * conv_slot_bytes(cfg) + rows)


def decode_step_flops(cfg: Mapping, sequences: float,
                      context_tokens: float) -> float:
    """The interface's: ``context_tokens`` cached tokens in all, each
    read by the attention layers."""
    return decode_step_flops_rows(
        cfg, sequences, n_layers(cfg, ATTENTION) * context_tokens)


def decode_step_bytes(cfg: Mapping, sequences: float,
                      context_tokens: float) -> float:
    """The interface's: the slots' own contexts in the attention layers,
    the experts an even router reaches."""
    return decode_step_bytes_rows(
        cfg, sequences, n_layers(cfg, ATTENTION) * context_tokens,
        _expert_layers(cfg) * experts_reached_even(cfg, sequences))


def page_walk_flops(cfg: Mapping, rows_read: float) -> float:
    """The head-64 walk's two matmuls over ``rows_read`` cached rows."""
    return 4 * rows_read * cfg["num_attention_heads"] * head_dim(cfg)


def page_walk_bytes(cfg: Mapping, rows_read: float) -> float:
    """The rows the walk must read: a k and a v row of every KV head at
    its own width."""
    return rows_read * kv_row_bytes(cfg)


def moe_matmul_flops(cfg: Mapping, assignments: float) -> float:
    """The three routed-expert matmuls of ``assignments`` (token,
    expert) pairs: gate, up and down, each hidden x an expert's width."""
    return (2 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
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
