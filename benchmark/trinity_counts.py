"""Trinity's (AFMoE's) operations and bytes from shapes; never imports
jax.

The stack is not uniform, so every count sums over the layers the
configuration runs: the first ``num_hidden_layers`` entries of
``layer_types``, of which the first ``num_dense_layers`` have a dense
FFN of ``intermediate_size`` and the others ``num_experts`` routed
experts of ``moe_intermediate_size`` (a token is multiplied by
``num_experts_per_tok`` of them, the router and the shared expert). A
layer's attention has five matrices: q, k, v, the output gate and the
output projection. A ``sliding_attention`` layer attends to, and keeps,
at most ``sliding_window`` tokens.

The interface's ``kv_bytes_per_token``, ``decode_step_flops`` and
``decode_step_bytes`` take one context for every layer: they are the
FULL-ATTENTION figures, what a context under the window costs, and an
upper bound over it. What a step really reads is the engine's to count
(``decode_kv_rows_read``, ``experts_reached``): ``decode_step_flops_rows``
and ``decode_step_bytes_rows`` take those counts.
"""

from __future__ import annotations

from typing import Dict, List, Mapping

from .flops import _BYTES, head_dim  # noqa: F401  (part of the interface)


def layer_types(cfg: Mapping) -> List[str]:
    """The attention kind of each layer that runs: the published list's
    first ``num_hidden_layers`` entries."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    if len(kinds) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types is shorter than num_hidden_layers")
    return kinds


def _expert_layers(cfg: Mapping) -> int:
    return cfg["num_hidden_layers"] - cfg["num_dense_layers"]


def param_counts(cfg: Mapping) -> Dict[str, int]:
    """``matmul`` is what one token is multiplied by: every layer's
    attention, the dense layers' FFN, and in an expert layer the router,
    ``num_experts_per_tok`` experts and the shared one; then the head.
    ``layer`` is an expert layer whole, ``dense_layer`` a dense one."""
    m, vocab = cfg["hidden_size"], cfg["vocab_size"]
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  head_dim(cfg))
    layers, dense = cfg["num_hidden_layers"], cfg["num_dense_layers"]
    experts, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    attn = 3 * m * h * dh + 2 * m * hkv * dh        # q, gate, o; k, v
    expert = 3 * m * cfg["moe_intermediate_size"]
    shared = cfg["num_shared_experts"] * expert
    router = m * experts
    dense_layer = attn + 3 * m * cfg["intermediate_size"]
    layer = attn + router + shared + experts * expert
    sparse = layers - dense
    # Four norms of hidden width and two of a head's a layer, the final
    # norm, and an expert layer's selection bias.
    norms = layers * (4 * m + 2 * dh) + m + sparse * experts
    return {
        "layer": layer,
        "dense_layer": dense_layer,
        "attn": attn,
        "expert": expert,
        "embed": vocab * m,
        "lm_head": m * vocab,
        "norms": norms,
        "matmul": (dense * dense_layer
                   + sparse * (attn + router + shared + k * expert)
                   + m * vocab),
        "total": (dense * dense_layer + sparse * layer + 2 * vocab * m
                  + norms),
    }


def attended_pairs(tokens: int, window=None) -> int:
    """(query, key) pairs of a causal attention over ``tokens``
    positions, each query over the last ``window`` keys where one is
    given: token t attends to min(t + 1, window)."""
    if window is None or tokens <= window:
        return tokens * (tokens + 1) // 2
    return window * (window + 1) // 2 + (tokens - window) * window


def _pairs_all_layers(cfg: Mapping, tokens: int) -> int:
    return sum(attended_pairs(
        tokens, cfg["sliding_window"] if kind == "sliding_attention" else None)
        for kind in layer_types(cfg))


def train_flops_per_token(cfg: Mapping, seqlen: int) -> float:
    """6 per matmul weight, and the attention's three passes over the
    pairs each layer attends to. (No cell trains this architecture.)"""
    attn = (12 * cfg["num_attention_heads"] * head_dim(cfg)
            * _pairs_all_layers(cfg, seqlen) / seqlen)
    return 6 * param_counts(cfg)["matmul"] + attn


def flash_prefill_flops(cfg: Mapping, tokens: int) -> int:
    """The attention kernels' work in a prefill of ``tokens``: two
    matmuls over the pairs each layer attends to, a window layer's cut
    at its lower bound."""
    return (4 * cfg["num_attention_heads"] * head_dim(cfg)
            * _pairs_all_layers(cfg, tokens))


def flash_prefill_bytes(cfg: Mapping, tokens: int) -> int:
    """HBM traffic the forward kernel cannot avoid: q, k, v in and o
    out, once a layer."""
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return (cfg["num_hidden_layers"] * (2 * h + 2 * hkv) * tokens
            * head_dim(cfg) * _BYTES[cfg["dtype"]])


def flash_train_flops(cfg: Mapping, batch: int, seqlen: int) -> int:
    """Forward and backward: three times the forward's."""
    return 3 * batch * flash_prefill_flops(cfg, seqlen)


def flash_train_bytes(cfg: Mapping, batch: int, seqlen: int) -> int:
    """Forward as ``flash_prefill_bytes``; backward reads q, k, v, o, do
    and writes dq, dk, dv: twice the forward's again."""
    return 3 * batch * flash_prefill_bytes(cfg, seqlen)


def kv_row_bytes(cfg: Mapping) -> int:
    """One token's key and value in ONE layer."""
    return (2 * cfg["num_key_value_heads"] * head_dim(cfg)
            * _BYTES[cfg["dtype"]])


def kv_bytes_per_token(cfg: Mapping) -> int:
    """The FULL-ATTENTION figure: a token kept by every layer. A
    sliding_attention layer stops keeping it ``sliding_window`` later."""
    return cfg["num_hidden_layers"] * kv_row_bytes(cfg)


def decode_step_flops_rows(cfg: Mapping, sequences: float,
                           rows_read: float) -> float:
    """One token for each of ``sequences``; ``rows_read`` cached rows
    attended to, summed over sequences AND layers (the engine's
    ``decode_kv_rows_read`` a step)."""
    attn = 4 * rows_read * cfg["num_attention_heads"] * head_dim(cfg)
    return 2 * param_counts(cfg)["matmul"] * sequences + attn


def decode_step_bytes_rows(cfg: Mapping, sequences: float, rows_read: float,
                           pairs_reached: float) -> float:
    """Every weight outside the routed experts once, the
    ``pairs_reached`` (layer, expert) pairs that were given a token
    once each, the rows read, one embedding row a sequence."""
    counts = param_counts(cfg)
    size = _BYTES[cfg["dtype"]]
    routed = _expert_layers(cfg) * cfg["num_experts"] * counts["expert"]
    weights = (counts["total"] - counts["embed"] - routed
               + pairs_reached * counts["expert"])
    rows = sequences * cfg["hidden_size"] * size
    return weights * size + rows_read * kv_row_bytes(cfg) + rows


def experts_reached_even(cfg: Mapping, sequences: float) -> float:
    """Experts of a layer that ``sequences`` tokens reach when the
    router is even: E (1 - (1 - k/E) ** sequences)."""
    experts, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    return experts * (1 - (1 - k / experts) ** sequences)


def decode_step_flops(cfg: Mapping, sequences: float,
                      context_tokens: float) -> float:
    """The interface's: every layer attends to the whole context."""
    return decode_step_flops_rows(
        cfg, sequences, cfg["num_hidden_layers"] * context_tokens)


def decode_step_bytes(cfg: Mapping, sequences: float,
                      context_tokens: float) -> float:
    """The interface's: every layer reads the whole context, and the
    experts reached are those of an even router."""
    return decode_step_bytes_rows(
        cfg, sequences, cfg["num_hidden_layers"] * context_tokens,
        _expert_layers(cfg) * experts_reached_even(cfg, sequences))


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
