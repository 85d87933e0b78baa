"""Brumby's (power retention on Qwen3-14B's widths) operations and bytes
from shapes; never imports jax.

Every layer is a retention layer: q, k, v and o as a grouped-query
attention's, a gate of one scalar a KV head a token, and in place of a
KV cache a state a KV head: ``S`` [D, d] and the normaliser ``z`` [D],
float32, ``D = d (d + 1) / 2`` (each unordered pair of a key's
coordinates once; 8,256 at ``d`` 128). These counts take that, the least
a state of degree 2 holds: 8 x 8,256 x 129 x 4 B = 34.08 MB a slot a
layer. What the program's tiling makes of it (8,320 by 136) is the
engine's gauge ``state_slot_bytes``, not a count.

A decode step reads and writes every sequence's state once a layer
whatever its context, so ``decode_step_flops/bytes`` do not depend on
``context_tokens`` and ``kv_bytes_per_token`` is 0: nothing is kept a
token.
"""

from __future__ import annotations

from typing import Dict, Mapping

from .flops import _BYTES

STATE_BYTES = 4  # the state and the normaliser are float32


def head_dim(cfg: Mapping) -> int:
    return cfg["head_dim"]


def state_width(cfg: Mapping) -> int:
    """D: the unordered pairs of a head's coordinates."""
    d = head_dim(cfg)
    return d * (d + 1) // 2


def param_counts(cfg: Mapping) -> Dict[str, int]:
    """``matmul`` is every weight a token is multiplied by: a layer's
    q, k, v, o, the gate's projection and the three of the FFN, and the
    head. ``norms`` holds the vectors: two norms of hidden width and the
    q and k norms of a head's width a layer, the gate's bias, the final
    norm."""
    m, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 head_dim(cfg))
    layers, vocab = cfg["num_hidden_layers"], cfg["vocab_size"]
    attn = 2 * m * h * d + 2 * m * hkv * d
    gate = m * hkv
    mlp = 3 * m * f
    layer = attn + gate + mlp
    norms = layers * (2 * m + 2 * d + hkv) + m
    return {
        "layer": layer,
        "attn": attn,
        "gate": gate,
        "embed": vocab * m,
        "lm_head": m * vocab,
        "norms": norms,
        "matmul": layers * layer + m * vocab,
        "total": layers * layer + 2 * vocab * m + norms,
    }


def state_slot_bytes(cfg: Mapping) -> int:
    """One sequence's state and normaliser in ONE layer, unpadded."""
    return (cfg["num_key_value_heads"] * state_width(cfg)
            * (head_dim(cfg) + 1) * STATE_BYTES)


def kv_bytes_per_token(cfg: Mapping) -> int:
    """Nothing is kept a token: the state does not grow."""
    return 0


def state_walk_flops(cfg: Mapping, slot_layers: float) -> float:
    """The decode step's retention over ``slot_layers`` (sequences x
    layers): a KV head's decay and rank-one update of S and z (3 D
    (d + 1)), and each query head's read-out against them (2 D (d + 1))."""
    d, width = head_dim(cfg), state_width(cfg)
    return slot_layers * width * (d + 1) * (
        3 * cfg["num_key_value_heads"] + 2 * cfg["num_attention_heads"])


def state_walk_bytes(cfg: Mapping, slot_layers: float) -> float:
    """Each state read once and written once."""
    return 2 * slot_layers * state_slot_bytes(cfg)


def retention_prefill_flops(cfg: Mapping, tokens: int, chunk: int = 256
                            ) -> float:
    """The chunked prefill of ``tokens`` real tokens, every layer: a
    query head's token against the state (2 D (d + 1)), a KV head's
    token into it (2 D (d + 1)), and inside a chunk a head's q.k and
    a.v over the causal pairs (4 d a pair, about ``chunk`` / 2 a
    token)."""
    d, width = head_dim(cfg), state_width(cfg)
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    per_token = (2 * width * (d + 1) * (h + hkv)
                 + 4 * d * h * (min(chunk, tokens) + 1) / 2)
    return cfg["num_hidden_layers"] * tokens * per_token


def retention_prefill_bytes(cfg: Mapping, tokens: int) -> float:
    """HBM traffic no chunked kernel can avoid: q in and o out, k, v and
    the gate in, the final state out; every layer. (The state between
    chunks can stay on chip.)"""
    d = head_dim(cfg)
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    rows = tokens * ((2 * h + 2 * hkv) * d * _BYTES[cfg["dtype"]]
                     + hkv * STATE_BYTES)
    return cfg["num_hidden_layers"] * (rows + state_slot_bytes(cfg))


def decode_step_flops_state(cfg: Mapping, sequences: float,
                            slot_layers: float) -> float:
    """One token for each of ``sequences``; ``slot_layers`` states
    updated and read (the engine's ``decode_state_slot_layers`` a
    step)."""
    return (2 * param_counts(cfg)["matmul"] * sequences
            + state_walk_flops(cfg, slot_layers))


def decode_step_bytes_state(cfg: Mapping, sequences: float,
                            slot_layers: float) -> float:
    """Every matmul weight and norm once, the counted states read and
    written, one embedding row a sequence."""
    counts = param_counts(cfg)
    size = _BYTES[cfg["dtype"]]
    weights = (counts["matmul"] + counts["norms"]) * size
    rows = sequences * cfg["hidden_size"] * size
    return weights + state_walk_bytes(cfg, slot_layers) + rows


def decode_step_flops(cfg: Mapping, sequences: float,
                      context_tokens: float) -> float:
    """The interface's; ``context_tokens`` moves nothing: every
    sequence's state in every layer, whatever it has seen."""
    return decode_step_flops_state(
        cfg, sequences, sequences * cfg["num_hidden_layers"])


def decode_step_bytes(cfg: Mapping, sequences: float,
                      context_tokens: float) -> float:
    return decode_step_bytes_state(
        cfg, sequences, sequences * cfg["num_hidden_layers"])


def train_flops_per_token(cfg: Mapping, seqlen: int) -> float:
    """6 per matmul weight and three passes of the chunked retention.
    (No cell trains this architecture: the scan has no backward.)"""
    return (6 * param_counts(cfg)["matmul"]
            + 3 * retention_prefill_flops(cfg, seqlen) / seqlen)


def flash_train_flops(cfg: Mapping, batch: int, seqlen: int) -> float:
    """The interface's name for the attention's share of a step: here
    the retention's, forward and backward."""
    return 3 * batch * retention_prefill_flops(cfg, seqlen)


def flash_train_bytes(cfg: Mapping, batch: int, seqlen: int) -> float:
    return 3 * batch * retention_prefill_bytes(cfg, seqlen)
