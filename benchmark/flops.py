"""Operations and bytes an algorithm needs, from shapes alone.

``cfg`` is a configuration file's dict (the published key names). Counts
are what the mathematics requires: recomputation (remat, the flash
backward's second pass over the scores) is not counted, a causal
attention counts its lower triangle only, and the embedding lookup is a
gather with no multiplications. bench.py's formula (6 P + 12 L S H Dh)
counts the embedding table's rows and the whole square; this one reads
about a sixth lower and cannot flatter a step.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Mapping

_HERE = os.path.dirname(os.path.abspath(__file__))

_BYTES = {"bfloat16": 2, "float32": 4}


def head_dim(cfg: Mapping) -> int:
    if cfg.get("head_dim"):
        return cfg["head_dim"]
    assumed = (cfg.get("assumed") or {}).get("head_dim")
    return assumed or cfg["hidden_size"] // cfg["num_attention_heads"]


def param_counts(cfg: Mapping) -> Dict[str, int]:
    """Parameters by where they are used. ``matmul`` is every weight a
    token is multiplied by: the layers' seven matrices and the head."""
    m, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  head_dim(cfg))
    layers, vocab = cfg["num_hidden_layers"], cfg["vocab_size"]
    attn = m * h * dh + 2 * m * hkv * dh + h * dh * m
    mlp = 3 * m * f
    layer = attn + mlp
    norms = layers * 2 * m + m
    return {
        "layer": layer,
        "embed": vocab * m,
        "lm_head": m * vocab,
        "norms": norms,
        "matmul": layers * layer + m * vocab,
        "total": layers * layer + 2 * vocab * m + norms,
    }


def train_flops_per_token(cfg: Mapping, seqlen: int) -> int:
    """Forward and backward: 6 per matmul weight, plus causal attention,
    2 (forward) + 4 (backward) matmuls over half of S x S."""
    attn = 6 * cfg["num_hidden_layers"] * seqlen * (
        cfg["num_attention_heads"] * head_dim(cfg))
    return 6 * param_counts(cfg)["matmul"] + attn


def flash_train_flops(cfg: Mapping, batch: int, seqlen: int) -> int:
    """The attention kernels' share of a step: the second term above
    times the step's tokens."""
    return (6 * cfg["num_hidden_layers"] * batch * seqlen * seqlen
            * cfg["num_attention_heads"] * head_dim(cfg))


def flash_train_bytes(cfg: Mapping, batch: int, seqlen: int) -> int:
    """HBM traffic the kernels cannot avoid: forward reads q, k, v and
    writes o; backward reads q, k, v, o, do and writes dq, dk, dv."""
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    per_head_row = batch * seqlen * head_dim(cfg) * _BYTES[cfg["dtype"]]
    forward = (2 * h + 2 * hkv) * per_head_row
    backward = (4 * h + 4 * hkv) * per_head_row
    return cfg["num_hidden_layers"] * (forward + backward)


def kv_bytes_per_token(cfg: Mapping) -> int:
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * head_dim(cfg) * _BYTES[cfg["dtype"]])


def decode_step_flops(cfg: Mapping, sequences: float,
                      context_tokens: float) -> float:
    """One token for each of ``sequences``, attending to
    ``context_tokens`` cached tokens in all."""
    attn = 4 * cfg["num_hidden_layers"] * context_tokens * (
        cfg["num_attention_heads"] * head_dim(cfg))
    return 2 * param_counts(cfg)["matmul"] * sequences + attn


def decode_step_bytes(cfg: Mapping, sequences: float,
                      context_tokens: float) -> float:
    """Every matmul weight and norm once, the keys and values of the
    tokens really cached, and one embedding row a sequence."""
    counts = param_counts(cfg)
    weights = (counts["matmul"] + counts["norms"]) * _BYTES[cfg["dtype"]]
    rows = sequences * cfg["hidden_size"] * _BYTES[cfg["dtype"]]
    return weights + context_tokens * kv_bytes_per_token(cfg) + rows


def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's published peaks; a kind not in the table is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}: add it to "
            f"benchmark/peaks.json with its source"
        )
    return table[device_kind]


def roofline_s(flops: float, nbytes: float, peak: Mapping) -> float:
    """The least time the chip could take for this work."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
