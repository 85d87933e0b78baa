"""Ouro's (a looped model's) operations and bytes from shapes; never
imports jax.

The stack of ``num_hidden_layers`` layers runs ``total_ut_steps`` times
over ONE set of weights, so the weights are counted once where they are
HELD (``param_counts``: ``total`` is what a checkpoint holds) and once
for every pass where they are READ or MULTIPLIED BY (``matmul``, the
decode step's bytes and operations): a pass cannot reuse what the pass
before it read, 4.9 GB of layers do not stay on the chip between them.
Each (pass, layer) keeps keys and values of its own, so a token holds
``total_ut_steps x num_hidden_layers`` rows of K and of V. The head runs
once, behind the last pass; the final norm and the exit gate
(``Linear(hidden, 1)`` with a bias) once a pass.

Hidden 2048, 16 heads on 16 KV heads of 128, SwiGLU of 5632, 48 layers,
4 passes, vocabulary 49,152 untied: 2,667,974,657 parameters and
1,572,864 B of K and V a token in bfloat16
(tests/bench_harness/test_benchmark_ouro.py pins both).
"""

from __future__ import annotations

from typing import Dict, Mapping

from .flops import _BYTES, head_dim  # noqa: F401  (part of the interface)


def passes(cfg: Mapping) -> int:
    return cfg["total_ut_steps"]


def layer_walks(cfg: Mapping) -> int:
    """(pass, layer) pairs a token goes through: the depth of a step,
    of the KV pool, and the calls of a layer's kernel a program makes."""
    return passes(cfg) * cfg["num_hidden_layers"]


def param_counts(cfg: Mapping) -> Dict[str, int]:
    """Parameters by where they are used. ``layer`` is a layer whole,
    its four norms among it; ``layer_matmul`` its seven matrices;
    ``gate`` the exit gate's weight and bias; ``total`` what the model
    holds, every layer ONCE. ``matmul`` is every weight a token is
    multiplied by on its way to a logit: the layers' matrices and the
    gate's weight once a PASS, the head once."""
    m, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  head_dim(cfg))
    layers, vocab = cfg["num_hidden_layers"], cfg["vocab_size"]
    attn = m * h * dh + 2 * m * hkv * dh + h * dh * m
    layer_matmul = attn + 3 * m * f
    layer = layer_matmul + 4 * m
    norms = layers * 4 * m + m
    gate = m + 1
    return {
        "layer": layer,
        "layer_matmul": layer_matmul,
        "attn": attn,
        "embed": vocab * m,
        "lm_head": m * vocab,
        "norms": norms,
        "gate": gate,
        "matmul": passes(cfg) * (layers * layer_matmul + m) + m * vocab,
        "total": layers * layer + 2 * vocab * m + m + gate,
    }


def _pass_weights(cfg: Mapping) -> int:
    """Parameters one pass reads: every layer, the final norm, the gate."""
    counts = param_counts(cfg)
    return (cfg["num_hidden_layers"] * counts["layer"] + cfg["hidden_size"]
            + counts["gate"])


def kv_row_bytes(cfg: Mapping) -> int:
    """One token's key and value in ONE (pass, layer): what the page
    walk reads of a cached token in one call."""
    return (2 * cfg["num_key_value_heads"] * head_dim(cfg)
            * _BYTES[cfg["dtype"]])


def kv_bytes_per_token(cfg: Mapping) -> int:
    """A token's K and V over all passes and layers: a pool
    ``total_ut_steps`` times as deep as the stack."""
    return layer_walks(cfg) * kv_row_bytes(cfg)


def decode_step_flops(cfg: Mapping, sequences: float,
                      context_tokens: float) -> float:
    """One token for each of ``sequences``, through every pass,
    attending in every (pass, layer) to ``context_tokens`` cached
    tokens in all."""
    attn = 4 * layer_walks(cfg) * context_tokens * (
        cfg["num_attention_heads"] * head_dim(cfg))
    return 2 * param_counts(cfg)["matmul"] * sequences + attn


def decode_step_weight_bytes(cfg: Mapping) -> Dict[str, float]:
    """The weights a decode step reads, by why: ``first_pass`` (the
    layers, norm and gate once, as a model without a loop would),
    ``later_passes`` (the same again for every pass after the first:
    what the loop costs) and ``head``."""
    size = _BYTES[cfg["dtype"]]
    one = _pass_weights(cfg) * size
    return {"first_pass": one, "later_passes": (passes(cfg) - 1) * one,
            "head": param_counts(cfg)["lm_head"] * size}


def decode_step_bytes_rows(cfg: Mapping, sequences: float,
                           rows_read: float) -> float:
    """The weights of every pass, the head, ``rows_read`` cached rows
    (summed over sequences, passes and layers: the engine's
    ``decode_kv_rows_read`` a step) and one embedding row a sequence."""
    rows = sequences * cfg["hidden_size"] * _BYTES[cfg["dtype"]]
    return (sum(decode_step_weight_bytes(cfg).values())
            + rows_read * kv_row_bytes(cfg) + rows)


def decode_step_bytes(cfg: Mapping, sequences: float,
                      context_tokens: float) -> float:
    """The interface's: the layers' weights ``total_ut_steps`` times,
    the head once, the K and V of the tokens held in every (pass,
    layer), one embedding row a sequence."""
    return decode_step_bytes_rows(cfg, sequences,
                                  layer_walks(cfg) * context_tokens)


def flash_prefill_flops(cfg: Mapping, tokens: int) -> int:
    """The flash kernel's work in the ``num_hidden_layers`` calls of ONE
    pass over a prompt of ``tokens``: two matmuls over the causal pairs.
    A prefill makes ``layer_walks`` calls (192); ``readers/window.py``'s
    ``prefill_flash_roofline`` divides the calls it saw by
    ``num_hidden_layers``, so a whole prefill reads as ``total_ut_steps``
    times this, which is what it is."""
    pairs = tokens * (tokens + 1) // 2
    return (4 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * head_dim(cfg) * pairs)


def flash_prefill_bytes(cfg: Mapping, tokens: int) -> int:
    """HBM traffic the forward kernel cannot avoid in one pass's calls:
    q, k, v in and o out, once a layer."""
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return (cfg["num_hidden_layers"] * (2 * h + 2 * hkv) * tokens
            * head_dim(cfg) * _BYTES[cfg["dtype"]])


def prefill_flops(cfg: Mapping, tokens: int) -> float:
    """A whole prefill of ``tokens``: every pass's matmuls and causal
    attention, the head for the one row that is read."""
    counts = param_counts(cfg)
    return (2 * (counts["matmul"] - counts["lm_head"]) * tokens
            + 2 * counts["lm_head"]
            + passes(cfg) * flash_prefill_flops(cfg, tokens))


def train_flops_per_token(cfg: Mapping, seqlen: int) -> float:
    """6 per weight a token is multiplied by and the attention's three
    passes over the causal pairs of every (pass, layer). (No cell trains
    this architecture: its objective weights every pass's loss.)"""
    attn = 6 * layer_walks(cfg) * seqlen * (
        cfg["num_attention_heads"] * head_dim(cfg))
    return 6 * param_counts(cfg)["matmul"] + attn


def flash_train_flops(cfg: Mapping, batch: int, seqlen: int) -> int:
    """Forward and backward of every pass: three times the forward's."""
    return 3 * batch * passes(cfg) * flash_prefill_flops(cfg, seqlen)


def flash_train_bytes(cfg: Mapping, batch: int, seqlen: int) -> int:
    return 3 * batch * passes(cfg) * flash_prefill_bytes(cfg, seqlen)
