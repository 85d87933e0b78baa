"""SmallThinker's operations and bytes from shapes; never imports jax.

Every layer is an expert layer: attention of ``num_attention_heads`` on
``num_key_value_heads`` (28 on 4: q, k, v and the output projection, no
bias, no gate), a float32 router of ``moe_num_primary_experts`` columns
that reads the ATTENTION's normed input, and that many ReGLU experts of
``moe_ffn_hidden_size`` of which a token is multiplied by
``moe_num_active_primary_experts``; no shared expert, no dense layer.
Layer ``l`` attends to the last ``sliding_window_size`` tokens where
``sliding_window_layout[l]`` is 1 and to everything where it is 0; the
counts sum over the layers that run, the published lists' first
``num_hidden_layers`` entries.

The interface's ``kv_bytes_per_token``, ``decode_step_flops`` and
``decode_step_bytes`` take one context for every layer: the
FULL-ATTENTION figures, an upper bound over the window. What a step
really reads is the engine's to count (``decode_kv_rows_read``,
``experts_reached``): ``decode_step_flops_rows`` and
``decode_step_bytes_rows`` take those counts, as Trinity's do.

A prefill's attention runs one of two forms of the flash forward kernel
(ray_tpu/ops/flash_attention.py): the resident one where a head's K and
V fit VMEM whole (buckets to 8,192 here), the streamed one over that
(16,384). The work asked of either is the same, the (query, key) pairs
each layer attends to, a window layer's cut at its lower bound:
``flash_prefill_*`` is what the accepted ``prefill_flash_roofline``
reads for the resident form's calls, ``flash_streamed_*`` what
``prefill_stream_roofline`` reads for the streamed form's.
"""

from __future__ import annotations

from typing import Dict, List, Mapping, Optional

from .flops import _BYTES, head_dim  # noqa: F401  (part of the interface)
from .trinity_counts import attended_pairs  # noqa: F401  (likewise)


def _layout(cfg: Mapping, key: str) -> list:
    """The published per-layer list ``key``'s first ``num_hidden_layers``
    entries: the layers that run."""
    layout = cfg[key][:cfg["num_hidden_layers"]]
    if len(layout) != cfg["num_hidden_layers"]:
        raise ValueError(f"{key} is shorter than num_hidden_layers")
    return layout


def layer_windows(cfg: Mapping) -> List[Optional[int]]:
    """Each running layer's attention window, None for a full layer:
    ``sliding_window_layout``."""
    return [cfg["sliding_window_size"] if flag else None
            for flag in _layout(cfg, "sliding_window_layout")]


def layer_rotary(cfg: Mapping) -> List[bool]:
    """Whether each running layer rotates q and k: ``rope_layout``."""
    return [bool(flag) for flag in _layout(cfg, "rope_layout")]


def param_counts(cfg: Mapping) -> Dict[str, int]:
    """``matmul`` is what one token is multiplied by: every layer's
    attention, its router and ``moe_num_active_primary_experts`` experts,
    then the head. ``layer`` is a layer whole."""
    m, vocab = cfg["hidden_size"], cfg["vocab_size"]
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  head_dim(cfg))
    layers = cfg["num_hidden_layers"]
    experts, k = (cfg["moe_num_primary_experts"],
                  cfg["moe_num_active_primary_experts"])
    attn = 2 * m * h * dh + 2 * m * hkv * dh        # q, o; k, v
    expert = 3 * m * cfg["moe_ffn_hidden_size"]
    router = m * experts
    norms = layers * 2 * m + m                      # two a layer, the final
    return {
        "layer": attn + router + experts * expert,
        "attn": attn,
        "expert": expert,
        "router": router,
        "embed": vocab * m,
        "lm_head": m * vocab,
        "norms": norms,
        "matmul": layers * (attn + router + k * expert) + m * vocab,
        "total": (layers * (attn + router + experts * expert)
                  + 2 * vocab * m + norms),
    }


def _pairs_all_layers(cfg: Mapping, tokens: int) -> int:
    return sum(attended_pairs(tokens, window)
               for window in layer_windows(cfg))


def train_flops_per_token(cfg: Mapping, seqlen: int) -> float:
    """6 per matmul weight, and the attention's three passes over the
    pairs each layer attends to. (No cell trains this architecture.)"""
    attn = (12 * cfg["num_attention_heads"] * head_dim(cfg)
            * _pairs_all_layers(cfg, seqlen) / seqlen)
    return 6 * param_counts(cfg)["matmul"] + attn


def flash_prefill_flops(cfg: Mapping, tokens: int) -> int:
    """The attention kernels' work in a prefill of ``tokens``, all
    layers: two matmuls over the pairs each layer attends to, a window
    layer's cut at its lower bound."""
    return (4 * cfg["num_attention_heads"] * head_dim(cfg)
            * _pairs_all_layers(cfg, tokens))


def flash_prefill_bytes(cfg: Mapping, tokens: int) -> int:
    """HBM traffic the forward kernel cannot avoid: q, k, v in and o
    out, once a layer."""
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return (cfg["num_hidden_layers"] * (2 * h + 2 * hkv) * tokens
            * head_dim(cfg) * _BYTES[cfg["dtype"]])


def flash_streamed_flops(cfg: Mapping, tokens: int) -> int:
    """The streamed forward's work in a prefill of ``tokens``, all
    layers: the same pairs as the resident form's, so a window layer's
    from its window (at 16,384 tokens 58.7 M pairs where the full
    triangle has 134.2 M) and not from the bucket the prompt is padded
    to."""
    return flash_prefill_flops(cfg, tokens)


def flash_streamed_bytes(cfg: Mapping, tokens: int) -> int:
    """What no forward can avoid: q, k, v in and o out, once a layer.
    The streamed form reads a head's K and V once for every query block
    that attends to them, which is its price and no part of the least."""
    return flash_prefill_bytes(cfg, tokens)


def flash_train_flops(cfg: Mapping, batch: int, seqlen: int) -> int:
    """Forward and backward: three times the forward's."""
    return 3 * batch * flash_prefill_flops(cfg, seqlen)


def flash_train_bytes(cfg: Mapping, batch: int, seqlen: int) -> int:
    return 3 * batch * flash_prefill_bytes(cfg, seqlen)


def kv_row_bytes(cfg: Mapping) -> int:
    """One token's key and value in ONE layer."""
    return (2 * cfg["num_key_value_heads"] * head_dim(cfg)
            * _BYTES[cfg["dtype"]])


def kv_bytes_per_token(cfg: Mapping) -> int:
    """The FULL-ATTENTION figure: a token kept by every layer. A window
    layer stops keeping it ``sliding_window_size`` later."""
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
    """Every weight outside the experts once (the router's columns in
    float32), the ``pairs_reached`` (layer, expert) pairs that were
    given a token once each, the rows read, one embedding row a
    sequence."""
    counts = param_counts(cfg)
    size = _BYTES[cfg["dtype"]]
    layers = cfg["num_hidden_layers"]
    experts = layers * cfg["moe_num_primary_experts"] * counts["expert"]
    routers = layers * counts["router"]
    weights = (counts["total"] - counts["embed"] - experts - routers
               + pairs_reached * counts["expert"])
    rows = sequences * cfg["hidden_size"] * size
    return (weights * size + routers * 4 + rows_read * kv_row_bytes(cfg)
            + rows)


def experts_reached_even(cfg: Mapping, sequences: float) -> float:
    """Experts of a layer that ``sequences`` tokens reach when the
    router is even: E (1 - (1 - k/E) ** sequences)."""
    experts, k = (cfg["moe_num_primary_experts"],
                  cfg["moe_num_active_primary_experts"])
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
        cfg["num_hidden_layers"] * experts_reached_even(cfg, sequences))


def moe_matmul_flops(cfg: Mapping, assignments: float) -> float:
    """The three expert matmuls of ``assignments`` (token, expert)
    pairs: gate, up and down, each hidden x an expert's width."""
    return (2 * 3 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"]
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
