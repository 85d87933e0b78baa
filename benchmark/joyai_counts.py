"""JoyAI-LLM-Flash's (DeepSeek-V3's keys') operations and bytes from
shapes; never imports jax.

Every layer attends latently: q through a bottleneck of ``q_lora_rank``,
k and v from one latent of ``kv_lora_rank`` a token and one rotary key of
``qk_rope_head_dim`` shared by all heads. The first
``first_k_dense_replace`` layers have a dense FFN of
``intermediate_size``, the others ``n_routed_experts`` experts of
``moe_intermediate_size`` (a token is multiplied by
``num_experts_per_tok`` of them, the router and the shared one).

``head_dim`` here, and the program config's ``dh``, name the width of a
head's q and k, ``qk_nope_head_dim + qk_rope_head_dim`` = 192 (the
file's ``qk_head_dim``); the source's own ``head_dim`` key, 64, is the
rotary width and nothing here reads it under that name. A head's v is
``v_head_dim`` = 128.

What is cached is the latent and the rotary key, 576 values a token a
layer: ``kv_bytes_per_token`` and ``latent_walk_bytes`` count those,
whatever a pool pads a row to (the engine's ``stats()`` says what a row
really holds). The decode step's attention is counted absorbed
(``latent_walk_flops``: every head's query against the 576 and its
probabilities against the latent's 512), the prefill's rebuilt (a head's
192-wide q.k and 128-wide p.v over the causal pairs).
"""

from __future__ import annotations

from typing import Dict, Mapping

from .flops import _BYTES


def head_dim(cfg: Mapping) -> int:
    """The q.k width of a head: the unrotated and the rotated part."""
    return cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]


def latent_row_values(cfg: Mapping) -> int:
    """Values cached a token a layer: the latent and the rotary key."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def _expert_layers(cfg: Mapping) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def param_counts(cfg: Mapping) -> Dict[str, int]:
    """``matmul`` is what one token is multiplied by: every layer's
    attention (both up-projections: absorbed or rebuilt, a token meets
    each once), the dense layers' FFN, and in an expert layer the router,
    ``num_experts_per_tok`` experts and the shared one; then the head.
    ``layer`` is an expert layer whole, ``dense_layer`` a dense one."""
    m, vocab, h = (cfg["hidden_size"], cfg["vocab_size"],
                   cfg["num_attention_heads"])
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    experts, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    attn = (m * rq + rq * h * head_dim(cfg)              # q_a, q_b
            + m * latent_row_values(cfg)                 # kv_a
            + rkv * h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * m)                 # kv_b, o
    expert = 3 * m * cfg["moe_intermediate_size"]
    shared = cfg["n_shared_experts"] * expert
    router = m * experts
    dense_layer = attn + 3 * m * cfg["intermediate_size"]
    layer = attn + router + shared + experts * expert
    sparse = layers - dense
    # Two norms of hidden width and the two latent norms a layer, the
    # final norm, and an expert layer's selection bias.
    norms = layers * (2 * m + rq + rkv) + m + sparse * experts
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


def _causal_pairs(tokens: int) -> int:
    return tokens * (tokens + 1) // 2


def flash_prefill_flops(cfg: Mapping, tokens: int) -> int:
    """The prefill's attention, rebuilt: a head's q.k over ``head_dim``
    and p.v over ``v_head_dim``, over the causal pairs, every layer. A
    kernel that pads v to q's width does no more work by this count."""
    return (2 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * (head_dim(cfg) + cfg["v_head_dim"]) * _causal_pairs(tokens))


def flash_prefill_bytes(cfg: Mapping, tokens: int) -> int:
    """HBM traffic no forward kernel can avoid: q, a head's own part of
    k, the one rotary key, v in and o out, once a layer."""
    h = cfg["num_attention_heads"]
    values = (h * (head_dim(cfg) + cfg["qk_nope_head_dim"]
                   + 2 * cfg["v_head_dim"]) + cfg["qk_rope_head_dim"])
    return (cfg["num_hidden_layers"] * tokens * values
            * _BYTES[cfg["dtype"]])


def train_flops_per_token(cfg: Mapping, seqlen: int) -> float:
    """6 per matmul weight, and the attention's three passes over the
    causal pairs. (No cell trains this architecture.)"""
    return (6 * param_counts(cfg)["matmul"]
            + 3 * flash_prefill_flops(cfg, seqlen) / seqlen)


def flash_train_flops(cfg: Mapping, batch: int, seqlen: int) -> int:
    """Forward and backward: three times the forward's."""
    return 3 * batch * flash_prefill_flops(cfg, seqlen)


def flash_train_bytes(cfg: Mapping, batch: int, seqlen: int) -> int:
    """Forward as ``flash_prefill_bytes``; backward twice that again."""
    return 3 * batch * flash_prefill_bytes(cfg, seqlen)


def latent_row_bytes(cfg: Mapping) -> int:
    """One token's latent and rotary key in ONE layer, unpadded."""
    return latent_row_values(cfg) * _BYTES[cfg["dtype"]]


def kv_bytes_per_token(cfg: Mapping) -> int:
    return cfg["num_hidden_layers"] * latent_row_bytes(cfg)


def latent_walk_flops(cfg: Mapping, rows: float) -> float:
    """The absorbed decode attention over ``rows`` cached rows (summed
    over sequences and layers): every head's query against a row's
    latent and rotary key, and its probability against the latent."""
    return (2 * cfg["num_attention_heads"]
            * (latent_row_values(cfg) + cfg["kv_lora_rank"]) * rows)


def latent_walk_bytes(cfg: Mapping, rows: float) -> float:
    """Each row read once, for scores and values both."""
    return rows * latent_row_bytes(cfg)


def decode_step_flops_rows(cfg: Mapping, sequences: float,
                           rows_read: float) -> float:
    """One token for each of ``sequences``; ``rows_read`` cached rows
    attended to, summed over sequences AND layers (the engine's
    ``decode_kv_rows_read`` a step)."""
    return (2 * param_counts(cfg)["matmul"] * sequences
            + latent_walk_flops(cfg, rows_read))


def decode_step_bytes_rows(cfg: Mapping, sequences: float, rows_read: float,
                           pairs_reached: float) -> float:
    """Every weight outside the routed experts once, the
    ``pairs_reached`` (layer, expert) pairs that were given a token
    once each, the rows read, one embedding row a sequence."""
    counts = param_counts(cfg)
    size = _BYTES[cfg["dtype"]]
    routed = _expert_layers(cfg) * cfg["n_routed_experts"] * counts["expert"]
    weights = (counts["total"] - counts["embed"] - routed
               + pairs_reached * counts["expert"])
    rows = sequences * cfg["hidden_size"] * size
    return weights * size + latent_walk_bytes(cfg, rows_read) + rows


def experts_reached_even(cfg: Mapping, sequences: float) -> float:
    """Experts of a layer that ``sequences`` tokens reach when the
    router is even: E (1 - (1 - k/E) ** sequences)."""
    experts, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
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
