"""GLM-5.2's (``glm_moe_dsa``) operations and bytes from shapes, for ONE
CHIP'S SHARE of a deployment as the configuration file states it; never
imports jax.

Every layer attends latently (as ``joyai_counts``: q through a bottleneck
of ``q_lora_rank``, k and v from one latent of ``kv_lora_rank`` a token
and one rotary key of ``qk_rope_head_dim``) over a SELECTION of at most
``index_topk`` cached positions. A layer that ``indexer_types`` calls
"full" makes the selection with an indexer of its own
(``index_n_heads`` queries of ``index_head_dim`` from the q bottleneck,
one key a token, the heads' weights) and caches its key; a "shared" one
takes the last selection made. ``mlp_layer_types`` says which layers
have a dense FFN of ``intermediate_size`` and which the experts: the
router at its published width (``router_experts``), of which
``n_routed_experts`` are held here, ``num_experts_per_tok`` chosen a
token over all of them, and the shared expert.

The file keeps both lists whole as published and holds
``num_hidden_layers`` consecutive layers from ``layers_from``;
``layer_kinds`` reads the lists there.

``head_dim`` here, and the program config's ``dh``, name the width of a
head's q and k, ``qk_nope_head_dim + qk_rope_head_dim`` = 256 (the
file's ``qk_head_dim``); the source's own ``head_dim`` key, 192, is the
unrotated part and nothing here reads it under that name.

What a roofline counts is the same whatever implements it: the index
scoring reads each cached key of an indexing layer once; the attention
reads the SELECTED rows once (``min(index_topk, context)`` a sequence a
layer), so a walk that reads every row reads low by this count.
"""

from __future__ import annotations

from typing import Dict, Mapping, Tuple

from .flops import _BYTES


def head_dim(cfg: Mapping) -> int:
    """The q.k width of a head: the unrotated and the rotated part."""
    return cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]


def latent_row_values(cfg: Mapping) -> int:
    """Values cached a token a layer: the latent and the rotary key."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def layer_kinds(cfg: Mapping) -> Tuple[Tuple[str, ...], Tuple[str, ...]]:
    """(``mlp_layer_types``, ``indexer_types``) of the layers held."""
    first = cfg.get("layers_from", 0)
    held = slice(first, first + cfg["num_hidden_layers"])
    return (tuple(cfg["mlp_layer_types"][held]),
            tuple(cfg["indexer_types"][held]))


def router_experts(cfg: Mapping) -> int:
    """Experts the router scores: the published count where the file
    holds a share of them."""
    cut = cfg.get("reduced", {}).get("n_routed_experts")
    return cut["published"] if cut else cfg["n_routed_experts"]


def _layers(cfg: Mapping) -> Dict[str, int]:
    mlp, indexers = layer_kinds(cfg)
    return {"dense": mlp.count("dense"), "sparse": mlp.count("sparse"),
            "indexing": indexers.count("full"), "all": len(mlp)}


def param_counts(cfg: Mapping) -> Dict[str, int]:
    """``matmul`` is what one token is multiplied by HERE: every layer's
    attention, an indexing layer's indexer, the dense layers' FFN, in an
    expert layer the router, the shared expert and the token's share of
    the held experts (``num_experts_per_tok`` x held / scored of them when
    the router is even); then the head's held columns. ``layer`` is an
    expert layer's share whole, ``dense_layer`` a dense one; both without
    an indexer (``indexer``)."""
    m, vocab, h = (cfg["hidden_size"], cfg["vocab_size"],
                   cfg["num_attention_heads"])
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    n = _layers(cfg)
    held, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    attn = (m * rq + rq * h * head_dim(cfg)              # q_a, q_b
            + m * latent_row_values(cfg)                 # kv_a
            + rkv * h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * m)                 # kv_b, o
    indexer = (rq * cfg["index_n_heads"] * cfg["index_head_dim"]
               + m * cfg["index_head_dim"] + m * cfg["index_n_heads"])
    expert = 3 * m * cfg["moe_intermediate_size"]
    shared = cfg["n_shared_experts"] * expert
    router = m * router_experts(cfg)
    dense_layer = attn + 3 * m * cfg["intermediate_size"]
    layer = attn + router + shared + held * expert
    # Two norms of hidden width and the two latent norms a layer, the
    # final norm, an expert layer's selection bias, an indexer's
    # LayerNorm (weight and bias).
    norms = (n["all"] * (2 * m + rq + rkv) + m
             + n["sparse"] * router_experts(cfg)
             + n["indexing"] * 2 * cfg["index_head_dim"])
    return {
        "layer": layer,
        "dense_layer": dense_layer,
        "attn": attn,
        "indexer": indexer,
        "expert": expert,
        "router": router,
        "embed": vocab * m,
        "lm_head": m * vocab,
        "norms": norms,
        "matmul": (n["dense"] * dense_layer + n["indexing"] * indexer
                   + n["sparse"] * (attn + router + shared
                                    + k * held / router_experts(cfg) * expert)
                   + m * vocab),
        "total": (n["dense"] * dense_layer + n["sparse"] * layer
                  + n["indexing"] * indexer + 2 * vocab * m + norms),
    }


def latent_row_bytes(cfg: Mapping) -> int:
    """One token's latent and rotary key in ONE layer, unpadded."""
    return latent_row_values(cfg) * _BYTES[cfg["dtype"]]


def index_row_bytes(cfg: Mapping) -> int:
    """One token's indexer key in ONE indexing layer."""
    return cfg["index_head_dim"] * _BYTES[cfg["dtype"]]


def kv_bytes_per_token(cfg: Mapping) -> int:
    n = _layers(cfg)
    return (n["all"] * latent_row_bytes(cfg)
            + n["indexing"] * index_row_bytes(cfg))


def pool_bytes_per_token(cfg: Mapping) -> int:
    """What a token holds over both pools as the engine lays them: a
    latent row on whole lane tiles (the rotary key on a tile of its
    own), every layer, and an indexer key an indexing layer."""
    size = _BYTES[cfg["dtype"]]
    row = cfg["kv_lora_rank"] + -(-cfg["qk_rope_head_dim"] // 128) * 128
    n = _layers(cfg)
    return n["all"] * row * size + n["indexing"] * index_row_bytes(cfg)


# ------------------------------------------------ the selection's work

def index_score_flops(cfg: Mapping, keys: float) -> float:
    """Scoring ``keys`` cached keys (summed over sequences and indexing
    layers) for one token each: every index head's query against the
    key."""
    return 2 * cfg["index_n_heads"] * cfg["index_head_dim"] * keys


def index_score_bytes(cfg: Mapping, keys: float) -> float:
    """Each key read once."""
    return keys * index_row_bytes(cfg)


def sparse_walk_flops(cfg: Mapping, rows: float) -> float:
    """The absorbed attention over ``rows`` SELECTED rows (summed over
    sequences and layers): every head's query against a row's latent and
    rotary key, and its probability against the latent."""
    return (2 * cfg["num_attention_heads"]
            * (latent_row_values(cfg) + cfg["kv_lora_rank"]) * rows)


def sparse_walk_bytes(cfg: Mapping, rows: float) -> float:
    """Each selected row read once, for scores and values both."""
    return rows * latent_row_bytes(cfg)


def restricted_pairs(cfg: Mapping, tokens: int) -> int:
    """(query, key) pairs a prompt of ``tokens`` attends over:
    sum_t min(t + 1, index_topk)."""
    k = cfg["index_topk"]
    if tokens <= k:
        return tokens * (tokens + 1) // 2
    return k * (k + 1) // 2 + (tokens - k) * k


def _causal_pairs(tokens: int) -> int:
    return tokens * (tokens + 1) // 2


def prefill_sparse_flops(cfg: Mapping, tokens: int) -> float:
    """A prefill's selection and restricted attention: in every indexing
    layer each index head's query against every key before it, and in
    every layer a head's q.k over ``head_dim`` and p.v over
    ``v_head_dim`` over the restricted pairs."""
    n = _layers(cfg)
    return (n["indexing"] * index_score_flops(cfg, _causal_pairs(tokens))
            + 2 * n["all"] * cfg["num_attention_heads"]
            * (head_dim(cfg) + cfg["v_head_dim"])
            * restricted_pairs(cfg, tokens))


def prefill_sparse_bytes(cfg: Mapping, tokens: int) -> float:
    """HBM traffic no forward kernel can avoid: q, a head's own part of
    k, the one rotary key, v in and o out, once a layer; an indexing
    layer's index queries and keys once."""
    h, n = cfg["num_attention_heads"], _layers(cfg)
    values = (h * (head_dim(cfg) + cfg["qk_nope_head_dim"]
                   + 2 * cfg["v_head_dim"]) + cfg["qk_rope_head_dim"])
    index = (cfg["index_n_heads"] + 1) * cfg["index_head_dim"]
    return ((n["all"] * values + n["indexing"] * index) * tokens
            * _BYTES[cfg["dtype"]])


# ------------------------------------------------------ the interface

def flash_prefill_flops(cfg: Mapping, tokens: int) -> float:
    return prefill_sparse_flops(cfg, tokens)


def flash_prefill_bytes(cfg: Mapping, tokens: int) -> float:
    return prefill_sparse_bytes(cfg, tokens)


def train_flops_per_token(cfg: Mapping, seqlen: int) -> float:
    """6 per matmul weight, and the attention's three passes. (No cell
    trains this architecture.)"""
    return (6 * param_counts(cfg)["matmul"]
            + 3 * prefill_sparse_flops(cfg, seqlen) / seqlen)


def flash_train_flops(cfg: Mapping, batch: int, seqlen: int) -> float:
    return 3 * batch * prefill_sparse_flops(cfg, seqlen)


def flash_train_bytes(cfg: Mapping, batch: int, seqlen: int) -> float:
    return 3 * batch * prefill_sparse_bytes(cfg, seqlen)


def decode_step_flops_rows(cfg: Mapping, sequences: float,
                           rows_read: float) -> float:
    """One token for each of ``sequences``; ``rows_read`` cached rows
    held by the steps' sequences, summed over sequences AND layers (the
    engine's ``decode_kv_rows_read`` a step): an indexing layer scores
    its share of them, and the attention takes at most ``index_topk`` of
    a sequence's in a layer (counted here as if every context were the
    mean one)."""
    n = _layers(cfg)
    per_layer = rows_read / n["all"]
    selected = n["all"] * min(per_layer, sequences * cfg["index_topk"])
    return (2 * param_counts(cfg)["matmul"] * sequences
            + index_score_flops(cfg, n["indexing"] * per_layer)
            + sparse_walk_flops(cfg, selected))


def decode_step_bytes_rows(cfg: Mapping, sequences: float, rows_read: float,
                           pairs_reached: float) -> float:
    """Every weight outside the held routed experts once, the
    ``pairs_reached`` (layer, held expert) pairs that were given a token
    once each, the indexing layers' keys, the selected rows, one
    embedding row a sequence."""
    counts = param_counts(cfg)
    size = _BYTES[cfg["dtype"]]
    n = _layers(cfg)
    routed = n["sparse"] * cfg["n_routed_experts"] * counts["expert"]
    weights = (counts["total"] - counts["embed"] - routed
               + pairs_reached * counts["expert"])
    per_layer = rows_read / n["all"]
    selected = n["all"] * min(per_layer, sequences * cfg["index_topk"])
    rows = sequences * cfg["hidden_size"] * size
    return (weights * size
            + index_score_bytes(cfg, n["indexing"] * per_layer)
            + sparse_walk_bytes(cfg, selected) + rows)


def experts_reached_even(cfg: Mapping, sequences: float) -> float:
    """HELD experts of a layer that ``sequences`` tokens reach when the
    router is even over all it scores."""
    held, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    return held * (1 - (1 - k / router_experts(cfg)) ** sequences)


def decode_step_flops(cfg: Mapping, sequences: float,
                      context_tokens: float) -> float:
    return decode_step_flops_rows(
        cfg, sequences, cfg["num_hidden_layers"] * context_tokens)


def decode_step_bytes(cfg: Mapping, sequences: float,
                      context_tokens: float) -> float:
    return decode_step_bytes_rows(
        cfg, sequences, cfg["num_hidden_layers"] * context_tokens,
        _layers(cfg)["sparse"] * experts_reached_even(cfg, sequences))


def moe_matmul_flops(cfg: Mapping, assignments: float) -> float:
    """The three routed-expert matmuls of ``assignments`` (token, held
    expert) pairs."""
    return (2 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
            * assignments)


def moe_matmul_bytes(cfg: Mapping, assignments: float,
                     pairs_reached: float) -> float:
    size = _BYTES[cfg["dtype"]]
    weights = pairs_reached * param_counts(cfg)["expert"]
    rows = assignments * 2 * cfg["hidden_size"]
    return (weights + rows) * size
