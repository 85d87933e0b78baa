"""Kimi-Linear's (``kimi_linear``) operations and bytes from shapes, for
ONE CHIP'S SHARE of a deployment as the configuration file states it;
never imports jax.

Two kinds of layer, ``linear_attn_config.kda_layers`` and
``.full_attn_layers`` (counted from 1; ``layer_kinds`` reads them):

a KDA ("delta") layer: q, k, v and o of ``num_heads`` heads of
``head_dim`` (``linear_attn_config``'s), a causal convolution of
``short_conv_kernel_size`` taps a channel on q, k and v, the decay gate
and the output gate each through a bottleneck of the head's width, a
write strength a head; what it keeps a sequence is a state
[heads, d, d] float32 and the convolution's last taps - 1 input rows,
whatever the context;

an MLA ("latent") layer as ``joyai_counts``, without a q bottleneck
(``q_lora_rank`` null) and unrotated: one row a token, the latent of
``kv_lora_rank`` and ``qk_rope_head_dim`` values all heads share.

The FFN: the first ``first_k_dense_replace`` layers dense, the others
the router at its published width (``router_experts``), of which
``num_experts`` are held here, ``num_experts_per_token`` chosen a token
over all of them, and the shared expert.

``head_dim`` here, and the program config's ``dh``, name the width of an
MLA head's q and k, 192; the source's own ``head_dim`` 72 is read by
nothing (the file's ``not_read``).
"""

from __future__ import annotations

from typing import Dict, Mapping, Tuple

from .flops import _BYTES

STATE_BYTES = 4   # the delta state is float32
CHUNK = 128       # tokens a chunk of the delta prefill covers


def head_dim(cfg: Mapping) -> int:
    """The q.k width of an MLA head: 128 of its own and 64 shared."""
    return cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]


def layer_kinds(cfg: Mapping) -> Tuple[str, ...]:
    """"delta" or "latent" for each layer held, in order."""
    linear = cfg["linear_attn_config"]
    kda, full = set(linear["kda_layers"]), set(linear["full_attn_layers"])
    layers = range(1, cfg["num_hidden_layers"] + 1)
    if kda & full or any(i not in kda | full for i in layers):
        raise ValueError(
            "linear_attn_config: kda_layers and full_attn_layers must "
            "name each layer once")
    return tuple("delta" if i in kda else "latent" for i in layers)


def router_experts(cfg: Mapping) -> int:
    """Experts the router scores: the published count where the file
    holds a share of them."""
    cut = cfg.get("reduced", {}).get("num_experts")
    return cut["published"] if cut else cfg["num_experts"]


def _layers(cfg: Mapping) -> Dict[str, int]:
    kinds = layer_kinds(cfg)
    dense = cfg["first_k_dense_replace"]
    return {"delta": kinds.count("delta"), "latent": kinds.count("latent"),
            "dense": dense, "sparse": len(kinds) - dense, "all": len(kinds)}


def _delta(cfg: Mapping) -> Tuple[int, int, int]:
    linear = cfg["linear_attn_config"]
    return (linear["num_heads"], linear["head_dim"],
            linear["short_conv_kernel_size"])


def latent_row_values(cfg: Mapping) -> int:
    """Values cached a token an MLA layer: the latent and the shared key."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def param_counts(cfg: Mapping) -> Dict[str, int]:
    """``matmul`` is what one token is multiplied by HERE: every layer's
    attention of its kind, the dense layers' FFN, in an expert layer the
    router, the shared expert and the token's share of the held experts
    (``num_experts_per_token`` x held / scored of them when the router is
    even); then the head's held columns. ``delta_attn`` and
    ``latent_attn`` are one layer's attention of each kind."""
    m, vocab, h = (cfg["hidden_size"], cfg["vocab_size"],
                   cfg["num_attention_heads"])
    hd, d, taps = _delta(cfg)
    n = _layers(cfg)
    held, k = cfg["num_experts"], cfg["num_experts_per_token"]
    rkv = cfg["kv_lora_rank"]
    delta_attn = (4 * m * hd * d                     # q, k, v, o
                  + 2 * (m * d + d * hd * d)         # the two gates
                  + m * hd)                          # beta
    delta_vectors = (3 * hd * d * taps               # the convolution
                     + hd + hd * d + d)              # A_log, dt_bias, o_norm
    latent_attn = (m * h * head_dim(cfg)             # q
                   + m * latent_row_values(cfg)      # kv_a
                   + rkv * h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
                   + h * cfg["v_head_dim"] * m)      # kv_b, o
    expert = 3 * m * cfg["moe_intermediate_size"]
    shared = cfg["num_shared_experts"] * expert
    router = m * router_experts(cfg)
    dense_ffn = 3 * m * cfg["intermediate_size"]
    attn = n["delta"] * delta_attn + n["latent"] * latent_attn
    # Two norms of hidden width a layer, the latent norm, the final norm,
    # an expert layer's selection bias, a delta layer's vectors.
    norms = (n["all"] * 2 * m + n["latent"] * rkv + m
             + n["sparse"] * router_experts(cfg)
             + n["delta"] * delta_vectors)
    return {
        "delta_attn": delta_attn,
        "latent_attn": latent_attn,
        "layer": delta_attn + router + shared + held * expert,
        "expert": expert,
        "router": router,
        "embed": vocab * m,
        "lm_head": m * vocab,
        "norms": norms,
        "matmul": (attn + n["dense"] * dense_ffn
                   + n["sparse"] * (router + shared
                                    + k * held / router_experts(cfg) * expert)
                   + m * vocab),
        "total": (attn + n["dense"] * dense_ffn
                  + n["sparse"] * (router + shared + held * expert)
                  + 2 * vocab * m + norms),
    }


# ------------------------------------------------- what a sequence holds

def delta_slot_bytes(cfg: Mapping) -> int:
    """One sequence's state and convolution history in ONE KDA layer."""
    hd, d, taps = _delta(cfg)
    return (hd * d * d * STATE_BYTES
            + (taps - 1) * 3 * hd * d * _BYTES[cfg["dtype"]])


def latent_row_bytes(cfg: Mapping) -> int:
    """One token's latent and shared key in ONE MLA layer, unpadded."""
    return latent_row_values(cfg) * _BYTES[cfg["dtype"]]


def kv_bytes_per_token(cfg: Mapping) -> int:
    """Only the MLA layers keep anything a token."""
    return _layers(cfg)["latent"] * latent_row_bytes(cfg)


# ------------------------------------------------------ the delta rule

def delta_step_flops(cfg: Mapping, slot_layers: float) -> float:
    """The decode step's delta rule over ``slot_layers`` (sequences x
    KDA layers): a head's decay (d^2), what the state already holds for
    the key (2 d^2), the rank-one write (2 d^2) and the read-out
    (2 d^2)."""
    hd, d, _ = _delta(cfg)
    return slot_layers * hd * 7 * d * d


def delta_step_bytes(cfg: Mapping, slot_layers: float) -> float:
    """Each state read once and written once."""
    hd, d, _ = _delta(cfg)
    return 2 * slot_layers * hd * d * d * STATE_BYTES


def delta_prefill_flops(cfg: Mapping, tokens: int, chunk: int = CHUNK
                        ) -> float:
    """The chunked delta rule of ``tokens`` real tokens, every KDA layer,
    a head a token: against the state its key (W S), its query (Q S) and
    its write (K^T U), 2 d^2 each; inside a chunk the pairs' k.k and q.k
    (2 d a pair each), the solve's two right-hand sides U and W and the
    read-out against U (2 d a pair each), about ``chunk`` / 2 pairs a
    token. The inverse itself (``chunk`` squared a token at most) is not
    counted: a forward substitution needs a quarter of it."""
    hd, d, _ = _delta(cfg)
    pairs = (min(chunk, tokens) + 1) / 2
    per_token = hd * (3 * 2 * d * d + 5 * 2 * d * pairs)
    return _layers(cfg)["delta"] * tokens * per_token


def delta_prefill_bytes(cfg: Mapping, tokens: int) -> float:
    """HBM traffic no chunked kernel can avoid: q, k, v in and o out in
    the model's dtype, the decays (float32 a channel) and the write
    strength in, the final state out; every KDA layer."""
    hd, d, _ = _delta(cfg)
    rows = tokens * hd * (4 * d * _BYTES[cfg["dtype"]]
                          + (d + 1) * STATE_BYTES)
    return _layers(cfg)["delta"] * (rows + hd * d * d * STATE_BYTES)


# -------------------------------------------------- the latent attention

def latent_walk_flops(cfg: Mapping, rows: float) -> float:
    """The absorbed decode attention over ``rows`` cached rows (summed
    over sequences and MLA layers): every head's query against a row's
    latent and shared key, and its probability against the latent."""
    return (2 * cfg["num_attention_heads"]
            * (latent_row_values(cfg) + cfg["kv_lora_rank"]) * rows)


def latent_walk_bytes(cfg: Mapping, rows: float) -> float:
    """Each row read once, for scores and values both."""
    return rows * latent_row_bytes(cfg)


def _causal_pairs(tokens: int) -> int:
    return tokens * (tokens + 1) // 2


def flash_prefill_flops(cfg: Mapping, tokens: int) -> float:
    """The prefill's causal attention, rebuilt, of the MLA layers: a
    head's q.k over ``head_dim`` and p.v over ``v_head_dim``."""
    return (2 * _layers(cfg)["latent"] * cfg["num_attention_heads"]
            * (head_dim(cfg) + cfg["v_head_dim"]) * _causal_pairs(tokens))


def flash_prefill_bytes(cfg: Mapping, tokens: int) -> float:
    """q, a head's own part of k, the one shared key, v in and o out,
    once an MLA layer."""
    h = cfg["num_attention_heads"]
    values = (h * (head_dim(cfg) + cfg["qk_nope_head_dim"]
                   + 2 * cfg["v_head_dim"]) + cfg["qk_rope_head_dim"])
    return (_layers(cfg)["latent"] * tokens * values
            * _BYTES[cfg["dtype"]])


# ------------------------------------------------------- the whole step

def decode_step_flops_hybrid(cfg: Mapping, sequences: float,
                             rows_read: float, slot_layers: float) -> float:
    """One token for each of ``sequences``; ``rows_read`` latent rows
    attended to (the engine's ``decode_kv_rows_read`` a step: sequences
    x MLA layers x context) and ``slot_layers`` states stepped
    (``decode_state_slot_layers`` a step: sequences x KDA layers)."""
    return (2 * param_counts(cfg)["matmul"] * sequences
            + latent_walk_flops(cfg, rows_read)
            + delta_step_flops(cfg, slot_layers))


def decode_step_bytes_hybrid(cfg: Mapping, sequences: float,
                             rows_read: float, slot_layers: float,
                             experts_reached: float) -> float:
    """Every weight outside the held routed experts once, the
    ``experts_reached`` (layer, held expert) pairs that were given a
    token once each, each latent row read once, each state read and
    written, each convolution history read and written, one embedding
    row a sequence."""
    counts = param_counts(cfg)
    size = _BYTES[cfg["dtype"]]
    hd, d, taps = _delta(cfg)
    routed = _layers(cfg)["sparse"] * cfg["num_experts"] * counts["expert"]
    weights = (counts["total"] - counts["embed"] - routed
               + experts_reached * counts["expert"])
    histories = 2 * slot_layers * (taps - 1) * 3 * hd * d * size
    rows = sequences * cfg["hidden_size"] * size
    return (weights * size + latent_walk_bytes(cfg, rows_read)
            + delta_step_bytes(cfg, slot_layers) + histories + rows)


def experts_reached_even(cfg: Mapping, sequences: float) -> float:
    """HELD experts of a layer that ``sequences`` tokens reach when the
    router is even over all it scores."""
    held, k = cfg["num_experts"], cfg["num_experts_per_token"]
    return held * (1 - (1 - k / router_experts(cfg)) ** sequences)


def decode_step_flops(cfg: Mapping, sequences: float,
                      context_tokens: float) -> float:
    """The interface's: every MLA layer attends to the whole context,
    every KDA layer steps every sequence's state."""
    n = _layers(cfg)
    return decode_step_flops_hybrid(
        cfg, sequences, n["latent"] * context_tokens, n["delta"] * sequences)


def decode_step_bytes(cfg: Mapping, sequences: float,
                      context_tokens: float) -> float:
    n = _layers(cfg)
    return decode_step_bytes_hybrid(
        cfg, sequences, n["latent"] * context_tokens, n["delta"] * sequences,
        n["sparse"] * experts_reached_even(cfg, sequences))


def moe_matmul_flops(cfg: Mapping, assignments: float) -> float:
    """The three routed-expert matmuls of ``assignments`` (token, held
    expert) pairs."""
    return (2 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
            * assignments)


def moe_matmul_bytes(cfg: Mapping, assignments: float,
                     pairs_reached: float) -> float:
    """The weights of the ``pairs_reached`` (layer, held expert) pairs
    that were given a token, once each, and a hidden-wide row in and out
    for every assignment."""
    size = _BYTES[cfg["dtype"]]
    weights = pairs_reached * param_counts(cfg)["expert"]
    rows = assignments * 2 * cfg["hidden_size"]
    return (weights + rows) * size


# ------------------------------------------------------ the interface

def train_flops_per_token(cfg: Mapping, seqlen: int) -> float:
    """6 per matmul weight, and three passes of the two attentions. (No
    cell trains this architecture: ``require_uniform`` refuses it.)"""
    return (6 * param_counts(cfg)["matmul"]
            + 3 * (flash_prefill_flops(cfg, seqlen)
                   + delta_prefill_flops(cfg, seqlen)) / seqlen)


def flash_train_flops(cfg: Mapping, batch: int, seqlen: int) -> float:
    return 3 * batch * (flash_prefill_flops(cfg, seqlen)
                        + delta_prefill_flops(cfg, seqlen))


def flash_train_bytes(cfg: Mapping, batch: int, seqlen: int) -> float:
    return 3 * batch * (flash_prefill_bytes(cfg, seqlen)
                        + delta_prefill_bytes(cfg, seqlen))
