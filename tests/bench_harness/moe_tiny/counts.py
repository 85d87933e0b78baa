"""The fixture's counts: a token is multiplied by ``num_experts_per_tok``
of a layer's experts and by its router; attention is the dense model's,
so those counts are ``benchmark/flops.py``'s own."""

from benchmark.flops import (  # noqa: F401  (part of the interface)
    _BYTES, flash_train_bytes, flash_train_flops, head_dim,
    kv_bytes_per_token,
)


def param_counts(cfg):
    """``matmul`` is what one token is multiplied by (its experts, the
    router); ``total`` and ``layer`` hold every expert."""
    m, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  head_dim(cfg))
    layers, vocab = cfg["num_hidden_layers"], cfg["vocab_size"]
    experts, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    attn = m * h * dh + 2 * m * hkv * dh + h * dh * m
    expert, router = 3 * m * f, m * experts
    layer = attn + router + experts * expert
    norms = layers * 2 * m + m
    return {
        "layer": layer,
        "expert": expert,
        "embed": vocab * m,
        "lm_head": m * vocab,
        "norms": norms,
        "matmul": layers * (attn + router + k * expert) + m * vocab,
        "total": layers * layer + 2 * vocab * m + norms,
    }


def train_flops_per_token(cfg, seqlen):
    attn = 6 * cfg["num_hidden_layers"] * seqlen * (
        cfg["num_attention_heads"] * head_dim(cfg))
    return 6 * param_counts(cfg)["matmul"] + attn


def decode_step_flops(cfg, sequences, context_tokens):
    attn = 4 * cfg["num_hidden_layers"] * context_tokens * (
        cfg["num_attention_heads"] * head_dim(cfg))
    return 2 * param_counts(cfg)["matmul"] * sequences + attn


def decode_step_bytes(cfg, sequences, context_tokens):
    """Every weight outside the experts once, and of each layer's
    experts those that ``sequences`` tokens reach when the router is
    even: E (1 - (1 - k/E) ** sequences). A real configuration reads the
    experts reached from a counter; the fixture has none."""
    counts = param_counts(cfg)
    experts, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    reached = experts * (1 - (1 - k / experts) ** sequences)
    layers = cfg["num_hidden_layers"]
    weights = (counts["total"] - counts["embed"]
               - layers * (experts - reached) * counts["expert"])
    size = _BYTES[cfg["dtype"]]
    rows = sequences * cfg["hidden_size"] * size
    return weights * size + context_tokens * kv_bytes_per_token(cfg) + rows
