"""MiniCPM-SALA's operations and bytes from shapes; never imports jax.

The stack is two kinds of layer, ``mixer_types`` says which is which
(``mixers``: the layers this file keeps, ``layers_kept`` of the published
list): ``"minicpm4"``, grouped-query attention that past ``dense_len``
attends over ``topk`` selected blocks a KV head (k, v and a mean key a
page are kept), and ``"lightning-attn"``, a linear attention of
``lightning_nh`` heads whose state ``[heads, d, d]`` float32 a slot is
all that is kept. Both have an output gate of hidden x hidden and the
same SiLU-gated FFN.

At the published widths (hidden 4096, 32 x 128 heads on 2 KV heads,
lightning 32 x 128, FFN 16384, vocabulary 73,448): a lightning layer is
285.2 M parameters, a ``minicpm4`` layer 253.8 M, the cut of 12 layers (9
and 3) 3,328 M beside 601.7 M of embedding and head: 7.86 GB in bfloat16
(tests/bench_harness/test_benchmark_minicpm_sala.py pins these).
"""

from __future__ import annotations

from typing import Dict, List, Mapping

from .flops import _BYTES, head_dim  # noqa: F401  (part of the interface)

SPARSE, LINEAR = "minicpm4", "lightning-attn"
_STATE_BYTES = 4    # the lightning state is float32 whatever the dtype


def mixers(cfg: Mapping) -> List[str]:
    """The kind of each layer held here: ``num_hidden_layers`` entries of
    the published ``mixer_types`` from ``layers_kept[0]`` on."""
    first, last = cfg["layers_kept"]
    kinds = cfg["mixer_types"][first:last + 1]
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - {SPARSE, LINEAR}:
        raise ValueError(
            f"layers_kept {cfg['layers_kept']} of mixer_types gives {kinds}: "
            f"{cfg['num_hidden_layers']} layers, each {SPARSE} or {LINEAR}")
    return kinds


def sparse(cfg: Mapping) -> Mapping:
    """The block selection's sizes (the file's ``assumed.sparse_config``)."""
    return cfg["assumed"]["sparse_config"]


def n_layers(cfg: Mapping, kind: str) -> int:
    return mixers(cfg).count(kind)


def param_counts(cfg: Mapping) -> Dict[str, int]:
    """Parameters by where they are used. ``sparse_layer`` and
    ``linear_layer`` are a layer whole (its matrices and norms);
    ``matmul`` every weight a token is multiplied by."""
    m, f, vocab = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    h, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 head_dim(cfg))
    hl, dl = cfg["lightning_nh"], cfg["lightning_head_dim"]
    ffn = 3 * m * f
    # q, o and the output gate; k and v on the KV heads.
    sparse_matmul = 3 * m * h * d + 2 * m * hkv * d + ffn
    # q, k, v, o and the output gate, a key and value head a query head.
    linear_matmul = (3 * m * hl * dl + 2 * m * cfg["lightning_nkv"] * dl
                     + ffn)
    sparse_norms, linear_norms = 2 * m + 2 * d, 2 * m + 3 * dl
    ns, nl = n_layers(cfg, SPARSE), n_layers(cfg, LINEAR)
    matmul = ns * sparse_matmul + nl * linear_matmul + m * vocab
    norms = ns * sparse_norms + nl * linear_norms + m
    return {
        "sparse_layer": sparse_matmul + sparse_norms,
        "linear_layer": linear_matmul + linear_norms,
        "layer": (ns * (sparse_matmul + sparse_norms)
                  + nl * (linear_matmul + linear_norms))
        // cfg["num_hidden_layers"],
        "embed": vocab * m,
        "lm_head": m * vocab,
        "norms": norms,
        "matmul": matmul,
        "total": matmul + norms + vocab * m,
    }


def kv_row_bytes(cfg: Mapping) -> int:
    """One token's key and value in ONE ``minicpm4`` layer."""
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * _BYTES[cfg["dtype"]]


def kv_bytes_per_token(cfg: Mapping) -> int:
    """What a cached token holds: k and v in the ``minicpm4`` layers and
    its share of a page's mean key; a lightning layer holds nothing a
    token."""
    page = sparse(cfg)["kernel_stride"]
    return n_layers(cfg, SPARSE) * (
        kv_row_bytes(cfg) + mean_row_bytes(cfg) // page)


def mean_row_bytes(cfg: Mapping) -> int:
    """A page's mean key, every KV head's, in one ``minicpm4`` layer."""
    return cfg["num_key_value_heads"] * head_dim(cfg) * _BYTES[cfg["dtype"]]


def linear_slot_bytes(cfg: Mapping) -> int:
    """A slot's state in ONE lightning layer."""
    return cfg["lightning_nh"] * cfg["lightning_head_dim"] ** 2 * _STATE_BYTES


def selected_tokens(cfg: Mapping, context: float) -> float:
    """Tokens one KV head attends over for a token at position
    ``context``: everything before ``dense_len``, ``topk`` blocks after."""
    s = sparse(cfg)
    if context < s["dense_len"]:
        return context + 1
    return min(context + 1, s["topk"] * s["block_size"])


# ---- the decode step ------------------------------------------------------


def block_walk_bytes(cfg: Mapping, pages: float, pages_held: float) -> float:
    """What the selection and the walk under it must move for ``pages``
    selected (page, layer) pairs of ``pages_held`` held (each summed over
    sequences and ``minicpm4`` layers): the selected pages' k and v, and a
    mean key for every page held (the scores read them all)."""
    page = sparse(cfg)["kernel_stride"]
    return (pages * page * kv_row_bytes(cfg)
            + pages_held * mean_row_bytes(cfg))


def block_walk_flops(cfg: Mapping, pages: float, pages_held: float) -> float:
    """Scores and values over the selected tokens, every query head, and
    the scores of every compressed key."""
    page = sparse(cfg)["kernel_stride"]
    width = cfg["num_attention_heads"] * head_dim(cfg)
    return 4 * pages * page * width + 2 * pages_held * width


def linear_step_bytes(cfg: Mapping, slot_layers: float) -> float:
    """Each of ``slot_layers`` states read once and written once."""
    return 2 * slot_layers * linear_slot_bytes(cfg)


def linear_step_flops(cfg: Mapping, slot_layers: float) -> float:
    """Decay, the write ``k v^T`` and the read-out of every state."""
    return 5 * slot_layers * cfg["lightning_nh"] * cfg["lightning_head_dim"] ** 2


def decode_step_flops_blocks(cfg: Mapping, sequences: float, pages: float,
                             pages_held: float, slot_layers: float) -> float:
    return (2 * param_counts(cfg)["matmul"] * sequences
            + block_walk_flops(cfg, pages, pages_held)
            + linear_step_flops(cfg, slot_layers))


def decode_step_bytes_blocks(cfg: Mapping, sequences: float, pages: float,
                             pages_held: float, slot_layers: float) -> float:
    """Every weight once, the SELECTED pages and the page means of the
    ``minicpm4`` layers, every lightning state read and written, one
    embedding row a sequence: whatever implements them."""
    counts = param_counts(cfg)
    weights = (counts["matmul"] + counts["norms"]) * _BYTES[cfg["dtype"]]
    rows = sequences * cfg["hidden_size"] * _BYTES[cfg["dtype"]]
    return (weights + rows + block_walk_bytes(cfg, pages, pages_held)
            + linear_step_bytes(cfg, slot_layers))


def _step_work(cfg: Mapping, sequences: float, context_tokens: float):
    """(selected pages, pages held, states) of a decode step whose
    ``sequences`` hold ``context_tokens`` between them, each taken at
    the mean context."""
    page = sparse(cfg)["kernel_stride"]
    ns = n_layers(cfg, SPARSE)
    mean = context_tokens / sequences if sequences else 0.0
    pages = ns * sequences * selected_tokens(cfg, mean) / page
    return (pages, ns * context_tokens / page,
            sequences * n_layers(cfg, LINEAR))


def decode_step_flops(cfg: Mapping, sequences: float,
                      context_tokens: float) -> float:
    return decode_step_flops_blocks(
        cfg, sequences, *_step_work(cfg, sequences, context_tokens))


def decode_step_bytes(cfg: Mapping, sequences: float,
                      context_tokens: float) -> float:
    """The interface's: the SELECTED pages, the page means and the
    states read and written, at the sequences' mean context."""
    return decode_step_bytes_blocks(
        cfg, sequences, *_step_work(cfg, sequences, context_tokens))


# ---- a prefill's kernels --------------------------------------------------


def block_prefill_flops(cfg: Mapping, tokens: int) -> int:
    """The restricted flash attention in the ``minicpm4`` layers of one
    prefill: two matmuls over the pairs a query attends to, causal up to
    ``dense_len`` and ``topk`` blocks after."""
    s = sparse(cfg)
    dense = min(tokens, s["dense_len"])
    pairs = dense * (dense + 1) // 2 + max(0, tokens - dense) * min(
        s["topk"] * s["block_size"], tokens)
    return (4 * n_layers(cfg, SPARSE) * cfg["num_attention_heads"]
            * head_dim(cfg) * pairs)


def block_prefill_bytes(cfg: Mapping, tokens: int) -> int:
    """q, k, v in and o out, once a ``minicpm4`` layer."""
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return (n_layers(cfg, SPARSE) * (2 * h + 2 * hkv) * tokens
            * head_dim(cfg) * _BYTES[cfg["dtype"]])


def linear_prefill_flops(cfg: Mapping, tokens: int, chunk: int = 256) -> int:
    """The chunked scan in the lightning layers of one prefill: inside a
    chunk the masked ``Q K^T`` and its product with V, between chunks
    the state read and updated."""
    hl, d = cfg["lightning_nh"], cfg["lightning_head_dim"]
    return n_layers(cfg, LINEAR) * hl * tokens * (4 * chunk * d + 4 * d * d)


def linear_prefill_bytes(cfg: Mapping, tokens: int) -> int:
    """q, k, v in and o out, once a lightning layer, and the state out."""
    hl, d = cfg["lightning_nh"], cfg["lightning_head_dim"]
    return n_layers(cfg, LINEAR) * (
        4 * hl * tokens * d * _BYTES[cfg["dtype"]] + hl * d * d * _STATE_BYTES)


def prefill_flops(cfg: Mapping, tokens: int) -> float:
    counts = param_counts(cfg)
    return (2 * (counts["matmul"] - counts["lm_head"]) * tokens
            + 2 * counts["lm_head"] + block_prefill_flops(cfg, tokens)
            + linear_prefill_flops(cfg, tokens))


# ---- training: no cell trains this architecture ---------------------------


def train_flops_per_token(cfg: Mapping, seqlen: int) -> float:
    attn = 6 * n_layers(cfg, SPARSE) * seqlen * (
        cfg["num_attention_heads"] * head_dim(cfg))
    return 6 * param_counts(cfg)["matmul"] + attn


def flash_train_flops(cfg: Mapping, batch: int, seqlen: int) -> int:
    return 3 * batch * block_prefill_flops(cfg, seqlen)


def flash_train_bytes(cfg: Mapping, batch: int, seqlen: int) -> int:
    return 3 * batch * block_prefill_bytes(cfg, seqlen)
