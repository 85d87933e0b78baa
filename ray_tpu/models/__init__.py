"""ray_tpu.models: TPU-first model zoo for the benchmark configs
(BASELINE.json): the Llama-3 family (a mixture of experts via n_experts,
QK-norm via qk_norm: OLMoE's block) and ResNet/CIFAR.

The Llama family has ONE transformer block, ``llama.block``, and ONE KV
cache, ``generation.PagedKVCache``. Training (``llama.hidden_forward``),
prefill (``generation.paged_prefill``) and decode
(``generation.paged_decode``) each call that block with their own
``attend``; which causal self-attention runs is ``llama.causal_attention``'s
choice and which decode attention ``ops/paged_attention.py``'s.

A model's layers are an ordered list of runs of alike layers
(``llama.layer_runs``: dense or expert FFN, window or full attention),
one stacked tree and one layer scan a run; Llama, Mistral and OLMoE are
one run. What an architecture states beyond Llama's block (leading
dense layers, a shared expert, the router's scoring, window layers,
per-head QK-norm, an output gate, post-norms) is a ``LlamaConfig`` field
each. A stack of several runs or with window layers is served only:
training it raises by name."""

from .llama import (  # noqa: F401
    LlamaConfig,
    causal_lm_loss,
    forward,
    init_params,
    num_params,
    param_logical_axes,
)
from .resnet import ResNet, resnet18, resnet50  # noqa: F401
