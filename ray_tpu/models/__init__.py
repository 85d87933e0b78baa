"""ray_tpu.models: TPU-first model zoo for the benchmark configs
(BASELINE.json): the Llama-3 family (a mixture of experts via n_experts,
QK-norm via qk_norm: OLMoE's block) and ResNet/CIFAR.

The Llama family has ONE transformer block, ``llama.block``, and ONE KV
cache, ``generation.PagedKVCache``. Training (``llama.hidden_forward``),
prefill (``generation.paged_prefill``) and decode
(``generation.paged_decode``) each call that block with their own
``attend``; which causal self-attention runs is ``llama.causal_attention``'s
choice and which decode attention ``ops/paged_attention.py``'s."""

from .llama import (  # noqa: F401
    LlamaConfig,
    causal_lm_loss,
    forward,
    init_params,
    num_params,
    param_logical_axes,
)
from .resnet import ResNet, resnet18, resnet50  # noqa: F401
