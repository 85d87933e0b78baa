"""ray_tpu.models: TPU-first model zoo for the benchmark configs
(BASELINE.json): Llama-3 family (+ a mixture of experts via n_experts, QK-norm via
qk_norm: OLMoE's block), ResNet/CIFAR,
ViT for image pipelines."""

from .llama import (  # noqa: F401
    LlamaConfig,
    causal_lm_loss,
    forward,
    init_params,
    num_params,
    param_logical_axes,
)
from .resnet import ResNet, resnet18, resnet50  # noqa: F401
