"""TPU-native compute primitives.

This package is the compute path the reference outsources to Ollama/llama.cpp
(reference: client/src/services/OllamaService.ts:17-27 — an HTTP adapter to an
external engine; SURVEY.md §0). Everything here is functional JAX: static
shapes, scan-friendly, shardable. Pure-jnp reference implementations live
beside Pallas TPU kernels; the engine picks per-platform.
"""

from gridllm_tpu.ops.layers import (
    apply_rope,
    precompute_rope,
    rms_norm,
    RopeScaling,
)
from gridllm_tpu.ops.kvcache import PagedKVCache
from gridllm_tpu.ops.attention import attention_prefill
from gridllm_tpu.ops.sampling import SamplingParams, sample_tokens

__all__ = [
    "apply_rope",
    "precompute_rope",
    "rms_norm",
    "RopeScaling",
    "PagedKVCache",
    "attention_prefill",
    "SamplingParams",
    "sample_tokens",
]
