"""Core transformer primitives: RMSNorm and rotary embeddings.

No reference analogue (the reference has no compute path of its own —
SURVEY.md §0); conventions follow the HF Llama formulation (split-half
rotate, norm in fp32) so HF checkpoints load bit-compatibly.
"""

from __future__ import annotations

import dataclasses
import math

import jax.numpy as jnp


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    """RMSNorm computed in fp32, cast back to the input dtype."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jnp.reciprocal(jnp.sqrt(var + eps))
    return (x * weight.astype(jnp.float32)).astype(dtype)


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """RoPE frequency rescaling (HF `rope_scaling` dict). Two rules:
    `rope_type` "llama3" (the NTK band rule: factor, low/high_freq_factor)
    and "yarn" (DeepSeek-V2's: factor, beta_fast/beta_slow ramp over the
    pair index, mscale / mscale_all_dim for the softmax scale and the
    cos/sin multiplier, `yarn_factors`). Both read
    original_max_position_embeddings."""

    factor: float = 8.0
    low_freq_factor: float = 1.0
    high_freq_factor: float = 4.0
    original_max_position_embeddings: int = 8192
    rope_type: str = "llama3"
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def _yarn_inv_freq(inv_freq: jnp.ndarray, head_dim: int, theta: float,
                   s: RopeScaling) -> jnp.ndarray:
    """YaRN: pairs that turn more than beta_fast times over the original
    context keep their frequency, those that turn less than beta_slow
    times are divided by `factor`, a linear ramp over the pair index
    between."""
    def dim(turns: float) -> float:
        return (head_dim * math.log(
            s.original_max_position_embeddings / (turns * 2 * math.pi))
        ) / (2 * math.log(theta))

    low = max(math.floor(dim(s.beta_fast)), 0)
    high = min(math.ceil(dim(s.beta_slow)), head_dim - 1)
    j = jnp.arange(head_dim // 2, dtype=jnp.float32)
    ramp = jnp.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
    return inv_freq / s.factor * ramp + inv_freq * (1.0 - ramp)


def yarn_factors(scaling: RopeScaling | None) -> tuple[float, float]:
    """(softmax-scale multiplier, cos/sin multiplier) of a YaRN scaling;
    (1, 1) for any other. With m(x) = 0.1 x ln(factor) + 1: the scale is
    multiplied by m(mscale_all_dim)^2 and cos/sin by m(mscale) /
    m(mscale_all_dim) (1 for DeepSeek-V2-Lite, whose two are equal)."""
    if scaling is None or scaling.rope_type != "yarn" or scaling.factor <= 1:
        return 1.0, 1.0

    def m(x: float) -> float:
        return 0.1 * x * math.log(scaling.factor) + 1.0

    all_dim = m(scaling.mscale_all_dim)
    return all_dim * all_dim, m(scaling.mscale) / all_dim


def precompute_rope(
    head_dim: int,
    theta: float = 10000.0,
    scaling: RopeScaling | None = None,
) -> jnp.ndarray:
    """Inverse frequencies [head_dim//2], fp32, rescaled by `scaling`'s
    rule (llama3 or yarn) where given."""
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    if scaling is not None and scaling.rope_type == "yarn":
        return _yarn_inv_freq(inv_freq, head_dim, theta, scaling)
    if scaling is not None:
        low_wavelen = scaling.original_max_position_embeddings / scaling.low_freq_factor
        high_wavelen = scaling.original_max_position_embeddings / scaling.high_freq_factor
        wavelen = 2.0 * math.pi / inv_freq
        # smooth interpolation between scaled and unscaled bands
        smooth = (scaling.original_max_position_embeddings / wavelen - scaling.low_freq_factor) / (
            scaling.high_freq_factor - scaling.low_freq_factor
        )
        smooth = jnp.clip(smooth, 0.0, 1.0)
        scaled = inv_freq / scaling.factor
        inv_freq = jnp.where(
            wavelen > low_wavelen,
            scaled,
            jnp.where(wavelen < high_wavelen, inv_freq, (1.0 - smooth) * scaled + smooth * inv_freq),
        )
    return inv_freq


def apply_rope(
    x: jnp.ndarray,
    positions: jnp.ndarray,
    inv_freq: jnp.ndarray,
) -> jnp.ndarray:
    """Rotate `x` [..., T, H, D] by position-dependent angles.

    Uses the HF split-half convention: the first D/2 lanes pair with the
    last D/2 (`rotate_half`), NOT interleaved pairs — this is what HF Llama
    checkpoints are trained with.
    `positions`: [..., T] int32 absolute positions.
    """
    dtype = x.dtype
    angles = positions[..., :, None].astype(jnp.float32) * inv_freq  # [..., T, D/2]
    cos = jnp.cos(angles)[..., :, None, :]  # [..., T, 1, D/2]
    sin = jnp.sin(angles)[..., :, None, :]
    x = x.astype(jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(dtype)


def layer_norm(
    x: jnp.ndarray, weight: jnp.ndarray, bias: jnp.ndarray, eps: float = 1e-12
) -> jnp.ndarray:
    """Classic LayerNorm (mean-centered, affine w/ bias) in fp32 — the
    BERT-family norm; decoder families use rms_norm."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * jnp.reciprocal(jnp.sqrt(var + eps))
    return (x * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)
