"""The routed experts' grouped product (models/mixtral.py's third form):
the dispatcher and the jnp reference that is the kernel's oracle
(ops/kernels.py). The kernel is `pallas_kernels.grouped_experts`."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from gridllm_tpu.ops.kvcache import _pallas_mode, record_kernel_path


def grouped_experts_ref(x, gates, touched, wg, wu, wd, layer=None, *,
                        act: str):
    """`pallas_kernels.grouped_experts` in plain jnp: every expert of the
    layer times every row, an expert no live row touched weighted zero.
    Operands as they come, float32 sums, one cast at the end."""
    if wg.ndim == 4:
        layer = 0 if layer is None else layer
        wg, wu, wd = (jax.lax.dynamic_index_in_dim(w, layer, keepdims=False)
                      for w in (wg, wu, wd))
    p = jax.lax.Precision.HIGHEST if x.dtype == jnp.float32 else None
    g = jnp.einsum("te,xef->txf", x, wg, precision=p,
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("te,xef->txf", x, wu, precision=p,
                   preferred_element_type=jnp.float32)
    g = jax.nn.silu(g) if act == "silu" else jnp.maximum(g, 0.0)
    on = (touched > 0).astype(jnp.float32)
    y = g * u * (gates.astype(jnp.float32) * on)[..., None]
    return jnp.einsum("txf,xfe->te", y.astype(wd.dtype), wd, precision=p,
                      preferred_element_type=jnp.float32).astype(x.dtype)


def grouped_experts(x, gates, touched, wg, wu, wd, layer=None, *, act: str,
                    use_pallas: bool | None = None):
    """The touched experts' products of x [T, E] (the kernel has the
    contract), by the kernel where kernels are on and by the reference
    where they are not."""
    use, interpret = _pallas_mode(use_pallas)
    record_kernel_path("grouped_experts", use)
    if not use:
        return grouped_experts_ref(x, gates, touched, wg, wu, wd, layer,
                                   act=act)
    from gridllm_tpu.ops.pallas_kernels import grouped_experts as kernel

    return kernel(x, gates, touched, wg, wu, wd, layer, act=act,
                  interpret=interpret)
