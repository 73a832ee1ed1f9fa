"""LayerNorm without bias, beside ``rmsnorm.py``: the mean is taken off
before the variance scales.  Plain jnp, float32 inside."""

from __future__ import annotations

import jax.numpy as jnp


def layernorm(x, weight, eps: float = 1e-5):
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    scale = jnp.reciprocal(
        jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps))
    return (x32 * scale).astype(dtype) * weight
