"""Rotary position embeddings (Llama-style, half-split layout)."""

from __future__ import annotations

import jax.numpy as jnp


def rope_frequencies(head_dim: int, max_seq: int, theta: float = 500000.0,
                     dtype=jnp.float32):
    """Precompute cos/sin tables: (max_seq, head_dim // 2)."""
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_seq, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    return jnp.cos(freqs).astype(dtype), jnp.sin(freqs).astype(dtype)


def apply_rope(x, cos, sin, positions=None):
    """x: (..., seq, heads, head_dim); cos/sin: (max_seq, head_dim//2);
    positions: int32 of x's leading shape (..., seq) — (batch, seq) in
    training, (rows,) for a serving step's rows — defaults to arange."""
    if positions is None:
        positions = slice(x.shape[-3])
    cos_sel = cos[positions][..., None, :]        # (..., s, 1, d/2)
    sin_sel = sin[positions][..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate(
        [x1 * cos_sel - x2 * sin_sel, x2 * cos_sel + x1 * sin_sel], axis=-1)
    return out.astype(x.dtype)
