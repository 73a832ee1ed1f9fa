"""Rotary position embeddings (Llama-style, half-split layout), plain or
with YaRN's blended frequencies."""

from __future__ import annotations

import dataclasses
import math

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """A published ``rope_scaling`` of type ``yarn`` (arXiv 2309.00071),
    under its published names."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature: 0.1 * mscale * ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, scaling: YarnScaling):
    """(dim // 2,) float32.  Pair ``j`` turns at ``f_j = theta^(-2j/dim)``
    where it completes more than ``beta_fast`` turns over the original
    context, at ``f_j / factor`` where fewer than ``beta_slow``, and at a
    linear blend of the two between those pairs."""
    s = scaling
    f = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)

    def pair_of(turns: float) -> float:
        return dim * math.log(s.original_max_position_embeddings / (
            turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_of(s.beta_fast)), 0)
    high = min(math.ceil(pair_of(s.beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return f / s.factor * ramp + f * (1.0 - ramp)


def rope_frequencies(head_dim: int, max_seq: int, theta: float = 500000.0,
                     dtype=jnp.float32, scaling: YarnScaling | None = None):
    """Precompute cos/sin tables: (max_seq, head_dim // 2).  With
    ``scaling`` the frequencies are YaRN's, and cos and sin carry its
    ``mscale`` over ``mscale_all_dim`` temperatures' ratio."""
    if scaling is None:
        inv_freq, scale = 1.0 / (theta ** (
            jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)), 1.0
    else:
        inv_freq = yarn_inv_freq(head_dim, theta, scaling)
        scale = (yarn_mscale(scaling.factor, scaling.mscale)
                 / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
    t = jnp.arange(max_seq, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    cos, sin = jnp.cos(freqs), jnp.sin(freqs)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    return cos.astype(dtype), sin.astype(dtype)


def half_split_from_interleaved(dim: int):
    """Column order that takes weights published for the interleaved
    rotation (pairs (2j, 2j + 1)) to ``apply_rope``'s half-split layout
    (pairs (j, j + dim/2)): ``w[..., perm]``.  Applied to the queries'
    and the keys' rotary columns alike it leaves every score as it was.
    Its inverse is ``argsort`` of it."""
    return jnp.concatenate([jnp.arange(0, dim, 2), jnp.arange(1, dim, 2)])


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
