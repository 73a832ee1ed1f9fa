"""Ulysses-style sequence parallelism: all-to-all head scatter.

The sequence-sharded activations are re-sharded so each device holds the
*full* sequence for a *subset of heads* (one `all_to_all` on the sp axis),
attention runs locally per head group, and a second all_to_all restores
sequence sharding.  Complements ring attention: Ulysses moves activations
twice but runs attention unblocked (better for moderate sequence lengths);
ring never materializes the full sequence (better for extreme lengths).

Net-new vs the reference (no sequence parallelism exists there).
"""

from __future__ import annotations

import functools

from ant_ray_tpu._private.jax_utils import import_jax
from ant_ray_tpu.parallel.ring import reference_attention


def ulysses_attention_kernel(q, k, v, *, axis_name: str, axis_size: int,
                             causal: bool = True,
                             scale: float | None = None,
                             attn_fn=None):
    """Per-device Ulysses attention (call inside shard_map).

    q: (batch, seq_local, heads, head_dim); heads must be divisible by
    axis_size.  attn_fn(q, k, v, causal, scale) runs full local attention;
    defaults to the exact reference implementation (swap in a flash
    kernel for production).
    """
    jax = import_jax()
    from jax import lax  # noqa: PLC0415

    attn_fn = attn_fn or (
        lambda q_, k_, v_: reference_attention(q_, k_, v_, causal=causal,
                                               scale=scale))
    num_heads = q.shape[2]
    num_kv_heads = k.shape[2]
    if num_heads % axis_size != 0:
        raise ValueError(
            f"heads {num_heads} not divisible by sp axis {axis_size}")
    if num_kv_heads % axis_size != 0:
        raise ValueError(
            f"kv heads {num_kv_heads} not divisible by sp axis {axis_size}")

    def scatter_heads(x):
        # (b, s_local, h, d) → (b, s_global, h/axis, d)
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def gather_heads(x):
        # (b, s_global, h/axis, d) → (b, s_local, h, d)
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    out = attn_fn(scatter_heads(q), scatter_heads(k), scatter_heads(v))
    return gather_heads(out)


def ulysses_attention(q, k, v, *, mesh, axis_name: str = "sp",
                      causal: bool = True, scale: float | None = None,
                      batch_axes=("dp", "fsdp")):
    """Standalone sharded Ulysses attention over global arrays (heads are
    NOT tp-sharded here: the sp axis claims the head dimension)."""
    jax = import_jax()
    from jax import shard_map  # noqa: PLC0415
    from jax.sharding import PartitionSpec as P  # noqa: PLC0415

    axis_size = mesh.shape[axis_name]
    spec = P(batch_axes, axis_name, None, None)
    kernel = functools.partial(
        ulysses_attention_kernel, axis_name=axis_name, axis_size=axis_size,
        causal=causal, scale=scale)
    fn = shard_map(kernel, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec)
    return jax.jit(fn)(q, k, v)
