"""Ring attention: exact attention over sequence shards with the KV blocks
rotating around the ICI ring (`ppermute`), flash-style online softmax so
memory stays O(seq_local).

This is net-new capability vs the reference (SURVEY §2.3: no sequence /
context parallelism anywhere in ant-ray; its long-context story is
delegated to vLLM).  Design follows the blockwise-parallel / ring attention
formulation: each step attends the local Q block against the currently
held KV block while the next KV block is already in flight around the
ring — XLA overlaps the ppermute with the matmuls.

Two entry points:
* :func:`ring_attention_kernel` — per-device code, call inside an existing
  ``shard_map`` (what the model layer uses).
* :func:`ring_attention` — standalone wrapper that shard_maps the kernel
  over a mesh for direct use / testing.
"""

from __future__ import annotations

import functools

from ant_ray_tpu._private.jax_utils import import_jax


def ring_attention_kernel(q, k, v, *, axis_name: str, axis_size: int,
                          causal: bool = True, scale: float | None = None):
    """Exact ring attention for one device's shard.

    Args:
      q: (batch, q_len_local, num_heads, head_dim)
      k, v: (batch, kv_len_local, num_kv_heads, head_dim)
      axis_name: mesh axis the sequence is sharded over.
      axis_size: static size of that axis (number of ring stations).
      causal: apply causal masking using *global* positions.
      scale: softmax scale; default 1/sqrt(head_dim).

    Returns (batch, q_len_local, num_heads, head_dim), dtype of q.
    """
    jax = import_jax()
    import jax.numpy as jnp  # noqa: PLC0415
    from jax import lax  # noqa: PLC0415

    batch, q_len, num_heads, head_dim = q.shape
    kv_len = k.shape[1]
    num_kv_heads = k.shape[2]
    if num_heads % num_kv_heads != 0:
        raise ValueError(f"heads {num_heads} not divisible by kv heads "
                         f"{num_kv_heads}")
    groups = num_heads // num_kv_heads
    scale = scale if scale is not None else head_dim ** -0.5

    my_idx = lax.axis_index(axis_name)
    q_positions = my_idx * q_len + jnp.arange(q_len)          # global q pos

    q32 = q.astype(jnp.float32) * scale

    def attend_block(carry, step):
        o_acc, l_acc, m_acc, k_cur, v_cur = carry
        kv_block = (my_idx - step) % axis_size
        kv_positions = kv_block * kv_len + jnp.arange(kv_len)

        # scores: (batch, heads, q_len, kv_len)
        k_rep = jnp.repeat(k_cur.astype(jnp.float32), groups, axis=2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q32, k_rep)
        if causal:
            mask = kv_positions[None, :] > q_positions[:, None]
            scores = jnp.where(mask[None, None], -jnp.inf, scores)

        block_max = jnp.max(scores, axis=-1)                  # (b,h,q)
        m_new = jnp.maximum(m_acc, block_max)
        # All -inf rows (nothing attendable yet) stay neutral.
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(scores - m_safe[..., None])
        p = jnp.where(jnp.isneginf(scores), 0.0, p)
        correction = jnp.where(
            jnp.isneginf(m_acc), 0.0, jnp.exp(m_acc - m_safe))

        v_rep = jnp.repeat(v_cur.astype(jnp.float32), groups, axis=2)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p, v_rep)
        o_acc = o_acc * correction.transpose(0, 2, 1)[..., None] + pv
        l_acc = l_acc * correction + jnp.sum(p, axis=-1)

        # Rotate KV one station around the ring (overlapped by XLA with
        # the next step's compute).
        perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        return (o_acc, l_acc, m_new, k_next, v_next), None

    # Derive accumulators from q so they carry q's varying-axes type under
    # shard_map (plain zeros are "unvarying" and fail the scan carry check).
    o0 = jnp.zeros_like(q32)
    l0 = jnp.swapaxes(q32[..., 0] * 0.0, 1, 2)               # (b, h, q)
    m0 = l0 - jnp.inf
    (o, l, _m, _k, _v), _ = lax.scan(
        attend_block, (o0, l0, m0, k, v), jnp.arange(axis_size))

    l = jnp.where(l == 0.0, 1.0, l)                            # fully-masked rows
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_attention(q, k, v, *, mesh, axis_name: str = "sp",
                   causal: bool = True, scale: float | None = None,
                   batch_axes=("dp", "fsdp"), head_axis: str | None = "tp"):
    """Standalone sharded ring attention over global arrays.

    q/k/v: (batch, seq, heads, head_dim) jax arrays (or numpy); sequence
    sharded over ``axis_name``, batch over ``batch_axes``, heads over
    ``head_axis``.
    """
    jax = import_jax()
    from jax import shard_map  # noqa: PLC0415
    from jax.sharding import PartitionSpec as P  # noqa: PLC0415

    axis_size = mesh.shape[axis_name]
    spec = P(batch_axes, axis_name, head_axis, None)
    kernel = functools.partial(
        ring_attention_kernel, axis_name=axis_name, axis_size=axis_size,
        causal=causal, scale=scale)
    fn = shard_map(kernel, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec)
    return jax.jit(fn)(q, k, v)


def reference_attention(q, k, v, causal: bool = True,
                        scale: float | None = None):
    """Plain full attention (testing oracle for the parallel variants)."""
    import jax.numpy as jnp  # noqa: PLC0415

    batch, q_len, num_heads, head_dim = q.shape
    groups = num_heads // k.shape[2]
    scale = scale if scale is not None else head_dim ** -0.5
    k = jnp.repeat(k.astype(jnp.float32), groups, axis=2)
    v = jnp.repeat(v.astype(jnp.float32), groups, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale, k)
    if causal:
        q_pos = jnp.arange(q_len)
        mask = q_pos[None, :, None] < jnp.arange(k.shape[1])[None, None, :]
        scores = jnp.where(mask[:, None], -jnp.inf, scores)
    weights = jnp.exp(
        scores - jnp.max(scores, axis=-1, keepdims=True))
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    out = jnp.einsum("bhqk,bkhd->bqhd", weights, v)
    return out.astype(q.dtype)
