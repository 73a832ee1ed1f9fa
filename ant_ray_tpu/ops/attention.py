"""Attention implementations and the dispatch layer.

* :func:`blockwise_attention` — pure-JAX flash-style attention: a
  ``lax.scan`` over KV blocks with online softmax, O(seq · block) memory,
  fully differentiable (JAX derives the backward through the scan, and
  ``jax.checkpoint`` on the block body keeps the residuals bounded).  This
  is the training default: static shapes, MXU-shaped matmuls, no custom
  VJP to maintain.
* pallas flash kernels (ops/pallas/flash_attention.py) — the TPU path,
  wired as custom_vjp over the forward and backward kernels.
* :func:`attention` — dispatcher.  ``impl="auto"`` is the pallas kernel
  on a TPU and blockwise on the CPU (tests); on a TPU a shape the kernel
  cannot take RAISES unless the caller named ``blockwise`` — the kernel
  is never swapped out silently.  Ring attention (parallel/ring.py)
  takes over when the sequence axis is sharded.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30  # avoids -inf NaN pitfalls in fully-masked blocks


def _repeat_kv(k, groups: int):
    return jnp.repeat(k, groups, axis=2) if groups > 1 else k


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "block_k", "window"))
def blockwise_attention(q, k, v, *, causal: bool = True,
                        scale: float | None = None, block_k: int = 512,
                        window: int = 0):
    """Flash-style attention in pure JAX.

    q: (batch, q_len, heads, dim); k/v: (batch, kv_len, kv_heads, dim).
    Memory is O(q_len · block_k) per head instead of O(q_len · kv_len).
    ``window`` > 0 (causal only): query ``t`` sees key ``s`` iff
    0 <= t - s < window; every block is still computed (skipping the
    blocks behind the window is the kernels' to do).
    """
    batch, q_len, num_heads, head_dim = q.shape
    kv_len, num_kv_heads = k.shape[1], k.shape[2]
    groups = num_heads // num_kv_heads
    scale = scale if scale is not None else head_dim ** -0.5
    block_k = min(block_k, kv_len)
    if kv_len % block_k != 0:
        raise ValueError(f"kv_len {kv_len} % block_k {block_k} != 0")
    num_blocks = kv_len // block_k

    # Matmul inputs stay in the model dtype (bf16 on TPU) with fp32
    # accumulation — fp32 inputs would cut the MXU rate severalfold.
    qt = q.transpose(0, 2, 1, 3)                                 # b h q d
    kt = _repeat_kv(k, groups).transpose(0, 2, 1, 3)
    vt = _repeat_kv(v, groups).transpose(0, 2, 1, 3)
    k_blocks = kt.reshape(batch, num_heads, num_blocks, block_k, head_dim)
    v_blocks = vt.reshape(batch, num_heads, num_blocks, block_k, head_dim)

    q_pos = jnp.arange(q_len)

    @jax.checkpoint
    def body(carry, blk):
        o, l, m = carry
        k_b, v_b, blk_idx = blk
        scores = jnp.einsum("bhqd,bhkd->bhqk", qt, k_b,
                            preferred_element_type=jnp.float32) * scale
        if causal:
            kv_pos = blk_idx * block_k + jnp.arange(block_k)
            mask = kv_pos[None, :] > q_pos[:, None]
            if window:
                mask |= q_pos[:, None] - kv_pos[None, :] >= window
            scores = jnp.where(mask[None, None], NEG_INF, scores)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
        p = jnp.exp(scores - m_new[..., None])
        corr = jnp.exp(m - m_new)
        o = o * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(qt.dtype), v_b,
            preferred_element_type=jnp.float32)
        l = l * corr + jnp.sum(p, axis=-1)
        return (o, l, m_new), None

    o0 = jnp.zeros((batch, num_heads, q_len, head_dim), jnp.float32)
    l0 = jnp.zeros((batch, num_heads, q_len), jnp.float32)
    m0 = jnp.full((batch, num_heads, q_len), NEG_INF, jnp.float32)
    (o, l, _m), _ = lax.scan(
        body, (o0, l0, m0),
        (k_blocks.transpose(2, 0, 1, 3, 4),
         v_blocks.transpose(2, 0, 1, 3, 4),
         jnp.arange(num_blocks)))
    l = jnp.where(l == 0.0, 1.0, l)
    out = (o / l[..., None]).transpose(0, 2, 1, 3)
    return out.astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, scale, interpret):
    from ant_ray_tpu.ops.pallas.flash_attention import flash_attention_forward  # noqa: PLC0415

    return flash_attention_forward(q, k, v, causal=causal, scale=scale,
                                   interpret=interpret)


def _flash_fwd(q, k, v, causal, scale, interpret):
    from ant_ray_tpu.ops.pallas.flash_attention import flash_attention_fwd_lse  # noqa: PLC0415

    from jax.ad_checkpoint import checkpoint_name  # noqa: PLC0415

    out, lse = flash_attention_fwd_lse(q, k, v, causal=causal, scale=scale,
                                       interpret=interpret)
    # Named so remat policies can keep the attention output + softmax
    # stats without saving (or recomputing) anything inside the kernel:
    # saveable_attention_policy() below matches these names.
    out = checkpoint_name(out, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, interpret, residuals, g):
    from ant_ray_tpu.ops.pallas.flash_attention import flash_attention_backward  # noqa: PLC0415

    q, k, v, out, lse = residuals
    return flash_attention_backward(q, k, v, out, lse, g, causal=causal,
                                    scale=scale, interpret=interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def saveable_attention_policy():
    """Remat policy: save matmul outputs AND the flash kernel's named
    residuals (attention output + logsumexp), so the backward pass never
    re-runs the attention forward.  Combine with jax.checkpoint."""
    cp = jax.checkpoint_policies
    return cp.save_from_both_policies(
        cp.dots_saveable,
        cp.save_only_these_names("attn_out", "attn_lse"))


def kernel_fits(q_shape, k_shape) -> bool:
    """Whether the pallas kernels tile (batch, seq, heads, dim) shapes."""
    return (q_shape[1] % 128 == 0 and k_shape[1] % 128 == 0
            and q_shape[-1] in (64, 128, 256))


def attention(q, k, v, *, causal: bool = True, scale: float | None = None,
              impl: str = "auto", mesh=None, q_spec=None, kv_spec=None,
              window: int = 0):
    """Dispatch: 'pallas' | 'blockwise' | 'reference' | 'auto'.
    A sliding ``window`` is computed by 'blockwise' alone, which the
    caller has to name.

    Under a ``mesh`` (q/k/v sharded by ``q_spec``/``kv_spec`` over batch
    and heads, sequence whole) the pallas kernel runs per shard inside a
    ``shard_map``: a Mosaic kernel is not partitioned automatically, and
    attention is independent per batch row and head group."""
    on_tpu = jax.default_backend() == "tpu"
    if window and not (impl == "blockwise" and causal):
        raise ValueError(
            f"attention: a sliding window is computed by the causal "
            f"blockwise path only, not by impl={impl!r}")
    if impl == "auto":
        if on_tpu and not kernel_fits(q.shape, k.shape):
            raise ValueError(
                f"attention: q {q.shape} / k {k.shape} cannot take the "
                "pallas flash kernel (sequence lengths must be multiples "
                "of 128, head_dim 64/128/256); on a TPU the kernel is "
                "not swapped out silently — pass impl='blockwise' for "
                "this shape")
        impl = "pallas" if on_tpu else "blockwise"
    if impl == "pallas":
        # Off the TPU (tests) the kernels run in the pallas interpreter.
        def kernel(q, k, v):
            return _flash(q, k, v, causal, scale, not on_tpu)

        if mesh is not None:
            kernel = jax.shard_map(
                kernel, mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec),
                out_specs=q_spec, check_vma=False)
        return kernel(q, k, v)
    if impl == "blockwise":
        return blockwise_attention(q, k, v, causal=causal, scale=scale,
                                   window=window)
    if impl == "reference":
        from ant_ray_tpu.parallel.ring import reference_attention  # noqa: PLC0415

        return reference_attention(q, k, v, causal=causal, scale=scale)
    raise ValueError(f"unknown attention impl {impl!r}")
