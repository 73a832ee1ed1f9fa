"""The plain reference: a pre-norm decoder with RMSNorm, rotary position
embeddings, grouped-query attention and a SwiGLU feed-forward, as
InternLM2 (arXiv 2403.17297, section 2.2) and Mistral 7B (arXiv
2310.06825, section 2) publish it.

Straightforward ``jax.numpy`` in float32 at the highest matmul
precision: no kernels, no cache, no scan, no batching tricks.  It
imports nothing from ``ant_ray_tpu.models`` or ``ant_ray_tpu.ops``.

Departures from the published models, each on purpose:

* weights are whatever the caller passes (random from a seed in the
  benchmark), cast to float32 leaf by leaf as they are used, so a
  float32 copy of a 7B-width model never exists at once;
* InternLM2's dynamic-NTK rope scaling is left out: it changes nothing
  below ``max_position_embeddings`` and no cell goes there;
* rotary embedding in the "rotate half" layout of the published
  Hugging Face implementations of both models (first half of a head
  paired with the second half), not the interleaved layout of the
  original RoPE paper;
* matrices are stored ``(in, out)`` and applied as ``x @ w``.

A layer is a dict: ``attn_norm`` (d,), ``wq`` (d, h*hd), ``wk`` / ``wv``
(d, kvh*hd), ``wo`` (h*hd, d), ``mlp_norm`` (d,), ``w_gate`` / ``w_up``
(d, f), ``w_down`` (f, d).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HIGHEST = "highest"


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def rms_norm(x, weight, eps: float):
    variance = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(variance + eps) * _f32(weight)


def rotary(x, positions, theta: float):
    """x: (seq, heads, head_dim); positions: (seq,)."""
    head_dim = x.shape[-1]
    inv_freq = 1.0 / (theta ** (
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(angles)[:, None, :]
    sin = jnp.sin(angles)[:, None, :]
    first, second = x[..., :head_dim // 2], x[..., head_dim // 2:]
    return jnp.concatenate(
        [first * cos - second * sin, second * cos + first * sin], axis=-1)


def attention(q, k, v):
    """Causal grouped-query attention.  q: (seq, heads, hd); k, v:
    (seq, kv_heads, hd).  Query head i reads key/value head
    i // (heads / kv_heads)."""
    seq, heads, head_dim = q.shape
    group = heads // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=_HIGHEST)
    scores = scores / jnp.sqrt(jnp.float32(head_dim))
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("hqk,khd->qhd", probs, v, precision=_HIGHEST)


def block(layer: dict, x, positions, *, n_heads: int, n_kv_heads: int,
          rope_theta: float, norm_eps: float):
    """One decoder layer on one sequence.  x: (seq, d) float32."""
    seq, dim = x.shape
    head_dim = dim // n_heads
    with jax.default_matmul_precision(_HIGHEST):
        h = rms_norm(x, layer["attn_norm"], norm_eps)
        q = (h @ _f32(layer["wq"])).reshape(seq, n_heads, head_dim)
        k = (h @ _f32(layer["wk"])).reshape(seq, n_kv_heads, head_dim)
        v = (h @ _f32(layer["wv"])).reshape(seq, n_kv_heads, head_dim)
        q = rotary(q, positions, rope_theta)
        k = rotary(k, positions, rope_theta)
        a = attention(q, k, v).reshape(seq, n_heads * head_dim)
        x = x + a @ _f32(layer["wo"])
        h = rms_norm(x, layer["mlp_norm"], norm_eps)
        gated = jax.nn.silu(h @ _f32(layer["w_gate"])) * (
            h @ _f32(layer["w_up"]))
        return x + gated @ _f32(layer["w_down"])


def embed_tokens(embed, tokens):
    """(seq,) token ids -> (seq, d) float32: a lookup, not a matmul."""
    return _f32(jnp.take(embed, tokens, axis=0))


def logits_of(norm_f, head, x, norm_eps: float):
    with jax.default_matmul_precision(_HIGHEST):
        return rms_norm(x, norm_f, norm_eps) @ _f32(head)


def head_loss(norm_f, head, x, targets, norm_eps: float):
    """Final norm, output head and the mean next-token cross entropy of
    one sequence.  x: (seq, d); targets: (seq,)."""
    logp = jax.nn.log_softmax(logits_of(norm_f, head, x, norm_eps), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], axis=-1))


def hidden(embed, layers, tokens, *, block_fn=block, **dims):
    """The last layer's output (seq, d) for ONE sequence.  ``layers`` is
    a list of layer dicts, or a ``(layer(i), n)`` pair that yields them
    one at a time.  ``block_fn`` lets a caller pass ``jax.jit(block)``:
    the same mathematics, compiled once for all layers."""
    if isinstance(layers, tuple):
        get, n = layers
    else:
        get, n = layers.__getitem__, len(layers)
    positions = jnp.arange(tokens.shape[0])
    x = embed_tokens(embed, tokens)
    for i in range(n):
        x = block_fn(get(i), x, positions, **dims)
    return x


def forward(embed, layers, norm_f, head, tokens, *, block_fn=block, **dims):
    """Logits (seq, vocab) of ONE sequence of token ids."""
    x = hidden(embed, layers, tokens, block_fn=block_fn, **dims)
    return logits_of(norm_f, head, x, dims["norm_eps"])


def loss(embed, layers, norm_f, head, tokens, **dims):
    """Mean next-token cross entropy over a batch (b, s + 1) of token
    ids, one sequence after the other."""
    total = jnp.float32(0.0)
    for row in tokens:
        x = hidden(embed, layers, row[:-1], **dims)
        total = total + head_loss(norm_f, head, x, row[1:],
                                  dims["norm_eps"])
    return total / tokens.shape[0]


def dims_of(spec: dict) -> dict:
    """The reference's four numbers from a configuration file."""
    return {"n_heads": spec["num_attention_heads"],
            "n_kv_heads": spec["num_key_value_heads"],
            "rope_theta": float(spec["rope_theta"]),
            "norm_eps": float(spec["rms_norm_eps"])}
