"""The plain reference of Command A+ (Cohere, ``model_type``
``cohere2_moe``; language model only), written from its published
``config.json``: a decoder whose block has ONE LayerNorm (no bias) and
runs attention and feed-forward side by side on the normed input,

    h = LN(x) = (x - mean(x)) / sqrt(var(x) + eps) * w
    x <- x + Attention_l(h) + FFN(h)

whose layers come in two kinds (``layer_types``): a "sliding_attention"
layer rotates the whole head (``rope_gptj``: pairs (2j, 2j + 1), theta
50,000) and lets query ``t`` see key ``s`` iff 0 <= t - s < window; a
"full_attention" layer has no positional embedding at all and is causal
over the whole context.  Both are grouped-query, softmax(q k^T /
sqrt(head_dim)) v, with a head width the config STATES (heads *
head_dim is not the hidden size).  The feed-forward is

    s = sigmoid(h W_r)                                (over ALL experts)
    g_e = s_e / (sum over the k largest s + 1e-20)
    y = sum over the k experts e of largest s_e, e HELD:  g_e SwiGLU_e(h)
        + 1/n * sum over the n shared experts j:  SwiGLU_j(h)

and the logits are ``LN(x) E^T`` with the tied embedding ``E``.

Straightforward ``jax.numpy`` in float32 at the highest matmul
precision: the window is a mask over the full score matrix, no cache,
no ring, no batching, no kernels; every held expert is computed on
every token and combined under a gate map that is zero outside a
token's k experts — no sort, no grouped product.  It imports nothing of
``ant_ray_tpu``; the embedding lookup and the expert loop are
``dense_decoder.py``'s and ``axk1_decoder.py``'s.

Departures from the published description, each on purpose:

* it is given a SHARE of the model, as ``axk1_decoder.py`` is: the
  expert matrices hold only the experts from ``first_expert`` on, the
  router scores all of them, what an absent expert would add is left
  out and the partial sum goes on to the next layer;
* "average" (``shared_expert_combination_strategy``) is read as the
  mean of the shared experts' outputs, added to the routed sum; the
  shared experts arrive as ONE SwiGLU as wide as all of them together
  (columns j * f to (j + 1) * f are expert j's), whose output is their
  sum;
* weights are whatever the caller passes, cast to float32 product by
  product, the experts one by one, and attention is computed in blocks
  of ``QUERY_BLOCK`` query rows (every row still against the full score
  row over all keys): a layer of the benchmark's cut is 4.6 GB in
  float32 and 128 heads of 5,128 x 5,128 scores 13.5 GB;
* the harness compiles ``block`` with only the dense reference's four
  static names, so everything else arrives traced: a layer's KIND
  travels with its weights (``windowed``, 1.0 or 0.0) and both kinds
  are one program — the rotation and the window are selected by it;
  widths are read off the weights' shapes; the k largest are found by
  rank, as ``axk1_decoder.gate_map`` does;
* matrices are stored ``(in, out)`` and applied as ``x @ w``.

A layer is a dict: ``attn_norm`` (d,), ``wq`` (d, h * hd), ``wk`` /
``wv`` (d, kvh * hd), ``wo`` (h * hd, d), ``windowed`` (), ``router``
(d, E), ``w_gate`` / ``w_up`` (held, d, f), ``w_down`` (held, f, d),
``shared_gate`` / ``shared_up`` (d, n * f), ``shared_down`` (n * f, d).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.axk1_decoder import held_experts, swiglu
from chipbench.reference.dense_decoder import embed_tokens

_HIGHEST = "highest"
QUERY_BLOCK = 128


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def layer_norm(x, weight, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    variance = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(variance + eps) * _f32(weight)


def rotary(x, positions, theta):
    """x: (seq, heads, head_dim); turns the pairs (2j, 2j + 1)."""
    head_dim = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                         / head_dim)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def attention(q, k, v, positions, window):
    """Grouped-query attention under a mask over the full score matrix:
    query ``t`` sees key ``s`` iff 0 <= t - s < ``window`` (a window no
    position reaches is plain causal).  q: (seq, heads, hd); k,
    v: (seq, kv_heads, hd).  Query head i reads key/value head
    i // (heads / kv_heads).  Computed ``QUERY_BLOCK`` query rows at a
    time, one block after the other."""
    seq, heads, head_dim = q.shape
    kv_heads = k.shape[1]
    blocks = -(-seq // QUERY_BLOCK)
    pad = blocks * QUERY_BLOCK - seq
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    t = jnp.pad(positions, (0, pad), constant_values=seq - 1)

    def rows(block):
        qb, tb = block             # (QUERY_BLOCK, kv_heads, group, hd)
        scores = jnp.einsum("qkgd,skd->kgqs", qb, k, precision=_HIGHEST)
        scores = scores / jnp.sqrt(jnp.float32(head_dim))
        behind = tb[:, None] - positions[None, :]
        seen = (behind >= 0) & (behind < window)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", probs, v, precision=_HIGHEST)

    out = jax.lax.map(rows, (
        q.reshape(blocks, QUERY_BLOCK, kv_heads, heads // kv_heads,
                  head_dim),
        t.reshape(blocks, QUERY_BLOCK)))
    return out.reshape(blocks * QUERY_BLOCK, heads, head_dim)[:seq]


def gate_map(h, router, experts_per_token):
    """(seq, d) -> (seq, E): a token's sigmoid score at its
    ``experts_per_token`` best experts over their sum, zero elsewhere."""
    scores = jax.nn.sigmoid(h @ _f32(router))
    rank = jnp.argsort(jnp.argsort(-scores, axis=-1), axis=-1)
    kept = jnp.where(rank < experts_per_token, scores, 0.0)
    return kept / (jnp.sum(kept, axis=-1, keepdims=True) + 1e-20)


def block(layer: dict, x, positions, *, n_heads: int, n_kv_heads: int,
          rope_theta: float, norm_eps: float, window=4096,
          experts_per_token=8, n_shared_experts=4, first_expert=0):
    """One decoder layer on one sequence.  x: (seq, d) float32."""
    seq = x.shape[0]
    head_dim = layer["wq"].shape[1] // n_heads
    windowed = layer["windowed"] > 0
    with jax.default_matmul_precision(_HIGHEST):
        h = layer_norm(x, layer["attn_norm"], norm_eps)
        q = (h @ _f32(layer["wq"])).reshape(seq, n_heads, head_dim)
        k = (h @ _f32(layer["wk"])).reshape(seq, n_kv_heads, head_dim)
        v = (h @ _f32(layer["wv"])).reshape(seq, n_kv_heads, head_dim)
        # a window layer rotates the whole head, a full layer nothing
        q = jnp.where(windowed, rotary(q, positions, rope_theta), q)
        k = jnp.where(windowed, rotary(k, positions, rope_theta), k)
        a = attention(q, k, v, positions, jnp.where(
            windowed, window, jnp.iinfo(jnp.int32).max)).reshape(seq, -1)
        gates = gate_map(h, layer["router"], experts_per_token)
        routed = held_experts(layer, h, gates, first_expert)
        shared = swiglu(h, layer["shared_gate"], layer["shared_up"],
                        layer["shared_down"]) / n_shared_experts
        return x + a @ _f32(layer["wo"]) + routed + shared


def hidden(embed, layers, tokens, *, block_fn=block, **dims):
    """The last layer's output (seq, d) for ONE sequence; ``layers`` a
    list of layer dicts or a ``(layer(i), n)`` pair."""
    if isinstance(layers, tuple):
        get, n = layers
    else:
        get, n = layers.__getitem__, len(layers)
    positions = jnp.arange(tokens.shape[0])
    x = embed_tokens(embed, tokens)
    for i in range(n):
        x = block_fn(get(i), x, positions, **dims)
    return x


def logits_of(norm_f, head, x, norm_eps: float):
    with jax.default_matmul_precision(_HIGHEST):
        return layer_norm(x, norm_f, norm_eps) @ _f32(head)


def forward(embed, layers, norm_f, head, tokens, *, block_fn=block, **dims):
    """Logits (seq, vocab) of ONE sequence of token ids."""
    x = hidden(embed, layers, tokens, block_fn=block_fn, **dims)
    return logits_of(norm_f, head, x, dims["norm_eps"])


def dims_of(spec: dict) -> dict:
    """What ``block`` needs of a configuration file: the published
    numbers, and the first expert of the share the file's ``deployment``
    states."""
    return {"n_heads": spec["num_attention_heads"],
            "n_kv_heads": spec["num_key_value_heads"],
            "rope_theta": float(spec["rope_theta"]),
            "norm_eps": float(spec["layer_norm_eps"]),
            "window": spec["sliding_window"],
            "experts_per_token": spec["num_experts_per_tok"],
            "n_shared_experts": spec["num_shared_experts"],
            "first_expert": spec["deployment"]["experts_held"][0]}
