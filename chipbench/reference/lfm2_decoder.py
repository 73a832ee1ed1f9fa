"""The plain reference of LFM2-8B-A1B (Liquid AI, ``model_type``
``lfm2_moe``), written from its published ``config.json`` and the
equations its keys name: a pre-norm, sequential decoder,

    x <- x + mix_l(RMSNorm(x))
    x <- x + ffn_l(RMSNorm(x))
    logits = RMSNorm(x) E^T                           (the head is tied)

whose layers come in two kinds (``layer_types``; three ``conv`` layers
to one ``full_attention`` layer).  With ``h = RMSNorm(x)``:

A CONV layer is the gated short convolution: no state matrix, no
decay, nothing of a position,

    [B | C | u] = h W_in                   three times the hidden width
    z_t = sum_{j=0..L-1} w_j * (B * u)_{t-(L-1)+j}   zeros before t = 0
    mix = W_out (C * z)

a depth-wise causal convolution of ``conv_L_cache`` = L taps a channel
with NO bias (``conv_bias`` false) and NO activation; ``*`` is
element-wise.

A FULL_ATTENTION layer is grouped-query softmax attention, causal, with
an RMSNorm over each head's values of q and of k (ONE weight of the
head's width, shared by the heads) BEFORE the rotary embedding, which
turns the whole head ("rotate half" pairs: the first half of a head
with the second), scores times head_dim^-1/2, no bias: ``mix = W_o
attn(rope(norm(h W_q)), rope(norm(h W_k)), h W_v)``.

The feed-forward of the first ``num_dense_layers`` layers is a SwiGLU
``intermediate_size`` wide; of every other layer

    s = sigmoid(h W_r)                                 (over ALL experts)
    picked = the k experts of largest s_e + expert_bias_e
    g_e = s_e / (sum over picked s + 1e-6) * routed_scaling_factor
    y = sum over picked e of g_e SwiGLU_e(h)

``expert_bias`` (``use_expert_bias``) picks and does not weigh; the
gates are the picked experts' scores as they were, divided by their sum
(``norm_topk_prob``); there is no shared expert.

Straightforward ``jax.numpy`` in float32 at the highest matmul
precision.  The convolution goes TOKEN BY TOKEN (``lax.map`` over
positions: each reads its own L gated inputs out of the zero-padded
sequence — no tail is kept, nothing is shifted); no cache, no chunks,
no batching, no grouped product: every expert is computed on every
token, one after the other in a Python loop, under a gate map that is
zero outside a token's k experts.  It imports nothing of
``ant_ray_tpu``; SwiGLU and the experts' sum are ``axk1_decoder.py``'s,
the blocked softmax attention ``granite_hybrid_decoder.py``'s, RMSNorm,
the rotary embedding, the embedding lookup and the head
``dense_decoder.py``'s.

What the published config leaves open, each read as follows (the
configuration file lists them under ``assumed``):

* the head is the embedding, transposed (no ``tie_word_embeddings``
  key; the published parameter count is met with one matrix only);
* a head is ``hidden_size / num_attention_heads`` wide (no key);
* the published modelling code was not at hand: where it differs, the
  equations above are what is run and compared.

Departures from the description above, each on purpose:

* the program divides the picked scores by their sum + 1e-20
  (``models/llama.py`` ``_routed_mlp``), this file by their sum + 1e-6
  as published: four sigmoid scores add up to about 2, so the two
  differ by 5e-7 of a gate;
* weights are whatever the caller passes, cast to float32 product by
  product, the experts one by one, and the softmax attention runs in
  blocks of query rows (each against its full score row);
* the harness compiles ``block`` with the dense reference's four static
  names; a layer's KIND is read off its leaves (a conv layer has
  ``conv_w``, a routed feed-forward a ``router``), so each is a program
  of its own, and widths are read off the weights' shapes;
* matrices are stored ``(in, out)`` and applied as ``x @ w``.

A conv layer is a dict: ``attn_norm`` (d,), ``in_proj`` (d, 3 d),
``conv_w`` (L, d), ``out_proj`` (d, d); an attention layer:
``attn_norm``, ``wq`` (d, h * hd), ``wk`` / ``wv`` (d, kvh * hd), ``wo``
(h * hd, d), ``q_norm`` / ``k_norm`` (hd,); both: ``mlp_norm`` (d,) and
either ``w_gate`` / ``w_up`` (d, f), ``w_down`` (f, d), or ``router``
(d, E), ``router_bias`` (E,), ``w_gate`` / ``w_up`` (E, d, f),
``w_down`` (E, f, d).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.axk1_decoder import held_experts, swiglu
from chipbench.reference.dense_decoder import (
    embed_tokens,
    logits_of,
    rms_norm,
    rotary,
)
from chipbench.reference.granite_hybrid_decoder import attention

_HIGHEST = "highest"
GATE_EPS = 1e-6


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def short_conv(u, w):
    """(seq, channels) under (taps, channels), no bias: ``z_t = sum_j
    w_j * u_{t - (taps - 1) + j}``, zeros before the sequence's start —
    one token after the other, each from its own ``taps`` inputs."""
    taps, seq = w.shape[0], u.shape[0]
    padded = jnp.pad(u, ((taps - 1, 0), (0, 0)))
    w = _f32(w)

    def token(t):
        mine = jax.lax.dynamic_slice_in_dim(padded, t, taps, axis=0)
        return jnp.sum(mine * w, axis=0)

    return jax.lax.map(token, jnp.arange(seq))


def conv_mix(layer: dict, h):
    b, c, u = jnp.split(h @ _f32(layer["in_proj"]), 3, axis=-1)
    return (c * short_conv(b * u, layer["conv_w"])) @ _f32(
        layer["out_proj"])


def softmax_mix(layer: dict, h, positions, n_heads, n_kv_heads,
                rope_theta, norm_eps):
    seq = h.shape[0]
    q = (h @ _f32(layer["wq"])).reshape(seq, n_heads, -1)
    k = (h @ _f32(layer["wk"])).reshape(seq, n_kv_heads, -1)
    v = (h @ _f32(layer["wv"])).reshape(seq, n_kv_heads, -1)
    q = rotary(rms_norm(q, layer["q_norm"], norm_eps), positions,
               rope_theta)
    k = rotary(rms_norm(k, layer["k_norm"], norm_eps), positions,
               rope_theta)
    return attention(q, k, v, q.shape[-1] ** -0.5).reshape(
        seq, -1) @ _f32(layer["wo"])


def gate_map(h, router, expert_bias, experts_per_token,
             routed_scaling_factor):
    """(seq, d) -> (seq, E): the sigmoid scores of the
    ``experts_per_token`` experts of largest score + bias, divided by
    their sum, zero elsewhere."""
    scores = jax.nn.sigmoid(h @ _f32(router))
    picking = scores + _f32(expert_bias)
    rank = jnp.argsort(jnp.argsort(-picking, axis=-1), axis=-1)
    kept = jnp.where(rank < experts_per_token, scores, 0.0)
    return kept / (jnp.sum(kept, axis=-1, keepdims=True) + GATE_EPS) \
        * routed_scaling_factor


def block(layer: dict, x, positions, *, n_heads: int, n_kv_heads: int,
          rope_theta: float, norm_eps: float, experts_per_token=4,
          routed_scaling_factor=1.0):
    """One decoder layer on one sequence.  x: (seq, d) float32."""
    with jax.default_matmul_precision(_HIGHEST):
        h = rms_norm(x, layer["attn_norm"], norm_eps)
        if "conv_w" in layer:
            x = x + conv_mix(layer, h)
        else:
            x = x + softmax_mix(layer, h, positions, n_heads, n_kv_heads,
                                rope_theta, norm_eps)
        h = rms_norm(x, layer["mlp_norm"], norm_eps)
        if "router" not in layer:
            return x + swiglu(h, layer["w_gate"], layer["w_up"],
                              layer["w_down"])
        gates = gate_map(h, layer["router"], layer["router_bias"],
                         experts_per_token, routed_scaling_factor)
        return x + held_experts(layer, h, gates, 0)


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


def forward(embed, layers, norm_f, head, tokens, *, block_fn=block, **dims):
    """Logits (seq, vocab) of ONE sequence of token ids; ``head`` is
    the embedding, transposed (the head is tied)."""
    x = hidden(embed, layers, tokens, block_fn=block_fn, **dims)
    return logits_of(norm_f, head, x, dims["norm_eps"])


def dims_of(spec: dict) -> dict:
    """What ``forward`` needs of a configuration file: the published
    numbers."""
    return {"n_heads": spec["num_attention_heads"],
            "n_kv_heads": spec["num_key_value_heads"],
            "rope_theta": float(spec["rope_theta"]),
            "norm_eps": float(spec["norm_eps"]),
            "experts_per_token": spec["num_experts_per_tok"],
            "routed_scaling_factor": float(spec["routed_scaling_factor"])}
