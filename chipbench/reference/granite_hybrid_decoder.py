"""The plain reference of Granite 4.0-H (IBM, ``model_type``
``granitemoehybrid``), written from its published ``config.json`` and
the equations its keys name: a pre-norm, sequential decoder with four
scalar multipliers,

    x_0 = embedding_multiplier * E[token]
    x <- x + residual_multiplier * mix_l(RMSNorm(x))
    x <- x + residual_multiplier * ffn(RMSNorm(x))
    logits = RMSNorm(x) E^T / logits_scaling          (the head is tied)

whose layers come in two kinds (``layer_types``; nine ``mamba`` layers
to one ``attention`` layer).  With ``h = RMSNorm(x)``:

A MAMBA layer is Mamba-2's selective state-space recurrence (Dao & Gu,
arXiv 2405.21060), H heads of width P with a state of width N, one
group (all heads share ``B`` and ``C``):

    [z | u | delta] = h W_in            widths H * P | H * P + 2 N | H
    u~_t = SiLU( b + sum_{j=0..3} w_j * u_{t-3+j} )   zeros before t = 0
    u~_t = [x_t (H x P) | B_t (N) | C_t (N)]
    dt_t = softplus(delta_t + dt_bias),  a_t = exp(-dt_t * exp(A_log))
    S_t = a_t S_{t-1} + dt_t x_t B_t^T,   S_0 = 0,   S a head's (P, N)
    y_t = S_t C_t + D x_t
    mix = W_out RMSNorm_{H * P}( y_t * SiLU(z_t) )

the gate FIRST, then one RMS over all H * P channels with a learned
weight; ``dt`` is not clamped (no key gives a limit).

An ATTENTION layer is grouped-query attention with NO positional
embedding (``position_embedding_type`` ``nope``), causal, its scores
times ``attention_multiplier`` (NOT head_dim^-1/2): ``mix = W_o
attn(h W_q, h W_k, h W_v)``.

The feed-forward of EVERY layer is

    l = h W_r                                    (over ALL experts)
    g_e = softmax over the k largest l of l_e
    y = sum over the k experts e of largest l_e, e HELD:  g_e SwiGLU_e(h)
        + SwiGLU_shared(h)

Straightforward ``jax.numpy`` in float32 at the highest matmul
precision: the recurrence TOKEN BY TOKEN (``lax.scan`` over positions),
the convolution as a sum of four shifted products plus its bias, no
cache, no blocks of the recurrence, no batching, no kernels; every held
expert is computed on every token under a gate map that is zero outside
a token's k experts.  It imports nothing of ``ant_ray_tpu``.

Departures from the description above, each on purpose:

* it is given a SHARE of the model, as ``axk1_decoder.py`` is: the
  expert matrices hold only the experts from ``first_expert`` on, the
  router scores all of them, what an absent expert would add is left
  out and the partial sum goes on to the next layer; the embedding is
  the slice of the vocabulary held;
* weights are whatever the caller passes, cast to float32 product by
  product, the experts one by one, and the softmax attention runs in
  blocks of ``QUERY_BLOCK`` query rows (each against its full score
  row): a layer of the benchmark's cut is 1.8 GB in float32;
* the harness compiles ``block`` with the dense reference's four static
  names; a layer's KIND is read off its leaves (a mamba layer has
  ``a_log``), so each kind is a program of its own, widths are read off
  the weights' shapes, and the multipliers are arguments;
* matrices are stored ``(in, out)`` and applied as ``x @ w``.

A mamba layer is a dict: ``attn_norm`` (d,), ``in_proj`` (d, 2 H P + 2
N + H), ``conv_w`` (4, H P + 2 N), ``conv_b`` (H P + 2 N,), ``dt_bias``
/ ``a_log`` / ``d_skip`` (H,), ``ssm_norm`` (H P,), ``out_proj`` (H P,
d); an attention layer: ``attn_norm``, ``wq`` (d, h * hd), ``wk`` /
``wv`` (d, kvh * hd), ``wo`` (h * hd, d); both: ``mlp_norm`` (d,),
``router`` (d, E), ``w_gate`` / ``w_up`` (held, d, f), ``w_down``
(held, f, d), ``shared_gate`` / ``shared_up`` (d, fs), ``shared_down``
(fs, d).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.axk1_decoder import held_experts, swiglu
from chipbench.reference.dense_decoder import (
    embed_tokens,
    logits_of,
    rms_norm,
)

_HIGHEST = "highest"
QUERY_BLOCK = 512


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def short_conv(u, w, bias):
    """(seq, channels) under (taps, channels) and a bias a channel:
    ``y_t = b + sum_j w_j * u_{t - (taps - 1) + j}``, zeros before the
    sequence's start — a sum of shifted products."""
    taps, seq = w.shape[0], u.shape[0]
    padded = jnp.pad(u, ((taps - 1, 0), (0, 0)))
    return _f32(bias) + sum(padded[j:j + seq] * _f32(w[j])
                            for j in range(taps))


def selective_scan(x, dt, rate, b, c, skip):
    """The recurrence, one token after the other from an empty state.
    x (seq, H, P), dt (seq, H), rate, skip (H,), b, c (seq, N) -> y
    (seq, H, P)."""

    def token(s, inputs):
        x, dt, b, c = inputs
        s = jnp.exp(-dt * rate)[:, None, None] * s \
            + dt[:, None, None] * x[:, :, None] * b[None, None, :]
        y = jnp.einsum("hpn,n->hp", s, c, precision=_HIGHEST)
        return s, y + skip[:, None] * x

    heads, width = x.shape[1:]
    return jax.lax.scan(
        token, jnp.zeros((heads, width, b.shape[-1]), jnp.float32),
        (x, dt, b, c))[1]


def mamba_mix(layer: dict, h, norm_eps):
    seq, heads = h.shape[0], layer["a_log"].shape[0]
    inner = layer["ssm_norm"].shape[0]
    channels = layer["conv_w"].shape[1]
    state = (channels - inner) // 2
    zxd = h @ _f32(layer["in_proj"])
    z, u, delta = (zxd[:, :inner], zxd[:, inner:inner + channels],
                   zxd[:, inner + channels:])
    u = jax.nn.silu(short_conv(u, layer["conv_w"], layer["conv_b"]))
    dt = jax.nn.softplus(delta + _f32(layer["dt_bias"]))
    y = selective_scan(
        u[:, :inner].reshape(seq, heads, -1), dt,
        jnp.exp(_f32(layer["a_log"])), u[:, inner:inner + state],
        u[:, inner + state:], _f32(layer["d_skip"]))
    gated = y.reshape(seq, inner) * jax.nn.silu(z)
    return rms_norm(gated, layer["ssm_norm"], norm_eps) @ _f32(
        layer["out_proj"])


def attention(q, k, v, scale):
    """Causal grouped-query attention, the scores times ``scale``.  q:
    (seq, heads, hd); k, v: (seq, kv_heads, hd).  Query head i reads
    key/value head i // (heads / kv_heads).  Computed ``QUERY_BLOCK``
    query rows at a time, one block after the other."""
    seq, heads, head_dim = q.shape
    kv_heads = k.shape[1]
    blocks = -(-seq // QUERY_BLOCK)
    pad = blocks * QUERY_BLOCK - seq
    positions = jnp.arange(seq)
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    t = jnp.pad(positions, (0, pad), constant_values=seq - 1)

    def rows(block):
        qb, tb = block             # (QUERY_BLOCK, kv_heads, group, hd)
        scores = jnp.einsum("qkgd,skd->kgqs", qb, k,
                            precision=_HIGHEST) * scale
        seen = tb[:, None] >= positions[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", probs, v, precision=_HIGHEST)

    out = jax.lax.map(rows, (
        q.reshape(blocks, QUERY_BLOCK, kv_heads, heads // kv_heads,
                  head_dim),
        t.reshape(blocks, QUERY_BLOCK)))
    return out.reshape(blocks * QUERY_BLOCK, heads, head_dim)[:seq]


def softmax_mix(layer: dict, h, n_heads, n_kv_heads, scale):
    seq = h.shape[0]
    q = (h @ _f32(layer["wq"])).reshape(seq, n_heads, -1)
    k = (h @ _f32(layer["wk"])).reshape(seq, n_kv_heads, -1)
    v = (h @ _f32(layer["wv"])).reshape(seq, n_kv_heads, -1)
    return attention(q, k, v, scale).reshape(seq, -1) @ _f32(layer["wo"])


def gate_map(h, router, experts_per_token):
    """(seq, d) -> (seq, E): the softmax over a token's
    ``experts_per_token`` largest router LOGITS at those experts, zero
    elsewhere."""
    logits = h @ _f32(router)
    rank = jnp.argsort(jnp.argsort(-logits, axis=-1), axis=-1)
    return jax.nn.softmax(
        jnp.where(rank < experts_per_token, logits, -jnp.inf), axis=-1)


def block(layer: dict, x, positions, *, n_heads: int, n_kv_heads: int,
          rope_theta: float, norm_eps: float, experts_per_token=10,
          first_expert=0, residual_multiplier=1.0,
          attention_multiplier=1.0):
    """One decoder layer on one sequence.  x: (seq, d) float32.
    ``rope_theta`` and ``positions`` are the harness's and are not
    read: no layer of this model rotates anything."""
    del rope_theta, positions
    with jax.default_matmul_precision(_HIGHEST):
        h = rms_norm(x, layer["attn_norm"], norm_eps)
        if "a_log" in layer:
            mix = mamba_mix(layer, h, norm_eps)
        else:
            mix = softmax_mix(layer, h, n_heads, n_kv_heads,
                              attention_multiplier)
        x = x + residual_multiplier * mix
        h = rms_norm(x, layer["mlp_norm"], norm_eps)
        gates = gate_map(h, layer["router"], experts_per_token)
        return x + residual_multiplier * (
            held_experts(layer, h, gates, first_expert) + swiglu(
                h, layer["shared_gate"], layer["shared_up"],
                layer["shared_down"]))


def hidden(embed, layers, tokens, *, block_fn=block,
           embedding_multiplier=1.0, **dims):
    """The last layer's output (seq, d) for ONE sequence; ``layers`` a
    list of layer dicts or a ``(layer(i), n)`` pair."""
    if isinstance(layers, tuple):
        get, n = layers
    else:
        get, n = layers.__getitem__, len(layers)
    positions = jnp.arange(tokens.shape[0])
    x = embedding_multiplier * embed_tokens(embed, tokens)
    for i in range(n):
        x = block_fn(get(i), x, positions, **dims)
    return x


def forward(embed, layers, norm_f, head, tokens, *, block_fn=block,
            logits_scaling=1.0, **dims):
    """Logits (seq, vocab) of ONE sequence of token ids; ``head`` is
    the embedding, transposed (the head is tied)."""
    x = hidden(embed, layers, tokens, block_fn=block_fn, **dims)
    return logits_of(norm_f, head, x, dims["norm_eps"]) / logits_scaling


def dims_of(spec: dict) -> dict:
    """What ``forward`` needs of a configuration file: the published
    numbers, and the first expert of the share the file's ``deployment``
    states."""
    return {"n_heads": spec["num_attention_heads"],
            "n_kv_heads": spec["num_key_value_heads"],
            "rope_theta": float(spec["rope_theta"]),
            "norm_eps": float(spec["rms_norm_eps"]),
            "experts_per_token": spec["num_experts_per_tok"],
            "first_expert": spec["deployment"]["experts_held"][0],
            "residual_multiplier": float(spec["residual_multiplier"]),
            "attention_multiplier": float(spec["attention_multiplier"]),
            "embedding_multiplier": float(spec["embedding_multiplier"]),
            "logits_scaling": float(spec["logits_scaling"])}
