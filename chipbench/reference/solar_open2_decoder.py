"""The plain reference of Solar Open 2 (Upstage, ``model_type``
``solar_open2``), written from its published ``config.json`` and the
equations its keys name: a pre-norm, sequential decoder

    x <- x + mix_l(RMSNorm(x));  x <- x + ffn(RMSNorm(x))

whose layers come in two kinds (``gqa_layers``; three linear layers to
one softmax layer).  With ``h = RMSNorm(x)``:

A SOFTMAX layer is grouped-query attention with NO positional embedding
(``use_rope`` false), causal, scale head_dim^-1/2, its output under an
element-wise gate (``use_gqa_gate``):

    mix = W_o ( attn(h W_q, h W_k, h W_v) * sigmoid(h W_gate) )

A LINEAR layer is the gated delta rule with a decay a channel its own
(``kda_*``), behind a short convolution (``short_conv_kernel_size`` 4):

    u~_t = h_t [W_q | W_k | W_v]
    u_t  = SiLU( sum_{j=0..3} w_j * u~_{t-3+j} )     zeros before t = 0
    q_t = q_t / |q_t| * d_k^-1/2,  k_t = k_t / |k_t|        a head each
    g_t = -exp(A_log) * softplus(W_fb (W_fa h_t) + dt_bias)  a channel
    beta_t = 2 * sigmoid(W_beta h_t)                          a head
    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t,   S_0 = 0,   S a head's (d_k, d_v) matrix
    mix = W_o ( RMSNorm_head(o_t) * sigmoid(W_gb (W_ga h_t)) )

The feed-forward of EVERY layer is

    s = sigmoid(h W_r)                                (over ALL experts)
    g_e = s_e / (sum over the k largest s + 1e-20)
    y = sum over the k experts e of largest s_e, e HELD:  g_e SwiGLU_e(h)
        + SwiGLU_shared(h)

and the logits are ``RMSNorm(x) W_head`` (the embedding is not tied).

Straightforward ``jax.numpy`` in float32 at the highest matmul
precision: the recurrence TOKEN BY TOKEN (``lax.scan`` over positions),
the convolution as a sum of four shifted products, no cache, no blocks
of the delta rule, no batching, no kernels; every held expert is
computed on every token under a gate map that is zero outside a token's
k experts.  It imports nothing of ``ant_ray_tpu``.

Departures from the description above, each on purpose:

* it is given a SHARE of the model, as ``axk1_decoder.py`` is: the
  expert matrices hold only the experts from ``first_expert`` on, the
  router scores all of them, what an absent expert would add is left
  out and the partial sum goes on to the next layer;
* ``x / |x|`` is ``x / sqrt(sum x^2 + 1e-6)``: a zero vector stays zero;
* weights are whatever the caller passes, cast to float32 product by
  product, the experts one by one, and the softmax attention runs in
  blocks of ``QUERY_BLOCK`` query rows (each against its full score
  row): a layer of the benchmark's cut is 3 GB in float32;
* the harness compiles ``block`` with the dense reference's four static
  names; a layer's KIND is read off its leaves (a linear layer has
  ``a_log``), so each kind is a program of its own, and widths are read
  off the weights' shapes;
* matrices are stored ``(in, out)`` and applied as ``x @ w``.

A softmax layer is a dict: ``attn_norm`` (d,), ``wq`` (d, h * hd),
``wk`` / ``wv`` (d, kvh * hd), ``w_attn_gate`` (d, h * hd), ``wo`` (h *
hd, d); a linear layer: ``attn_norm``, ``wq`` / ``wk`` / ``wv`` (d, H *
d_k), ``conv_w`` (4, 3 * H * d_k; q, k and v side by side), ``w_fa``
(d, r), ``w_fb`` (r, H * d_k), ``a_log`` (H,), ``dt_bias`` (H * d_k,),
``w_beta`` (d, H), ``w_ga`` (d, r), ``w_gb`` (r, H * d_k), ``o_norm``
(d_k,), ``wo`` (H * d_k, d); both: ``mlp_norm`` (d,), ``router`` (d,
E), ``w_gate`` / ``w_up`` (held, d, f), ``w_down`` (held, f, d),
``shared_gate`` / ``shared_up`` (d, f), ``shared_down`` (f, d).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.axk1_decoder import held_experts, swiglu
from chipbench.reference.command_a_plus_decoder import attention, gate_map
from chipbench.reference.dense_decoder import (
    embed_tokens,
    logits_of,
    rms_norm,
)

_HIGHEST = "highest"


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def short_conv(u, w):
    """(seq, channels) under (taps, channels): ``y_t = sum_j w_j *
    u_{t - (taps - 1) + j}``, zeros before the sequence's start — a sum
    of shifted products."""
    taps, seq = w.shape[0], u.shape[0]
    padded = jnp.pad(u, ((taps - 1, 0), (0, 0)))
    return sum(padded[j:j + seq] * _f32(w[j]) for j in range(taps))


def unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, g, beta):
    """The recurrence, one token after the other from an empty state.
    q, k, g (seq, H, d_k), v (seq, H, d_v), beta (seq, H) -> o (seq, H,
    d_v)."""

    def token(s, x):
        q, k, v, g, beta = x
        s = jnp.exp(g)[..., None] * s                       # Diag(a) S
        s = s - beta[:, None, None] * k[..., None] * jnp.einsum(
            "hk,hkv->hv", k, s, precision=_HIGHEST)[:, None, :]
        s = s + beta[:, None, None] * k[..., None] * v[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q, precision=_HIGHEST)

    heads, d_k, d_v = q.shape[1], q.shape[2], v.shape[2]
    return jax.lax.scan(token, jnp.zeros((heads, d_k, d_v), jnp.float32),
                        (q, k, v, g, beta))[1]


def linear_mix(layer: dict, h, norm_eps):
    seq, heads = h.shape[0], layer["a_log"].shape[0]
    u = jnp.concatenate([h @ _f32(layer[w]) for w in ("wq", "wk", "wv")], -1)
    u = jax.nn.silu(short_conv(u, layer["conv_w"]))
    q, k, v = (part.reshape(seq, heads, -1) for part in jnp.split(u, 3, -1))
    q, k = unit(q) * q.shape[-1] ** -0.5, unit(k)
    step = jax.nn.softplus(
        (h @ _f32(layer["w_fa"])) @ _f32(layer["w_fb"])
        + _f32(layer["dt_bias"])).reshape(seq, heads, -1)
    g = -jnp.exp(_f32(layer["a_log"]))[:, None] * step
    beta = 2.0 * jax.nn.sigmoid(h @ _f32(layer["w_beta"]))
    o = rms_norm(delta_rule(q, k, v, g, beta), layer["o_norm"], norm_eps)
    gate = jax.nn.sigmoid((h @ _f32(layer["w_ga"])) @ _f32(layer["w_gb"]))
    return (o.reshape(seq, -1) * gate) @ _f32(layer["wo"])


def softmax_mix(layer: dict, h, positions, n_heads, n_kv_heads):
    seq = h.shape[0]
    q = (h @ _f32(layer["wq"])).reshape(seq, n_heads, -1)
    k = (h @ _f32(layer["wk"])).reshape(seq, n_kv_heads, -1)
    v = (h @ _f32(layer["wv"])).reshape(seq, n_kv_heads, -1)
    a = attention(q, k, v, positions, jnp.iinfo(jnp.int32).max)
    gate = jax.nn.sigmoid(h @ _f32(layer["w_attn_gate"]))
    return (a.reshape(seq, -1) * gate) @ _f32(layer["wo"])


def block(layer: dict, x, positions, *, n_heads: int, n_kv_heads: int,
          rope_theta: float, norm_eps: float, experts_per_token=8,
          first_expert=0):
    """One decoder layer on one sequence.  x: (seq, d) float32.
    ``rope_theta`` is the harness's and is not read: no layer of this
    model rotates anything."""
    del rope_theta
    with jax.default_matmul_precision(_HIGHEST):
        h = rms_norm(x, layer["attn_norm"], norm_eps)
        if "a_log" in layer:
            x = x + linear_mix(layer, h, norm_eps)
        else:
            x = x + softmax_mix(layer, h, positions, n_heads, n_kv_heads)
        h = rms_norm(x, layer["mlp_norm"], norm_eps)
        gates = gate_map(h, layer["router"], experts_per_token)
        return x + held_experts(layer, h, gates, first_expert) + swiglu(
            h, layer["shared_gate"], layer["shared_up"],
            layer["shared_down"])


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
            "norm_eps": float(spec["rms_norm_eps"]),
            "experts_per_token": spec["num_experts_per_tok"],
            "first_expert": spec["deployment"]["experts_held"][0]}
