"""The plain reference of Olmo Hybrid (Allen AI, ``model_type``
``olmo_hybrid``), written from its published ``config.json`` and the
equations its keys name (``configs/olmo-hybrid-7b.json``, ``assumed``):
a decoder with Olmo 2's REORDERED norm — the norms sit on each
sub-layer's output, nothing norms its input —

    x <- x + RMSNorm(mix_l(x));  x <- x + RMSNorm(SwiGLU(x))

whose layers come in two kinds (``layer_types``; three
``linear_attention`` layers, then a ``full_attention`` one).

A FULL layer is multi-head attention with NO positional embedding
(``rope_theta`` null), causal, scale head_dim^-1/2, q and k under an
RMSNorm over the WHOLE projection (all heads' channels at once):

    mix = W_o attn( RMSNorm(x W_q), RMSNorm(x W_k), x W_v )

A LINEAR layer is the gated delta rule with ONE decay a head (Gated
DeltaNet), keys ``linear_key_head_dim`` and values
``linear_value_head_dim`` wide, behind a short convolution
(``linear_conv_kernel_dim`` 4, no bias):

    u~_t = x_t [W_q | W_k | W_v]
    u_t  = SiLU( sum_{j=0..3} w_j * u~_{t-3+j} )     zeros before t = 0
    q_t = q_t / |q_t| * d_k^-1/2,  k_t = k_t / |k_t|        a head each
    g_t = -exp(A_log) * softplus(W_a x_t + dt_bias)      a head: a scalar
    beta_t = 2 * sigmoid(W_b x_t)                         a head
    S_t = (I - beta_t k_t k_t^T) exp(g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t,   S_0 = 0,   S a head's (d_k, d_v) matrix
    mix = W_o ( RMSNorm_head(o_t) * SiLU(x_t W_g) )

and the logits are ``RMSNorm(x) W_head`` (the embedding is not tied).

Straightforward ``jax.numpy`` in float32 at the highest matmul
precision: the recurrence TOKEN BY TOKEN (``lax.scan`` over positions),
the convolution as a sum of four shifted products, no cache, no blocks
of the delta rule, no batching, no kernels.  It imports nothing of
``ant_ray_tpu``.

Departures from the description above, each on purpose:

* ``x / |x|`` is ``x / sqrt(sum x^2 + 1e-6)``: a zero vector stays zero;
* weights are whatever the caller passes, cast to float32 product by
  product; the softmax attention runs in blocks of ``QUERY_BLOCK``
  query rows (each against its full score row) and the head in blocks
  of ``HEAD_BLOCK`` vocabulary columns: the untied head alone is 1.5 GB
  in float32;
* the harness compiles ``block`` with the dense reference's four static
  names; a layer's KIND is read off its leaves (a linear layer has
  ``a_log``), so each kind is a program of its own, and widths are read
  off the weights' shapes;
* matrices are stored ``(in, out)`` and applied as ``x @ w``.

A full layer is a dict: ``wq`` / ``wk`` / ``wv`` (d, h * hd),
``q_norm`` / ``k_norm`` (h * hd,), ``wo`` (h * hd, d); a linear layer:
``wq`` / ``wk`` (d, H * d_k), ``wv`` (d, H * d_v), ``conv_w`` (4, H *
(2 d_k + d_v); q, k and v side by side), ``w_a`` (d, H), ``a_log``
(H,), ``dt_bias`` (H,), ``w_beta`` (d, H), ``w_g`` (d, H * d_v),
``o_norm`` (d_v,), ``wo`` (H * d_v, d); both: ``attn_norm`` (d,) — the
norm on the mix's output — ``mlp_norm`` (d,), ``w_gate`` / ``w_up``
(d, f), ``w_down`` (f, d).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.command_a_plus_decoder import attention
from chipbench.reference.dense_decoder import (
    embed_tokens,
    rms_norm,
    rotary,
)
from chipbench.reference.solar_open2_decoder import short_conv, unit

_HIGHEST = "highest"
HEAD_BLOCK = 12544           # 100,352 / 8


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def gated_delta_rule(q, k, v, g, beta):
    """The recurrence, one token after the other from an empty state.
    q, k (seq, H, d_k), v (seq, H, d_v), g and beta (seq, H) -> o (seq,
    H, d_v)."""

    def token(s, x):
        q, k, v, g, beta = x
        s = jnp.exp(g)[:, None, None] * s                   # alpha S
        s = s - beta[:, None, None] * k[..., None] * jnp.einsum(
            "hk,hkv->hv", k, s, precision=_HIGHEST)[:, None, :]
        s = s + beta[:, None, None] * k[..., None] * v[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q, precision=_HIGHEST)

    heads, d_k, d_v = q.shape[1], q.shape[2], v.shape[2]
    return jax.lax.scan(token, jnp.zeros((heads, d_k, d_v), jnp.float32),
                        (q, k, v, g, beta))[1]


def linear_mix(layer: dict, x, norm_eps, write_scale=2.0):
    seq, heads = x.shape[0], layer["a_log"].shape[0]
    width = layer["wq"].shape[1]
    u = jnp.concatenate([x @ _f32(layer[w]) for w in ("wq", "wk", "wv")], -1)
    u = jax.nn.silu(short_conv(u, layer["conv_w"]))
    q, k, v = (part.reshape(seq, heads, -1) for part in (
        u[:, :width], u[:, width:2 * width], u[:, 2 * width:]))
    q, k = unit(q) * q.shape[-1] ** -0.5, unit(k)
    g = -jnp.exp(_f32(layer["a_log"])) * jax.nn.softplus(
        x @ _f32(layer["w_a"]) + _f32(layer["dt_bias"]))
    beta = write_scale * jax.nn.sigmoid(x @ _f32(layer["w_beta"]))
    o = rms_norm(gated_delta_rule(q, k, v, g, beta), layer["o_norm"],
                 norm_eps)
    gate = jax.nn.silu(x @ _f32(layer["w_g"]))
    return (o.reshape(seq, -1) * gate) @ _f32(layer["wo"])


def softmax_mix(layer: dict, x, positions, n_heads, n_kv_heads, norm_eps,
                rope_theta=0.0, qk_norm=True):
    seq = x.shape[0]
    q, k, v = (x @ _f32(layer[w]) for w in ("wq", "wk", "wv"))
    if qk_norm:
        q = rms_norm(q, layer["q_norm"], norm_eps)
        k = rms_norm(k, layer["k_norm"], norm_eps)
    q, k = q.reshape(seq, n_heads, -1), k.reshape(seq, n_kv_heads, -1)
    if rope_theta:
        q, k = (rotary(y, positions, rope_theta) for y in (q, k))
    a = attention(q, k, v.reshape(seq, n_kv_heads, -1), positions,
                  jnp.iinfo(jnp.int32).max)
    return a.reshape(seq, -1) @ _f32(layer["wo"])


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ _f32(w_gate)) * (x @ _f32(w_up))) @ _f32(w_down)


def block(layer: dict, x, positions, *, n_heads: int, n_kv_heads: int,
          rope_theta: float, norm_eps: float, write_scale=2.0,
          reordered_norm=True, qk_norm=True):
    """One decoder layer on one sequence.  x: (seq, d) float32.
    ``rope_theta`` 0 — the published null — rotates nothing;
    ``write_scale`` is 2 with ``linear_allow_neg_eigval``.  The last
    two are the family's and no key's: a caller that turns one off
    (``benchmarks/olmo_hybrid_parity.py``'s controls) computes ANOTHER
    model — pre-norm blocks, q and k as projected — on the same
    leaves."""
    def sub(x, mix, norm):
        if reordered_norm:
            return x + rms_norm(mix(x), layer[norm], norm_eps)
        return x + mix(rms_norm(x, layer[norm], norm_eps))

    with jax.default_matmul_precision(_HIGHEST):
        if "a_log" in layer:
            x = sub(x, lambda h: linear_mix(layer, h, norm_eps, write_scale),
                    "attn_norm")
        else:
            x = sub(x, lambda h: softmax_mix(
                layer, h, positions, n_heads, n_kv_heads, norm_eps,
                rope_theta, qk_norm), "attn_norm")
        return sub(x, lambda h: swiglu(
            h, layer["w_gate"], layer["w_up"], layer["w_down"]), "mlp_norm")


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


def logits_of(norm_f, head, x, norm_eps):
    """``RMSNorm(x) W_head``, the head ``HEAD_BLOCK`` columns at a
    time."""
    with jax.default_matmul_precision(_HIGHEST):
        h = rms_norm(x, norm_f, norm_eps)
        return jnp.concatenate(
            [h @ _f32(head[:, at:at + HEAD_BLOCK])
             for at in range(0, head.shape[1], HEAD_BLOCK)], axis=-1)


def forward(embed, layers, norm_f, head, tokens, *, block_fn=block, **dims):
    """Logits (seq, vocab) of ONE sequence of token ids."""
    x = hidden(embed, layers, tokens, block_fn=block_fn, **dims)
    return logits_of(norm_f, head, x, dims["norm_eps"])


def dims_of(spec: dict) -> dict:
    """What ``block`` needs of a configuration file: the published
    numbers; ``rope_theta`` null (no rotation) goes as 0."""
    return {"n_heads": spec["num_attention_heads"],
            "n_kv_heads": spec["num_key_value_heads"],
            "rope_theta": float(spec["rope_parameters"]["rope_theta"] or 0),
            "norm_eps": float(spec["rms_norm_eps"]),
            "write_scale": 2.0 if spec["linear_allow_neg_eigval"] else 1.0}
