"""The plain reference of Xing4.0-29B-A4B (XingChen-AGI, ``model_type``
``xing4_0``): DeepSeek-V3's block — latent attention (arXiv 2405.04434
section 2.1), a sigmoid router with ``noaux_tc``'s correction bias beside
a shared expert (arXiv 2412.19437 section 2.1.2), leading dense layers —
around which the residual path is manifold-constrained hyper-connections
(mHC, arXiv 2512.24880, over hyper-connections, arXiv 2409.19606): a
token's residual state is ``n = hc_mult`` streams, ``X`` in R^{n x C}.

``X_0``: all n rows equal to the token's embedding.  Every sub-layer
``F`` — attention under its RMSNorm, the feed-forward under its own — has
leaves ``phi`` (n C, 2 n + n^2), ``b`` (2 n + n^2) and three scalars
``alpha``.  In float32:

    x_hat = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)
    [p | q | r] = x_hat phi                                (n | n | n^2)
    H_pre  = sigmoid(alpha_pre p + b_pre)                  in R^n
    H_post = 2 sigmoid(alpha_post q + b_post)              in R^n
    R = clamp(alpha_res mat(r) + b_res, clamp_min, clamp_max)
    M <- exp(R);  hc_sinkhorn_iters times:
        M <- M / (colsum(M) + hc_eps);  M <- M / (rowsum(M) + hc_eps)
    H_res = M
    h = H_pre X;   y = F(h);   X <- H_res X + H_post^T y

Behind the last layer ``x = sum_i X_i``, then the final norm and the
head.  ``F`` for attention is ``axk1_decoder.latent_attention`` with the
output projection; for the feed-forward a dense SwiGLU in the leading
layers and from then on

    s = sigmoid(h W_r);   picked = the k largest of s + b_corr
    g_e = s_e / (sum over picked s + 1e-20) * routed_scaling_factor
    y = sum over picked e of g_e SwiGLU_e(h) + SwiGLU_shared(h)

``b_corr`` picks and does not weigh.

Straightforward ``jax.numpy`` in float32 at the highest matmul
precision: no cache, no batching, no sort; every expert on every token
under a gate map that is zero outside a token's k; the Sinkhorn loop a
Python loop.  It imports nothing of ``ant_ray_tpu``; latent attention,
SwiGLU, YaRN and the experts' sum are ``axk1_decoder.py``'s (the same
published equations), RMSNorm, the embedding lookup and the head
``dense_decoder.py``'s.

What the published config leaves open, each read as follows (the
configuration file lists them under ``assumed``; the program follows the
same statements):

* ``X_0`` is the embedding replicated (the hyper-connections paper's
  section 3), and the streams are joined by their plain SUM (the config
  names no key for a learned join);
* ``x_hat`` has no learned scale: one would fold into ``phi``'s rows;
* the clamp (``mhc_h_res_clamp_min`` / ``max``) is on the mix's logits
  before the exponential, ``hc_eps`` is added to every sum the
  iteration divides by, and a pass is columns THEN rows (mHC's
  ``T_r(T_c(.))``);
* ``n_group`` = ``topk_group`` = 1: one group, so the group-limited
  selection of ``noaux_tc`` limits nothing and is not written;
* ``num_nextn_predict_layers``: the multi-token-prediction module is a
  further block beside the stack, read by a training loss and by
  self-drafting; the forward pass of the layers does not read it.

Departures, each on purpose: as in ``axk1_decoder.py`` weights are cast
leaf by leaf as they are used, the k largest are found by rank, rotary
pairs are turned where they lie and matrices are ``(in, out)``.  The
harness compiles ``block`` with only the dense reference's four static
names, so the count of Sinkhorn passes, which a Python loop needs as a
number, arrives as a SHAPE: ``forward`` gives every layer a leaf
``sinkhorn_passes`` of that many zeros.

A layer is ``axk1_decoder.py``'s dict (every expert held) with
``router_bias`` (E,) and, for ``sub`` in ``attn``, ``mlp``:
``hc_<sub>_phi`` (n C, 2 n + n^2), ``hc_<sub>_b`` (2 n + n^2,),
``hc_<sub>_alpha`` (3,: pre, post, res).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import axk1_decoder as axk1
from chipbench.reference.axk1_decoder import (
    latent_attention, swiglu, yarn_inv_freq, yarn_temperature)
from chipbench.reference.dense_decoder import (
    embed_tokens, logits_of, rms_norm)

_HIGHEST = "highest"


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def sinkhorn(m, passes: int, eps):
    """(..., n, n) positive -> doubly stochastic up to the iteration's
    error: ``passes`` times, columns then rows."""
    for _ in range(passes):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
    return m


def hc_maps(x, phi, b, alpha, passes: int, clamp_min, clamp_max, hc_eps,
            norm_eps):
    """x (seq, n, C) -> H_pre (seq, n), H_post (seq, n), H_res (seq, n,
    n), as the module's docstring writes them."""
    seq, n, dim = x.shape
    flat = x.reshape(seq, n * dim)
    x_hat = flat / jnp.sqrt(
        jnp.mean(flat * flat, axis=-1, keepdims=True) + norm_eps)
    b, alpha = _f32(b), _f32(alpha)
    pqr = x_hat @ _f32(phi)
    h_pre = jax.nn.sigmoid(alpha[0] * pqr[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * pqr[:, n:2 * n] + b[n:2 * n])
    r = jnp.clip(alpha[2] * pqr[:, 2 * n:] + b[2 * n:], clamp_min,
                 clamp_max).reshape(seq, n, n)
    return h_pre, h_post, sinkhorn(jnp.exp(r), passes, hc_eps)


def gate_map(h, router, router_bias, experts_per_token,
             routed_scaling_factor):
    """(seq, d) -> (seq, E): a token's normalised, scaled sigmoid score
    at the ``experts_per_token`` experts whose score PLUS BIAS is
    largest, zero elsewhere."""
    scores = jax.nn.sigmoid(h @ _f32(router))
    picked_by = scores + _f32(router_bias)
    # rank 0 is the token's best expert
    rank = jnp.argsort(jnp.argsort(-picked_by, axis=-1), axis=-1)
    kept = jnp.where(rank < experts_per_token, scores, 0.0)
    return kept / (jnp.sum(kept, axis=-1, keepdims=True) + 1e-20) \
        * routed_scaling_factor


def block(layer: dict, x, positions, *, n_heads: int, n_kv_heads: int,
          rope_theta: float, norm_eps: float, yarn_factor=1.0,
          yarn_original=4096.0, yarn_beta_fast=32.0, yarn_beta_slow=1.0,
          yarn_mscale=1.0, yarn_mscale_all_dim=0.0, experts_per_token=4,
          routed_scaling_factor=1.0, hc_eps=1e-6, clamp_min=-30.0,
          clamp_max=30.0):
    """One decoder layer on one sequence.  x: (seq, n, C) float32, the
    residual streams.  Dense where the layer has no ``router``.
    ``n_kv_heads`` is the harness's and is not read."""
    del n_kv_heads
    rope = layer["w_kva"].shape[1] - layer["kv_a_norm"].shape[0]
    head = layer["w_qb"].shape[1] // n_heads
    passes = layer["sinkhorn_passes"].shape[0]

    def attention(h):
        inv_freq = yarn_inv_freq(rope, rope_theta, yarn_factor,
                                 yarn_original, yarn_beta_fast,
                                 yarn_beta_slow)
        rotary_scale = yarn_temperature(yarn_factor, yarn_mscale) / (
            yarn_temperature(yarn_factor, yarn_mscale_all_dim))
        softmax_scale = head ** -0.5 * jnp.where(
            yarn_mscale_all_dim > 0,
            yarn_temperature(yarn_factor, yarn_mscale_all_dim) ** 2, 1.0)
        h = rms_norm(h, layer["attn_norm"], norm_eps)
        return latent_attention(layer, h, positions, n_heads, inv_freq,
                                rotary_scale, softmax_scale,
                                norm_eps) @ _f32(layer["wo"])

    def feed_forward(h):
        h = rms_norm(h, layer["mlp_norm"], norm_eps)
        if "router" not in layer:
            return swiglu(h, layer["w_gate"], layer["w_up"],
                          layer["w_down"])
        gates = gate_map(h, layer["router"], layer["router_bias"],
                         experts_per_token, routed_scaling_factor)
        return axk1.held_experts(layer, h, gates, 0) + swiglu(
            h, layer["shared_gate"], layer["shared_up"],
            layer["shared_down"])

    with jax.default_matmul_precision(_HIGHEST):
        for sub, f in (("attn", attention), ("mlp", feed_forward)):
            h_pre, h_post, h_res = hc_maps(
                x, layer[f"hc_{sub}_phi"], layer[f"hc_{sub}_b"],
                layer[f"hc_{sub}_alpha"], passes, clamp_min, clamp_max,
                hc_eps, norm_eps)
            y = f(jnp.einsum("sn,snc->sc", h_pre, x, precision=_HIGHEST))
            x = jnp.einsum("sij,sjc->sic", h_res, x, precision=_HIGHEST) \
                + h_post[:, :, None] * y[:, None, :]
        return x


def forward(embed, layers, norm_f, head, tokens, *, block_fn=block,
            hc_mult: int, hc_sinkhorn_iters: int, **dims):
    """Logits (seq, vocab) of ONE sequence of token ids: the streams
    opened behind the embedding, joined before the final norm.
    ``layers`` is a list of layer dicts, or a ``(layer(i), n)`` pair;
    ``block_fn`` lets a caller pass ``jax.jit(block)``."""
    if isinstance(layers, tuple):
        get, n = layers
    else:
        get, n = layers.__getitem__, len(layers)
    positions = jnp.arange(tokens.shape[0])
    passes = jnp.zeros((hc_sinkhorn_iters,), jnp.float32)
    x = jnp.repeat(embed_tokens(embed, tokens)[:, None, :], hc_mult, axis=1)
    for i in range(n):
        x = block_fn({**get(i), "sinkhorn_passes": passes}, x, positions,
                     **dims)
    return logits_of(norm_f, head, jnp.sum(x, axis=1), dims["norm_eps"])


def dims_of(spec: dict) -> dict:
    """What ``forward`` and ``block`` need of a configuration file: the
    published numbers."""
    dims = axk1.dims_of(spec)
    del dims["first_expert"]         # every expert is held: nothing to offset
    return {**dims, "hc_mult": spec["hc_mult"],
            "hc_sinkhorn_iters": spec["hc_sinkhorn_iters"],
            "hc_eps": float(spec["hc_eps"]),
            "clamp_min": float(spec["mhc_h_res_clamp_min"]),
            "clamp_max": float(spec["mhc_h_res_clamp_max"])}
