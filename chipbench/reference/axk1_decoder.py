"""The plain reference of A.X-K1 (SKT, ``model_type`` ``axk1``; a member
of DeepSeek-V3's family — latent attention as arXiv 2405.04434 section
2.1 publishes it, the router of arXiv 2412.19437 section 2.1.2): a
pre-norm decoder with RMSNorm whose attention goes through two low-rank
projections and whose feed-forward, from the second layer on, is routed
SwiGLU experts beside a shared one.  With ``h = RMSNorm(x)``:

    c_q = RMSNorm(h W_qa)             [q_nope | q_rope]_i = c_q W_qb   (head i)
    [c | k_r] = h W_kva               c_kv = RMSNorm(c)
    [k_nope | v]_i = c_kv W_kvb       q_rope_i <- RoPE(q_rope_i),  k_rope <- RoPE(k_r)
    score_i(t, s) = (q_nope_i(t).k_nope_i(s) + q_rope_i(t).k_rope(s))
                    * (nope + rope)^-1/2 * m^2,   m = 0.1 mscale_all_dim ln(factor) + 1
    x <- x + concat_i(softmax_s<=t(score_i) v_i) W_o

ONE rotary key ``k_rope`` serves all heads; RoPE turns the pairs
(2j, 2j + 1) of the rotary dimensions at YaRN's blended frequencies
(``yarn_inv_freq``).  Layer 0's feed-forward is a dense SwiGLU; the
others'

    s = sigmoid(h W_r)                                (over ALL experts)
    g_e = s_e / (sum over the k largest s + 1e-20) * routed_scaling_factor
    y = sum over the k experts e of largest s_e, e HELD:  g_e SwiGLU_e(h)
        + SwiGLU_shared(h)

Straightforward ``jax.numpy`` in float32 at the highest matmul
precision: per-head keys and values are made for every position and
nothing is absorbed, no cache, no batching; every held expert is computed
on every token and combined under a gate map that is zero outside a
token's k experts — no sort, no grouped product.  It imports nothing of
``ant_ray_tpu``; RMSNorm, the embedding lookup, the head and the walk
over the layers are ``dense_decoder.py``'s.

Departures from the published description, each on purpose:

* it is given a SHARE of the model, the one the benchmark's chip holds:
  ``w_gate`` / ``w_up`` / ``w_down`` hold only the experts from
  ``first_expert`` on (as many as their leading axis), while the router
  scores all of them.  What an absent expert would add to ``y`` is left
  out, and the partial ``x + y`` goes on to the next layer — the sum a
  rank of an expert-parallel deployment computes before the exchange.
  With every expert held it is the published layer;
* ``topk_method: "none"`` is read as a plain top-k over the sigmoid
  scores: no group-limited selection (``n_group`` / ``topk_group`` are
  not read) and no score-correction bias — both belong to ``noaux_tc``,
  which the configuration does not name.  ``seq_aux`` is a training loss
  and no part of the forward pass;
* weights are whatever the caller passes (random from a seed in the
  benchmark), cast to float32 leaf by leaf, the experts one by one, as
  they are used, so a float32 copy of a layer never exists at once;
* the k largest are found by rank (``rank < k``), not by ``top_k``: the
  harness compiles ``block`` with only the dense reference's four static
  names, so every other number arrives as a traced value (the widths are
  read off the weights' shapes).  Exact ties between two scores would
  keep both; in float32 on random weights there are none;
* the published implementation permutes the rotary dimensions to the
  half-split order and rotates there; this rotates the pairs (2j, 2j + 1)
  where they lie.  Applied to queries and keys alike the permutation
  changes no score;
* matrices are stored ``(in, out)`` and applied as ``x @ w``.

A layer is a dict: ``attn_norm`` (d,), ``w_qa`` (d, rq), ``q_a_norm``
(rq,), ``w_qb`` (rq, h * (nope + rope)), ``w_kva`` (d, rkv + rope),
``kv_a_norm`` (rkv,), ``w_kvb`` (rkv, h * (nope + v)), ``wo`` (h * v,
d), ``mlp_norm`` (d,), then ``w_gate`` / ``w_up`` (d, f), ``w_down``
(f, d) — or ``router`` (d, E), ``w_gate`` / ``w_up`` (held, d, f),
``w_down`` (held, f, d), ``shared_gate`` / ``shared_up`` (d, fs),
``shared_down`` (fs, d).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.reference import dense_decoder as dense
from chipbench.reference.dense_decoder import (  # noqa: F401 — the family's
    embed_tokens, head_loss, logits_of, rms_norm)

_HIGHEST = "highest"


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def yarn_inv_freq(dim, theta, factor, original, beta_fast, beta_slow):
    """(dim / 2,) turns per position of the rotary pairs: ``f_j =
    theta^(-2j/dim)`` for the pairs that turn more than ``beta_fast``
    times over the ``original`` context, ``f_j / factor`` for those that
    turn fewer than ``beta_slow`` times, a linear blend between."""
    j = jnp.arange(dim // 2, dtype=jnp.float32)
    f = theta ** (-2.0 * j / dim)

    def pair_of(turns):
        return dim * jnp.log(original / (turns * 2 * jnp.pi)) / (
            2 * jnp.log(theta))

    low = jnp.clip(jnp.floor(pair_of(beta_fast)), 0, dim - 1)
    high = jnp.clip(jnp.ceil(pair_of(beta_slow)), 0, dim - 1)
    ramp = jnp.clip((j - low) / jnp.maximum(high - low, 1e-3), 0.0, 1.0)
    return f / factor * ramp + f * (1.0 - ramp)


def yarn_temperature(factor, mscale):
    return jnp.where(factor > 1, 0.1 * mscale * jnp.log(factor) + 1.0, 1.0)


def rotary(x, positions, inv_freq, scale):
    """x: (seq, heads, rope); turns the pairs (2j, 2j + 1)."""
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = (jnp.cos(angles) * scale)[:, None, :]
    sin = (jnp.sin(angles) * scale)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def latent_attention(layer: dict, h, positions, n_heads, inv_freq,
                     rotary_scale, softmax_scale, norm_eps):
    """(seq, d) -> (seq, heads * v), before ``wo``."""
    seq = h.shape[0]
    rank = layer["kv_a_norm"].shape[0]
    rope = layer["w_kva"].shape[1] - rank
    nope = layer["w_qb"].shape[1] // n_heads - rope
    c_q = rms_norm(h @ _f32(layer["w_qa"]), layer["q_a_norm"], norm_eps)
    q = (c_q @ _f32(layer["w_qb"])).reshape(seq, n_heads, nope + rope)
    kv = h @ _f32(layer["w_kva"])
    c_kv = rms_norm(kv[:, :rank], layer["kv_a_norm"], norm_eps)
    k_rope = rotary(kv[:, None, rank:], positions, inv_freq, rotary_scale)
    q_rope = rotary(q[..., nope:], positions, inv_freq, rotary_scale)
    kv = (c_kv @ _f32(layer["w_kvb"])).reshape(seq, n_heads, -1)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scores = (jnp.einsum("qhd,khd->hqk", q[..., :nope], k_nope,
                         precision=_HIGHEST)
              + jnp.einsum("qhd,kd->hqk", q_rope, k_rope[:, 0],
                           precision=_HIGHEST)) * softmax_scale
    scores = jnp.where(jnp.tril(jnp.ones((seq, seq), bool))[None], scores,
                       -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("hqk,khd->qhd", probs, v,
                      precision=_HIGHEST).reshape(seq, -1)


def swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ _f32(w_gate)) * (h @ _f32(w_up))) @ _f32(w_down)


def gate_map(h, router, experts_per_token, routed_scaling_factor):
    """(seq, d) -> (seq, E): a token's normalised, scaled sigmoid score at
    its ``experts_per_token`` best experts, zero elsewhere."""
    scores = jax.nn.sigmoid(h @ _f32(router))
    # rank 0 is the token's best expert
    rank = jnp.argsort(jnp.argsort(-scores, axis=-1), axis=-1)
    kept = jnp.where(rank < experts_per_token, scores, 0.0)
    return kept / (jnp.sum(kept, axis=-1, keepdims=True) + 1e-20) \
        * routed_scaling_factor


def held_experts(layer: dict, h, gates, first_expert):
    """The experts held — ``first_expert`` and those after it — on every
    token, weighted by their columns of ``gates`` (seq, E)."""
    held = layer["w_gate"].shape[0]
    mine = jax.lax.dynamic_slice_in_dim(gates, first_expert, held, axis=1)
    out = jnp.zeros_like(h)
    for e in range(held):
        out = out + mine[:, e:e + 1] * swiglu(
            h, layer["w_gate"][e], layer["w_up"][e], layer["w_down"][e])
    return out


def block(layer: dict, x, positions, *, n_heads: int, n_kv_heads: int,
          rope_theta: float, norm_eps: float, yarn_factor=1.0,
          yarn_original=4096.0, yarn_beta_fast=32.0, yarn_beta_slow=1.0,
          yarn_mscale=1.0, yarn_mscale_all_dim=0.0, experts_per_token=8,
          routed_scaling_factor=1.0, first_expert=0):
    """One decoder layer on one sequence.  x: (seq, d) float32.  Dense
    where the layer has no ``router``.  ``n_kv_heads`` is the harness's
    and is not read: a latent layer has no key heads of its own."""
    del n_kv_heads
    rope = layer["w_kva"].shape[1] - layer["kv_a_norm"].shape[0]
    head = layer["w_qb"].shape[1] // n_heads
    with jax.default_matmul_precision(_HIGHEST):
        inv_freq = yarn_inv_freq(rope, rope_theta, yarn_factor,
                                 yarn_original, yarn_beta_fast,
                                 yarn_beta_slow)
        rotary_scale = yarn_temperature(yarn_factor, yarn_mscale) / (
            yarn_temperature(yarn_factor, yarn_mscale_all_dim))
        softmax_scale = head ** -0.5 * jnp.where(
            yarn_mscale_all_dim > 0,
            yarn_temperature(yarn_factor, yarn_mscale_all_dim) ** 2, 1.0)
        h = rms_norm(x, layer["attn_norm"], norm_eps)
        a = latent_attention(layer, h, positions, n_heads, inv_freq,
                             rotary_scale, softmax_scale, norm_eps)
        x = x + a @ _f32(layer["wo"])
        h = rms_norm(x, layer["mlp_norm"], norm_eps)
        if "router" not in layer:
            return x + swiglu(h, layer["w_gate"], layer["w_up"],
                              layer["w_down"])
        gates = gate_map(h, layer["router"], experts_per_token,
                         routed_scaling_factor)
        y = held_experts(layer, h, gates, first_expert)
        return x + y + swiglu(h, layer["shared_gate"], layer["shared_up"],
                              layer["shared_down"])


# The walk over the layers and the head are the dense reference's, with
# this file's ``block`` where a caller passes none.
hidden = functools.partial(dense.hidden, block_fn=block)
forward = functools.partial(dense.forward, block_fn=block)


def dims_of(spec: dict) -> dict:
    """What ``block`` needs of a configuration file: the published
    numbers, and the first expert of the share the file's ``deployment``
    states."""
    yarn = spec["rope_scaling"]
    return {"n_heads": spec["num_attention_heads"],
            "n_kv_heads": spec["num_key_value_heads"],
            "rope_theta": float(spec["rope_theta"]),
            "norm_eps": float(spec["rms_norm_eps"]),
            "yarn_factor": float(yarn["factor"]),
            "yarn_original": float(yarn["original_max_position_embeddings"]),
            "yarn_beta_fast": float(yarn["beta_fast"]),
            "yarn_beta_slow": float(yarn["beta_slow"]),
            "yarn_mscale": float(yarn["mscale"]),
            "yarn_mscale_all_dim": float(yarn["mscale_all_dim"]),
            "experts_per_token": spec["num_experts_per_tok"],
            "routed_scaling_factor": float(spec["routed_scaling_factor"]),
            "first_expert": spec["deployment"]["experts_held"][0]}
