"""The plain reference of OLMoE (arXiv 2409.02060; layer equations as the
published ``modeling_olmoe`` of Hugging Face transformers computes
them): a pre-norm decoder with RMSNorm, RMSNorm over the WHOLE q and k
projections before the rotary embedding, causal attention, and a
feed-forward of routed SwiGLU experts with no shared expert:

    q = RMSNorm_q(h W_q)        k = RMSNorm_k(h W_k)     (all heads at once)
    p = softmax_float32(h W_r)                           (over ALL experts)
    y = sum over the k experts e of largest p_e:  p_e * W_down,e(
            silu(h W_gate,e) * (h W_up,e))               (p_e NOT renormalised)

Straightforward ``jax.numpy`` in float32 at the highest matmul
precision: EVERY expert is computed on every token, one after the other,
and the outputs are combined under a gate map that is zero outside a
token's k experts — no sort, no grouped product, no gather, no cache, no
scan.  It imports nothing of ``ant_ray_tpu``; what OLMoE shares with the
dense decoders (RMSNorm, the rotary embedding, causal attention, the
embedding lookup, the head, the walk over the layers) it takes from
``dense_decoder.py`` beside it, so that this file holds what is OLMoE's
own.

Departures from the published model, each on purpose:

* weights are whatever the caller passes (random from a seed in the
  benchmark), cast to float32 expert by expert as they are used, so a
  float32 copy of a layer's 64 experts never exists at once;
* the k largest are found by rank (``rank < k``), not by ``top_k``: the
  harness compiles ``block`` with only the dense reference's four
  static names, so ``experts_per_token`` and ``norm_topk_prob`` arrive
  as traced values.  Exact ties between two probabilities would keep
  both; in float32 on random weights there are none;
* ``norm_topk_prob`` true (the published value is false) divides the
  kept gates by their sum — there so that the tests can show the
  difference is seen;
* ``clip_qkv`` is null in the published configuration and is not built;
  the auxiliary load-balancing loss is a training regulariser and no
  part of the forward pass or of the next-token loss here;
* rotary embedding in the "rotate half" layout of the published
  implementation; matrices are stored ``(in, out)`` and applied as
  ``x @ w``.

A layer is a dict: ``attn_norm`` (d,), ``wq`` (d, h*hd), ``wk`` / ``wv``
(d, kvh*hd), ``q_norm`` (h*hd,), ``k_norm`` (kvh*hd,), ``wo`` (h*hd, d),
``mlp_norm`` (d,), ``router`` (d, E), ``w_gate`` / ``w_up`` (E, d, f),
``w_down`` (E, f, d).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.reference import dense_decoder as dense
from chipbench.reference.dense_decoder import (  # noqa: F401 — the family's
    attention, embed_tokens, head_loss, logits_of, rms_norm, rotary)

_HIGHEST = "highest"


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def gate_map(h, router, experts_per_token, norm_topk_prob):
    """(seq, d) -> (seq, E): a token's router probability at its
    ``experts_per_token`` most probable experts, zero elsewhere."""
    probs = jax.nn.softmax(h @ _f32(router), axis=-1)
    # rank 0 is the most probable expert of the token
    rank = jnp.argsort(jnp.argsort(-probs, axis=-1), axis=-1)
    gates = jnp.where(rank < experts_per_token, probs, 0.0)
    normed = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return jnp.where(norm_topk_prob, normed, gates)


def experts(layer: dict, h, gates):
    """Every expert on every token, weighted by ``gates`` (seq, E)."""
    out = jnp.zeros_like(h)
    for e in range(layer["w_gate"].shape[0]):
        hidden = jax.nn.silu(h @ _f32(layer["w_gate"][e])) * (
            h @ _f32(layer["w_up"][e]))
        out = out + gates[:, e:e + 1] * (hidden @ _f32(layer["w_down"][e]))
    return out


def block(layer: dict, x, positions, *, n_heads: int, n_kv_heads: int,
          rope_theta: float, norm_eps: float, experts_per_token=8,
          norm_topk_prob=False):
    """One decoder layer on one sequence.  x: (seq, d) float32."""
    seq, dim = x.shape
    head_dim = dim // n_heads
    with jax.default_matmul_precision(_HIGHEST):
        h = rms_norm(x, layer["attn_norm"], norm_eps)
        q = rms_norm(h @ _f32(layer["wq"]), layer["q_norm"], norm_eps)
        k = rms_norm(h @ _f32(layer["wk"]), layer["k_norm"], norm_eps)
        v = h @ _f32(layer["wv"])
        q = rotary(q.reshape(seq, n_heads, head_dim), positions, rope_theta)
        k = rotary(k.reshape(seq, n_kv_heads, head_dim), positions,
                   rope_theta)
        v = v.reshape(seq, n_kv_heads, head_dim)
        a = attention(q, k, v).reshape(seq, n_heads * head_dim)
        x = x + a @ _f32(layer["wo"])
        h = rms_norm(x, layer["mlp_norm"], norm_eps)
        gates = gate_map(h, layer["router"], experts_per_token,
                         norm_topk_prob)
        return x + experts(layer, h, gates)


# The walk over the layers and the head are the dense reference's, with
# this file's ``block`` where a caller passes none.
hidden = functools.partial(dense.hidden, block_fn=block)
forward = functools.partial(dense.forward, block_fn=block)


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
    """What ``block`` needs of a configuration file."""
    return {"n_heads": spec["num_attention_heads"],
            "n_kv_heads": spec["num_key_value_heads"],
            "rope_theta": float(spec["rope_theta"]),
            "norm_eps": float(spec["rms_norm_eps"]),
            "experts_per_token": spec["num_experts_per_tok"],
            "norm_topk_prob": bool(spec["norm_topk_prob"])}
