"""The plain reference of Ouro (ByteDance, ``model_type`` ``ouro``;
arXiv 2510.25741, "Scaling Latent Reasoning via Looped Language
Models"), written from its published ``config.json`` and, from memory
with no network, its ``modeling_ouro.py`` (``configs/ouro-2.6b.json``,
``assumed``): a LOOPED decoder.  ``num_hidden_layers`` layers, all
alike, with SANDWICH norms — four RMSNorms a layer, each with its own
weight —

    x <- x + N2(Attn(N1(x)));   x <- x + N4(SwiGLU(N3(x)))

``Attn`` plain multi-head causal softmax attention (as many key/value
heads as query heads, no bias, no QK-norm), every head rotated whole
(RoPE, the half-split pairing), scale head_dim^-1/2.  A token passes
through the stack ``total_ut_steps`` times with the SAME weights, and
the final RMSNorm closes EVERY pass:

    x_0 = E[tokens]
    for u in 0 .. total_ut_steps - 1:
        x <- layers(x);  x <- Norm_f(x)        # what pass u + 1 starts from
        lambda_u = sigmoid(w_gate . x + b_gate)   # the exit gate
    logits = x W_head                          # no second norm

Causal attention in pass ``u`` sees the earlier positions' keys and
values OF PASS ``u``: with no cache that is simply the pass run over the
whole sequence.  The exit: ``p_u = lambda_u prod_{j<u} (1 - lambda_j)``,
``p_last`` the remainder; a token leaves at the first pass whose running
sum of ``p`` reaches ``early_exit_threshold``, at the last if none does,
and its logits are that pass's.  At the published threshold of 1 only
the last running sum reaches it.

Straightforward ``jax.numpy`` in float32 at the highest matmul
precision: the passes a Python loop, no cache, no scan, no kernels, no
batching.  It imports nothing of ``ant_ray_tpu``.

Departures from the published code that the writer knows of, each on
purpose:

* weights are whatever the caller passes, cast to float32 product by
  product, and the head runs ``HEAD_BLOCK`` vocabulary columns at a
  time: it is computed beside the served model, which fills the chip;
* the published code computes every pass's logits and picks by the exit
  rule; here the rule picks the pass's normed STATE and the head runs
  once on it — the same values;
* the running sum is compared in float32 as it is summed here; the
  published code's order of that sum is not known to the writer;
* the training objective (the loss weighted by the exit distribution
  with its entropy term) is not here: nothing trains this model;
* the harness compiles ``block`` with the dense reference's four static
  names; ``forward`` takes the loop's two numbers besides and hands
  ``block`` the four; widths are read off the weights' shapes;
* matrices are stored ``(in, out)`` and applied as ``x @ w``.

A layer is a dict: ``attn_norm`` / ``attn_out_norm`` / ``mlp_norm`` /
``mlp_out_norm`` (d,), ``wq`` / ``wk`` / ``wv`` (d, h * hd), ``wo``
(h * hd, d), ``w_gate`` / ``w_up`` (d, f), ``w_down`` (f, d).  What
closes a pass is a dict too: ``norm_f`` (d,), ``gate_w`` (d, 1),
``gate_b`` (1,).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.dense_decoder import (
    attention,
    embed_tokens,
    rms_norm,
    rotary,
)

_HIGHEST = "highest"
HEAD_BLOCK = 6144            # 49,152 / 8


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def block(layer: dict, x, positions, *, n_heads: int, n_kv_heads: int,
          rope_theta: float, norm_eps: float, sandwich: bool = True):
    """One decoder layer on one sequence.  x: (seq, d) float32.
    ``sandwich`` is the family's and no key's: a caller that turns it
    off (``benchmarks/ouro_parity.py``'s control) computes ANOTHER
    model — pre-norm blocks — on the same leaves."""
    seq = x.shape[0]

    def out_norm(y, name):
        return rms_norm(y, layer[name], norm_eps) if sandwich else y

    with jax.default_matmul_precision(_HIGHEST):
        h = rms_norm(x, layer["attn_norm"], norm_eps)
        q = (h @ _f32(layer["wq"])).reshape(seq, n_heads, -1)
        k = (h @ _f32(layer["wk"])).reshape(seq, n_kv_heads, -1)
        v = (h @ _f32(layer["wv"])).reshape(seq, n_kv_heads, -1)
        q = rotary(q, positions, rope_theta)
        k = rotary(k, positions, rope_theta)
        a = attention(q, k, v).reshape(seq, -1) @ _f32(layer["wo"])
        x = x + out_norm(a, "attn_out_norm")
        h = rms_norm(x, layer["mlp_norm"], norm_eps)
        gated = jax.nn.silu(h @ _f32(layer["w_gate"])) * (
            h @ _f32(layer["w_up"]))
        return x + out_norm(gated @ _f32(layer["w_down"]), "mlp_out_norm")


def gate_of(closing: dict, x):
    """The exit gate on a pass's normed state (seq, d) -> (seq,)."""
    with jax.default_matmul_precision(_HIGHEST):
        return jax.nn.sigmoid(
            (x @ _f32(closing["gate_w"]))[:, 0] + _f32(closing["gate_b"])[0])


def passes(embed, layers, closing: dict, tokens, *, total_ut_steps: int,
           block_fn=block, norm_between: bool = True, **dims):
    """ONE sequence through the stack ``total_ut_steps`` times ->
    ``(states, gates)``: each pass's closing state (seq, d) — normed —
    and its gate (seq,).  ``layers``: a list of layer dicts or a
    ``(layer(i), n)`` pair.  ``norm_between`` False (a control: ANOTHER
    model) leaves the norm off behind every pass but the last, and the
    gate then reads the state as it is."""
    if isinstance(layers, tuple):
        get, n = layers
    else:
        get, n = layers.__getitem__, len(layers)
    positions = jnp.arange(tokens.shape[0])
    x = embed_tokens(embed, tokens)
    states, gates = [], []
    for u in range(total_ut_steps):
        for i in range(n):
            x = block_fn(get(i), x, positions, **dims)
        if norm_between or u == total_ut_steps - 1:
            x = rms_norm(x, closing["norm_f"], dims["norm_eps"])
        states.append(x)
        gates.append(gate_of(closing, x))
    return states, gates


def exit_distribution(gates):
    """The passes' gates (each (seq,)) -> p (passes, seq): the
    probability that a token leaves behind each pass, the last pass's
    the remainder."""
    stay, out = jnp.ones_like(gates[0]), []
    for lam in gates[:-1]:
        out.append(lam * stay)
        stay = stay * (1.0 - lam)
    return jnp.stack([*out, stay])


def exit_pass(gates, early_exit_threshold: float):
    """(seq,) int: the pass each token leaves behind — the first whose
    running sum of the exit distribution reaches the threshold, the
    last if none does."""
    reached = jnp.cumsum(exit_distribution(gates), axis=0) >= float(
        early_exit_threshold)
    last = len(gates) - 1
    return jnp.where(jnp.any(reached[:last], axis=0),
                     jnp.argmax(reached[:last], axis=0), last)


def head_of(head, x):
    """``x W_head``, the head ``HEAD_BLOCK`` columns at a time."""
    with jax.default_matmul_precision(_HIGHEST):
        return jnp.concatenate(
            [x @ _f32(head[:, at:at + HEAD_BLOCK])
             for at in range(0, head.shape[1], HEAD_BLOCK)], axis=-1)


def forward(embed, layers, closing: dict, head, tokens, *,
            total_ut_steps: int, early_exit_threshold: float,
            block_fn=block, **dims):
    """Logits (seq, vocab) of ONE sequence of token ids: each token's
    are those of the pass the exit rule lets it leave behind."""
    states, gates = passes(embed, layers, closing, tokens,
                           total_ut_steps=total_ut_steps,
                           block_fn=block_fn, **dims)
    chosen = exit_pass(gates, early_exit_threshold)
    x = jnp.take_along_axis(jnp.stack(states), chosen[None, :, None],
                            axis=0)[0]
    return head_of(head, x)


def dims_of(spec: dict) -> dict:
    """What ``forward`` needs of a configuration file: ``block``'s four
    published numbers and the loop's two."""
    return {"n_heads": spec["num_attention_heads"],
            "n_kv_heads": spec["num_key_value_heads"],
            "rope_theta": float(spec["rope_theta"]),
            "norm_eps": float(spec["rms_norm_eps"]),
            "total_ut_steps": spec["total_ut_steps"],
            "early_exit_threshold": float(spec["early_exit_threshold"])}
