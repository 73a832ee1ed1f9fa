"""The selective state-space recurrence of Mamba-2 (Dao & Gu, arXiv
2405.21060: a DIAGONAL recurrence whose step depends on the input, the
"state-space dual" of a masked linear attention), in its three forms.
Plain jnp: no kernel here computes it yet.

A head keeps a state ``S`` (P, N), float32 — P the head's width, N the
state's — and reads one token as

    a_t = exp(-dt_t * A)                     dt_t > 0 the token's step
    S_t = a_t S_{t-1} + dt_t x_t B_t^T       A > 0 the head's rate
    y_t = S_t C_t + D x_t

``x_t`` (P,) the head's input, ``B_t`` and ``C_t`` (N,) the write and
the read direction, which ALL heads share (one group), ``D`` the head's
skip.  There is no delta rule: nothing of the state is erased along a
key, it only decays, a head as a whole, and so a block needs no
triangular solve.

* ``ssd_scan`` — the recurrence token by token (``lax.scan``): what the
  other two are held to.
* ``chunk_ssd`` — blocks of ``BLOCK`` tokens, all matrix products.
  With ``L_t`` the sum of ``log a`` from the block's start to ``t``,

      y_t = exp(L_t) S_0 C_t
            + sum_{i<=t} exp(L_t - L_i) (B_i . C_t) dt_i x_i + D x_t
      S_Q = exp(L_Q) S_0 + sum_i exp(L_Q - L_i) dt_i x_i B_i^T

  A decay enters only as ``exp`` of a DIFFERENCE ``L_t - L_i <= 0``,
  never as ``exp(-L_i)``: a head with ``dt * A`` = 1.6 a token is
  exp(410) after 256 and overflows float32 inside one block.  The state
  passes from block to block in the scan's carry.  The products'
  operands are rounded to ``operand`` (the model's dtype: bfloat16 on
  the chip, one pass of the matrix unit); sums, decays and the state
  are float32.
* ``ssd_step`` — one token a row, elementwise in float32, one pass over
  the states; a row that is not ``active`` keeps its state bit for bit.

A token with ``dt = 0`` neither decays nor writes (``a = 1``): that is
how a caller pads.

``BLOCK`` is 256, the ``mamba_chunk_size`` the family publishes: the
engine's 512-token chunk is two of them, so the state is handed block
to block inside a chunk as well as chunk to chunk; the (heads, 256,
256) decays of one block are 32 MiB of float32 at 128 heads, one
block's at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

BLOCK = 256
_HIGHEST = lax.Precision.HIGHEST


def ssd_scan(x, dt, a, b, c, d, s0):
    """Token by token.  x (tokens, heads, P), dt (tokens, heads), a
    (heads,) the rates, b, c (tokens, N), d (heads,), s0 (heads, P, N),
    all float32 -> (y (tokens, heads, P), the last state)."""

    def token(s, inputs):
        x, dt, b, c = inputs
        s = jnp.exp(-dt * a)[:, None, None] * s \
            + (dt[:, None] * x)[..., None] * b
        y = jnp.einsum("hpn,n->hp", s, c, precision=_HIGHEST)
        return s, y + d[:, None] * x

    s, y = lax.scan(token, s0, (x, dt, b, c))
    return y, s


def chunk_ssd(x, dt, a, b, c, d, s0, block: int = 0,
              operand=jnp.float32):
    """``ssd_scan``'s values in blocks of ``block`` tokens (0: ``BLOCK``,
    read when the program is traced), the last one filled up with tokens
    that change nothing."""
    with jax.named_scope("ssd_chunk"):
        given, heads = x.shape[:2]
        block = min(block or BLOCK, given)
        x, dt, b, c = (
            jnp.pad(v, ((0, -given % block),) + ((0, 0),) * (v.ndim - 1))
            for v in (x, dt, b, c))
        tokens = x.shape[0]
        lower = jnp.tril(jnp.ones((block, block), bool))
        f32 = {"preferred_element_type": jnp.float32}

        def one(s, inputs):
            x, dt, b, c = inputs              # a block's tokens first
            gc = jnp.cumsum(-dt * a, axis=0).T            # (heads, block)
            # exp(L_t - L_i) for i <= t, 0 above the diagonal
            decay = jnp.exp(jnp.where(
                lower, gc[:, :, None] - gc[:, None, :], -jnp.inf))
            cb = jnp.dot(c.astype(operand), b.astype(operand).T, **f32)
            pairs = (cb * decay * dt.T[:, None, :]).astype(operand)
            xs = x.astype(operand)
            y = jnp.einsum("hti,ihp->thp", pairs, xs, **f32)
            y = y + jnp.exp(gc).T[..., None] * jnp.einsum(
                "hpn,tn->thp", s.astype(operand), c.astype(operand), **f32)
            out_of = jnp.exp(gc[:, -1:] - gc) * dt.T      # to the end
            s = jnp.exp(gc[:, -1])[:, None, None] * s + jnp.einsum(
                "hip,in->hpn", (out_of[..., None] * jnp.moveaxis(
                    x, 0, 1)).astype(operand), b.astype(operand), **f32)
            return s, y + d[:, None] * x

        s, y = lax.scan(one, s0, tuple(
            v.reshape(tokens // block, block, *v.shape[1:])
            for v in (x, dt, b, c)))
        return y.reshape(tokens, heads, -1)[:given], s


def ssd_step(x, dt, a, b, c, d, s, active):
    """One token a row: x (rows, heads, P), dt (rows, heads), a, d
    (heads,), b, c (rows, N), s (rows, heads, P, N), active (rows,)
    bool -> (y (rows, heads, P), the new states).  Elementwise products
    and sums in float32: the state is read once (decayed, written to,
    read out along ``c``) and written once."""
    with jax.named_scope("ssd_step"):
        new = jnp.exp(-dt * a)[..., None, None] * s \
            + (dt[..., None] * x)[..., None] * b[:, None, None, :]
        y = jnp.sum(new * c[:, None, None, :], axis=-1) + d[:, None] * x
        return y, jnp.where(active[:, None, None, None], new, s)
