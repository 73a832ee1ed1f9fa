"""The gated delta rule (linear attention whose state is a matrix a
head), in its three forms, with a decay a CHANNEL its own (``kda``) or
ONE decay a head (``gdn``: a scalar), and the short causal convolution
in front of it.  Plain jnp: no kernel here computes it yet.

A head keeps a state ``S`` (d_k, d_v), float32, and reads one token as

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                       alpha_t = exp(g_t), g_t <= 0

``g`` the log-decay of every key channel, ``beta`` the write strength
(up to 2: the state's eigenvalues may turn negative).  With ``u_t =
beta_t (v_t - (alpha_t * S_{t-1})^T k_t)`` that is ``S_t = alpha_t *
S_{t-1} + k_t u_t^T``: a decay, then a rank-one write.

* ``delta_rule_scan`` — the recurrence token by token (``lax.scan``):
  what the other two are held to.
* ``chunk_delta_rule`` — blocks of ``BLOCK`` tokens.  Inside a block,
  with ``G`` the cumulative log-decay from the block's start, the
  ``u`` of all its tokens solve ONE unit-triangular system

      (I + Diag(beta) A) U = Diag(beta) (V - (K * exp(G)) S_0)
      A[t, i] = sum_c k_t[c] k_i[c] exp(G_t[c] - G_i[c]),   i < t

  whose inverse is matrix products too (``_unit_lower_inverse``), in
  float32 at the highest matmul precision; outputs and the next block's state are
  matrix products with ``U``.  A decay enters only as a DIFFERENCE of
  cumulative log-decays, ``exp(G_t - G_i)`` with ``t >= i``, so at most
  1: ``exp(-G_i)`` alone overflows float32 within one block when a
  channel forgets fast (g = -1.6 a token is exp(102) after 64).  The
  state passes from block to block in the scan's carry.

  The pair sums ``A`` (and the same with ``q_t`` for the outputs) keep
  the exponential inside the sum over ``c`` only where they must
  (``_pair_sums``).  The block is cut into sub-blocks of ``SUB`` = 16
  tokens; with ``R_a = G_{SUB a}``, the cumulative log-decay at
  sub-block ``a``'s first token, a pair that lies on two sides of it —
  ``t`` in ``a``, ``i`` in an earlier sub-block — has

      exp(G_t - G_i) = exp(G_t - R_a) * exp(R_a - G_i)

  and BOTH factors are differences of the kind above, so at most 1
  (``G`` only falls: ``t >= SUB a`` gives ``G_t <= R_a``, ``i < SUB a``
  gives ``R_a <= G_i``); a factor that underflows stands for a product
  under 1e-38.  Its sum over ``c`` is then a MATRIX product of the
  sub-block's rows ``k_t * exp(G_t - R_a)`` with the earlier tokens'
  rows ``k_i * exp(R_a - G_i)``, at the highest precision (``A`` enters
  the triangular system).  Only a pair inside ONE sub-block has no
  reference point between its tokens and keeps the pairwise form:
  block * SUB * d_k exponentials a head and as many terms in each sum,
  a quarter of the block taken pairwise whole, and 3 * block * d_k for
  the factors (block = 4 SUB: the later sub-blocks' own rows, and the
  tokens before the last sub-block once a reference point).
* ``delta_rule_step`` — one token a row, elementwise in float32; a row
  that is not ``active`` keeps its state bit for bit.

A token with ``beta = 0`` and ``g = 0`` changes nothing: that is how a
caller pads.

With ONE log-decay a head (``g`` (..., heads): Gated DeltaNet as Olmo
Hybrid publishes it) the same equations hold with ``Diag(alpha_t)`` a
multiple of the identity, and the state may be RECTANGULAR, d_k x d_v
with d_k != d_v:

* ``chunk_gdn`` — the block form's pair products become MATRIX
  products over d_k, ``A = (K K^T) * exp(G_t - G_i)`` and ``(Q K^T) *
  exp(G_t - G_i)`` with ``G`` (block,) a head: block * block
  exponentials a head where the channel form takes block * SUB * d_k
  (its ``k_t[c] k_i[c] exp(G_t[c] - G_i[c])`` cannot leave the
  exponential out of the sum over ``c`` for a pair inside one
  sub-block; across a sub-block's first token it can, above).
  Broadcasting a scalar decay to d_k channels through
  ``chunk_delta_rule`` gives the same values at SUB * d_k times the
  exponentials.
* ``gdn_step`` — ``delta_rule_step`` with one ``exp`` a head.

How such a state lies: ``s`` (..., heads, d_k, d_v) float32 row-major,
so d_v runs along the 128 lanes and d_k along the sublanes.  Olmo
Hybrid's 96 x 192: 96 = 12 whole sublane tiles of 8, 192 = one lane
tile and a half — the device pads a state's rows from 192 to 256 lanes
(a third more bytes than values: 98,304 B a head against 73,728); the
other order, d_k on the lanes, pads 96 to 128, the same third, so the
order is the one ``S^T k`` and ``S^T q`` read without a transpose: the
step's sums run over sublanes, the block form's products contract the
96 (three quarters of an MXU pass).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

BLOCK = 64
SUB = 16              # a block's sub-blocks, as _unit_lower_inverse's leaf
_HIGHEST = lax.Precision.HIGHEST


def causal_conv(u, tail, w, bias=None):
    """The depth-wise causal convolution over a sequence: ``y_t = sum_j
    w_j * ext[t + j]`` with ``ext`` the ``taps - 1`` inputs before the
    sequence (``tail``, zeros at a sequence's start) and then ``u``
    (tokens, channels); ``w`` (taps, channels), a channel its own taps,
    and its own ``bias`` (channels,) where the layer has one.
    Returns (y float32, ext)."""
    taps, tokens = w.shape[0], u.shape[0]
    ext = jnp.concatenate([tail.astype(u.dtype), u])
    y = sum(ext[j:j + tokens].astype(jnp.float32)
            * w[j].astype(jnp.float32) for j in range(taps))
    return y if bias is None else y + bias.astype(jnp.float32), ext


def causal_conv_step(u, tail, w, bias=None):
    """``causal_conv`` of one token a row: u (rows, channels), tail
    (rows, taps - 1, channels) -> (y float32, the new tail)."""
    ext = jnp.concatenate([tail, u[:, None].astype(tail.dtype)], axis=1)
    y = jnp.sum(ext.astype(jnp.float32) * w.astype(jnp.float32), axis=1)
    return y if bias is None else y + bias.astype(jnp.float32), ext[:, 1:]


def delta_rule_scan(q, k, v, g, beta, s0):
    """Token by token.  q, k, g (tokens, heads, d_k), v (tokens, heads,
    d_v), beta (tokens, heads), s0 (heads, d_k, d_v), all float32 ->
    (o (tokens, heads, d_v), the last state)."""

    def token(s, x):
        q, k, v, g, beta = x
        s = jnp.exp(g)[..., None] * s
        u = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", s, k,
                                            precision=_HIGHEST))
        s = s + k[..., None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q, precision=_HIGHEST)

    s, o = lax.scan(token, s0, (q, k, v, g, beta))
    return o, s


def _diagonal_blocks(x, m: int):
    """(..., n, n) -> (..., n / m, m, m): the blocks on the diagonal."""
    n = x.shape[-1]
    x = x.reshape(*x.shape[:-2], n // m, m, n // m, m)
    return jnp.moveaxis(jnp.diagonal(x, axis1=-4, axis2=-2), -1, -3)


def _unit_lower_inverse(lower, leaf: int = 16):
    """(I + lower)^-1 of strictly lower triangular (..., n, n), by
    matrix products: the diagonal blocks of ``leaf`` rows by the series
    ``(I - M)^-1 = (I + M)(I + M^2)(I + M^4)...`` (M nilpotent: it ends),
    then pairs of neighbours merged, ``[[A, 0], [C, B]]^-1 = [[A^-1,
    0], [-B^-1 C A^-1, B^-1]]``, until one block is left.  The series
    over all n rows at once loses digits where a block's keys lie close
    together (its powers grow before they vanish); merged from small
    blocks it is as good as substitution row by row."""
    n = lower.shape[-1]
    m = min(leaf, n)
    power = -_diagonal_blocks(lower, m)
    inverse, reach = jnp.eye(m, dtype=lower.dtype) + power, 2
    while reach < m:
        power = jnp.matmul(power, power, precision=_HIGHEST)
        inverse = inverse + jnp.matmul(inverse, power, precision=_HIGHEST)
        reach *= 2
    while m < n:
        below = _diagonal_blocks(lower, 2 * m)[..., m:, :m]
        pairs = inverse.reshape(*inverse.shape[:-3], -1, 2, m, m)
        first, second = pairs[..., 0, :, :], pairs[..., 1, :, :]
        corner = -jnp.matmul(jnp.matmul(second, below, precision=_HIGHEST),
                             first, precision=_HIGHEST)
        inverse = jnp.concatenate([
            jnp.concatenate([first, jnp.zeros_like(first)], axis=-1),
            jnp.concatenate([corner, second], axis=-1)], axis=-2)
        m *= 2
    return inverse[..., 0, :, :]


def _by_blocks(form, q, k, v, g, beta, s0, block: int):
    """The scaffolding of a block form: the sequence filled up to whole
    blocks with tokens that change nothing, each input as (n, heads,
    block, ...), ``form(lower, strict)`` — given the block's lower and
    strictly lower triangular masks — the scan's body ``(s, a block's
    inputs) -> (s, o)``, the state handed block to block in the carry;
    -> (o (tokens, heads, d_v), the last state)."""
    given, heads = q.shape[:2]
    q, k, v, g, beta = (
        jnp.pad(x, ((0, -given % block),) + ((0, 0),) * (x.ndim - 1))
        for x in (q, k, v, g, beta))
    tokens = q.shape[0]
    n = tokens // block

    def blocks(x):                        # -> (n, heads, block, ...)
        x = x.reshape(n, block, *x.shape[1:])
        return jnp.moveaxis(x, 2, 1)

    one = form(jnp.tril(jnp.ones((block, block), bool)),
               jnp.tril(jnp.ones((block, block), bool), -1))
    s, o = lax.scan(one, s0, tuple(map(blocks, (q, k, v, g, beta))))
    return jnp.moveaxis(o, 1, 2).reshape(tokens, heads, -1)[:given], s


def _pairwise(q, k, gc, lower):
    """The pair sums with the exponential inside the sum: q, k, gc
    (..., n, d_k), ``lower`` (n, n) -> (kk, qk) (..., n, n), 0 above the
    diagonal."""
    # exp(G_t - G_i) for i <= t, 0 above the diagonal
    decay = jnp.exp(jnp.where(
        lower[..., None], gc[..., :, None, :] - gc[..., None, :, :],
        -jnp.inf))
    kk = jnp.sum(k[..., :, None, :] * k[..., None, :, :] * decay, axis=-1)
    qk = jnp.sum(q[..., :, None, :] * k[..., None, :, :] * decay, axis=-1)
    return kk, qk


def _pair_sums(q, k, gc, lower):
    """A block's ``kk[t, i] = sum_c k_t[c] k_i[c] exp(G_t[c] - G_i[c])``
    and ``qk`` (the same with ``q_t``) for ``i <= t``: q, k, gc (heads,
    block, d_k) -> two (heads, block, block), 0 above the diagonal.
    Pairwise inside a sub-block of ``SUB``; between sub-blocks ONE
    matrix product (the module's docstring): the rows of every later
    sub-block ``a``, ``k`` and ``q`` together, scaled to its first
    token, with the tokens before the last sub-block scaled from it, 0
    where a token does not stand before ``a``."""
    heads, block, d_k = k.shape
    if block <= SUB:
        return _pairwise(q, k, gc, lower)
    n, m = block // SUB, block - SUB
    qs, ks, gs = (x.reshape(heads, n, SUB, d_k) for x in (q, k, gc))
    kk, qk = _pairwise(qs, ks, gs, lower[:SUB, :SUB])   # (heads, n, SUB, SUB)
    ref = gs[:, 1:, :1]                   # R_a, a = 1 .. n - 1
    fall = jnp.exp(gs[:, 1:] - ref)       # exp(G_t - R_a) <= 1
    rows = jnp.concatenate([ks[:, 1:] * fall, qs[:, 1:] * fall], axis=2)
    # exp(R_a - G_i) <= 1 for a token i before sub-block a, 0 from a on
    before = jnp.arange(m) < SUB * jnp.arange(1, n)[:, None]
    earlier = k[:, None, :m] * jnp.exp(jnp.where(
        before[..., None], ref - gc[:, None, :m], -jnp.inf))
    off = jnp.einsum("hatc,haic->hati", rows, earlier, precision=_HIGHEST)
    off = jnp.pad(off, ((0, 0), (1, 0), (0, 0), (0, SUB)))
    eye = jnp.eye(n, dtype=k.dtype)[:, None, :, None]

    def whole(off, diagonal):             # (heads, n, SUB, block) + blocks
        placed = diagonal[:, :, :, None, :] * eye
        return (off + placed.reshape(off.shape)).reshape(heads, block, block)

    return whole(off[:, :, :SUB], kk), whole(off[:, :, SUB:], qk)


def chunk_delta_rule(q, k, v, g, beta, s0, block: int = BLOCK):
    """``delta_rule_scan``'s values in blocks of ``block`` tokens, the
    last one filled up with tokens that change nothing."""
    def form(lower, strict):
        def one(s, x):
            q, k, v, g, beta = x              # (heads, block, ...)
            gc = jnp.cumsum(g, axis=1)
            kk, qk = _pair_sums(q, k, gc, lower)
            solve = _unit_lower_inverse(
                beta[..., None] * jnp.where(strict, kk, 0.0))
            into = jnp.exp(gc)                # from the block's start
            u = jnp.matmul(solve, beta[..., None] * (
                v - jnp.matmul(k * into, s)),
                precision=_HIGHEST)
            o = jnp.matmul(q * into, s) + jnp.matmul(qk, u)
            out_of = jnp.exp(gc[:, -1:] - gc)   # to the block's end
            s = into[:, -1, :, None] * s + jnp.einsum(
                "hck,hcv->hkv", k * out_of, u)
            return s, o

        return one

    with jax.named_scope("kda_chunk"):
        return _by_blocks(form, q, k, v, g, beta, s0, block)


def _step(decayed, q, k, v, beta, s, active):
    """One token a row from the states already ``decayed`` (alpha * S):
    two passes over them, the first reads ``(alpha * S)^T k`` and
    ``(alpha * S)^T q`` together, the second writes ``S``; ``o =
    S_new^T q = (alpha * S)^T q + u (k . q)``."""
    sk = jnp.sum(decayed * k[..., None], axis=-2)
    sq = jnp.sum(decayed * q[..., None], axis=-2)
    u = beta[..., None] * (v - sk)
    o = sq + u * jnp.sum(k * q, axis=-1, keepdims=True)
    new = decayed + k[..., None] * u[..., None, :]
    return o, jnp.where(active[:, None, None, None], new, s)


def delta_rule_step(q, k, v, g, beta, s, active):
    """One token a row: q, k, g (rows, heads, d_k), v (rows, heads,
    d_v), beta (rows, heads), s (rows, heads, d_k, d_v), active (rows,)
    bool -> (o (rows, heads, d_v), the new states).  Elementwise
    products and sums in float32 (``_step``)."""
    with jax.named_scope("kda_step"):
        return _step(jnp.exp(g)[..., None] * s, q, k, v, beta, s, active)


def chunk_gdn(q, k, v, g, beta, s0, block: int = BLOCK):
    """``chunk_delta_rule`` for ONE log-decay a head: q, k (tokens,
    heads, d_k), v (tokens, heads, d_v), g and beta (tokens, heads), s0
    (heads, d_k, d_v), all float32 -> (o (tokens, heads, d_v), the last
    state): what ``delta_rule_scan`` gives with ``g`` repeated over the
    d_k channels.  The pair products are matrix products (the module's
    docstring); those the triangular system is made of and solved with
    run at the highest matmul precision, as the channel form's
    elementwise sums are exact float32."""
    def form(lower, strict):
        def one(s, x):
            q, k, v, g, beta = x              # (heads, block[, ...])
            gc = jnp.cumsum(g, axis=1)
            # exp(G_t - G_i) for i <= t, 0 above the diagonal
            decay = jnp.exp(jnp.where(
                lower, gc[:, :, None] - gc[:, None], -jnp.inf))
            kk = jnp.einsum("htk,hik->hti", k, k,
                            precision=_HIGHEST) * decay
            qk = jnp.einsum("htk,hik->hti", q, k,
                            precision=_HIGHEST) * decay
            solve = _unit_lower_inverse(
                beta[..., None] * jnp.where(strict, kk, 0.0))
            into = jnp.exp(gc)[..., None]     # from the block's start
            u = jnp.matmul(solve, beta[..., None] * (
                v - into * jnp.matmul(k, s)),
                precision=_HIGHEST)
            o = into * jnp.matmul(q, s) + jnp.matmul(qk, u)
            out_of = jnp.exp(gc[:, -1:] - gc)[..., None]  # to its end
            s = into[:, -1:] * s + jnp.einsum(
                "hck,hcv->hkv", k * out_of, u)
            return s, o

        return one

    with jax.named_scope("gdn_chunk"):
        return _by_blocks(form, q, k, v, g, beta, s0, block)


def gdn_step(q, k, v, g, beta, s, active):
    """``delta_rule_step`` for ONE log-decay a head: g (rows, heads)."""
    with jax.named_scope("gdn_step"):
        return _step(jnp.exp(g)[..., None, None] * s, q, k, v, beta, s,
                     active)
