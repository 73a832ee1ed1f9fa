"""Pallas TPU kernel for the routed experts' way BACK to token order in
a step program: ``out[t] = sum_j select(back[t, j] < held, rows[back[t,
j]], 0) * gates[t, j]`` — each token reads the k rows of the grouped
down product that are its own and writes one, in ONE pass over them.

``rows`` (the product's float32 output, sorted by expert) comes through
VMEM in PANELS of whole columns, one a grid step, the next panel's copy
running beside this one's sums; a row of the chip's (8, 128) tiles is
no contiguous stretch of HBM (Mosaic refuses a copy of one), so a
token's row is not fetched by a copy of its own but read out of the
panel where it lies (one sublane of each of its tiles).  Inside a panel
the tokens go by tiles of ``TOKENS``: pick j's rows are laid under one
another, a row from ``held`` on (a share's assignment to an expert held
elsewhere, whatever the product's buffer holds there, a NaN too) is
dropped by a SELECT, then gated and added in float32 in the order j =
0, 1, ..., k - 1, spelt as k adds: a token's sum is its own, the same
bits whatever tokens share its tile or its batch — the equation and the
order of ``llama._back_to_tokens``, the plain form.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# tokens a tile: whole sublane tiles of a bf16 result
TOKENS = 16
# the bytes of one panel of rows: two are in flight where there are
# several (the pipeline's double buffer)
PANEL_BYTES = 20 << 20


def panel(m: int, n: int, budget: int = PANEL_BYTES) -> int:
    """The columns of a panel of ``m`` float32 rows of ``n``: n halved
    while the panel is over ``budget`` bytes and stays whole lane
    tiles."""
    tn = n
    while m * tn * 4 > budget and tn % 256 == 0:
        tn //= 2
    return tn


def _kernel(flat_ref, held_ref, back_ref, gates_ref, rows_ref, out_ref, buf,
            *, k: int):
    held, last = held_ref[0], rows_ref.shape[0] - 1

    def tile(i, _):
        first = pl.multiple_of(i * TOKENS, TOKENS)
        back = back_ref[pl.ds(first, TOKENS), :]
        gates = gates_ref[pl.ds(first, TOKENS), :]
        out = None
        for j in range(k):                    # k adds, in the picks' order
            # unrolled: as a loop the copies no longer hide under the
            # next panel's DMA (3 x the time at a 512-token chunk)
            for t in range(TOKENS):
                # a row of no token (a padded tile's) reads the last one
                row = jnp.minimum(flat_ref[(first + t) * k + j], last)
                buf[pl.ds(t, 1), :] = rows_ref[pl.ds(row, 1), :]
            term = jnp.where(back[:, j:j + 1] < held, buf[...],
                             0.0) * gates[:, j:j + 1]
            out = term if out is None else out + term
        out_ref[pl.ds(first, TOKENS), :] = out.astype(out_ref.dtype)

    jax.lax.fori_loop(0, out_ref.shape[0] // TOKENS, tile, None)


@functools.partial(jax.jit, static_argnames=("dtype", "columns", "interpret"))
def gather_sum(rows, back, gates, held, *, dtype, columns: int | None = None,
               interpret: bool = False):
    """``rows`` (m, n) float32, ``back`` (tokens, k) int32 — token t's
    pick j is row ``back[t, j]`` — ``gates`` (tokens, k) float32,
    ``held`` () int32: the rows from ``held`` on belong to no token ->
    (tokens, n) ``dtype``, the float32 sum rounded once.  ``columns``: a
    panel's, a multiple of 128 that divides n (``panel`` unless
    given)."""
    (m, n), (tokens, k) = rows.shape, back.shape
    tn = columns or panel(m, n)
    if n % tn:
        raise ValueError(f"gather_sum: panels of {tn} columns do not tile "
                         f"rows of {n}")
    padded = pl.cdiv(tokens, TOKENS) * TOKENS
    if padded != tokens:                # whole tiles: tokens of no row
        back = jnp.pad(back, ((0, padded - tokens), (0, 0)),
                       constant_values=m)
        gates = jnp.pad(gates, ((0, padded - tokens), (0, 0)))
    held = jnp.asarray(held, jnp.int32).reshape(1)
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    out_bytes = jnp.dtype(dtype).itemsize
    blocks = (1 if tn == n else 2) * (m * tn * 4 + padded * tn * out_bytes) \
        + TOKENS * tn * 4 + 2 * padded * 128 * 4
    out = pl.pallas_call(
        functools.partial(_kernel, k=k),
        out_shape=jax.ShapeDtypeStruct((padded, n), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[whole, whole, whole if tn == n else pl.BlockSpec(
                (m, tn), lambda p, flat, held: (0, p))],
            out_specs=whole if tn == n else pl.BlockSpec(
                (padded, tn), lambda p, flat, held: (0, p)),
            grid=(n // tn,),
            scratch_shapes=[pltpu.VMEM((TOKENS, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=blocks + (8 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=2 * tokens * k * n, transcendentals=0,
            bytes_accessed=m * n * 4 + tokens * n * out_bytes),
        name="gather_sum", interpret=interpret,
    )(back.reshape(-1), held, back, gates, rows)
    return out[:tokens]
