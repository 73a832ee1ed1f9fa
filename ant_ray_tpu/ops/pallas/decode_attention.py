"""Pallas TPU attention of a decode step's rows over the carried KV
slabs: row ``r`` — one new token of slot ``r`` — attends over positions
0..``pos[r]`` of (layer ``layer``, slot ``r``) of the WHOLE ``ks`` /
``vs`` (layers, slots, max_seq, …), which stay in HBM and are read
where they lie, a block of ``block`` positions at a time, each element
once a step, no further than the row's own length, and not at all for a
slot that is not ``active``.

The form is that of ``grouped_matmul``: a work list of (row, block)
VISITS built from ``pos`` and ``active`` — an active row's whole blocks
in their order, row after row; ``work_list``, built ONCE a step and
shared by its layers — handed to the index maps by scalar prefetch
beside the layer, and a dynamic grid over the visits alone.
The pipeline fetches visit v + 1's blocks of keys and values while visit
v is multiplied, across the rows' borders too; a slot without a visit
costs no grid step and no byte.

The three layouts ``llama.kv_slabs`` holds are ONE kernel over a block
as a matrix ``(columns, width)``, told apart by the slabs' shapes:

* heads side by side in a position (``flat_kv_heads``: (…, max_seq,
  kv_heads * head_dim)): a column is a position, its width every head's
  lanes; the queries are laid block-diagonally, head ``h`` in the lanes
  of its KV head and zeros elsewhere (``_spread``), so ONE product
  ``(heads, width) x (columns, width)^T`` gives every head's scores and
  ONE ``(heads, columns) x (columns, width)`` every head's sum of
  values, of which head ``h`` keeps its own ``head_dim`` lanes;
* a heads axis ((…, max_seq, kv_heads, head_dim)): the slab is taken as
  (…, max_seq * kv_heads, head_dim) — the same bytes, a position's heads
  are consecutive sublanes — a column is (position, KV head), and a
  query head sees the columns of its own KV head alone: the others are
  masked like the positions behind the row's own;
* latent slabs (no heads axis, unequal widths: ``ks`` the latents (…,
  max_seq, rank), ``vs`` the one rotary key a position (…, max_seq,
  rope)), attended in the ABSORBED form: the queries come as the pair
  (q times ``w_kvb``'s keys' part (rows, heads, rank), the rotated part
  (rows, heads, rope)), a column is a position, every head shares it;
  the scores are the sum of two products — with the block of latents
  and with the block of rotary keys — and the values ARE the latents:
  the output is (rows, heads, rank), ``w_kvb``'s values' part the
  caller's.  The chip holds 64 values a position ACROSS the lanes with
  the positions along them, so the rotary keys are taken as (…, rope,
  max_seq) — there the same bytes, a bitcast — and their block (rope,
  columns) multiplies as it lies.

In each the products are the MXU's with bfloat16 inputs and float32
sums (what they cost is the block's tiles loaded as the stationary
operand, 256 B a cycle a unit: the chip's four keep up with its HBM),
the scores, the running maximum, the denominator and the output are
float32, and the probabilities are rounded to the slab's dtype before
the values product: what ``llama._attend_slab``'s walk states.  A row's
sums run over blocks 0, 1, 2, … of its own slot in that order and see
nothing of any other row: its output does not depend, to the bit, on
the other rows' lengths or on which slots are active.  The output of a
row that is not ``active`` is zeros.

A slab no longer than a block is one block; the last block of one that
is no multiple of the block starts early (``pl.Element``: the offset is
in positions) and masks what the block before it covered.

With a ``window`` (static; 0: none of this, and not an operation traced
differently) the slabs handed in are a window layer's RINGS
(``llama.ring_positions`` rows a slot, position ``p`` at row ``p mod
ring``) in either of the first two layouts, and a column's mask is the
walk's own: ring row ``at`` holds ``pos[r] - (pos[r] - at) mod ring``
(``llama._ring_holds``), seen iff that is no position before 0 and less
than ``window`` behind the row's own.  A row reads the ring's blocks 0,
1, 2, … in the order they lie — ``pos[r] // block + 1`` of them until
the ring has wrapped, all of them after (``blocks_read`` of the ring's
length): a block that holds only positions behind the window is masked
whole and adds exact zeros under a rescale of exactly 1 (the running
maximum starts at float32's lowest finite value, not at -inf), the
walk's sums in the walk's order.

Measured on a v5e (``benchmarks/decode_walk.py``, PERF.md section 6,
PR 47; a layer of the cell's slabs at the cell's contexts, the XLA walk
-> this kernel, device ms): 30 heads side by side, 6 of 8 slots live at
1.5-11k positions 4.40 -> 0.84 (743 GB/s over the live blocks, the
chip's HBM peak 819); with a heads axis 128 / 8 heads, 3 of 16 slots
live 3.50 -> 0.25; 64 / 8, 24 slots at 8-30k 5.75 -> 2.55; 32 / 8, 16
slots at 300-900 0.127 -> 0.083; 16 / 16 the same 0.218 -> 0.147; 16 /
8, 12 slots at 150-450 0.048 -> 0.043.  No shape ran slower through the
kernel, so none keeps the walk by its sizes; ``llama._decode_kernel``
leaves out what was not measured (heads that are no whole lane tiles)
and what the kernel does not read (slabs under a mesh).  The
same products on the vector unit (float32 multiply, a lane reduce a
head) read the same 0.83 ms at the first shape: the time is the HBM's.
Latent slabs (PR 57; a layer's attention between ``w_kvb``'s two
by-head products, which both paths run): 64 heads, 48 slots all live at
512-2,560 0.537 -> 0.319; 32 heads, 4 of 16 slots live at 1.5-11k
0.803 -> 0.096 (289 and 272 GB/s over the live blocks: a visit of 295
KB is ~0.9 us, the MXU's — the block is the stationary operand of
products with 32-64 rows — and the grid step's, not the HBM's 0.36).
A window layer's rings (PR 59; ``command-a-plus.docqa``'s: 16 slots x
4,608 rows, 128 / 8 heads, a window of 4,096, 3 slots live past it):
0.620 -> 0.104 (543 GB/s over the live rows' 18 blocks each; in the
cell's step three such layers, ~0.04 ms a call at 1.9 rows live).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def blocks_read(pos, active, block: int, max_seq: int):
    """(rows,) int32: the blocks of ``block`` positions row ``r`` reads —
    those that hold positions 0..``pos[r]`` of a ``max_seq``-position
    slab, none for a row that is not ``active``."""
    size = min(block, max_seq)
    most = -(-max_seq // size)
    return jnp.where(active, jnp.clip(pos // size + 1, 1, most),
                     0).astype(jnp.int32)


def work_list(pos, active, block: int, max_seq: int):
    """The visits of a step: ``(row of visit v, block of visit v, blocks
    of each row, number of visits)``; the two lists are as long as there
    can be visits (rows x the slab's blocks) and mean nothing behind the
    number."""
    rows = pos.shape[0]
    size = min(block, max_seq)
    most = rows * -(-max_seq // size)
    per_row = blocks_read(pos, active, block, max_seq)
    before = jnp.cumsum(per_row) - per_row
    row_ids = jnp.repeat(jnp.arange(rows, dtype=jnp.int32), per_row,
                         total_repeat_length=most)
    block_ids = jnp.clip(jnp.arange(most, dtype=jnp.int32)
                         - before[row_ids], 0, None).astype(jnp.int32)
    return row_ids, block_ids, per_row, jnp.sum(per_row)


def _spread(q, kv_heads: int):
    """Queries (rows, heads, head_dim) laid block-diagonally over the
    lanes of a position whose KV heads lie side by side: (rows, heads,
    kv_heads * head_dim), head ``h``'s values in the lanes of KV head
    ``h // group``, zeros elsewhere."""
    rows, heads, width = q.shape
    mine = jnp.arange(heads)[:, None] // (heads // kv_heads) == jnp.arange(
        kv_heads)
    return jnp.where(mine[None, :, :, None], q[:, :, None, :], 0).reshape(
        rows, heads, kv_heads * width)


def _kernel(layer_ref, row_ids_ref, block_ids_ref, per_row_ref, pos_ref,
            *refs, size: int, max_seq: int, per: int, group: int,
            head_dim: int, scale: float, latent: bool, window: int):
    del layer_ref                                   # the index maps' alone
    *q_refs, k_ref, v_ref, out_ref, high_ref, denom_ref, acc_ref = refs
    visit = pl.program_id(0)
    row, b = row_ids_ref[visit], block_ids_ref[visit]

    @pl.when(b == 0)
    def _begin():
        high_ref[...] = jnp.full_like(high_ref, jnp.finfo(jnp.float32).min)
        denom_ref[...] = jnp.zeros_like(denom_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k, v = k_ref[0, 0], v_ref[0, 0]                 # (columns, width)
    s = jax.lax.dot_general(q_refs[0][...], k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if latent:      # k: the latents, the values too; v: the rotary keys,
        s += jnp.dot(q_refs[1][...], v,             # (rope, columns)
                     preferred_element_type=jnp.float32)
        v = k
    s = s * scale
    heads, columns = s.shape
    first = b * size
    start = jnp.minimum(first, max_seq - size)
    column = jax.lax.broadcasted_iota(jnp.int32, (heads, columns), 1)
    at = start + column // per
    if window:
        # a ring of ``max_seq`` rows: row ``at`` holds the newest position
        # ``gap`` behind the row's own with that remainder
        # (``llama._ring_holds``) — none yet where that lies before 0
        here = pos_ref[row]
        gap = jax.lax.rem(here, max_seq) - at
        gap = jnp.where(gap < 0, gap + max_seq, gap)
        valid = (at >= first) & (gap <= here) & (gap < window)
    else:
        valid = (at >= first) & (at <= pos_ref[row])
    if per > 1:                     # a column is one KV head's: its own
        head = jax.lax.broadcasted_iota(jnp.int32, (heads, columns), 0)
        valid &= column % per == head // group
    s = jnp.where(valid, s, -jnp.inf)
    high = high_ref[...]
    new_high = jnp.maximum(high, jnp.max(s, axis=1, keepdims=True))
    keep = jnp.exp(high - new_high)
    probs = jnp.exp(s - new_high)
    denom_ref[...] = keep * denom_ref[...] + jnp.sum(
        probs, axis=1, keepdims=True)
    acc_ref[...] = keep * acc_ref[...] + jnp.dot(
        probs.astype(v.dtype), v, preferred_element_type=jnp.float32)
    high_ref[...] = new_high

    @pl.when(b == per_row_ref[row] - 1)
    def _store():
        out = acc_ref[...] / denom_ref[...]
        if per > 1 or latent:
            out_ref[...] = out.astype(out_ref.dtype)
        else:           # of all heads' lanes, head h's own KV head's
            for h in range(heads):
                at = h // group * head_dim
                out_ref[h:h + 1, :] = out[h:h + 1, at:at + head_dim].astype(
                    out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "scale", "window",
                                             "interpret"))
def decode_attention(q, ks, vs, layer, pos, visits, *, block: int,
                     scale: float, window: int = 0,
                     interpret: bool = False):
    """``q`` (rows, heads, head_dim), ``ks`` / ``vs`` (layers, rows,
    max_seq, kv_heads, head_dim) or (layers, rows, max_seq, kv_heads *
    head_dim) of which layer ``layer`` (traced) is meant, ``pos`` (rows,)
    int32 each row's own position, ``visits`` the step's
    ``work_list(pos, active, block, max_seq)`` -> (rows, heads,
    head_dim) in ``q``'s dtype: row ``r``'s softmax attention over
    positions 0..``pos[r]`` of slot ``r``, the scores times ``scale``;
    zeros for a row that is not active (none of the visits).

    With ``window`` ``ks`` / ``vs`` are a window layer's rings (layers,
    rows, ring, …), ``visits`` the work list of the RING's length, and
    row ``r`` attends over the positions its slot's ring holds that lie
    less than ``window`` behind ``pos[r]``, itself among them.

    Latent slabs — ``ks`` the latents (layers, rows, max_seq, rank),
    ``vs`` the rotary keys (layers, rows, max_seq, rope): no heads axis
    and unequal widths — take ``q`` as the pair (``q_lat`` (rows, heads,
    rank), ``q_rope`` (rows, heads, rope)) and give (rows, heads, rank):
    the absorbed form's sum of latents, ``w_kvb``'s two parts the
    caller's."""
    max_seq = ks.shape[2]
    size = min(block, max_seq)
    latent = ks.ndim == 4 and ks.shape[3] != vs.shape[3]
    if latent:                      # one "KV head" every head shares
        per, kv_heads, qs = 1, 1, tuple(q)
        head_dim = ks.shape[3]
        # as the chip holds them: so narrow a position lies ACROSS the
        # lanes, the positions along them — a bitcast there, and the
        # block's product with the queries needs no transpose
        vs = jnp.swapaxes(vs, 2, 3)
    elif ks.ndim == 5:              # a heads axis: (position, head) columns
        per = kv_heads = ks.shape[3]
        head_dim, qs = q.shape[2], (q,)
        ks, vs = (x.reshape(*x.shape[:2], max_seq * per, head_dim)
                  for x in (ks, vs))
    else:                           # side by side: a position a column
        head_dim = q.shape[2]
        per, kv_heads = 1, ks.shape[3] // head_dim
        qs = (_spread(q, kv_heads),)
    rows, heads = qs[0].shape[:2]
    width = ks.shape[3]             # of the scores' first product, and of
    columns = size * per            # the values: the output's sums
    row_ids, block_ids, per_row, visits = visits
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def slab(v, layer, row_ids, block_ids, per_row, pos):
        start = jnp.minimum(block_ids[v] * size, max_seq - size)
        if size % 8 == 0 and max_seq % 8 == 0:      # whole sublane tiles
            start = pl.multiple_of(start, 8)
        return layer[0], row_ids[v], start * per, 0

    def row(v, layer, row_ids, block_ids, per_row, pos):
        return row_ids[v], 0, 0

    def across(v, *lists):
        layer, row, start, _ = slab(v, *lists)
        if size % 128 == 0 and max_seq % 128 == 0:  # whole lane tiles
            start = pl.multiple_of(start, 128)
        return layer, row, 0, start

    # offsets in elements, so every dimension's are (the lowering's rule)
    one = pl.Element(1)
    taken = [pl.BlockSpec((one, one, pl.Element(columns), pl.Element(width)),
                          slab)] * 2
    other = width                   # the second slab's values a column
    if latent:
        other = vs.shape[2]
        taken[1] = pl.BlockSpec((one, one, pl.Element(other),
                                 pl.Element(columns)), across)
    held = 2 * columns * (width + other) * ks.dtype.itemsize
    working = 4 * heads * (2 * width + 4 * columns) + 4 * heads * sum(
        x.shape[2] for x in qs)
    out = pl.pallas_call(
        functools.partial(_kernel, size=size, max_seq=max_seq, per=per,
                          group=heads // kv_heads, head_dim=head_dim,
                          scale=scale, latent=latent, window=window),
        out_shape=jax.ShapeDtypeStruct((rows, heads, head_dim),
                                       qs[0].dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            in_specs=[*(pl.BlockSpec((None, heads, x.shape[2]), row)
                        for x in qs), *taken],
            out_specs=pl.BlockSpec((None, heads, head_dim), row),
            # no row active: one visit, whose output nobody keeps
            grid=(jnp.maximum(visits, 1),),
            scratch_shapes=[pltpu.VMEM((heads, 1), jnp.float32),
                            pltpu.VMEM((heads, 1), jnp.float32),
                            pltpu.VMEM((heads, width), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=held + working + (8 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * heads * max_seq * per * (
                2 * width + (other if latent else 0)),
            transcendentals=rows * heads * max_seq * per,
            bytes_accessed=rows * max_seq * per * (width + other)
            * ks.dtype.itemsize),
        name="decode_attention", interpret=interpret,
    )(layer, row_ids, block_ids, per_row, pos.astype(jnp.int32), *qs, ks,
      vs)
    return jnp.where(per_row[:, None, None] > 0, out, 0)
