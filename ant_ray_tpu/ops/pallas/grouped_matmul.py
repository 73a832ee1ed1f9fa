"""Pallas TPU grouped matrix product for a step program's routed experts:
``(rows sorted by expert, m x k) x (the WHOLE expert stack, layers x
experts x k x n) -> m x n float32``, the layer and the group sizes by
scalar prefetch, so the stack is read where it lies and an expert's
weights only if it has a row.

The form is that of ``jax.experimental.pallas.ops.tpu.megablox.gmm``: a
work list of (row tile, group) VISITS built from the group sizes, a
dynamic grid over the visits only, and a store mask where a tile holds
rows of more than one group.  A visit multiplies one ``tm``-row tile by
one expert, so what a product costs follows the rows the experts really
got, not the operand's row count: XLA's ``ragged-dot`` kernel takes its
row tile from the operand (a 64-token chunk of 8 experts a token is ONE
512-row tile per expert hit, whatever rows the expert has).  Rows behind
the last group (a share's assignments to experts held elsewhere) are
never visited and hold whatever was there: the caller masks them.

Grid ``(n tiles, visits, k tiles)``, k innermost: a visit's float32
accumulator sums its k tiles in their order, and a row's sum is its own
— the same bits whatever rows share its tile or its batch.  WHO fetches
an expert follows from its size.  Cut into panels (``_kernel``), a panel
is a ``BlockSpec`` block and Pallas' pipeline fetches the next grid
step's during this one: the step from a visit's last k tile to the next
visit's first changes the block, so a group's every row tile reads its
expert again.  ONE block (``_kernel_by_group``), the stack stays in HBM
and the kernel copies experts itself, double-buffered by GROUP over the
work list: the next hit group's matrix is in flight from the current
group's FIRST visit on, and a group that straddles row tiles is fetched
once.  A pipeline keyed by the grid step had no copy in flight during
such a group's later visits, and the next expert then arrived under ONE
visit's arithmetic (``PERF.md`` section 6, PR 63 and PR 66); the visits,
their sums and the store mask are the same in both.

``tiling`` is the rule for (tm, tk, tn), a function of shapes alone,
read from ``benchmarks/grouped_product``'s sweeps on a v5e (``PERF.md``
section 6, PR 37 and PR 63): a visit costs its expert's bytes — 680-740
GB/s over the experts hit from 32 to 128 rows a tile — unless the block
is the one already there.  So the row tile decides how often a run of
rows crosses a tile boundary, and the panel whether a crossing costs
the expert a second time: an expert within ``WHOLE_BYTES`` is one block
and is read once a call, however many row tiles its rows cross.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The bytes of one weight panel (tk x tn) a visit step fetches where an
# expert is cut into panels: two of them are in flight (the pipeline's
# double buffer).
PANEL_BYTES = 4 << 20
# An expert's matrix of at most this many bytes is ONE block (two in
# flight): consecutive visits of its group then fetch it once.  Read from
# ``benchmarks/grouped_product``'s sweep of twelve shapes on a v5e
# (``PERF.md`` section 6, PR 63): whole, experts of 6.3, 7.3 and 10.5 MB
# take 10-26 % less a call at a 512-token chunk and as much or 1-4 %
# less at a decode step; experts of 29 and 34 MB are slower whole at
# their decode steps, and two of them are 60-75 MB of VMEM.
WHOLE_BYTES = 12 << 20
# bf16 rows come in tiles of 16 sublanes: what a row tile is a multiple of
MIN_ROWS = 16
# the rule's row tiles
LEAST_TILE, MOST_TILE, TILE_OVER_MEAN = 32, 128, 8


def row_tile(rows_an_expert: float) -> int:
    """The row tile for experts that get ``rows_an_expert`` rows under
    an even router: the power of two at or above ``TILE_OVER_MEAN``
    times that, from 32 to 128.  A visit costs its expert's bytes, not
    its rows (``tm`` operations a byte against the chip's 240), and a
    run of rows that crosses a tile is a second visit: a tile of
    several times the mean keeps crossings few where routing is uneven;
    beyond 128 rows a visit's arithmetic no longer hides under its
    expert's DMA."""
    tm = LEAST_TILE
    while tm < min(TILE_OVER_MEAN * rows_an_expert, MOST_TILE):
        tm *= 2
    return tm


def cut(k: int, n: int, itemsize: int = 2,
        budget: int = PANEL_BYTES) -> tuple[int, int]:
    """(tk, tn): the widest panel of whole rows of an expert's (k, n)
    matrix within ``budget`` bytes — ``tn`` = n, so a panel is one
    contiguous stretch of the stack, and ``tk`` k halved while it is
    too large and stays a multiple of 128; a matrix too wide for 128
    whole rows is cut in columns too."""
    tn = n
    while 128 * tn * itemsize > budget and tn % 256 == 0:
        tn //= 2
    tk = k
    while tk * tn * itemsize > budget and tk % 256 == 0:
        tk //= 2
    return tk, tn


def panel(k: int, n: int, itemsize: int = 2) -> tuple[int, int]:
    """(tk, tn), the block of an expert's (k, n) matrix a visit step
    fetches: the WHOLE matrix where it is within ``WHOLE_BYTES`` — a
    visit costs its expert's bytes unless the block is the one already
    there, and with k in tiles it never is: every row tile a group's
    rows cross would read the expert again — else ``cut`` to
    ``PANEL_BYTES`` (an expert of tens of MB: two whole ones in flight
    are most of the core's VMEM, and its steps' rows are one tile)."""
    if k * n * itemsize <= WHOLE_BYTES:
        return k, n
    return cut(k, n, itemsize)


def tiling(rows_an_expert: float, k: int, n: int,
           itemsize: int = 2) -> tuple[int, int, int]:
    """(tm, tk, tn) for experts of (k, n) that get ``rows_an_expert``
    rows under an even router."""
    return (row_tile(rows_an_expert),) + panel(k, n, itemsize)


def visits_by_group(sizes, tm: int):
    """(groups,) int32: the row tiles of ``tm`` rows each group's run of
    rows touches, the groups' rows lying one behind the other from row
    0 — none for an empty group.  Their sum is the work list's length;
    times ``tm``, the rows the grouped product multiplies."""
    ends = jnp.cumsum(sizes)
    first = (ends - sizes) // tm
    last = (ends - 1) // tm
    return jnp.where(sizes > 0, last - first + 1, 0).astype(jnp.int32)


def visits(sizes, tm: int):
    """The work list's length for ``sizes``: the groups' visits, and
    with no row in any group still ONE, of an empty group (it stores
    nothing) — a grid of no step at all is not asked of the chip."""
    return jnp.maximum(jnp.sum(visits_by_group(sizes, tm)), 1)


def fetches(sizes, tm: int, k_tiles: int):
    """The expert matrices one product over ``sizes`` fetches: with k in
    ``k_tiles`` tiles its VISITS — a visit's first k tile is never the
    block the last left there, so a group's every row tile reads its
    expert again — and with the expert ONE block the groups HIT
    (consecutive visits of one group name the block already there); of
    no row in any group still one, the empty visit's."""
    if k_tiles > 1:
        return visits(sizes, tm)
    return jnp.maximum(jnp.sum(sizes > 0), 1)


def fetches_ahead(sizes, tm: int, k_tiles: int):
    """Of the expert matrices one product over ``sizes`` fetches, those
    whose copy starts under an EARLIER group's crossing visit: where the
    expert is one block the kernel starts a hit group's copy at its
    predecessor's FIRST visit, so every hit group with two or more
    visits and a hit group behind it moves that one's fetch forward,
    under visits the step-keyed pipeline left without a copy in flight;
    none with k in tiles (that pipeline is the path there)."""
    if k_tiles > 1:
        return jnp.int32(0)
    per_group = visits_by_group(sizes, tm)
    hit = (per_group > 0).astype(jnp.int32)
    behind = jnp.cumsum(hit[::-1])[::-1] - hit       # hit groups after one
    return jnp.sum((per_group >= 2) & (behind > 0)).astype(jnp.int32)


def work_list(sizes, m: int, tm: int):
    """The visits of ``sizes`` (groups,) over ``m`` rows in tiles of
    ``tm``: ``(offsets (groups + 1,), group of visit i, row tile of
    visit i, number of visits)``; the two lists are as long as there can
    be visits (tiles + groups - 1) and mean nothing behind the number.
    Visits are ordered by group, so a row tile's visits are consecutive
    and an output tile is complete when the grid leaves it."""
    groups = sizes.shape[0]
    tiles = pl.cdiv(m, tm)
    most = tiles + groups - 1
    ends = jnp.cumsum(sizes).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    per_group = visits_by_group(sizes, tm)
    before = jnp.cumsum(per_group) - per_group       # visits before a group
    group_ids = jnp.repeat(jnp.arange(groups, dtype=jnp.int32), per_group,
                           total_repeat_length=most)
    tile_ids = (offsets[group_ids] // tm
                + jnp.arange(most, dtype=jnp.int32) - before[group_ids])
    tile_ids = jnp.clip(tile_ids, 0, tiles - 1).astype(jnp.int32)
    return offsets, group_ids, tile_ids, visits(sizes, tm)


def _visit(offsets_ref, group_ids_ref, tile_ids_ref, lhs_ref, rhs_ref, held,
           out_ref, acc_ref, *, tm: int, k_tiles: int):
    """One grid step: the row tile times ``rhs_ref[held]``, the block of
    its visit's expert this step holds, into the visit's accumulator;
    behind the last k tile the store of the rows that are the visit's
    group's."""
    visit, k_i = pl.program_id(1), pl.program_id(2)

    @pl.when(k_i == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(lhs_ref[...], rhs_ref[held],
                            preferred_element_type=jnp.float32)

    @pl.when(k_i == k_tiles - 1)
    def _store():
        # the rows of this tile that are this visit's group's: the
        # others are another visit's, before or after it
        group = group_ids_ref[visit]
        row = tile_ids_ref[visit] * tm + jax.lax.broadcasted_iota(
            jnp.int32, acc_ref.shape, 0)
        mine = (row >= offsets_ref[group]) & (row < offsets_ref[group + 1])
        out_ref[...] = jnp.where(mine, acc_ref[...], out_ref[...])


def _kernel(offsets_ref, group_ids_ref, tile_ids_ref, layer_ref, lhs_ref,
            rhs_ref, out_ref, acc_ref, *, tm: int, k_tiles: int):
    """An expert in panels: the pipeline fetches a step's panel."""
    del layer_ref                                   # the index maps' alone
    _visit(offsets_ref, group_ids_ref, tile_ids_ref, lhs_ref, rhs_ref, ...,
           out_ref, acc_ref, tm=tm, k_tiles=k_tiles)


def fetch_plan(offsets, group_ids, visit, visits, tm: int):
    """What the by-group kernel does at ``visit`` of ``visits``, from the
    work list it has anyway: ``(whether the visit is its group's first,
    the next group hit — -1 behind the last)``, the second meaningful at
    a first visit alone: a group's visits are the row tiles its rows
    touch, and behind them comes the next hit group's first.
    ``offsets`` and ``group_ids``: anything a scalar indexes — the
    kernel's SMEM, a test's arrays."""
    group = group_ids[visit]
    first = (visit == 0) | (group != group_ids[jnp.maximum(visit - 1, 0)])
    # the row tiles its rows touch (``visits_by_group``, of scalars)
    its = (offsets[group + 1] - 1) // tm - offsets[group] // tm + 1
    behind = visit + jnp.maximum(its, 1)        # (the empty visit: one)
    ahead = jnp.where(behind < visits,
                      group_ids[jnp.minimum(behind, visits - 1)], -1)
    return first, ahead


def _kernel_by_group(offsets_ref, group_ids_ref, tile_ids_ref, layer_ref,
                     lhs_ref, stack_ref, out_ref, acc_ref, experts_ref,
                     arrived, turn_ref, *, tm: int):
    """An expert ONE block: the stack stays in HBM and the kernel copies
    experts into ``experts_ref`` (2, k, n) itself, by group
    (``fetch_plan``).  At a group's first visit it starts the NEXT hit
    group's copy into the other buffer — free: its last holder's visits
    all ended before this one began, the visits' axis being sequential —
    and waits for its own, which its predecessor's first visit started
    (the call's very first visit starts its own).  So a copy is in
    flight under every visit but the last group's, and each one started
    is waited for.  ``turn_ref``: the buffer of the group being visited,
    turned at every group's first visit."""
    visit = pl.program_id(1)
    group = group_ids_ref[visit]
    first, ahead = fetch_plan(offsets_ref, group_ids_ref, visit,
                              pl.num_programs(1), tm)

    def copy(group, buffer):
        return pltpu.make_async_copy(stack_ref.at[layer_ref[0], group],
                                     experts_ref.at[buffer],
                                     arrived.at[buffer])

    @pl.when(first)
    def _fetch():
        buffer = jnp.where(visit == 0, 0, 1 - turn_ref[0])
        turn_ref[0] = buffer

        @pl.when(visit == 0)
        def _mine():
            copy(group, buffer).start()

        @pl.when(ahead >= 0)
        def _ahead():
            copy(ahead, 1 - buffer).start()

        copy(group, buffer).wait()

    _visit(offsets_ref, group_ids_ref, tile_ids_ref, lhs_ref, experts_ref,
           turn_ref[0], out_ref, acc_ref, tm=tm, k_tiles=1)


@functools.partial(jax.jit, static_argnames=("tiling", "interpret"))
def grouped_matmul(lhs, rhs, sizes, layer=0, *,
                   tiling: tuple[int, int, int], interpret: bool = False):
    """``lhs`` (m, k) rows sorted by group, ``rhs`` (layers, groups, k,
    n) of which layer ``layer`` (traced) is meant, ``sizes`` (groups,)
    int32 rows of each group, from row 0 on -> (m, n) float32: row r of
    group g is ``lhs[r] @ rhs[layer, g]``.  Rows behind the groups are
    not computed: they hold whatever was there.  ``tiling`` = (tm, tk,
    tn), tm a multiple of 16, tk and tn multiples of 128 that divide k
    and n (or k and n themselves); with (tk, tn) the whole expert the
    kernel fetches experts by group (``_kernel_by_group``), else the
    pipeline a panel a step."""
    m, k = lhs.shape
    n = rhs.shape[-1]
    tm, tk, tn = tiling
    if k % tk or n % tn or tm % MIN_ROWS:
        raise ValueError(
            f"grouped_matmul: tiling {tiling} does not tile ({m}, {k}) x "
            f"({k}, {n}): tm must be a multiple of {MIN_ROWS}, tk and tn "
            "must divide k and n")
    padded = pl.cdiv(m, tm) * tm
    if padded != m:                 # whole row tiles: rows of no group
        lhs = jnp.pad(lhs, ((0, padded - m), (0, 0)))
    offsets, group_ids, tile_ids, visits = work_list(sizes, padded, tm)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    k_tiles = k // tk
    # two experts' blocks in VMEM either way: the pipeline's double
    # buffer, or the kernel's own two
    blocks = 2 * (tm * tk * lhs.dtype.itemsize + tk * tn * rhs.dtype.itemsize
                  + tm * tn * 4) + tm * tn * 4
    scratch = [pltpu.VMEM((tm, tn), jnp.float32)]
    if (tk, tn) == (k, n):
        kernel = functools.partial(_kernel_by_group, tm=tm)
        rhs_spec = pl.BlockSpec(memory_space=pl.ANY)
        scratch += [pltpu.VMEM((2, k, n), rhs.dtype),
                    pltpu.SemaphoreType.DMA((2,)),
                    pltpu.SMEM((1,), jnp.int32)]
    else:
        kernel = functools.partial(_kernel, tm=tm, k_tiles=k_tiles)
        rhs_spec = pl.BlockSpec(
            (None, None, tk, tn),
            lambda n_i, v, k_i, offsets, groups, tiles, layer: (
                layer[0], groups[v], k_i, n_i))
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((padded, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n_i, v, k_i, offsets, groups,
                             tiles, layer: (tiles[v], k_i)),
                rhs_spec,
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda n_i, v, k_i, offsets, groups, tiles,
                layer: (tiles[v], n_i)),
            grid=(n // tn, visits, k_tiles),
            scratch_shapes=scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=blocks + (8 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * k * lhs.dtype.itemsize + m * n * 4
                            + sizes.shape[0] * k * n * rhs.dtype.itemsize)),
        name="grouped_matmul", interpret=interpret,
    )(offsets, group_ids, tile_ids, layer, lhs, rhs)
    return out[:m]
