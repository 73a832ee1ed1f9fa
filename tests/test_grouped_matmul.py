"""``ops/pallas/grouped_matmul.py`` in the Pallas interpreter against
``lax.ragged_dot``, which the step programs ran before it and which
``forward`` / ``loss_fn`` keep: the same rows by the same experts, the
whole stack with a traced layer, a share's rows of no group, empty
groups, ragged row counts, tiles that hold many groups — and that a
row's sum is bit for bit its own whatever rows share its tile or its
batch.  Then the step programs through it: the same logits as through
XLA's kernel, and the counter that says what the work list visited.
(That the kernel compiles for the chip, at the cells' shapes and with
the stack read in place: ``tests/test_tpu_compile.py``.)
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ant_ray_tpu.llm.programs import step_programs
from ant_ray_tpu.models import llama
from ant_ray_tpu.ops.pallas import grouped_matmul as gm

LAYERS, K, N = 3, 256, 384

# name: (rows, sizes of the groups from row 0 on, (tm, tk, tn))
CASES = {
    "all-held": (64, [16, 16, 16, 16], (16, 256, 384)),
    "all-held-ragged-groups": (64, [5, 30, 2, 27], (16, 256, 384)),
    "a-share-rows-of-no-group-behind": (96, [7, 11, 3, 9], (16, 256, 384)),
    "empty-groups": (64, [0, 20, 0, 0, 9, 0], (16, 256, 384)),
    "first-and-last-groups-empty": (48, [0, 0, 13, 21, 0], (32, 256, 384)),
    "no-row-in-any-group": (32, [0, 0, 0, 0], (16, 256, 384)),
    "rows-not-a-multiple-of-the-tile": (40, [9, 14, 10], (16, 256, 384)),
    "rows-under-one-tile": (8, [3, 0, 4], (16, 256, 384)),
    "a-tile-straddles-three-groups": (64, [3, 4, 5, 20, 1, 1, 1], (
        16, 256, 384)),
    "one-group-over-many-tiles": (128, [2, 97, 5], (16, 256, 384)),
    "k-in-tiles": (64, [5, 30, 2, 20], (16, 128, 384)),
    "k-and-n-in-tiles": (64, [5, 30, 2, 20], (32, 128, 128)),
    "a-tile-as-tall-as-the-rows": (64, [5, 30, 2, 20], (64, 256, 384)),
}


def _operands(rows, groups, dtype=jnp.bfloat16):
    lhs = jax.random.normal(jax.random.PRNGKey(1), (rows, K), dtype)
    rhs = jax.random.normal(jax.random.PRNGKey(2), (LAYERS, groups, K, N),
                            dtype) * K ** -0.5
    return lhs, rhs


def _kernel(tiling):
    return jax.jit(lambda lhs, rhs, sizes, layer: gm.grouped_matmul(
        lhs, rhs, sizes, layer, tiling=tiling, interpret=True))


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("name", CASES)
def test_kernel_equals_ragged_dot_on_the_rows_of_a_group(name, layer):
    """Layer ``layer`` of the whole stack, the index traced: every row
    of a group is what ``lax.ragged_dot`` gives against that layer's
    experts alone (float32 sums of bf16 products; the k tiles' partial
    sums round apart by a few units in the last place)."""
    rows, sizes, tiling = CASES[name]
    sizes = jnp.asarray(sizes, jnp.int32)
    lhs, rhs = _operands(rows, sizes.shape[0])
    got = _kernel(tiling)(lhs, rhs, sizes, jnp.int32(layer))
    want = lax.ragged_dot(lhs, rhs[layer], sizes,
                          preferred_element_type=jnp.float32)
    assert got.shape == want.shape and got.dtype == jnp.float32
    real = int(sizes.sum())
    np.testing.assert_allclose(np.asarray(got)[:real],
                               np.asarray(want)[:real], rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("name", CASES)
def test_work_list_visits_every_tile_a_group_touches_once(name):
    """The work list against the pairs counted row by row: (row tile,
    group) for every row of a group, in the groups' order; tiles of a
    run are consecutive, so an output tile is left complete."""
    rows, sizes, (tm, _, _) = CASES[name]
    padded = -(-rows // tm) * tm
    offsets, group_ids, tile_ids, visits = gm.work_list(
        jnp.asarray(sizes, jnp.int32), padded, tm)
    group_of_row = np.repeat(np.arange(len(sizes)), sizes)
    want = sorted({(int(g), r // tm) for r, g in enumerate(group_of_row)})
    got = list(zip(np.asarray(group_ids).tolist(),
                   np.asarray(tile_ids).tolist()))[:int(visits)]
    if want:
        assert got == want
        assert int(gm.visits_by_group(jnp.asarray(sizes), tm).sum()) == len(
            want)
    else:                       # one visit that stores nothing
        assert len(got) == 1 and sizes[got[0][0]] == 0
    assert group_ids.shape == tile_ids.shape == (
        padded // tm + len(sizes) - 1,)
    np.testing.assert_array_equal(np.asarray(offsets),
                                  np.concatenate([[0], np.cumsum(sizes)]))
    assert np.all(np.diff(np.asarray(tile_ids)[:int(visits)]) >= 0)


# hand-made sizes in tiles of 16: name -> (rows, sizes, per visit: its
# group and, at the group's first visit, the group it fetches ahead —
# -1 behind the last — else None; fetches moved forward)
PLANS = {
    "empty-groups-first-last-and-between": (
        64, [0, 20, 0, 0, 9, 0],        # rows 0-19: tiles 0, 1 | 20-28: 1
        [(1, 4), (1, None), (4, -1)], 1),
    "one-group-over-three-tiles": (
        64, [10, 32, 22],               # 0-9 | 10-41: 0, 1, 2 | 42-63: 2, 3
        [(0, 1), (1, 2), (1, None), (1, None), (2, -1), (2, None)], 1),
    "every-group-crosses": (
        96, [20, 0, 30, 40],            # 0-19: 0, 1 | 20-49: 1, 2, 3 | 50-89
        [(0, 2), (0, None), (2, 3), (2, None), (2, None), (3, -1),
         (3, None), (3, None)], 2),
    "one-group-only": (
        48, [0, 0, 40, 0], [(2, -1), (2, None), (2, None)], 0),
    "no-crossing": (
        64, [16, 16, 16, 16], [(0, 1), (1, 2), (2, 3), (3, -1)], 0),
    # the ONE visit of an empty group (whichever the list names): it
    # fetches its own, nothing ahead
    "no-row-at-all": (32, [0, 0, 0, 0], [(None, -1)], 0),
}


@pytest.mark.parametrize("name", PLANS)
def test_fetch_plan_is_by_group(name):
    """What the by-group kernel does at a visit, from the work list's
    own ``offsets`` and ``group_ids``: a group's first visit fetches,
    and ahead of its turn the next group HIT — the empty ones skipped,
    -1 behind the last; ``fetches_ahead`` counts the hit groups with a
    crossing visit and a hit group behind them — none where k is in
    tiles."""
    rows, sizes, want, ahead = PLANS[name]
    sizes = jnp.asarray(sizes, jnp.int32)
    offsets, group_ids, _, visits = gm.work_list(sizes, rows, 16)
    offsets, group_ids = np.asarray(offsets), np.asarray(group_ids)
    assert int(visits) == len(want)
    started = None              # the copy in flight, by its group
    for visit, (group, ahead_of) in enumerate(want):
        first, got_ahead = gm.fetch_plan(offsets, group_ids, visit,
                                         len(want), 16)
        assert bool(first) == (ahead_of is not None), visit
        if group is None:
            assert sizes[group_ids[visit]] == 0
        else:
            assert group_ids[visit] == group
        if first:
            # every copy started is waited for, by the group it is of
            assert started in (None, group_ids[visit])
            assert int(got_ahead) == ahead_of, visit
            started = None if ahead_of < 0 else ahead_of
    assert started is None
    assert int(gm.fetches_ahead(sizes, 16, 1)) == ahead
    assert int(gm.fetches_ahead(sizes, 16, 2)) == 0


@pytest.mark.parametrize("layer", [1, 2])
@pytest.mark.parametrize("name", PLANS)
def test_fetched_by_group_every_row_is_its_experts_own_product(name, layer):
    """The by-group kernel (the whole expert one block) at the plans'
    sizes, a layer that is not the stack's first: every row of a group
    has bit for bit ``lhs[r] @ rhs[layer, g]`` — ONE float32 dot over
    the whole k, so no copy landed in the wrong buffer, came late or
    from another layer."""
    rows, sizes, _, _ = PLANS[name]
    lhs, rhs = _operands(rows, len(sizes))
    got = np.asarray(_kernel((16, K, N))(
        lhs, rhs, jnp.asarray(sizes, jnp.int32), jnp.int32(layer)))
    dot = jax.jit(lambda a, w: jnp.dot(a, w,
                                       preferred_element_type=jnp.float32))
    first = 0
    for group, size in enumerate(sizes):
        if size:
            # the same (16, K) x (K, N) product the kernel's visit makes
            want = np.concatenate([np.asarray(dot(
                jnp.pad(lhs[at:at + 16], ((0, 16 - len(lhs[at:at + 16])),
                                          (0, 0))), rhs[layer, group]))
                for at in range(first, first + size, 16)])[:size]
            np.testing.assert_array_equal(_bits(got[first:first + size]),
                                          _bits(want), str(group))
        first += size


@pytest.mark.parametrize("tiling", [(16, 256, 384), (32, 128, 384),
                                    (64, 128, 128)], ids=str)
def test_a_rows_sum_is_its_own_whatever_shares_its_batch(tiling):
    """The same 12 rows of expert 2 — alone in the batch, in the middle
    of a full one (another place in another tile, other neighbours), and
    behind an expert that now has rows — give the same bits."""
    mine = jax.random.normal(jax.random.PRNGKey(5), (12, K), jnp.bfloat16)
    other = jax.random.normal(jax.random.PRNGKey(6), (200, K), jnp.bfloat16)
    _, rhs = _operands(1, 4)
    kernel = _kernel(tiling)

    def rows_of_expert_2(before, behind, total):
        """``before`` rows of experts 0 and 1 in front, ``behind`` rows
        of expert 3 after, padded to ``total`` rows."""
        lhs = jnp.concatenate([other[:before], mine,
                               other[before:before + behind]])
        lhs = jnp.pad(lhs, ((0, total - lhs.shape[0]), (0, 0)))
        sizes = jnp.asarray([before // 2, before - before // 2, 12, behind],
                            jnp.int32)
        return np.asarray(kernel(lhs, rhs, sizes, jnp.int32(1)))[
            before:before + 12]

    alone = rows_of_expert_2(0, 0, 64)
    np.testing.assert_array_equal(alone, rows_of_expert_2(37, 50, 128))
    np.testing.assert_array_equal(alone, rows_of_expert_2(8, 0, 32))
    np.testing.assert_array_equal(alone, rows_of_expert_2(100, 88, 200))


# the rule, at the shapes of the benchmark's routed cells: (tokens of the
# step, k a token, router width, an expert's (in, out)) -> tiling
@pytest.mark.parametrize("tokens,k,width,expert,want", [
    (16, 8, 64, (2048, 1024), (32, 2048, 1024)),
    (64, 8, 64, (1024, 2048), (64, 1024, 2048)),
    (48, 8, 192, (7168, 2048), (32, 896, 2048)),
    (64, 8, 192, (2048, 7168), (32, 256, 7168)),
    (16, 8, 128, (4096, 4096), (32, 512, 4096)),
    (512, 8, 128, (4096, 4096), (128, 512, 4096)),
    # PR 63: an expert within ``WHOLE_BYTES`` is one block — granite's
    # decode step and chunk, xing's, solar-open2's, gate / up and down
    (48, 10, 72, (4096, 768), (64, 4096, 768)),
    (48, 10, 72, (768, 4096), (64, 768, 4096)),
    (512, 10, 72, (4096, 768), (128, 4096, 768)),
    (512, 10, 72, (768, 4096), (128, 768, 4096)),
    (16, 4, 64, (3584, 1024), (32, 3584, 1024)),
    (16, 4, 64, (1024, 3584), (32, 1024, 3584)),
    (512, 4, 64, (3584, 1024), (128, 3584, 1024)),
    (512, 4, 64, (1024, 3584), (128, 1024, 3584)),
    (24, 8, 320, (4096, 1280), (32, 4096, 1280)),
    (24, 8, 320, (1280, 4096), (32, 1280, 4096)),
    (512, 8, 320, (4096, 1280), (128, 4096, 1280)),
    (512, 8, 320, (1280, 4096), (128, 1280, 4096)),
], ids=str)
def test_tiling_follows_the_rows_an_expert_gets(tokens, k, width, expert,
                                                want):
    """``tm`` from eight times rows * k over the router's width, from 32
    to 128; the whole expert where it is within ``WHOLE_BYTES``, else a
    panel of whole rows within ``PANEL_BYTES``: a function of shapes
    alone (``benchmarks/grouped_product``'s sweeps on the chip:
    ``PERF.md`` section 6, PR 37 and PR 63)."""
    got = gm.tiling(tokens * k / width, *expert)
    assert got == want
    tm, tk, tn = got
    if expert[0] * expert[1] * 2 <= gm.WHOLE_BYTES:
        assert (tk, tn) == expert
    else:
        assert tk * tn * 2 <= gm.PANEL_BYTES and expert[0] % tk == 0
    assert tm % gm.MIN_ROWS == 0 and tk % 128 == 0 and tn % 128 == 0


def test_a_group_over_three_tiles_of_a_whole_expert_keeps_its_rows_bits():
    """Under a whole-expert tiling (tk = k, tn = n) a group that
    straddles three row tiles is three visits of ONE block: each of its
    rows has the bits the same row has with the group alone in one tile
    of (tm, k, n) — a row's sum is one dot over the whole k, whatever
    tile it lies in and whatever was fetched before it."""
    tm = 16
    lhs, rhs = _operands(64, 3)
    kernel = _kernel((tm, K, N))
    # rows 0-9 expert 0 | 10-41 expert 1: tiles 0, 1, 2 | 42-63 expert 2
    sizes = jnp.asarray([10, 32, 22], jnp.int32)
    assert gm.visits_by_group(sizes, tm).tolist() == [1, 3, 2]
    among = np.asarray(kernel(lhs, rhs, sizes, jnp.int32(2)))[10:42]
    for first in (10, 26):          # the same rows, alone, a tile at a time
        alone = np.asarray(kernel(
            lhs[first:first + tm], rhs, jnp.asarray([0, tm, 0], jnp.int32),
            jnp.int32(2)))
        np.testing.assert_array_equal(
            _bits(among[first - 10:first - 10 + tm]), _bits(alone))


def test_fetches_are_visits_where_k_is_tiled_and_groups_hit_where_not():
    """``fetches``: a product's visits with k in tiles, the groups hit
    with the expert one block, one for no row at all — and at sessions'
    chunk shape (512 tokens, a top-10 of 72 with 36 held, tiles of 128)
    the visits are 1.4 to 1.65 times the groups hit: what reading an
    expert once a call saves there."""
    sizes = jnp.asarray([3, 0, 20, 1, 40], jnp.int32)
    # rows 0-2 | 3-22 | 23 | 24-63 in tiles of 16: 1 + 2 + 1 + 3 visits
    assert int(gm.fetches(sizes, 16, 2)) == int(gm.visits(sizes, 16)) == 7
    assert int(gm.fetches(sizes, 16, 1)) == 4
    none = jnp.zeros((5,), jnp.int32)
    assert int(gm.fetches(none, 16, 2)) == int(gm.fetches(none, 16, 1)) == 1
    _, picks = lax.top_k(jax.random.normal(jax.random.PRNGKey(63),
                                           (512, 72)), 10)
    held = jnp.zeros((36,), jnp.int32).at[picks.reshape(-1)].add(
        1, mode="drop")
    tm = gm.row_tile(512 * 10 / 72)
    visits, hit = int(gm.fetches(held, tm, 2)), int(gm.fetches(held, tm, 1))
    assert tm == 128 and hit == 36
    assert 1.4 <= visits / hit <= 1.65


def test_a_tiling_that_does_not_tile_is_refused():
    lhs, rhs = _operands(32, 2)
    with pytest.raises(ValueError, match="does not tile"):
        gm.grouped_matmul(lhs, rhs, jnp.asarray([16, 16], jnp.int32), 0,
                          tiling=(16, 96, 384), interpret=True)
    with pytest.raises(ValueError, match="does not tile"):
        gm.grouped_matmul(lhs, rhs, jnp.asarray([16, 16], jnp.int32), 0,
                          tiling=(8, 256, 384), interpret=True)


# ------------------------------------------------ through ``_routed_mlp``

def _routed_layer(name):
    """``(the routed stack's config, its layer 1 with the whole stack's
    expert matrices, weights x 8 so that the routing is uneven)``."""
    cfg = llama.CONFIGS[name].stacks()["layers"]
    stack = llama.init_params(llama.CONFIGS[name],
                              jax.random.PRNGKey(3))["layers"]
    return cfg, {**{k: v[1] * 8 for k, v in stack.items()},
                 **{k: stack[k] * 8 for k in ("w_gate", "w_up", "w_down")}}


@pytest.mark.parametrize("name", ["moe-tiny", "olmoe-tiny", "axk1-tiny",
                                  "cmdaplus-tiny"])
@pytest.mark.parametrize("rows", [5, 48])
def test_routed_mlp_through_the_kernel_is_what_ragged_dot_gives(name, rows):
    """The whole routed feed-forward of the step programs — all experts
    held, and a share whose absent experts' rows sort behind — with the
    kernel (tile 16) against XLA's product, the stack whole and the
    layer traced; and the same ``load``."""
    cfg, layer = _routed_layer(name)
    h = jax.random.normal(jax.random.PRNGKey(4), (rows, cfg.dim))

    def run(tile):
        return jax.jit(lambda i: llama._routed_mlp(layer, h, cfg, i, tile))(
            jnp.int32(1))

    got, want = run(16), run(0)
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=2e-5, atol=2e-6)
    assert np.isfinite(np.asarray(got[0])).all()


@pytest.mark.parametrize("name", ["axk1-tiny", "cmdaplus-tiny",
                                  "granite-h-tiny"])
def test_rows_of_no_group_may_hold_anything(name, monkeypatch):
    """A share's rows behind the last group are never visited: the
    kernel's output holds there whatever the buffer held.  With every
    such row of all three products set to NaN the routed feed-forward is
    what XLA's product gives — the way back to token order drops them
    by a select, not by a product with 0."""
    kernel = gm.grouped_matmul

    def poisoned(lhs, rhs, sizes, layer=0, **how):
        out = kernel(lhs, rhs, sizes, layer, **how)
        return jnp.where((jnp.arange(out.shape[0]) < jnp.sum(sizes))[:, None],
                         out, jnp.nan)

    cfg, layer = _routed_layer(name)
    h = jax.random.normal(jax.random.PRNGKey(4), (48, cfg.dim))

    def run(tile):
        return jax.jit(lambda i: llama._routed_mlp(layer, h, cfg, i, tile))(
            jnp.int32(1))

    want = run(0)
    monkeypatch.setattr(gm, "grouped_matmul", poisoned)
    got = run(16)
    assert int(jnp.sum(got[1])) < 48 * cfg.experts_per_token   # some absent
    assert np.isfinite(np.asarray(got[0])).all()
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("name", ["olmoe-tiny", "axk1-tiny",
                                  "cmdaplus-tiny"])
def test_step_programs_through_the_kernel(name, monkeypatch):
    """A chunk and three decode steps with the kernel's tile forced (on
    the CPU ``_grouped_tile`` keeps XLA's product) give the logits of
    the same programs without it, and ``moe_tile_rows`` counts tile x
    visits by the work list's own rule where it stays 0 without, as
    ``moe_expert_reads`` does."""
    cfg = llama.CONFIGS[name]
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (16,), 0, 256)
    counted = llama.ROUTING_COUNTERS.index

    def run(tile):
        monkeypatch.setattr(llama, "_grouped_tile",
                            lambda c, rows, mesh: tile if c.num_experts
                            else 0)
        cache = llama.init_kv_cache(cfg, 4, 64, chunk=16)
        # (built anew: traced under THIS tile)
        steps = step_programs(cfg, slots=4, max_seq=64, chunk=16)
        decode = steps.decode
        logits, cache = steps.prefill_chunk(params, cache, tokens, 1, 0, 11)
        out = [logits]
        active = jnp.asarray([False, True, False, False])
        for _ in range(3):
            logits, cache = decode(
                params, cache, jnp.argmax(out[-1], axis=-1).reshape(
                    -1)[:1].repeat(4).astype(jnp.int32), active)
            out.append(logits[1])
        return np.stack([np.asarray(o).reshape(-1) for o in out]), \
            np.asarray(cache["routing"])

    got, got_routing = run(16)
    want, want_routing = run(0)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    tiled = np.isin(np.arange(len(got_routing)), [
        counted("moe_tile_rows"), counted("moe_expert_reads"),
        counted("moe_fetches_ahead")])
    np.testing.assert_array_equal(got_routing[~tiled], want_routing[~tiled])
    assert (want_routing[tiled] == 0).all()
    # a tiny expert is one block: every expert hit is read once a product
    # (and of a layer-step with no held pick, the empty visit's)
    reads = int(got_routing[counted("moe_expert_reads")])
    hit = int(got_routing[counted("moe_experts_hit")])
    assert 3 * hit <= reads <= 3 * (hit + 4 * cfg.n_layers)
    tile_rows = int(got_routing[counted("moe_tile_rows")])
    assert tile_rows % 16 == 0
    routed_layers = cfg.stacks()["layers"].n_layers
    assert tile_rows >= 16 * routed_layers * 4      # a visit a layer at least
    assert tile_rows >= int(got_routing[counted("moe_assignments")])


def test_tile_rows_are_the_work_lists_visits():
    """``_count_routing`` with a tile: tile x the visits of each layer's
    loads, one at least a layer (the kernel's grid is never empty)."""
    loads = jnp.asarray([[3, 0, 20, 1], [0, 0, 0, 0], [16, 16, 0, 1]],
                        jnp.int32)
    cache = {"routing": jnp.zeros((len(llama.ROUTING_COUNTERS),),
                                  jnp.uint32)}
    tile_rows = llama.ROUTING_COUNTERS.index("moe_tile_rows")
    seen = np.asarray(llama._count_routing(
        cache, loads, 99, True, 16, 0, (256, 384, 2))["routing"])
    # layer 0: rows 0-2 | 3-22 | 23 -> tiles 0 | 0, 1 | 1 = 4 visits;
    # layer 1: none -> 1; layer 2: 0 | 1 | 2 = 3
    assert seen[tile_rows] == 16 * 8
    assert seen[0] == 57
    none = np.asarray(llama._count_routing(cache, loads, 99, True, 0)[
        "routing"])
    assert none[tile_rows] == 0
    kernels = [tile_rows, llama.ROUTING_COUNTERS.index("moe_expert_reads"),
               llama.ROUTING_COUNTERS.index("moe_fetches_ahead")]
    assert (np.delete(none, kernels) == np.delete(seen, kernels)).all()


@pytest.mark.parametrize("expert,want,want_ahead", [
    # an expert of 256 x 384 bf16 is one block in all three products:
    # the groups hit, 3 | 1 (the empty visit's) | 3, three times; ahead
    # of its turn comes expert 3 of layer 0 (expert 2 crosses a tile),
    # of layer 2 none (16 | 16 | 1: a visit each)
    ((256, 384, 2), 3 * 7, 3 * 1),
    # A.X-K1's: k in 8 tiles, gate / up and down: the visits, 4 | 1 | 3
    ((7168, 2048, 2), 3 * 8, 0),
], ids=["one-block", "k-in-tiles"])
def test_expert_reads_are_what_the_kernels_rule_fetches(expert, want,
                                                        want_ahead):
    """``_count_routing``'s ``moe_expert_reads``: ``gm.fetches`` of each
    layer's loads for gate, up and down, the k tiles ``gm.panel``'s at
    the expert's shape, and ``moe_fetches_ahead`` ``gm.fetches_ahead``
    of the same; 0 under XLA's kernel."""
    loads = jnp.asarray([[3, 0, 20, 1], [0, 0, 0, 0], [16, 16, 0, 1]],
                        jnp.int32)
    cache = {"routing": jnp.zeros((len(llama.ROUTING_COUNTERS),),
                                  jnp.uint32)}
    reads = llama.ROUTING_COUNTERS.index("moe_expert_reads")
    ahead = llama.ROUTING_COUNTERS.index("moe_fetches_ahead")
    seen = np.asarray(llama._count_routing(
        cache, loads, 99, True, 16, 0, expert)["routing"])
    assert (seen[reads], seen[ahead]) == (want, want_ahead)
    assert [int(gm.fetches_ahead(sizes, 16, 1)) for sizes in loads] == [
        1, 0, 0]
    none = np.asarray(llama._count_routing(
        cache, loads, 99, True, 0, 0, expert)["routing"])
    assert none[reads] == none[ahead] == 0


def test_grouped_tile_keeps_ragged_dot_off_the_tpu_and_under_a_mesh(
        monkeypatch):
    cfg = llama.CONFIGS["olmoe-tiny"]
    assert llama._grouped_tile(cfg, 16, None) == 0          # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert llama._grouped_tile(cfg, 16, None) == 32        # 8 * 16 * 2 / 8
    assert llama._grouped_tile(cfg, 16, object()) == 0      # a mesh
    assert llama._grouped_tile(llama.CONFIGS["tiny"], 16, None) == 0
    wide = dataclasses.replace(cfg, num_experts=16, experts_per_token=8,
                               router_width=128)
    assert llama._grouped_tile(wide, 16, None) == 32        # 8 * 1: the least
    assert llama._grouped_tile(wide, 128, None) == 64       # 8 * 128 * 8 / 128
    assert llama._grouped_tile(wide, 512, None) == 128      # the most


# ------------------------------------------------- rows that nobody reads

# a share's (absent experts' picks are of no group already) and models
# that hold every expert; through the interpreter's kernel and XLA's
MASKED = ["cmdaplus-tiny", "axk1-tiny", "olmoe-tiny", "xing4-tiny"]
BOTH_PRODUCTS = pytest.mark.parametrize("tile", [16, 0],
                                        ids=["kernel", "ragged_dot"])


def _bits(x):
    return np.asarray(x, np.float32).view(np.uint32)


@BOTH_PRODUCTS
@pytest.mark.parametrize("name", MASKED)
def test_a_row_nobody_reads_reaches_no_expert(name, tile):
    """``_routed_mlp`` with ``live``: a live row's sum is bit for bit
    the same call's without the mask, a dead row comes back as zeros,
    and ``load`` counts the live rows' held picks alone — what the same
    block gives the live rows by themselves."""
    cfg, layer = _routed_layer(name)
    h = jax.random.normal(jax.random.PRNGKey(4), (48, cfg.dim))
    live = jax.random.bernoulli(jax.random.PRNGKey(5), 0.3, (48,))
    assert 4 < int(live.sum()) < 24
    run = jax.jit(lambda h, live: llama._routed_mlp(
        layer, h, cfg, jnp.int32(1), tile, live))
    got, load = run(h, live)
    want, every = run(h, None)
    on = np.asarray(live)
    np.testing.assert_array_equal(_bits(got)[on], _bits(want)[on])
    assert (np.asarray(got)[~on] == 0).all()
    _, alone = run(h[on], None)
    np.testing.assert_array_equal(np.asarray(load), np.asarray(alone))
    assert int(load.sum()) < int(every.sum())
    assert (np.asarray(load) <= np.asarray(every)).all()
    # every row live: the mask changes nothing
    full, same = run(h, jnp.ones((48,), bool))
    np.testing.assert_array_equal(_bits(full), _bits(want))
    np.testing.assert_array_equal(np.asarray(same), np.asarray(every))


@BOTH_PRODUCTS
@pytest.mark.parametrize("name", ["cmdaplus-tiny", "olmoe-tiny"])
def test_no_row_live_is_one_empty_visit(name, tile):
    """All rows dead (the mixed program's empty run in a server's
    warm-up): no group has a row, the kernel makes its ONE visit of an
    empty group, and zeros come back — no NaN from rows never stored."""
    cfg, layer = _routed_layer(name)
    h = jax.random.normal(jax.random.PRNGKey(4), (48, cfg.dim))
    out, load = jax.jit(lambda: llama._routed_mlp(
        layer, h, cfg, jnp.int32(1), tile, jnp.zeros((48,), bool)))()
    assert int(load.sum()) == 0 and int(gm.visits(load, 16)) == 1
    assert (np.asarray(out) == 0).all()


def _without_the_mask(monkeypatch):
    """The parent's formulation: the routed block never learns which
    rows are live."""
    routed = llama._routed_mlp
    monkeypatch.setattr(
        llama, "_routed_mlp",
        lambda layer, h, c, index=None, tile=0, live=None: routed(
            layer, h, c, index, tile))


def _force_tile(monkeypatch, tile):
    monkeypatch.setattr(llama, "_grouped_tile",
                        lambda c, rows, mesh: tile if c.num_experts else 0)


def _counted(before, after):
    return dict(zip(llama.ROUTING_COUNTERS, (
        np.asarray(after["routing"]).astype(np.int64)
        - np.asarray(before["routing"]).astype(np.int64)).tolist()))


def _same_but_the_counters(got, want):
    assert got.keys() == want.keys()
    for name in got:
        if name != "routing":
            np.testing.assert_array_equal(np.asarray(got[name]),
                                          np.asarray(want[name]), name)


@BOTH_PRODUCTS
@pytest.mark.parametrize("name", MASKED)
def test_a_decode_steps_idle_slots_reach_no_expert(name, tile, monkeypatch):
    """``_decode`` with 2 of 8 slots active behind a prompt each: the
    active rows' logits and everything the step writes are the bits of
    the program whose routed block takes no mask; the six idle slots'
    pairs are counted dead and hit no expert."""
    cfg = llama.CONFIGS[name]
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    size = {"slots": 8, "max_seq": 64, "chunk": 16}
    active = jnp.asarray([s in (1, 5) for s in range(8)])
    last = jax.random.randint(jax.random.PRNGKey(2), (8,), 0, 256)

    def run():
        _force_tile(monkeypatch, tile)
        steps = step_programs(cfg, **size)      # traced under the patches
        cache = llama.init_kv_cache(cfg, 8, 64, chunk=16)
        for slot, n in ((1, 16), (5, 9)):
            tokens = jax.random.randint(jax.random.PRNGKey(slot), (16,), 0,
                                        256)
            _, cache = steps.prefill_chunk(params, cache, tokens, slot, 0, n)
        before = {"routing": np.asarray(cache["routing"])}
        logits, cache = steps.decode(params, cache, last, active)
        return np.asarray(logits), cache, _counted(before, cache)

    got, cache, counted = run()
    _without_the_mask(monkeypatch)
    want, parent_cache, _ = run()
    on = np.asarray(active)
    np.testing.assert_array_equal(_bits(got)[on], _bits(want)[on])
    _same_but_the_counters(cache, parent_cache)
    layers = cfg.stacks()["layers"].n_layers
    pairs = cfg.experts_per_token * layers
    assert counted["moe_decode_rows_routed"] == 2 * pairs
    assert counted["moe_rows_routed"] == 2 * pairs
    assert counted["moe_dead_pairs"] == 6 * pairs
    assert 0 < counted["moe_decode_experts_hit"] <= 2 * pairs
    assert counted["moe_decode_assignments"] <= 2 * pairs
    assert counted["moe_decode_assignments"] == counted["moe_assignments"]


@BOTH_PRODUCTS
@pytest.mark.parametrize("name", MASKED)
def test_a_chunks_padding_reaches_no_expert(name, tile, monkeypatch):
    """``_prefill_chunk`` with 8 real tokens of 16: the logits at the
    last real token and the cache are the unmasked program's bits, and
    the padding's pairs are counted dead."""
    cfg = llama.CONFIGS[name]
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (16,), 0, 256)

    def run():
        _force_tile(monkeypatch, tile)
        steps = step_programs(cfg, slots=4, max_seq=64, chunk=16)
        cache = llama.init_kv_cache(cfg, 4, 64, chunk=16)
        before = {"routing": np.asarray(cache["routing"])}
        logits, cache = steps.prefill_chunk(params, cache, tokens, 2, 0, 8)
        return np.asarray(logits), cache, _counted(before, cache)

    got, cache, counted = run()
    _without_the_mask(monkeypatch)
    want, parent_cache, _ = run()
    np.testing.assert_array_equal(_bits(got), _bits(want))
    _same_but_the_counters(cache, parent_cache)
    layers = cfg.stacks()["layers"].n_layers
    pairs = cfg.experts_per_token * layers
    assert counted["moe_rows_routed"] == 8 * pairs
    assert counted["moe_dead_pairs"] == 8 * pairs
    assert 0 < counted["moe_experts_hit"] <= 8 * pairs
    assert counted["moe_assignments"] <= 8 * pairs
    assert counted["moe_decode_rows_routed"] == 0


@BOTH_PRODUCTS
def test_a_block_steps_dead_closing_rows_reach_no_expert(tile, monkeypatch):
    """``_block_decode`` with four slots in flight, ONE of them with a
    block closing, and no chunk: the logits and the cache are the
    unmasked program's bits; live are the 4 blocks in flight and 1
    closing, dead the other 3 closing blocks and the chunk's 8 rows."""
    cfg = llama.CONFIGS["sdar-tiny"]
    size, block = {"slots": 4, "max_seq": 64, "chunk": 8}, cfg.block_length
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    key = jax.random.split(jax.random.PRNGKey(7), 3)
    blocks = jax.random.randint(key[0], (4, block), 0, 255)
    closed = jax.random.randint(key[1], (4, block), 0, 255)
    masked = jax.random.bernoulli(key[2], 0.5, (4, block))
    closing = jnp.asarray([False, True, False, False])
    active = jnp.ones((4,), bool)

    def run():
        _force_tile(monkeypatch, tile)
        steps = step_programs(cfg, **size)
        cache = llama.init_kv_cache(cfg, 4, 64, chunk=8)
        nothing = (jnp.zeros((4, block), jnp.int32), jnp.zeros((4,), bool))
        for slot in range(4):
            tokens = jax.random.randint(jax.random.PRNGKey(slot), (8,), 0,
                                        255)
            _, cache = steps.prefill_chunk(
                params, cache, blocks, masked, *nothing, tokens, slot, 0, 8)
        before = {"routing": np.asarray(cache["routing"])}
        logits, _, cache = steps.mixed_step(
            params, cache, blocks, masked, closed, closing, active,
            *steps.no_chunk)
        return np.asarray(logits), cache, _counted(before, cache)

    got, cache, counted = run()
    _without_the_mask(monkeypatch)
    want, parent_cache, _ = run()
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(_bits(got), _bits(want))
    _same_but_the_counters(cache, parent_cache)
    pairs = cfg.experts_per_token * cfg.n_layers
    assert counted["moe_rows_routed"] == (4 + 1) * block * pairs
    assert counted["moe_dead_pairs"] == (3 * block + 8) * pairs
    assert 0 < counted["moe_experts_hit"] <= cfg.n_layers * cfg.num_experts
    assert counted["moe_assignments"] == counted["moe_rows_routed"]
    assert counted["moe_decode_rows_routed"] == 0
