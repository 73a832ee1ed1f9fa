"""``ops/pallas/decode_attention.py`` in the Pallas interpreter against
``llama._attend_slab``'s XLA walk (the CPU has no Mosaic: what the
chip's compiler makes of the kernel is ``tests/test_tpu_compile.py``'s,
what it computes there ``benchmarks/attend_invariance.py``'s), the
invariances the mixed step and the benchmark's probes rest on, to the
bit, and the step programs through the kernel."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ant_ray_tpu.llm.programs import step_programs
from ant_ray_tpu.models import llama
from ant_ray_tpu.ops.pallas import decode_attention as da

BLOCK = 16                    # positions a block here (256 on the chip)


def _config(heads, kv_heads, head_dim, max_seq):
    return llama.LlamaConfig(
        vocab_size=64, dim=heads * head_dim, n_layers=2, n_heads=heads,
        n_kv_heads=kv_heads, head_width=head_dim, mlp_dim=64,
        max_seq=max_seq)


def _slabs(c, slots, max_seq, seed=0):
    """Random queries and two layers of random slabs as ``c`` holds
    them."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (2, slots, max_seq) + llama.kv_slabs(c)["k"]
    ks, vs = (jax.random.normal(k, shape, jnp.float32).astype(c.dtype)
              for k in keys[:2])
    xq = jax.random.normal(keys[2], (slots, c.n_heads, c.head_dim),
                           jnp.float32).astype(c.dtype)
    return xq, ks, vs


def _kernel(c, xq, ks, vs, pos, active):
    pos = jnp.asarray(pos, jnp.int32)
    return da.decode_attention(
        xq, ks, vs, 1, pos, da.work_list(pos, jnp.asarray(active), BLOCK,
                                         ks.shape[2]),
        block=BLOCK, scale=c.head_dim ** -0.5, interpret=True)


def _latent_config(heads, max_seq, rank=32, rope=8):
    """Latent attention at test size: ``heads`` of 16 + ``rope`` / 16
    over latents of ``rank``."""
    return llama.LlamaConfig(
        vocab_size=64, dim=64, n_layers=2, n_heads=heads, n_kv_heads=heads,
        mlp_dim=64, max_seq=max_seq, q_lora_rank=24, kv_lora_rank=rank,
        qk_nope_head_dim=16, qk_rope_head_dim=rope, v_head_dim=16)


def _latent_slabs(c, slots, max_seq, seed=0):
    """Random queries, two layers of random latents and rotary keys, and
    a ``w_kvb``."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    c_kv, k_rope = (
        jax.random.normal(k, (2, slots, max_seq) + position,
                          jnp.float32).astype(c.dtype)
        for k, position in zip(keys, llama.kv_slabs(c).values()))
    xq = jax.random.normal(keys[2], (slots, c.n_heads, c.head_dim),
                           jnp.float32).astype(c.dtype)
    w_kvb = (jax.random.normal(keys[3], (c.kv_lora_rank, c.n_heads * 32),
                               jnp.float32) / 4).astype(c.dtype)
    return xq, c_kv, k_rope, w_kvb


def _latent_rows(c, xq, c_kv, k_rope, w_kvb, pos, active):
    """``_attend_slab``'s decode rows with the step's visits: through
    the kernel, interpreted (the CPU)."""
    pos = jnp.asarray(pos, jnp.int32)
    return llama._attend_slab(
        xq, c_kv, k_rope, 1, None, pos, None, c, w_kvb,
        visits=da.work_list(pos, jnp.asarray(active), BLOCK,
                            c_kv.shape[2]))


def _bits(x):
    return np.asarray(x.astype(jnp.float32)).view(np.uint32)


# (heads, KV heads, head_dim, max_seq, each row's position): the shapes
# the cells run, at test size
SHAPES = {
    "30x128-flat": (30, 30, 128, 40, (39, 0, 17, 31)),
    "16of16x128-heads-axis": (16, 16, 128, 48, (47, 5, 16, 15)),
    "32of8x128": (32, 8, 128, 48, (20, 47, 3, 32)),
    "16of8x128": (16, 8, 128, 64, (63, 1, 40, 16)),
    "64-wide-head": (8, 4, 64, 48, (30, 47, 2, 15)),
    "grouped-flat": (20, 10, 32, 48, (30, 47, 2, 15)),
    "slab-shorter-than-a-block": (4, 2, 32, 12, (11, 0, 5, 7)),
    "last-block-starts-early": (4, 2, 32, 40, (39, 33, 31, 32)),
}


@pytest.mark.parametrize("shape", SHAPES)
def test_kernel_agrees_with_the_walk(shape, monkeypatch):
    """Every active row's output within bf16 rounding of the XLA
    walk's, in both held layouts; an idle row's is zeros."""
    heads, kv_heads, head_dim, max_seq, pos = SHAPES[shape]
    monkeypatch.setattr(llama, "ATTEND_BLOCK", BLOCK)
    c = _config(heads, kv_heads, head_dim, max_seq)
    assert c.flat_kv_heads == ("flat" in shape)
    xq, ks, vs = _slabs(c, len(pos), max_seq)
    active = np.array([True, True, False, True])
    pos = jnp.asarray(pos, jnp.int32)
    want = llama._attend_slab(
        xq, ks, vs, 1, None, pos,
        llama._span_blocks(jnp.max(pos) + 1, max_seq), c)
    got = _kernel(c, xq, ks, vs, pos, active)
    assert got.dtype == xq.dtype and got.shape == xq.shape
    np.testing.assert_allclose(
        np.asarray(got.astype(jnp.float32))[active],
        np.asarray(want.astype(jnp.float32))[active], rtol=2e-2, atol=2e-2)
    assert (np.asarray(got.astype(jnp.float32))[~active] == 0).all()


# (heads, max_seq, each row's position): the latent cells' head counts
LATENT_SHAPES = {
    "64-heads": (64, 48, (47, 5, 16, 15)),
    "32-heads": (32, 64, (63, 1, 40, 16)),
    "slab-shorter-than-a-block": (4, 12, (11, 0, 5, 7)),
    "last-block-starts-early": (4, 40, (39, 33, 31, 32)),
}


@pytest.mark.parametrize("shape", LATENT_SHAPES)
def test_latent_kernel_agrees_with_the_walk(shape, monkeypatch):
    """The third layout — latents and rotary keys, no heads axis, the
    absorbed form — against ``_attend_slab``'s walk over the same slabs,
    both behind the same two by-head products: every active row within
    bf16 rounding, an idle row's latent sum zeros (so its output)."""
    heads, max_seq, pos = LATENT_SHAPES[shape]
    monkeypatch.setattr(llama, "ATTEND_BLOCK", BLOCK)
    c = _latent_config(heads, max_seq)
    xq, c_kv, k_rope, w_kvb = _latent_slabs(c, len(pos), max_seq)
    active = np.array([True, True, False, True])
    pos = jnp.asarray(pos, jnp.int32)
    want = llama._attend_slab(
        xq, c_kv, k_rope, 1, None, pos,
        llama._span_blocks(jnp.max(pos) + 1, max_seq), c, w_kvb)
    got = _latent_rows(c, xq, c_kv, k_rope, w_kvb, pos, active)
    assert got.dtype == xq.dtype and got.shape == (
        len(pos), heads, c.v_head_dim)
    np.testing.assert_allclose(
        np.asarray(got.astype(jnp.float32))[active],
        np.asarray(want.astype(jnp.float32))[active], rtol=2e-2, atol=2e-2)
    assert (np.asarray(got.astype(jnp.float32))[~active] == 0).all()


def _ring_config(heads, kv_heads, head_dim, window):
    """Window layers and full ones by turns: a ring's position is held
    as a slab's, so ``_slabs`` of the ring's rows are its rings."""
    return dataclasses.replace(
        _config(heads, kv_heads, head_dim, 4096), window=window,
        window_pattern=(True, False))


def _ring_rows(c, xq, ks, vs, pos, active):
    """``_attend_slab``'s decode rows over rings with the step's visits
    of the RING's length: through the kernel, interpreted (the CPU)."""
    pos = jnp.asarray(pos, jnp.int32)
    return llama._attend_slab(
        xq, ks, vs, 1, None, pos, None, c, None, c.window, pos,
        da.work_list(pos, jnp.asarray(active), BLOCK, ks.shape[2]))


# (heads, KV heads, head_dim, ring rows, window, each row's position —
#  the third slot idle): a ring holds the window and a chunk
RINGS = {
    "not-yet-wrapped": (8, 4, 32, 48, 32, (30, 5, 16, 47)),
    "wrapped-once": (8, 4, 32, 48, 32, (50, 70, 60, 95)),
    "wrapped-many-times": (8, 4, 32, 48, 32, (500, 1007, 16, 4090)),
    "a-row-exactly-at-the-window": (8, 4, 32, 48, 32, (31, 32, 40, 33)),
    "heads-side-by-side": (10, 10, 32, 48, 32, (30, 70, 16, 1000)),
    "128-lane-heads": (16, 2, 128, 64, 48, (200, 63, 64, 47)),
    "ring-no-longer-than-a-block": (4, 2, 32, 12, 8, (11, 0, 5, 70)),
    "ring-no-multiple-of-the-block": (4, 2, 32, 40, 24, (39, 33, 31, 100)),
}


@pytest.mark.parametrize("shape", RINGS)
def test_ring_kernel_is_the_walk_to_the_bit(shape, monkeypatch):
    """A window layer's rings through the kernel (PR 59): every active
    row's output is ``_attend_slab``'s XLA walk's over the same rings
    TO THE BIT — the same blocks in the order they lie, the same mask
    (``_ring_holds``), the same sums and roundings — before the ring has
    wrapped, after one turn and after many, for a row whose context is
    exactly the window and one a position past it (128-lane heads:
    within a rounding here, the CPU's doing); an idle slot's is
    zeros."""
    heads, kv_heads, head_dim, ring, window, pos = RINGS[shape]
    monkeypatch.setattr(llama, "ATTEND_BLOCK", BLOCK)
    c = _ring_config(heads, kv_heads, head_dim, window)
    assert c.flat_kv_heads == ("side-by-side" in shape)
    xq, ks, vs = _slabs(c, len(pos), ring)
    active = np.array([True, True, False, True])
    pos = jnp.asarray(pos, jnp.int32)
    want = llama._attend_slab(
        xq, ks, vs, 1, None, pos, llama._span_blocks(jnp.max(pos) + 1, ring),
        c, None, window, pos)
    got = _ring_rows(c, xq, ks, vs, pos, active)
    assert got.dtype == xq.dtype and got.shape == xq.shape
    if head_dim < 128:
        np.testing.assert_array_equal(_bits(got)[active],
                                      _bits(want)[active])
    else:       # XLA's CPU products group a 128-wide sum by the matrix's
        np.testing.assert_allclose(          # shape: a rounding apart
            np.asarray(got.astype(jnp.float32))[active],
            np.asarray(want.astype(jnp.float32))[active], rtol=1e-2,
            atol=1e-2)
    assert (np.asarray(got.astype(jnp.float32))[~active] == 0).all()
    # and what a row sees is its newest ``window`` positions, itself
    # among them: against the plain softmax over them
    held = np.asarray(llama._ring_holds(pos[:, None], jnp.arange(ring),
                                        ring))
    seen = (held >= 0) & (np.asarray(pos)[:, None] - held < window)
    assert seen.sum(1).tolist() == [
        min(int(p) + 1, window) for p in pos]
    k, v = (np.asarray(x[1].astype(jnp.float32)).reshape(
        len(pos), ring, kv_heads, head_dim) for x in (ks, vs))
    q = np.asarray(xq.astype(jnp.float32)).reshape(
        len(pos), kv_heads, heads // kv_heads, head_dim)
    scores = np.einsum("rkgd,rtkd->rkgt", q, k) * head_dim ** -0.5
    scores = np.where(seen[:, None, None], scores, -np.inf)
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    plain = np.einsum("rkgt,rtkd->rkgd", probs / probs.sum(-1, keepdims=True),
                      v).reshape(xq.shape)
    np.testing.assert_allclose(
        np.asarray(got.astype(jnp.float32))[active], plain[active],
        rtol=2e-2, atol=2e-2)


def _others(ks, vs, pos, active, case):
    """Row 0 stays as it is; what ``case`` changes of the others."""
    pos, active = np.array(pos), np.array(active)
    if case == "other-rows-longer":
        pos[1:] = [47, 46, 33]
    elif case == "other-rows-shorter":
        pos[1:] = [0, 1, 2]
    elif case == "other-slots-idle":
        active[1:] = False
    elif case == "an-idle-slots-slab-nan":
        active[2] = False
        ks, vs = (x.at[:, 2].set(jnp.nan) for x in (ks, vs))
    elif case == "only-the-last-slot-beside-it":
        active[1:3] = False
    return ks, vs, pos, active


@pytest.mark.parametrize("layout", ["flat", "heads-axis", "latent", "ring"])
@pytest.mark.parametrize("case", [
    "other-rows-longer", "other-rows-shorter", "other-slots-idle",
    "an-idle-slots-slab-nan", "only-the-last-slot-beside-it"])
def test_a_rows_output_is_its_own_to_the_bit(case, layout):
    """Row 0's output does not depend, to the bit, on the other rows'
    lengths, on which other slots are active (one live or all), or on
    what an idle slot's slab holds — NaN included: nothing of it is
    read.  (``ring``: a window layer's rings of 48 rows under a window
    of 32 — row 0 has not wrapped, its company wraps or not.)"""
    pos, active = (37, 20, 9, 40), (True,) * 4
    if layout == "ring":
        c = _ring_config(8, 4, 32, 32)
        xq, ks, vs = _slabs(c, 4, 48, seed=3)
        rows = functools.partial(_ring_rows, c, xq)
    elif layout == "latent":
        c = _latent_config(8, 48)
        xq, ks, vs, w_kvb = _latent_slabs(c, 4, 48, seed=3)
        rows = functools.partial(_latent_rows, c, xq, w_kvb=w_kvb)
    else:
        c = _config(*{"flat": (10, 10, 32),
                      "heads-axis": (8, 4, 32)}[layout], 48)
        xq, ks, vs = _slabs(c, 4, 48, seed=3)
        rows = functools.partial(_kernel, c, xq)
    alone = rows(ks, vs, pos=pos, active=active)
    ks, vs, pos, active = _others(ks, vs, pos, active, case)
    beside = rows(ks, vs, pos=pos, active=active)
    assert np.isfinite(np.asarray(beside.astype(jnp.float32))).all()
    np.testing.assert_array_equal(_bits(alone[0]), _bits(beside[0]))


@pytest.mark.parametrize("case", [
    "other-rows-wrapped-further", "other-rows-not-wrapped",
    "other-slots-idle", "an-idle-slots-slab-nan"])
def test_a_wrapped_ring_rows_output_is_its_own_to_the_bit(case):
    """Row 0 of a ring that has wrapped many times (position 1,000 in 48
    rows): the same bits whether the other rows' rings have wrapped
    further, not at all, or their slots are idle — an idle slot's ring
    full of NaN included."""
    c = _ring_config(8, 4, 32, 32)
    xq, ks, vs = _slabs(c, 4, 48, seed=5)
    pos, active = np.array([1000, 1020, 999, 1040]), np.array([True] * 4)
    alone = _ring_rows(c, xq, ks, vs, pos, active)
    if case == "other-rows-wrapped-further":
        pos[1:] = [4000, 2047, 1001]
    elif case == "other-rows-not-wrapped":
        pos[1:] = [0, 31, 47]
    elif case == "other-slots-idle":
        active[1:] = False
    else:
        active[2] = False
        ks, vs = (x.at[:, 2].set(jnp.nan) for x in (ks, vs))
    beside = _ring_rows(c, xq, ks, vs, pos, active)
    assert np.isfinite(np.asarray(beside.astype(jnp.float32))).all()
    np.testing.assert_array_equal(_bits(alone[0]), _bits(beside[0]))


def test_ring_work_list_reads_a_ring_as_far_as_it_is_written(monkeypatch):
    """The ring's work list is ``work_list`` of the RING's length: an
    active row's blocks 0 … ``pos // block`` until its ring has wrapped,
    all the ring's blocks in the order they lie after — none skipped for
    holding only positions behind the window — and none for an idle
    slot; the host's ``read_positions`` of the ring counts the same."""
    monkeypatch.setattr(llama, "ATTEND_BLOCK", BLOCK)
    pos = jnp.asarray([20, 500, 47, 48, 15], jnp.int32)
    active = jnp.asarray([True, False, True, True, True])
    rows, blocks, per_row, visits = da.work_list(pos, active, BLOCK, 48)
    n = int(visits)
    assert per_row.tolist() == [2, 0, 3, 3, 1] and n == 9
    assert rows[:n].tolist() == [0, 0, 2, 2, 2, 3, 3, 3, 4]
    assert blocks[:n].tolist() == [0, 1, 0, 1, 2, 0, 1, 2, 0]
    assert llama.read_positions([21, 48, 49, 16], 48) == BLOCK * n
    # a ring that is no multiple of the block: its last block starts
    # early, and is the one more
    assert da.blocks_read(jnp.asarray([31, 32, 39, 4000]),
                          jnp.ones((4,), bool), BLOCK, 40).tolist() == [
        2, 3, 3, 3]


def test_work_list_visits_each_active_rows_own_blocks_in_order(monkeypatch):
    """An active row's blocks 0, 1, 2, … row after row, none for an idle
    slot, and the host's ``read_positions`` counts the same."""
    monkeypatch.setattr(llama, "ATTEND_BLOCK", BLOCK)
    pos = jnp.asarray([37, 20, 9, 47], jnp.int32)
    active = jnp.asarray([True, False, True, True])
    rows, blocks, per_row, visits = da.work_list(pos, active, BLOCK, 48)
    n = int(visits)
    assert per_row.tolist() == [3, 0, 1, 3] and n == 7
    assert rows[:n].tolist() == [0, 0, 0, 2, 3, 3, 3]
    assert blocks[:n].tolist() == [0, 1, 2, 0, 0, 1, 2]
    assert llama.read_positions([38, 10, 48], 48) == BLOCK * n
    none = da.work_list(pos, jnp.zeros((4,), bool), BLOCK, 48)
    assert int(none[3]) == 0 and int(none[1].min()) >= 0


@pytest.fixture
def through_the_kernel(monkeypatch):
    """The step programs take the kernel as they do on one TPU device
    (on the CPU ``_decode_kernel`` keeps the walk), interpreted."""
    monkeypatch.setattr(llama, "ATTEND_BLOCK", BLOCK)

    def switch(on):
        monkeypatch.setattr(
            llama, "_decode_kernel",
            lambda c, mesh, max_seq: on and mesh is None)
    return switch


@pytest.mark.parametrize("name", ["tiny", "cmdaplus-tiny", "axk1-tiny",
                                  "xing4-tiny"])
def test_step_programs_through_the_kernel(name, through_the_kernel):
    """A chunk and decode steps with the kernel behind ``_attend_slab``
    give the logits of the same programs through the walk inside the
    parity tolerance and the same arg-max; and a row comes out of
    ``mixed_step`` with the BITS ``decode_step`` gives it — both call
    ``_decode_rows.attend``, so both get the kernel.  (Command A+'s
    tiny preset: its window layers' rings of 32 rows — slot 2's has
    wrapped — go through the kernel under the ring's mask beside the
    full layers' slabs, PR 59; A.X-K1's and Xing4.0's: latent slabs, the
    kernel's third layout between ``w_kvb``'s two by-head products.)"""
    cfg = dataclasses.replace(llama.CONFIGS[name], max_seq=64)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    prompts = {0: 23, 2: 40}                     # slot -> prompt tokens
    tokens = jax.random.randint(jax.random.PRNGKey(1), (48,), 0,
                                cfg.vocab_size)
    active = jnp.asarray([True, False, True, False])
    last = jnp.asarray([3, 1, 4, 1], jnp.int32)
    ride = jax.random.randint(jax.random.PRNGKey(2), (16,), 0,
                              cfg.vocab_size)

    windows = set()
    attention = da.decode_attention

    def watched(*args, window=0, **kwargs):
        windows.add((args[1].shape[2], window))
        return attention(*args, window=window, **kwargs)

    def run(on):
        through_the_kernel(on)
        # (built anew: traced with the kernel on, or off)
        steps = step_programs(cfg, slots=4, max_seq=64, chunk=16)
        chunk = steps.prefill_chunk
        cache = llama.init_kv_cache(cfg, 4, 64, chunk=16)
        for slot, n in prompts.items():
            for at in range(0, n, 16):
                _, cache = chunk(params, cache, tokens[at:at + 16], slot,
                                 at, min(16, n - at))
        out = []
        for _ in range(3):
            # (of a copy: the engine's programs take their cache)
            beside = steps.mixed_step(params, jax.tree.map(jnp.copy, cache),
                                      last, active, ride, 1, 0, 11)[0]
            logits, cache = steps.decode(params, cache, last, active)
            np.testing.assert_array_equal(_bits(logits[active]),
                                          _bits(beside[active]))
            out.append(np.asarray(logits)[np.asarray(active)])
        return np.stack(out)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(da, "decode_attention", watched)
        got = run(True)
    # the slabs' call, and a window layer's rings' with the window
    assert windows == {(64, 0)} | ({(32, cfg.window)} if cfg.window
                                   else set())
    want = run(False)
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 0.02
    np.testing.assert_array_equal(got.argmax(-1), want.argmax(-1))


def test_decode_kernel_keeps_the_walk_where_the_arguments_say():
    """``_decode_kernel``: off the TPU, under a mesh, for heads — or
    latents, or latent slabs' lengths — that are no whole lane tiles,
    the XLA walk; latent slabs of whole lane tiles take the kernel
    since PR 57, a window layer's rings with the slabs since PR 59 (the
    rule is the slabs': a ring's position has the slab's shape, and no
    option, variable or name chooses —
    ``test_step_programs_through_the_kernel``)."""
    wide = _config(4, 2, 128, 64)
    assert not llama._decode_kernel(wide, None, 64)          # the CPU
    on_tpu = pytest.MonkeyPatch()
    try:
        on_tpu.setattr(jax, "default_backend", lambda: "tpu")
        assert llama._decode_kernel(wide, None, 64)
        assert not llama._decode_kernel(wide, object(), 64)  # a mesh
        rings = _ring_config(4, 2, 128, 16)
        assert llama._decode_kernel(rings, None, 64)
        assert not llama._decode_kernel(rings, object(), 64)
        assert not llama._decode_kernel(_ring_config(4, 2, 64, 16), None,
                                        64)
        assert not llama._decode_kernel(_config(4, 2, 64, 64), None, 64)
        assert not llama._decode_kernel(llama.CONFIGS["axk1-tiny"], None,
                                        512)
        latent = _latent_config(4, 4096, rank=512, rope=64)
        assert llama._decode_kernel(latent, None, 4096)
        assert not llama._decode_kernel(latent, object(), 4096)
        # the rotary keys' positions lie along the lanes: whole tiles
        assert not llama._decode_kernel(latent, None, 1000)
    finally:
        on_tpu.undo()
