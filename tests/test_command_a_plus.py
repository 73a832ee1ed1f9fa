"""Command A+'s block (window and full layers in one model and ONE
cache — rings for the window beside full slabs —, a parallel block under
one LayerNorm, a stated head width, a sigmoid router over more experts
than are held, four shared experts averaged, the embedding tied) on the
program's normal paths, against the plain reference
``chipbench/reference/command_a_plus_decoder.py`` on seeded random
weights at a tiny shape, on the CPU in float32.

The measure is the benchmark's own: the relative L2 distance of the
logits, per position.  Both sides compute in float32, so the distance is
rounding and the order of summation (the program's online softmax walks
the cache in blocks): 2e-6 at worst here.  ``TOL`` = 5e-5 is three
orders under what it must catch: the window mask left off (0.9), a full
layer rotated, the shared experts summed instead of averaged (1.2).

The window is 16 positions and the chunk 8, so a ring is 24 rows; the
step programs' block is cut to 16 positions (``ATTEND_BLOCK`` is 256),
so that rings and slabs are walked in several blocks with a tail.
"""

import dataclasses
import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ant_ray_tpu.llm import LLMEngine, SamplingParams
from ant_ray_tpu.models import llama
from chipbench.models import command_a_plus
from chipbench.reference import command_a_plus_decoder as ref

CFG = llama.CONFIGS["cmdaplus-tiny"]
WINDOW = CFG.window
LAYER_TYPES = ["sliding_attention" if windowed else "full_attention"
               for windowed in CFG.period] * (CFG.n_layers // len(CFG.period))
DIMS = dict(
    n_heads=CFG.n_heads, n_kv_heads=CFG.n_kv_heads,
    rope_theta=CFG.rope_theta, norm_eps=CFG.norm_eps, window=WINDOW,
    experts_per_token=CFG.experts_per_token,
    n_shared_experts=CFG.n_shared_experts, first_expert=CFG.first_expert)
TOL = 5e-5
SLOTS, MAX_SEQ, CHUNK = 3, 96, 8
PUBLISHED = os.path.join(os.path.dirname(__file__), "..", "chipbench",
                         "configs", "command-a-plus.json")


@pytest.fixture(autouse=True)
def short_blocks(monkeypatch):
    monkeypatch.setattr(llama, "ATTEND_BLOCK", 16)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 16)


def seeded_params(cfg=CFG, seed=0):
    """Seeded weights, less bland than the initialiser's: matrices large
    enough that the router decides and attention attends, norm weights
    that are not all ones."""
    p = llama.init_params(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    return {**p, "norm_f": p["norm_f"] * 0.7, "layers": {
        name: leaf * (jax.random.uniform(
            next(keys), leaf.shape, minval=0.5, maxval=1.5)
            if name.startswith("ln_") else 6.0)
        for name, leaf in p["layers"].items()}}


@pytest.fixture(scope="module")
def params():
    return seeded_params()


_BLOCK = jax.jit(ref.block, static_argnames=(       # as the harness does
    "n_heads", "n_kv_heads", "rope_theta", "norm_eps"))


def reference_logits(params, tokens, **dims):
    embed, layer, n, norm_f, head = command_a_plus.reference_layers(
        params, LAYER_TYPES, CFG.head_dim)
    return ref.forward(embed, (layer, n), norm_f, head, jnp.asarray(tokens),
                       block_fn=_BLOCK, **{**DIMS, **dims})


def rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(((got - want) ** 2).sum(-1) / (want ** 2).sum(-1))


def tokens_of(seed, *shape):
    return np.random.default_rng(seed).integers(
        0, CFG.vocab_size, shape).astype(np.int32)


def _bits(x):
    return np.asarray(x).view(np.uint32)


# The step programs jitted once a shape, as the engine runs them (eagerly
# every call would compile its layer scan anew: thousands of programs in
# one test process).  ``ATTEND_BLOCK`` is read when they are traced:
# every test of this file runs under the same 16.
@functools.partial(jax.jit, static_argnames=("cfg",))
def chunk_step(params, tokens, cache, slot, start, n, cfg=CFG):
    return llama.prefill_chunk_into_cache(params, tokens, cache, slot,
                                          start, n, cfg)


@functools.partial(jax.jit, static_argnames=("cfg",))
def decode_step(params, last, cache, active, cfg=CFG):
    return llama.decode_step(params, last, cache, cfg, active)


def ingest(params, cache, tokens, slot, chunk=CHUNK, start=0, cfg=CFG):
    """``tokens`` into ``slot`` from position ``start`` on, in chunks ->
    (logits at the last token, cache)."""
    for at in range(0, len(tokens), chunk):
        part = tokens[at:at + chunk]
        buf = np.zeros((chunk,), np.int32)
        buf[:len(part)] = part
        logits, cache = chunk_step(params, jnp.asarray(buf), cache, slot,
                                   start + at, len(part), cfg=cfg)
    return logits, cache


def through_the_cache(params, tokens, prompt, chunk=CHUNK, ring_chunk=None,
                      slot=1):
    """``prompt`` tokens in chunks, the rest decoded one by one (teacher
    forced) in ``slot`` -> logits from the last prompt token on, cache.
    ``ring_chunk``: the chunk width the cache's rings are made for, where
    it is not the one the prompt arrives in."""
    cache = llama.init_kv_cache(
        CFG, SLOTS, MAX_SEQ, chunk if ring_chunk is None else ring_chunk)
    logits, cache = ingest(params, cache, tokens[:prompt], slot, chunk)
    got = [logits]
    active = np.zeros((SLOTS,), bool)
    active[slot] = True
    for token in tokens[prompt:]:
        last = np.zeros((SLOTS,), np.int32)
        last[slot] = token
        logits, cache = decode_step(params, jnp.asarray(last), cache,
                                    jnp.asarray(active))
        got.append(logits[slot])
    return jnp.stack(got), cache


# ------------------------------------------------ (a) against the reference

def test_forward_logits_equal_the_reference(params):
    tokens = tokens_of(0, 48)
    got = llama.forward(params, tokens[None], CFG, remat="none")[0]
    assert rel_l2(got, reference_logits(params, tokens)).max() < TOL


@pytest.mark.parametrize("chunk", [8, 4, 5, 7], ids=lambda c: f"chunk{c}")
@pytest.mark.parametrize("prompt", [WINDOW // 2, WINDOW, WINDOW + 1,
                                    3 * WINDOW])
def test_chunks_then_decode_through_the_mixed_cache_equal_the_reference(
        params, prompt, chunk):
    """Prefill in chunks and decode through full slabs and rings against
    the reference's full forward — contexts of half a window, a window,
    one more and three, chunk widths that divide the ring (8, 4: rings
    of 24 and 20) and that do not (5, 7: 21 and 23), six decode steps
    behind each, so that rings wrap inside chunks and under decode."""
    tokens = tokens_of(prompt + chunk, prompt + 6)
    got, cache = through_the_cache(params, tokens, prompt, chunk)
    assert cache["k_ring"].shape == (6, SLOTS, WINDOW + chunk, 2, 16)
    assert cache["k"].shape == (2, SLOTS, MAX_SEQ, 2, 16)
    want = reference_logits(params, tokens)[prompt - 1:-1]
    assert rel_l2(got[:-1], want).max() < TOL


def test_a_chunk_across_the_windows_edge_needs_more_than_a_window_of_rows(
        params):
    """The step programs write a chunk's rows and only THEN attend: a
    chunk that starts inside the first window and ends beyond it (rows
    12..19 of a window of 16) overwrites, in a ring of exactly 16 rows,
    positions 0..3 — which its first queries still see.  The shipped
    ring (window + chunk rows) is right; a ring of the window is not."""
    tokens = tokens_of(7, 26)
    want = reference_logits(params, tokens)[19:21]

    def last_logits(ring_chunk):
        cache = llama.init_kv_cache(CFG, SLOTS, MAX_SEQ, ring_chunk)
        _, cache = ingest(params, cache, tokens[:12], 1, chunk=12)
        at_19, cache = ingest(params, cache, tokens[12:20], 1, start=12)
        return at_19, cache

    shipped, cache = last_logits(CHUNK)
    assert cache["k_ring"].shape[2] == WINDOW + CHUNK
    assert rel_l2(shipped, want[0]) < TOL
    last = jnp.zeros((SLOTS,), jnp.int32).at[1].set(int(tokens[20]))
    active = jnp.asarray([False, True, False])
    after, _ = decode_step(params, last, cache, active)
    assert rel_l2(after[1], want[1]) < TOL
    # a ring of exactly the window: the chunk's last query sees 4..19,
    # all still there, but what its first queries made of the
    # overwritten keys is in rows 12.. of every deeper layer
    exact, cache = last_logits(0)
    assert cache["k_ring"].shape[2] == WINDOW
    assert rel_l2(exact, want[0]) > 1e-3
    after, _ = decode_step(params, last, cache, active)
    assert rel_l2(after[1], want[1]) > 1e-3


def test_a_decode_batch_with_rows_on_both_sides_of_the_wrap(params):
    """Three slots decode together: one short of a window, one whose
    ring is about to wrap (context 23 of 24 rows), one well past it (61)
    — each row's logits are the reference's for its own sequence."""
    lengths = [9, WINDOW + CHUNK - 1, 61]
    seqs = [tokens_of(40 + i, n + 3) for i, n in enumerate(lengths)]
    cache = llama.init_kv_cache(CFG, SLOTS, MAX_SEQ, CHUNK)
    for slot, (seq, n) in enumerate(zip(seqs, lengths)):
        _, cache = ingest(params, cache, seq[:n], slot)
    assert cache["length"].tolist() == lengths
    wants = [reference_logits(params, seq) for seq in seqs]
    for step in range(3):
        last = jnp.asarray([seq[n + step] for seq, n in zip(seqs, lengths)])
        logits, cache = decode_step(params, last, cache,
                                    jnp.ones((SLOTS,), bool))
        for slot, (want, n) in enumerate(zip(wants, lengths)):
            assert rel_l2(logits[slot], want[n + step]) < TOL


@pytest.mark.parametrize("wrong,least", [
    (dict(window=10 ** 6), 0.1),              # the window mask left off
    (dict(first_expert=4), 5e-2),             # another rank's experts
    (dict(n_shared_experts=1), 0.1),          # shared experts summed
    (dict(norm_eps=1.0), 1e-3),               # another norm
])
def test_the_tolerance_sees_what_it_must(params, wrong, least):
    tokens = tokens_of(3, 48)
    got = llama.forward(params, tokens[None], CFG, remat="none")[0]
    off = rel_l2(got, reference_logits(params, tokens, **wrong))
    assert off.max() > least > TOL


@pytest.mark.parametrize("change", [
    dict(full_rope=True), dict(norm="rms"),
    dict(shared_experts_average=False)], ids=lambda c: next(iter(c)))
def test_each_of_the_configs_switches_is_read(params, change):
    """A full layer that rotates, an RMSNorm in the LayerNorm's place,
    shared experts summed: each is another function."""
    tokens = tokens_of(4, 40)
    got = llama.forward(params, tokens[None], dataclasses.replace(
        CFG, **change), remat="none")[0]
    assert rel_l2(got, reference_logits(params, tokens)).max() > 1e-3


# ------------------------------------- (b) a row's sums are its own: bits

def _step_logits(params, lengths, active, seed=1):
    """One ``decode_step`` over a random mixed cache whose slots hold
    ``lengths`` positions."""
    cache = llama.init_kv_cache(CFG, len(lengths), MAX_SEQ, CHUNK)
    for name, key in zip(llama.kv_slabs(CFG),
                         jax.random.split(jax.random.PRNGKey(seed), 4)):
        cache[name] = jax.random.normal(key, cache[name].shape, CFG.dtype)
    cache["length"] = jnp.asarray(lengths, jnp.int32)
    last = jnp.arange(len(lengths), dtype=jnp.int32) + 3
    return decode_step(params, last, cache, jnp.asarray(active))


@pytest.mark.parametrize("mine", [9, 30, 70], ids=[
    "inside-the-window", "wrapped-once", "wrapped-twice"])
def test_decode_step_row_is_bit_equal_whatever_the_other_rows_hold(
        params, mine):
    """PR 33's property on the mixed cache: row 0's logits with the other
    rows short (one block of the slab, the rings not full), with one of
    them near its slab's end (every block of slab and rings), and with
    that long row INACTIVE — bit for bit the same, whether row 0's own
    rings have wrapped or not, and through the routed experts."""
    short, long_ = [mine, 3, 12, 7], [mine, 3, 90, 7]
    on, off = [True] * 4, [True, True, False, True]
    a, _ = _step_logits(params, short, on)
    b, _ = _step_logits(params, long_, on)
    d, after = _step_logits(params, long_, off)
    assert np.isfinite(np.asarray(b)).all()
    assert (_bits(a[0]) == _bits(b[0])).all()
    assert (_bits(a[0]) == _bits(d[0])).all()
    assert (_bits(a[2]) != _bits(b[2])).any()
    assert after["length"].tolist() == [mine + 1, 4, 90, 8]
    _, before = _step_logits(params, long_, [False] * 4)
    for name in llama.kv_slabs(CFG):
        assert (_bits(after[name][:, 2]) == _bits(before[name][:, 2])).all()


def test_ring_rows_hold_the_newest_position_of_their_residue():
    """``_ring_holds``: row r of a 24-row ring once position 61 is
    written holds the newest p <= 61 with p = r (mod 24); before the
    ring is full the rows behind the top hold nothing (negative)."""
    held = np.asarray(llama._ring_holds(jnp.int32(61), jnp.arange(24), 24))
    assert sorted(held) == list(range(38, 62))
    assert all(p % 24 == r for r, p in enumerate(held))
    early = np.asarray(llama._ring_holds(jnp.int32(5), jnp.arange(24), 24))
    assert early[:6].tolist() == list(range(6)) and (early[6:] < 0).all()
    assert llama.ring_positions(CFG, 96, 8) == 24
    assert llama.ring_positions(CFG, 20, 8) == 20       # never past max_seq
    assert llama.ring_positions(llama.CONFIGS["tiny"], 96, 8) == 0


# ------------------------------------------------------ (c) shares add up

def test_the_ranks_shares_add_up_to_the_uncut_layer(params):
    """Two ranks hold four of the router's eight experts each: the
    routed parts of their shares, plus the averaged shared experts
    counted once, are the uncut layer's feed-forward — the reference's,
    with all eight held.  (At the published cut: eight ranks of 16.)"""
    layer = {name: leaf[0] for name, leaf in params["layers"].items()}
    h = jax.random.normal(jax.random.PRNGKey(9), (40, CFG.dim))
    keys = jax.random.split(jax.random.PRNGKey(10), 3)
    full = {name: jnp.concatenate([layer[name], 6.0 * 0.02 * jax.random.normal(
        key, layer[name].shape)]) for name, key in zip(
            ("w_gate", "w_up", "w_down"), keys)}          # experts 4-7
    total, loads = 0.0, []
    for rank in range(2):
        cfg = dataclasses.replace(CFG, first_expert=4 * rank)
        held = {name: leaf[4 * rank:4 * rank + 4]
                for name, leaf in full.items()}
        out, load = llama._routed_mlp({**layer, **held}, h, cfg)
        total, loads = total + out, loads + [load]
    # the shared experts, four of width 32 averaged: one by one, as published
    f = CFG.mlp_dim
    shared = sum(ref.swiglu(h, layer["shared_gate"][:, j * f:(j + 1) * f],
                            layer["shared_up"][:, j * f:(j + 1) * f],
                            layer["shared_down"][j * f:(j + 1) * f])
                 for j in range(4)) / 4
    assert int(sum(jnp.sum(load) for load in loads)) == 40 * 2   # none lost
    uncut = {**layer, **full}
    gates = ref.gate_map(h, uncut["router"], 2)
    want = ref.held_experts(uncut, h, gates, 0) + shared
    np.testing.assert_allclose(total + shared, want, rtol=2e-4, atol=2e-6)
    # and the program with all eight held says the same
    whole, load = llama._mlp(uncut, h, dataclasses.replace(
        CFG, num_experts=8))
    np.testing.assert_allclose(whole, want, rtol=2e-4, atol=2e-6)
    np.testing.assert_array_equal(load, jnp.concatenate(loads))


# ------------------------------------------- (d) the cache, written in place

def test_a_step_returns_the_donated_mixed_cache(params, recwarn):
    """As the engine jits them (``donate_argnums=(1,)``): every leaf of
    the cache a step returns — full slabs and rings — is the buffer it
    was given, its rows written where they lie."""
    cache = jax.tree.map(jnp.copy, llama.init_kv_cache(
        CFG, SLOTS, MAX_SEQ, CHUNK))
    decode = jax.jit(lambda p, k, t, a: llama.decode_step(
        p, t, k, CFG, active=a), donate_argnums=(1,))
    chunk = jax.jit(lambda p, k, t, s, st, n: llama.prefill_chunk_into_cache(
        p, t, k, s, st, n, CFG), donate_argnums=(1,))
    for step in (
            lambda c: chunk(params, c, jnp.arange(CHUNK, dtype=jnp.int32),
                            1, 0, 5),
            lambda c: decode(params, c, jnp.zeros((SLOTS,), jnp.int32),
                             jnp.asarray([False, True, False]))):
        given = {name: leaf.unsafe_buffer_pointer()
                 for name, leaf in cache.items()}
        before = {name: np.asarray(jnp.copy(cache[name]))
                  for name in llama.kv_slabs(CFG)}
        _, cache = step(cache)
        jax.block_until_ready(cache)
        assert {name: leaf.unsafe_buffer_pointer()
                for name, leaf in cache.items()} == given
        for name, old in before.items():
            new = np.asarray(jnp.copy(cache[name]))
            changed = np.argwhere((new != old).any(axis=-1))
            # only slot 1, only the rows just written, in every layer
            assert set(changed[:, 0]) == set(range(old.shape[0]))
            assert set(changed[:, 1]) == {1}
            assert changed[:, 2].max() <= 5
    assert int(cache["length"][1]) == 6
    assert not [w for w in recwarn if "donated" in str(w.message)]


# ----------------------------------------------------- through the engine

def _engine(params, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_seq", MAX_SEQ)
    kw.setdefault("prefill_chunk_tokens", CHUNK)
    return LLMEngine(CFG, params, **kw)


def _turn(eng, sid, prompt, n):
    eng.add_request(list(prompt), SamplingParams(max_tokens=n), admit=False,
                    session_id=sid)
    outs, deadline = [], time.monotonic() + 120
    while eng.has_unfinished():
        outs.extend(eng.step())
        assert time.monotonic() < deadline, "engine never drained"
    assert len(outs) == 1
    return outs[0].token_ids


def test_engine_greedy_tokens_are_the_references(params):
    """Through ``LLMEngine``, prompts inside and beyond the window, six
    greedy tokens each: the reference's argmax, token by token."""
    eng = _engine(params, slots=3)
    prompts = [tokens_of(20 + i, n).tolist()
               for i, n in enumerate((5, 19, 45))]
    outs = eng.generate(prompts, SamplingParams(max_tokens=6))
    for prompt, out in zip(prompts, outs):
        # causal: one pass over prompt + answer gives every step's logits
        seq = np.asarray(prompt + out.token_ids, np.int32)
        want = jnp.argmax(reference_logits(params, seq), axis=-1)
        assert out.token_ids == want[len(prompt) - 1:-1].tolist()
    assert set(llama.ROUTING_COUNTERS) <= set(eng.stats)
    assert 0 < eng.stats["moe_assignments"] < eng.stats["moe_rows_routed"]


def test_an_evicted_wrapped_ring_round_trips_bit_for_bit(params):
    """Idle eviction moves whichever slabs ``kv_slabs`` names — the
    rings too, their rows where ``p mod ring`` put them — to the store
    and back into ANOTHER slot; the turns' tokens, the second and third
    decoded over rings that have wrapped, are those of an engine that
    never evicts."""
    turns = [(tokens_of(61, 30).tolist(), 6), ([3, 88, 41, 2], 6),
             ([11, 12], 6)]
    base = _engine(params)
    want = [_turn(base, "s", p, n) for p, n in turns]
    assert base.stats["offloads"] == 0
    evict = _engine(params, kv_idle_evict_s=0.0)
    got = []
    for p, n in turns:
        got.append(_turn(evict, "s", p, n))
        evict.step()                 # idle sweep fires (cutoff = now)
        assert evict._sessions["s"].state == "offloaded"
        evict._free_slots.reverse()  # the restore lands in the other slot
    assert got == want
    assert evict.stats["restores"] >= 2
    *slabs, length = evict._store().get(evict._sessions["s"].handle)
    ring = WINDOW + CHUNK
    assert [s.shape for s in slabs] == [
        (2, MAX_SEQ, 2, 16), (2, MAX_SEQ, 2, 16),
        (6, ring, 2, 16), (6, ring, 2, 16)]
    assert length == evict._sessions["s"].kv_len > ring


def test_a_restored_wrapped_ring_gives_identical_next_logits(params):
    """The same through the step programs: a slot's slabs and rings
    taken out after the rings have wrapped and installed into another
    slot give, bit for bit, the next step's logits."""
    tokens = tokens_of(77, 52)
    eng = _engine(params, slots=3)
    _, cache = ingest(params, eng.cache, tokens[:51], 0)
    slabs = eng._extract_jit(cache, 0)
    moved = eng._install_jit(
        llama.init_kv_cache(CFG, 3, MAX_SEQ, CHUNK), slabs, jnp.int32(51), 2)
    last = jnp.full((3,), int(tokens[51]), jnp.int32)
    here, _ = decode_step(params, last, cache,
                          jnp.asarray([True, False, False]))
    there, _ = decode_step(params, last, moved,
                           jnp.asarray([False, False, True]))
    assert (_bits(here[0]) == _bits(there[2])).all()
    assert rel_l2(here[0], reference_logits(params, tokens)[-1]) < TOL


def test_the_tp_engine_shards_rings_and_slabs_alike(params):
    """``tensor_parallel_size=2``: every slab ``kv_slabs`` names is
    split by KV head, the rings too, and the tokens are those of one
    device."""
    prompts = [tokens_of(30, 29).tolist()]
    want = _engine(params).generate(prompts, SamplingParams(max_tokens=5))
    eng = _engine(params, tensor_parallel_size=2)
    for name in llama.kv_slabs(CFG):
        assert eng.cache[name].sharding.spec[3] == "tp"
    got = eng.generate(prompts, SamplingParams(max_tokens=5))
    assert got[0].token_ids == want[0].token_ids


def test_the_pipeline_schedule_refuses_window_layers(params):
    with pytest.raises(ValueError, match="no window layers"):
        llama.loss_fn_pp(params, {"tokens": jnp.zeros((2, 9), jnp.int32)},
                         CFG, mesh=type("M", (), {"shape": {"pp": 2}})())


def test_a_window_is_computed_by_the_blockwise_path_alone():
    from ant_ray_tpu.ops.attention import attention

    q = jnp.ones((1, 32, 2, 16))
    with pytest.raises(ValueError, match="sliding window"):
        attention(q, q, q, impl="pallas", window=8)
    out = attention(q, q, q, impl="blockwise", window=8)
    assert out.shape == q.shape


# ------------------------------------------- the counts, without allocating

def test_num_params_and_cache_at_the_published_cut():
    """The issue's arithmetic: 4,733,292,544 parameters held (one period
    of four layers with 16 of 128 experts, 1/8 of the vocabulary), by
    ``jax.eval_shape`` of the initialiser — nothing is allocated — and
    the cell's cache, 16 x 32,768 with rings of 4,608 rows: 2.84 GiB."""
    spec = json.load(open(PUBLISHED))
    cfg = command_a_plus.build(spec)
    assert cfg.period == (True, True, True, False)
    assert cfg.layer_counts() == (3, 1)
    assert (cfg.head_dim, cfg.n_heads * cfg.head_dim) == (128, 16384)
    shapes = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    held = sum(leaf.size for leaf in jax.tree.leaves(shapes))
    assert held == cfg.num_params() == 4_733_292_544
    assert "ln_mlp" not in shapes["layers"] and "lm_head" not in shapes
    assert shapes["layers"]["wo"].shape == (4, 16384, 4096)
    attention = 2 * 67_108_864 + 2 * 4_194_304
    outside = attention + 4 * 50_331_648 + 524_288 + 4_096
    assert outside == 344_461_312
    assert sum(leaf.size for leaf in jax.tree.leaves(
        shapes["layers"])) == 4 * (outside + 16 * 50_331_648)
    assert shapes["embed"].size == 134_217_728
    cache = jax.eval_shape(lambda: llama.init_kv_cache(
        cfg, 16, 32768, spec["serve"]["kwargs"]["prefill_chunk_tokens"]))
    assert {n: cache[n].shape for n in llama.kv_slabs(cfg)} == {
        "k": (1, 16, 32768, 8, 128), "v": (1, 16, 32768, 8, 128),
        "k_ring": (3, 16, 4608, 8, 128), "v_ring": (3, 16, 4608, 8, 128)}
    held = sum(cache[n].size * cache[n].dtype.itemsize
               for n in llama.kv_slabs(cfg))
    assert held == 2 * 2 ** 30 + 3 * 16 * 4608 * 4096
    # as ONE slab of today's kind it would be 8 GiB beside 8.82 of weights
    assert 4 * 16 * 32768 * 4096 == 8 * 2 ** 30


def test_the_factory_reads_the_published_keys():
    spec = json.load(open(PUBLISHED))
    assert len(spec["layer_types"]) == 32          # kept whole
    assert command_a_plus._pattern(spec["layer_types"]) == (
        True, True, True, False)
    with pytest.raises(ValueError, match="not what"):
        command_a_plus.build({**spec, "use_parallel_block": False})
    with pytest.raises(ValueError, match="experts_held"):
        command_a_plus.build({**spec, "num_experts": 12})
