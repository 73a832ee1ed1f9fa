"""SDAR's generation by DIFFUSION OVER BLOCKS (a step that gives a slot
none or a whole block of tokens, full attention inside the block in
flight, a store pass a block, an RMSNorm a head on q and k, routed
experts with renormalised gates) on the program's normal paths, against
the plain reference ``chipbench/reference/sdar_moe_decoder.py`` on seeded
random weights at a tiny shape, on the CPU in float32.

The measure is the benchmark's own: the relative L2 distance of the
logits, per position.  Both sides compute in float32, so the distance is
rounding and the order of summation; ``TOL`` = 1e-4 lies two orders
under what it must catch — every control below (weights in float8, a
causal mask inside the block, the store pass dropped, q and k normed
over the whole width, the prompt under the plain causal mask, a closing
block that sees its successor, a successor that does not see it) reads
over 1e-2.  Generation is compared token for token, greedy: the engine's
loop against the reference's, a full forward pass a step.  A block's
store pass RIDES the next block's first step; that the fused step stores
what a store pass of its own stores is held bit for bit.

The step programs' attention block is cut to 16 positions.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ant_ray_tpu.llm import LLMEngine, SamplingParams
from ant_ray_tpu.llm import engine as engine_mod
from ant_ray_tpu.models import llama
from chipbench.models import sdar_moe
from chipbench.reference import sdar_moe_decoder as ref

CFG = llama.CONFIGS["sdar-tiny"]
B, MASK = CFG.block_length, CFG.mask_token
TOL = 1e-4
SLOTS, MAX_SEQ, CHUNK = 3, 64, 8


@pytest.fixture(autouse=True)
def short_blocks(monkeypatch):
    monkeypatch.setattr(llama, "ATTEND_BLOCK", 16)


def seeded_params(cfg=CFG, seed=0):
    """Seeded weights, less bland than the initialiser's: matrices large
    enough that attention attends and the router decides, norm weights
    (the heads' q and k norms too) that are not all ones."""
    p = llama.init_params(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def livelier(name, leaf):
        if name.startswith("ln_") or name.endswith("_norm"):
            return leaf * jax.random.uniform(
                next(keys), leaf.shape, minval=0.5, maxval=1.5)
        return leaf * 6.0

    return {**p, "norm_f": p["norm_f"] * jax.random.uniform(
        next(keys), p["norm_f"].shape, minval=0.5, maxval=1.5),
        "layers": {n: livelier(n, x) for n, x in p["layers"].items()}}


@pytest.fixture(scope="module")
def params():
    return seeded_params()


_BLOCK = jax.jit(ref.block, static_argnames=(
    "n_heads", "n_kv_heads", "head_dim", "rope_theta", "norm_eps",
    "experts_per_token", "norm_topk_prob", "qk_norm", "weights"))
DIMS = {"n_heads": CFG.n_heads, "n_kv_heads": CFG.n_kv_heads,
        "head_dim": CFG.head_dim, "rope_theta": CFG.rope_theta,
        "norm_eps": CFG.norm_eps,
        "experts_per_token": CFG.experts_per_token,
        "norm_topk_prob": CFG.norm_topk_prob}
RULE = {"block_length": B, "denoising_steps": CFG.denoising_steps,
        "threshold": CFG.confidence_threshold, "mask_token": MASK}


def reference_logits(params, tokens, mask=None, **changed):
    embed, layer, n, norm_f, head = sdar_moe.reference_layers(params)
    return ref.forward(embed, (layer, n), norm_f, head,
                       jnp.asarray(tokens, jnp.int32), block_length=B,
                       mask=mask, block_fn=_BLOCK, **{**DIMS, **changed})


def reference_generate(params, prompt, max_tokens, **more):
    embed, layer, n, norm_f, head = sdar_moe.reference_layers(params)
    return ref.generate(embed, (layer, n), norm_f, head, list(prompt),
                        max_tokens, block_fn=_BLOCK, **RULE, **DIMS, **more)


def rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(((got - want) ** 2).sum(-1) / (want ** 2).sum(-1))


def tokens_of(seed, n):
    # below the mask token and the tokenizer's end-of-sequence id
    return np.random.default_rng(seed).integers(3, 250, n).astype(np.int32)


@functools.partial(jax.jit, static_argnames=("cfg",))
def chunk_step(params, tokens, cache, slot, start, n, cfg=CFG):
    return llama.prefill_chunk_into_cache(params, tokens, cache, slot,
                                          start, n, cfg)


@functools.partial(jax.jit, static_argnames=("cfg",))
def block_step(params, fed, cache, active, store, cfg=CFG):
    return llama.decode_step(params, fed, cache, cfg, active, store=store)


@functools.partial(jax.jit, static_argnames=("cfg",))
def mixed_block_step(params, fed, tokens, cache, active, store, slot,
                     start, n, cfg=CFG):
    return llama.mixed_step(params, fed, tokens, cache, cfg, active, slot,
                            start, n, store=store)


@functools.partial(jax.jit, static_argnames=("cfg",))
def fused_step(params, fed, closed, closing, tokens, cache, active, store,
               slot, start, n, cfg=CFG):
    """The engine's ONE step program: blocks in flight, blocks closing,
    a chunk's rows."""
    return llama.mixed_step(params, fed, tokens, cache, cfg, active, slot,
                            start, n, store=store, closing=(closed, closing))


def ingest(params, cache, tokens, slot, cfg=CFG):
    """``tokens`` (whole blocks) into ``slot`` in chunks of CHUNK."""
    for start in range(0, len(tokens), CHUNK):
        part = tokens[start:start + CHUNK]
        buf = np.zeros((CHUNK,), np.int32)
        buf[:len(part)] = part
        _, cache = chunk_step(params, jnp.asarray(buf), cache, slot, start,
                              len(part), cfg)
    return cache


def one_slot(slot, fed=None):
    """(the blocks as fed, active) with only ``slot`` live."""
    blocks = np.full((SLOTS, B), MASK, np.int32)
    active = np.zeros((SLOTS,), bool)
    if fed is not None:
        blocks[slot], active[slot] = fed, True
    return jnp.asarray(blocks), jnp.asarray(active)


def a_step(params, cache, slot, fed, store, cfg=CFG):
    """One block step of ``slot`` alone -> (its B rows' logits, cache)."""
    blocks, active = one_slot(slot, fed)
    logits, cache = block_step(params, blocks, cache, active,
                               jnp.asarray(active) & store, cfg)
    return logits.reshape(SLOTS, B, -1)[slot], cache


# ------------------------------------------- (a) logits: the five paths

def test_forward_under_the_block_causal_mask(params):
    tokens = tokens_of(1, 30)                 # a partial last block too
    got = jax.jit(lambda p, t: llama.forward(p, t, CFG))(
        params, jnp.asarray(tokens[None]))[0]
    assert rel_l2(got, reference_logits(params, tokens)).max() < TOL
    # and not under the causal one: a place sees places behind it, and
    # above the first layer a block's last sees what they made of that
    causal = jnp.tril(jnp.ones((30, 30), bool))
    off = rel_l2(got, reference_logits(params, tokens, mask=causal))
    assert off.min() > 1e-2


@pytest.fixture(scope="module")
def probe(params):
    """Teacher-forced block states through the cache, as the chip's
    probe runs them: 16 prompt tokens stored in chunks + a tail of 2,
    block 0 with 2, 1, 0 masks (the last its store pass), block 1 with
    4, 3, 2, 1 masks in a seeded order and its store pass, block 2's
    first step.  Returns each denoise step's (stored tokens, block as
    fed, logits of its B rows) and the cache behind it all."""
    tokens = tokens_of(2, 16 + 3 * B)
    order = np.random.default_rng(3).permutation(B)
    cache = llama.init_kv_cache(CFG, SLOTS, MAX_SEQ, CHUNK)
    cache = ingest(params, cache, tokens[:16], 1)
    steps, stored = [], 16
    for known_first in (2, 0, 0):
        final = tokens[stored:stored + B]
        known = np.zeros((B,), bool)
        known[:known_first] = True
        for filled in order[np.isin(order, np.nonzero(~known)[0])]:
            fed = np.where(known, final, MASK)
            logits, cache = a_step(params, cache, 1, fed, False)
            steps.append((tokens[:stored], fed, logits))
            known[filled] = True
            if stored == 16 + 2 * B:
                break                          # block 2: one step
        else:
            _, cache = a_step(params, cache, 1, final, True)
            assert int(cache["length"][1]) == stored + B
            stored += B
    return steps, cache, tokens


def test_chunks_block_steps_and_store_passes_through_the_cache(probe,
                                                               params):
    steps, cache, _ = probe
    assert len(steps) == 2 + 4 + 1
    for stored, fed, logits in steps:
        want = reference_logits(params, np.concatenate([stored, fed]))
        assert rel_l2(logits, want[len(stored):]).max() < TOL
    assert cache["length"].tolist() == [0, 16 + 2 * B, 0]


def test_a_denoise_step_stores_nothing(params):
    """The rows it writes lie behind the length: the next step of the
    block — fed other tokens — reads what it writes itself."""
    tokens = tokens_of(4, 8 + B)
    cache = ingest(params, llama.init_kv_cache(CFG, SLOTS, MAX_SEQ, CHUNK),
                   tokens[:8], 0)
    _, cache = a_step(params, cache, 0, tokens_of(5, B), False)
    assert int(cache["length"][0]) == 8
    fed = np.where([True, False, True, False], tokens[8:], MASK)
    logits, _ = a_step(params, cache, 0, fed, False)
    want = reference_logits(params, np.concatenate([tokens[:8], fed]))
    assert rel_l2(logits, want[8:]).max() < TOL


def test_the_mixed_step_is_both_programs(params):
    """Block rows of two slots and a riding chunk of a third, one
    program: each part's logits are its own program's, the chunk's
    under the block-causal mask."""
    tokens = tokens_of(6, 40)
    cache = llama.init_kv_cache(CFG, SLOTS, MAX_SEQ, CHUNK)
    cache = ingest(params, cache, tokens[:8], 0)
    cache = ingest(params, cache, tokens[8:20], 2)
    blocks = np.full((SLOTS, B), MASK, np.int32)
    blocks[0, :2] = tokens[20:22]
    blocks[2] = tokens[22:26]                 # no mask: its store pass
    active = np.array([True, False, True])
    store = np.array([False, False, True])
    buf = np.zeros((CHUNK,), np.int32)
    buf[:4] = tokens[30:34]
    logits, chunk_logits, cache = mixed_block_step(
        params, jnp.asarray(blocks), jnp.asarray(buf), cache,
        jnp.asarray(active), jnp.asarray(store), 1, 0, 4)
    logits = logits.reshape(SLOTS, B, -1)
    want = reference_logits(params, np.concatenate([tokens[:8], blocks[0]]))
    assert rel_l2(logits[0], want[8:]).max() < TOL
    want = reference_logits(params, np.concatenate([tokens[8:20],
                                                    blocks[2]]))
    assert rel_l2(logits[2], want[12:]).max() < TOL
    want = reference_logits(params, tokens[30:34])
    assert rel_l2(chunk_logits, want[3]) < TOL
    assert cache["length"].tolist() == [8, 4, 12 + B]


def _fused(params, cache, slot, fed, closed=None, store=False):
    """One step of the ONE program for ``slot`` alone, no chunk: its
    block in flight ``fed`` (None: nothing active), its closing block
    ``closed`` (None: none) -> (the block in flight's logits, cache)."""
    blocks, active = one_slot(slot, fed)
    closing, live = one_slot(slot, closed)
    logits, _, cache = fused_step(
        params, blocks, closing, live, jnp.zeros((CHUNK,), jnp.int32),
        cache, active, active & store, SLOTS, 1, 0)
    return logits.reshape(SLOTS, B, -1)[slot], cache


@pytest.fixture(scope="module")
def riding(params):
    """The state "block b closing + block b+1's first step" behind 8
    stored tokens, as the fused step ran it, and its cache."""
    tokens = tokens_of(7, 8 + 2 * B)
    cache = ingest(params, llama.init_kv_cache(CFG, SLOTS, MAX_SEQ, CHUNK),
                   tokens[:8], 1)
    fed = np.where([False, True, False, False], tokens[8 + B:], MASK)
    logits, after = _fused(params, cache, 1, fed, tokens[8:8 + B])
    return tokens, cache, fed, logits, after


def test_a_closing_block_rides_the_next_blocks_first_step(riding, params):
    """The closing block's rows and the next block's, one step: the new
    block sees the stored positions, the closing block as this step
    wrote it and itself — the block-causal mask, unchanged — and the
    length moves over the closing block alone."""
    tokens, _, fed, logits, after = riding
    want = reference_logits(params, np.concatenate([tokens[:8 + B], fed]))
    assert rel_l2(logits, want[8 + B:]).max() < TOL
    assert after["length"].tolist() == [0, 8 + B, 0]


def test_the_fused_step_stores_what_a_store_pass_stores(riding, params):
    """Through the ONE program, bit for bit: a store pass of its own
    (block rows with no mask left, nothing closing) and then the next
    block's first step give the logits and leave the keys and values
    that the one fused step gives and leaves."""
    tokens, cache, fed, logits, after = riding
    _, apart = _fused(params, cache, 1, tokens[8:8 + B], store=True)
    assert apart["length"].tolist() == [0, 8 + B, 0]
    step, apart = _fused(params, apart, 1, fed)
    assert np.array_equal(np.asarray(step), np.asarray(logits))
    assert apart["length"].tolist() == after["length"].tolist()
    for name in llama.kv_slabs(CFG):
        # the stored positions and the block in flight behind them
        assert np.array_equal(np.asarray(apart[name][:, 1, :8 + 2 * B]),
                              np.asarray(after[name][:, 1, :8 + 2 * B]))


def test_control_closing_rows_that_see_the_new_block_fail(riding, params):
    tokens, _, fed, logits, _ = riding
    n = 8 + 2 * B
    mask = ref.block_mask(n, B).at[8:8 + B, 8 + B:].set(True)
    want = reference_logits(params, np.concatenate([tokens[:8 + B], fed]),
                            mask)
    assert rel_l2(logits, want[8 + B:]).min() > 1e-2


def test_control_a_new_block_that_does_not_see_the_closing_one_fails(
        riding, params):
    tokens, _, fed, logits, _ = riding
    n = 8 + 2 * B
    mask = ref.block_mask(n, B).at[8 + B:, 8:8 + B].set(False)
    want = reference_logits(params, np.concatenate([tokens[:8 + B], fed]),
                            mask)
    assert rel_l2(logits, want[8 + B:]).min() > 1e-2


def test_nothing_closing_and_no_mask_left_is_still_a_store_pass(params):
    """What the benchmark's probe relies on (``eng._decode_jit``): block
    rows with no mask left, nothing closing, move the length by a block;
    a slot with a block closing and NOTHING in flight (no mask left: the
    slab's end) moves it once, not twice."""
    tokens = tokens_of(8, 8 + 2 * B)
    cache = ingest(params, llama.init_kv_cache(CFG, SLOTS, MAX_SEQ, CHUNK),
                   tokens[:8], 2)
    _, stored = _fused(params, cache, 2, tokens[8:8 + B], store=True)
    assert stored["length"].tolist() == [0, 0, 8 + B]
    _, once = _fused(params, cache, 2, tokens[8 + B:], tokens[8:8 + B],
                     store=True)
    assert once["length"].tolist() == [0, 0, 8 + B]
    for name in llama.kv_slabs(CFG):
        assert np.array_equal(np.asarray(stored[name][:, 2, :8 + B]),
                              np.asarray(once[name][:, 2, :8 + B]))


# --------------------------------------------- (b) the controls all FAIL

def _worst(params, steps, **how):
    """The probe's reading under a changed reference: the worst position
    of its best step (a control must move EVERY step)."""
    return min(rel_l2(logits, how["want"](stored, fed)[len(stored):]).max()
               for stored, fed, logits in steps)


def test_control_weights_in_float8_read_outside_the_tolerance(probe, params):
    steps, _, _ = probe
    low = jax.tree.map(lambda x: x.astype(jnp.float8_e4m3fn).astype(
        jnp.float32), params)
    assert _worst(params, steps, want=lambda stored, fed: reference_logits(
        low, np.concatenate([stored, fed]))) > 1e-2


def test_control_a_causal_mask_inside_the_block_fails(probe, params):
    steps, _, _ = probe

    def want(stored, fed):
        n = len(stored) + B
        mask = ref.block_mask(n, B).at[len(stored):].set(
            jnp.tril(jnp.ones((n, n), bool))[len(stored):])
        return reference_logits(params, np.concatenate([stored, fed]), mask)

    assert _worst(params, steps, want=want) > 1e-2


def test_control_the_prompt_under_the_causal_mask_fails(probe, params):
    steps, _, _ = probe

    def want(stored, fed):
        n = len(stored) + B
        mask = ref.block_mask(n, B).at[:16].set(
            jnp.tril(jnp.ones((n, n), bool))[:16])
        return reference_logits(params, np.concatenate([stored, fed]), mask)

    assert _worst(params, steps, want=want) > 1e-2


def test_control_q_and_k_normed_over_the_whole_width_fail(probe, params):
    steps, _, _ = probe
    assert _worst(params, steps, want=lambda stored, fed: reference_logits(
        params, np.concatenate([stored, fed]), qk_norm="whole")) > 1e-2


def test_control_the_store_pass_dropped_fails(probe, params):
    """A block's keys and values kept from its LAST DENOISE step are
    those of a block with one place still the mask token: what the
    reference gives when that is what it is fed for the stored block."""
    steps, _, tokens = probe
    order = np.random.default_rng(3).permutation(B)
    seen = 0
    for stored, fed, logits in steps:
        if len(stored) < 16 + 2 * B:
            continue                           # behind block 1's store
        kept = stored.copy()
        kept[16 + B + order[-1]] = MASK        # block 1's last-filled
        want = reference_logits(params, np.concatenate([kept, fed]))
        assert rel_l2(logits, want[len(stored):]).max() > 1e-2
        seen += 1
    assert seen == 1


# ----------------------------------------------- (c) the transfer rule

def _engine(params, **kw):
    kw = {"slots": SLOTS, "max_seq": MAX_SEQ, "prefill_chunk_tokens": CHUNK,
          **kw}
    return LLMEngine(CFG, params, **kw)


def _sharp(confident, seed, vocab=CFG.vocab_size):
    """Logits (B, vocab): a peak of probability ~0.99 at the places
    named ``confident``, ~0.5 + a place's own share elsewhere."""
    rng = np.random.default_rng(seed)
    logits = rng.normal(size=(B, vocab)).astype(np.float32)
    for j in range(B):
        peak = int(rng.integers(3, 250))
        logits[j, peak] = 12.0 if j in confident else 5.6 + 0.1 * j
    return logits


@pytest.mark.parametrize("confident,masked,temperature", [
    ((), (0, 1, 2, 3), 0.0),          # none passes: the most probable one
    ((2,), (0, 1, 2, 3), 0.0),        # one passes
    ((0, 3), (0, 1, 2, 3), 0.0),      # several pass: all of them go
    ((0, 1, 2, 3), (0, 1, 2, 3), 0.0),  # all pass: the block fills
    ((1,), (0, 2, 3), 0.0),           # a confident place already decided
    ((), (3,), 0.0),                  # the last mask goes: FILLED
    ((0, 2), (0, 1, 2, 3), 1.0),      # drawn, the slot's key chain
    ((), (1, 2), 0.7),
])
def test_the_transfer_rule_place_by_place(params, confident, masked,
                                          temperature):
    eng = _engine(params)
    logits = np.zeros((SLOTS, B, CFG.vocab_size), np.float32)
    logits[1] = _sharp(confident, seed=len(confident) + len(masked))
    mask_row = np.isin(np.arange(B), masked)
    blocks = np.full((SLOTS, B), 7, np.int32)
    places = np.zeros((SLOTS, B), bool)
    places[1] = mask_row
    active = np.array([False, True, False])
    keys = jax.random.split(jax.random.PRNGKey(9), SLOTS)
    read, new_keys, new_blocks, left, closed, closing, counts = \
        eng._sample_jit(
            jnp.asarray(logits.reshape(SLOTS * B, -1)), keys,
            jnp.asarray(active),
            jnp.full((SLOTS,), temperature, jnp.float32),
            jnp.zeros((SLOTS,), jnp.int32), jnp.ones((SLOTS,), jnp.float32),
            None, jnp.asarray(blocks), jnp.asarray(places),
            jnp.full((SLOTS, B), 5, jnp.int32), jnp.zeros((SLOTS,), bool),
            jnp.zeros((3,), jnp.uint32), jnp.zeros((SLOTS,), jnp.int32))
    place_keys = jax.random.split(jax.random.split(keys[1])[1], B)
    want_block, want_left, taken, sure = ref.transfer(
        logits[1], blocks[1], mask_row, place_keys,
        temperature=temperature, threshold=CFG.confidence_threshold,
        n_transfer=B // CFG.denoising_steps)
    filled = not any(want_left)
    if filled:
        # its last mask went: the block is closing, its tokens final,
        # and the next one starts behind it as masks at once
        assert np.asarray(closed)[1].tolist() == want_block
        assert np.asarray(new_blocks)[1].tolist() == [MASK] * B
        assert np.asarray(left)[1].tolist() == [True] * B
    else:
        assert np.asarray(closed)[1].tolist() == [5] * B
        assert np.asarray(new_blocks)[1].tolist() == want_block
        assert np.asarray(left)[1].tolist() == want_left
    assert np.asarray(closing).tolist() == [False, filled, False]
    assert sure == (len(set(confident) & set(masked)) >= 1)
    assert np.asarray(counts).tolist() == [
        0, sum(taken), sum(taken) if sure else 0]
    read = np.asarray(read)
    assert read[:SLOTS].tolist() == [
        0, engine_mod.BLOCK_FILLED if filled else 0, 0]
    assert read[SLOTS:SLOTS * (1 + B)].reshape(SLOTS, B)[1].tolist() \
        == want_block
    # an idle slot's block, masks and key stay as they are
    assert np.asarray(new_blocks)[0].tolist() == [7] * B
    assert (np.asarray(new_keys)[0] == np.asarray(keys)[0]).all()
    assert (np.asarray(new_keys)[1] != np.asarray(keys)[1]).any()


def test_the_transfer_rule_breaks_ties_by_place(params):
    """Equal probabilities: the earlier place takes its draw."""
    eng = _engine(params)
    one = np.random.default_rng(0).normal(size=(CFG.vocab_size,))
    logits = np.tile(one.astype(np.float32), (SLOTS * B, 1))
    places = np.zeros((SLOTS, B), bool)
    places[0, 1:] = True
    _, _, _, left, *_ = eng._sample_jit(
        jnp.asarray(logits), jax.random.split(jax.random.PRNGKey(1), SLOTS),
        jnp.asarray([True, False, False]), jnp.zeros((SLOTS,), jnp.float32),
        jnp.zeros((SLOTS,), jnp.int32), jnp.ones((SLOTS,), jnp.float32),
        None, jnp.zeros((SLOTS, B), jnp.int32), jnp.asarray(places),
        jnp.zeros((SLOTS, B), jnp.int32), jnp.zeros((SLOTS,), bool),
        jnp.zeros((3,), jnp.uint32), jnp.zeros((SLOTS,), jnp.int32))
    assert np.asarray(left)[0].tolist() == [False, False, True, True]


def _transfer(eng, places, closing, length, active=(True, True, True)):
    """The sampler on flat logits (greedy: the first masked place takes
    token 0): every slot's block in flight all 9s, its closing block all
    5s -> (status, blocks, masks left, closed, closing, counts)."""
    read, _, blocks, left, closed, closing, counts = eng._sample_jit(
        jnp.zeros((SLOTS * B, CFG.vocab_size), jnp.float32),
        jax.random.split(jax.random.PRNGKey(1), SLOTS),
        jnp.asarray(active), jnp.zeros((SLOTS,), jnp.float32),
        jnp.zeros((SLOTS,), jnp.int32), jnp.ones((SLOTS,), jnp.float32),
        None, jnp.full((SLOTS, B), 9, jnp.int32), jnp.asarray(places),
        jnp.full((SLOTS, B), 5, jnp.int32), jnp.asarray(closing),
        jnp.zeros((3,), jnp.uint32), jnp.asarray(length, jnp.int32))
    return (np.asarray(read)[:SLOTS].tolist(), np.asarray(blocks),
            np.asarray(left).tolist(), np.asarray(closed),
            np.asarray(closing).tolist(), np.asarray(counts).tolist())


def test_a_store_pass_starts_the_next_block_as_masks(params):
    """No mask left and nothing closing: a store pass of its own,
    STORED, and the next block's masks reach as far as the slab (a last
    block of two places under max_seq 62).  A block whose last mask
    goes is FILLED: it is closing, and its successor's masks lie a
    block further on."""
    eng = _engine(params, max_seq=62)
    places = np.zeros((SLOTS, B), bool)
    places[2, 0] = True
    status, blocks, left, closed, closing, counts = _transfer(
        eng, places, [False] * 3, [20, 60, 52])
    assert status == [engine_mod.BLOCK_STORED, engine_mod.BLOCK_STORED,
                      engine_mod.BLOCK_FILLED]
    assert left == [[True] * 4, [True, True, False, False],
                    [True, True, True, True]]
    assert blocks.tolist() == [[MASK] * B] * 3
    assert closing == [False, False, True]
    assert closed.tolist() == [[5] * B, [5] * B, [0, 9, 9, 9]]
    assert counts == [2, 1, 0]
    # ... and behind a filled block at the slab's end nothing starts
    _, _, left, _, closing, _ = _transfer(eng, places, [False] * 3,
                                          [20, 60, 58])
    assert left[2] == [False] * 4 and closing[2]


def test_a_closing_block_is_stored_by_the_step_that_carried_it(params):
    """The status bits a slot: a block closing beside a denoise step of
    its successor is STORED and FUSED; beside the step that fills the
    successor FILLED too; with NOTHING in flight behind it (no mask
    left: the slab's end) it is a store pass and nothing else — STORED,
    counted in ``block_store_rows``, and not a second store."""
    eng = _engine(params, max_seq=62)
    stored, fused, filled = (engine_mod.BLOCK_STORED, engine_mod.BLOCK_FUSED,
                             engine_mod.BLOCK_FILLED)
    places = np.zeros((SLOTS, B), bool)
    places[0, :2] = True
    places[1, 3] = True
    status, blocks, left, closed, closing, counts = _transfer(
        eng, places, [True] * 3, [24, 24, 62])
    assert status == [stored | fused, stored | fused | filled, stored]
    assert closing == [False, True, False]
    assert closed.tolist() == [[5] * B, [9, 9, 9, 0], [5] * B]
    assert left == [[False, True, False, False], [True] * B, [False] * B]
    assert blocks[0].tolist() == [0, 9, 9, 9]
    assert counts == [1, 2, 0]
    # an idle slot keeps what it has closing
    status, _, _, _, closing, _ = _transfer(
        eng, places, [True] * 3, [24, 24, 62], active=(False, True, False))
    assert status == [0, stored | fused | filled, 0]
    assert closing == [True, True, True]


# ------------------------------------------ (d) generation, the engine's

def _eos(eng):
    return getattr(eng.tokenizer, "eos_id",
                   getattr(eng.tokenizer, "eos_token_id", None))


def _stops(eng, extra=()):
    eos = _eos(eng)
    return tuple(extra) + (() if eos is None else (int(eos),))


@pytest.mark.parametrize("prompt_tokens", [3, 8, 9, 10, 11, 21])
def test_generation_is_the_references(params, prompt_tokens):
    """Prompts of every length mod 4 (one shorter than a block: no whole
    block, an empty chunk sets the slot's length): chunks, a tail that
    seeds the first block, denoise steps and store passes give the
    reference's tokens."""
    prompt = tokens_of(10 + prompt_tokens, prompt_tokens).tolist()
    eng = _engine(params)
    out = eng.generate([prompt], SamplingParams(max_tokens=10))[0]
    want, reason = reference_generate(params, prompt, 10, stop=_stops(eng))
    assert (out.token_ids, out.finish_reason) == (want, reason)
    stats = eng.stats
    assert stats["tokens_generated"] == 10 and reason == "length"
    assert stats["block_rows"] == stats["block_steps"]      # one slot
    assert stats["d2h_syncs"] == stats["decode_steps"] == stats[
        "block_steps"]
    # greedy on random weights: no draw passes 0.9, a place a step
    assert stats["block_places_confident"] == 0
    assert stats["blocks_stored"] == (prompt_tokens % B + 10) // B - (
        (prompt_tokens % B + 10) % B == 0)


@pytest.mark.parametrize("blocks,prompt_tokens", [(3, 8), (2, 11), (4, 4)])
def test_whole_blocks_take_denoising_steps_row_steps_each(params, blocks,
                                                          prompt_tokens):
    """A request of N whole blocks (after the prompt's tail): N x
    ``denoising_steps`` row-steps and the one dispatched behind its end
    — not N x (``denoising_steps`` + 1): every block but the last is
    stored by its successor's first step, none by a pass of its own."""
    prompt = tokens_of(60 + blocks, prompt_tokens).tolist()
    tail = prompt_tokens % B
    eng = _engine(params)
    out = eng.generate([prompt], SamplingParams(
        max_tokens=blocks * B - tail))[0]
    assert len(out.token_ids) == blocks * B - tail
    stats = eng.stats
    assert stats["block_places_confident"] == 0       # a place a step
    assert stats["block_rows"] == blocks * CFG.denoising_steps - tail + 1
    assert stats["block_store_rows"] == 0
    assert stats["blocks_stored"] == stats["blocks_fused"] == blocks - 1


def test_generation_stores_the_keys_a_store_pass_stores(params):
    """The engine's loop, its store passes riding, against the unfused
    schedule by hand through the same ONE program (``eng._decode_jit``:
    a store pass of its own a block): the tokens are the reference's
    and the stored keys and values equal bit for bit."""
    prompt = tokens_of(61, 10).tolist()
    eng = _engine(params)
    out = eng.generate([prompt], SamplingParams(max_tokens=14))[0]
    want, _ = reference_generate(params, prompt, 14, stop=_stops(eng))
    assert out.token_ids == want
    sequence = np.asarray(prompt + out.token_ids, np.int32)
    stored = 8 + 3 * B           # 2 + 14 = 4 blocks; the last is not stored
    assert eng.stats["blocks_fused"] == 3
    hand = _engine(params)
    slot = hand._free_slots[-1]
    buf = np.zeros((CHUNK,), np.int32)
    buf[:8] = sequence[:8]
    _, hand.cache = hand._prefill_chunk_jit(
        hand.params, hand.cache, jnp.asarray(buf), slot, 0, 8)
    for at in range(8, stored, B):
        blocks, active = one_slot(slot, sequence[at:at + B])
        _, hand.cache = hand._decode_jit(
            hand.params, hand.cache, blocks, jnp.zeros((SLOTS, B), bool),
            active)
    assert int(hand.cache["length"][slot]) == stored
    assert hand._mixed_step_jit._cache_size() == 1
    for name in llama.kv_slabs(CFG):
        assert np.array_equal(np.asarray(eng.cache[name][:, slot, :stored]),
                              np.asarray(hand.cache[name][:, slot, :stored]))


@pytest.mark.parametrize("max_tokens", [1, 2, 3, 4, 5, 6, 7, 8])
def test_max_tokens_cuts_the_last_block(params, max_tokens):
    prompt = tokens_of(20, 9).tolist()
    eng = _engine(params)
    out = eng.generate([prompt], SamplingParams(max_tokens=max_tokens))[0]
    want, _ = reference_generate(params, prompt, 8, stop=_stops(eng))
    assert out.finish_reason == "length"
    assert out.token_ids == want[:max_tokens]


@pytest.mark.parametrize("place", [0, 1, 2, 3])
def test_a_stop_token_ends_the_sequence_inside_its_block(params, place):
    prompt = tokens_of(21, 8).tolist()
    eng = _engine(params)
    want, _ = reference_generate(params, prompt, 12, stop=_stops(eng))
    at = next(i for i in range(4, 12) if i % B == place
              and want[i] not in want[:i])
    events = []
    eng.add_request(prompt, SamplingParams(
        max_tokens=12, stop_token_ids=(want[at],)),
        on_event=events.append)
    while eng.has_unfinished():
        eng.step()
    final = events[-1]["output"]
    assert final.finish_reason == "stop" and final.token_ids == want[:at]
    # what followed it in the block went to nobody
    assert [e["token_id"] for e in events[:-1]] == want[:at]
    assert not eng._active and sorted(eng._free_slots) == [0, 1, 2]


@pytest.mark.parametrize("max_seq", [21, 22, 23, 24])
def test_max_seq_is_reached_inside_a_block(params, max_seq):
    """A slab of 21..24 positions: the last block holds the places the
    slab has, and the sequence ends by length at the slab's end."""
    prompt = tokens_of(22, 9).tolist()
    eng = _engine(params, max_seq=max_seq)
    out = eng.generate([prompt], SamplingParams(max_tokens=max_seq - 9))[0]
    want, reason = reference_generate(params, prompt, 99, max_seq=max_seq,
                                      stop=_stops(eng))
    assert (out.token_ids, out.finish_reason) == (want, reason)
    assert reason == "length" and len(want) == max_seq - 9


def _run(eng, requests, land_every_step=False):
    got = {}
    for rid, (prompt, sampling) in requests.items():
        eng.add_request(prompt, sampling, rid)
    while eng.has_unfinished():
        for out in eng.step():
            got[out.request_id] = (out.token_ids, out.finish_reason)
        if land_every_step:
            eng._land_flight()
            for out in eng._finished:
                got[out.request_id] = (out.token_ids, out.finish_reason)
    return got


def test_the_flight_one_ahead_gives_the_tokens_of_landing_every_step(params):
    """Five sampled and greedy requests over three slots: the step
    dispatched before the last one is read decides nothing by it."""
    requests = {
        f"r{i}": (tokens_of(30 + i, n).tolist(), SamplingParams(
            max_tokens=m, temperature=t, top_k=k, seed=100 + i))
        for i, (n, m, t, k) in enumerate([
            (9, 11, 1.0, 0), (4, 6, 0.0, 0), (14, 9, 0.8, 5),
            (3, 13, 1.0, 0), (10, 5, 0.0, 0)])}
    ahead = _engine(params)
    a = _run(ahead, requests)
    b = _run(_engine(params), requests, land_every_step=True)
    assert a == b and len(a) == 5
    assert ahead.stats["decode_ahead_steps"] > 0
    assert ahead.stats["chunks_fused"] > 0        # chunks rode block steps


def test_a_block_model_runs_one_compiled_step_program(params):
    """Lone chunks, block steps alone and block steps with a riding
    chunk are ONE executable (``LLMEngine._block_programs``: on the chip
    a row's bits follow the program it goes through) — compiled once,
    whatever called it; a block step alone leaves every length it does
    not store where it was, and its padding rows are finite."""
    eng = _engine(params)
    assert not hasattr(eng._decode_jit, "lower")
    assert not hasattr(eng._prefill_chunk_jit, "lower")
    _run(eng, {"a": (tokens_of(50, 13).tolist(), SamplingParams(max_tokens=9)),
               "b": (tokens_of(51, 22).tolist(), SamplingParams(
                   max_tokens=6, temperature=1.0, seed=3)),
               "c": (tokens_of(52, 2).tolist(), SamplingParams(max_tokens=5)),
               "d": (tokens_of(53, 9).tolist(), SamplingParams(max_tokens=7))})
    assert eng.stats["chunks_fused"] > 0 and eng.stats["block_steps"] > 0
    assert eng._mixed_step_jit._cache_size() == 1
    # the no-chunk form by hand: slot SLOTS, behind the last
    before = np.asarray(eng.cache["length"])
    blocks, active = one_slot(1, tokens_of(54, B))
    seen = []
    rows = llama._routed_mlp

    def spy(layer, h, *a, **k):
        jax.debug.callback(lambda v: seen.append(
            bool(np.isfinite(np.asarray(v, np.float32)).all())), h)
        return rows(layer, h, *a, **k)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(llama, "_routed_mlp", spy)
        _, _, cache = jax.jit(
            lambda cache: llama.mixed_step(
                eng.params, blocks, jnp.zeros((CHUNK,), jnp.int32), cache,
                CFG, active, SLOTS, 1, 0,
                store=jnp.zeros((SLOTS,), bool)))(eng.cache)
        jax.effects_barrier()
    assert seen and all(seen)
    assert np.array_equal(cache["length"], before)


def test_seeded_requests_repeat_alone_and_in_company(params):
    prompt = tokens_of(40, 10).tolist()
    sampling = SamplingParams(max_tokens=12, temperature=1.0, seed=5)
    alone = _run(_engine(params), {"a": (prompt, sampling)})["a"]
    again = _run(_engine(params), {"a": (prompt, sampling)})["a"]
    company = _run(_engine(params), {
        "x": (tokens_of(41, 7).tolist(), SamplingParams(
            max_tokens=20, temperature=1.0, seed=6)),
        "a": (prompt, sampling),
        "y": (tokens_of(42, 13).tolist(), SamplingParams(max_tokens=9))})
    assert alone == again == company["a"] and len(alone[0]) == 12
    other = _run(_engine(params), {"a": (prompt, dataclasses.replace(
        sampling, seed=7))})["a"]
    assert other != alone


def test_a_confident_model_fills_a_block_in_one_step():
    """Logits sharpened by the head (x 40): draws pass the threshold,
    several places a step — fewer steps than places, and still the
    reference's tokens."""
    sharp = seeded_params()
    sharp = {**sharp, "lm_head": sharp["lm_head"] * 40.0}
    prompt = tokens_of(50, 10).tolist()
    eng = _engine(sharp)
    out = eng.generate([prompt], SamplingParams(max_tokens=14))[0]
    want, reason = reference_generate(sharp, prompt, 14, stop=_stops(eng))
    assert (out.token_ids, out.finish_reason) == (want, reason)
    stats = eng.stats
    assert stats["block_places_confident"] > 0
    assert stats["block_rows"] - stats["block_store_rows"] \
        < stats["block_places_filled"]


def test_stream_hands_over_a_block_at_a_time(params):
    prompt = tokens_of(51, 8).tolist()
    eng = _engine(params)
    chunks = list(eng.stream(prompt, SamplingParams(max_tokens=8)))
    want, _ = reference_generate(params, prompt, 8, stop=_stops(eng))
    assert [c["token_id"] for c in chunks[:-1]] == want
    assert chunks[-1]["finished"] and chunks[-1]["token_ids"] == want


# ------------------------------------- (e) block_length 0 is the parent

def test_no_block_length_is_the_model_without_the_field():
    plain = llama.CONFIGS["olmoe-tiny"]
    assert dataclasses.replace(plain, block_length=0) == plain
    eng = LLMEngine(plain, slots=2, max_seq=64, prefill_chunk_tokens=8)
    assert eng._masked is None and eng._last.shape == (2,)
    assert not any(name.startswith("block_steps") or name in
                   engine_mod.BLOCK_COUNTERS for name in eng.stats)
    decode = eng._decode_jit.lower(
        eng.params, eng.cache, eng._last,
        eng._jnp.ones((2,), bool)).as_text(debug_info=True)
    chunk = eng._prefill_chunk_jit.lower(
        eng.params, eng.cache, eng._jnp.zeros((8,), "int32"), 0, 0,
        3).as_text(debug_info=True)
    mixed = eng._mixed_step_jit.lower(
        eng.params, eng.cache, eng._last, eng._jnp.ones((2,), bool),
        eng._jnp.zeros((8,), "int32"), 0, 0, 3).as_text(debug_info=True)
    for text in (decode, chunk, mixed):
        assert "block_rows" not in text and "attn_full/" in text
    assert "jit(_decode)" in decode and "jit(_mixed_step)" in mixed
    blocks = _engine(seeded_params())
    text = blocks._mixed_step_jit.lower(
        blocks.params, blocks.cache, *blocks._fed(),
        blocks._jnp.ones((SLOTS,), bool),
        blocks._jnp.zeros((CHUNK,), "int32"), SLOTS, 0,
        0).as_text(debug_info=True)
    assert "block_rows/" in text and "jit(_block_decode)" in text
    sampler = blocks._sample_jit.lower(
        jnp.zeros((SLOTS * B, CFG.vocab_size)), blocks._keys,
        jnp.ones((SLOTS,), bool), jnp.zeros((SLOTS,)),
        jnp.zeros((SLOTS,), jnp.int32), jnp.ones((SLOTS,)),
        blocks.cache["routing"], *blocks._fed(),
        blocks._block_counts, blocks.cache["length"]).as_text(
            debug_info=True)
    assert "block_transfer/" in sampler


def test_the_two_forms_of_qk_norm_are_told_apart_in_one_place():
    whole = llama.CONFIGS["olmoe-tiny"]
    head = dataclasses.replace(whole, qk_norm="head")
    assert llama.param_shapes(whole)["layers"]["q_norm"] == (2, 64)
    assert llama.param_shapes(head)["layers"]["q_norm"] == (2, 16)
    assert llama.param_shapes(head)["layers"]["k_norm"] == (2, 16)
    p = seeded_params(head, 3)
    h = jnp.asarray(np.random.default_rng(0).normal(size=(5, 64)),
                    jnp.float32)
    layer = jax.tree.map(lambda x: x[0], p["layers"])
    q, k = llama._qk_proj(layer, h, head)
    raw = (h @ layer["wq"]).reshape(5, 4, 16)
    want = raw / np.sqrt((np.asarray(raw) ** 2).mean(-1, keepdims=True)
                         + head.norm_eps) * layer["q_norm"]
    assert np.allclose(q.reshape(5, 4, 16), want, atol=1e-5)
    assert k.shape == (5, 32)


# ------------------------------------------------------- (f) refusals

@pytest.mark.parametrize("changed,message", [
    ({"window": 8, "window_pattern": (True, False)}, "window layers"),
    ({"layer_kinds": ("full", "linear"), "linear_heads": 4,
      "linear_head_dim": 16, "full_rope": False}, "a recurrent kind"),
    ({"layer_kinds": ("ssm", "full"), "ssm_heads": 4, "ssm_head_dim": 16,
      "ssm_state": 16, "full_rope": False}, "a recurrent kind"),
    ({"q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
      "qk_rope_head_dim": 8, "v_head_dim": 16}, "latent attention"),
    ({"num_experts": 0, "loops": 2}, "loops"),
    ({"denoising_steps": 3}, "denoising_steps"),
    ({"denoising_steps": 0}, "denoising_steps"),
    ({"block_length": 1, "denoising_steps": 1}, "at least two places"),
    ({"mask_token": 256}, "mask_token"),
    ({"confidence_threshold": 0.0}, "confidence_threshold"),
    ({"block_length": 0}, "block_length is 0"),
    ({"qk_norm": "heads"}, "unknown qk_norm"),
])
def test_the_config_refuses_by_name_what_is_not_written(changed, message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(CFG, **changed)


def test_training_sessions_meshes_and_odd_chunks_are_refused(params):
    batch = {"tokens": jnp.zeros((1, 9), jnp.int32)}
    with pytest.raises(ValueError, match="noised copy"):
        llama.loss_fn(params, batch, CFG)
    with pytest.raises(ValueError, match="noised copy"):
        llama.loss_fn_pp(params, batch, CFG, mesh=None)
    with pytest.raises(ValueError, match="multiple of block_length"):
        _engine(params, prefill_chunk_tokens=6)
    eng = _engine(params)
    with pytest.raises(ValueError, match="block in flight"):
        eng.add_request([5, 6, 7], session_id="s")
    with pytest.raises(ValueError, match="not sharded"):
        _engine(params, tensor_parallel_size=2)


def _published():
    import json
    import os

    with open(os.path.join(os.path.dirname(__file__), "..", "chipbench",
                           "configs", "sdar-30b-a3b.json")) as f:
        return json.load(f)


def test_the_factory_maps_the_published_file():
    spec = _published()
    cfg = sdar_moe.build(spec)
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (
        2048, 32, 4, 128)
    assert (cfg.num_experts, cfg.experts_per_token, cfg.mlp_dim) == (
        128, 8, 768)
    assert (cfg.block_length, cfg.denoising_steps, cfg.mask_token,
            cfg.confidence_threshold) == (4, 4, 151669, 0.9)
    assert cfg.qk_norm == "head" and cfg.norm_topk_prob
    assert not cfg.tie_embeddings and cfg.vocab_size == 151936
    assert cfg.n_layers == 7 == spec["reduced"]["num_hidden_layers"]["to"]
    assert ref.dims_of(spec)["head_dim"] == 128


@pytest.mark.parametrize("changed", [
    {"mlp_only_layers": [0]}, {"decoder_sparse_step": 2},
    {"sliding_window": 4096}, {"use_sliding_window": True},
    {"rope_scaling": {"type": "yarn"}}, {"tie_word_embeddings": True},
    {"attention_bias": True},
])
def test_the_factory_refuses_what_it_does_not_map(changed):
    with pytest.raises(ValueError, match="sdar_moe.py maps"):
        sdar_moe.build({**_published(), **changed})


def test_the_factory_refuses_a_program_without_block_length(monkeypatch):
    """How the parent fails at once on the new cell."""
    fields = [f for f in dataclasses.fields(llama.LlamaConfig)
              if f.name != "block_length"]
    monkeypatch.setattr(dataclasses, "fields", lambda _cls: fields)
    with pytest.raises(ValueError, match="no block_length"):
        sdar_moe.build(_published())
