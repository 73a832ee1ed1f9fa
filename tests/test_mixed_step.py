"""``llama.mixed_step`` (PR 39): a decode step and one prompt's chunk as
ONE program, held to the two programs it stands in for —
``prefill_chunk_into_cache`` followed by ``decode_step`` on the same
cache — over the presets that cover every kind of layer the engine
serves: dense full slabs (``tiny``), routed experts (``olmoe-tiny``),
window rings beside full slabs under a parallel block with shared
experts (``cmdaplus-tiny``), a latent cache with a leading dense layer
and a share of a router's experts (``axk1-tiny``), linear layers with
their states and conv tails beside a gated softmax layer
(``solar2-tiny``).

What is compared, and how closely.  The decode rows and the chunk's
rows go through the same equations either way; only the products' row
count differs (slots + chunk rows at once), and a row's sums are its
own whatever rows share its operand — so everything a caller reads is
held to the BIT: the active rows' logits, the chunk's logits (the head
is multiplied as each part's own program multiplies it: as ONE operand
with the decode rows the chunk's last row read 2e-7 off here on the CPU
and 1.6e-3 off on the chip, and a greedy token then depended on whether
its prompt ended in company), slabs, rings, states, tails and lengths.
Rows nobody reads (an inactive slot's logits) are not compared, and of
the routing counters only the totals that do not depend on them: a row
computed either way routes either way, but an idle row's input
differs, so which experts it hits does."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ant_ray_tpu.llm import LLMEngine
from ant_ray_tpu.models import llama

PRESETS = ["tiny", "olmoe-tiny", "cmdaplus-tiny", "axk1-tiny", "solar2-tiny",
           "granite-h-tiny"]
SLOTS, MAX_SEQ, CHUNK = 4, 96, 8


@functools.lru_cache(maxsize=None)
def programs(name):
    """The three step programs of a preset, jitted once (run eagerly
    they compile their layer scan anew on every call), and weights."""
    c = llama.CONFIGS[name]
    return (c, llama.init_params(c, jax.random.PRNGKey(1)),
            jax.jit(functools.partial(llama.prefill_chunk_into_cache,
                                      config=c)),
            jax.jit(functools.partial(llama.decode_step, config=c)),
            jax.jit(functools.partial(llama.mixed_step, config=c)))


def padded(part):
    buf = np.zeros((CHUNK,), np.int32)
    buf[:len(part)] = part
    return jnp.asarray(buf)


@functools.lru_cache(maxsize=None)
def staged(name):
    """A cache with two prompts ingested (slots 0 and 1: 13 and 30
    tokens — 30 wraps ``cmdaplus-tiny``'s rings of 24 rows), the first
    chunk of a third in slot 2, and slot 3 never used; the third
    prompt's tokens; a token for every slot."""
    c, params, chunk, _, _ = programs(name)
    rng = np.random.default_rng(0)
    cache = llama.init_kv_cache(c, SLOTS, MAX_SEQ, CHUNK)
    for slot, n in ((0, 13), (1, 30)):
        tokens = rng.integers(1, c.vocab_size, n)
        for at in range(0, n, CHUNK):
            part = tokens[at:at + CHUNK]
            _, cache = chunk(params, padded(part), cache, slot, at,
                             len(part))
    third = rng.integers(1, c.vocab_size, 13)
    _, cache = chunk(params, padded(third[:CHUNK]), cache, 2, 0, CHUNK)
    last = jnp.asarray(rng.integers(1, c.vocab_size, SLOTS), jnp.int32)
    return cache, third, last


def rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def kept(c):
    """The cache's leaves that hold a slot's sequence."""
    return (*llama.kv_slabs(c), *llama.state_slabs(c))


@pytest.mark.parametrize("name", PRESETS)
@pytest.mark.parametrize("real", [CHUNK, 5], ids=["whole", "padded"])
def test_mixed_step_is_the_chunk_and_the_decode_step(name, real):
    """Rows 0 and 1 decode while slot 2 ingests its second chunk —
    whole, or 5 real tokens padded to 8 (a prompt's last)."""
    c, params, chunk, decode, mixed = programs(name)
    cache, third, last = staged(name)
    active = jnp.asarray([True, True, False, False])
    tokens = padded(third[CHUNK:CHUNK + real])
    chunk_logits, two = chunk(params, tokens, cache, 2, CHUNK, real)
    decode_logits, two = decode(params, last, two, active=active)
    got_decode, got_chunk, one = mixed(
        params, last, tokens, cache, active=active, slot=2, start=CHUNK,
        chunk_len=real)
    assert got_decode.shape == (SLOTS, c.vocab_size)
    assert got_chunk.shape == (c.vocab_size,)
    assert np.array_equal(got_decode[:2], decode_logits[:2])
    assert np.array_equal(got_chunk, chunk_logits)
    assert set(one) == set(two) == set(cache)
    for leaf in (*kept(c), "length"):
        assert np.array_equal(one[leaf], two[leaf]), leaf
    assert np.asarray(one["length"]).tolist() == [14, 31, CHUNK + real, 0]
    # the slot nobody touched keeps every bit of what it held, and so
    # do the decoding rows' positions behind their new one
    for leaf in kept(c):
        assert np.array_equal(np.asarray(one[leaf])[:, 3],
                              np.asarray(cache[leaf])[:, 3]), leaf
    if c.num_experts:
        # one execution among all, none among the decode steps'
        counted = dict(zip(llama.ROUTING_COUNTERS,
                           np.asarray(one["routing"]).tolist()))
        before = dict(zip(llama.ROUTING_COUNTERS,
                          np.asarray(cache["routing"]).tolist()))
        apart = dict(zip(llama.ROUTING_COUNTERS,
                         np.asarray(two["routing"]).tolist()))
        routed_layers = c.n_layers - c.n_dense_layers
        # the two decoding rows' and the real tokens' pairs are routed,
        # the idle slots' and the padding's reach no expert
        pairs = routed_layers * c.experts_per_token
        assert counted["moe_rows_routed"] == apart["moe_rows_routed"] == (
            before["moe_rows_routed"] + pairs * (2 + real))
        assert counted["moe_dead_pairs"] == apart["moe_dead_pairs"] == (
            before["moe_dead_pairs"] + pairs * (SLOTS - 2 + CHUNK - real))
        assert counted["moe_expert_slots"] - before["moe_expert_slots"] \
            == routed_layers * c.num_experts
        assert apart["moe_expert_slots"] - before["moe_expert_slots"] \
            == 2 * routed_layers * c.num_experts
        for key in ("moe_decode_assignments", "moe_decode_experts_hit",
                    "moe_decode_expert_slots", "moe_decode_rows_routed"):
            assert counted[key] == before[key] == 0
            assert apart[key] > 0
        if not c.router_width:        # every expert held: every pick counts
            assert counted["moe_assignments"] == apart["moe_assignments"]


@pytest.mark.parametrize("name", PRESETS)
def test_the_empty_call_leaves_the_cache_as_it_is(name):
    """No row active, no token of the chunk real, at the slot's own
    length — a used slot's (14 positions) and a never used one's (0,
    where a linear layer begins from an empty state: the zeros it
    finds): slabs, rings, states, tails and lengths keep every value.
    The routing counters count the execution: its rows were computed."""
    c, params, _, _, mixed = programs(name)
    cache, third, last = staged(name)
    for slot in (0, 3):
        _, _, after = mixed(
            params, last, padded(third[:CHUNK]), cache,
            active=jnp.zeros((SLOTS,), bool), slot=slot,
            start=cache["length"][slot], chunk_len=0)
        for leaf in (*kept(c), "length"):
            assert np.array_equal(after[leaf], cache[leaf]), (slot, leaf)
        if c.num_experts:
            assert int(after["routing"][2]) > int(cache["routing"][2])


@pytest.mark.parametrize("name", PRESETS)
def test_a_full_slot_and_a_prompts_first_chunk(name):
    """The edges of both parts at once: the chunk BEGINS a prompt in a
    slot another sequence left (``start`` 0: a linear layer's state
    begins empty, whatever lies there), and a decoding row is at its
    slab's last position."""
    c, params, chunk, decode, mixed = programs(name)
    cache, third, last = staged(name)
    cache = {**cache, "length": cache["length"].at[1].set(MAX_SEQ - 1)}
    active = jnp.asarray([True, True, False, False])
    tokens = padded(third[:6])
    chunk_logits, two = chunk(params, tokens, cache, 2, 0, 6)
    decode_logits, two = decode(params, last, two, active=active)
    got_decode, got_chunk, one = mixed(
        params, last, tokens, cache, active=active, slot=2, start=0,
        chunk_len=6)
    assert np.array_equal(got_decode[:2], decode_logits[:2])
    assert np.array_equal(got_chunk, chunk_logits)
    for leaf in (*kept(c), "length"):
        assert np.array_equal(one[leaf], two[leaf]), leaf
    assert np.asarray(one["length"]).tolist() == [14, MAX_SEQ, 6, 0]


@pytest.mark.parametrize("name", ["tiny", "olmoe-tiny", "cmdaplus-tiny"])
def test_mixed_step_under_a_tp_mesh(name):
    """The engine's own jitted programs with parameters and slabs
    sharded two ways over ``tp`` (the CPU's devices; a latent cache and
    a recurrent state are refused there by name): the mixed step
    against the chunk then the decode step.  The partitioner splits the
    products, so sums regroup: float32 tolerance 1e-4, slabs included."""
    c = llama.CONFIGS[name]
    eng = LLMEngine(c, slots=SLOTS, max_seq=MAX_SEQ,
                    prefill_chunk_tokens=CHUNK, tensor_parallel_size=2)
    rng = np.random.default_rng(3)
    for slot, n in ((0, 13), (1, 30)):
        tokens = rng.integers(1, c.vocab_size, n)
        for at in range(0, n, CHUNK):
            part = tokens[at:at + CHUNK]
            _, eng.cache = eng._prefill_chunk_jit(
                eng.params, eng.cache, padded(part), slot, at, len(part))
    tokens = padded(rng.integers(1, c.vocab_size, 5))
    last = jnp.asarray(rng.integers(1, c.vocab_size, SLOTS), jnp.int32)
    active = jnp.asarray([True, True, False, False])
    copy = jax.tree.map(jnp.copy, eng.cache)       # both paths donate
    chunk_logits, two = eng._prefill_chunk_jit(
        eng.params, copy, tokens, 2, 0, 5)
    decode_logits, two = eng._decode_jit(eng.params, two, last, active)
    got_decode, got_chunk, one = eng._mixed_step_jit(
        eng.params, eng.cache, last, active, tokens, 2, 0, 5)
    assert rel_l2(got_decode[:2], decode_logits[:2]) <= 1e-4
    assert rel_l2(got_chunk, chunk_logits) <= 1e-4
    assert np.array_equal(one["length"], two["length"])
    for leaf in llama.kv_slabs(c):
        assert one[leaf].sharding == two[leaf].sharding
        assert rel_l2(np.asarray(one[leaf], np.float32)[:, :3],
                      np.asarray(two[leaf], np.float32)[:, :3]) <= 1e-4, leaf


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_the_spelt_out_rounding_is_the_rounding(dtype):
    """``_swiglu(step=True)`` rounds the gate's and the up product by
    an operation of their own (``_rounded``), so that no program skips
    the rounding on the chip: the values are the ones the bare form's
    ``astype`` gives, to the bit, here where nothing is skipped."""
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    h = jax.random.normal(keys[0], (12, 64)).astype(dtype)
    w_gate, w_up = (jax.random.normal(k, (64, 256)).astype(dtype) * 0.1
                    for k in keys[1:3])
    w_down = jax.random.normal(keys[3], (256, 64)).astype(dtype) * 0.1
    bare = jax.jit(llama._swiglu)(h, w_gate, w_up, w_down)
    spelt = jax.jit(functools.partial(llama._swiglu, step=True))(
        h, w_gate, w_up, w_down)
    assert spelt.dtype == bare.dtype == dtype
    assert np.array_equal(np.asarray(spelt.astype(jnp.float32)),
                          np.asarray(bare.astype(jnp.float32)))
    x = jnp.asarray([1.00390625, -3.0e-5, 1e30], jnp.float32)
    assert np.array_equal(
        np.asarray(llama._rounded(x, jnp.bfloat16).astype(jnp.float32)),
        np.asarray(x.astype(jnp.bfloat16).astype(jnp.float32)))
