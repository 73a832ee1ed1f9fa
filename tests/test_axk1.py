"""A.X-K1's block (latent attention with YaRN, a leading dense layer, a
sigmoid router over more experts than are held, a shared expert) on the
program's normal paths, against the plain reference
``chipbench/reference/axk1_decoder.py`` on seeded random weights at a
tiny shape, on the CPU in float32.

The measure is the benchmark's own: the relative L2 distance of the
logits, per position.  Both sides compute in float32 (the reference at
the highest matmul precision, float32's own on the CPU), so the distance
is rounding and the order of summation — the program absorbs ``W_kvb``
into the query and sums over the latent's 16 values where the reference
sums over a head's 16 + 8: 4e-6 at worst here.  ``TOL`` = 5e-5 leaves it
ten times that and is three orders under what it must catch: the
softmax without YaRN's temperature (0.02 and more), the rotary key
un-rotated, gates not rescaled by 2.5 (0.3).
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
from jax import lax

from ant_ray_tpu.llm import LLMEngine, SamplingParams
from ant_ray_tpu.llm.programs import step_programs
from ant_ray_tpu.models import llama
from ant_ray_tpu.ops import rope
from chipbench.models import axk1
from chipbench.reference import axk1_decoder as ref

CFG = llama.CONFIGS["axk1-tiny"]
YARN = CFG.rope_scaling
DIMS = dict(
    n_heads=CFG.n_heads, n_kv_heads=CFG.n_kv_heads,
    rope_theta=CFG.rope_theta, norm_eps=CFG.norm_eps,
    yarn_factor=YARN.factor,
    yarn_original=float(YARN.original_max_position_embeddings),
    yarn_beta_fast=YARN.beta_fast, yarn_beta_slow=YARN.beta_slow,
    yarn_mscale=YARN.mscale, yarn_mscale_all_dim=YARN.mscale_all_dim,
    experts_per_token=CFG.experts_per_token,
    routed_scaling_factor=CFG.routed_scaling_factor,
    first_expert=CFG.first_expert)
TOL = 5e-5
SLOTS, MAX_SEQ, CHUNK = 3, 96, 16
PUBLISHED = os.path.join(os.path.dirname(__file__), "..", "chipbench",
                         "configs", "ax-k1.json")


def seeded_params(cfg, seed=0):
    """Seeded weights, less bland than the initialiser's: matrices large
    enough that the router decides and attention attends, norm weights
    that are not all ones (a swapped or missing norm shows)."""
    p = llama.init_params(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def livelier(stack):
        return {name: leaf * (jax.random.uniform(
            next(keys), leaf.shape, minval=0.5, maxval=1.5)
            if name.endswith("norm") or name.startswith("ln_") else 6.0)
            for name, leaf in stack.items()}

    return {**p, "norm_f": p["norm_f"] * 0.7,
            **{name: livelier(p[name]) for name in ("dense_layers", "layers")
               if name in p}}


@pytest.fixture(scope="module")
def params():
    return seeded_params(CFG)


def reference_logits(params, tokens, **dims):
    embed, layer, n, norm_f, head = axk1.reference_layers(params)
    return ref.forward(embed, (layer, n), norm_f, head, jnp.asarray(tokens),
                       **{**DIMS, **dims})


def rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(((got - want) ** 2).sum(-1) / (want ** 2).sum(-1))


def tokens_of(seed, *shape):
    return np.random.default_rng(seed).integers(
        0, CFG.vocab_size, shape).astype(np.int32)


def through_the_cache(cfg, params, tokens, prompt, slot=1):
    """``prompt`` tokens in chunks, the rest decoded one by one (teacher
    forced) in ``slot`` -> logits from the last prompt token on, cache."""
    cache = llama.init_kv_cache(cfg, SLOTS, MAX_SEQ)
    for start in range(0, prompt, CHUNK):
        part = tokens[start:min(start + CHUNK, prompt)]
        buf = np.zeros((CHUNK,), np.int32)
        buf[:len(part)] = part
        logits, cache = llama.prefill_chunk_into_cache(
            params, jnp.asarray(buf), cache, slot, start, len(part), cfg)
    got = [logits]
    active = np.zeros((SLOTS,), bool)
    active[slot] = True
    for token in tokens[prompt:]:
        last = np.zeros((SLOTS,), np.int32)
        last[slot] = token
        logits, cache = llama.decode_step(params, jnp.asarray(last), cache,
                                          cfg, jnp.asarray(active))
        got.append(logits[slot])
    return jnp.stack(got), cache


# ------------------------------------------------ (a) against the reference

def test_forward_logits_equal_the_reference(params):
    tokens = tokens_of(0, 48)
    got = llama.forward(params, tokens[None], CFG, remat="none")[0]
    assert rel_l2(got, reference_logits(params, tokens)).max() < TOL


@pytest.mark.parametrize("block", [llama.ATTEND_BLOCK, 20], ids=[
    "slab-under-a-block", "blocks-with-a-tail"])
@pytest.mark.parametrize("prompt", [40, 16, 7])
def test_chunks_then_decode_through_the_latent_cache_equal_the_reference(
        params, prompt, block, monkeypatch):
    """Prefill in the engine's chunks (a whole one, several, a partial
    one) and decode through the latent cache, absorbed, against the
    reference's full forward in the published per-head form — the slab
    attended over as one block, and in blocks of 20 positions with a
    tail (chunks and steps end on and across their edges)."""
    assert MAX_SEQ % 20
    monkeypatch.setattr(llama, "ATTEND_BLOCK", block)
    tokens = tokens_of(prompt, prompt + 8)
    got, _ = through_the_cache(CFG, params, tokens, prompt)
    want = reference_logits(params, tokens)[prompt - 1:-1]
    assert rel_l2(got[:-1], want).max() < TOL


@pytest.mark.parametrize("wrong,least", [
    (dict(yarn_mscale_all_dim=0.0), 5e-3),    # no temperature on the scores
    (dict(routed_scaling_factor=1.0), 5e-2),  # gates not rescaled
    (dict(yarn_factor=1.0), 5e-4),            # plain rotary frequencies
    (dict(first_expert=4), 5e-2),             # another rank's experts
])
def test_the_tolerance_sees_what_it_must(params, wrong, least):
    tokens = tokens_of(3, 48)
    got = llama.forward(params, tokens[None], CFG, remat="none")[0]
    off = rel_l2(got, reference_logits(params, tokens, **wrong))
    assert off.max() > least > TOL


# --------------------------------- (b) absorbed = per-head on the same cache

@pytest.mark.parametrize("a_slab_a_row", [False, True])
def test_absorbed_attention_equals_the_per_head_form_on_the_same_cache(
        a_slab_a_row):
    """The absorbed form of ``_attend_slab`` never makes a key or a
    value; made from the same cached latents, head by head as
    published, they give the same output."""
    c, rows, seq = CFG, 5, 24
    keys = jax.random.split(jax.random.PRNGKey(4), 4)
    lead = (rows,) if a_slab_a_row else ()
    xq = jax.random.normal(keys[0], (rows, c.n_heads, c.head_dim))
    c_kv = jax.random.normal(keys[1], (*lead, seq, c.kv_lora_rank))
    k_rope = jax.random.normal(keys[2], (*lead, seq, c.qk_rope_head_dim))
    w_kvb = jax.random.normal(keys[3], (c.kv_lora_rank, c.n_heads * (
        c.qk_nope_head_dim + c.v_head_dim))) * 0.3
    pos = jnp.asarray([0, 3, 23, 11, 7])
    # as the step programs hold them: (layers, slots, max_seq, ·)
    carried = [slab[None] if a_slab_a_row else slab[None, None]
               for slab in (c_kv, k_rope)]
    got = llama._attend_slab(xq, *carried, 0, None if a_slab_a_row else 0,
                             pos, llama._span_blocks(seq, seq), c, w_kvb)

    w = np.asarray(w_kvb).reshape(c.kv_lora_rank, c.n_heads, -1)
    want = np.zeros((rows, c.n_heads, c.v_head_dim))
    for r in range(rows):
        latent = np.asarray(c_kv[r] if a_slab_a_row else c_kv)
        rotary = np.asarray(k_rope[r] if a_slab_a_row else k_rope)
        n = int(pos[r]) + 1
        for h in range(c.n_heads):
            kv = latent[:n] @ w[:, h]                       # (n, nope + v)
            k = np.concatenate([kv[:, :c.qk_nope_head_dim], rotary[:n]], 1)
            s = k @ np.asarray(xq[r, h]) * c.attn_scale
            p = np.exp(s - s.max())
            want[r, h] = (p / p.sum()) @ kv[:, c.qk_nope_head_dim:]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


# ------------------------------------------------------ (c) shares add up

def test_the_ranks_shares_add_up_to_the_uncut_layer(params):
    """Two ranks hold four of the router's eight experts each: the
    routed parts of their shares, plus the shared expert counted once,
    are the uncut layer — the reference's, with all eight held."""
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
    with jax.named_scope("moe_shared"):
        total = total + llama._swiglu(h, layer["shared_gate"],
                                      layer["shared_up"],
                                      layer["shared_down"])
    assert int(sum(jnp.sum(load) for load in loads)) == 40 * 2   # none lost
    uncut = {**layer, **full}
    gates = ref.gate_map(h, uncut["router"], 2, 2.5)
    want = ref.held_experts(uncut, h, gates, 0) + ref.swiglu(
        h, layer["shared_gate"], layer["shared_up"], layer["shared_down"])
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-6)
    # and the program with all eight held says the same
    whole, load = llama._mlp(uncut, h, dataclasses.replace(
        CFG, num_experts=8))
    np.testing.assert_allclose(whole, want, rtol=2e-4, atol=2e-6)
    np.testing.assert_array_equal(load, jnp.concatenate(loads))


# ------------------------- (d) all held, softmax: the router that was there

def routed_mlp_as_it_was(layer, h, c, index=None):
    """``llama._routed_mlp`` before it learned sigmoid scores and a
    share (PR 27's), kept here and nowhere else."""
    lead, dim = h.shape[:-1], h.shape[-1]
    k, n_exp = c.experts_per_token, c.num_experts
    x = h.reshape(-1, dim)
    probs = jax.nn.softmax(jnp.dot(
        x, layer["router"], preferred_element_type=jnp.float32), axis=-1)
    gates, experts = lax.top_k(probs, k)
    if c.norm_topk_prob:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    experts = experts.reshape(-1)
    order = jnp.argsort(experts)
    load = jnp.zeros((n_exp,), jnp.int32).at[experts].add(1)
    rows = x[order // k]
    sizes = load if index is None else lax.dynamic_update_slice(
        jnp.zeros((layer["w_down"].shape[0] * n_exp,), jnp.int32),
        load, (index * n_exp,))

    def grouped(a, w):
        return lax.ragged_dot(a, w.reshape(-1, *w.shape[-2:]), sizes,
                              preferred_element_type=jnp.float32)

    gated = jax.nn.silu(grouped(rows, layer["w_gate"])) * grouped(
        rows, layer["w_up"])
    out = grouped(gated.astype(h.dtype), layer["w_down"])
    out = out[jnp.argsort(order)].reshape(-1, k, dim) * gates[..., None]
    # the picks' terms in their order (PR 53: a sum over the axis is
    # added in an order the compiler takes from the shape)
    out = functools.reduce(jnp.add, [out[:, j] for j in range(k)])
    return out.astype(h.dtype).reshape(*lead, dim), load


@pytest.mark.parametrize("whole_stack", [False, True])
@pytest.mark.parametrize("name", ["olmoe-tiny", "moe-tiny"])
def test_with_all_experts_held_the_router_is_bit_for_bit_what_it_was(
        name, whole_stack):
    cfg = llama.CONFIGS[name]
    stack = seeded_params(cfg, 3)["layers"]
    layer = stack if whole_stack else {k: v[1] for k, v in stack.items()}
    if whole_stack:
        layer = {**{k: v[1] for k, v in stack.items()},
                 **{k: stack[k] for k in ("w_gate", "w_up", "w_down")}}
    h = jax.random.normal(jax.random.PRNGKey(5), (33, cfg.dim))
    index = jnp.int32(1) if whole_stack else None
    got = jax.jit(lambda: llama._routed_mlp(layer, h, cfg, index))()
    want = jax.jit(lambda: routed_mlp_as_it_was(layer, h, cfg, index))()
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])


# ------------------------------------------------------------- (e) YaRN

def test_yarn_frequencies_at_the_published_numbers():
    """64 rotary dimensions, theta 10,000, factor 32 over 4,096, beta 32
    and 1: the blend runs from pair 10 to pair 23 (64 ln(4096 / (beta 2
    pi)) / (2 ln theta) = 10.47 and 22.51), worked by hand."""
    scaling = rope.YarnScaling(32.0, 4096, mscale=1.0, mscale_all_dim=1.0)
    got = np.asarray(rope.yarn_inv_freq(64, 10000.0, scaling))
    assert got.shape == (32,)
    f = lambda j: 10000.0 ** (-2 * j / 64)                   # noqa: E731
    np.testing.assert_allclose(got[0], 1.0)
    np.testing.assert_allclose(got[10], 10 ** -1.25, rtol=1e-5)     # plain
    np.testing.assert_allclose(got[:11], [f(j) for j in range(11)],
                               rtol=1e-5)
    np.testing.assert_allclose(                   # 6/13 of the way scaled
        got[16], 0.01 / 32 * 6 / 13 + 0.01 * 7 / 13, rtol=1e-5)
    np.testing.assert_allclose(got[16], 0.00552885, rtol=1e-5)
    np.testing.assert_allclose(got[23:], [f(j) / 32 for j in range(23, 32)],
                               rtol=1e-5)
    np.testing.assert_allclose(got[23], 4.1673e-5, rtol=1e-4)
    # the reference's own, written apart, agrees
    np.testing.assert_allclose(got, ref.yarn_inv_freq(
        64, 10000.0, 32.0, 4096.0, 32.0, 1.0), rtol=1e-6)
    # mscale = mscale_all_dim: cos and sin unscaled, scores x 1.3466^2
    cos, sin = rope.rope_frequencies(64, 8, 10000.0, scaling=scaling)
    np.testing.assert_allclose(cos[0], 1.0)
    np.testing.assert_allclose(sin[1], np.sin(got), rtol=1e-5)
    assert rope.yarn_mscale(32.0, 1.0) == pytest.approx(1.3466, abs=5e-5)
    published = axk1.build(json.load(open(PUBLISHED)))
    assert published.attn_scale == pytest.approx(
        192 ** -0.5 * 1.8133, rel=1e-4)


def test_rotating_half_split_equals_rotating_the_published_pairs():
    """The program rotates pairs (j, j + d/2), the published weights pair
    (2j, 2j + 1): re-laid by ``half_split_from_interleaved`` the scores
    are the same."""
    perm = rope.half_split_from_interleaved(8)
    q, k = jax.random.normal(jax.random.PRNGKey(0), (2, 6, 1, 8))
    pos = jnp.arange(6)
    inv = rope.yarn_inv_freq(8, 10000.0, YARN)
    cos, sin = rope.rope_frequencies(8, 6, 10000.0, scaling=YARN)
    ours = jnp.sum(rope.apply_rope(q[..., perm], cos, sin, pos)
                   * rope.apply_rope(k[..., perm], cos, sin, pos), -1)
    theirs = jnp.sum(ref.rotary(q, pos, inv, 1.0)
                     * ref.rotary(k, pos, inv, 1.0), -1)
    np.testing.assert_allclose(ours, theirs, rtol=1e-5, atol=1e-6)


# ------------------------------------------- (f) the cache, written in place

def test_the_latent_cache_is_576_values_a_position_at_published_widths():
    """8,064 bytes a position over the 7 layers, from the cache's own
    leaves: the latent and ONE rotary key, in bfloat16 — no heads axis,
    no per-head key or value anywhere."""
    cfg = axk1.build(json.load(open(PUBLISHED)))
    cache = jax.eval_shape(lambda: llama.init_kv_cache(cfg, 48, 4096))
    slabs = {name: leaf for name, leaf in cache.items()
             if name not in ("length", "routing")}
    assert {name: leaf.shape for name, leaf in slabs.items()} == {
        "c_kv": (7, 48, 4096, 512), "k_rope": (7, 48, 4096, 64)}
    held = sum(leaf.size * leaf.dtype.itemsize for leaf in slabs.values())
    assert held == 8064 * 48 * 4096
    assert all(leaf.dtype == jnp.bfloat16 for leaf in slabs.values())
    assert cache["routing"].shape == (len(llama.ROUTING_COUNTERS),)


def test_a_step_returns_the_donated_latent_cache(params, recwarn):
    """The engine's own programs (``step_programs``: the cache
    donated): every leaf of the cache a step returns is the buffer it
    was given, its rows written where they lie."""
    cache = jax.tree.map(jnp.copy, llama.init_kv_cache(CFG, SLOTS, MAX_SEQ))
    run = step_programs(CFG, slots=SLOTS, max_seq=MAX_SEQ, chunk=CHUNK)
    decode, chunk = run.decode, run.prefill_chunk
    for step in (
            lambda c: chunk(params, c, jnp.arange(CHUNK, dtype=jnp.int32),
                            1, 0, 9),
            lambda c: decode(params, c, jnp.zeros((SLOTS,), jnp.int32),
                             jnp.asarray([False, True, False]))):
        given = {name: leaf.unsafe_buffer_pointer()
                 for name, leaf in cache.items()}
        # of a copy: the host's view of a leaf would pin its buffer
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
            assert set(changed[:, 0]) == set(range(CFG.n_layers))
            assert set(changed[:, 1]) == {1}
            assert changed[:, 2].max() <= 9
    assert int(cache["length"][1]) == 10
    assert not [w for w in recwarn if "donated" in str(w.message)]


# ----------------------------------------------------- through the engine

def _engine(params, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_seq", MAX_SEQ)
    kw.setdefault("prefill_chunk_tokens", 8)
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


def test_engine_greedy_tokens_are_the_references_and_routing_is_counted(
        params):
    eng = _engine(params, slots=3)
    prompts = [tokens_of(20 + i, n).tolist() for i, n in enumerate((5, 19))]
    outs = eng.generate(prompts, SamplingParams(max_tokens=6))
    for prompt, out in zip(prompts, outs):
        seq = list(prompt)
        for token in out.token_ids:
            want = int(jnp.argmax(reference_logits(
                params, np.asarray(seq, np.int32))[-1]))
            assert token == want
            seq.append(token)
    stats = eng.stats
    assert set(llama.ROUTING_COUNTERS) <= set(stats)
    # 2 routed layers; every execution offers each its 4 held experts
    assert stats["moe_expert_slots"] % (2 * 4) == 0
    runs = stats["moe_expert_slots"] // (2 * 4)
    # a chunk that rode a decode step is one program with it, and the
    # first lone chunk ran the mixed program once before it, empty
    chunks = stats["chunks"]
    assert stats["chunks_fused"] > 0
    assert runs == stats["decode_steps"] + chunks - stats["chunks_fused"] + 1
    assert 0 < stats["moe_assignments"] < stats["moe_rows_routed"]
    assert stats["moe_experts_hit"] <= stats["moe_expert_slots"]
    # a chunk computes 8 rows, a decode step 3, each routed to 2 of 8
    # experts in 2 layers: the prompts' tokens and the decoding rows are
    # live, the others' pairs (the empty run's all) reach no expert
    assert stats["moe_rows_routed"] == 2 * 2 * (
        sum(map(len, prompts)) + stats["decode_slots"])
    assert stats["moe_rows_routed"] + stats["moe_dead_pairs"] == 2 * 2 * (
        8 * chunks + 3 * stats["decode_steps"] + (3 + 8))


def test_decode_steps_are_counted_apart_from_chunks(params):
    """``ROUTING_COUNTERS``: the first five over every execution, the
    ``moe_decode_*`` four over the decode steps alone — 2 chunks of 16
    rows, then 5 steps of 3 rows of which ONE is live, 2 routed layers
    of 4 held experts, 2 picks a row over the router's 8."""
    _, cache = through_the_cache(CFG, params, tokens_of(31, 37), 32)
    seen = dict(zip(llama.ROUTING_COUNTERS, np.asarray(cache["routing"])))
    assert seen["moe_expert_slots"] == 2 * 4 * (2 + 5)
    assert seen["moe_decode_expert_slots"] == 2 * 4 * 5
    assert seen["moe_rows_routed"] == 2 * 2 * (2 * 16 + 5 * 1)
    assert seen["moe_decode_rows_routed"] == 2 * 2 * 5 * 1
    assert seen["moe_dead_pairs"] == 2 * 2 * 5 * 2
    assert 0 < seen["moe_decode_assignments"] < seen["moe_assignments"]
    assert 0 < seen["moe_decode_experts_hit"] < seen["moe_experts_hit"]
    assert seen["moe_decode_experts_hit"] <= seen["moe_decode_expert_slots"]


# (g)
def test_an_evicted_latent_slot_round_trips_bit_for_bit(params):
    """Idle eviction moves the slot's LATENT slabs (``c_kv``,
    ``k_rope``) to the store and back; the turns' tokens are those of
    an engine that never evicts."""
    turns = [([5, 9, 17], 6), ([3, 88, 41, 2], 6), ([11, 12], 6)]
    base = _engine(params)
    want = [_turn(base, "s", p, n) for p, n in turns]
    assert base.stats["offloads"] == 0
    evict = _engine(params, kv_idle_evict_s=0.0)
    got = []
    for p, n in turns:
        got.append(_turn(evict, "s", p, n))
        evict.step()                 # idle sweep fires (cutoff = now)
        assert evict._sessions["s"].state == "offloaded"
    assert got == want
    assert evict.stats["restores"] >= 2
    *slabs, length = evict._store().get(evict._sessions["s"].handle)
    assert [s.shape for s in slabs] == [
        (CFG.n_layers, MAX_SEQ, CFG.kv_lora_rank),
        (CFG.n_layers, MAX_SEQ, CFG.qk_rope_head_dim)]
    assert length == evict._sessions["s"].kv_len > 20
    assert evict.stats["offload_bytes"] >= sum(s.nbytes for s in slabs)


def test_the_engine_refuses_to_shard_a_latent_cache(params):
    with pytest.raises(ValueError, match="latent .* no heads axis"):
        LLMEngine(CFG, params, slots=2, max_seq=MAX_SEQ,
                  tensor_parallel_size=2)


# ------------------------------------------- the counts, without allocating

def test_num_params_and_flops_at_the_published_widths():
    """The issue's table: 4,841,331,712 parameters held (1 dense + 6
    routed layers with 12 of 192 experts, 1/8 of the vocabulary), by
    ``jax.eval_shape`` of the initialiser — nothing is allocated."""
    cfg = axk1.build(json.load(open(PUBLISHED)))
    shapes = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    held = sum(leaf.size for leaf in jax.tree.leaves(shapes))
    assert held == cfg.num_params() == 4_841_331_712
    attention = (7168 * 1536 + 1536 * 64 * 192 + 7168 * 576
                 + 512 * 64 * 256 + 8192 * 7168 + 2 * 7168 + 1536 + 512)
    assert attention == 101_138_432
    expert = 3 * 7168 * 2048
    routed = attention + expert + 7168 * 192 + 12 * expert
    assert routed == 675_037_184
    assert sum(leaf.size for leaf in jax.tree.leaves(
        shapes["dense_layers"])) == attention + 3 * 7168 * 18432 \
        == 497_500_160
    # training operations a token: 6 x the parameters it multiplies with
    # (of the 12 held experts the 8 x 12/192 = 0.5 an even router sends
    # it to), and attention over seq keys at 192 + 128 a head
    seq = 4096
    want = 6 * (held - 6 * 11.5 * expert) + 6 * 7 * 64 * seq * (192 + 128)
    assert llama.flops_per_token(cfg, seq) == pytest.approx(want, rel=1e-12)
    # and of a model whose fields are the old ones, what it was
    old = llama.CONFIGS["olmoe-tiny"]
    assert llama.flops_per_token(old, 128) == 6 * (
        old.num_params() - 2 * 6 * 3 * 64 * 32) + 12 * 2 * 16 * 4 * 128
