"""``decode_step`` and ``prefill_chunk_into_cache`` write their rows into
the cache they are given — the cache is the layer loop's carry — and
must give what the form they replace gave (the cache scanned over as
input and output, each layer's slab taken out, written and put back).

The reference is that replaced form itself, kept here and nowhere else:
``scanned_decode_step`` and ``scanned_prefill_chunk`` are the two bodies
as ``models/llama.py`` had them before the cache became the carry.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ant_ray_tpu.llm.programs import step_programs
from ant_ray_tpu.models import llama
from ant_ray_tpu.ops.rmsnorm import rmsnorm

CONFIGS = {
    "tiny": llama.CONFIGS["tiny"],                          # float32
    "olmoe-tiny": llama.CONFIGS["olmoe-tiny"],
    "tiny-bf16": dataclasses.replace(llama.CONFIGS["tiny"],
                                     dtype=jnp.bfloat16),
    "olmoe-tiny-bf16": dataclasses.replace(llama.CONFIGS["olmoe-tiny"],
                                           dtype=jnp.bfloat16),
}
SLOTS, MAX_SEQ, CHUNK = 4, 96, 16
# Logits, relative L2 a row, by the cache's itemsize.  float32: the two
# forms differ by the order of their sums.  bf16: they round the
# probabilities at different places (see ROW_ATOL), and through two
# layers whose weights are scaled x 4 each form reads 0.6-1.3 % from the
# same step in float32 and 0.5-1.3 % from the other (PR 33, measured
# here): four steps of bf16.
LOGIT_TOL = {4: 2.0 ** -8, 2: 2.0 ** -5}


def _rope_one(x, cos, sin):
    """Rotate (rows, heads, hd) by cos/sin already gathered at the rows'
    positions (rows, 1, hd/2) — the reference's own, as ``llama.py`` had
    it beside the two bodies below."""
    half = x.shape[-1] // 2
    xf1 = x[..., :half].astype(jnp.float32)
    xf2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin],
        axis=-1).astype(x.dtype)


def _counted(cache, loads, decode, c, live):
    """The program's own counters, of a model that holds every expert
    (what it routes for its ``live`` rows is what it computes; the other
    rows' pairs reach no expert and are counted dead)."""
    if loads is None:
        return {}
    return llama._count_routing(
        cache, loads, jnp.sum(loads), decode,
        dead=c.n_layers * c.experts_per_token * jnp.sum(~live))


def scanned_prefill_chunk(params, tokens, cache, slot, start, chunk_len,
                          config):
    c = config
    chunk = tokens.shape[0]
    max_seq = cache["k"].shape[2]
    cos, sin = llama.rope_frequencies(c.head_dim, c.max_seq, c.rope_theta,
                                      jnp.float32)
    group = c.n_heads // c.n_kv_heads
    slot = jnp.asarray(slot, jnp.int32)
    start = jnp.asarray(start, jnp.int32)
    chunk_len = jnp.asarray(chunk_len, jnp.int32)
    offs = jnp.arange(chunk, dtype=jnp.int32)
    pos = start + offs
    real = offs < chunk_len
    write_pos = jnp.where(real, pos, jnp.int32(max_seq))
    rope_pos = jnp.minimum(pos, jnp.int32(c.max_seq - 1))
    pc = cos[rope_pos][:, None, :]
    ps = sin[rope_pos][:, None, :]

    layers, experts, index = llama._hoist_experts(params["layers"], c)

    def block(x, scanned):
        layer, ck_all, cv_all, i = scanned
        h = rmsnorm(x, layer["ln_attn"], c.norm_eps)
        xq, xk = llama._qk_proj(layer, h, c, True)
        xq = xq.reshape(chunk, c.n_heads, c.head_dim)
        xk = xk.reshape(chunk, c.n_kv_heads, c.head_dim)
        xv = llama._proj(h, layer["wv"], True).reshape(
            chunk, c.n_kv_heads, c.head_dim)
        xq = _rope_one(xq, pc, ps)
        xk = _rope_one(xk, pc, ps)
        ck = lax.dynamic_index_in_dim(ck_all, slot, axis=0,
                                      keepdims=False)
        cv = lax.dynamic_index_in_dim(cv_all, slot, axis=0,
                                      keepdims=False)
        ck = ck.at[write_pos].set(xk.astype(ck.dtype))
        cv = cv.at[write_pos].set(xv.astype(cv.dtype))
        q = xq.reshape(chunk, c.n_kv_heads, group, c.head_dim)
        scores = jnp.einsum("ckgd,tkd->ckgt", q, ck,
                            preferred_element_type=jnp.float32)
        scores = scores / jnp.sqrt(jnp.float32(c.head_dim))
        valid = jnp.arange(max_seq)[None, :] <= pos[:, None]
        scores = jnp.where(valid[:, None, None, :], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("ckgt,tkd->ckgd", probs.astype(ck.dtype), cv,
                         preferred_element_type=jnp.float32)
        out = out.reshape(chunk, c.n_heads * c.head_dim).astype(x.dtype)
        x = x + (out @ layer["wo"]).astype(x.dtype)
        h = rmsnorm(x, layer["ln_mlp"], c.norm_eps)
        out, load = llama._mlp({**layer, **experts}, h, c, i, live=real)
        x = x + out.astype(x.dtype)
        ck_all = lax.dynamic_update_slice(ck_all, ck[None],
                                          (slot, 0, 0, 0))
        cv_all = lax.dynamic_update_slice(cv_all, cv[None],
                                          (slot, 0, 0, 0))
        return x, (ck_all, cv_all, load)

    x = params["embed"][tokens].astype(c.dtype)
    x, (new_k, new_v, loads) = lax.scan(
        block, x, (layers, cache["k"], cache["v"], index))
    x = rmsnorm(x, params["norm_f"], c.norm_eps)
    x_last = jnp.take(x, jnp.maximum(chunk_len - 1, 0), axis=0)
    head = (params["embed"].T if c.tie_embeddings else params["lm_head"])
    logits = (x_last @ head.astype(c.dtype)).astype(jnp.float32)
    cache = {**_counted(cache, loads, False, c, real), "k": new_k, "v": new_v,
             "length": cache["length"].at[slot].set(start + chunk_len)}
    return logits, cache


def scanned_decode_step(params, last_tokens, cache, config, active=None):
    c = config
    slots = last_tokens.shape[0]
    max_seq = cache["k"].shape[2]
    pos = cache["length"]
    if active is None:
        active = jnp.ones((slots,), bool)
    write_pos = jnp.where(active, pos, jnp.int32(max_seq))
    cos, sin = llama.rope_frequencies(c.head_dim, c.max_seq, c.rope_theta,
                                      jnp.float32)
    group = c.n_heads // c.n_kv_heads

    layers, experts, index = llama._hoist_experts(params["layers"], c)

    def block(x, scanned):
        layer, ck, cv, i = scanned
        h = rmsnorm(x, layer["ln_attn"], c.norm_eps)
        xq, xk = llama._qk_proj(layer, h, c, True)
        xq = xq.reshape(slots, c.n_heads, c.head_dim)
        xk = xk.reshape(slots, c.n_kv_heads, c.head_dim)
        xv = llama._proj(h, layer["wv"], True).reshape(
            slots, c.n_kv_heads, c.head_dim)
        pc = cos[pos][:, None, :]
        ps = sin[pos][:, None, :]
        xq = _rope_one(xq, pc, ps)
        xk = _rope_one(xk, pc, ps)
        ck = ck.at[jnp.arange(slots), write_pos].set(xk.astype(ck.dtype))
        cv = cv.at[jnp.arange(slots), write_pos].set(xv.astype(cv.dtype))
        q = xq.reshape(slots, c.n_kv_heads, group, c.head_dim)
        scores = jnp.einsum("skgd,stkd->skgt", q, ck,
                            preferred_element_type=jnp.float32)
        scores = scores / jnp.sqrt(jnp.float32(c.head_dim))
        valid = jnp.arange(max_seq)[None, :] <= pos[:, None]
        scores = jnp.where(valid[:, None, None, :], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("skgt,stkd->skgd", probs.astype(ck.dtype), cv,
                         preferred_element_type=jnp.float32)
        out = out.reshape(slots, c.n_heads * c.head_dim).astype(x.dtype)
        x = x + (out @ layer["wo"]).astype(x.dtype)
        h = rmsnorm(x, layer["ln_mlp"], c.norm_eps)
        out, load = llama._mlp({**layer, **experts}, h, c, i, live=active)
        x = x + out.astype(x.dtype)
        return x, (ck, cv, load)

    x = params["embed"][last_tokens].astype(c.dtype)
    x, (new_k, new_v, loads) = lax.scan(
        block, x, (layers, cache["k"], cache["v"], index))
    x = rmsnorm(x, params["norm_f"], c.norm_eps)
    head = (params["embed"].T if c.tie_embeddings else params["lm_head"])
    logits = (x @ head.astype(c.dtype)).astype(jnp.float32)
    new_len = jnp.minimum(cache["length"] + 1, jnp.int32(max_seq))
    new_len = jnp.where(active, new_len, cache["length"])
    cache = {**_counted(cache, loads, True, c, active), "k": new_k, "v": new_v,
             "length": new_len}
    return logits, cache


def make_model(name):
    """(config, weights, a cache whose every row holds something: what
    a call leaves alone is then visibly left alone)."""
    c = CONFIGS[name]
    params = llama.init_params(c, jax.random.PRNGKey(0))
    # init's 0.02 leaves every layer near the identity: four times
    # that, and what a layer reads from the cache shows in the logits
    params = {**params, "layers": {
        leaf_name: leaf if leaf_name.startswith("ln_")
        or leaf_name.endswith("norm") else leaf * 4.0
        for leaf_name, leaf in params["layers"].items()}}
    cache = llama.init_kv_cache(c, SLOTS, MAX_SEQ)
    kk, kv = jax.random.split(jax.random.PRNGKey(1))
    cache["k"] = jax.random.normal(kk, cache["k"].shape, c.dtype)
    cache["v"] = jax.random.normal(kv, cache["v"].shape, c.dtype)
    cache["length"] = jnp.asarray([5, MAX_SEQ - 1, 40, MAX_SEQ], jnp.int32)
    return c, params, cache


@pytest.fixture(scope="module", params=list(CONFIGS))
def model(request):
    return make_model(request.param)


def bits(x):
    x = np.asarray(x)
    return x.view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


# What the first layer writes does not pass through attention and is the
# scanned form's bit for bit.  A deeper layer's rows do: since PR 33 the
# step programs walk the slab block by block with an online softmax
# (probabilities rounded to the cache's dtype BEFORE the division, sums
# in another order), so those rows agree to the dtype's rounding — two
# units in the last place of a bf16 value in [2, 4), the largest here;
# float32 sums of 96 terms reordered.
ROW_ATOL = {2: 2.0 ** -5, 4: 1e-5}


def assert_same_step(got, want):
    """(logits, cache) of the two forms: the logits within bf16
    rounding by the benchmark's measure (relative L2 a row), the first
    layer's slabs, the lengths and (in float32) the counters bit for
    bit, the deeper layers' slabs to the dtype's rounding."""
    got_logits, want_logits = (np.atleast_2d(np.asarray(x[0], np.float64))
                               for x in (got, want))
    rel_l2 = np.sqrt(((got_logits - want_logits) ** 2).sum(-1)
                     / (want_logits ** 2).sum(-1))
    assert rel_l2.max() < LOGIT_TOL[want[1]["k"].dtype.itemsize]
    assert sorted(got[1]) == sorted(want[1])
    for name, leaf in want[1].items():
        new = got[1][name]
        if name == "routing" and want[1]["k"].dtype.itemsize == 2:
            # in bf16 a near-tie of two router scores may fall the other
            # way in a deeper layer: the counts of what was computed are
            # equal, the experts hit and the busiest one's rows nearly
            np.testing.assert_allclose(np.asarray(new, np.int64),
                                       np.asarray(leaf, np.int64), atol=2)
            counted = [llama.ROUTING_COUNTERS.index(n) for n in (
                "moe_assignments", "moe_expert_slots", "moe_rows_routed")]
            np.testing.assert_array_equal(new[np.array(counted)],
                                          leaf[np.array(counted)])
            continue
        if name not in ("k", "v"):
            np.testing.assert_array_equal(bits(new), bits(leaf),
                                          err_msg=name)
            continue
        np.testing.assert_array_equal(bits(new[0]), bits(leaf[0]),
                                      err_msg=name)
        np.testing.assert_allclose(
            np.asarray(new, np.float32), np.asarray(leaf, np.float32),
            rtol=0, atol=ROW_ATOL[leaf.dtype.itemsize], err_msg=name)


@pytest.mark.parametrize("active", [(True, True, True, True),
                                    (True, False, True, True),
                                    (False, True, False, False)], ids=str)
def test_decode_step_equals_the_scanned_form(model, active):
    c, params, cache = model
    last = jnp.asarray([3, 250, 77, 9], jnp.int32)
    act, on = jnp.asarray(active), np.asarray(active)
    pos = cache["length"]

    _, new = got = jax.jit(
        lambda p, t, k, a: llama.decode_step(p, t, k, c, active=a))(
            params, last, cache, act)
    assert_same_step(got, jax.jit(
        lambda p, t, k, a: scanned_decode_step(p, t, k, c, active=a))(
            params, last, cache, act))
    # the rows written are the slots' own positions, and only those: a
    # slot that sat out, or is full, keeps every bit
    before_k, before_v = bits(cache["k"]), bits(cache["v"])
    for s in range(SLOTS):
        p = int(pos[s])
        rest = np.ones((MAX_SEQ,), bool)
        if on[s] and p < MAX_SEQ:
            rest[p] = False
            assert (bits(new["k"])[:, s, p] != before_k[:, s, p]).any()
        np.testing.assert_array_equal(
            bits(new["k"])[:, s, rest], before_k[:, s, rest])
        np.testing.assert_array_equal(
            bits(new["v"])[:, s, rest], before_v[:, s, rest])
    want_len = np.where(on, np.minimum(np.asarray(pos) + 1, MAX_SEQ), pos)
    np.testing.assert_array_equal(np.asarray(new["length"]), want_len)
    if c.num_experts:
        # the active rows' pairs reach an expert, the others' none
        pairs = c.experts_per_token * c.n_layers
        assert int(new["routing"][0]) == int(on.sum()) * pairs
        dead = llama.ROUTING_COUNTERS.index("moe_dead_pairs")
        assert int(new["routing"][dead]) == int((~on).sum()) * pairs


@pytest.mark.parametrize("start,chunk_len", [
    (0, CHUNK),                    # a whole chunk
    (32, 5),                       # pad rows past chunk_len stay
    (MAX_SEQ - 10, 10),            # start + CHUNK > max_seq: no clamping
    (MAX_SEQ - 3, 3),
], ids=str)
def test_prefill_chunk_equals_the_scanned_form(model, start, chunk_len):
    c, params, cache = model
    slot = 2
    tokens = np.zeros((CHUNK,), np.int32)
    tokens[:chunk_len] = np.random.default_rng(start).integers(
        1, c.vocab_size, chunk_len)
    args = (params, jnp.asarray(tokens), cache, slot, start, chunk_len)

    _, new = got = jax.jit(
        lambda p, t, k, s, st, n: llama.prefill_chunk_into_cache(
            p, t, k, s, st, n, c))(*args)
    assert_same_step(got, jax.jit(
        lambda p, t, k, s, st, n: scanned_prefill_chunk(
            p, t, k, s, st, n, c))(*args))
    written = np.zeros((SLOTS, MAX_SEQ), bool)
    written[slot, start:start + chunk_len] = True
    for name in ("k", "v"):
        rows, before = bits(new[name]), bits(cache[name])
        np.testing.assert_array_equal(rows[:, ~written], before[:, ~written])
        assert (rows[:, written] != before[:, written]).any()
    want_len = np.asarray(cache["length"]).copy()
    want_len[slot] = start + chunk_len
    np.testing.assert_array_equal(np.asarray(new["length"]), want_len)


def test_the_donated_cache_is_reused_leaf_for_leaf(model, recwarn):
    """The engine's own programs (``step_programs``: the cache
    donated): no leaf of the cache is left unaliased, which jax reports
    as a warning."""
    c, params, cache = model
    cache = jax.tree.map(jnp.copy, cache)
    run = step_programs(c, slots=SLOTS, max_seq=MAX_SEQ, chunk=CHUNK)
    decode, chunk = run.decode, run.prefill_chunk
    _, cache = decode(params, cache, jnp.zeros((SLOTS,), jnp.int32),
                      jnp.ones((SLOTS,), bool))
    _, cache = chunk(params, cache, jnp.zeros((CHUNK,), jnp.int32), 1, 0, 4)
    jax.block_until_ready(cache)
    assert not [w for w in recwarn if "donated" in str(w.message)]
