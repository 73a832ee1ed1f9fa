"""Xing4.0's block (four residual streams a token under
manifold-constrained hyper-connections around latent attention and a
bias-corrected sigmoid router) on the program's normal paths, against
the plain reference ``chipbench/reference/xing4_decoder.py`` on seeded
random weights at a tiny shape, on the CPU in float32.

The measure is the benchmark's own: the relative L2 distance of the
logits, per position.  Both sides compute in float32, so the distance is
rounding and the order of summation.  ``TOL`` = 1e-4 is an order under
the least it must catch; the MAPS' own test pins the count of Sinkhorn
passes, to 1e-5 absolute.
"""

import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ant_ray_tpu.llm import LLMEngine, SamplingParams
from ant_ray_tpu.models import llama
from chipbench.models import xing4
from chipbench.reference import axk1_decoder as axk1_ref
from chipbench.reference import xing4_decoder as ref

CFG = llama.CONFIGS["xing4-tiny"]
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
    hc_mult=CFG.hc_mult, hc_sinkhorn_iters=CFG.hc_sinkhorn_iters,
    hc_eps=CFG.hc_eps, clamp_min=CFG.hc_res_clamp[0],
    clamp_max=CFG.hc_res_clamp[1])
TOL = 1e-4
SLOTS, MAX_SEQ, CHUNK = 3, 96, 16
PUBLISHED = os.path.join(os.path.dirname(__file__), "..", "chipbench",
                         "configs", "xing4.0-29b-a4b.json")


def seeded_params(cfg, seed=0):
    """Seeded weights, less bland than the initialiser's: matrices large
    enough that the router decides and attention attends, norm weights
    that are not all ones; the maps' leaves as the initialiser draws
    them (of order 1 already), their three scalars unlike."""
    p = llama.init_params(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 96))

    def livelier(stack):
        out = {}
        for name, leaf in stack.items():
            if name.endswith("norm") or name.startswith("ln_") \
                    or name.endswith("alpha"):
                out[name] = leaf * jax.random.uniform(
                    next(keys), leaf.shape, minval=0.5, maxval=1.5)
            elif name.startswith("hc_") or name == "router_bias":
                out[name] = leaf
            else:
                out[name] = leaf * 6.0
        return out

    return {**p, "norm_f": p["norm_f"] * 0.7,
            **{name: livelier(p[name]) for name in ("dense_layers", "layers")
               if name in p}}


@pytest.fixture(scope="module")
def params():
    return seeded_params(CFG)


def reference_logits(params, tokens, **dims):
    embed, layer, n, norm_f, head = xing4.reference_layers(params)
    return ref.forward(embed, (layer, n), norm_f, head, jnp.asarray(tokens),
                       **{**DIMS, **dims})


def rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(((got - want) ** 2).sum(-1) / (want ** 2).sum(-1))


def tokens_of(seed, *shape):
    return np.random.default_rng(seed).integers(
        0, CFG.vocab_size, shape).astype(np.int32)


# jitted once: run eagerly the step programs compile their scan anew on
# every call
_CHUNK = jax.jit(lambda p, t, k, s, st, n: llama.prefill_chunk_into_cache(
    p, t, k, s, st, n, CFG))
_DECODE = jax.jit(lambda p, t, k, a: llama.decode_step(p, t, k, CFG, a))
_MIXED = jax.jit(lambda p, last, t, k, a, s, st, n: llama.mixed_step(
    p, last, t, k, CFG, a, s, st, n))


def chunks_into(params, cache, tokens, slot, start=0):
    for at in range(0, len(tokens), CHUNK):
        part = tokens[at:at + CHUNK]
        buf = np.zeros((CHUNK,), np.int32)
        buf[:len(part)] = part
        logits, cache = _CHUNK(params, jnp.asarray(buf), cache, slot,
                               start + at, len(part))
    return logits, cache


def through_the_cache(params, tokens, prompt, slot=1):
    """``prompt`` tokens in chunks, the rest decoded one by one (teacher
    forced) in ``slot`` -> logits from the last prompt token on."""
    logits, cache = chunks_into(
        params, llama.init_kv_cache(CFG, SLOTS, MAX_SEQ), tokens[:prompt],
        slot)
    got = [logits]
    active = np.zeros((SLOTS,), bool)
    active[slot] = True
    for token in tokens[prompt:]:
        last = np.zeros((SLOTS,), np.int32)
        last[slot] = token
        logits, cache = _DECODE(params, jnp.asarray(last), cache,
                                jnp.asarray(active))
        got.append(logits[slot])
    return jnp.stack(got)


# ----------------------------------------------------------- (a) the maps

@pytest.mark.parametrize("dim", [64, 3584])
def test_the_maps_equal_the_references_and_the_mix_is_doubly_stochastic(dim):
    """``llama.hc_maps`` against ``xing4_decoder.hc_maps`` at n = 4, to
    1e-5 absolute: what pins the 20 passes, the clamp, ``hc_eps`` and the
    order columns-then-rows (each changed in the reference alone moves a
    map by more: at logits of this spread the iteration has NOT converged
    behind 20 passes — 19 read 1e-4 and more off — and the last thing
    normalised, the rows, is what adds up exactly)."""
    c = dataclasses.replace(CFG, dim=dim)
    n, rows = c.hc_mult, 24
    keys = jax.random.split(jax.random.PRNGKey(dim), 4)
    x = jax.random.normal(keys[0], (rows, n, dim)) * 3.0
    phi = jax.random.normal(keys[1], (n * dim, 2 * n + n * n)) \
        * (n * dim) ** -0.5
    b = jax.random.normal(keys[2], (2 * n + n * n,))
    alpha = jnp.asarray([0.7, 1.3, 2.0])
    got = jax.jit(lambda: llama.hc_maps(phi, b, alpha, x, c))()

    def reference(passes=c.hc_sinkhorn_iters, clamp=c.hc_res_clamp,
                  eps=c.hc_eps):
        return ref.hc_maps(x, phi, b, alpha, passes, *clamp, eps, c.norm_eps)

    with jax.default_matmul_precision("highest"):
        want = reference()
        for ours, theirs in zip(got, want):
            np.testing.assert_allclose(ours, theirs, rtol=0, atol=1e-5)
        h_res = np.asarray(got[2])
        np.testing.assert_allclose(h_res.sum(-1), 1.0, atol=1e-5)
        assert np.abs(h_res.sum(-2) - 1.0).max() < 0.2
        assert h_res.min() > 0 and np.abs(h_res - np.eye(n)).max() > 0.5
        # the comparison sees each of them
        for wrong in (reference(passes=19), reference(clamp=(-0.5, 0.5)),
                      reference(eps=1e-2)):
            assert np.abs(np.asarray(wrong[2]) - h_res).max() > 1e-4
        # ... and maps made of streams and phi rounded to bfloat16 (on
        # the chip that control reads INSIDE the logits' tolerance,
        # PERF.md section 6, PR 56: this is what holds the maps' precision)
        low = ref.hc_maps(x.astype(jnp.bfloat16).astype(jnp.float32),
                          phi.astype(jnp.bfloat16).astype(jnp.float32), b,
                          alpha, c.hc_sinkhorn_iters, *c.hc_res_clamp,
                          c.hc_eps, c.norm_eps)
        assert np.abs(np.asarray(low[2]) - h_res).max() > 1e-4
        columns_last = ref.hc_maps(x, phi, b, alpha, 0, *c.hc_res_clamp,
                                   c.hc_eps, c.norm_eps)[2]
        for _ in range(c.hc_sinkhorn_iters):
            columns_last = columns_last / (columns_last.sum(-1, keepdims=True)
                                           + c.hc_eps)
            columns_last = columns_last / (columns_last.sum(-2, keepdims=True)
                                           + c.hc_eps)
        assert np.abs(np.asarray(columns_last) - h_res).max() > 1e-4
    # logits of a narrow spread (a checkpoint's: near a constant mix):
    # the iteration converges and rows AND columns add up to 1
    mild = llama.hc_maps(phi, b * 0.2, alpha * 0.2, x, c)[2]
    np.testing.assert_allclose(mild.sum(-1), 1.0, atol=1e-4)
    np.testing.assert_allclose(mild.sum(-2), 1.0, atol=1e-4)


# ------------------------------------------------ (b) against the reference

def test_forward_logits_equal_the_reference(params):
    tokens = tokens_of(0, 48)
    got = llama.forward(params, tokens[None], CFG, remat="none")[0]
    assert rel_l2(got, reference_logits(params, tokens)).max() < TOL


@pytest.mark.parametrize("prompt", [40, 16, 7])
def test_chunks_then_decode_through_the_cache_equal_the_reference(
        params, prompt):
    tokens = tokens_of(prompt, prompt + 8)
    got = through_the_cache(params, tokens, prompt)
    want = reference_logits(params, tokens)[prompt - 1:-1]
    assert rel_l2(got[:-1], want).max() < TOL


def test_a_mixed_step_equals_the_reference(params):
    """Two slots decode while a third prompt's chunk rides: every part's
    logits are the reference's of its own sequence."""
    a, b, new = tokens_of(1, 21), tokens_of(2, 34), tokens_of(3, 11)
    cache = llama.init_kv_cache(CFG, SLOTS, MAX_SEQ)
    _, cache = chunks_into(params, cache, a[:-1], 0)
    _, cache = chunks_into(params, cache, b[:-1], 2)
    last = jnp.asarray([a[-1], 0, b[-1]], jnp.int32)
    buf = np.zeros((CHUNK,), np.int32)
    buf[:len(new)] = new
    rows, chunk, cache = _MIXED(
        params, last, jnp.asarray(buf), cache,
        jnp.asarray([True, False, True]), 1, 0, len(new))
    assert np.asarray(cache["length"]).tolist() == [21, 11, 34]
    for got, seq in ((rows[0], a), (rows[2], b), (chunk, new)):
        assert rel_l2(got, reference_logits(params, seq)[-1]) < TOL


@pytest.mark.parametrize("wrong,least", [
    (dict(hc_sinkhorn_iters=1), 1e-3),        # one pass of the projection
    (dict(clamp_min=-0.5, clamp_max=0.5), 1e-3),
    (dict(hc_mult=1), None),                  # ONE stream: another model
    (dict(routed_scaling_factor=1.0), 5e-2),
])
def test_the_tolerance_sees_what_it_must(params, wrong, least):
    tokens = tokens_of(3, 48)
    got = llama.forward(params, tokens[None], CFG, remat="none")[0]
    if least is None:
        with pytest.raises((TypeError, ValueError)):
            reference_logits(params, tokens, **wrong)
        return
    off = rel_l2(got, reference_logits(params, tokens, **wrong))
    assert off.max() > least > TOL


@pytest.mark.parametrize("change,least", [
    (lambda name, leaf: leaf * 0 if name.endswith("alpha") else leaf, 1e-2),
    (lambda name, leaf: leaf * 0 if name == "router_bias" else leaf, 1e-2),
], ids=["maps-without-their-input", "no-correction-bias"])
def test_the_logits_hang_on_the_maps_input_and_on_the_bias(
        params, change, least):
    """The controls the chip runs read at the published widths, here at
    the tiny ones: with the maps' input-dependent part dropped, or the
    router's bias, the program is another function."""
    tokens = tokens_of(5, 48)
    other = {**params, **{stack: {name: change(name, leaf) for name, leaf
                                  in params[stack].items()}
                          for stack in ("dense_layers", "layers")}}
    got = llama.forward(other, tokens[None], CFG, remat="none")[0]
    assert rel_l2(got, reference_logits(params, tokens)).max() > least


# ------------------------------------------------------------ (c) the router

def _routed_layer(params):
    return {name: leaf[1] for name, leaf in params["layers"].items()}


def test_the_bias_picks_and_does_not_weigh(params):
    layer = _routed_layer(params)
    h = jax.random.normal(jax.random.PRNGKey(9), (40, CFG.dim))
    scores = np.asarray(jax.nn.sigmoid(h @ layer["router"]))
    bias = np.asarray(layer["router_bias"])
    picked = np.argsort(-(scores + bias), axis=-1)[:, :2]
    plain = np.argsort(-scores, axis=-1)[:, :2]
    assert (np.sort(picked) != np.sort(plain)).any()     # it picks ...
    out, load = llama._routed_mlp(layer, h, CFG)
    assert load.tolist() == np.bincount(picked.reshape(-1),
                                        minlength=8).tolist()
    # ... and the gates are the picked experts' SCORES over their sum
    gates = ref.gate_map(h, layer["router"], layer["router_bias"], 2, 2.0)
    kept = np.take_along_axis(scores, picked, axis=-1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(gates), picked, axis=-1),
        kept / kept.sum(-1, keepdims=True) * 2.0, rtol=1e-5)
    np.testing.assert_allclose(
        out, axk1_ref.held_experts(layer, h, gates, 0), rtol=2e-4, atol=2e-6)


def test_without_a_bias_the_router_is_the_one_that_was_there(params):
    layer = _routed_layer(params)
    h = jax.random.normal(jax.random.PRNGKey(10), (33, CFG.dim))
    zero = {**layer, "router_bias": layer["router_bias"] * 0}
    got = jax.jit(lambda: llama._routed_mlp(zero, h, CFG))()
    was = jax.jit(lambda: llama._routed_mlp(
        layer, h, dataclasses.replace(CFG, router_bias=False)))()
    np.testing.assert_array_equal(got[0], was[0])
    np.testing.assert_array_equal(got[1], was[1])
    np.testing.assert_allclose(
        ref.gate_map(h, layer["router"], zero["router_bias"], 2, 2.0),
        axk1_ref.gate_map(h, layer["router"], 2, 2.0), rtol=1e-6)


# ------------------------- (d) one stream: the block that was there, untouched

def _block_jaxpr(name):
    """``apply_block``'s jaxpr on the preset's first layer of each stack,
    as a step program calls it (rows, an index)."""
    cfg = llama.CONFIGS[name]
    params = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    cos, sin = llama._rope_tables(cfg, 64)
    texts = []
    for stacks, c in llama._stacks(params, cfg):
        layer = jax.tree.map(lambda leaf: jax.ShapeDtypeStruct(
            leaf.shape[1:], leaf.dtype), stacks["layers"])

        def attend(xq, xk, xv, w_kvb=None):
            return xq[..., :c.v_head_dim or c.head_dim], None

        def block(layer, x, pos):
            return llama.apply_block(layer, x, c, cos, sin, pos, attend,
                                     llama._unconstrained)[0]

        texts.append(str(jax.make_jaxpr(block)(
            layer, jax.ShapeDtypeStruct((12, c.dim), c.dtype),
            jax.ShapeDtypeStruct((12,), jnp.int32))))
    return texts


@pytest.mark.parametrize("name", ["tiny", "axk1-tiny", "olmoe-tiny"])
def test_with_one_stream_the_block_traces_what_it_traced(name, monkeypatch):
    """``hc_mult`` 1: not an operation more than with the parent's
    ``_residual`` (a plain sum) and no ``_hc_read`` at all."""
    now = _block_jaxpr(name)
    assert not any("hc" in scope for text in now
                   for scope in re.findall(r"name=(\w+)", text))
    monkeypatch.setattr(llama, "_hc_read", lambda layer, x, c, sub: (x, None))
    monkeypatch.setattr(llama, "_residual",
                        lambda x, out, c, streams=None: x + out)
    assert _block_jaxpr(name) == now


# ------------------------------------------------------------ (e) training

def test_the_loss_differentiates_through_the_maps(params):
    tokens = tokens_of(7, 2, 25)
    loss, grads = jax.value_and_grad(lambda p: llama.loss_fn(
        p, {"tokens": jnp.asarray(tokens)}, CFG))(params)
    assert np.isfinite(float(loss))
    for stack in ("dense_layers", "layers"):
        for name, grad in grads[stack].items():
            assert np.isfinite(np.asarray(grad)).all(), name
            if name.startswith("hc_"):
                assert float(jnp.abs(grad).max()) > 0, name
    # the bias picks and does not weigh: nothing flows back to it
    assert float(jnp.abs(grads["layers"]["router_bias"]).max()) == 0.0


def test_what_is_not_written_is_refused_by_name(params):
    with pytest.raises(ValueError, match="hc_mult"):
        llama.loss_fn_pp(params, {"tokens": jnp.zeros((4, 9), jnp.int32)},
                         dataclasses.replace(llama.CONFIGS["tiny"],
                                             hc_mult=2), mesh=_mesh("pp"))
    with pytest.raises(ValueError, match="not laid out over a mesh"):
        llama.forward(params, jnp.zeros((2, 8), jnp.int32), CFG,
                      mesh=_mesh("tp"))
    for field in (dict(parallel_block=True), dict(residual_multiplier=0.5)):
        with pytest.raises(ValueError, match="hc_mult"):
            dataclasses.replace(CFG, **field)
    with pytest.raises(ValueError, match="router_bias"):
        dataclasses.replace(llama.CONFIGS["tiny"], router_bias=True)
    spec = json.load(open(PUBLISHED))
    with pytest.raises(ValueError, match="group-limited"):
        xing4.build({**spec, "n_group": 8, "topk_group": 4})


def _mesh(axis):
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:2]), (axis,))


# ----------------------------------------------------- through the engine

def test_engine_greedy_tokens_are_the_references_and_rows_are_counted(
        params):
    eng = LLMEngine(CFG, params, slots=3, max_seq=MAX_SEQ,
                    prefill_chunk_tokens=8)
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
    # the rows dispatched: every prompt token through a chunk, every
    # decode step's live rows
    assert stats["hc_chunk_rows"] == stats["chunk_tokens"] == 5 + 19
    assert stats["hc_decode_rows"] == stats["decode_slots"] > 0
    # counted on the host: the schedule and its reads are those of the
    # same model with ONE stream, which has no such counter
    one = LLMEngine(dataclasses.replace(CFG, hc_mult=1), slots=3,
                    max_seq=MAX_SEQ, prefill_chunk_tokens=8)
    one.generate(prompts, SamplingParams(max_tokens=6))
    assert "hc_chunk_rows" not in one.stats
    for name in ("steps", "d2h_syncs", "decode_steps", "chunks"):
        assert one.stats[name] == stats[name], name


def test_the_engine_refuses_a_mesh(params):
    gqa = dataclasses.replace(llama.CONFIGS["tiny"], hc_mult=2)
    with pytest.raises(ValueError, match="not laid out over a mesh"):
        LLMEngine(gqa, slots=2, max_seq=64, tensor_parallel_size=2)


def test_the_hc_scope_is_in_every_program():
    """The maps and the mixes sit under the named scope ``hc`` in the
    chunk, the decode, the mixed and the training program: a device
    trace attributes their operations by it."""
    params = jax.eval_shape(
        lambda: llama.init_params(CFG, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: llama.init_kv_cache(CFG, SLOTS, MAX_SEQ))
    tokens = jax.ShapeDtypeStruct((CHUNK,), jnp.int32)
    last = jax.ShapeDtypeStruct((SLOTS,), jnp.int32)
    active = jax.ShapeDtypeStruct((SLOTS,), bool)
    lowered = {
        "chunk": _CHUNK.lower(params, tokens, cache, 1, 0, 9),
        "decode": _DECODE.lower(params, last, cache, active),
        "mixed": _MIXED.lower(params, last, tokens, cache, active, 1, 0, 9),
        "train": jax.jit(lambda p, t: llama.loss_fn(
            p, {"tokens": t}, CFG)).lower(
                params, jax.ShapeDtypeStruct((2, 17), jnp.int32)),
    }
    for name, program in lowered.items():
        text = program.as_text(debug_info=True)
        for scope in ("hc/", "mla/", "moe/", "moe_shared/"):
            assert scope in text, (name, scope)


# ------------------------------------------- the counts, without allocating

def test_num_params_and_flops_at_the_published_widths():
    """The issue's count: a routed layer 745.0 M, a leading dense layer
    128.2 M, the embedding and the head 939.5 M; 2 + 5 layers 4.92 G
    parameters, 9.84 GB of bfloat16 — by ``jax.eval_shape``."""
    spec = json.load(open(PUBLISHED))
    cfg = xing4.build(spec)
    shapes = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    held = sum(leaf.size for leaf in jax.tree.leaves(shapes))
    attention = (3584 * 768 + 768 * 32 * 192 + 3584 * 576
                 + 512 * 32 * 256 + 4096 * 3584 + 2 * 3584 + 768 + 512)
    assert attention == 28_409_856 + 2 * 3584 + 768 + 512
    maps = 2 * (4 * 3584 * 24 + 24 + 3)
    expert = 3 * 3584 * 1024
    routed = attention + 65 * expert + 3584 * 64 + 64 + maps
    dense = attention + 3 * 3584 * 9216 + maps
    n_routed = spec["num_hidden_layers"] - 2
    assert held == cfg.num_params() == (
        2 * 131072 * 3584 + 3584 + 2 * dense + n_routed * routed)
    assert round(routed / 1e6, 1) == 745.0
    assert round(dense / 1e6, 1) == 128.2
    assert shapes["layers"]["hc_mlp_phi"].shape == (n_routed, 14336, 24)
    # training operations a token: 6 x the parameters it multiplies with
    # (4 of the 64 experts), attention, and the streams' read, write and
    # mix around the 2 sub-layers of every layer: (16 + 8) x 3584 each
    seq, layers = 4096, spec["num_hidden_layers"]
    want = 6 * (held - n_routed * 60 * expert) \
        + 6 * layers * 32 * seq * (192 + 128) + 6 * layers * 2 * 24 * 3584
    assert llama.flops_per_token(cfg, seq) == pytest.approx(want, rel=1e-12)
    # the cache is A.X-K1's: 576 values a position a layer, no streams
    cache = jax.eval_shape(lambda: llama.init_kv_cache(cfg, 16, 16384))
    assert cache["c_kv"].shape == (layers, 16, 16384, 512)
    assert cache["k_rope"].shape == (layers, 16, 16384, 64)
