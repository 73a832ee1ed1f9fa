"""OLMoE's block (routed experts with un-renormalised gates, RMSNorm over
the whole q and k projections) on the program's normal paths, against
the plain reference ``chipbench/reference/olmoe_decoder.py`` on seeded
random weights at a tiny shape, on the CPU in float32.

The measure is the benchmark's own: the relative L2 distance of the
logits, per position.  Both sides compute in float32 (the reference at
the highest matmul precision, float32's own on the CPU), so the distance
is rounding and the order of summation: 7e-7 at worst here.  ``TOL`` =
2e-5 leaves it thirty times that and is four orders under what it must
catch — gates renormalised over the top-k (0.16 to 0.52 here, at every
position) and q/k normalised head by head (up to 0.35; a sequence's
first token alone cannot tell, it attends to itself whatever its q and
k): see the last test.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ant_ray_tpu.llm import LLMEngine, SamplingParams
from ant_ray_tpu.models import llama
from ant_ray_tpu.ops.rmsnorm import rmsnorm
from chipbench.models import dense_llama, olmoe
from chipbench.reference import dense_decoder as dense_ref
from chipbench.reference import olmoe_decoder as ref

CFG = llama.CONFIGS["olmoe-tiny"]
SPEC = {"num_attention_heads": CFG.n_heads,
        "num_key_value_heads": CFG.n_kv_heads,
        "rope_theta": CFG.rope_theta, "rms_norm_eps": CFG.norm_eps,
        "num_experts_per_tok": CFG.experts_per_token,
        "norm_topk_prob": CFG.norm_topk_prob}
TOL = 2e-5
# Every head its own KV head, dense: the layout whose step programs
# compile to another shape than grouped queries' (PERF.md §6, PR 28).
DENSE_UNGROUPED = dataclasses.replace(llama.CONFIGS["tiny"], n_kv_heads=4)


def seeded_params(cfg):
    """Seeded weights, made less bland than the initialiser's: matrices
    large enough that the router decides and attention attends, norm
    weights that are not all ones (a swapped or missing norm shows)."""
    p = llama.init_params(cfg, jax.random.PRNGKey(0))
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 16))
    layers = dict(p["layers"])
    for name, leaf in layers.items():
        if name.endswith("norm") or name.startswith("ln_"):
            layers[name] = leaf * jax.random.uniform(
                next(keys), leaf.shape, minval=0.5, maxval=1.5)
        else:
            layers[name] = leaf * 6.0
    return {**p, "layers": layers, "norm_f": p["norm_f"] * 0.7}


@pytest.fixture(scope="module")
def params():
    return seeded_params(CFG)


def as_reference(p):
    embed, layer, n, norm_f, head = olmoe.reference_layers(p)
    return embed, [layer(i) for i in range(n)], norm_f, head


def rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(((got - want) ** 2).sum(-1) / (want ** 2).sum(-1))


def tokens_of(seed, *shape):
    return np.random.default_rng(seed).integers(
        0, CFG.vocab_size, shape).astype(np.int32)


def test_forward_logits_equal(params):
    tokens = tokens_of(0, 48)
    want = ref.forward(*as_reference(params), jnp.asarray(tokens),
                       **ref.dims_of(SPEC))
    got = llama.forward(params, jnp.asarray(tokens)[None], CFG,
                        attn_impl="reference")[0]
    assert rel_l2(got, want).max() < TOL


def test_loss_and_gradients_equal(params):
    """Through the sort, the grouped product and the gates: the loss to
    1e-5, every leaf's gradient to 2e-3 of its largest entry (float32
    sums of different order over 64 tokens; a wrong gate or a token
    sent to another expert moves whole rows by their own size)."""
    tokens = jnp.asarray(tokens_of(1, 2, 33))

    def ref_loss(p):
        return ref.loss(*as_reference(p), tokens, **ref.dims_of(SPEC))

    def own_loss(p):
        return llama.loss_fn(p, {"tokens": tokens}, CFG,
                             attn_impl="reference", remat="none")

    want, want_g = jax.jit(jax.value_and_grad(ref_loss))(params)
    got, got_g = jax.jit(jax.value_and_grad(own_loss))(params)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got_g),
                            jax.tree.leaves(want_g)):
        scale = float(jnp.abs(b).max())
        assert scale > 0, path
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3 * scale,
                                   err_msg=str(path))


def prefill_in_chunks(p, cfg, tokens, prompt, chunk, slot, cache):
    for start in range(0, prompt, chunk):
        part = tokens[start:min(start + chunk, prompt)]
        buf = np.zeros((chunk,), np.int32)
        buf[:len(part)] = part
        logits, cache = llama.prefill_chunk_into_cache(
            p, jnp.asarray(buf), cache, slot, start, len(part), cfg)
    return logits, cache


def through_the_cache(p, cfg, tokens, prompt, chunk=16, slots=4, slot=2):
    """Logits from the last prompt position on: the prompt in chunks,
    then one decode step per further token (teacher-forced)."""
    cache = llama.init_kv_cache(cfg, slots, 128)
    logits, cache = prefill_in_chunks(p, cfg, tokens, prompt, chunk, slot,
                                      cache)
    got = [logits]
    active = np.zeros((slots,), bool)
    active[slot] = True
    for j in range(prompt, len(tokens)):
        last = np.zeros((slots,), np.int32)
        last[slot] = tokens[j]
        logits, cache = llama.decode_step(
            p, jnp.asarray(last), cache, cfg, active=jnp.asarray(active))
        got.append(logits[slot])
    return jnp.stack(got), cache


def reference_logits(p, cfg, tokens):
    """The plain reference's full forward: OLMoE's, or for a dense
    model the dense decoder's."""
    if cfg.num_experts:
        return ref.forward(*as_reference(p), tokens, **ref.dims_of(SPEC))
    embed, layer, n, norm_f, head = dense_llama.reference_layers(p)
    return dense_ref.forward(
        embed, [layer(i) for i in range(n)], norm_f, head, tokens,
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps)


@pytest.mark.parametrize("block", [llama.ATTEND_BLOCK, 48, 16], ids=[
    "slab-under-a-block", "blocks-with-a-tail", "whole-blocks"])
@pytest.mark.parametrize("cfg", [CFG, DENSE_UNGROUPED],
                         ids=["olmoe-tiny", "dense-ungrouped"])
def test_prefill_in_chunks_then_decode_equals_the_full_forward(
        params, cfg, block, monkeypatch):
    """``block``: the positions the step programs attend over at a time
    — the 128-position slab as one block, as 48 + 48 + a tail of 32
    (chunks end on and across the edges), and as eight of 16."""
    monkeypatch.setattr(llama, "ATTEND_BLOCK", block)
    p = params if cfg is CFG else seeded_params(cfg)
    tokens, prompt = tokens_of(2, 60), 50
    got, cache = through_the_cache(p, cfg, tokens, prompt)
    want = reference_logits(p, cfg, jnp.asarray(tokens))[prompt - 1:]
    assert rel_l2(got[:-1], want[:-1]).max() < TOL
    if not cfg.num_experts:
        assert "routing" not in cache
        return
    # The step programs counted what reached an expert: the prompt's 50
    # tokens (4 chunks of 16 rows) and 10 steps' ONE active slot of 4, k
    # experts a row, in each layer — and, apart, the pairs of the rows
    # nobody reads: the last chunk's padding and the idle slots.
    executions, rows = 4 + 10, 4 * 16 + 10 * 4
    counted = dict(zip(llama.ROUTING_COUNTERS,
                       np.asarray(cache["routing"]).tolist()))
    assert counted["moe_assignments"] == counted["moe_rows_routed"] == \
        (prompt + 10) * CFG.experts_per_token * CFG.n_layers
    assert counted["moe_dead_pairs"] == \
        (rows - prompt - 10) * CFG.experts_per_token * CFG.n_layers
    assert counted["moe_expert_slots"] == \
        executions * CFG.num_experts * CFG.n_layers
    assert 0 < counted["moe_experts_hit"] <= counted["moe_expert_slots"]
    assert counted["moe_load_max"] >= counted["moe_assignments"] / \
        CFG.num_experts


def greedy_margins(p, prompt, generated):
    """The reference's full forward over prompt + generated: whether
    each generated token is the reference's most probable next token,
    and the smallest margin between the two largest logits there."""
    seq = jnp.asarray(list(prompt) + list(generated), jnp.int32)
    logits = np.asarray(ref.forward(*as_reference(p), seq,
                                    **ref.dims_of(SPEC)))
    at = logits[len(prompt) - 1:len(seq) - 1]
    top = np.sort(at, axis=-1)[:, -2:]
    return at.argmax(-1).tolist(), float((top[:, 1] - top[:, 0]).min())


def test_engine_batch_of_two_lengths_equals_the_reference(params):
    """Through ``LLMEngine``: two prompts of different length decode
    side by side (chunks of 8, so one takes three chunks and the other
    one), greedy, and each gets the tokens the reference's full forward
    picks — the margins say the comparison is not decided by rounding.
    The routing counters came to the host with the tokens: no read
    beyond one a step and one a prompt's end."""
    prompts = [tokens_of(5, 19).tolist(), tokens_of(6, 5).tolist()]
    engine = LLMEngine(CFG, params=params, slots=4, max_seq=64,
                       prefill_chunk_tokens=8)
    outs = engine.generate(prompts, SamplingParams(max_tokens=6,
                                                   temperature=0.0))
    for prompt, out in zip(prompts, outs):
        assert len(out.token_ids) == 6
        want, margin = greedy_margins(params, prompt, out.token_ids)
        assert margin > 1e-3
        assert out.token_ids == want
    stats = engine.stats
    assert stats["d2h_syncs"] == stats["decode_steps"] + len(prompts)
    # the programs run: a chunk that rode a decode step is one with it
    # (PR 39), and the first lone chunk ran the mixed program once
    # before it, empty — 4 + 8 rows, none of them live
    assert stats["chunks_fused"] == 3       # the longer prompt's
    assert stats["moe_expert_slots"] == CFG.num_experts * CFG.n_layers * (
        stats["decode_steps"] + stats["chunks"] - stats["chunks_fused"] + 1)
    # the prompts' tokens and the decoding rows reach their experts; the
    # other rows the programs computed — idle slots, padding — reach none
    pairs = CFG.experts_per_token * CFG.n_layers
    assert stats["moe_assignments"] == stats["moe_rows_routed"] == pairs * (
        sum(map(len, prompts)) + stats["decode_slots"])
    assert stats["moe_assignments"] + stats["moe_dead_pairs"] == pairs * (
        4 * stats["decode_steps"] + 8 * stats["chunks"] + (4 + 8))
    assert 0 < stats["moe_experts_hit"] <= stats["moe_expert_slots"]
    assert stats["moe_load_max"] > 0


@pytest.mark.parametrize("stop", [False, True])
def test_routing_counters_arrive_with_their_step_and_are_added_once(
        params, stop):
    """The decode step runs one ahead of its read: a step's counters
    still come with that step's tokens — each reading covers exactly
    the programs dispatched up to its sampler — and ``stats`` holds
    what the device counted, once, when the last step has landed; a row
    dropped after a stop token was computed, so it counts."""
    engine = LLMEngine(CFG, params=params, slots=4, max_seq=64,
                       prefill_chunk_tokens=8)
    prompts = [tokens_of(5, 19).tolist(), tokens_of(6, 5).tolist()]
    first = engine.generate(prompts[:1], SamplingParams(max_tokens=6))[0]
    engine = LLMEngine(CFG, params=params, slots=4, max_seq=64,
                       prefill_chunk_tokens=8)
    readings, note = [], engine._note_routing
    per_program = CFG.num_experts * CFG.n_layers

    def spy(counters):
        before = engine.stats["moe_expert_slots"]
        note(counters)
        readings.append((engine.stats["moe_expert_slots"] - before)
                        // per_program)

    engine._note_routing = spy
    cut = first.token_ids.index(first.token_ids[3]) if stop else 6
    assert cut > 0                   # a decode step's token, not the chunk's
    outs = engine.generate(prompts, SamplingParams(
        max_tokens=6, stop_token_ids=tuple(first.token_ids[cut:cut + 1])))
    assert [len(o.token_ids) for o in outs][0] == cut
    stats = engine.stats
    assert len(readings) == stats["decode_steps"]
    # every reading brings its own decode step, and the chunks between
    # the sampler before it and its own; a chunk that rode a step is
    # that step's program, and the first reading brings the empty run
    assert stats["chunks_fused"] > 0
    assert min(readings) == 1 and sum(readings) == \
        stats["decode_steps"] + stats["chunks"] - stats["chunks_fused"] + 1
    assert [stats[name] for name in llama.ROUTING_COUNTERS] == \
        np.asarray(engine.cache["routing"]).tolist()
    assert stats["decode_slots"] - stats["tokens_generated"] == int(stop)


def test_a_dense_engine_has_no_routing_counters():
    engine = LLMEngine("tiny", slots=2, max_seq=32)
    assert "routing" not in engine.cache
    assert not any(key.startswith("moe_") for key in engine.stats)


def per_head_qk_proj(layer, h, c, step=False):
    """The wrong QK-norm: RMS over each head, not the whole width
    (``step``: the step programs' spelling of the products, not this
    stand-in's concern)."""
    def norm(x, weight, heads):
        split = x.reshape(*x.shape[:-1], heads, c.head_dim)
        return rmsnorm(split, weight.reshape(heads, c.head_dim),
                       c.norm_eps).reshape(x.shape)

    return (norm(h @ layer["wq"], layer["q_norm"], c.n_heads),
            norm(h @ layer["wk"], layer["k_norm"], c.n_kv_heads))


@pytest.mark.parametrize("wrong", ["renormalised-top-k", "per-head-qk-norm"])
def test_the_tolerance_sees_a_wrong_variant(params, wrong, monkeypatch):
    """What the tolerance is for: each near miss of the published
    equations lands orders above ``TOL``, on the forward pass and
    through the cache."""
    cfg = CFG
    if wrong == "renormalised-top-k":
        cfg = dataclasses.replace(CFG, norm_topk_prob=True)
    else:
        monkeypatch.setattr(llama, "_qk_proj", per_head_qk_proj)
    tokens, prompt = tokens_of(7, 40), 32
    want = ref.forward(*as_reference(params), jnp.asarray(tokens),
                       **ref.dims_of(SPEC))
    got = llama.forward(params, jnp.asarray(tokens)[None], cfg,
                        attn_impl="reference")[0]
    cached, _ = through_the_cache(params, cfg, tokens, prompt)
    assert rel_l2(got, want).max() > 100 * TOL
    assert rel_l2(cached[:-1], want[prompt - 1:-1]).max() > 100 * TOL
    # and the reference told to renormalise agrees with that variant
    if wrong == "renormalised-top-k":
        dims = {**ref.dims_of(SPEC), "norm_topk_prob": True}
        same = ref.forward(*as_reference(params), jnp.asarray(tokens),
                           **dims)
        assert rel_l2(got, same).max() < TOL
