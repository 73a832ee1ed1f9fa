"""LFM2-8B-A1B's stack (gated short-convolution layers, whose state a
slot is a convolution TAIL and no state matrix, beside softmax layers
with an RMSNorm a head on q and k; two leading DENSE layers that are
themselves convolution layers, a run of their own with its own period;
a sigmoid router whose picks a bias corrects; the head tied) on the
program's normal paths, against the plain reference
``chipbench/reference/lfm2_decoder.py`` on seeded random weights at a
tiny shape, on the CPU in float32.

The measure is the benchmark's own: the relative L2 distance of the
logits, per position.  Both sides compute in float32, so the distance
is rounding and the order of summation: 5e-6 at worst here.  ``TOL`` =
5e-5 is two orders and more under what it must catch: a tail not
handed from chunk to chunk, a gate left off, the picks made without the
bias, the q and k norms left off (each over 1e-2 below).

The step programs' attention block is cut to 16 positions.
"""

import contextlib
import dataclasses
import functools
import json
import os
import statistics

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ant_ray_tpu.llm import LLMEngine, SamplingParams
from ant_ray_tpu.models import llama
from benchmarks.lfm2_parity import (
    LEFT_OFF,
    held_to_the_forced_limits,
    left_off,
    picks_in_hand,
    program_picks,
    watched_programs,
)
from benchmarks.solar_open2_parity import _through_engine
from chipbench.models import lfm2
from chipbench.reference import lfm2_decoder as ref

CFG = llama.CONFIGS["lfm2-tiny"]         # two dense layers, two periods
ONE = dataclasses.replace(CFG, n_layers=6, layer_kinds=CFG.layer_kinds[:6])
LAYER_TYPES = ["conv", "conv"] + ["full_attention", "conv", "conv",
                                  "conv"] * 2
TOL = 5e-5
SLOTS, MAX_SEQ = 3, 256
PUBLISHED = os.path.join(os.path.dirname(__file__), "..", "chipbench",
                         "configs", "lfm2-8b-a1b.json")
STACKS = ("dense_conv_layers", "layers", llama.CONV)


@pytest.fixture(autouse=True)
def short_blocks(monkeypatch):
    monkeypatch.setattr(llama, "ATTEND_BLOCK", 16)


def dims_of(cfg):
    return dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps,
                experts_per_token=cfg.experts_per_token,
                routed_scaling_factor=cfg.routed_scaling_factor)


def seeded_params(cfg=CFG, seed=0):
    """Seeded weights, less bland than the initialiser's: matrices
    large enough that the router decides, attention attends and the
    gates B and C are of the size of their inputs; norm weights that
    are not all ones.  The taps and the router's bias stay as drawn."""
    p = llama.init_params(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 128))

    def livelier(stack):
        out = {}
        for name, leaf in stack.items():
            if name in ("conv_w", "router_bias"):
                out[name] = leaf
            elif name.startswith("ln_") or name in ("q_norm", "k_norm"):
                out[name] = leaf * jax.random.uniform(
                    next(keys), leaf.shape, minval=0.5, maxval=1.5)
            else:
                out[name] = leaf * 6.0
        return out

    return {**p, "norm_f": p["norm_f"] * 0.7,
            **{name: livelier(p[name]) for name in STACKS}}


@pytest.fixture(scope="module")
def params():
    return seeded_params()


def reference_logits(params, tokens, cfg=CFG, off=None):
    """``off``: a part of the mathematics the reference leaves off
    (``benchmarks.lfm2_parity.left_off``)."""
    embed, layer, n, norm_f, head = lfm2.reference_layers(
        params, LAYER_TYPES, cfg.n_dense_layers)
    with left_off(off) if off else contextlib.nullcontext():
        block = jax.jit(ref.block, static_argnames=(     # as the harness
            "n_heads", "n_kv_heads", "rope_theta", "norm_eps"))
        return ref.forward(embed, (layer, n), norm_f, head,
                           jnp.asarray(tokens), block_fn=block,
                           **dims_of(cfg))


def rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(((got - want) ** 2).sum(-1) / (want ** 2).sum(-1))


def tokens_of(seed, *shape):
    return np.random.default_rng(seed).integers(
        0, CFG.vocab_size, shape).astype(np.int32)


def _bits(x):
    return np.asarray(x).view(np.uint32)


# The model's step functions jitted once a shape, under their own
# argument order (the engine's programs are ``llm/programs.py``'s).
@functools.partial(jax.jit, static_argnames=("cfg",))
def forward(params, tokens, cfg=CFG):
    return llama.forward(params, tokens, cfg)


@functools.partial(jax.jit, static_argnames=("cfg",))
def chunk_step(params, tokens, cache, slot, start, n, cfg=CFG):
    return llama.prefill_chunk_into_cache(params, tokens, cache, slot,
                                          start, n, cfg)


@functools.partial(jax.jit, static_argnames=("cfg",))
def decode_step(params, last, cache, active, cfg=CFG):
    return llama.decode_step(params, last, cache, cfg, active)


@jax.jit
def mixed_step(params, last, tokens, cache, active, slot, start, n):
    return llama.mixed_step(params, last, tokens, cache, CFG, active, slot,
                            start, n)


def ingest(params, cache, tokens, slot, chunk, start=0, cfg=CFG):
    """``tokens`` into ``slot`` from position ``start`` on, in chunks ->
    (logits at each chunk's last token, cache)."""
    logits = []
    for at in range(0, len(tokens), chunk):
        part = tokens[at:at + chunk]
        buf = np.zeros((chunk,), np.int32)
        buf[:len(part)] = part
        out, cache = chunk_step(params, jnp.asarray(buf), cache, slot,
                                start + at, len(part), cfg=cfg)
        logits.append(out)
    return logits, cache


def decode(params, cache, tokens, slot, cfg=CFG, others=()):
    """``tokens`` one by one (teacher forced) in ``slot``; ``others``:
    slots that decode token 7 beside it."""
    active = np.zeros((SLOTS,), bool)
    active[[slot, *others]] = True
    got = []
    for token in tokens:
        last = np.full((SLOTS,), 7, np.int32)
        last[slot] = token
        logits, cache = decode_step(params, jnp.asarray(last), cache,
                                    jnp.asarray(active), cfg=cfg)
        got.append(logits[slot])
    return got, cache


def through_the_cache(params, tokens, prompt, chunk, slot=1, cfg=CFG):
    """-> logits at every chunk's end and from the last prompt token
    on, with the positions they belong to."""
    cache = llama.init_kv_cache(cfg, SLOTS, MAX_SEQ, chunk)
    ends, cache = ingest(params, cache, tokens[:prompt], slot, chunk,
                         cfg=cfg)
    at = [min(a + chunk, prompt) - 1 for a in range(0, prompt, chunk)]
    rest, cache = decode(params, cache, tokens[prompt:], slot, cfg=cfg)
    return jnp.stack(ends + rest), at + list(range(prompt, len(tokens)))


# ------------------------------------------------ (a) against the reference

@pytest.mark.parametrize("cfg", [ONE, CFG], ids=["one-period", "two-periods"])
def test_forward_is_the_reference(cfg):
    params = seeded_params(cfg)
    tokens = tokens_of(1, 75)
    got = forward(params, jnp.asarray(tokens)[None], cfg=cfg)[0]
    want = reference_logits(params, tokens, cfg)
    assert rel_l2(got, want).max() < TOL


@pytest.mark.parametrize("chunk,prompt", [
    (32, 64), (24, 65), (32, 35), (8, 75), (16, 1), (16, 2), (16, 18)],
    ids=["chunks-divide-the-prompt", "a-padded-last-chunk-of-17",
         "a-padded-last-chunk-of-3", "short-chunks", "a-prompt-of-1-token",
         "a-prompt-of-2-tokens", "a-last-chunk-shorter-than-the-tail"])
def test_chunks_and_decode_through_the_cache_are_forward(params, chunk,
                                                         prompt):
    """Prefill in chunks — the tail handed from chunk to chunk, a last
    chunk padded behind its real tokens, a prompt shorter than the tail
    itself (2 inputs) — then decode steps, equal ``forward`` (and the
    reference) at every position read."""
    tokens = tokens_of(2, prompt + 9)
    got, at = through_the_cache(params, tokens, prompt, chunk)
    want = reference_logits(params, tokens)
    assert rel_l2(got, want[np.asarray(at)]).max() < TOL
    whole = forward(params, jnp.asarray(tokens)[None])[0]
    assert rel_l2(got, whole[np.asarray(at)]).max() < TOL


def test_a_mixed_step_agrees(params):
    """Two slots decode while a third prompt's chunk rides behind them:
    every part's logits are the reference's of its own sequence, and
    the riding chunk leaves the tail a lone chunk leaves."""
    a, b, new = tokens_of(3, 21), tokens_of(4, 34), tokens_of(5, 11)
    cache = llama.init_kv_cache(CFG, SLOTS, MAX_SEQ, 16)
    _, cache = ingest(params, cache, a[:-1], 0, 16)
    _, cache = ingest(params, cache, b[:-1], 2, 16)
    _, alone = ingest(params, cache, new, 1, 16)
    last = jnp.asarray([a[-1], 0, b[-1]], jnp.int32)
    buf = np.zeros((16,), np.int32)
    buf[:len(new)] = new
    rows, chunk, cache = mixed_step(
        params, last, jnp.asarray(buf), cache,
        jnp.asarray([True, False, True]), 1, 0, len(new))
    assert np.asarray(cache["length"]).tolist() == [21, 11, 34]
    for got, seq in ((rows[0], a), (rows[2], b), (chunk, new)):
        assert rel_l2(got, reference_logits(params, seq)[-1]) < TOL
    assert (_bits(cache["conv"][:, 1]) == _bits(alone["conv"][:, 1])).all()
    # and the rows that decoded moved their tails up by one input
    assert (_bits(cache["conv"][:, 0, 0]) == _bits(alone["conv"][:, 0, 1])
            ).all()


def test_a_tail_that_is_not_handed_over_is_caught(params):
    """What ``TOL`` must catch: the slot's tail emptied between two
    chunks."""
    tokens = tokens_of(6, 48)
    want = reference_logits(params, tokens)[25]
    cache = llama.init_kv_cache(CFG, SLOTS, MAX_SEQ, 24)
    _, cache = ingest(params, cache, tokens[:24], 1, 24)
    dropped = {**cache, "conv": cache["conv"].at[:, 1].set(0.0)}
    (kept,), _ = ingest(params, cache, tokens[24:26], 1, 24, start=24)
    (lost,), _ = ingest(params, dropped, tokens[24:26], 1, 24, start=24)
    assert rel_l2(kept, want) < TOL < 1e-2 < rel_l2(lost, want)


@pytest.mark.parametrize("off", LEFT_OFF)
def test_each_part_of_the_mathematics_is_read(params, off):
    """The reference with one part left off — the gate B, the gate C,
    the router's correction bias, the q and k norms — is no longer the
    program: the tolerance tells the two apart."""
    tokens = tokens_of(7, 40)
    got = forward(params, jnp.asarray(tokens)[None])[0]
    assert rel_l2(got, reference_logits(params, tokens)).max() < TOL
    assert np.median(rel_l2(got, reference_logits(
        params, tokens, off=off))) > 1e-2


# ------------------------------------------- (b) slots, neighbours, idle rows

def test_padding_hands_on_the_tail_behind_the_last_real_token(params):
    """A chunk of 5 real tokens padded to 16 leaves the last two REAL
    gated inputs as the slot's tail — what the same 5 tokens leave in a
    chunk of 5 — whatever the padding's tokens are; a chunk of ONE real
    token moves the tail up by one."""
    tokens = tokens_of(8, 6)
    tails = []
    for chunk, filler in ((16, 0), (16, 99), (5, 0)):
        buf = np.full((chunk,), filler, np.int32)
        buf[:5] = tokens[:5]
        _, cache = chunk_step(
            params, jnp.asarray(buf),
            llama.init_kv_cache(CFG, SLOTS, MAX_SEQ, chunk), 1, 0, 5)
        tails.append(np.asarray(cache["conv"][:, 1]))
    np.testing.assert_array_equal(tails[0], tails[1])
    np.testing.assert_allclose(tails[0], tails[2], rtol=1e-4, atol=1e-5)
    assert np.abs(tails[0]).min(axis=-1).max() > 0
    buf = np.full((16,), 99, np.int32)
    buf[0] = tokens[5]
    _, after = chunk_step(params, jnp.asarray(buf), cache, 1, 5, 1)
    # (layer 0's input is the embedding alone: its tail moves up bit
    # for bit; deeper layers' follow what the layers below made of it)
    assert (_bits(after["conv"][0, 1, 0]) == _bits(cache["conv"][0, 1, 1])
            ).all()


def test_a_slot_used_again_gives_what_a_fresh_cache_gives(params):
    """A chunk whose ``start`` is 0 begins from an EMPTY tail whatever
    the slot's last occupant left — selected in the program, no reset
    call: bit-equal logits to the same prompt in a fresh cache."""
    first, second = tokens_of(10, 70), tokens_of(11, 50)
    cache = llama.init_kv_cache(CFG, SLOTS, MAX_SEQ, 24)
    _, cache = ingest(params, cache, first, 1, 24)
    _, cache = decode(params, cache, tokens_of(12, 5), 1)
    assert float(jnp.abs(cache["conv"][:, 1]).max()) > 0
    again, cache = ingest(params, cache, second, 1, 24)
    fresh, clean = ingest(
        params, llama.init_kv_cache(CFG, SLOTS, MAX_SEQ, 24), second, 1, 24)
    for a, b in zip(again, fresh):
        assert (_bits(a) == _bits(b)).all()
    assert tuple(llama.state_slabs(CFG)) == ("conv",)
    assert (_bits(cache["conv"][:, 1]) == _bits(clean["conv"][:, 1])).all()


def test_a_row_between_two_of_its_chunks_is_not_advanced(params):
    """While a prompt's chunks wait, its neighbours decode and ingest:
    the row is not ``active``, so its tail stays bit for bit and its
    prompt ends on the logits it gives alone."""
    prompt = tokens_of(13, 60)
    alone, _ = ingest(params, llama.init_kv_cache(CFG, SLOTS, MAX_SEQ, 24),
                      prompt, 1, 24)
    cache = llama.init_kv_cache(CFG, SLOTS, MAX_SEQ, 24)
    _, cache = ingest(params, cache, tokens_of(14, 30), 0, 24)
    _, cache = ingest(params, cache, tokens_of(15, 40), 2, 24)
    among = []
    for at in range(0, 60, 24):
        out, cache = ingest(params, cache, prompt[at:at + 24], 1, 24,
                            start=at)
        among += out
        held = np.asarray(cache["conv"][:, 1])
        _, cache = decode(params, cache, tokens_of(16 + at, 3), 0,
                          others=(2,))
        _, cache = ingest(params, cache, tokens_of(17 + at, 20), 2, 24)
        assert (_bits(cache["conv"][:, 1]) == _bits(held)).all()
    for a, b in zip(among, alone):
        assert (_bits(a) == _bits(b)).all()


def test_an_idle_row_of_a_step_keeps_its_tail(params):
    cache = llama.init_kv_cache(CFG, SLOTS, MAX_SEQ, 24)
    for slot, seed in enumerate((20, 21, 22)):
        _, cache = ingest(params, cache, tokens_of(seed, 30), slot, 24)
    before = np.asarray(cache["conv"])
    _, cache = decode(params, cache, tokens_of(23, 2), 1)
    after = np.asarray(cache["conv"])
    for idle in (0, 2):
        assert (_bits(after[:, idle]) == _bits(before[:, idle])).all()
    assert (_bits(after[:, 1]) != _bits(before[:, 1])).any()


def test_a_row_decodes_the_same_beside_longer_and_shorter_rows(params):
    """The probes' rule: a row's logits do not depend, to the bit, on
    the rows that decode beside it."""
    mine, more = tokens_of(24, 60), tokens_of(25, 6)
    cache = llama.init_kv_cache(CFG, SLOTS, MAX_SEQ, 24)
    _, cache = ingest(params, cache, mine, 1, 24)
    alone, _ = decode(params, cache, more, 1)
    _, cache = ingest(params, cache, tokens_of(26, 140), 0, 24)
    _, cache = ingest(params, cache, tokens_of(27, 2), 2, 24)
    beside, _ = decode(params, cache, more, 1, others=(0, 2))
    for a, b in zip(alone, beside):
        assert (_bits(a) == _bits(b)).all()
    assert rel_l2(alone[-1], reference_logits(
        params, np.concatenate([mine, more]))[-1]) < TOL


# --------------------------------------------------------- (c) the engine

class _NoEos:
    def encode(self, text):
        return [ord(c) % CFG.vocab_size for c in text]

    def decode(self, ids):
        return " ".join(str(i) for i in ids)


def _engine(params, **kw):
    kw.setdefault("slots", 3)
    kw.setdefault("max_seq", MAX_SEQ)
    kw.setdefault("prefill_chunk_tokens", 16)
    kw.setdefault("tokenizer", _NoEos())
    return LLMEngine(CFG, params, **kw)


def test_the_engine_serves_it_and_a_request_among_others_is_itself_alone(
        params):
    """Prompts longer than a chunk, shorter than a chunk, and shorter
    than the tail (2 and 1 tokens) share the engine."""
    prompts = [tokens_of(40, 57).tolist(), [5, 9], tokens_of(41, 23).tolist(),
               [17]]
    eng = _engine(params)
    together = eng.generate(prompts, SamplingParams(max_tokens=12))
    for prompt, out in zip(prompts, together):
        alone = _engine(params).generate([prompt],
                                         SamplingParams(max_tokens=12))
        assert alone[0].token_ids == out.token_ids
    # greedy tokens are the reference's arg-max, chunks and steps through
    for prompt, out in ((prompts[0], together[0]), (prompts[3], together[3])):
        tokens = np.asarray(prompt + out.token_ids)
        want = np.asarray(reference_logits(params, tokens))[
            len(prompt) - 1:-1].argmax(-1)
        assert want.tolist() == out.token_ids
    # eight conv layers (two dense, six routed), counted as the other
    # recurrent kinds' are: a tail-only layer is a recurrent layer
    stats = eng.stats
    assert eng.config.n_recurrent == 8
    assert stats["recurrent_resets"] == 4
    assert stats["recurrent_chunk_rows"] == 8 * 16 * stats["chunks"]
    assert stats["recurrent_chunk_tokens"] == 8 * (57 + 2 + 23 + 1)
    assert stats["recurrent_slot_rows"] == 8 * 3 * stats["decode_steps"]
    assert 0 < stats["recurrent_decode_rows"] <= stats["recurrent_slot_rows"]
    assert stats["chunks_fused"] > 0          # a chunk rode a decode step


def test_the_probe_of_three_geometries_holds_the_program_to_each():
    """``chipbench/replica_median_triple.py`` (the configuration's
    ``serve.replica``) through the configuration FILE, its factory and
    ``reference_layers``, at the published depth and pattern and tiny
    widths: whole chunks; a prompt that ends a few tokens behind a chunk
    boundary, where a tail that is not handed over is most of the
    answer; a prompt of a few tokens.  All three medians in ``rel_l2``."""
    from chipbench.replica_median_triple import MedianTripleProbeLLMServer

    with open(PUBLISHED) as f:
        spec = json.load(f)
    assert spec["serve"]["replica"] == (
        "chipbench.replica_median_triple:MedianTripleProbeLLMServer")
    spec.update(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        vocab_size=256, intermediate_size=96, moe_intermediate_size=32,
        num_experts=8, num_experts_per_tok=2, max_position_embeddings=512)
    spec["serve"]["probe_short_last_chunk"] = {"tokens_behind_boundary": 2}
    spec["serve"]["probe_short_prompt"] = {"tokens": 9}
    server = MedianTripleProbeLLMServer(spec, slots=2, max_seq=256, seed=3,
                                        prefill_chunk_tokens=32)
    try:
        eng = server.engine
        assert eng.config.kinds == ("conv", "conv") + (
            "full", "conv", "conv", "conv") * 3
        # at 64 wide a normed row times a matrix drawn at 0.02 is an
        # eighth of what it is at 2,048: scaled up, B, C, u and the
        # softmax layers' projections have the published size
        eng.params = {**eng.params, **{
            name: {leaf: w * (8 if leaf in ("in_proj", "wq", "wk", "wv")
                              else 1) for leaf, w in eng.params[name].items()}
            for name in STACKS}}
        out = server.probe_logits(7, 128, 4)
        assert out["prompt_tokens"] == [128, 96 + 2, 9]
        assert out["positions"] == 15 and len(out["rel_l2"]) == 3
        assert out["rel_l2"] == [statistics.median(by) for by in
                                 out["rel_l2_by_position"]]
        # bfloat16 weights against the float32 reference, on the CPU
        assert max(out["rel_l2"]) < 0.1
        plain = eng._prefill_chunk_jit

        def not_handed_over(params, cache, buf, slot, start, n):
            if start:
                cache = {**cache, "conv": cache["conv"].at[:, slot].set(0)}
            return plain(params, cache, buf, slot, start, n)

        eng._prefill_chunk_jit = not_handed_over
        lost = server.probe_logits(7, 128, 4)["rel_l2"]
        assert lost[1] > 3 * out["rel_l2"][1]
        assert lost[2] == out["rel_l2"][2]          # one chunk: no hand-over
    finally:
        server.shutdown()


def _tiny_spec():
    """What ``benchmarks.lfm2_parity``'s reference reads of a
    configuration file, for ``CFG``."""
    return {"reference": {
        "module": "chipbench.reference.lfm2_decoder",
        "params": "chipbench.models.lfm2:reference_layers"},
        "num_attention_heads": CFG.n_heads,
        "num_key_value_heads": CFG.n_kv_heads, "rope_theta": CFG.rope_theta,
        "norm_eps": CFG.norm_eps,
        "num_experts_per_tok": CFG.experts_per_token,
        "routed_scaling_factor": CFG.routed_scaling_factor}


@pytest.mark.parametrize("prompt", [40, 34, 7], ids=str)
def test_the_program_s_picks_are_read_and_forced_on_the_reference(
        params, prompt):
    """``benchmarks.lfm2_parity --picks``: the step programs compiled
    with a watch on every routed layer give the timed programs' logits
    to the bit and two experts a (routed layer, position); in float32
    they are the reference's own two everywhere, the reference GIVEN
    them is the reference, and a reference that picks without the bias
    picks others."""
    spec, steps, seen = _tiny_spec(), 4, []
    eng = _engine(params)
    watched = watched_programs(
        CFG, eng.slots, MAX_SEQ, 16,
        lambda softmax, index, picks: seen.append(
            (softmax, index, np.asarray(picks))))
    tokens = tokens_of(60 + prompt, prompt + steps)
    got = _through_engine(eng, tokens, prompt, steps)
    with picks_in_hand() as reference:
        want, theirs, own = reference(spec, params, tokens, prompt - 1)
        again, mine = program_picks(eng, watched, seen, CFG, tokens, prompt,
                                    steps)
        forced, _, theirs_again = reference(spec, params, tokens,
                                            prompt - 1, picks=mine)
    assert np.array_equal(_bits(again), _bits(got))
    assert mine.shape == (8, prompt + steps, CFG.num_experts)
    assert (mine.sum(-1) == CFG.experts_per_token).all()
    assert np.array_equal(mine, theirs) and np.array_equal(own, theirs)
    assert np.array_equal(theirs_again, mine)
    assert rel_l2(got, want).max() < TOL
    assert rel_l2(forced, want).max() < 1e-6
    with left_off("no_expert_bias"), picks_in_hand() as reference:
        _, _, unbiased = reference(spec, params, tokens, prompt - 1,
                                   picks=mine)
    assert (unbiased == mine).all(-1).mean() < 0.9
    # ... and a pick forced that is not the reference's own moves it
    other = np.roll(mine, 1, axis=-1)
    with picks_in_hand() as reference:
        moved, _, _ = reference(spec, params, tokens, prompt - 1, picks=other)
    assert rel_l2(moved, want).min() > 100 * TOL


def test_the_forced_limits_decide_by_one_probe_of_three():
    """``tolerance.forced_picks`` of the configuration file, as
    ``benchmarks.lfm2_parity``'s exit code holds it: the sound program
    inside at every probe's worst position, a control outside at ONE
    probe at least."""
    with open(PUBLISHED) as f:
        tolerance = json.load(f)["tolerance"]
    limits = tolerance["forced_picks"]
    assert limits["rel_l2_worst_position"] < tolerance["serve_logit_rel_l2"]
    inside, outside = (limits["rel_l2_worst_position"] * f for f in (0.6, 2))

    def probe(prompt, forced=inside, equal=95.0, **controls):
        return {"seed": 1, "prompt": prompt, "forced": {"worst": forced},
                "picks_equal_pct": equal,
                **{name: {"worst": worst} for name, worst in controls.items()}}

    sound = [probe(1024, no_qk_norm_forced=inside),
             probe(514, no_qk_norm_forced=inside),
             probe(40, no_qk_norm_forced=outside)]
    assert held_to_the_forced_limits(sound, tolerance) == 0
    assert held_to_the_forced_limits(
        [{"seed": 1, "prompt": 40, "program": {"worst": 9.0}}], tolerance) == 0
    for broken in (
            [probe(40, forced=outside)],                 # the program itself
            [probe(40, equal=limits["picks_equal_pct_min"] - 1)],
            [probe(1024, no_qk_norm_forced=inside),     # a control none sees
             probe(40, no_qk_norm_forced=inside)],
            [{**probe(40), "no_expert_bias_picks_equal_pct": 95.0}]):
        assert held_to_the_forced_limits(broken, tolerance) == 1


def test_what_a_tail_only_state_is_refused_by_name(params):
    eng = _engine(params)
    with pytest.raises(ValueError, match="sessions are not kept over a "
                                         "recurrent state.*short-convolution"):
        eng.add_request([1, 2, 3], session_id="turns")
    with pytest.raises(ValueError, match="a recurrent state .*short "
                                         "convolution's tail.* is not "
                                         "sharded"):
        _engine(params, tensor_parallel_size=2)
    with pytest.raises(ValueError, match="no conv layers"):
        llama.loss_fn_pp(params, {"tokens": jnp.zeros((2, 9), jnp.int32)},
                         CFG, mesh=type("M", (), {"shape": {"pp": 2}})())


@pytest.mark.parametrize("change,message", [
    # what stays refused around leading dense layers of a kind
    (dict(layer_kinds=("full", "conv", "conv", "conv", "conv")),
     "leading dense ones are all conv"),       # a period whose first is full
    (dict(layer_kinds=("full", "conv") + CFG.layer_kinds[2:]),
     "leading dense ones are all conv"),                   # a dense softmax
    (dict(layer_kinds=("conv",) * 10), "routed ones full and conv"),
    (dict(layer_kinds=("linear", "linear") + ("full", "linear") * 4,
          linear_heads=4, linear_head_dim=16),
     "leading dense ones are all conv"),
    (dict(layer_kinds=("conv", "conv") + ("full", "conv", "conv", "ssm") * 2),
     "in ONE model are not computed"),
    # and around the kind itself
    (dict(conv_L_cache=0), "conv layers state their convolution's taps"),
    (dict(conv_L_cache=1), "conv layers state their convolution's taps"),
    (dict(parallel_block=True), "sequential block"),
    (dict(loops=2), "a recurrent kind"),
    (dict(block_length=4, denoising_steps=4, confidence_threshold=0.9,
          mask_token=255), "a recurrent kind"),
], ids=["a-period-beside-dense-layers", "a-dense-softmax-layer",
        "no-softmax-layer-routed", "dense-linear-layers", "two-kinds",
        "no-taps", "one-tap", "parallel-block", "loops", "blocks"])
def test_what_the_config_still_refuses(change, message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(CFG, **change)


def test_a_period_is_cut_into_runs_as_the_whole_pattern_is():
    """``layer_kinds`` means one thing: layer i is of kind
    ``layer_kinds[i % len]``.  A period of five over ten layers gives
    the runs its ten entries written out give."""
    period = ("conv", "conv", "full", "conv", "conv")
    short = dataclasses.replace(CFG, layer_kinds=period)
    whole = dataclasses.replace(CFG, layer_kinds=period * 2)
    assert short.pattern == whole.pattern == period * 2
    assert short.stacks() == whole.stacks()
    assert [run.layer_kinds for run in short.stacks().values()] == [
        ("conv",), ("full", "conv", "conv", "conv", "conv", "full", "conv",
                    "conv")]
    assert short.n_recurrent == 8 and short.num_params() == whole.num_params()


def test_the_tail_moves_with_the_slabs(params):
    """``_extract`` / ``_install`` (what ``kv_offload.py`` stores) move
    a slot's ``conv`` by the slabs' own rule — there is no ``s`` to
    move: the row decodes in another slot of another cache what it
    decodes where it lay."""
    tokens = tokens_of(43, 52)
    eng = _engine(params)
    _, cache = ingest(params, eng.cache, tokens[:51], 0, 16)
    taken = eng._extract_jit(cache, 0)
    assert [x.shape for x in taken] == [
        (2, MAX_SEQ, 2, 16), (2, MAX_SEQ, 2, 16), (8, 2, 64)]
    moved = eng._install_jit(
        llama.init_kv_cache(CFG, 3, MAX_SEQ, 16), taken, jnp.int32(51), 2)
    last = jnp.full((3,), int(tokens[51]), jnp.int32)
    here, _ = decode_step(params, last, cache,
                          jnp.asarray([True, False, False]))
    there, _ = decode_step(params, last, moved,
                           jnp.asarray([False, False, True]))
    assert (_bits(here[0]) == _bits(there[2])).all()
    assert rel_l2(here[0], reference_logits(params, tokens)[-1]) < TOL


# ------------------------------------------- the factory, and the counts

def _published():
    with open(PUBLISHED) as f:
        return json.load(f)


@pytest.mark.parametrize("key,value,message", [
    ("conv_bias", True, "a convolution with bias"),
    ("use_expert_bias", False, "a router without its correction bias"),
    ("norm_topk_prob", False, "gates left as the scores were"),
    ("model_type", "lfm2", "a model_type other than lfm2_moe"),
    ("num_hidden_layers", 13, "routed run is not whole periods"),
    ("num_hidden_layers", 24, "routed run is not whole periods"),
    ("num_dense_layers", 3, "leading dense layers of a kind other than conv"),
])
def test_the_factory_refuses_what_it_does_not_map(key, value, message):
    with pytest.raises(ValueError, match=message):
        lfm2.build({**_published(), key: value})


@pytest.mark.parametrize("depth", [6, 10, 14, 18])
def test_the_factory_maps_every_whole_prefix(depth):
    cfg = lfm2.build({**_published(), "num_hidden_layers": depth})
    runs = cfg.stacks()
    assert runs["dense_layers"].kinds == ("conv",)
    assert runs["layers"].kinds == ("full", "conv", "conv", "conv")
    assert runs["layers"].n_layers == depth - 2
    assert cfg.n_recurrent == depth - (depth - 2) // 4
    assert float(cfg.routed_scaling_factor) == 1.0


def test_num_params_and_cache_at_the_published_cut():
    """4,667,077,376 parameters held (layers 0-13 with all 32 experts,
    the whole vocabulary, tied), by ``jax.eval_shape`` of the
    initialiser — nothing is allocated — and the cell's cache, 96 x
    4,096: three layers' slabs, 6,144 B a position, beside eleven tails
    of 2 x 2,048 a slot, 90,112 B, in bfloat16 — and no state matrix."""
    spec = _published()
    cfg = lfm2.build(spec)
    assert cfg.kinds == ("conv", "conv") + ("full", "conv", "conv",
                                            "conv") * 3
    assert cfg.layer_counts() == (0, 3) and cfg.n_recurrent == 11
    assert cfg.recurrent == "conv" and cfg.n_linear == 0
    assert cfg.full_rope and cfg.tie_embeddings and cfg.qk_norm == "head"
    assert cfg.head_dim == 64 and cfg.conv_L_cache == 3
    assert cfg.flat_kv_heads and not llama.CONFIGS["lfm2-tiny"].flat_kv_heads
    assert (cfg.router_scoring, cfg.router_bias, cfg.norm_topk_prob) == (
        "sigmoid", True, True)
    runs = cfg.stacks()
    assert [(name, run.n_layers, run.kinds, llama.run_stacks(name, run))
            for name, run in runs.items()] == [
        ("dense_layers", 2, ("conv",),
         {"conv_layers": "dense_conv_layers"}),
        ("layers", 12, ("full", "conv", "conv", "conv"),
         {"layers": "layers", "conv_layers": "conv_layers"})]
    assert [runs["layers"].place(j) for j in range(4)] == [
        ("layers", 1, 0), ("conv_layers", 3, 0), ("conv_layers", 3, 1),
        ("conv_layers", 3, 2)]
    shapes = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    held = sum(leaf.size for leaf in jax.tree.leaves(shapes))
    assert held == cfg.num_params() == 4_667_077_376
    assert set(shapes) == {"embed", "norm_f", *STACKS}
    dense, softmax, conv = (shapes[name] for name in STACKS)
    assert dense["in_proj"].shape == (2, 2048, 6144)
    assert dense["conv_w"].shape == (2, 3, 2048)
    assert dense["wo"].shape == (2, 2048, 2048)
    assert dense["w_gate"].shape == (2, 2048, 7168)
    assert conv["in_proj"].shape == (9, 2048, 6144)
    assert conv["w_gate"].shape == (9, 32, 2048, 1792)
    assert conv["router"].shape == (9, 2048, 32)
    assert conv["router_bias"].shape == (9, 32)
    assert softmax["wq"].shape == (3, 2048, 2048)
    assert softmax["wk"].shape == (3, 2048, 512)
    assert softmax["q_norm"].shape == softmax["k_norm"].shape == (3, 64)
    assert "q_norm" not in conv and "conv_b" not in conv
    ffn = 32 * 11_010_048 + 65_536 + 32 + 2 * 2048
    assert sum(leaf.size for leaf in jax.tree.leaves(dense)) == 121_655_296
    assert sum(leaf.size for leaf in jax.tree.leaves(conv)) == 9 * (
        16_783_360 + ffn)
    assert sum(leaf.size for leaf in jax.tree.leaves(softmax)) == 3 * (
        10_485_888 + ffn)
    assert shapes["embed"].size == 134_217_728 and "lm_head" not in shapes
    assert llama.flops_per_token(cfg, 1) < 6 * cfg.num_params()
    cache = jax.eval_shape(lambda: llama.init_kv_cache(
        cfg, 96, 4096, spec["serve"]["kwargs"]["prefill_chunk_tokens"]))
    assert {n: (cache[n].shape, cache[n].dtype.name) for n in (
        *llama.kv_slabs(cfg), *llama.state_slabs(cfg))} == {
        # 8 heads of 64 side by side: whole lane tiles, nothing padded
        "k": ((3, 96, 4096, 512), "bfloat16"),
        "v": ((3, 96, 4096, 512), "bfloat16"),
        "conv": ((11, 96, 2, 2048), "bfloat16")}
    assert "s" not in cache
    assert 2 * 3 * 8 * 64 * 2 == 6_144                   # B a position
    assert 11 * 2 * 2048 * 2 == 90_112                   # B a slot
    assert 96 * 4096 * 6_144 == 2.25 * 2 ** 30
    # the floors of a model_config cut
    assert spec["num_hidden_layers"] == 14 and spec["num_experts"] == 32
    assert set(spec["reduced"]) == {"num_hidden_layers"}
    # what the cut leaves as published
    assert len(spec["layer_types"]) == 24
    assert spec["layer_types"].count("conv") == 18


def test_the_file_holds_the_catalogs_keys():
    """Letter for letter, but the depth."""
    spec = _published()
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
        "num_experts_per_tok": 4, "num_key_value_heads": 8,
        "rope_theta": 1000000, "routed_scaling_factor": 1,
        "use_expert_bias": True, "vocab_size": 65536}
    assert {key: spec[key] for key in published} == published
    assert spec["layer_types"] == (
        ["conv", "conv", "full_attention"] + ["conv", "conv", "conv",
                                              "full_attention"] * 4
        + ["conv", "conv", "full_attention", "conv", "conv"])
    assert spec["reduced"]["num_hidden_layers"]["from"] == 24
    assert spec["reduced"]["num_hidden_layers"]["to"] == 14


@pytest.mark.parametrize("preset,recurrent,tree,cache", [
    ("solar2-tiny", ("linear", 6), {"embed", "layers", "linear_layers",
                                    "norm_f", "lm_head"},
     {"k": ((2, 3, 64, 2, 16), "float32"),
      "v": ((2, 3, 64, 2, 16), "float32"),
      "s": ((6, 3, 4, 16, 16), "float32"),
      "conv": ((6, 3, 3, 192), "float32")}),
    ("granite-h-tiny", ("ssm", 6), {"embed", "layers", "ssm_layers",
                                    "norm_f"},
     {"k": ((2, 3, 64, 2, 16), "float32"),
      "v": ((2, 3, 64, 2, 16), "float32"),
      "s": ((6, 3, 4, 16, 16), "float32"),
      "conv": ((6, 3, 3, 96), "float32")}),
    ("axk1-tiny", ("", 0), {"embed", "dense_layers", "layers", "norm_f",
                            "lm_head"},
     {"c_kv": ((3, 3, 64, 16), "float32"),
      "k_rope": ((3, 3, 64, 8), "float32")}),
])
def test_the_other_kinds_cache_and_tree_are_what_they_were(
        preset, recurrent, tree, cache):
    """The linear and the state-space kind keep ``s`` AND ``conv``,
    their stacks lie where they lay, and a routed model with plain
    leading dense layers has the two stacks it had (their programs are
    pinned by ``benchmarks/step_program_hashes.py``)."""
    cfg = llama.CONFIGS[preset]
    assert (cfg.recurrent, cfg.n_recurrent) == recurrent
    assert set(llama.param_shapes(cfg)) == tree
    made = jax.eval_shape(lambda: llama.init_kv_cache(cfg, 3, 64, 16))
    assert {n: (made[n].shape, made[n].dtype.name) for n in made
            if n not in ("length", "routing")} == cache
