"""LLM engine tests: KV-cache decode parity with the no-cache reference
path, continuous batching, sampling, serve + batch integration
(capability mirror of the reference's llm/ test tiers)."""

import dataclasses

import numpy as np
import pytest

import jax

from ant_ray_tpu.llm import LLMEngine, SamplingParams
from ant_ray_tpu.llm import engine as engine_mod
from ant_ray_tpu.models import llama

import ant_ray_tpu as art


CFG = llama.CONFIGS["tiny"]


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.PRNGKey(7))


def _reference_greedy(params, prompt, n, cfg=CFG):
    """No-KV-cache greedy decode via the training forward pass."""
    toks = llama.greedy_generate(params, cfg, np.asarray(prompt, np.int32),
                                 max_new_tokens=n)
    return [int(t) for t in np.asarray(toks[0])[len(prompt):]]


def _truncate_at_eos(ids, eos=255):
    out = []
    for t in ids:
        if t == eos:
            break
        out.append(t)
    return out


@pytest.mark.slow
def test_kv_cache_matches_reference(params):
    engine = LLMEngine(CFG, params, slots=2, max_seq=128)
    prompt = [5, 9, 17, 3, 88, 41]
    n = 12
    ref = _truncate_at_eos(_reference_greedy(params, prompt, n))
    out = engine.generate([prompt], SamplingParams(max_tokens=n))[0]
    assert out.token_ids == ref[:len(out.token_ids)]
    assert len(out.token_ids) >= min(len(ref), 1)


@pytest.mark.slow
def test_continuous_batching_matches_sequential(params):
    prompts = [[1, 2, 3], [44, 55], [7, 8, 9, 10, 11]]
    n = 8
    solo = []
    for p in prompts:
        eng = LLMEngine(CFG, params, slots=1, max_seq=128)
        solo.append(eng.generate([p], SamplingParams(max_tokens=n))[0]
                    .token_ids)

    # Staggered arrivals share one engine's slots.
    eng = LLMEngine(CFG, params, slots=2, max_seq=128)
    rids = [eng.add_request(prompts[0], SamplingParams(max_tokens=n)),
            eng.add_request(prompts[1], SamplingParams(max_tokens=n))]
    outs = {}
    eng.step()
    rids.append(eng.add_request(prompts[2], SamplingParams(max_tokens=n)))
    while eng.has_unfinished():
        for o in eng.step():
            outs[o.request_id] = o
    got = [outs[r].token_ids for r in rids]
    assert got == solo


def test_sampling_determinism_and_greedy_equivalence(params):
    engine = LLMEngine(CFG, params, slots=2, max_seq=128)
    p = [10, 20, 30]
    sp = SamplingParams(max_tokens=6, temperature=0.8, top_k=40,
                       top_p=0.95, seed=123)
    a = engine.generate([p], sp)[0].token_ids
    b = LLMEngine(CFG, params, slots=2, max_seq=128).generate(
        [p], sp)[0].token_ids
    assert a == b  # seeded sampling is reproducible

    greedy = engine.generate([p], SamplingParams(max_tokens=6))[0].token_ids
    topk1 = engine.generate(
        [p], SamplingParams(max_tokens=6, temperature=0.7, top_k=1,
                            seed=1))[0].token_ids
    assert topk1 == greedy  # top_k=1 collapses to argmax


# Seeded streams of the tiny config as the engine gave them BEFORE the
# sampling keys moved onto the device (PR 25; taken from commit 2381737
# with the bucketed and the chunked prefill, which agree): one request,
# temperature 0.8 / top_k 40 / top_p 0.95, by seed; and a mixed batch —
# one greedy, two sampled, three lengths.
PINNED = {
    123: [145, 251, 152, 167, 51, 175, 90, 24, 167, 191, 165, 155],
    7: [152, 178, 135, 15, 186, 19, 52, 57, 110, 138, 115, 15],
}
MIXED = [
    ([5, 9, 17, 3, 88, 41, 12, 13, 14, 15, 16], None, 7,
     [202, 150, 114, 117, 243, 232, 194]),
    ([44, 55, 66], 11, 12,
     [48, 97, 64, 165, 2, 172, 253, 42, 172, 0, 22, 32]),
    ([7, 8, 9, 10, 11], 99, 9,
     [206, 100, 29, 158, 23, 41, 12, 34, 222]),
]


def _pinned_engine(params, chunk, slots=4):
    return LLMEngine(CFG, params, slots=slots, max_seq=96,
                     prefill_chunk_tokens=chunk)


def _sampling(seed, n):
    """Greedy for ``seed=None``, else the pinned streams' parameters."""
    if seed is None:
        return SamplingParams(max_tokens=n)
    return SamplingParams(max_tokens=n, temperature=0.8, top_k=40,
                          top_p=0.95, seed=seed)


def _run_all(eng, requests):
    rids = [eng.add_request(list(p), sp, admit=False) for p, sp in requests]
    outs = {}
    while eng.has_unfinished():
        for out in eng.step():
            outs[out.request_id] = out
    return [outs[r].token_ids for r in rids]


@pytest.mark.parametrize("chunk", [64, 8])
@pytest.mark.parametrize("seed", sorted(PINNED))
def test_seeded_stream_is_the_pinned_one(params, seed, chunk):
    got = _pinned_engine(params, chunk).generate([[10, 20, 30]],
                                                 _sampling(seed, 12))
    assert got[0].token_ids == PINNED[seed]


@pytest.mark.parametrize("chunk", [64, 8])
def test_mixed_batch_streams_are_the_pinned_ones(params, chunk):
    got = _run_all(_pinned_engine(params, chunk),
                   [(p, _sampling(seed, n)) for p, seed, n, _ in MIXED])
    assert got == [want for *_, want in MIXED]


@pytest.mark.parametrize("which", range(len(MIXED)))
def test_stream_depends_on_neither_slot_nor_neighbours(params, which):
    """Alone in a one-slot engine, and last into a full batch of four
    behind three sampled neighbours (so in another slot): the same ids
    as in the mixed batch."""
    prompt, seed, n, want = MIXED[which]
    alone = _pinned_engine(params, 8, slots=1)
    assert _run_all(alone, [(prompt, _sampling(seed, n))]) == [want]
    neighbours = [([60 + i, 61, 62], _sampling(1000 + i, 16))
                  for i in range(3)]
    full = _run_all(_pinned_engine(params, 8),
                    neighbours + [(prompt, _sampling(seed, n))])
    assert full[-1] == want


# The sampler does what the active rows ask (PR 35): one program, three
# amounts of work.  The reference is the sampler as it stood before —
# three sorts of the vocabulary on every step, whatever was asked.
VOCAB = 2048


def _three_sorts(logits, keys, active, temps, top_ks, top_ps):
    """``LLMEngine._sample_batch`` before PR 35, and the mask it drew
    from: ranks by a double ``argsort`` (stable: ties by index)."""
    jnp = jax.numpy
    vocab = logits.shape[-1]
    split = jax.vmap(jax.random.split)(keys)
    next_keys = jnp.where(active[:, None], split[:, 0], keys)
    greedy = jnp.argmax(logits, axis=-1)
    scaled = logits / jnp.maximum(temps[:, None], 1e-6)
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    k_idx = jnp.clip(top_ks - 1, 0, vocab - 1)
    kth = jnp.take_along_axis(sorted_desc, k_idx[:, None], axis=-1)
    keep_k = (top_ks[:, None] <= 0) | (scaled >= kth)
    probs_sorted = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs_sorted, axis=-1)
    cutoff_rank = jnp.sum(cum < top_ps[:, None], axis=-1)
    ranks = jnp.argsort(jnp.argsort(-scaled, axis=-1), axis=-1)
    keep_p = ranks <= cutoff_rank[:, None]
    masked = jnp.where(keep_k & keep_p, scaled, -jnp.inf)
    sampled = jax.vmap(jax.random.categorical)(split[:, 1], masked)
    tokens = jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)
    return tokens, next_keys, keep_k & keep_p, scaled


def _tied_logits(seed, rows, coarse=False):
    """Logits as the model's head gives them: bf16 values cast to
    float32, so equal values are the rule; ``coarse`` leaves some 60
    distinct values, and every cutoff falls inside a group of ties."""
    x = 2.0 * jax.random.normal(jax.random.PRNGKey(seed), (rows, VOCAB))
    if coarse:
        x = jax.numpy.round(4.0 * x) / 4.0
    return x.astype(jax.numpy.bfloat16).astype(jax.numpy.float32)


# (temperature, top_k, top_p, active) a row; the work the rows ask for
T, F = True, False
SAMPLER_CASES = {
    "all-greedy": ([(0.0, 0, 1.0, T)] * 4 + [(1.0, 0, 1.0, F),
                                             (0.8, 5, 0.9, F)], 0),
    "plain": ([(1.0, 0, 1.0, T), (0.7, 0, 1.0, T), (0.0, 0, 1.0, T),
               (1.3, 0, 1.0, T), (1.0, 0, 1.0, F), (0.7, 0, 1.0, T)], 1),
    "top-p": ([(1.0, 0, 0.9, T), (0.7, 0, 0.5, T), (1.0, 0, 0.95, T),
               (0.7, 0, 0.3, T), (1.5, 0, 0.99, T), (0.7, 0, 0.0, T)], 2),
    "top-k": ([(1.0, 1, 1.0, T), (0.7, 5, 1.0, T), (1.0, 40, 1.0, T),
               (0.7, 300, 1.0, T), (1.5, 2, 1.0, T),
               (1.0, VOCAB + 5, 1.0, T)], 2),
    "both": ([(0.8, 40, 0.95, T), (0.7, 5, 0.5, T), (1.0, 300, 0.9, T),
              (1.0, 2, 0.99, T), (1.5, 40, 0.3, T), (0.8, 40, 0.95, F)], 2),
    "mixed": ([(0.0, 0, 1.0, T), (0.7, 0, 0.9, T), (0.0, 0, 1.0, T),
               (1.0, 0, 1.0, T), (0.8, 40, 0.95, T), (0.0, 7, 0.5, F)], 2),
    "greedy-rows-filter-nothing": ([(0.0, 40, 0.5, T), (1.0, 0, 1.0, T),
                                    (0.0, 1, 0.9, T)], 1),
    "inactive-filter": ([(1.0, 0, 1.0, T), (0.7, 0, 1.0, T),
                         (5.0, 1, 1.0, F), (0.0, 0, 1.0, T)], 1),
    "one-greedy": ([(0.0, 0, 1.0, T)], 0),
    "one-plain": ([(1.0, 0, 1.0, T)], 1),
    "one-filtered": ([(0.8, 40, 0.95, T)], 2),
    "top-k-1": ([(0.7, 1, 1.0, T), (1.0, 1, 0.9, T), (3.0, 1, 1.0, T)], 2),
    "straddle": ([(1.0, 0, 0.9, T), (0.7, 0, 0.5, T), (1.0, 0, 0.95, T),
                  (1.3, 0, 0.6, T)], 2),
}


@pytest.fixture(scope="module")
def sampler(params):
    return LLMEngine(CFG, params, slots=2, max_seq=32)


@pytest.mark.parametrize("case", sorted(SAMPLER_CASES))
def test_sampler_draws_what_three_sorts_drew(sampler, case):
    """Tokens of the active rows, every row's next key and the set a
    filtering row draws from (tie by tie) are the old formula's; the
    host's rule names the work, and where the work shows it was done."""
    jnp = jax.numpy
    rows, work = SAMPLER_CASES[case]
    temps, top_ks, top_ps, active = (np.asarray(c) for c in zip(*rows))
    seed = sorted(SAMPLER_CASES).index(case)
    logits = _tied_logits(seed, len(rows), coarse=case == "straddle")
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(len(rows)) + 100 * seed)
    args = (logits, keys, jnp.asarray(active),
            jnp.asarray(temps, jnp.float32), jnp.asarray(top_ks, jnp.int32),
            jnp.asarray(top_ps, jnp.float32))
    want, want_keys, want_kept, scaled = _three_sorts(*args)
    got, got_keys, _ = sampler._sample_jit(*args)
    assert engine_mod.sampler_work(r[:3] for r in rows if r[3]) == work
    np.testing.assert_array_equal(np.asarray(got)[active],
                                  np.asarray(want)[active])
    np.testing.assert_array_equal(np.asarray(got_keys),
                                  np.asarray(want_keys))
    # the kept set, on the rows that filter (a row with top_p == 1 and
    # no top-k is not filtered at all: the next test)
    filters = (top_ks > 0) | (top_ps < 1.0)
    kept = np.asarray(sampler._kept(scaled, args[4], args[5]))
    np.testing.assert_array_equal(kept[filters],
                                  np.asarray(want_kept)[filters])
    assert kept[~filters].all()
    greedy = np.asarray(jnp.argmax(logits, axis=-1))
    if case == "all-greedy":
        # the inactive rows sample and filter, and decide nothing: no
        # draw was made for them
        assert (np.asarray(got) == greedy).all()
    if case == "inactive-filter":
        # a sort would have left the inactive row its arg-max alone
        # (top_k 1): the draw was plain
        assert np.asarray(want)[2] == greedy[2] != np.asarray(got)[2]
    if case == "top-k-1":
        assert (np.asarray(got) == greedy).all()
    if case == "straddle":
        # equal logits on both sides of the cutoff, in every row: the
        # first by index are in, a bare ``scaled >= v_cut`` keeps all
        lowest = np.where(kept, np.asarray(scaled), np.inf).min(
            axis=-1, keepdims=True)
        tied_out = (np.asarray(scaled) == lowest) & ~kept
        assert tied_out.any(axis=-1).all()
        for row, out in zip(np.asarray(scaled) == lowest, tied_out):
            assert np.flatnonzero(out).min() > np.flatnonzero(
                row & ~out).max()


def test_top_p_one_is_no_filter(sampler):
    """The one stream the old formula gave another answer to: its
    float32 running sum reaches 1.0 at the first column here (the tail's
    whole mass, 2,047 × e^-40, is far under float32's resolution), so
    ``cum < 1.0`` counted no column and top_p == 1 cut the tail off.
    ``SamplingParams`` documents 1 as disabled: nothing is cut now, alone
    (a plain draw) or beside a row that filters (the sort's mask)."""
    jnp = jax.numpy
    logits = jnp.full((2, VOCAB), -40.0).at[:, 3].set(0.0)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(2))
    temps = jnp.ones((2,), jnp.float32)
    top_ks = jnp.zeros((2,), jnp.int32)
    top_ps = jnp.asarray([1.0, 0.9], jnp.float32)
    *_, old_kept, scaled = _three_sorts(
        logits, keys, jnp.ones((2,), bool), temps, top_ks, top_ps)
    assert np.asarray(old_kept).sum(axis=-1).tolist() == [1, 1]
    kept = np.asarray(sampler._kept(scaled, top_ks, top_ps))
    assert kept.sum(axis=-1).tolist() == [VOCAB, 1]
    assert engine_mod.sampler_work([(1.0, 0, 1.0)]) == 1


@pytest.mark.parametrize("asked, plain, sorted_steps", [
    (dict(), False, False),
    (dict(temperature=1.0, seed=3), True, False),
    (dict(temperature=0.7, top_p=0.9, seed=3), False, True),
    (dict(temperature=0.7, top_k=40, seed=3), False, True),
], ids=["greedy", "plain", "top-p", "top-k"])
def test_sampler_counters_count_what_the_rows_ask(params, asked, plain,
                                                  sorted_steps):
    eng = _pinned_engine(params, 8)
    eng.generate([[10, 20, 30], [7, 8, 9, 10]],
                 SamplingParams(max_tokens=6, **asked))
    steps = eng.stats["decode_steps"]
    assert steps >= 5
    assert eng.stats["sample_plain_steps"] == steps * plain
    assert eng.stats["sample_sorted_steps"] == steps * sorted_steps


def test_sampler_counters_follow_the_rows_of_each_step(params):
    """A greedy stream of 12 beside a top-p stream of 4 and a plain one
    of 8, admitted a step apart: the sort is counted while the top-p
    row decodes (3 steps), the plain draw on the plain row's 7 steps
    less the 2 it shares with the sort, neither once both have left."""
    eng = _pinned_engine(params, 8)
    _run_all(eng, [
        ([10, 20, 30], SamplingParams(max_tokens=12)),
        ([7, 8, 9], SamplingParams(max_tokens=4, temperature=0.7,
                                   top_p=0.9, seed=1)),
        ([4, 5, 6], SamplingParams(max_tokens=8, temperature=1.0, seed=2))])
    assert eng.stats["decode_steps"] == 11
    assert eng.stats["sample_sorted_steps"] == 3
    assert eng.stats["sample_plain_steps"] == 5


# Six requests through three slots as the UNPIPELINED engine answered
# them (commit b1d9bd2, bucketed and chunked prefill agree): A, C and F
# end at a stop token, at different steps, B, D and E at ``max_tokens``;
# D, E and F arrive later and wait for the slots A and C leave.
# (request, prompt, seed, max_tokens, stop ids, added before step,
#  token ids, finish reason)
STAGGERED = [
    ("A", [5, 9, 17, 3, 88, 41, 12, 13, 14, 15, 16], None, 7, (117,), 0,
     [202, 150, 114], "stop"),
    ("B", [44, 55, 66], 11, 12, (), 0,
     [48, 97, 64, 165, 2, 172, 253, 42, 172, 0, 22, 32], "length"),
    ("C", [7, 8, 9, 10, 11], 99, 9, (23,), 0, [206, 100, 29, 158], "stop"),
    ("D", [60, 61, 62], 1000, 6, (), 2,
     [111, 37, 57, 80, 107, 229], "length"),
    ("E", [1, 2, 3], None, 5, (), 4, [21, 29, 21, 29, 21], "length"),
    ("F", [10, 20, 30], 123, 12, (167,), 6, [145, 251, 152], "stop"),
]
_STAGGERED_RUNS = {}


def _run_staggered(params, chunk):
    """The staggered batch, once a chunk width: the outputs by request,
    the engine, and what the spy on ``_land`` saw — for every row the
    engine dropped, who held the row's slot when it was."""
    if chunk in _STAGGERED_RUNS:
        return _STAGGERED_RUNS[chunk]
    eng = _pinned_engine(params, chunk, slots=3)
    dropped, land = [], eng._land

    def spy(flight):
        holders = {seq.slot: seq for seq in
                   list(eng._active.values()) + eng._prefilling}
        for slot, seq in flight[1]:
            if seq.slot != slot:
                dropped.append((seq.request_id, holders.get(slot)))
        return land(flight)

    eng._land = spy
    pending, outs, step = list(STAGGERED), {}, 0
    while pending or eng.has_unfinished():
        while pending and pending[0][5] <= step:
            rid, prompt, seed, n, stop, *_ = pending.pop(0)
            eng.add_request(list(prompt), dataclasses.replace(
                _sampling(seed, n), stop_token_ids=stop),
                request_id=rid, admit=False)
        for out in eng.step():
            outs[out.request_id] = out
        step += 1
    _STAGGERED_RUNS[chunk] = outs, eng, dropped
    return _STAGGERED_RUNS[chunk]


@pytest.mark.parametrize("chunk", [64, 8])
@pytest.mark.parametrize("rid", [r[0] for r in STAGGERED])
def test_pipelined_batch_answers_as_the_unpipelined_engine_did(
        params, rid, chunk):
    """Joins, finishes by ``max_tokens`` and by a stop token at
    different steps: every request's ids and finish reason are the
    ones recorded from the engine that read each step before it
    dispatched the next."""
    outs, _, _ = _run_staggered(params, chunk)
    (want,) = [r for r in STAGGERED if r[0] == rid]
    assert outs[rid].token_ids == want[6]
    assert outs[rid].finish_reason == want[7]


@pytest.mark.parametrize("chunk", [64, 8])
def test_a_stop_costs_one_dropped_row_and_it_reaches_nobody(params, chunk):
    """A stop token is read one step late: the step in flight computed
    a row for the sequence that ended.  The row is counted as decoded
    (``decode_slots``) and as nothing else, and where the freed slot
    already holds a new prompt when the stale row is read, the new
    sequence's stream is untouched (its ids are the recorded ones)."""
    outs, eng, dropped = _run_staggered(params, chunk)
    assert sorted(rid for rid, _ in dropped) == ["A", "C", "F"]
    assert eng.stats["tokens_generated"] == 30       # as unpipelined
    assert eng.stats["decode_slots"] == 30 + len(dropped)
    assert sum(len(o.token_ids) + (o.finish_reason == "stop") - 1
               for o in outs.values()) == 30
    # a waiting prompt took A's slot in the iteration after the stop
    # was read: its prefill and the stale row's read crossed
    readmitted = [seq.request_id for _, seq in dropped if seq]
    assert readmitted, dropped
    for rid in readmitted:
        (want,) = [r for r in STAGGERED if r[0] == rid]
        assert outs[rid].token_ids == want[6]
    assert eng._flight is None and not eng._active


def _watch_programs(eng):
    """Every step program ``eng`` dispatches from now on, by name — a
    mixed step with no real token "empty" — in a list."""
    seen = []

    def watch(name, program):
        def watched(*args):
            seen.append("empty" if name == "mixed" and not int(args[-1])
                        else name)
            return program(*args)
        return watched

    for name, attr in (("chunk", "_prefill_chunk_jit"),
                       ("decode", "_decode_jit"),
                       ("mixed", "_mixed_step_jit")):
        setattr(eng, attr, watch(name, getattr(eng, attr)))
    return seen


LONG = list(range(40, 59))                   # 19 tokens: chunks of 8, 8, 3


def test_a_chunk_rides_the_decode_step_it_shares(params):
    """Prompts that arrive while rows decode (PR 39): an iteration
    dispatches ONE step program, the mixed one — the chunk's rows behind
    the decode rows — where it dispatched the chunk program and then the
    decode program.  The counters as documented: a mixed step is a
    decode step (``decode_steps``, ``decode_slots``,
    ``decode_ahead_steps``) and a chunk (``chunks``, ``chunk_tokens``), and
    ``chunks_fused`` counts it; reads are one a decode step and one a
    prompt's end, as before; the streams are those of each request
    alone."""
    want = [_pinned_engine(params, 8, slots=1).generate(
        [p], _sampling(seed, n))[0].token_ids
        for p, seed, n in (([10, 20, 30], 11, 14), (LONG, 99, 5),
                           ([7, 8, 9], None, 4))]
    eng = _pinned_engine(params, 8, slots=3)
    seen = _watch_programs(eng)
    outs, per_step = {}, []

    def step():
        del seen[:]
        for out in eng.step():
            outs[out.request_id] = out
        per_step.append(list(seen))

    eng.add_request([10, 20, 30], _sampling(11, 14), request_id="a",
                    admit=False)
    step()
    # alone: the chunk program — before it, once, the mixed one, empty,
    # so that it is compiled — and the prompt's end joins a decode step
    assert per_step == [["empty", "chunk", "decode"]]
    step()
    assert per_step[-1] == ["decode"] and eng.stats["chunks_fused"] == 0
    eng.add_request(LONG, _sampling(99, 5), request_id="b", admit=False)
    eng.add_request([7, 8, 9], _sampling(None, 4), request_id="c",
                    admit=False)
    before = dict(eng.stats)
    while eng.has_unfinished():
        step()
    # shortest first: c's one chunk, then b's three, each on a step
    assert per_step[2:6] == [["mixed"]] * 4
    assert all(programs in (["decode"], []) for programs in per_step[6:])
    stats = eng.stats
    assert stats["chunks_fused"] == 4 == stats["chunks"] - before["chunks"]
    assert stats["chunk_tokens"] == 3 + 19 + 3
    assert stats["decode_steps"] == sum(
        programs[-1:] in (["decode"], ["mixed"]) for programs in per_step)
    assert stats["decode_ahead_steps"] == stats["decode_steps"] - 1
    assert stats["d2h_syncs"] == stats["decode_steps"] + 3
    assert stats["decode_slots"] == stats["tokens_generated"] \
        == 13 + 4 + 3
    assert stats["steps"] == sum(bool(programs) for programs in per_step)
    assert [outs[rid].token_ids for rid in "abc"] == want
    assert "empty" not in sum(per_step[1:], [])


def test_the_empty_mixed_step_is_the_compilation_of_the_real_ones(params):
    """The mixed program that the first lone chunk runs empty is the
    one every later mixed step finds compiled: one entry in its cache,
    whatever rode (a server must not compile under its first prompt
    that meets decoding rows — the benchmark counts it as a
    compilation inside the window)."""
    eng = _pinned_engine(params, 8, slots=3)
    eng.add_request([10, 20, 30], _sampling(11, 14), admit=False)
    eng.step()
    assert eng._mixed_step_jit._cache_size() == 1
    assert eng.stats["chunks_fused"] == 0
    eng.add_request(LONG, _sampling(99, 5), admit=False)
    eng.add_request([7, 8, 9], _sampling(None, 4), admit=False)
    while eng.has_unfinished():
        eng.step()
    assert eng.stats["chunks_fused"] == 4
    assert eng._mixed_step_jit._cache_size() == 1
    assert eng._prefill_chunk_jit._cache_size() == 1
    assert eng._decode_jit._cache_size() == 1


@pytest.mark.parametrize("rows, rides", [(10, True), (9, False)])
def test_a_chunk_too_wide_to_ride_runs_alone(params, monkeypatch, rows,
                                             rides):
    """``RIDE_ROWS``: where slots + chunk pass it the iteration keeps
    the two programs, the chunk and then the decode step, and the mixed
    one is never run — not even empty; at it the chunk rides.  The
    streams are the same either way."""
    monkeypatch.setattr(engine_mod, "RIDE_ROWS", rows)
    eng = _pinned_engine(params, 8, slots=2)
    seen = _watch_programs(eng)
    eng.add_request([10, 20, 30], _sampling(11, 14), request_id="a",
                    admit=False)
    eng.step()
    assert seen == (["empty"] if rides else []) + ["chunk", "decode"]
    eng.add_request(LONG, _sampling(99, 5), request_id="b", admit=False)
    outs, order = {}, []
    while eng.has_unfinished():
        del seen[:]
        for out in eng.step():
            outs[out.request_id] = out
        order.append(list(seen))
    if rides:
        assert order[:3] == [["mixed"]] * 3
        assert eng.stats["chunks_fused"] == 3
    else:
        assert order[:3] == [["chunk", "decode"]] * 3
        assert eng.stats["chunks_fused"] == 0
        assert "mixed" not in sum(order, [])
    assert eng.stats["chunks"] == 4
    want = [_pinned_engine(params, 8, slots=1).generate(
        [p], _sampling(seed, n))[0].token_ids
        for p, seed, n in (([10, 20, 30], 11, 14), (LONG, 99, 5))]
    assert [outs[rid].token_ids for rid in "ab"] == want


class _NoEos:
    """Token ids through, no end-of-sequence: a stop is the asked one."""

    def encode(self, text):
        return [ord(c) % CFG.vocab_size for c in text]

    def decode(self, ids):
        return " ".join(str(i) for i in ids)


def _wide_engine(name):
    """Three slots under a 256-token chunk: 259 rows pass ``RIDE_ROWS``,
    so a chunk runs alone and the decode step behind it."""
    eng = LLMEngine(llama.CONFIGS[name], slots=3, max_seq=32,
                    prefill_chunk_tokens=256, tokenizer=_NoEos())
    assert not eng._chunk_rides
    return eng


def _run_wide(eng, requests, overlap):
    """``requests`` (rid, prompt, sampling, session) through ``eng``:
    one at a time, or — ``overlap`` — the first alone until it decodes
    and the rest at once beside it.  Outputs by request."""
    outs = {}

    def run(until=lambda: not eng.has_unfinished()):
        while not until():
            for out in eng.step():
                outs[out.request_id] = out

    for i, (rid, prompt, sampling, session) in enumerate(requests):
        eng.add_request(list(prompt), sampling, request_id=rid,
                        admit=False, session_id=session)
        if not overlap:
            run()
        elif i == 0:
            run(lambda: eng._flight is not None)
    run()
    return outs


@pytest.mark.parametrize("name", ["tiny", "granite-h-tiny"])
def test_wide_chunk_prompt_ends_beside_decoding_rows_answer_as_alone(name):
    """A prompt that ends in a lone chunk beside decoding rows (PR 48)
    joins step N+1 from its first token ON THE DEVICE, before that
    token is read: greedy and seeded streams, a prompt whose FIRST
    token is a stop token (the row step N+1 computed for it is dropped,
    and the prompt that takes the freed slot starts from an empty
    state), ``max_tokens == 1`` (known on the host: never joins) and —
    dense only, a recurrent state keeps no session — a session's turn
    that ends at ``max_seq - 1`` (likewise) give the tokens and the
    finish reasons they give one at a time."""
    dense = not llama.CONFIGS[name].n_recurrent
    seeded = SamplingParams(max_tokens=8, temperature=1.0, seed=5)
    alone = _run_wide(_wide_engine(name), [
        ("c", [44, 55, 66, 77], SamplingParams(max_tokens=8), None),
        ("d", [9, 8, 7], dataclasses.replace(seeded, seed=11), None)],
        overlap=False)
    stops = {rid: (alone[rid].token_ids[0],) for rid in "cd"}
    requests = [
        ("a", [10, 20, 30], SamplingParams(max_tokens=24), None),
        ("b", [7, 8, 9, 10, 11], seeded, None),
        ("c", [44, 55, 66, 77],
         SamplingParams(max_tokens=8, stop_token_ids=stops["c"]), None),
        ("d", [9, 8, 7], dataclasses.replace(
            seeded, seed=11, stop_token_ids=stops["d"]), None),
        ("e", [5, 9, 17, 3, 88, 41], SamplingParams(max_tokens=1), None),
        ("f", [12, 13], dataclasses.replace(seeded, max_tokens=1), None),
        ("g", list(range(60, 69)), SamplingParams(max_tokens=5), None)]
    if dense:
        # 10 + 3 - 1 positions and a carried token, then 18 more: 31
        requests += [
            ("s1", list(range(100, 110)), SamplingParams(max_tokens=3), "s"),
            ("s2", list(range(120, 138)), SamplingParams(max_tokens=4), "s")]
    want = _run_wide(_wide_engine(name), requests, overlap=False)
    eng = _wide_engine(name)
    joined, join = [], eng._join_decode

    def spy_join(seq, token=None):
        join(seq, token)
        if seq.slot in eng._active and eng._flight is not None:
            joined.append(seq.request_id)   # beside an unread step

    eng._join_decode = spy_join
    got = _run_wide(eng, requests, overlap=True)
    assert set(got) == set(want) == {rid for rid, *_ in requests}
    for rid in want:
        assert got[rid].token_ids == want[rid].token_ids, rid
        assert got[rid].finish_reason == want[rid].finish_reason, rid
    assert [len(got[rid].token_ids) for rid in "abcdefg"] == [
        24, 8, 0, 0, 1, 1, 5]
    assert [got[rid].finish_reason for rid in "cdef"] == [
        "stop", "stop", "length", "length"]
    if dense:
        assert got["s2"].finish_reason == "length"
        assert len(got["s2"].token_ids) == 1
    # every other prompt ended beside a's unread step and joined there;
    # e, f (and s2) were known to end with their first token
    assert set(joined) == set("bcdg") | ({"s1"} if dense else set())
    stats = eng.stats
    assert stats["chunks_fused"] == 0
    assert stats["prompt_ends"] == len(requests)
    assert stats["d2h_syncs"] == stats["decode_steps"] + len(requests)
    # c's and d's rows of the step dispatched before their stop was read
    assert stats["decode_slots"] - stats["tokens_generated"] == 2
    assert stats["tokens_generated"] == sum(
        len(out.token_ids) - 1 for out in got.values()
        if out.finish_reason != "stop")
    assert eng._flight is None and not eng._active
    assert len(set(eng._free_slots)) == 3 - dense    # the session's stays


def test_prompt_longer_than_a_chunk(params):
    engine = LLMEngine(CFG, params, slots=1, max_seq=128)
    prompt = list(np.random.RandomState(0).randint(1, 200, 100))
    out = engine.generate([prompt],
                          SamplingParams(max_tokens=4))[0]
    assert 1 <= len(out.token_ids) <= 4
    assert engine.stats["chunks"] == 2


PRESETS = ("tiny", "moe-tiny", "olmoe-tiny", "axk1-tiny", "cmdaplus-tiny",
           "solar2-tiny", "granite-h-tiny")


@pytest.mark.parametrize("name", PRESETS)
def test_a_default_engine_ingests_in_chunks(name):
    """``LLMEngine(name)`` with no width given: every architecture is
    ingested through the ONE chunk program, 64 tokens at a time (a
    recurrent state, a ring and a latent included), and a prompt longer
    than a chunk and one shorter get the cacheless reference's greedy
    tokens."""
    cfg = llama.CONFIGS[name]
    eng = LLMEngine(name, slots=2, max_seq=128)
    assert eng._chunk_tokens == engine_mod.PREFILL_CHUNK_TOKENS == 64
    rng = np.random.default_rng(45)
    prompts = [rng.integers(1, 250, n).tolist() for n in (90, 20)]
    outs = eng.generate(prompts, SamplingParams(max_tokens=6))
    for prompt, out in zip(prompts, outs):
        assert out.token_ids == _truncate_at_eos(
            _reference_greedy(eng.params, prompt, 6, cfg))
    assert eng.stats["chunks"] == 3              # 64 + 26, then 20
    assert eng.stats["chunk_tokens"] == 90 + 20
    assert eng._prefill_chunk_jit._cache_size() == 1


@pytest.mark.parametrize("width", [None, 0, -8])
def test_a_chunk_width_that_is_no_positive_int_is_refused(params, width):
    with pytest.raises(ValueError, match="prefill_chunk_tokens"):
        LLMEngine(CFG, params, slots=1, max_seq=32,
                  prefill_chunk_tokens=width)


def test_a_chunk_wider_than_a_slot_is_masked_not_refused(params):
    """A slot of 32 positions under the default 64-token chunk: the
    chunk program's masks drop the rows behind the prompt, whatever the
    slab's length — no second rule for a small engine."""
    eng = LLMEngine(CFG, params, slots=1, max_seq=32)
    prompt = list(range(3, 23))
    out = eng.generate([prompt], SamplingParams(max_tokens=6))[0]
    assert out.token_ids == _truncate_at_eos(
        _reference_greedy(params, prompt, 6))
    assert eng.stats["chunks"] == 1


@pytest.mark.slow
def test_serve_llm_deployment(shutdown_only):
    art.init(num_cpus=2, num_tpus=1)   # the replica leases a chip
    from ant_ray_tpu import serve
    from ant_ray_tpu.llm.serve_llm import build_llm_deployment

    app = build_llm_deployment("tiny", slots=2, max_seq=64)
    handle = serve.run(app)
    reply = art.get(handle.remote({"prompt": "hi", "max_tokens": 4}),
                    timeout=180)
    assert reply["object"] == "text_completion"
    assert len(reply["choices"]) == 1
    assert reply["choices"][0]["finish_reason"] in ("stop", "length")
    serve.shutdown()


@pytest.mark.slow
def test_batch_inference(shutdown_only):
    art.init(num_cpus=2)
    from ant_ray_tpu import data
    from ant_ray_tpu.llm.batch import build_llm_processor

    ds = data.from_items(
        [{"prompt": f"item {i}"} for i in range(6)], parallelism=3)
    processor = build_llm_processor(
        "tiny", concurrency=2, slots=2, max_seq=64,
        sampling=SamplingParams(max_tokens=4))
    out = processor(ds).take_all()
    assert len(out) == 6
    assert all("generated_text" in row for row in out)


@pytest.mark.slow
def test_llm_sse_token_streaming(shutdown_only):
    """End-to-end token streaming: the SSE response yields its first
    token chunk before generation finishes (ref: serve streaming path +
    vllm streaming outputs)."""
    import json
    import urllib.request

    art.init(num_cpus=2, num_tpus=1)   # the replica leases a chip
    from ant_ray_tpu import serve
    from ant_ray_tpu.llm.serve_llm import build_llm_deployment

    app = build_llm_deployment("tiny", slots=2, max_seq=64)
    serve.run(app, port=0)
    port = serve.run.last_http_port
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/completions",
        data=json.dumps({"prompt": "hello", "max_tokens": 6,
                         "stream": True}).encode(),
        headers={"Content-Type": "application/json"})
    chunks = []
    with urllib.request.urlopen(req, timeout=180) as resp:
        assert resp.headers["Content-Type"].startswith("text/event-stream")
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data: "):
                continue
            payload = line[len("data: "):]
            if payload == "[DONE]":
                break
            chunks.append(json.loads(payload))
    # Token chunks then a final finish chunk.
    assert chunks, "no SSE chunks received"
    assert chunks[-1]["done"] is True
    token_chunks = [c for c in chunks if not c["done"]]
    assert 1 <= len(token_chunks) <= 6
    assert all("text" in c["choices"][0] for c in token_chunks)
    serve.shutdown()
