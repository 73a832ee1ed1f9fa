"""The engine loop accounts for its own time (llm/engine.py
``_PhaseRecorder``): phase seconds that tile the loop, step and sync
counters, ``engine:<phase>`` events in the jax profiler's trace, one
``llm:engine`` stage span per request, and the proxy's ``http:`` span
for a stream that succeeds — what ``chipbench/layer_metrics`` reads.

The counter readings asserted here are the DOCUMENTED ones: a PR that
changes how often the engine synchronises with the device edits them
knowingly."""

import functools
import json
import logging
import os
import time
import urllib.request

import numpy as np
import pytest

import jax

import ant_ray_tpu as art
from ant_ray_tpu.llm import LLMEngine, SamplingParams
from ant_ray_tpu.llm.engine import PHASES, RIDE_ROWS, STALL_S, EngineLoop
from ant_ray_tpu.llm.kv_offload import LocalKvStore
from ant_ray_tpu.models import llama
from ant_ray_tpu.observability import tracing_plane

CFG = llama.CONFIGS["tiny"]
PROMPTS = ([5, 9, 17, 3, 88, 41, 12, 13, 14, 15, 16],   # two chunks of 8
           [44, 55, 66],
           [7, 8, 9, 10, 11])


class _NoEos:
    """Token ids through, no end-of-sequence: lengths are the asked."""

    def encode(self, text):
        return [ord(c) % CFG.vocab_size for c in text]

    def decode(self, ids):
        return " ".join(str(i) for i in ids)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.PRNGKey(7))


def _engine(params, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("max_seq", 96)
    kw.setdefault("prefill_chunk_tokens", 8)
    kw.setdefault("tokenizer", _NoEos())
    return LLMEngine(CFG, params, **kw)


def _spans(trace_id, name=None):
    return [s for s in tracing_plane.recorder().snapshot()
            if s["trace_id"] == trace_id
            and (name is None or s["name"] == name)]


def test_counters_are_deterministic_for_a_fixed_batch(params):
    """The reading, documented: every decode step reads its tokens
    (1) — the sampling keys stay on the device (PR 25) — and every
    prompt's end reads its first token (1).  Every decode step but the
    batch's first is dispatched with the one before it unread (PR 31):
    a prompt's end does not drain the pipeline."""
    readings = []
    for _ in range(2):
        eng = _engine(params)
        outs = eng.generate(list(PROMPTS), SamplingParams(max_tokens=6))
        assert [len(o.token_ids) for o in outs] == [6, 6, 6]
        readings.append({k: v for k, v in eng.stats.items()
                         if isinstance(v, int)})
    first = readings[0]
    assert first == readings[1]
    assert first["chunks"] == 4 and first["chunk_tokens"] == 19
    # the first token of a prompt comes from its last chunk
    assert first["decode_slots"] == first["tokens_generated"] == 3 * 5
    assert first["d2h_syncs"] == first["decode_steps"] + len(PROMPTS)
    assert first["decode_ahead_steps"] == first["decode_steps"] - 1
    assert 0 < first["decode_steps"] <= first["steps"]
    assert first["steps"] <= first["decode_steps"] + first["chunks"]


def test_blocked_time_is_counted_once_and_by_phase(params):
    store = LocalKvStore()
    eng = _engine(params, slots=2, kv_offload_store=store)
    eng.add_request(list(PROMPTS[1]), SamplingParams(max_tokens=4),
                    admit=False, session_id="s")
    while eng.has_unfinished():
        eng.step()
    before = eng.stats["d2h_syncs"]
    assert eng.evict_session("s")           # slab k and v; the length
    assert eng.stats["d2h_syncs"] == before + 2   # is the host's (PR 31)
    stats = eng.stats
    by_phase = sum(stats[f"block_{p}_s"] for p in PHASES)
    assert 0 < by_phase <= stats["block_s"]  # the eviction ran in no phase
    for phase in PHASES:
        assert stats[f"block_{phase}_s"] <= stats[f"phase_{phase}_s"] + 1e-9
    # a decode step blocks for its tokens, a prompt's end for its first
    # token; sampling only dispatches
    assert stats["block_fetch_s"] > 0 and stats["block_chunk_s"] > 0
    assert stats["block_sample_s"] == 0


def test_full_batch_decode_reads_once_a_step_and_splits_no_key_eagerly(
        params, monkeypatch):
    """N decode steps of a full batch, half of it sampled: ``d2h_syncs``
    grows by exactly N, all of it through ``_PhaseRecorder.to_host`` in
    ``fetch``, and no eager ``jax.random.split`` runs (the keys advance
    inside the jitted sampler, traced long before)."""
    eng = _engine(params)
    for i in range(eng.slots):
        eng.add_request([3 + i, 9, 17 + i], SamplingParams(
            max_tokens=40, temperature=0.8 * (i % 2), seed=i), admit=False)
    while len(eng._active) < eng.slots:
        eng.step()
    eng.step()                               # one step with the batch full
    splits, reads = [], []
    real_split, real_read = jax.random.split, eng._rec.to_host
    monkeypatch.setattr(jax.random, "split",
                        lambda *a, **k: splits.append(1) or real_split(*a, **k))
    monkeypatch.setattr(eng._rec, "to_host",
                        lambda v: reads.append(eng._rec._phase)
                        or real_read(v))
    before = dict(eng.stats)
    for _ in range(8):
        eng.step()
    assert eng.stats["decode_steps"] - before["decode_steps"] == 8
    assert eng.stats["decode_slots"] - before["decode_slots"] == 8 * eng.slots
    assert eng.stats["d2h_syncs"] - before["d2h_syncs"] == 8
    assert eng.stats["decode_ahead_steps"] \
        - before["decode_ahead_steps"] == 8
    assert reads == ["fetch"] * 8 and splits == []


@pytest.mark.parametrize("slots,lengths", [
    (1, [5]),                     # a lone sequence: nothing else to overlap
    (4, [9, 4, 6, 12]),           # a batch whose rows end at different steps
    (2, [3, 7, 5, 4, 6]),         # more requests than slots: rows rejoin
])
def test_a_step_is_dispatched_before_the_step_before_it_is_read(
        params, slots, lengths):
    """The order of an iteration is dispatch N+1, read N, emit N, one
    deep: at most one step is unread at a dispatch, every step is read
    exactly once, ``decode_ahead_steps`` counts the dispatches that
    found one unread, and the phases keep their order."""
    eng = _engine(params, slots=slots)
    log, phases, flights = [], [], []
    dispatch, land, enter = eng._dispatch_decode, eng._land, eng._rec.enter

    def spy_dispatch(ahead, chunk=None):
        flight, chunk_logits = dispatch(ahead, chunk)
        flights.append(flight)
        log.append(("D", len(flights) - 1, ahead))
        return flight, chunk_logits

    def spy_land(flight):
        (key,) = [i for i, f in enumerate(flights) if f is flight]
        log.append(("L", key, None))
        return land(flight)

    eng._dispatch_decode, eng._land = spy_dispatch, spy_land
    eng._rec.enter = lambda name: phases.append(name) or enter(name)
    for i, n in enumerate(lengths):
        eng.add_request([3 + i, 9, 17 + i], SamplingParams(max_tokens=n),
                        admit=False)
    outs, per_step = [], []
    while eng.has_unfinished():
        del phases[:]
        outs.extend(eng.step())
        per_step.append(list(phases))
    assert sorted(len(o.token_ids) for o in outs) == sorted(lengths)
    order = {key: i for i, (kind, key, _) in enumerate(log) if kind == "D"}
    landed = [key for kind, key, _ in log if kind == "L"]
    assert sorted(landed) == sorted(order) and len(set(landed)) == len(landed)
    dispatched = [key for kind, key, _ in log if kind == "D"]
    assert landed == dispatched              # read in dispatch order
    ahead = 0
    for k, key in enumerate(dispatched):
        at = log.index(("L", key, None))
        assert order[key] < at               # read after its dispatch ...
        if k + 1 < len(dispatched):
            nxt = order[dispatched[k + 1]]
            ahead += nxt < at                # ... and mostly after the next
        if k + 2 < len(dispatched):
            assert at < order[dispatched[k + 2]]   # one deep, never two
    stats = eng.stats
    assert ahead == stats["decode_ahead_steps"] == sum(
        1 for kind, _, was_ahead in log if kind == "D" and was_ahead)
    assert 0 < ahead + 1 <= stats["decode_steps"] == len(dispatched)
    if len(lengths) <= slots:
        assert ahead == stats["decode_steps"] - 1   # it never drained
    assert stats["d2h_syncs"] == stats["decode_steps"] + len(lengths)
    assert stats["decode_slots"] == stats["tokens_generated"] \
        == sum(lengths) - len(lengths)       # no stop token: no row dropped
    tail = ["decode", "sample", "fetch", "emit", "housekeeping"]
    assert any(p[-5:] == tail for p in per_step)
    rode = 0
    for p in per_step:                       # from the dispatch on
        seen = p[p.index("decode"):] if "decode" in p else p[-3:]
        if "chunk" in seen:
            # a prompt whose last chunk rode the step (PR 39): its first
            # token is read behind the landing of the step before
            at = seen.index("chunk")
            assert seen[at - 1:at + 2] == ["emit", "chunk", "emit"], p
            del seen[at:at + 2]
            rode += 1
        assert seen == [name for name in tail if name in seen], p
    # every prompt here is one chunk: all but the batch's first rode
    assert rode == stats["chunks_fused"]
    assert (rode > 0) == (len(lengths) > 1)


@pytest.mark.parametrize("name", ["tiny", "granite-h-tiny"])
def test_a_lone_chunks_prompt_end_is_read_behind_the_step_in_flight(name):
    """The order test's twin for a chunk too wide to ride (slots + chunk
    = 260 > ``RIDE_ROWS``; PR 48), dense and recurrent: in the iteration
    where a lone chunk ends a prompt beside a decoding row, the prompt's
    first token is sampled on the device and its row joins step N+1
    from there; step N+1 is dispatched, step N is landed, and only then
    is the first token read — its one read, in ``chunk``."""
    eng = LLMEngine(llama.CONFIGS[name], slots=4, max_seq=96,
                    prefill_chunk_tokens=256, tokenizer=_NoEos())
    assert eng.slots + eng._chunk_tokens > RIDE_ROWS
    assert not eng._chunk_rides
    events, phases = [], []
    dispatch, land = eng._dispatch_decode, eng._land
    to_host, enter = eng._rec.to_host, eng._rec.enter

    def spy_dispatch(ahead, chunk=None):
        assert chunk is None                  # nothing rides
        flight, logits = dispatch(ahead, chunk)
        events.append(("dispatch", len(flight[1]), ahead is not None))
        return flight, logits

    def spy_land(flight):
        events.append(("land", len(flight[1])))
        return land(flight)

    def spy_read(value):
        events.append(("read", eng._rec._phase))
        return to_host(value)

    eng._dispatch_decode, eng._land = spy_dispatch, spy_land
    eng._rec.to_host = spy_read
    eng._rec.enter = lambda phase: phases.append(phase) or enter(phase)
    outs = {}

    def step():
        del events[:], phases[:]
        for out in eng.step():
            outs[out.request_id] = out

    eng.add_request([3, 9, 17], SamplingParams(max_tokens=12),
                    request_id="a", admit=False)
    step()
    # nothing was unread: the first token is read at once, as before,
    # and the row joins the decode step of the same iteration
    assert events == [("read", "chunk"), ("dispatch", 1, False)]
    step()
    assert eng._flight is not None and len(eng._active) == 1
    eng.add_request([7, 8, 9, 10, 11], SamplingParams(max_tokens=6),
                    request_id="b", admit=False)
    ends = eng.stats["prompt_ends"]
    step()
    # b's one chunk ran alone beside a's row and ended b's prompt: step
    # N+1 holds BOTH rows, step N (a's alone) lands before b's first
    # token is read
    assert events == [("dispatch", 2, True), ("land", 1), ("read", "fetch"),
                      ("read", "chunk")]
    assert eng.stats["prompt_ends"] == ends + 1 == 2
    assert phases[phases.index("decode"):] == [
        "decode", "sample", "fetch", "emit", "chunk", "emit",
        "housekeeping"]
    assert eng.stats["chunks_fused"] == 0
    while eng.has_unfinished():
        step()
    assert [len(outs[rid].token_ids) for rid in "ab"] == [12, 6]
    stats = eng.stats
    assert stats["d2h_syncs"] == stats["decode_steps"] + 2
    assert stats["decode_ahead_steps"] == stats["decode_steps"] - 1
    assert stats["decode_slots"] == stats["tokens_generated"] == 11 + 5


def test_decode_ahead_pct_reads_the_counter_and_nothing_without_it(params):
    """``chipbench/layer_metrics/decode_ahead_pct.py`` over a window of
    the engine's own stats; a program without the counter (the parent
    of PR 31) gives None, which leaves the metric out of the line."""
    from chipbench.layer_metrics import d2h_syncs_per_step, decode_ahead_pct

    eng = _engine(params)
    before = dict(eng.stats)
    eng.generate(list(PROMPTS), SamplingParams(max_tokens=6))
    obs = {"traced": {"engine": dict(eng.stats), "engine_before": before}}
    steps = eng.stats["decode_steps"]
    assert decode_ahead_pct.read(obs) == pytest.approx(
        100.0 * (steps - 1) / steps)
    assert d2h_syncs_per_step.read(obs) == pytest.approx(
        (steps + len(PROMPTS)) / steps)
    for side in obs["traced"].values():
        del side["decode_ahead_steps"]
    assert decode_ahead_pct.read(obs) is None
    assert decode_ahead_pct.read({"traced": None}) is None


def _span_by_step(eng, monkeypatch):
    """Drive ``eng`` to the end; per decode step ``(what the DEVICE's
    rule makes of the step's own inputs, what the host added to
    decode_span_positions, the longest active length on the device)``."""
    import numpy as np

    seen = []

    def watch(program):
        """``_decode_jit``, or a real mixed step (``*chunk``)."""
        def watched(params, cache, last, active, *chunk):
            if not chunk or int(chunk[-1]):
                longest = int(np.max(np.where(
                    np.asarray(active), np.asarray(cache["length"]), 0)))
                seen.append((llama.span_positions(longest + 1, eng.max_seq),
                             longest))
            return program(params, cache, last, active, *chunk)
        return watched

    monkeypatch.setattr(eng, "_decode_jit", watch(eng._decode_jit))
    monkeypatch.setattr(eng, "_mixed_step_jit", watch(eng._mixed_step_jit))
    rows = []
    while eng.has_unfinished():
        before = eng.stats["decode_span_positions"]
        eng.step()
        if len(seen) > len(rows):
            rows.append((seen[-1][0],
                         eng.stats["decode_span_positions"] - before,
                         seen[-1][1]))
    return rows


def test_decode_span_counters_follow_the_longest_active_row(
        params, monkeypatch):
    """``decode_span_positions`` / ``decode_slab_positions`` (PR 33), per
    decode step: the positions the step's attention walks — whole blocks
    (16 here) up to the longest ACTIVE row, from the host's own
    ``kv_len`` plus the step still unread, exactly what the device makes
    of its ``cache["length"]`` — and the slab's 96.  The first never
    passes the second, rises block by block with the longest row, and a
    resident session's long slab that sits the steps out does not raise
    it."""
    monkeypatch.setattr(llama, "ATTEND_BLOCK", 16)
    eng = _engine(params, slots=3)
    assert eng.stats["decode_span_positions"] == \
        eng.stats["decode_slab_positions"] == 0
    # a resident session whose slab holds 70 positions, then idle
    eng.add_request(list(range(3, 71)), SamplingParams(max_tokens=3),
                    admit=False, session_id="resident")
    first = _span_by_step(eng, monkeypatch)
    assert {device for device, _, _ in first} == {80}      # 69-71 -> 5 blocks
    assert eng.stats["decode_slab_positions"] == 96 * len(first)
    # rows of 5 and 3 prompt tokens, 40 and 9 more: 16 -> 32 -> 48
    eng.add_request([5, 9, 17, 3, 88], SamplingParams(max_tokens=41),
                    admit=False)
    eng.add_request([44, 55, 66], SamplingParams(max_tokens=10), admit=False)
    then = _span_by_step(eng, monkeypatch)
    assert [host for _, host, _ in then] == [device for device, _, _ in then]
    spans = [device for device, _, _ in then]
    assert spans == sorted(spans) and set(spans) == {16, 32, 48}
    for device, _, longest in then:
        assert longest < device <= longest + 16 <= 96
    stats = eng.stats
    assert stats["decode_span_positions"] == sum(
        host for _, host, _ in first + then)
    assert stats["decode_slab_positions"] == 96 * stats["decode_steps"]
    assert stats["decode_span_positions"] < stats["decode_slab_positions"]


@pytest.mark.parametrize("kernel", [False, True], ids=["walk", "kernel"])
def test_decode_read_counters_count_what_the_dispatched_program_reads(
        params, monkeypatch, kernel):
    """``decode_walk_positions`` / ``decode_read_positions`` (PR 47), per
    decode step and from the host's own ``kv_len``: what a walk bound by
    the longest active row reads of a layer's slabs — slots x the span —
    and what the dispatched program reads: the same where the rows walk
    in XLA (the CPU; ``llama._decode_kernel``), the sum of each ACTIVE
    row's own whole blocks (16 here) where they go through
    ``ops/pallas/decode_attention.py`` — the device's own rule,
    ``decode_attention.blocks_read`` over the step's own inputs — with a
    resident session's idle slab adding nothing."""
    from ant_ray_tpu.ops.pallas import decode_attention

    monkeypatch.setattr(llama, "ATTEND_BLOCK", 16)
    if kernel:
        monkeypatch.setattr(llama, "_decode_kernel", lambda *a: True)
    eng = _engine(params, slots=3)
    assert eng._decode_kernel == kernel
    assert eng.stats["decode_walk_positions"] == \
        eng.stats["decode_read_positions"] == 0
    device = {"walk": 0, "read": 0}

    def watch(program):
        def watched(params, cache, last, active, *chunk):
            if not chunk or int(chunk[-1]):
                active_, length = np.asarray(active), np.asarray(
                    cache["length"])
                blocks = np.asarray(decode_attention.blocks_read(
                    length, active_, 16, eng.max_seq))
                device["walk"] += 3 * 16 * int(blocks.max())
                device["read"] += 16 * int(blocks.sum())
            return program(params, cache, last, active, *chunk)
        return watched

    monkeypatch.setattr(eng, "_decode_jit", watch(eng._decode_jit))
    monkeypatch.setattr(eng, "_mixed_step_jit", watch(eng._mixed_step_jit))
    eng.add_request(list(range(3, 71)), SamplingParams(max_tokens=3),
                    admit=False, session_id="resident")
    while eng.has_unfinished():
        eng.step()
    eng.add_request([5, 9, 17, 3, 88], SamplingParams(max_tokens=41),
                    admit=False)
    eng.add_request([44, 55, 66], SamplingParams(max_tokens=10), admit=False)
    while eng.has_unfinished():
        eng.step()
    stats = eng.stats
    assert stats["decode_walk_positions"] == device["walk"] \
        == 3 * stats["decode_span_positions"] > 0
    assert stats["decode_read_positions"] == (
        device["read"] if kernel else device["walk"])
    assert (stats["decode_read_positions"]
            < stats["decode_walk_positions"]) == kernel


def test_decode_read_pct_reads_the_counters_and_nothing_without_them(params):
    """``chipbench/layer_metrics/decode_read_pct.py`` over a window of
    the engine's own stats: 100 where the rows walk in XLA; a program
    without the counters (the parent of PR 47) gives None, which leaves
    the metric out of the line."""
    from chipbench.layer_metrics import decode_read_pct

    eng = _engine(params)
    before = dict(eng.stats)
    eng.generate(list(PROMPTS), SamplingParams(max_tokens=6))
    obs = {"traced": {"engine": dict(eng.stats), "engine_before": before}}
    assert eng.stats["decode_walk_positions"] == \
        4 * 96 * eng.stats["decode_steps"] > 0
    assert decode_read_pct.read(obs) == 100.0
    obs["traced"]["engine"]["decode_read_positions"] //= 4
    assert decode_read_pct.read(obs) == 25.0
    for side in obs["traced"].values():
        del side["decode_read_positions"]
    assert decode_read_pct.read(obs) is None
    assert decode_read_pct.read({"traced": None}) is None


def test_decode_span_pct_reads_the_counters_and_nothing_without_them(params):
    """``chipbench/layer_metrics/decode_span_pct.py`` over a window of
    the engine's own stats: slabs of 96 are ONE serving block, so every
    step walks all of them; a program without the counters (the parent
    of PR 33) gives None, which leaves the metric out of the line."""
    from chipbench.layer_metrics import decode_span_pct

    eng = _engine(params)
    before = dict(eng.stats)
    eng.generate(list(PROMPTS), SamplingParams(max_tokens=6))
    obs = {"traced": {"engine": dict(eng.stats), "engine_before": before}}
    assert eng.stats["decode_slab_positions"] == \
        96 * eng.stats["decode_steps"] > 0
    assert decode_span_pct.read(obs) == 100.0
    obs["traced"]["engine"]["decode_span_positions"] //= 4
    assert decode_span_pct.read(obs) == 25.0
    for side in obs["traced"].values():
        del side["decode_span_positions"]
    assert decode_span_pct.read(obs) is None
    assert decode_span_pct.read({"traced": None}) is None


def test_window_and_full_walks_are_counted_by_the_devices_rule(monkeypatch):
    """``full_span_positions`` / ``window_span_positions`` (PR 34), per
    decode step AND per chunk of a model with window layers: the
    positions walked on its full layers' slabs and on its window layers'
    rings — whole blocks (16 here) up to the longest live row, capped by
    the ring's 24 rows, times the layers of each kind — from the host's
    own ``kv_len``, exactly what the device makes of the program's own
    inputs; ``decode_rows_past_window`` the active rows whose context
    exceeds the window of 16.  ``decode_span_positions`` counts the FULL
    layers' walk against ``max_seq``, as in every other model."""
    import numpy as np

    from chipbench.layer_metrics import window_walk_pct

    monkeypatch.setattr(llama, "ATTEND_BLOCK", 16)
    cfg = llama.CONFIGS["cmdaplus-tiny"]
    eng = LLMEngine(cfg, slots=3, max_seq=96, prefill_chunk_tokens=8,
                    tokenizer=_NoEos())
    assert eng._ring == 24 and cfg.layer_counts() == (6, 2)
    device = {"full": 0, "window": 0, "past": 0, "span": 0}
    decode_jit, chunk_jit = eng._decode_jit, eng._prefill_chunk_jit

    def walked(longest):
        device["full"] += 2 * llama.span_positions(longest, 96)
        device["window"] += 6 * llama.span_positions(longest, 24)

    def decode(params, cache, last, active):
        lengths = np.where(np.asarray(active), np.asarray(cache["length"]), 0)
        walked(int(lengths.max()) + 1)
        device["span"] += llama.span_positions(int(lengths.max()) + 1, 96)
        device["past"] += int((lengths + 1 > 16).sum())
        return decode_jit(params, cache, last, active)

    def chunk(params, cache, tokens, slot, start, length):
        walked(int(start) + int(length))
        return chunk_jit(params, cache, tokens, slot, start, length)

    def mixed(params, cache, last, active, tokens, slot, start, length):
        if int(length):                       # not the empty first run
            lengths = np.where(np.asarray(active),
                               np.asarray(cache["length"]), 0)
            walked(int(lengths.max()) + 1)
            device["span"] += llama.span_positions(int(lengths.max()) + 1, 96)
            device["past"] += int((lengths + 1 > 16).sum())
            walked(int(start) + int(length))
            device["mixed"] = device.get("mixed", 0) + 1
        return mixed_jit(params, cache, last, active, tokens, slot, start,
                         length)

    mixed_jit = eng._mixed_step_jit
    monkeypatch.setattr(eng, "_decode_jit", decode)
    monkeypatch.setattr(eng, "_prefill_chunk_jit", chunk)
    monkeypatch.setattr(eng, "_mixed_step_jit", mixed)
    before = dict(eng.stats)
    assert before["full_span_positions"] == before[
        "window_span_positions"] == before["decode_rows_past_window"] == 0
    eng.generate([list(range(3, 40)), [5, 9, 17], list(range(7, 20))],
                 SamplingParams(max_tokens=12))
    stats = eng.stats
    assert stats["chunks_fused"] == device["mixed"] > 0
    assert stats["full_span_positions"] == device["full"] > 0
    assert stats["window_span_positions"] == device["window"] > 0
    assert stats["decode_rows_past_window"] == device["past"] > 0
    assert stats["decode_span_positions"] == device["span"]
    assert stats["decode_slab_positions"] == 96 * stats["decode_steps"]
    # the rings stop at 24 rows where the slabs go on: under 100
    obs = {"traced": {"engine": dict(stats), "engine_before": before},
           "config": {"layer_types": ["sliding_attention"] * 3
                      + ["full_attention"], "num_hidden_layers": 4}}
    assert window_walk_pct.read(obs) == pytest.approx(
        100.0 * device["window"] / (device["full"] * 3))
    assert 30 < window_walk_pct.read(obs) < 100
    for side in obs["traced"].values():
        del side["window_span_positions"]
    assert window_walk_pct.read(obs) is None          # the parent's program


@pytest.mark.parametrize("kernel", [False, True], ids=["walk", "kernel"])
def test_window_read_counters_count_what_the_dispatched_program_reads(
        monkeypatch, kernel):
    """``window_walk_positions`` / ``window_read_positions`` (PR 59), per
    decode step of a model with window layers and from the host's own
    ``kv_len``: what a walk bound by the longest active row reads of the
    window layers' rings — window layers x slots x the ring's whole
    blocks (16 here) up to that row, the ring's 32 rows at most — and
    what the dispatched program reads: the same where the rows walk in
    XLA (the CPU), window layers x the sum of each ACTIVE row's own
    whole ring blocks where they go through
    ``ops/pallas/decode_attention.py`` — the device's own rule,
    ``decode_attention.blocks_read`` over the step's own inputs and the
    RING's length — an idle slot adding nothing.  The reader
    (``chipbench/layer_metrics/window_read_pct.py``) gives their ratio,
    and None for a program without the counters: the parent's."""
    from ant_ray_tpu.ops.pallas import decode_attention
    from chipbench.layer_metrics import window_read_pct

    monkeypatch.setattr(llama, "ATTEND_BLOCK", 16)
    if kernel:
        monkeypatch.setattr(llama, "_decode_kernel", lambda *a: True)
    cfg = llama.CONFIGS["cmdaplus-tiny"]
    eng = LLMEngine(cfg, slots=3, max_seq=96, prefill_chunk_tokens=16,
                    tokenizer=_NoEos())
    assert eng._ring == 32 and eng._decode_kernel == kernel
    before = dict(eng.stats)
    assert before["window_walk_positions"] == \
        before["window_read_positions"] == 0
    device = {"walk": 0, "read": 0, "steps": 0}

    def watch(program):
        def watched(params, cache, last, active, *chunk):
            if not chunk or int(chunk[-1]):
                blocks = np.asarray(decode_attention.blocks_read(
                    np.asarray(cache["length"]), np.asarray(active), 16, 32))
                device["walk"] += 6 * 3 * 16 * int(blocks.max())
                device["read"] += 6 * 16 * int(blocks.sum())
                device["steps"] += 1
            return program(params, cache, last, active, *chunk)
        return watched

    monkeypatch.setattr(eng, "_decode_jit", watch(eng._decode_jit))
    monkeypatch.setattr(eng, "_mixed_step_jit", watch(eng._mixed_step_jit))
    eng.generate([list(range(3, 43)), [5, 9, 17], list(range(7, 20))],
                 SamplingParams(max_tokens=12))
    eng.generate([[44, 55, 66]], SamplingParams(max_tokens=20))
    stats = eng.stats
    assert stats["decode_steps"] == device["steps"] > 0
    assert stats["window_walk_positions"] == device["walk"] > 0
    assert stats["window_read_positions"] == (
        device["read"] if kernel else device["walk"])
    # rows of other lengths and idle slots: the kernel reads less
    assert (stats["window_read_positions"]
            < stats["window_walk_positions"]) == kernel
    obs = {"traced": {"engine": dict(stats), "engine_before": before}}
    assert window_read_pct.read(obs) == pytest.approx(
        100.0 * (device["read"] if kernel else device["walk"])
        / device["walk"])
    assert (window_read_pct.read(obs) < 100.0) == kernel
    for side in obs["traced"].values():
        del side["window_read_positions"]
    assert window_read_pct.read(obs) is None          # the parent's program
    assert window_read_pct.read({"traced": None}) is None
    assert window_read_pct.read({}) is None


def test_a_model_without_window_layers_leaves_the_new_counters_at_zero(
        params):
    from chipbench.layer_metrics import window_read_pct

    eng = _engine(params)
    before = dict(eng.stats)
    eng.generate(list(PROMPTS), SamplingParams(max_tokens=6))
    assert eng._ring == 0 and eng.stats["decode_span_positions"] > 0
    assert eng.stats["full_span_positions"] == eng.stats[
        "window_span_positions"] == eng.stats["decode_rows_past_window"] == 0
    assert eng.stats["window_walk_positions"] == eng.stats[
        "window_read_positions"] == 0
    assert window_read_pct.read({"traced": {
        "engine": dict(eng.stats), "engine_before": before}}) is None
    assert [eng.stats[name] for name in RECURRENT_COUNTERS] == [0] * 5


RECURRENT_COUNTERS = ("recurrent_decode_rows", "recurrent_slot_rows",
                      "recurrent_chunk_tokens", "recurrent_chunk_rows",
                      "recurrent_resets")


@pytest.mark.parametrize("name", ["solar2-tiny", "granite-h-tiny",
                                  "olmo-hybrid-tiny"])
def test_recurrent_state_traffic_is_counted_by_the_devices_rule(
        monkeypatch, name):
    """The five ``recurrent_*`` counters (PR 38) of a model with
    recurrent layers — linear or state-space ones (PR 42), six here
    either way: per decode step the rows it decoded and the
    slots its program touched, per chunk its real tokens and its width,
    each times the recurrent layers, and the chunks that began a prompt
    — from the host's own bookkeeping, exactly what the device makes of
    the programs' own inputs (the ``active`` mask, a chunk's ``start``
    and length).  ``decode_span_positions`` counts the softmax layers'
    walk alone: a recurrent layer walks nothing."""
    import numpy as np

    from chipbench.layer_metrics import recurrent_state_live_pct

    monkeypatch.setattr(llama, "ATTEND_BLOCK", 16)
    cfg = llama.CONFIGS[name]
    assert cfg.n_recurrent == 6 and cfg.layer_counts() == (0, 2)
    eng = LLMEngine(cfg, slots=3, max_seq=96, prefill_chunk_tokens=8,
                    tokenizer=_NoEos())
    assert eng._ring == 0
    device = dict.fromkeys(RECURRENT_COUNTERS, 0)
    device["span"] = 0
    decode_jit, chunk_jit = eng._decode_jit, eng._prefill_chunk_jit
    mixed_jit = eng._mixed_step_jit

    def decoded(cache, active):
        active = np.asarray(active)
        device["recurrent_decode_rows"] += 6 * int(active.sum())
        device["recurrent_slot_rows"] += 6 * active.size
        lengths = np.where(active, np.asarray(cache["length"]), 0)
        device["span"] += llama.span_positions(int(lengths.max()) + 1, 96)

    def ingested(tokens, start, length):
        device["recurrent_chunk_tokens"] += 6 * int(length)
        device["recurrent_chunk_rows"] += 6 * len(tokens)
        device["recurrent_resets"] += int(start) == 0

    def decode(params, cache, last, active):
        decoded(cache, active)
        return decode_jit(params, cache, last, active)

    def chunk(params, cache, tokens, slot, start, length):
        ingested(tokens, start, length)
        return chunk_jit(params, cache, tokens, slot, start, length)

    def mixed(params, cache, last, active, tokens, slot, start, length):
        if int(length):                       # not the empty first run
            decoded(cache, active)
            ingested(tokens, start, length)
            device["mixed"] = device.get("mixed", 0) + 1
        return mixed_jit(params, cache, last, active, tokens, slot, start,
                         length)

    monkeypatch.setattr(eng, "_decode_jit", decode)
    monkeypatch.setattr(eng, "_prefill_chunk_jit", chunk)
    monkeypatch.setattr(eng, "_mixed_step_jit", mixed)
    before = dict(eng.stats)
    assert [before[name] for name in RECURRENT_COUNTERS] == [0] * 5
    eng.generate([list(range(3, 40)), [5, 9, 17], list(range(7, 20)),
                  [44, 55]], SamplingParams(max_tokens=12))
    stats = eng.stats
    assert stats["chunks_fused"] == device["mixed"] > 0
    for name in RECURRENT_COUNTERS:
        assert stats[name] == device[name] > 0, name
    assert stats["recurrent_resets"] == 4               # one a prompt
    assert stats["recurrent_chunk_tokens"] == 6 * stats["chunk_tokens"]
    assert stats["recurrent_decode_rows"] == 6 * stats["decode_slots"]
    assert stats["decode_span_positions"] == device["span"]
    assert stats["full_span_positions"] == stats["window_span_positions"] == 0
    obs = {"traced": {"engine": dict(stats), "engine_before": before}}
    assert recurrent_state_live_pct.read(obs) == pytest.approx(
        100.0 * stats["decode_slots"] / (3 * stats["decode_steps"]))
    assert 30 < recurrent_state_live_pct.read(obs) < 100


@pytest.mark.parametrize("name,scopes", [
    ("olmoe-tiny", {"moe", "attn_full"}),
    ("axk1-tiny", {"mla", "moe", "moe_shared"}),
    ("cmdaplus-tiny", {"moe", "moe_shared", "attn_window", "attn_full"}),
    ("solar2-tiny", {"moe", "moe_shared", "attn_full", "attn_linear",
                     "kda_step"}),
    ("granite-h-tiny", {"moe", "moe_shared", "attn_full", "attn_ssm",
                        "ssd_step"})])
def test_routed_models_name_their_counters_and_their_scopes(name, scopes):
    """What a routed model's step programs record, always on: the
    routing counters (``llama.ROUTING_COUNTERS``, PR 27; ``moe_rows_routed``
    and the decode steps' own PR 32, ``moe_tile_rows`` PR 37: 0 where XLA's
    kernel multiplies, as here, ``moe_dead_pairs`` PR 62: the pairs of the
    rows nobody reads, which reach no expert, ``moe_expert_reads`` PR 63:
    the expert matrices the grouped kernel fetched, 0 here too, as
    ``moe_fetches_ahead``, PR 66: those of them the kernel started under
    an earlier group's crossing visit) in ``LLMEngine.stats`` from the start, riding the step's one
    read, and the named scopes a reducer can file operations under —
    ``moe`` around the routed experts, ``moe_shared`` around the shared
    one, ``mla`` around latent attention, ``attn_window`` and
    ``attn_full`` around the two ways a grouped-query layer attends
    (PR 34), ``attn_linear`` around a linear layer's mix and inside it
    ``kda_step`` around the delta rule's step (PR 38; ``kda_chunk``
    around its block form, in the chunk program), ``attn_ssm`` around a
    state-space layer's mix and inside it ``ssd_step`` and ``ssd_chunk``
    likewise (PR 42)."""
    cfg = llama.CONFIGS[name]
    assert llama.ROUTING_COUNTERS == (
        "moe_assignments", "moe_experts_hit", "moe_expert_slots",
        "moe_load_max", "moe_rows_routed", "moe_decode_assignments",
        "moe_decode_experts_hit", "moe_decode_expert_slots",
        "moe_decode_rows_routed", "moe_tile_rows", "moe_dead_pairs",
        "moe_expert_reads", "moe_fetches_ahead")
    eng = LLMEngine(cfg, slots=2, max_seq=64, prefill_chunk_tokens=8,
                    tokenizer=_NoEos())
    assert set(llama.ROUTING_COUNTERS) <= set(eng.stats)
    assert eng.cache["routing"].shape == (13,)
    eng.generate([[5, 9, 17]], SamplingParams(max_tokens=3))
    stats = eng.stats
    assert stats["d2h_syncs"] == stats["decode_steps"] + 1
    held, routed = stats["moe_assignments"], stats["moe_rows_routed"]
    assert (held == routed) == (not cfg.router_width)
    assert stats["moe_tile_rows"] == stats["moe_expert_reads"] == 0
    assert stats["moe_fetches_ahead"] == 0           # XLA's kernel: tile 0
    routed_layers = cfg.n_layers - cfg.n_dense_layers
    # a lone prompt: its chunk ran alone, and before it, once, the
    # mixed program empty (PR 39) — an execution of 2 + 8 rows
    assert stats["chunks"] == 1 and stats["chunks_fused"] == 0
    assert stats["moe_expert_slots"] == cfg.num_experts * routed_layers * (
        stats["decode_steps"] + stats["chunks"] + 1)
    # routed are the LIVE rows' pairs: the prompt's 3 tokens and the one
    # decoding row; the idle slot's, the padding's and the empty run's
    # are dead
    pairs = cfg.experts_per_token * routed_layers
    assert routed == pairs * (3 + stats["decode_slots"])
    assert routed + stats["moe_dead_pairs"] == pairs * (
        2 * stats["decode_steps"] + 8 * stats["chunks"] + (2 + 8))
    # the decode steps' own, apart from the chunks'
    assert stats["moe_decode_expert_slots"] == (
        cfg.num_experts * routed_layers * stats["decode_steps"])
    assert stats["moe_decode_rows_routed"] == pairs * stats["decode_slots"]
    assert 0 < stats["moe_decode_assignments"] < held
    assert 0 < stats["moe_decode_experts_hit"] < stats["moe_experts_hit"]
    lowered = eng._decode_jit.lower(
        eng.params, eng.cache, eng._last, eng._jnp.ones((2,), bool))
    text = lowered.as_text(debug_info=True)
    for scope in ("mla", "moe", "moe_shared", "attn_window", "attn_full",
                  "attn_linear", "kda_step", "attn_ssm", "ssd_step"):
        assert (f'{scope}/' in text) == (scope in scopes), scope
    chunk = eng._prefill_chunk_jit.lower(
        eng.params, eng.cache, eng._jnp.zeros((8,), "int32"), 0, 0, 3)
    block_forms = ("attn_linear/kda_chunk", "attn_ssm/ssd_chunk")
    for scope in block_forms:
        assert (f"{scope}/" in chunk.as_text(debug_info=True)) == (
            scope.split("/")[0] in scopes), scope
    assert "kda_chunk/" not in text and "ssd_chunk/" not in text
    mixed = eng._mixed_step_jit.lower(
        eng.params, eng.cache, eng._last, eng._jnp.ones((2,), bool),
        eng._jnp.zeros((8,), "int32"), 0, 0, 3).as_text(debug_info=True)
    for scope in (*scopes, *block_forms):
        assert (f'{scope}/' in mixed) == (
            scope.split("/")[0] in scopes), scope


def test_a_dense_hybrid_names_the_scalar_forms_scopes():
    """Olmo Hybrid's linear layers (PR 46: ONE decay a head) run the
    scalar forms of ``ops/delta_rule.py`` under scopes of their own,
    ``gdn_step`` and ``gdn_chunk`` inside ``attn_linear`` — never the
    channel forms' ``kda_*`` — beside ``attn_full``; a dense model has
    no routing counters and no ``moe`` scope, and its recurrent
    counters count its linear layers."""
    cfg = llama.CONFIGS["olmo-hybrid-tiny"]
    eng = LLMEngine(cfg, slots=2, max_seq=64, prefill_chunk_tokens=8,
                    tokenizer=_NoEos())
    assert "routing" not in eng.cache
    eng.generate([[5, 9, 17]], SamplingParams(max_tokens=3))
    stats = eng.stats
    assert not any(name in stats for name in llama.ROUTING_COUNTERS)
    assert stats["recurrent_chunk_rows"] == 6 * 8 * stats["chunks"]
    assert stats["recurrent_chunk_tokens"] == 6 * 3
    assert stats["recurrent_slot_rows"] == 6 * 2 * stats["decode_steps"]
    decode = eng._decode_jit.lower(
        eng.params, eng.cache, eng._last,
        eng._jnp.ones((2,), bool)).as_text(debug_info=True)
    chunk = eng._prefill_chunk_jit.lower(
        eng.params, eng.cache, eng._jnp.zeros((8,), "int32"), 0, 0,
        3).as_text(debug_info=True)
    mixed = eng._mixed_step_jit.lower(
        eng.params, eng.cache, eng._last, eng._jnp.ones((2,), bool),
        eng._jnp.zeros((8,), "int32"), 0, 0, 3).as_text(debug_info=True)
    for text, inside, outside in (
            (decode, ("attn_linear/gdn_step/", "attn_full/"),
             ("gdn_chunk/",)),
            (chunk, ("attn_linear/gdn_chunk/", "attn_full/"),
             ("gdn_step/",)),
            (mixed, ("attn_linear/gdn_step/", "attn_linear/gdn_chunk/",
                     "attn_full/"), ())):
        for scope in inside:
            assert scope in text, scope
        for scope in (*outside, "kda_step/", "kda_chunk/", "moe/",
                      "attn_ssm/", "attn_window/"):
            assert scope not in text, scope


def test_phases_tile_the_loop_and_stats_keep_their_keys(params):
    eng = _engine(params)
    keys = set(eng.stats)
    assert {f"phase_{p}_s" for p in PHASES} <= keys
    loop = EngineLoop(eng)
    try:
        loop.submit(list(PROMPTS[1]), SamplingParams(max_tokens=2)).wait(120)
        s0, t0 = dict(eng.stats), time.perf_counter()
        handles = [loop.submit(list(p), SamplingParams(max_tokens=24))
                   for p in PROMPTS]
        for h in handles:
            h.wait(120)
        time.sleep(0.3)                      # an idle stretch counts too
        s1, t1 = dict(eng.stats), time.perf_counter()
    finally:
        loop.shutdown()
    assert set(eng.stats) == keys == set(s1)
    phases = sum(s1[k] - s0[k] for k in keys if k.startswith("phase_"))
    assert phases == pytest.approx(t1 - t0, rel=0.05)
    assert s1["phase_idle_wait_s"] - s0["phase_idle_wait_s"] >= 0.2
    for phase in ("drain", "admit", "chunk", "decode", "sample", "fetch",
                  "emit", "housekeeping"):
        assert s1[f"phase_{phase}_s"] > s0[f"phase_{phase}_s"], phase
    assert s1["steps"] - s0["steps"] >= s1["decode_steps"] - s0["decode_steps"]


def test_one_engine_span_per_sampled_request(params):
    eng = _engine(params)
    loop = EngineLoop(eng)
    ctxs = [tracing_plane.mint(sampled=True) for _ in PROMPTS]
    try:
        handles = [loop.submit(list(p), SamplingParams(max_tokens=5),
                               trace_ctx=c)
                   for p, c in zip(PROMPTS, ctxs)]
        outs = [h.wait(120) for h in handles]
    finally:
        loop.shutdown()
    for prompt, ctx, out in zip(PROMPTS, ctxs, outs):
        (span,) = _spans(ctx.trace_id, "llm:engine")
        stages, attrs = span["stages"], span["attrs"]
        assert set(stages) == {"queue", "prefill", "decode"}
        assert all(v >= 0 for v in stages.values())
        assert sum(stages.values()) == pytest.approx(span["dur_s"],
                                                     abs=1e-6)
        assert stages["prefill"] > 0 and stages["decode"] > 0
        assert attrs["prompt_tokens"] == len(prompt)
        assert attrs["chunks"] == -(-len(prompt) // 8)
        assert attrs["output_tokens"] == len(out.token_ids) == 5
        assert 0 <= attrs["slot"] < 4
        steps = [attrs[k] for k in ("submit_step", "first_chunk_step",
                                    "first_token_step", "last_step")]
        assert steps == sorted(steps)
        # the step that ends the prompt dispatches a decode step too,
        # and a step's token is read in the iteration after it: tokens
        # 2 to 5 come one iteration each after the first — or, where
        # the prompt's last chunk rode a decode step (PR 39), the row
        # joins the step after that one: one iteration more
        assert attrs["last_step"] - attrs["first_token_step"] in (4, 5)
        assert "error" not in span


def test_unsampled_context_records_nothing(params):
    eng = _engine(params)
    ctx = tracing_plane.mint(sampled=False)
    eng.add_request(list(PROMPTS[1]), SamplingParams(max_tokens=3),
                    admit=False, trace_ctx=ctx)
    while eng.has_unfinished():
        eng.step()
    assert _spans(ctx.trace_id) == []


def _run_out(eng):
    while eng.has_unfinished():
        eng.step()


@pytest.mark.parametrize("ended_by", ["length", "stop"])
def test_emit_ms_is_one_entry_a_token_handed_over(params, ended_by):
    """A stop token is read but not handed over: one entry fewer."""
    eng = _engine(params)
    alone = eng.generate([list(PROMPTS[0])], SamplingParams(max_tokens=9))
    sampling = SamplingParams(
        max_tokens=9, stop_token_ids=(alone[0].token_ids[5],)
        if ended_by == "stop" else ())
    ctx, events = tracing_plane.mint(sampled=True), []
    eng.add_request(list(PROMPTS[0]), sampling, admit=False, trace_ctx=ctx,
                    on_event=events.append)
    _run_out(eng)
    assert events[-1]["output"].finish_reason == ended_by
    (span,) = _spans(ctx.trace_id, "llm:engine")
    emit_ms, attrs = span["attrs"]["emit_ms"], span["attrs"]
    handed = sum(ev["type"] == "token" for ev in events)
    assert len(emit_ms) == handed == \
        attrs["output_tokens"] - (ended_by == "stop")
    assert 1 < handed <= 9
    assert emit_ms[0] == 0 and emit_ms == sorted(emit_ms)
    # the last hand-over lies inside the decode stage, at 0.01 ms
    assert emit_ms[-1] <= 1000 * span["stages"]["decode"] + 0.01
    assert attrs["chunk_gaps"] == []          # no other request


def test_chunk_gaps_name_the_gaps_that_saw_a_prefill_dispatched(params):
    """Two requests, the second submitted in mid-decode: its two chunks
    are dispatched across exactly as many of the first one's gaps."""
    eng = _engine(params, prefill_chunk_tokens=8)
    first, second = (tracing_plane.mint(sampled=True) for _ in range(2))
    chunks_at = []        # stats["chunks"] at each of the first's tokens

    def on_event(ev):
        if ev["type"] == "token":
            chunks_at.append(eng.stats["chunks"])

    eng.add_request(list(PROMPTS[1]), SamplingParams(max_tokens=12),
                    admit=False, trace_ctx=first, on_event=on_event)
    while len(chunks_at) < 4:
        eng.step()
    had = len(chunks_at)
    eng.add_request(list(PROMPTS[0]), SamplingParams(max_tokens=3),
                    admit=False, trace_ctx=second)
    _run_out(eng)
    (span,) = _spans(first.trace_id, "llm:engine")
    gaps = span["attrs"]["chunk_gaps"]
    assert len(span["attrs"]["emit_ms"]) == len(chunks_at) == 12
    # the iteration that admits the second dispatches its first
    # prefill program and then lands the first one's next token
    assert gaps == list(range(had, had + 2))
    assert gaps == [i for i in range(1, 12)
                    if chunks_at[i] != chunks_at[i - 1]]
    # both chunks rode the first one's decode steps (PR 39): a gap is
    # named where a prefill was dispatched, alone or not
    assert eng.stats["chunks_fused"] == 2
    # the second of them was the prompt's last (PR 48): the one gap
    # across which a prompt ended — the first request's own end, before
    # its first token, lies in no gap
    assert span["attrs"]["prompt_end_gaps"] == [had + 1]
    assert eng.stats["prompt_ends"] == 2
    # nothing was prefilled after the second one's own first token
    (span,) = _spans(second.trace_id, "llm:engine")
    assert span["attrs"]["chunk_gaps"] == []
    assert span["attrs"]["prompt_end_gaps"] == []
    assert len(span["attrs"]["emit_ms"]) == 3


@pytest.mark.parametrize("ctx", [None, tracing_plane.mint(sampled=False),
                                 tracing_plane.mint(sampled=True)],
                         ids=["no-context", "unsampled", "sampled"])
def test_only_a_sampled_request_keeps_its_hand_over_times(params, ctx):
    """Unsampled, a token costs the one ``is None`` test: no list."""
    eng = _engine(params)
    eng.add_request(list(PROMPTS[1]), SamplingParams(max_tokens=3),
                    admit=False, trace_ctx=ctx)
    seq = eng._waiting[-1]
    _run_out(eng)
    if ctx is not None and ctx.sampled:
        assert len(seq.emits) == 3
    else:
        assert seq.emits is None
        assert ctx is None or _spans(ctx.trace_id) == []


def _stalls():
    return [s for s in tracing_plane.recorder().snapshot()
            if s["name"] == "llm:stall"]


@pytest.mark.parametrize("where", ["fetch", "emit", "chunk"])
def test_a_step_that_keeps_rows_standing_still_leaves_one_forced_span(
        params, where, monkeypatch, caplog):
    """Blocked on the device (a read that sleeps) the iteration's
    ``blocked_s`` is its whole; asleep on the host (in a caller's
    ``on_event``) it is nothing.  A lone prompt's end (the read in
    ``chunk``, no row decoding yet) keeps no row waiting however long
    it takes, like the steps that compile: no record."""
    eng = _engine(params)
    eng.generate([list(PROMPTS[1])], SamplingParams(max_tokens=3))  # compile
    before, sleep_s, slept = _stalls(), STALL_S + 0.1, []

    def sleep_once():
        if not slept:
            slept.append(eng.stats["steps"])
            time.sleep(sleep_s)

    to_host = eng._rec.to_host

    class Slow:
        def __init__(self, value):
            self.value = value

        def __array__(self, *args, **kw):
            if eng._rec._phase == where:
                sleep_once()
            return np.asarray(self.value)

    monkeypatch.setattr(eng._rec, "to_host",
                        lambda value: to_host(Slow(value)))

    def on_event(ev):
        if where == "emit" and ev["type"] == "token" and len(seen) == 2:
            sleep_once()
        seen.append(ev)

    seen = []
    eng.add_request(list(PROMPTS[1]), SamplingParams(max_tokens=6),
                    admit=False, on_event=on_event)   # no trace context
    caplog.clear()
    with caplog.at_level(logging.WARNING, logger="ant_ray_tpu.llm.engine"):
        _run_out(eng)
    assert slept
    lines = [r.getMessage() for r in caplog.records
             if "stood still" in r.getMessage()]
    if where == "chunk":
        assert _stalls() == before and not lines
        return
    (stall,) = [s for s in _stalls() if s not in before]
    attrs = stall["attrs"]
    # kept whatever the sampling coin says, and no failure (PR 52)
    assert stall["forced"] is True and "error" not in stall
    assert attrs["phase"] == where and attrs["step"] == slept[0]
    assert sleep_s <= attrs["phase_s"] <= stall["dur_s"] < sleep_s + 0.2
    assert attrs["rows"] == 1          # the one row the read kept waiting
    if where == "fetch":
        assert attrs["blocked_s"] == pytest.approx(stall["dur_s"], abs=0.05)
    else:
        assert attrs["blocked_s"] < 0.05
    assert stall["ts"] == pytest.approx(time.time(), abs=60)
    (line,) = lines
    assert f"step {attrs['step']} " in line and where in line


def test_failed_request_records_a_forced_error_span(params):
    store = LocalKvStore()
    eng = _engine(params, slots=2, kv_offload_store=store)
    eng.add_request([5, 9, 17], SamplingParams(max_tokens=3), admit=False,
                    session_id="s")
    while eng.has_unfinished():
        eng.step()
    assert eng.evict_session("s")
    store.delete("s")                        # the restore will fail
    ctx = tracing_plane.mint(sampled=False)
    eng.add_request([21, 22], SamplingParams(max_tokens=3), admit=False,
                    session_id="s", trace_ctx=ctx)
    outs = []
    deadline = time.monotonic() + 120
    while eng.has_unfinished():
        outs.extend(eng.step())
        assert time.monotonic() < deadline
    assert [o.finish_reason for o in outs] == ["error"]
    (span,) = _spans(ctx.trace_id, "llm:engine")
    assert span["error"] is True and span["forced"] is True
    # it never reached a slot: all of its life was queue
    assert span["stages"]["queue"] == pytest.approx(span["dur_s"], abs=1e-6)
    assert span["attrs"]["chunks"] == 0


def test_profiler_trace_holds_engine_phases_by_step(params, tmp_path):
    """The trace as the benchmark takes it (host TraceMe events, no
    Python tracer): bare event names, the step number as metadata."""
    from jax.profiler import ProfileData

    eng = _engine(params)
    eng.generate([list(PROMPTS[1])], SamplingParams(max_tokens=3))  # warm
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    first = eng.stats["steps"]
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        eng.generate([list(PROMPTS[2])], SamplingParams(max_tokens=6))
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    events = [e for plane in ProfileData.from_file(str(path)).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events]
    names = {e.name for e in events}
    assert {"engine", "engine:admit", "engine:chunk", "engine:decode",
            "engine:sample", "engine:fetch", "engine:emit",
            "engine:housekeeping"} <= names, names
    decode = sorted((e.start_ns, dict(e.stats)["step"]) for e in events
                    if e.name == "engine:decode")
    # six tokens: one from the chunk, whose step decodes too, then four
    assert [s for _, s in decode] == list(range(first, first + 5))
    steps = sorted((e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats)["step_num"]) for e in events
                   if e.name == "engine")
    # and one iteration more, which only reads the last step's token
    assert [n for _, _, n in steps] == list(range(first, first + 6))
    fetch = sorted((e.start_ns, dict(e.stats)["step"]) for e in events
                   if e.name == "engine:fetch")
    assert [s for _, s in fetch] == list(range(first + 1, first + 6))
    # every phase event lies inside the step event that carries its number
    for e in events:
        if e.name.startswith("engine:"):
            lo, hi, _ = steps[dict(e.stats)["step"] - first]
            assert lo <= e.start_ns and e.start_ns + e.duration_ns <= hi


def test_feed_wait_is_named_in_a_trace(tmp_path):
    from jax.profiler import ProfileData

    from ant_ray_tpu._private.jax_utils import trace_annotation

    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace_annotation("feed:wait"):
            time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    assert any(e.name == "feed:wait"
               for plane in ProfileData.from_file(str(path)).planes
               for line in plane.lines for e in line.events)


@pytest.mark.parametrize("sampled", [True, False],
                         ids=["sampled", "unsampled"])
def test_ingress_span_of_a_stream_carries_a_sampled_roots_frames(
        monkeypatch, sampled):
    """``frame_ms`` on the clock of ``first_chunk_s``, ``pull_wait_ms``
    beside it; an unsampled root keeps no frames and records nothing."""
    from ant_ray_tpu.serve import api

    monkeypatch.setattr(tracing_plane, "mint", functools.partial(
        tracing_plane.mint, sampled=sampled))
    ctx, finish = api._stream_ingress_span("http:/x", "http-proxy",
                                           {"path": "/x"})
    at = time.perf_counter()
    if not sampled:
        finish(False, at + 0.010, 2, None, status=200)
        assert _spans(ctx.trace_id) == []
        return
    finish(False, None, 2, [(at + 0.010, 0.0012), (at + 0.030, 0.25)],
           status=200, writes=2)
    (span,) = _spans(ctx.trace_id)
    attrs = span["attrs"]
    assert attrs["chunks"] == len(attrs["frame_ms"]) == 2
    assert attrs["writes"] <= attrs["chunks"]
    assert attrs["frame_ms"][0] == pytest.approx(
        1000 * attrs["first_chunk_s"], abs=0.006)
    assert attrs["frame_ms"][1] - attrs["frame_ms"][0] == pytest.approx(
        20.0, abs=0.011)
    assert attrs["pull_wait_ms"] == [1.2, 250.0]


def test_streamed_request_is_one_trace_from_proxy_to_engine(shutdown_only):
    """http: (with first_chunk_s) -> llm:admission -> llm:engine under
    one trace id, for a stream that SUCCEEDS."""
    from ant_ray_tpu import serve
    from ant_ray_tpu._private import config as config_mod
    from ant_ray_tpu.llm.serve_llm import build_llm_deployment
    from ant_ray_tpu.util.timeline import fetch_span_events

    os.environ["ART_TRACE_SAMPLE_RATE"] = "1.0"
    config_mod._global_config = None
    try:
        art.init(num_cpus=2, num_tpus=1)     # the replica leases a chip
        serve.run(build_llm_deployment("tiny", slots=2, max_seq=64),
                  port=0)
        req = urllib.request.Request(
            f"http://127.0.0.1:{serve.run.last_http_port}/v1/completions",
            data=json.dumps({"prompt": "hello", "max_tokens": 5,
                             "stream": True}).encode(),
            headers={"Content-Type": "application/json"})
        frames = 0
        with urllib.request.urlopen(req, timeout=180) as resp:
            for raw in resp:
                line = raw.decode().strip()
                if line == "data: [DONE]":
                    break
                frames += line.startswith("data: ")
        assert frames >= 2

        def whole(spans):
            by_trace = {}
            for s in spans:
                by_trace.setdefault(s["trace_id"], {})[
                    s["name"].split(":/")[0]] = s
            return [t for t in by_trace.values()
                    if {"http", "llm:admission", "llm:engine",
                        "llm:stream"} <= set(t)]

        deadline = time.monotonic() + 30
        while not (found := whole(fetch_span_events())):
            assert time.monotonic() < deadline, "spans never landed"
            time.sleep(0.2)
        (trace,) = found
        http, engine = trace["http"], trace["llm:engine"]
        assert http["name"] == "http:/v1/completions"
        assert http["attrs"]["stream"] is True
        assert http["attrs"]["status"] == 200
        assert http["attrs"]["chunks"] == frames
        assert 0 < http["attrs"]["first_chunk_s"] <= http["dur_s"]
        assert engine["attrs"]["output_tokens"] == frames - 1
        # every frame's write time and pool wait, every token's
        # hand-over: frame k is token k's, the last the finish chunk
        frame_ms = http["attrs"]["frame_ms"]
        waits = http["attrs"]["pull_wait_ms"]
        assert len(frame_ms) == len(waits) == http["attrs"]["chunks"]
        assert 1 <= http["attrs"]["writes"] <= http["attrs"]["chunks"]
        assert len(frame_ms) == len(engine["attrs"]["emit_ms"]) + 1
        assert frame_ms[0] == pytest.approx(
            1000 * http["attrs"]["first_chunk_s"], abs=0.006)
        assert frame_ms == sorted(frame_ms)
        assert frame_ms[-1] <= 1000 * http["dur_s"] + 0.01
        assert all(0 <= w < 5000 for w in waits)
        # the engine's part lies inside the proxy's
        assert http["ts"] <= engine["ts"]
        assert engine["ts"] + engine["dur_s"] <= http["ts"] + http["dur_s"] \
            + 0.05
    finally:
        # a failure above must not leave the deployment up behind it
        if art.is_initialized():
            serve.shutdown()
        os.environ.pop("ART_TRACE_SAMPLE_RATE", None)
        config_mod._global_config = None
