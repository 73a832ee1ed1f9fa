"""Chunked prefill + session KV offload (llm/engine.py, llm/kv_offload.py):
one compiled chunk variant, TTFT isolation, offload→restore bit-parity
(idle sweep, pressure eviction, forced mid-generation eviction),
non-blocking restores with `llm:restore` attribution, and the chaos leg
— a dead slab holder fails exactly one session typed while the engine
loop keeps serving."""

import time

import numpy as np
import pytest

import jax

import ant_ray_tpu as art
from ant_ray_tpu.exceptions import BackPressureError, KVRestoreError
from ant_ray_tpu.llm import LLMEngine, SamplingParams
from ant_ray_tpu.llm.kv_offload import (KvStoreError, KvVault,
                                        LocalKvStore, ObjectPlaneKvStore)
from ant_ray_tpu.models import llama

CFG = llama.CONFIGS["tiny"]


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.PRNGKey(7))


def _engine(params, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_seq", 96)
    kw.setdefault("prefill_chunk_tokens", 8)
    return LLMEngine(CFG, params, **kw)


def _run_session_turn(eng, sid, prompt, n, **kw):
    eng.add_request(list(prompt), SamplingParams(max_tokens=n, **kw),
                    admit=False, session_id=sid)
    outs = []
    deadline = time.monotonic() + 120
    while eng.has_unfinished():
        outs.extend(eng.step())
        assert time.monotonic() < deadline, "engine never drained"
    assert len(outs) == 1
    return outs[0]


# ----------------------------------------------------- chunked prefill

def test_chunked_prefill_single_compile_entry_and_parity(params):
    """Acceptance: chunked prefill compiles EXACTLY ONE chunk variant
    (slot/offset/length traced — none per prompt length), and its
    greedy token stream is the cacheless reference's."""
    chunked = _engine(params)
    for prompt in ([5, 9, 17, 3, 88], list(range(2, 24))):
        want = np.asarray(llama.greedy_generate(
            params, CFG, np.asarray(prompt, np.int32), 8))[0, len(prompt):]
        got = chunked.generate([prompt], SamplingParams(max_tokens=8))[0]
        assert 255 not in want          # no stop token cuts the stream
        assert got.token_ids == want.tolist()
    assert chunked._prefill_chunk_jit._cache_size() == 1
    assert chunked.stats["chunks"] >= 1 + 3   # ceil(5/8) + ceil(22/8)


def test_short_request_first_token_lands_mid_long_prefill(params):
    """TTFT isolation: with a 64-token prompt trickling in by 4-token
    chunks, a short prompt admitted behind it produces its first token
    while the long prompt is STILL mid-prefill."""
    eng = _engine(params, prefill_chunk_tokens=4, max_seq=128)
    long_rid = eng.add_request(list(range(1, 65)),
                               SamplingParams(max_tokens=4), admit=False)
    eng.step()                                  # long starts ingesting
    eng.add_request([7, 8, 9], SamplingParams(max_tokens=4), admit=False)
    short_seq = next(s for s in eng._waiting + eng._prefilling
                     if s.request_id != long_rid)
    for _ in range(40):
        eng.step()
        if short_seq.generated:
            break
    assert short_seq.generated, "short request starved"
    long_seq = next((s for s in eng._prefilling
                     if s.request_id == long_rid), None)
    assert long_seq is not None and \
        long_seq.prefill_done < len(long_seq.prompt), \
        "long prompt already done — chunking did not interleave"
    while eng.has_unfinished():
        eng.step()


# ------------------------------------------------- offload/restore parity

def test_idle_evict_then_restore_bit_parity(params):
    """A session evicted by the idle LRU sweep restores transparently on
    its next turn, and every turn's tokens are bit-identical to an
    engine that never evicts."""
    turns = [([5, 9, 17], 6), ([3, 88, 41, 2], 6), ([11, 12], 6)]
    base = _engine(params)
    want = [_run_session_turn(base, "s", p, n).token_ids
            for p, n in turns]
    assert base.stats["offloads"] == 0

    evict = _engine(params, kv_idle_evict_s=0.0)
    got = []
    for p, n in turns:
        got.append(_run_session_turn(evict, "s", p, n).token_ids)
        evict.step()                 # idle sweep fires (cutoff = now)
        sess = evict._sessions["s"]
        assert sess.state == "offloaded"
    assert got == want
    assert evict.stats["idle_evictions"] >= 2
    assert evict.stats["restores"] >= 2


def test_forced_mid_generation_evict_bit_parity(params):
    """Acceptance: evict a session MID-GENERATION (force), let the
    automatic restore resume it — the full stream is bit-identical to
    an uninterrupted run, including temperature sampling (per-seq rng
    keys ride the seq, not the slot)."""
    prompt, n = [5, 9, 17, 3, 88, 41], 16
    sp = SamplingParams(max_tokens=n, temperature=0.7, seed=123)

    base = _engine(params)
    want = _run_session_turn(base, "s", prompt, n,
                             temperature=0.7, seed=123).token_ids

    eng = _engine(params)
    eng.add_request(list(prompt), sp, admit=False, session_id="s")
    for _ in range(6):               # past prefill, a few tokens in
        eng.step()
    sess = eng._sessions["s"]
    assert sess.current is not None and sess.current.generated
    assert eng.evict_session("s", force=True)
    assert sess.state == "offloaded" and sess.paused is not None
    outs = []
    deadline = time.monotonic() + 120
    while eng.has_unfinished():
        outs.extend(eng.step())
        assert time.monotonic() < deadline
    assert [int(t) for t in outs[0].token_ids] == \
        [int(t) for t in want]
    assert eng.stats["offloads"] == 1 and eng.stats["restores"] == 1


# A session's three turns beside a plain request, as the UNPIPELINED
# engine left them (commit b1d9bd2; 2 slots, max_seq 64, chunks of 8,
# with and without an eviction after turn 1): turn 1 ends at a stop
# token (its greedy stream's fourth, 42), turn 2 at ``max_tokens``, turn
# 3 at ``max_seq`` (45 of the 60 tokens asked).  Per turn: token ids,
# finish reason, the session's ``kv_len`` and ``carry`` after it.
TURN_1 = ([227, 234, 128], "stop", 6, [42])
TURN_2 = ([150, 114, 186, 12, 114, 186], "length", 16, [186])
TURN_3 = ([243, 199, 238, 79, 66, 203, 219, 60, 147, 251, 129, 129, 18, 43,
           110, 200, 151, 177, 190, 131, 103, 165, 133, 190, 182, 102, 99,
           128, 53, 211, 114, 161, 62, 114, 139, 184, 82, 167, 179, 230,
           106, 104, 157, 220, 211], "length", 63, [211])
BESIDE = [130, 21, 114, 47, 199, 220, 199, 220, 199, 220, 199, 220, 225, 29]


def _drain(eng, outs, until=None):
    deadline = time.monotonic() + 120
    while eng.has_unfinished() and until not in outs:
        for out in eng.step():
            outs[out.request_id] = out
        assert time.monotonic() < deadline, "engine never drained"


def _turn(eng, outs, rid):
    sess = eng._sessions["s"]
    return (outs[rid].token_ids, outs[rid].finish_reason, sess.kv_len,
            list(sess.carry))


@pytest.mark.parametrize("evict", [None, "idle", "pressure"])
def test_stop_token_drops_the_row_of_the_step_in_flight(params, evict):
    """A stop token at step N is read after step N+1 was dispatched:
    the row N+1 computed for the ended turn is dropped — not emitted,
    not in ``generated``, not in ``tokens_generated`` — the session's
    ``kv_len`` and ``carry`` are the unpipelined engine's, and so are
    the next turns' answers, whether the slab stays (the stale K/V row
    lies where turn 2 writes the carry), is evicted by hand while the
    plain request's step is in flight, or by admission pressure."""
    eng = _engine(params, slots=2, max_seq=64)
    outs = {}
    eng.add_request([7, 8, 9], SamplingParams(max_tokens=14),
                    request_id="bg", admit=False)
    eng.add_request([5, 9, 17], SamplingParams(
        max_tokens=8, stop_token_ids=(42,)), request_id="t1",
        admit=False, session_id="s")
    _drain(eng, outs, until="t1")
    assert _turn(eng, outs, "t1") == TURN_1
    sess = eng._sessions["s"]
    assert sess.current is None and sess.state == "resident"
    if evict == "idle":
        assert eng._flight is not None       # bg's step, and t1's stale row
        assert eng.evict_session("s")
        assert eng._flight is None and sess.state == "offloaded"
    elif evict == "pressure":
        assert eng._flight is not None
        eng.add_request([9, 9, 9], SamplingParams(max_tokens=2),
                        request_id="x", admit=False)
        eng.step()                           # no free slot: s is spilled
        assert sess.state == "offloaded"
        assert eng.stats["pressure_evictions"] == 1
    eng.add_request([3, 88, 41, 2], SamplingParams(max_tokens=6),
                    request_id="t2", admit=False, session_id="s")
    _drain(eng, outs)
    assert outs["bg"].token_ids == BESIDE
    assert _turn(eng, outs, "t2") == TURN_2
    eng.add_request([11, 12], SamplingParams(
        max_tokens=60, temperature=0.8, top_k=40, top_p=0.95, seed=77),
        request_id="t3", admit=False, session_id="s")
    _drain(eng, outs)
    assert _turn(eng, outs, "t3") == TURN_3  # ended by max_seq
    extra = len(outs["x"].token_ids) - 1 if evict == "pressure" else 0
    assert eng.stats["tokens_generated"] == 65 + extra
    # one row was computed and dropped: t1's, after its stop token
    assert eng.stats["decode_slots"] == 65 + extra + 1
    assert eng.stats["offloads"] == eng.stats["restores"] == (evict
                                                              is not None)


@pytest.mark.parametrize("how", ["evict_session", "offload", "end_session",
                                 "shutdown"])
def test_nothing_moves_a_slot_under_a_step_in_flight(params, how):
    """``evict_session`` (forced, mid-generation), ``_offload`` (an idle
    session, while another request's step is in flight),
    ``end_session`` and the loop's ``shutdown`` first land the step in
    flight: its tokens reach their sequences, and the streams are the
    uninterrupted ones."""
    from ant_ray_tpu.llm.engine import EngineLoop

    want = [_engine(params).generate([p], SamplingParams(max_tokens=n))[0]
            for p, n in (([5, 9, 17, 3], 12), ([7, 8, 9], 16))]
    eng = _engine(params)
    if how == "shutdown":
        loop = EngineLoop(eng)
        handle = loop.submit([5, 9, 17, 3], SamplingParams(max_tokens=40))
        first = handle.events.get(timeout=120)
        assert first["type"] == "token"
        loop.shutdown(timeout=60)
        assert not loop._thread.is_alive() and eng._flight is None
        streamed = [first["token_id"]]
        while not handle.events.empty():
            event = handle.events.get()
            if event["type"] == "token":
                streamed.append(event["token_id"])
        # every token read was emitted, and the step in flight was read
        # (a loop that outran the shutdown has finished the request)
        for seq in eng._active.values():
            assert streamed == seq.generated
            assert seq.kv_len == 4 + len(streamed) - 1
        assert eng._active or streamed == handle.wait(0).token_ids
        return
    outs = {}
    eng.add_request([5, 9, 17, 3], SamplingParams(max_tokens=12),
                    request_id="a", admit=False, session_id="s")
    eng.add_request([7, 8, 9], SamplingParams(max_tokens=16),
                    request_id="b", admit=False)
    for _ in range(5):
        eng.step()
    assert eng._flight is not None and len(eng._flight[1]) == 2
    seq_a, sess = eng._sessions["s"].current, eng._sessions["s"]
    before = len(seq_a.generated)
    if how == "evict_session":
        assert eng.evict_session("s", force=True)
        assert eng._flight is None and len(seq_a.generated) == before + 1
        assert sess.kv_len == seq_a.kv_len == 4 + before
    elif how == "offload":
        _drain(eng, outs, until="a")         # s idle; b's step in flight
        assert eng._flight is not None and "b" not in outs
        seq_b = eng._flight[1][-1][1]
        before = len(seq_b.generated)
        eng._offload(sess)
        assert eng._flight is None and len(seq_b.generated) == before + 1
        assert sess.state == "offloaded" and sess.kv_len == 4 + 11
    else:
        assert eng.end_session("s")          # mid-turn: the turn goes on
        assert eng._flight is None and len(seq_a.generated) == before + 1
    _drain(eng, outs)
    assert [outs["a"].token_ids, outs["b"].token_ids] == \
        [o.token_ids for o in want]


@pytest.mark.parametrize("how", ["evict_session", "end_session",
                                 "shutdown"])
def test_a_mixed_step_in_flight_is_landed_like_any_other(params, how):
    """The step in flight carries another prompt's chunk (PR 39: one
    program for both): a forced eviction, ``end_session`` and the
    loop's ``shutdown`` land it first all the same — its decode rows'
    tokens reach their sequences, the chunk's prompt goes on where it
    was, and the streams are the uninterrupted ones."""
    from ant_ray_tpu.llm.engine import EngineLoop

    long_prompt = list(range(3, 30))         # four chunks of 8
    want = [_engine(params).generate([p], SamplingParams(max_tokens=n))[0]
            for p, n in (([5, 9, 17, 3], 12), (long_prompt, 6))]
    eng = _engine(params, slots=3)
    if how == "shutdown":
        loop = EngineLoop(eng)
        first = loop.submit([5, 9, 17, 3], SamplingParams(max_tokens=60))
        assert first.events.get(timeout=120)["type"] == "token"
        second = loop.submit(long_prompt, SamplingParams(max_tokens=6))
        assert second.events.get(timeout=120)["type"] == "token"
        loop.shutdown(timeout=60)
        assert not loop._thread.is_alive() and eng._flight is None
        assert eng.stats["chunks_fused"] == 4          # all of them rode
        for seq in eng._active.values():
            assert seq.kv_len == len(seq.prompt) + len(seq.generated) - 1
        return
    outs = {}
    eng.add_request([5, 9, 17, 3], SamplingParams(max_tokens=12),
                    request_id="a", admit=False, session_id="s")
    for _ in range(3):
        eng.step()
    eng.add_request(long_prompt, SamplingParams(max_tokens=6),
                    request_id="c", admit=False)
    eng.step()
    eng.step()
    sampled, rows = eng._flight
    assert eng.stats["chunks_fused"] == 2    # the step in flight: mixed
    assert [seq.request_id for _, seq in rows] == ["a"]
    (seq_c,) = eng._prefilling
    assert seq_c.prefill_done == seq_c.kv_len == 16
    seq_a, sess = eng._sessions["s"].current, eng._sessions["s"]
    before = len(seq_a.generated)
    if how == "evict_session":
        assert eng.evict_session("s", force=True)
        assert sess.kv_len == seq_a.kv_len == 4 + before
    else:
        assert eng.end_session("s")          # mid-turn: the turn goes on
    assert eng._flight is None and len(seq_a.generated) == before + 1
    assert eng._prefilling == [seq_c] and seq_c.kv_len == 16
    _drain(eng, outs)
    assert [outs["a"].token_ids, outs["c"].token_ids] == \
        [o.token_ids for o in want]


def test_sessions_beyond_slots_all_complete(params):
    """Acceptance: resident sessions exceed the KV slot count at fixed
    HBM — sessions beyond `slots` complete via offload, and their
    second turns (restored slabs) stay bit-identical to a wide
    engine that never needed to evict."""
    n_sessions, slots = 4, 2
    turns = [([5 + i, 9, 17 + i], 5) for i in range(n_sessions)]

    wide = _engine(params, slots=n_sessions)
    want = {}
    for i, (p, n) in enumerate(turns):
        _run_session_turn(wide, f"s{i}", p, n)
    for i, (p, n) in enumerate(turns):
        want[i] = _run_session_turn(wide, f"s{i}", [99, 98 + i],
                                    5).token_ids

    narrow = _engine(params, slots=slots)
    for i, (p, n) in enumerate(turns):
        _run_session_turn(narrow, f"s{i}", p, n)
    assert narrow.resident_sessions() == n_sessions > slots
    assert narrow.stats["pressure_evictions"] >= n_sessions - slots
    for i in range(n_sessions):
        got = _run_session_turn(narrow, f"s{i}", [99, 98 + i],
                                5).token_ids
        assert got == want[i], f"session s{i} diverged after restore"
    assert narrow.stats["restores"] >= n_sessions - slots


def test_pressure_eviction_admits_instead_of_shedding(params):
    """KV-full admission with an idle resident session: the engine
    evicts it and ADMITS the new request instead of shedding typed —
    shedding only happens when nothing is evictable."""
    eng = _engine(params, slots=1, max_waiting=0)
    _run_session_turn(eng, "idle", [5, 9, 17], 4)
    assert eng._sessions["idle"].state == "resident"
    assert not eng._free_slots

    # Admission evicts the idle session rather than raising.
    eng.add_request([1, 2, 3], SamplingParams(max_tokens=4),
                    session_id="fresh")
    assert eng._sessions["idle"].state == "offloaded"
    assert eng.stats["pressure_evictions"] == 1
    while eng.has_unfinished():
        eng.step()

    # Both sessions busy/non-idle → nothing evictable → typed shed.
    eng2 = _engine(params, slots=1, max_waiting=0)
    eng2.add_request(list(range(1, 40)), SamplingParams(max_tokens=30),
                     admit=False)
    eng2.step()
    with pytest.raises(BackPressureError) as err:
        eng2.add_request([4, 5], SamplingParams(max_tokens=2))
    assert err.value.retry_after_s > 0


def test_a_default_engine_continues_a_session(params):
    """An engine given no chunk width keeps a session: turn 2, queued
    while turn 1 is still in flight, waits for it and appends at turn
    1's offset — its tokens are the cacheless reference's over the whole
    conversation."""
    eng = LLMEngine(CFG, params, slots=2, max_seq=96)
    starts, chunk = [], eng._prefill_chunk_jit
    eng._prefill_chunk_jit = lambda *args: (starts.append(args[4]),
                                            chunk(*args))[1]
    first, then = [5, 9, 17], [3, 4]
    rids = [eng.add_request(list(prompt), SamplingParams(max_tokens=n),
                            admit=False, session_id="s")
            for prompt, n in ((first, 8), (then, 4))]
    outs = {}
    _drain(eng, outs)
    one, two = (outs[rid] for rid in rids)
    assert one.finish_reason == two.finish_reason == "length"
    said = first + one.token_ids
    want = np.asarray(llama.greedy_generate(
        params, CFG, np.asarray(said + then, np.int32), 4))[0]
    assert two.token_ids == want[len(said) + len(then):].tolist()
    # turn 1's last token was never written: turn 2 carries it in
    assert starts == [0, len(said) - 1]
    assert eng._sessions["s"].kv_len == len(said) + len(then) + 4 - 1


def test_local_store_spill_capacity_and_distinct_files(tmp_path):
    """Spilled slabs get distinct files (monotonic names, not
    hash(key) — colliding hashes must never cross sessions' bytes) and
    ``capacity_slabs`` counts only real in-memory slabs, not spill
    bookkeeping."""
    store = LocalKvStore(spill_dir=str(tmp_path), capacity_slabs=2)
    slabs = {f"s{i}": (np.full((2, 2), i), -np.full((2, 2), i), i)
             for i in range(5)}
    for key, slab in slabs.items():
        store.put(key, slab)
    assert store.spills == 3
    assert len(store._mem) == 2              # capacity holds exactly
    spilled = sorted(tmp_path.iterdir())
    assert len(spilled) == 3                 # one file per spilled slab
    for key, (k, v, ln) in slabs.items():
        k2, v2, ln2 = store.get(key)
        np.testing.assert_array_equal(k2, k)
        np.testing.assert_array_equal(v2, v)
        assert ln2 == ln
    # Re-putting a spilled key supersedes its file; delete removes it.
    store.put("s0", slabs["s0"])
    for key in slabs:
        store.delete(key)
    assert not list(tmp_path.iterdir())


def test_engine_loop_end_session_runs_on_loop_thread(params):
    """EngineLoop.end_session routes through the loop inbox (like
    evict_session): the teardown never races a concurrent step, and
    the slot returns to the free pool."""
    from ant_ray_tpu.llm.engine import EngineLoop

    eng = _engine(params)
    loop = EngineLoop(eng)
    try:
        loop.submit([5, 9, 17], SamplingParams(max_tokens=4),
                    session_id="s").wait(timeout=120)
        assert not loop.end_session("missing")
        assert loop.end_session("s")
        assert "s" not in eng._sessions
        assert len(eng._free_slots) == eng.slots
    finally:
        loop.shutdown()


# --------------------------------------------------- restore concurrency

class _SlowStore(LocalKvStore):
    """LocalKvStore whose get() blocks until released — pins a restore
    in flight so the test can observe decode running under it."""

    def __init__(self):
        import threading

        super().__init__()
        self.release = threading.Event()

    def get(self, handle):
        assert self.release.wait(60), "test never released the restore"
        return super().get(handle)


def test_restore_overlaps_decode_and_records_span(params):
    """Acceptance: the step loop NEVER blocks on a restore — another
    request keeps generating while the fetch is pinned in flight — and
    the landed restore is attributed via an `llm:restore` trace span on
    the continuation's context."""
    from ant_ray_tpu.observability import tracing_plane

    store = _SlowStore()
    eng = _engine(params, slots=2, kv_offload_store=store)
    _run_session_turn(eng, "s", [5, 9, 17], 4)
    assert eng.evict_session("s")
    assert eng._sessions["s"].state == "offloaded"

    ctx = tracing_plane.mint(sampled=True)
    eng.add_request([21, 22], SamplingParams(max_tokens=4), admit=False,
                    session_id="s", trace_ctx=ctx)
    other = eng.add_request([7, 8, 9], SamplingParams(max_tokens=6),
                            admit=False)
    outs = {}
    deadline = time.monotonic() + 120
    while eng.has_unfinished():
        for out in eng.step():
            outs[out.request_id] = out
        if other in outs and not store.release.is_set():
            # The unrelated request finished START-TO-END while the
            # restore fetch was still pinned: decode never blocked.
            assert eng.stats["restores"] == 0
            assert eng._sessions["s"].state == "restoring"
            store.release.set()
        assert time.monotonic() < deadline, "engine wedged on restore"
    assert other in outs and len(outs) == 2
    assert eng.stats["restores"] == 1
    spans = [s for s in tracing_plane.recorder().snapshot()
             if s.get("name") == "llm:restore"]
    assert spans and spans[-1]["attrs"]["session"] == "s"
    assert spans[-1]["dur_s"] > 0


def test_restore_failure_fails_one_session_typed(params):
    """A failed restore (slab gone from the store) fails THAT session's
    request with KVRestoreError; other slots keep decoding and the
    session id is reusable afterwards as a fresh session."""
    store = LocalKvStore()
    eng = _engine(params, slots=2, kv_offload_store=store)
    _run_session_turn(eng, "s", [5, 9, 17], 4)
    assert eng.evict_session("s")
    store.delete("s")                       # the chaos: slab vanishes

    eng.add_request([21, 22], SamplingParams(max_tokens=4), admit=False,
                    session_id="s")
    eng.add_request([7, 8, 9], SamplingParams(max_tokens=6),
                    admit=False)
    outs = {}
    deadline = time.monotonic() + 120
    while eng.has_unfinished():
        for out in eng.step():
            outs[out.request_id] = out
        assert time.monotonic() < deadline, "loop wedged on failed restore"
    assert len(outs) == 2
    failed = [o for o in outs.values() if o.finish_reason == "error"]
    ok = [o for o in outs.values() if o.finish_reason != "error"]
    assert len(failed) == 1 and "restore" in failed[0].error
    assert len(ok) == 1 and len(ok[0].token_ids) == 6
    assert eng.stats["restore_failures"] == 1
    assert eng._sessions["s"].state == "failed"
    # The session id is reusable: a fresh request re-prefills from zero.
    out = _run_session_turn(eng, "s", [1, 2, 3], 3)
    assert out.finish_reason != "error"


# ------------------------------------------------------------ chaos leg

def test_holder_death_mid_restore_fails_one_session_typed(
        shutdown_only, chaos_schedule):
    """ISSUE 18 chaos leg: the KV slab holder (a KvVault actor) dies
    while a restore is in flight.  Exactly one session fails with
    KVRestoreError (typed, carried on the stream error event); the
    engine loop never wedges and keeps completing other requests.
    chunk_serve_delay keeps the transfer window open the way the
    transfer-plane chaos tests do."""
    chaos_schedule.chunk_serve_delay(0.005)
    art.init(num_cpus=2,
             _system_config=chaos_schedule.system_config())
    vault = art.remote(KvVault).remote()
    art.get(vault.put.remote("warm", 1), timeout=60)   # actor is up

    store = ObjectPlaneKvStore(vault=vault, get_timeout_s=15.0)
    params = llama.init_params(CFG, jax.random.PRNGKey(7))
    eng = _engine(params, slots=2, kv_offload_store=store)
    _run_session_turn(eng, "doomed", [5, 9, 17], 4)
    assert eng.evict_session("doomed")
    art.kill(vault)                       # holder dies, slab with it

    eng.add_request([21, 22], SamplingParams(max_tokens=4), admit=False,
                    session_id="doomed")
    events = []
    eng.add_request([7, 8, 9], SamplingParams(max_tokens=6),
                    admit=False, on_event=events.append)
    outs = {}
    deadline = time.monotonic() + 120
    while eng.has_unfinished():
        for out in eng.step():
            outs[out.request_id] = out
        assert time.monotonic() < deadline, \
            "engine wedged after holder death"
    failed = [o for o in outs.values() if o.finish_reason == "error"]
    assert len(failed) == 1 and "doomed" in failed[0].error
    survivors = [o for o in outs.values() if o.finish_reason != "error"]
    assert len(survivors) == 1 and len(survivors[0].token_ids) == 6
    assert eng.stats["restore_failures"] == 1
    # The typed error reaches streaming sinks as a KVRestoreError.
    errs = [e for e in events if e["type"] == "error"]
    assert not errs                        # survivor saw no error event
    sess = eng._sessions["doomed"]
    assert sess.state == "failed" and sess.paused is None


# ------------------------------------------------------- object plane

def test_object_plane_store_roundtrip_and_vault_errors(shutdown_only):
    """ObjectPlaneKvStore seals slabs through art.put/get bit-exactly;
    a vault fetch for an unknown key surfaces KvStoreError typed."""
    art.init(num_cpus=2)
    store = ObjectPlaneKvStore()
    k = np.arange(24, dtype=np.float32).reshape(2, 3, 4)
    v = -k
    store.put("sess", (k, v, 7))
    k2, v2, ln = store.get("sess")
    assert ln == 7
    np.testing.assert_array_equal(k2, k)
    np.testing.assert_array_equal(v2, v)
    store.delete("sess")
    with pytest.raises(KvStoreError):
        store.get("sess")

    vault = art.remote(KvVault).remote()
    vstore = ObjectPlaneKvStore(vault=vault, get_timeout_s=30.0)
    vstore.put("sess", (k, v, 7))
    k3, _v3, _ln = vstore.get("sess")
    np.testing.assert_array_equal(k3, k)
    with pytest.raises(Exception, match="no slab"):
        vstore.get("missing")


@pytest.mark.slow
def test_loadgen_soak_mixed_sessions(params):
    """Long soak (bench shape, committed loadgen): shorts, a long-prompt
    ingester, and pausing sessions against 2 slots with an aggressive
    idle sweep — every request completes, sessions exceed slots via
    offload, and nothing sheds or fails across sustained churn."""
    import os
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks"))
    from llm_loadgen import ClientSpec, LoadGen

    from ant_ray_tpu.llm.engine import EngineLoop

    eng = _engine(params, slots=2, max_seq=128,
                  kv_idle_evict_s=0.05)
    loop = EngineLoop(eng, metrics_interval_s=0.5)
    rep = LoadGen(loop, seed=1).run(
        [ClientSpec("short", 6, 6, count=2, think_time_s=0.01),
         ClientSpec("long", 60, 4, count=1),
         ClientSpec("session", 10, 4, count=4, session=True,
                    pause_s=0.12, turns=4)],
        duration_s=10.0)
    loop.shutdown()
    assert rep.failed == 0, rep.errors[:3]
    assert rep.shed == 0
    assert rep.finished >= 16 + 4          # 4 sessions x 4 turns + churn
    assert eng.resident_sessions() == 4 > eng.slots
    assert eng.stats["restores"] >= 4
    assert loop.stats()["art_llm_tokens_per_s"] >= 0


def test_kv_restore_error_pickles_with_session_id():
    import pickle

    err = KVRestoreError("session 's' lost", session_id="s")
    back = pickle.loads(pickle.dumps(err))
    assert isinstance(back, KVRestoreError)
    assert back.session_id == "s" and "lost" in str(back)
