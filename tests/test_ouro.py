"""Ouro's LOOPED stack (a token passes through the same layers several
times, a slab layer in the cache for every (pass, layer) pair, sandwich
norms, the final norm between the passes, the exit gate counted) on the
program's normal paths, against the plain reference
``chipbench/reference/ouro_decoder.py`` on seeded random weights at a
tiny shape, on the CPU in float32.

The measure is the benchmark's own: the relative L2 distance of the
logits, per position.  Both sides compute in float32, so the distance
is rounding and the order of summation.  ``TOL`` = 1e-4 is two orders
under what it must catch: with norms on every sub-layer's output and
behind every pass, a dropped (pass, layer) index, a norm left out or a
pass too few each read over 1e-2.

The step programs' attention block is cut to 16 positions.
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

from ant_ray_tpu.llm import LLMEngine, SamplingParams
from ant_ray_tpu.models import llama
from chipbench.models import ouro
from chipbench.reference import ouro_decoder as ref

CFG = llama.CONFIGS["ouro-tiny"]                     # 3 layers x 3 passes
TOL = 1e-4
SLOTS, MAX_SEQ = 3, 128
PUBLISHED = os.path.join(os.path.dirname(__file__), "..", "chipbench",
                         "configs", "ouro-2.6b.json")


@pytest.fixture(autouse=True)
def short_blocks(monkeypatch):
    monkeypatch.setattr(llama, "ATTEND_BLOCK", 16)


def seeded_params(cfg=CFG, seed=0):
    """Seeded weights, less bland than the initialiser's: matrices large
    enough that attention attends and the gates decide, norm weights
    that are not all ones, a gate that is not a coin."""
    p = llama.init_params(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def livelier(name, leaf):
        if name.startswith("ln_"):
            return leaf * jax.random.uniform(
                next(keys), leaf.shape, minval=0.5, maxval=1.5)
        return leaf * 6.0

    out = {**p, "norm_f": p["norm_f"] * jax.random.uniform(
        next(keys), p["norm_f"].shape, minval=0.5, maxval=1.5),
        "layers": {n: livelier(n, x) for n, x in p["layers"].items()}}
    if cfg.exit_gate:
        out["exit_gate"] = {"w": p["exit_gate"]["w"] * 6.0,
                            "b": p["exit_gate"]["b"] + 0.3}
    return out


@pytest.fixture(scope="module")
def params():
    return seeded_params()


_BLOCK = jax.jit(ref.block, static_argnames=(       # as the harness does
    "n_heads", "n_kv_heads", "rope_theta", "norm_eps", "sandwich"))


def dims_of(cfg, **changed):
    return {"n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "rope_theta": cfg.rope_theta, "norm_eps": cfg.norm_eps,
            "total_ut_steps": cfg.loops, "early_exit_threshold": 1.0,
            **changed}


def reference_logits(params, tokens, cfg=CFG, **changed):
    embed, layer, n, closing, head = ouro.reference_layers(params)
    return ref.forward(embed, (layer, n), closing, head, jnp.asarray(tokens),
                       block_fn=_BLOCK, **dims_of(cfg, **changed))


def reference_gates(params, tokens, cfg=CFG):
    embed, layer, n, closing, _ = ouro.reference_layers(params)
    dims = dims_of(cfg)
    dims.pop("early_exit_threshold")
    return ref.passes(embed, (layer, n), closing, jnp.asarray(tokens),
                      block_fn=_BLOCK, **dims)[1]


def rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(((got - want) ** 2).sum(-1) / (want ** 2).sum(-1))


def tokens_of(seed, *shape):
    return np.random.default_rng(seed).integers(
        0, CFG.vocab_size, shape).astype(np.int32)


def _bits(x):
    return np.asarray(x).view(np.uint32)


# The step programs jitted once a shape, as the engine runs them.
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


@functools.partial(jax.jit, static_argnames=("cfg",))
def mixed_step(params, last, tokens, cache, active, slot, start, n, cfg=CFG):
    return llama.mixed_step(params, last, tokens, cache, cfg, active, slot,
                            start, n)


def ingest(params, cache, tokens, slot, chunk, start=0, cfg=CFG,
           step=chunk_step):
    """``tokens`` into ``slot`` from position ``start`` on, in chunks ->
    (logits at each chunk's last token, cache)."""
    logits = []
    for at in range(0, len(tokens), chunk):
        part = tokens[at:at + chunk]
        buf = np.zeros((chunk,), np.int32)
        buf[:len(part)] = part
        out, cache = step(params, jnp.asarray(buf), cache, slot,
                          start + at, len(part), cfg=cfg)
        logits.append(out)
    return logits, cache


def decode(params, cache, tokens, slot, cfg=CFG, others=(),
           step=decode_step):
    """``tokens`` one by one (teacher forced) in ``slot``; ``others``:
    slots that decode token 7 beside it."""
    active = np.zeros((SLOTS,), bool)
    active[[slot, *others]] = True
    got = []
    for token in tokens:
        last = np.full((SLOTS,), 7, np.int32)
        last[slot] = token
        logits, cache = step(params, jnp.asarray(last), cache,
                             jnp.asarray(active), cfg=cfg)
        got.append(logits[slot])
    return got, cache


def through_the_cache(params, tokens, prompt, chunk, slot=1, cfg=CFG,
                      steps=(chunk_step, decode_step)):
    """-> logits at every chunk's end and from the last prompt token
    on, with the positions they belong to, and the cache; ``steps``:
    the chunk and the decode program."""
    cache = llama.init_kv_cache(cfg, SLOTS, MAX_SEQ, chunk)
    ends, cache = ingest(params, cache, tokens[:prompt], slot, chunk,
                         cfg=cfg, step=steps[0])
    at = [min(a + chunk, prompt) - 1 for a in range(0, prompt, chunk)]
    rest, cache = decode(params, cache, tokens[prompt:], slot, cfg=cfg,
                         step=steps[1])
    return (jnp.stack(ends + rest), at + list(range(prompt, len(tokens))),
            cache)


# ------------------------------------------------ (a) against the reference

@pytest.mark.parametrize("loops", [2, 3, 4])
def test_forward_is_the_reference(loops):
    cfg = dataclasses.replace(CFG, loops=loops)
    params = seeded_params(cfg, seed=2)
    tokens = tokens_of(3, 2, 50)
    got = forward(params, jnp.asarray(tokens), cfg=cfg)
    for row, logits in zip(tokens, got):
        assert rel_l2(logits, reference_logits(params, row, cfg)).max() < TOL


@pytest.mark.parametrize("chunk,prompt", [
    (16, 64),      # whole chunks
    (16, 53),      # a last chunk padded
    (64, 9),       # one chunk, mostly padding
])
def test_chunks_and_decode_through_the_cache_are_forward(params, chunk,
                                                         prompt):
    tokens = tokens_of(100 + prompt, prompt + 6)
    got, at, _ = through_the_cache(params, tokens, prompt, chunk)
    want = reference_logits(params, tokens)
    assert rel_l2(got, want[jnp.asarray(at)]).max() < TOL


def test_a_mixed_step_is_both_its_programs(params):
    """Slot 1 decodes while slot 2's prompt is ingested by the mixed
    program, the last chunk padded: both read what the reference's full
    forward gives, and the decode rows alone are counted."""
    first, second = tokens_of(7, 30), tokens_of(8, 41)
    cache = llama.init_kv_cache(CFG, SLOTS, MAX_SEQ, 16)
    _, cache = ingest(params, cache, first[:24], 1, 16)
    active = jnp.asarray([False, True, False])
    before = np.asarray(cache["exits"])
    got_decode, got_chunk = [], []
    for j, at in enumerate(range(0, 41, 16)):
        part = second[at:at + 16]
        buf = np.zeros((16,), np.int32)
        buf[:len(part)] = part
        last = jnp.full((SLOTS,), int(first[24 + j]), jnp.int32)
        rows, end, cache = mixed_step(params, last, jnp.asarray(buf), cache,
                                      active, 2, at, len(part))
        got_decode.append(rows[1])
        got_chunk.append(end)
    want = reference_logits(params, first)
    assert rel_l2(jnp.stack(got_decode), want[24:27]).max() < TOL
    want = reference_logits(params, second)
    assert rel_l2(jnp.stack(got_chunk),
                  want[jnp.asarray([15, 31, 40])]).max() < TOL
    assert cache["length"].tolist() == [0, 27, 41]
    # three steps, one decode row each; the chunk's 16 rows are not counted
    rows, passes = (np.asarray(cache["exits"]) - before).tolist()
    assert rows == 3
    gates = jnp.stack(reference_gates(params, first))[:, 24:27]
    want = float(jnp.sum(jnp.arange(1, 4)[:, None]
                         * ref.exit_distribution(list(gates))))
    assert passes * llama.EXIT_PASS_UNIT == pytest.approx(want, abs=2e-3)


# ------------------------------------- (b) a slab layer a (pass, layer) pair

def test_the_cache_has_a_slab_layer_for_every_pass_and_layer():
    cache = llama.init_kv_cache(CFG, SLOTS, MAX_SEQ, 16)
    assert CFG.layer_counts() == (0, 3) and CFG.slab_layers() == (0, 9)
    assert cache["k"].shape == cache["v"].shape == (9, SLOTS, MAX_SEQ, 4, 16)
    assert cache["exits"].shape == (2,) and cache["exits"].dtype == jnp.uint32
    assert set(cache) == {"k", "v", "length", "exits"}
    once = dataclasses.replace(CFG, loops=1, exit_gate=False)
    assert set(llama.init_kv_cache(once, SLOTS, MAX_SEQ, 16)) == {
        "k", "v", "length"}
    assert once.slab_layers() == once.layer_counts() == (0, 3)
    # the other kinds of cache count as they did
    for name in ("cmdaplus-tiny", "olmo-hybrid-tiny", "axk1-tiny"):
        other = llama.CONFIGS[name]
        assert other.slab_layers() == other.layer_counts()


@pytest.mark.parametrize("program", ["chunk", "decode"])
def test_a_pass_writes_its_own_slab_layers_and_no_other(params, program):
    """Pass ``u``, layer ``l`` writes slab layer ``u * n_layers + l``:
    every one of the nine layers takes the call's rows, each pass's
    from ITS state — no two passes' rows are equal — and nothing else
    of the slabs moves."""
    tokens = tokens_of(11, 21)
    cache = llama.init_kv_cache(CFG, SLOTS, MAX_SEQ, 16)
    if program == "chunk":
        _, cache = ingest(params, cache, tokens[:10], 1, 16)
        rows = slice(0, 10)
    else:
        _, cache = ingest(params, cache, tokens[:10], 1, 16)
        before = {n: np.asarray(cache[n]) for n in "kv"}
        _, cache = decode(params, cache, tokens[10:11], 1)
        rows = slice(10, 11)
    for name in "kv":
        slab = np.asarray(cache[name])
        if program == "decode":
            changed = np.argwhere((slab != before[name]).any(axis=(-1, -2)))
            assert set(changed[:, 0]) == set(range(9))
            assert set(changed[:, 1]) == {1} and set(changed[:, 2]) == {10}
        written = slab[:, 1, rows]
        assert np.abs(written).reshape(9, -1).max(axis=1).min() > 0
        assert not slab[:, 0].any() and not slab[:, 2].any()
        assert not slab[:, 1, rows.stop:].any()
        for a in range(9):
            for b in range(a + 1, 9):
                assert np.abs(written[a] - written[b]).max() > 1e-3
    # and they are the reference's: layer l of pass u reads the state
    # the reference has there
    embed, layer, n, closing, _ = ouro.reference_layers(params)
    x = ref.embed_tokens(embed, jnp.asarray(tokens[:rows.stop]))
    positions = jnp.arange(rows.stop)
    dims = dims_of(CFG)
    four = {k: dims[k] for k in ("n_heads", "n_kv_heads", "rope_theta",
                                 "norm_eps")}
    for u in range(CFG.loops):
        for i in range(n):
            h = ref.rms_norm(x, layer(i)["attn_norm"], CFG.norm_eps)
            want_v = (h @ layer(i)["wv"]).reshape(rows.stop, 4, 16)
            got_v = np.asarray(cache["v"])[u * n + i, 1, rows]
            assert np.abs(got_v - np.asarray(want_v)[rows]).max() < 1e-4
            x = _BLOCK(layer(i), x, positions, **four)
        x = ref.rms_norm(x, closing["norm_f"], CFG.norm_eps)


def test_reading_pass_zeros_slabs_in_every_pass_fails(params, monkeypatch):
    """The control: a program whose passes all write and read slab
    layers 0..n_layers-1 — the (pass, layer) index dropped — is caught
    by the comparison that holds the sound one."""
    tokens = tokens_of(13, 40)
    want = reference_logits(params, tokens)
    monkeypatch.setattr(llama, "_pass_first", lambda c, u: 0 * u)
    chunk = jax.jit(llama.prefill_chunk_into_cache, static_argnums=(6,))
    step = jax.jit(llama.decode_step, static_argnums=(3,))
    got, at, cache = through_the_cache(
        params, tokens, 34, 16, steps=(
            lambda p, t, c, s, st, n, cfg: chunk(p, t, c, s, st, n, cfg),
            lambda p, l, c, a, cfg: step(p, l, c, cfg, a)))
    assert not np.asarray(cache["k"])[3:].any()
    assert rel_l2(got, want[jnp.asarray(at)]).max() > 100 * TOL


@pytest.mark.parametrize("changed", [
    {"total_ut_steps": 2}, {"sandwich": False}, {"norm_between": False}],
    ids=lambda c: next(iter(c)))
def test_each_convention_of_the_loop_is_read(params, changed):
    """A reference that computes ANOTHER model on the same leaves — a
    pass fewer, pre-norm blocks, no norm between the passes — is far
    from the program."""
    tokens = tokens_of(17, 48)
    got = forward(params, jnp.asarray(tokens[None]))[0]
    embed, layer, n, closing, head = ouro.reference_layers(params)
    dims = dims_of(CFG, **{k: v for k, v in changed.items()
                           if k == "total_ut_steps"})
    dims.pop("early_exit_threshold")
    rest = {k: v for k, v in changed.items() if k == "norm_between"}
    block = functools.partial(_BLOCK, sandwich=False) \
        if "sandwich" in changed else _BLOCK
    states, _ = ref.passes(embed, (layer, n), closing, jnp.asarray(tokens),
                           block_fn=block, **dims, **rest)
    assert rel_l2(got, ref.head_of(head, states[-1])).min() > 100 * TOL
    assert rel_l2(got, reference_logits(params, tokens)).max() < TOL


# ------------------------------------------------------- (c) the exit gate

def test_the_exit_rule_of_the_reference():
    gates = [jnp.asarray([0.5, 0.1, 0.9]), jnp.asarray([0.5, 0.2, 0.5]),
             jnp.asarray([0.9, 0.3, 0.1])]
    p = ref.exit_distribution(gates)
    assert np.allclose(p.sum(0), 1.0)
    assert np.allclose(p[:, 0], [0.5, 0.25, 0.25])
    assert np.allclose(p, llama.exit_distribution(jnp.stack(gates)))
    assert ref.exit_pass(gates, 1.0).tolist() == [2, 2, 2]
    assert ref.exit_pass(gates, 0.7).tolist() == [1, 2, 0]


def test_one_pass_is_the_model_without_the_field():
    """``loops == 1``: bit-equal logits to a config without the field,
    through ``forward`` and through the cache."""
    plain = llama.CONFIGS["tiny"]
    looped = dataclasses.replace(plain, loops=1)
    assert looped == plain
    params = llama.init_params(plain, jax.random.PRNGKey(5))
    tokens = tokens_of(19, 30)
    a = jax.jit(lambda p, t: llama.forward(p, t, plain))(
        params, jnp.asarray(tokens[None]))
    b = jax.jit(lambda p, t: llama.forward(
        p, t, dataclasses.replace(plain, loops=1)))(
        params, jnp.asarray(tokens[None]))
    assert (_bits(a) == _bits(b)).all()
    text = [jax.jit(
        lambda p, t, c: llama.prefill_chunk_into_cache(p, t, c, 0, 0, 30,
                                                       cfg)).lower(
        params, jnp.asarray(np.pad(tokens, (0, 2))),
        llama.init_kv_cache(cfg, 2, 64, 32)).as_text()
        for cfg in (plain, dataclasses.replace(plain, loops=1))]
    assert text[0] == text[1]
    assert "loop_pass" not in text[0]


# ------------------------------------------------------------ (d) refusals

@pytest.mark.parametrize("changed,message", [
    ({"window": 8, "window_pattern": (True, False, False)}, "window layers"),
    ({"layer_kinds": ("full", "linear", "linear"), "linear_heads": 4,
      "linear_head_dim": 16, "full_rope": False}, "a recurrent kind"),
    ({"q_lora_rank": 8, "kv_lora_rank": 8, "qk_nope_head_dim": 8,
      "qk_rope_head_dim": 8, "v_head_dim": 8}, "latent attention"),
    ({"num_experts": 4, "n_dense_layers": 1, "dense_mlp_dim": 32},
     "leading dense layers, routed experts"),
    ({"num_experts": 4}, "routed experts"),
    ({"parallel_block": True}, "sandwich_norm puts a norm"),
    ({"norm_after": True}, "sandwich_norm puts a norm"),
    ({"loops": 0}, "at least once"),
    ({"loops": 1}, "exit_gate reads the state between the passes"),
])
def test_what_is_not_computed_is_refused_by_name(changed, message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(CFG, **changed)


def test_the_pipeline_schedule_refuses_loops():
    with pytest.raises(ValueError, match="loops .a looped stack. are not "
                                         "computed"):
        from jax.sharding import Mesh

        mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("pp",))
        llama.loss_fn_pp({}, {"tokens": jnp.zeros((4, 9), jnp.int32)}, CFG,
                         mesh=mesh)


# ----------------------------------------------------------- (e) the engine

class _NoEos:
    eos_token_id = None

    def encode(self, text):
        return [int(t) for t in text.split()]

    def decode(self, ids):
        return " ".join(str(i) for i in ids)


def _engine(params, **kw):
    kw.setdefault("slots", 3)
    kw.setdefault("max_seq", MAX_SEQ)
    kw.setdefault("prefill_chunk_tokens", 16)
    kw.setdefault("tokenizer", _NoEos())
    return LLMEngine(CFG, params, **kw)


def test_the_engine_serves_it_and_counts_the_gate(params):
    prompts = [tokens_of(40, 57).tolist(), [5, 9, 17],
               tokens_of(41, 23).tolist()]
    eng = _engine(params)
    together = eng.generate(prompts, SamplingParams(max_tokens=12))
    want_sum = 0.0
    for prompt, out in zip(prompts, together):
        tokens = np.asarray(prompt + out.token_ids)
        want = np.asarray(reference_logits(params, tokens))
        assert want[len(prompt) - 1:-1].argmax(-1).tolist() == out.token_ids
        # the decode steps' rows: the fed tokens, from the first answer
        # token to the one before the last
        gates = jnp.stack(reference_gates(params, tokens))[
            :, len(prompt):len(tokens) - 1]
        want_sum += float(jnp.sum(jnp.arange(1, 4)[:, None]
                                  * ref.exit_distribution(list(gates))))
    stats = eng.stats
    assert stats["exit_rows"] == 3 * 11 == stats["decode_slots"]
    assert stats["exit_pass_sum"] == pytest.approx(want_sum, abs=0.05)
    assert 1.0 < stats["exit_pass_sum"] / stats["exit_rows"] < 3.0
    programs = (stats["decode_steps"] + stats["chunks"]
                - stats["chunks_fused"])
    assert stats["loop_passes"] in (3 * programs, 3 * (programs + 1))
    plain = LLMEngine("tiny", slots=2, max_seq=64, prefill_chunk_tokens=8)
    assert not {"loop_passes", "exit_rows", "exit_pass_sum"} & set(
        plain.stats)


def test_a_looped_model_names_its_counters_and_its_scopes(params):
    """What a looped model's step programs record, always on:
    ``loop_passes`` in ``LLMEngine.stats`` from the start, the exit
    counters riding the step's one read as the routing counters do, and
    the named scopes a reducer can file operations under — ``loop_pass``
    around a pass (the layers' scan, ``attn_full`` inside its body,
    and the norm that closes it) and ``exit_gate`` around the gate, which is computed
    where its rows are counted: not in the chunk program."""
    assert llama.EXIT_COUNTERS == ("exit_rows", "exit_pass_sum")
    eng = _engine(params, slots=2)
    assert {"loop_passes", "exit_rows", "exit_pass_sum"} <= set(eng.stats)
    assert not any(name in eng.stats for name in llama.ROUTING_COUNTERS)
    eng.generate([[5, 9, 17]], SamplingParams(max_tokens=3))
    stats = eng.stats
    assert stats["d2h_syncs"] == stats["decode_steps"] + 1
    # a lone prompt: its chunk alone and, before it, the mixed program
    # once, empty; then the decode steps
    assert stats["loop_passes"] == 3 * (2 + stats["decode_steps"])
    assert stats["exit_rows"] == stats["decode_slots"] == 2
    decode = eng._decode_jit.lower(
        eng.params, eng.cache, eng._last,
        eng._jnp.ones((2,), bool)).as_text(debug_info=True)
    chunk = eng._prefill_chunk_jit.lower(
        eng.params, eng.cache, eng._jnp.zeros((16,), "int32"), 0, 0,
        3).as_text(debug_info=True)
    mixed = eng._mixed_step_jit.lower(
        eng.params, eng.cache, eng._last, eng._jnp.ones((2,), bool),
        eng._jnp.zeros((16,), "int32"), 0, 0, 3).as_text(debug_info=True)
    for text in (decode, chunk, mixed):
        assert "loop_pass/" in text and "attn_full/" in text
        assert "moe/" not in text
    assert "exit_gate/" in decode and "exit_gate/" in mixed
    plain = LLMEngine("tiny", slots=2, max_seq=64, prefill_chunk_tokens=8)
    text = plain._decode_jit.lower(
        plain.params, plain.cache, plain._last,
        plain._jnp.ones((2,), bool)).as_text(debug_info=True)
    assert "loop_pass" not in text and "exit_gate" not in text


def test_a_slot_moves_with_all_its_slab_layers(params):
    """``_extract`` / ``_install`` (what ``kv_offload.py`` stores) move
    every (pass, layer) pair's rows: the row decodes in another slot of
    another cache what it decodes where it lay."""
    tokens = tokens_of(43, 52)
    eng = _engine(params)
    _, cache = ingest(params, eng.cache, tokens[:51], 0, 16)
    taken = eng._extract_jit(cache, 0)
    assert [t.shape for t in taken] == [(9, MAX_SEQ, 4, 16)] * 2
    moved = eng._install_jit(llama.init_kv_cache(CFG, 3, MAX_SEQ, 16),
                             taken, jnp.int32(51), 2)
    for name in "kv":
        assert (_bits(moved[name][:, 2]) == _bits(cache[name][:, 0])).all()
    last = jnp.full((3,), int(tokens[51]), jnp.int32)
    here, _ = decode_step(params, last, cache,
                          jnp.asarray([True, False, False]))
    there, _ = decode_step(params, last, moved,
                           jnp.asarray([False, False, True]))
    assert (_bits(here[0]) == _bits(there[2])).all()
    assert rel_l2(here[0], reference_logits(params, tokens)[-1]) < TOL


def _turn(eng, sid, prompt, n):
    eng.add_request(list(prompt), SamplingParams(max_tokens=n), admit=False,
                    session_id=sid)
    outs, deadline = [], time.monotonic() + 120
    while eng.has_unfinished():
        outs.extend(eng.step())
        assert time.monotonic() < deadline, "engine never drained"
    assert len(outs) == 1
    return outs[0].token_ids


def test_a_resident_and_an_evicted_session_keep_every_layers_rows(params):
    turns = [(tokens_of(61, 30).tolist(), 6), ([3, 88, 41, 2], 6),
             ([11, 12], 6)]
    base = _engine(params, slots=2)
    want = [_turn(base, "s", p, n) for p, n in turns]
    assert base.stats["offloads"] == 0
    # the resident session's turns are one sequence's: the reference's
    tokens, at = [], []
    for (prompt, n), answer in zip(turns, want):
        tokens += prompt
        at += list(range(len(tokens) - 1, len(tokens) - 1 + n))
        tokens += answer
    logits = np.asarray(reference_logits(params, np.asarray(tokens)))
    assert logits[at].argmax(-1).tolist() == sum(want, [])
    evict = _engine(params, slots=2, kv_idle_evict_s=0.0)
    got = []
    for p, n in turns:
        got.append(_turn(evict, "s", p, n))
        evict.step()                 # idle sweep fires (cutoff = now)
        assert evict._sessions["s"].state == "offloaded"
        evict._free_slots.reverse()  # the restore lands in the other slot
    assert got == want
    *slabs, length = evict._store().get(evict._sessions["s"].handle)
    assert [s.shape for s in slabs] == [(9, MAX_SEQ, 4, 16)] * 2
    assert evict.stats["offload_bytes"] % (2 * 9 * MAX_SEQ * 4 * 16 * 4) == 0


# ------------------------------------------- the factory, and the counts

@pytest.mark.parametrize("key,value,message", [
    ("early_exit_threshold", 0.9, "a row that leaves the loop early is not "
                                  "computed"),
    ("use_sliding_window", True, "use_sliding_window true"),
    ("rope_scaling", {"type": "linear", "factor": 2.0}, "a rope_scaling"),
    ("tie_word_embeddings", True, "tied embeddings"),
    ("layer_types", ["full_attention"] * 47 + ["sliding_attention"],
     "layer_types other than all full_attention"),
    ("hidden_act", "gelu", "an activation other than silu"),
])
def test_the_factory_refuses_what_it_does_not_map(key, value, message):
    with open(PUBLISHED) as f:
        spec = json.load(f)
    with pytest.raises(ValueError, match=message):
        ouro.build({**spec, key: value})


def test_num_params_and_cache_at_the_published_size():
    """The issue's arithmetic, held by ``jax.eval_shape``: nothing of
    this size is ever allocated."""
    with open(PUBLISHED) as f:
        spec = json.load(f)
    assert spec["reduced"] == {}
    assert spec["layer_types"] == ["full_attention"] * 48
    config = ouro.build(spec)
    assert config.n_layers == 48 and config.loops == 4
    assert config.sandwich_norm and config.exit_gate
    assert config.n_kv_heads == config.n_heads == 16
    assert config.head_dim == 128 and not config.flat_kv_heads
    assert config.slab_layers() == (0, 192)
    shapes = jax.eval_shape(
        lambda: llama.init_params(config, jax.random.PRNGKey(0)))

    def count(tree):
        return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))

    a_layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert a_layer == 51_388_416
    assert count(shapes["layers"]) == 48 * a_layer == 2_466_643_968
    assert count(shapes["embed"]) == count(shapes["lm_head"]) == 100_663_296
    assert count(shapes["exit_gate"]) == 2049
    assert count(shapes) == config.num_params() == 2_667_974_657
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(shapes))
    # the cell's cache: 8 slots x 768 positions, 192 slab layers
    cache = jax.eval_shape(lambda: llama.init_kv_cache(config, 8, 768, 64))
    assert cache["k"].shape == cache["v"].shape == (192, 8, 768, 16, 128)
    assert cache["k"].dtype == jnp.bfloat16
    position = 192 * 2 * 16 * 128 * 2
    assert position == 1_572_864                     # 1.5 MiB
    slabs = count({n: cache[n] for n in "kv"}) * 2
    assert slabs == 8 * 768 * position == 9 * 2 ** 30   # 9.0 GiB
    # a token multiplies with every layer's matrices four times
    once = dataclasses.replace(config, loops=1, exit_gate=False)
    more = llama.flops_per_token(config, 128) - llama.flops_per_token(
        once, 128)
    assert more == 6 * 3 * count(shapes["layers"]) + 6 * 2049 \
        + 6 * 3 * 48 * 16 * 128 * 256
