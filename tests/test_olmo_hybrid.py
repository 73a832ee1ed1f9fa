"""Olmo Hybrid's block (gated delta-rule layers with ONE decay a head
over a RECTANGULAR state, d_k != d_v, no low-rank pairs, the output
gate a full projection under SiLU; a multi-head softmax layer without
positional embedding and with an RMSNorm over the whole q and k; the
block's norms on each sub-layer's OUTPUT; dense) on the program's
normal paths, against the plain reference
``chipbench/reference/olmo_hybrid_decoder.py`` on seeded random weights
at a tiny shape, on the CPU in float32.

The measure is the benchmark's own: the relative L2 distance of the
logits, per position.  Both sides compute in float32, so the distance
is rounding and the order of summation — the program runs the
recurrence in blocks of 64 tokens (16 where a test passes ``block``) with
its pair products as matrix products, the reference goes token by
token.  ``TOL`` = 1e-4 is two orders and more under what it must catch:
with the norms on the outputs every sub-layer adds a vector of the
norm's size whatever it computed, so a state not handed over, a decay
left off or a convention of the family dropped each read over 1e-2.

The step programs' attention block is cut to 16 positions.
"""

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
from ant_ray_tpu.ops import delta_rule
from chipbench.models import olmo_hybrid
from chipbench.reference import olmo_hybrid_decoder as ref

CFG = llama.CONFIGS["olmo-hybrid-tiny"]              # two periods
ONE = dataclasses.replace(CFG, n_layers=4)           # one period
LAYER_TYPES = ["linear_attention"] * 3 + ["full_attention"]
TOL = 1e-4
SLOTS, MAX_SEQ = 3, 256
PUBLISHED = os.path.join(os.path.dirname(__file__), "..", "chipbench",
                         "configs", "olmo-hybrid-7b.json")


@pytest.fixture(autouse=True)
def short_blocks(monkeypatch):
    monkeypatch.setattr(llama, "ATTEND_BLOCK", 16)


def dims_of(cfg):
    return dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                rope_theta=0.0, norm_eps=cfg.norm_eps)


def seeded_params(cfg=CFG, seed=0):
    """Seeded weights, less bland than the initialiser's: matrices large
    enough that the gates decide and attention attends, norm weights
    that are not all ones.  The decay's leaves and the taps stay as
    drawn."""
    p = llama.init_params(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 128))

    def livelier(stack):
        out = {}
        for name, leaf in stack.items():
            if name in ("a_log", "dt_bias", "conv_w"):
                out[name] = leaf
            elif name.endswith("norm") or name.startswith("ln_"):
                out[name] = leaf * jax.random.uniform(
                    next(keys), leaf.shape, minval=0.5, maxval=1.5)
            else:
                out[name] = leaf * 6.0
        return out

    return {**p, "norm_f": p["norm_f"] * 0.7,
            "layers": livelier(p["layers"]),
            llama.LINEAR: livelier(p[llama.LINEAR])}


@pytest.fixture(scope="module")
def params():
    return seeded_params()


_BLOCK = jax.jit(ref.block, static_argnames=(       # as the harness does
    "n_heads", "n_kv_heads", "rope_theta", "norm_eps", "reordered_norm",
    "qk_norm"))


def reference_logits(params, tokens, cfg=CFG, **changed):
    embed, layer, n, norm_f, head = olmo_hybrid.reference_layers(
        params, LAYER_TYPES * (cfg.n_layers // 4))
    return ref.forward(embed, (layer, n), norm_f, head, jnp.asarray(tokens),
                       block_fn=_BLOCK, **{**dims_of(cfg), **changed})


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


def ingest(params, cache, tokens, slot, chunk, start=0, cfg=CFG,
           carry=True):
    """``tokens`` into ``slot`` from position ``start`` on, in chunks ->
    (logits at each chunk's last token, cache); ``carry`` False empties
    the slot's state before every chunk but the first."""
    logits = []
    for at in range(0, len(tokens), chunk):
        part = tokens[at:at + chunk]
        buf = np.zeros((chunk,), np.int32)
        buf[:len(part)] = part
        if at and not carry:
            cache = {**cache, **{name: cache[name].at[:, slot].set(0)
                                 for name in llama.state_slabs(cfg)}}
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


def through_the_cache(params, tokens, prompt, chunk, slot=1, cfg=CFG,
                      carry=True):
    """-> logits at every chunk's end and from the last prompt token
    on, with the positions they belong to."""
    cache = llama.init_kv_cache(cfg, SLOTS, MAX_SEQ, chunk)
    ends, cache = ingest(params, cache, tokens[:prompt], slot, chunk,
                         cfg=cfg, carry=carry)
    at = [min(a + chunk, prompt) - 1 for a in range(0, prompt, chunk)]
    rest, cache = decode(params, cache, tokens[prompt:], slot, cfg=cfg)
    return jnp.stack(ends + rest), at + list(range(prompt, len(tokens)))


# ------------------------------------------------ (a) against the reference

@pytest.mark.parametrize("cfg", [ONE, CFG], ids=["one-period", "two-periods"])
def test_forward_is_the_reference(cfg):
    params = seeded_params(cfg, seed=2)
    # one sequence a call: two of them under ``vmap`` with as many
    # heads as a block has leaves (4 x 16 of 64) abort XLA's CPU
    # compiler in ``_unit_lower_inverse`` — Solar's preset does too
    for seed in (3, 4):
        tokens = tokens_of(seed, 70)
        got = forward(params, jnp.asarray(tokens[None]), cfg=cfg)[0]
        assert rel_l2(got, reference_logits(params, tokens, cfg)).max() < TOL


@pytest.mark.parametrize("chunk,prompt", [
    (16, 64),      # whole chunks, each a block filled up
    (16, 53),      # a last chunk padded
    (128, 200),    # two blocks a chunk, a last chunk padded
    (64, 9),       # one chunk, mostly padding
])
def test_chunks_and_decode_through_the_cache_are_forward(params, chunk,
                                                         prompt):
    tokens = tokens_of(100 + prompt, prompt + 6)
    got, at = through_the_cache(params, tokens, prompt, chunk)
    want = reference_logits(params, tokens)
    assert rel_l2(got, want[jnp.asarray(at)]).max() < TOL


def test_a_state_that_is_not_handed_over_is_caught(params):
    tokens = tokens_of(11, 46)
    want = reference_logits(params, tokens)
    got, at = through_the_cache(params, tokens, 40, 16, carry=False)
    err = rel_l2(got, want[jnp.asarray(at)])
    assert err[0] < TOL                    # the first chunk had no hand-over
    assert err[1:].min() > 100 * TOL


@pytest.mark.parametrize("changed", [
    {"write_scale": 1.0}, {"reordered_norm": False}, {"qk_norm": False},
    {"rope_theta": 10000.0}], ids=lambda c: next(iter(c)))
def test_each_convention_of_the_family_is_read(params, changed):
    """The reference computing ANOTHER model on the same leaves — beta
    without its factor 2, pre-norm blocks, q and k as projected, the
    full layers rotated — is far from the program."""
    tokens = tokens_of(12, 40)
    got = forward(params, jnp.asarray(tokens[None]))[0]
    assert rel_l2(got, reference_logits(params, tokens)).max() < TOL
    other = reference_logits(params, tokens, **changed)
    assert rel_l2(got, other)[8:].min() > 100 * TOL


# --------------------------------------------- (b) the three scalar forms

def _gdn_inputs(seed, tokens, heads=3, d_k=8, d_v=20, fastest=1.6):
    """q and k of unit length, beta up to 2, log-decays a HEAD from
    -0.001 to -``fastest`` a token, a state that is not empty."""
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.normal(size=(tokens, heads, d_k))) * d_k ** -0.5
    k = unit(rng.normal(size=(tokens, heads, d_k)))
    v = rng.normal(size=(tokens, heads, d_v))
    g = -np.exp(rng.uniform(np.log(1e-3), np.log(fastest),
                            size=(tokens, heads)))
    beta = rng.uniform(0.0, 2.0, size=(tokens, heads))
    s0 = rng.normal(size=(heads, d_k, d_v))
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta, s0))


def _scan(q, k, v, g, beta, s0):
    """The recurrence token by token: the channel form's scan with the
    head's one decay repeated over its d_k channels."""
    return delta_rule.delta_rule_scan(
        q, k, v, jnp.broadcast_to(g[..., None], k.shape), beta, s0)


@pytest.mark.parametrize("tokens,block", [
    (64, 16), (53, 16), (70, 32), (9, 64), (128, 64)])
def test_blocks_are_the_recurrence_token_by_token(tokens, block):
    q, k, v, g, beta, s0 = _gdn_inputs(tokens, tokens)
    want, s_want = _scan(q, k, v, g, beta, s0)
    got, s_got = delta_rule.chunk_gdn(q, k, v, g, beta, s0, block=block)
    assert got.shape == (tokens, 3, 20) and s_got.shape == (3, 8, 20)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) < 2e-5 * scale
    assert float(jnp.abs(s_got - s_want).max()) < 2e-5 * float(
        jnp.abs(s_want).max())


def test_a_fast_decay_does_not_overflow_a_block():
    """g = -1.6 a token is exp(102) after 64: the block form only ever
    takes exp of a DIFFERENCE of cumulative log-decays."""
    q, k, v, g, beta, s0 = _gdn_inputs(5, 64)
    g = jnp.full_like(g, -1.6)
    got, s_got = delta_rule.chunk_gdn(q, k, v, g, beta, s0, block=64)
    want, s_want = _scan(q, k, v, g, beta, s0)
    assert bool(jnp.isfinite(got).all()) and bool(jnp.isfinite(s_got).all())
    assert float(jnp.abs(got - want).max()) < 2e-5 * float(
        jnp.abs(want).max())


def test_the_scalar_form_is_the_channel_form_with_the_decay_repeated():
    q, k, v, g, beta, s0 = _gdn_inputs(6, 40, d_v=8)
    got, s_got = delta_rule.chunk_gdn(q, k, v, g, beta, s0, block=16)
    want, s_want = delta_rule.chunk_delta_rule(
        q, k, v, jnp.broadcast_to(g[..., None], k.shape), beta, s0, block=16)
    assert float(jnp.abs(got - want).max()) < 2e-5 * float(
        jnp.abs(want).max())
    assert float(jnp.abs(s_got - s_want).max()) < 2e-5 * float(
        jnp.abs(s_want).max())


def test_a_step_is_the_recurrence_and_an_idle_row_keeps_its_state():
    q, k, v, g, beta, s0 = _gdn_inputs(7, 12)
    want, _ = _scan(q, k, v, g, beta, s0)
    s = jnp.stack([s0, s0 * 2.0])
    active = jnp.asarray([True, False])
    for t in range(12):
        row = [jnp.stack([x[t], x[t]]) for x in (q, k, v, g, beta)]
        out, s = delta_rule.gdn_step(*row, s, active)
        assert float(jnp.abs(out[0] - want[t]).max()) < 2e-5
        assert (_bits(s[1]) == _bits(s0 * 2.0)).all()


def test_padding_neither_decays_nor_writes():
    q, k, v, g, beta, s0 = _gdn_inputs(8, 21)
    _, s_want = _scan(q, k, v, g, beta, s0)
    pad = [jnp.pad(x, ((0, 11),) + ((0, 0),) * (x.ndim - 1))
           for x in (q, k, v, g, beta)]
    _, s_got = delta_rule.chunk_gdn(*pad, s0, block=16)
    assert float(jnp.abs(s_got - s_want).max()) < 2e-5 * float(
        jnp.abs(s_want).max())


# ------------------------------------------------- (c) the cache's state

def test_the_state_in_the_cache():
    cache = llama.init_kv_cache(CFG, SLOTS, MAX_SEQ, 16)
    # six linear layers of eight; a head's state d_k x d_v, float32
    assert cache["s"].shape == (6, SLOTS, 4, 8, 16)
    assert cache["s"].dtype == jnp.float32
    assert cache["conv"].shape == (6, SLOTS, 3, 4 * (2 * 8 + 16))
    assert cache["conv"].dtype == CFG.dtype
    assert cache["s"].nbytes == 6 * SLOTS * 4 * 8 * 16 * 4
    # two softmax layers' slabs, four KV heads of 16 (up to eight heads
    # a position keeps its heads axis)
    assert cache["k"].shape == cache["v"].shape == (2, SLOTS, MAX_SEQ, 4, 16)
    assert llama.state_slabs(CFG)["s"] == ((4, 8, 16), jnp.float32)
    assert CFG.n_linear == CFG.n_recurrent == 6 and CFG.recurrent == "linear"
    assert CFG.place(3) == ("layers", 1, 0)
    assert CFG.place(2) == (llama.LINEAR, 3, 2)


def test_the_channel_kinds_cache_is_what_it_was():
    solar = llama.CONFIGS["solar2-tiny"]
    assert llama.state_slabs(solar) == {
        "s": ((4, 16, 16), jnp.float32), "conv": ((3, 3 * 4 * 16),
                                                  solar.dtype)}
    assert solar.linear_widths == (16, 16) and not solar.flat_kv_heads
    assert "w_fa" in llama.param_shapes(solar)[llama.LINEAR]
    assert "w_a" not in llama.param_shapes(solar)[llama.LINEAR]
    assert llama._delta_forms(solar) == (delta_rule.chunk_delta_rule,
                                         delta_rule.delta_rule_step)
    assert llama._delta_forms(CFG) == (delta_rule.chunk_gdn,
                                       delta_rule.gdn_step)


def test_thirty_heads_lie_side_by_side_in_a_position(params):
    """Over eight KV heads that are no whole sublane tiles a position
    holds its heads on ONE axis (``LlamaConfig.flat_kv_heads``): the
    same logits through such slabs."""
    wide = dataclasses.replace(CFG, n_heads=12, n_kv_heads=12, dim=96,
                               mlp_dim=64, n_layers=4)
    assert wide.flat_kv_heads and not CFG.flat_kv_heads
    assert not dataclasses.replace(wide, n_kv_heads=4).flat_kv_heads
    assert llama.kv_slabs(wide) == {"k": (96,), "v": (96,)}
    p = seeded_params(wide, seed=4)
    tokens = tokens_of(21, 45)
    got, at = through_the_cache(p, tokens, 39, 16, cfg=wide)
    want = forward(p, jnp.asarray(tokens[None]), cfg=wide)[0]
    assert rel_l2(got, want[jnp.asarray(at)]).max() < TOL
    assert rel_l2(want, reference_logits(p, tokens, wide)).max() < TOL


def test_heads_side_by_side_shard_over_tp_as_a_heads_axis_does():
    """The ``tp`` engine shards the axis behind the positions: a heads
    axis, or the heads side by side — heads-major, so halves of the
    axis are halves of the heads."""
    cfg = dataclasses.replace(llama.CONFIGS["tiny"], n_heads=12,
                              n_kv_heads=12, dim=96, head_width=8)
    assert cfg.flat_kv_heads
    weights = llama.init_params(cfg, jax.random.PRNGKey(0))
    prompts = [[5, 9, 17, 33, 2, 7, 8, 9, 10, 11], [3, 4]]
    outs = []
    for tp in (1, 2):
        eng = LLMEngine(cfg, weights, slots=2, max_seq=64,
                        prefill_chunk_tokens=8, tokenizer=_NoEos(),
                        tensor_parallel_size=tp)
        assert eng.cache["k"].shape == (2, 2, 64, 96)
        outs.append([out.token_ids for out in eng.generate(
            prompts, SamplingParams(max_tokens=6))])
    assert outs[0] == outs[1]


def test_a_slot_used_again_gives_what_a_fresh_cache_gives(params):
    first, second = tokens_of(31, 50), tokens_of(32, 37)
    cache = llama.init_kv_cache(CFG, SLOTS, MAX_SEQ, 16)
    _, cache = ingest(params, cache, first, 1, 16)
    again, _ = ingest(params, cache, second, 1, 16)
    fresh, _ = ingest(params, llama.init_kv_cache(CFG, SLOTS, MAX_SEQ, 16),
                      second, 1, 16)
    for a, b in zip(again, fresh):
        assert (_bits(a) == _bits(b)).all()


def test_a_row_between_two_of_its_chunks_is_not_advanced(params):
    tokens = tokens_of(33, 40)
    cache = llama.init_kv_cache(CFG, SLOTS, MAX_SEQ, 16)
    _, cache = ingest(params, cache, tokens[:16], 0, 16)
    held = {name: np.asarray(cache[name][:, 0]) for name in ("s", "conv")}
    # slot 2 decodes while slot 0 waits for its next chunk
    _, cache = ingest(params, cache, tokens_of(34, 5), 2, 16)
    _, cache = decode(params, cache, [3, 4, 5], 2)
    for name, before in held.items():
        assert (np.asarray(cache[name][:, 0]).view(np.uint8)
                == before.view(np.uint8)).all()


# ----------------------------------------------------------- (d) the engine

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


def test_the_engine_serves_it_and_a_request_among_others_is_itself_alone(
        params):
    prompts = [tokens_of(40, 57).tolist(), [5, 9, 17],
               tokens_of(41, 23).tolist()]
    eng = _engine(params)
    together = eng.generate(prompts, SamplingParams(max_tokens=12))
    for prompt, out in zip(prompts, together):
        alone = _engine(params).generate([prompt],
                                         SamplingParams(max_tokens=12))
        assert alone[0].token_ids == out.token_ids
    tokens = np.asarray(prompts[0] + together[0].token_ids)
    want = np.asarray(reference_logits(params, tokens))[56:-1].argmax(-1)
    assert want.tolist() == together[0].token_ids
    # the recurrent counters count THIS model's layers: six of eight
    stats = eng.stats
    assert stats["recurrent_resets"] == 3
    assert stats["recurrent_chunk_rows"] == 6 * 16 * stats["chunks"]
    assert stats["recurrent_chunk_tokens"] == 6 * (57 + 3 + 23)
    assert stats["recurrent_slot_rows"] == 6 * 3 * stats["decode_steps"]
    assert 0 < stats["recurrent_decode_rows"] <= stats["recurrent_slot_rows"]


def test_the_state_moves_with_the_slabs(params):
    """``_extract`` / ``_install`` (what ``kv_offload.py`` stores) move
    a slot's rectangular ``s`` and its ``conv`` by the slabs' own rule:
    the row decodes in another slot of another cache what it decodes
    where it lay."""
    tokens = tokens_of(43, 52)
    eng = _engine(params)
    _, cache = ingest(params, eng.cache, tokens[:51], 0, 16)
    taken = eng._extract_jit(cache, 0)
    moved = eng._install_jit(llama.init_kv_cache(CFG, 3, MAX_SEQ, 16),
                             taken, jnp.int32(51), 2)
    assert (_bits(moved["s"][:, 2]) == _bits(cache["s"][:, 0])).all()
    assert (_bits(moved["conv"][:, 2]) == _bits(cache["conv"][:, 0])).all()
    last = jnp.full((3,), int(tokens[51]), jnp.int32)
    here, _ = decode_step(params, last, cache,
                          jnp.asarray([True, False, False]))
    there, _ = decode_step(params, last, moved,
                           jnp.asarray([False, False, True]))
    assert (_bits(here[0]) == _bits(there[2])).all()
    assert rel_l2(here[0], reference_logits(params, tokens)[-1]) < TOL


def test_what_a_recurrent_state_is_refused_by_name(params):
    eng = _engine(params)
    with pytest.raises(ValueError, match="sessions are not kept over a "
                                         "recurrent state"):
        eng.add_request([1, 2, 3], session_id="turns")
    with pytest.raises(ValueError, match="a recurrent state .* is not "
                                         "sharded"):
        _engine(params, tensor_parallel_size=2)
    with pytest.raises(ValueError, match="no linear layers"):
        llama.loss_fn_pp(params, {"tokens": jnp.zeros((2, 9), jnp.int32)},
                         CFG, mesh=type("M", (), {"shape": {"pp": 2}})())
    with pytest.raises(ValueError, match="keep a square state"):
        dataclasses.replace(llama.CONFIGS["solar2-tiny"],
                            linear_value_dim=32)
    with pytest.raises(ValueError, match="linear layers state their heads"):
        dataclasses.replace(CFG, linear_heads=0)
    with pytest.raises(ValueError, match="norm_after reorders a sequential"):
        dataclasses.replace(llama.CONFIGS["cmdaplus-tiny"], norm_after=True)


def test_the_probe_of_two_geometries_holds_the_program_to_both():
    """``chipbench/replica_median_pair.py`` (the configuration's
    ``serve.replica``) through the configuration FILE, its factory and
    ``reference_layers``: the traffic file's probe of whole chunks and,
    in the same slot after it, one whose prompt ends a few tokens
    behind a chunk boundary; both medians in ``rel_l2``.  A state that
    is not handed from chunk to chunk is caught by the second."""
    from chipbench.replica_median_pair import MedianPairProbeLLMServer

    with open(PUBLISHED) as f:
        spec = json.load(f)
    assert spec["serve"]["replica"] == (
        "chipbench.replica_median_pair:MedianPairProbeLLMServer")
    spec.update(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
        vocab_size=256, intermediate_size=96, max_position_embeddings=512,
        linear_num_key_heads=4, linear_num_value_heads=4,
        linear_key_head_dim=8, linear_value_head_dim=16,
        # two periods: with the norms on the outputs bfloat16's rounding
        # grows layer by layer (0.008 behind 4 layers at this width,
        # 0.10 behind 8, over 0.5 behind 16; the chip reads 0.17 behind
        # 16 at the published widths)
        num_hidden_layers=8)
    spec["serve"]["probe_short_last_chunk"] = {"tokens_behind_boundary": 5}
    server = MedianPairProbeLLMServer(spec, slots=2, max_seq=256, seed=3,
                                      prefill_chunk_tokens=32)
    try:
        eng = server.engine
        assert eng.config.kinds == ("linear",) * 3 + ("full",)
        assert eng.config.n_layers == 8 and eng.config.norm_after
        out = server.probe_logits(7, 128, 4)
        assert out["prompt_tokens"] == [128, 96 + 5]
        assert out["positions"] == 10 and len(out["rel_l2"]) == 2
        assert out["rel_l2"] == [statistics.median(by) for by in
                                 out["rel_l2_by_position"]]
        # bfloat16 weights against the float32 reference, on the CPU
        assert max(out["rel_l2"]) < 0.2
        plain = eng._prefill_chunk_jit

        def not_handed_over(params, cache, buf, slot, start, n):
            if start:
                cache = {**cache, **{
                    name: cache[name].at[:, slot].set(0)
                    for name in llama.state_slabs(eng.config)}}
            return plain(params, cache, buf, slot, start, n)

        eng._prefill_chunk_jit = not_handed_over
        lost = server.probe_logits(7, 128, 4)["rel_l2"]
        assert lost[1] > 3 * max(out["rel_l2"]) and lost[1] > 0.5
    finally:
        server.shutdown()


# ------------------------------------------- the factory, and the counts

@pytest.mark.parametrize("key,value,message", [
    ("rope_parameters", {"rope_theta": 500000.0}, "a rotary base"),
    ("attention_bias", True, "a projection bias"),
    ("linear_allow_neg_eigval", False, "write strengths held under 1"),
    ("linear_num_key_heads", 15, "fewer key than value heads"),
    ("tie_word_embeddings", True, "a tied head"),
    ("hidden_act", "gelu", "an activation other than silu"),
    ("num_attention_heads", 28, "not a whole number of heads"),
])
def test_the_factory_refuses_what_it_does_not_map(key, value, message):
    with open(PUBLISHED) as f:
        spec = json.load(f)
    with pytest.raises(ValueError, match=message):
        olmo_hybrid.build({**spec, key: value})


def test_num_params_and_cache_at_the_published_cut():
    """The issue's arithmetic, held by ``jax.eval_shape``: nothing of
    this size is ever allocated."""
    with open(PUBLISHED) as f:
        spec = json.load(f)
    assert list(spec["reduced"]) == ["num_hidden_layers"]
    assert spec["layer_types"] == LAYER_TYPES * 8
    config = olmo_hybrid.build(spec)
    assert config.kinds == ("linear", "linear", "linear", "full")
    assert config.n_layers == 16 and config.n_linear == 12
    assert config.linear_widths == (96, 192) and config.linear_rank == 0
    assert config.norm_after and config.qk_norm and not config.full_rope
    assert config.n_kv_heads == config.n_heads == 30
    assert config.head_dim == 128 and config.flat_kv_heads
    shapes = jax.eval_shape(
        lambda: llama.init_params(config, jax.random.PRNGKey(0)))

    def count(tree):
        return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))

    linear, full = shapes[llama.LINEAR], shapes["layers"]
    mlp = 3 * 3840 * 11008
    mix = count({k: v for k, v in linear.items() if k not in (
        "ln_attn", "ln_mlp", "w_gate", "w_up", "w_down")}) // 12
    assert mix == 88_750_332
    assert count(linear) == 12 * 215_570_172
    assert count(linear) // 12 == mix + mlp + 2 * 3840
    assert count(full) == 4 * 185_809_920
    assert count(full) // 4 == 4 * 3840 * 3840 + 2 * 3840 + mlp + 2 * 3840
    assert count(linear) + count(full) == 3_330_081_744
    assert count(shapes) == config.num_params() == 4_100_788_944
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(shapes))
    # the cell's cache: 8 slots x 12,288 positions
    cache = jax.eval_shape(lambda: llama.init_kv_cache(config, 8, 12288, 512))
    assert cache["k"].shape == cache["v"].shape == (4, 8, 12288, 30 * 128)
    assert cache["k"].dtype == jnp.bfloat16
    # 61,440 B a position over the four softmax layers
    position = 2 * 4 * 30 * 128 * 2
    assert position == 61_440
    slabs = count({n: cache[n] for n in "kv"}) * 2
    assert slabs == 8 * 12288 * position            # 5.625 GiB
    assert cache["s"].shape == (12, 8, 30, 96, 192)
    assert cache["s"].dtype == jnp.float32
    assert cache["conv"].shape == (12, 8, 3, 30 * (2 * 96 + 192))
    state = count(cache["s"]) * 4 + count(cache["conv"]) * 2
    # a slot's state in twelve linear layers: 27.4 MB, what 446
    # positions of the four softmax layers weigh
    assert state // 8 == 12 * (30 * 96 * 192 * 4 + 3 * 11520 * 2)
    assert 445 < state / 8 / position < 451
