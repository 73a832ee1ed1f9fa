"""Granite 4.0-H's block (Mamba-2 state-space layers beside a softmax
layer without positional embedding: a recurrent state a slot of ANOTHER
shape than the delta rule's, carried across blocks, chunks and decode
steps; a softmax router over more experts than are held, a shared
SwiGLU, four scalar multipliers, the head tied) on the program's normal
paths, against the plain reference
``chipbench/reference/granite_hybrid_decoder.py`` on seeded random
weights at a tiny shape, on the CPU in float32.

The measure is the benchmark's own: the relative L2 distance of the
logits, per position.  Both sides compute in float32, so the distance
is rounding and the order of summation — the program runs the
recurrence in blocks (cut to 16 tokens here, ``ssd.BLOCK``) as matrix
products, the reference goes token by token: 3e-6 at worst here.
``TOL`` = 5e-5 is two orders and more under what it must catch: the
state not handed from chunk to chunk (over 3e-3 below: at 64 wide what a
layer writes and reads its state along is smaller than at 4,096), the
decay left off, a multiplier dropped (each over 1e-2).

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
from ant_ray_tpu.ops import delta_rule, ssd
from chipbench.models import granite_hybrid
from chipbench.reference import granite_hybrid_decoder as ref

CFG = llama.CONFIGS["granite-h-tiny"]                # two periods
ONE = dataclasses.replace(CFG, n_layers=4)           # one period
LAYER_TYPES = ["mamba", "mamba", "attention", "mamba"] * 2
TOL = 5e-5
SLOTS, MAX_SEQ = 3, 256
PUBLISHED = os.path.join(os.path.dirname(__file__), "..", "chipbench",
                         "configs", "granite-4.0-h-small.json")


@pytest.fixture(autouse=True)
def short_blocks(monkeypatch):
    monkeypatch.setattr(llama, "ATTEND_BLOCK", 16)
    monkeypatch.setattr(ssd, "BLOCK", 16)


def dims_of(cfg):
    return dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps,
                experts_per_token=cfg.experts_per_token,
                first_expert=cfg.first_expert,
                residual_multiplier=cfg.residual_multiplier,
                attention_multiplier=cfg.attention_multiplier,
                embedding_multiplier=cfg.embedding_multiplier,
                logits_scaling=cfg.logits_scaling)


def seeded_params(cfg=CFG, seed=0):
    """Seeded weights, less bland than the initialiser's: matrices
    large enough that the router decides and attention attends; norm
    weights and skips that are not all ones, a convolution bias that is
    not zero.  The decay's leaves and the taps stay as drawn."""
    p = llama.init_params(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 128))

    def livelier(stack):
        out = {}
        for name, leaf in stack.items():
            if name in ("a_log", "dt_bias", "conv_w"):
                out[name] = leaf
            elif name == "conv_b":
                out[name] = 0.3 * jax.random.normal(next(keys), leaf.shape)
            elif name.startswith("ln_") or name in ("ssm_norm", "d_skip"):
                out[name] = leaf * jax.random.uniform(
                    next(keys), leaf.shape, minval=0.5, maxval=1.5)
            else:
                out[name] = leaf * 6.0
        return out

    return {**p, "norm_f": p["norm_f"] * 0.7,
            "layers": livelier(p["layers"]),
            llama.SSM: livelier(p[llama.SSM])}


@pytest.fixture(scope="module")
def params():
    return seeded_params()


_BLOCK = jax.jit(ref.block, static_argnames=(       # as the harness does
    "n_heads", "n_kv_heads", "rope_theta", "norm_eps"))


def reference_logits(params, tokens, cfg=CFG, **changed):
    embed, layer, n, norm_f, head = granite_hybrid.reference_layers(
        params, LAYER_TYPES)
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
    tokens = tokens_of(1, 75)               # four blocks of 16 and a tail
    got = forward(params, jnp.asarray(tokens)[None], cfg=cfg)[0]
    want = reference_logits(params, tokens, cfg)
    assert rel_l2(got, want).max() < TOL


@pytest.mark.parametrize("chunk,prompt", [
    (32, 64), (24, 65), (32, 35), (8, 75)],
    ids=["chunks-divide-the-prompt", "a-padded-last-chunk-of-17",
         "a-padded-last-chunk-of-3", "chunks-shorter-than-a-block"])
def test_chunks_and_decode_through_the_cache_are_forward(params, chunk,
                                                         prompt):
    """Prefill in chunks — the state handed from block to block and from
    chunk to chunk, a last chunk padded behind its real tokens — then
    decode steps, equal ``forward`` (and the reference) at every
    position read."""
    tokens = tokens_of(2, prompt + 9)
    got, at = through_the_cache(params, tokens, prompt, chunk)
    want = reference_logits(params, tokens)
    assert rel_l2(got, want[np.asarray(at)]).max() < TOL
    whole = forward(params, jnp.asarray(tokens)[None])[0]
    assert rel_l2(got, whole[np.asarray(at)]).max() < TOL


def test_a_state_that_is_not_handed_over_is_caught(params):
    """What ``TOL`` must catch: the slot's state emptied between two
    chunks, and a decay left off."""
    tokens = tokens_of(3, 48)
    want = reference_logits(params, tokens)[-1]
    cache = llama.init_kv_cache(CFG, SLOTS, MAX_SEQ, 24)
    _, cache = ingest(params, cache, tokens[:24], 1, 24)
    dropped = {**cache, "s": cache["s"].at[:, 1].set(0.0)}
    (kept,), _ = ingest(params, cache, tokens[24:], 1, 24, start=24)
    (lost,), _ = ingest(params, dropped, tokens[24:], 1, 24, start=24)
    assert rel_l2(kept, want) < TOL < 3e-3 < rel_l2(lost, want)
    undamped = {**params, llama.SSM: {
        **params[llama.SSM],
        "a_log": jnp.full_like(params[llama.SSM]["a_log"], -jnp.inf)}}
    off = forward(undamped, jnp.asarray(tokens)[None])[0, -1]
    assert rel_l2(off, want) > 1e-2


@pytest.mark.parametrize("name,other", [
    ("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
    ("attention_multiplier", 0.25), ("logits_scaling", 1.0)])
def test_each_multiplier_is_read(params, name, other):
    """The program with one multiplier set to what a model without it
    has (the softmax scale to head_dim^-1/2) is no longer the reference,
    and is the reference given the same value: each is read, by both."""
    tokens = tokens_of(4, 40)
    changed = dataclasses.replace(CFG, **{name: other})
    got = forward(params, jnp.asarray(tokens)[None], cfg=changed)[0]
    assert rel_l2(got, reference_logits(params, tokens)).max() > 1e-2
    assert rel_l2(got, reference_logits(
        params, tokens, **{name: other})).max() < TOL


# ------------------------------------- (b) the block form, the step, the conv

def _ssd_inputs(seed, tokens, heads=3, width=8, state=16, fastest=16.0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(keys[0], (tokens, heads, width))
    dt = jnp.exp(jax.random.uniform(keys[1], (tokens, heads),
                                    minval=np.log(1e-3), maxval=np.log(0.1)))
    dt = dt.at[:, 0].set(0.1)                # one head always fastest
    a = jnp.array([fastest, 1.0, 4.0][:heads])
    b, c = (jax.random.normal(key, (tokens, state)) for key in keys[2:4])
    d = jax.random.uniform(keys[4], (heads,), minval=0.5, maxval=1.5)
    return x, dt, a, b, c, d, jax.random.normal(
        keys[5], (heads, width, state))


@pytest.mark.parametrize("tokens,block", [
    (256, 256), (512, 256), (150, 64), (70, 16), (40, 256)],
    ids=["one-block", "two-blocks", "a-padded-block-of-22",
         "a-padded-block-of-6", "shorter-than-a-block"])
def test_blocks_are_the_recurrence_token_by_token(tokens, block):
    """A head with ``dt * A`` = 1.6 a token over a whole block: the
    cumulative log-decay reaches -410 in a block of 256 (-102 in one of
    64), so ``exp(-L)`` alone is far past float32's 3.4e38 — the block
    form takes differences only and stays finite and right."""
    x, dt, a, b, c, d, s0 = _ssd_inputs(5, tokens)
    deepest = float(jnp.sum(-dt[:min(block, tokens), 0] * a[0]))
    assert dt[0, 0] * a[0] >= 1.6
    if min(block, tokens) >= 64:
        assert np.exp(-deepest) > np.finfo(np.float32).max
    want_y, want_s = ssd.ssd_scan(x, dt, a, b, c, d, s0)
    got_y, got_s = jax.jit(functools.partial(ssd.chunk_ssd, block=block))(
        x, dt, a, b, c, d, s0)
    assert np.isfinite(np.asarray(got_y)).all()
    np.testing.assert_allclose(got_y, want_y, rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(got_s, want_s, rtol=1e-4, atol=2e-5)


def test_a_step_is_the_recurrence_and_an_idle_row_keeps_its_state():
    x, dt, a, b, c, d, s0 = _ssd_inputs(6, 5)
    want_y, _ = ssd.ssd_scan(x, dt, a, b, c, d, s0)
    s = jnp.stack([s0, s0])
    for t in range(5):
        two = [jnp.stack([v[t], v[t]]) for v in (x, dt)]
        rows = [jnp.stack([v[t], v[t]]) for v in (b, c)]
        y, s = ssd.ssd_step(*two, a, *rows, d, s, jnp.array([True, False]))
        np.testing.assert_allclose(y[0], want_y[t], rtol=1e-5, atol=1e-6)
    assert (_bits(s[1]) == _bits(s0)).all()


def test_padding_and_the_biased_convolutions_tail():
    """A padded token (dt = 0) changes neither the outputs before it nor
    the state; the convolution WITH bias over a sequence cut in two is
    the convolution over the whole, its tail the last real inputs, and
    without a bias it is what it was (Solar's taps have none)."""
    x, dt, a, b, c, d, s0 = _ssd_inputs(7, 40)
    y, s = ssd.chunk_ssd(x, dt, a, b, c, d, s0)
    pad = [jnp.concatenate([v, 5.0 + jnp.zeros_like(v[:24])])
           for v in (x, b, c)]
    y2, s2 = ssd.chunk_ssd(pad[0], jnp.pad(dt, ((0, 24), (0, 0))), a,
                           pad[1], pad[2], d, s0)
    np.testing.assert_allclose(y2[:40], y, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(s2, s, rtol=1e-6, atol=1e-6)
    u = jax.random.normal(jax.random.PRNGKey(8), (30, 12))
    w = jax.random.normal(jax.random.PRNGKey(9), (4, 12))
    bias = jax.random.normal(jax.random.PRNGKey(10), (12,))
    whole, _ = delta_rule.causal_conv(u, jnp.zeros((3, 12)), w, bias)
    plain, _ = delta_rule.causal_conv(u, jnp.zeros((3, 12)), w)
    np.testing.assert_allclose(whole, plain + bias, rtol=1e-6, atol=1e-6)
    first, ext = delta_rule.causal_conv(u[:17], jnp.zeros((3, 12)), w, bias)
    second, _ = delta_rule.causal_conv(u[17:], ext[-3:], w, bias)
    np.testing.assert_allclose(jnp.concatenate([first, second]), whole,
                               rtol=1e-6, atol=1e-6)
    step, tail = delta_rule.causal_conv_step(u[17][None], ext[-3:][None], w,
                                             bias)
    np.testing.assert_allclose(step[0], whole[17], rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(tail[0]),
                                  np.asarray(u[15:18]))


# ------------------------------------------- (c) slots, neighbours, idle rows

def test_a_slot_used_again_gives_what_a_fresh_cache_gives(params):
    """A chunk whose ``start`` is 0 begins from an EMPTY state whatever
    the slot's last occupant left — selected in the program, no reset
    call: bit-equal logits to the same prompt in a fresh cache."""
    first, second = tokens_of(10, 70), tokens_of(11, 50)
    cache = llama.init_kv_cache(CFG, SLOTS, MAX_SEQ, 24)
    _, cache = ingest(params, cache, first, 1, 24)
    _, cache = decode(params, cache, tokens_of(12, 5), 1)
    assert float(jnp.abs(cache["s"][:, 1]).max()) > 0
    again, cache = ingest(params, cache, second, 1, 24)
    fresh, clean = ingest(
        params, llama.init_kv_cache(CFG, SLOTS, MAX_SEQ, 24), second, 1, 24)
    for a, b in zip(again, fresh):
        assert (_bits(a) == _bits(b)).all()
    for name in llama.state_slabs(CFG):
        assert (_bits(cache[name][:, 1]) == _bits(clean[name][:, 1])).all()


def test_a_row_between_two_of_its_chunks_is_not_advanced(params):
    """While a prompt's chunks wait, its neighbours decode and ingest:
    the row is not ``active``, so its ``s`` and ``conv`` stay bit for
    bit and its prompt ends on the logits it gives alone."""
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
        held = {n: np.asarray(cache[n][:, 1]) for n in llama.state_slabs(CFG)}
        _, cache = decode(params, cache, tokens_of(16 + at, 3), 0,
                          others=(2,))
        _, cache = ingest(params, cache, tokens_of(17 + at, 20), 2, 24)
        for name, before in held.items():
            assert (_bits(cache[name][:, 1]) == _bits(before)).all()
    for a, b in zip(among, alone):
        assert (_bits(a) == _bits(b)).all()


def test_a_row_decodes_the_same_beside_longer_and_shorter_rows(params):
    """The probes' rule: a row's logits do not depend, to the bit, on
    the rows that decode beside it."""
    mine, more = tokens_of(20, 60), tokens_of(21, 6)
    cache = llama.init_kv_cache(CFG, SLOTS, MAX_SEQ, 24)
    _, cache = ingest(params, cache, mine, 1, 24)
    alone, _ = decode(params, cache, more, 1)
    _, cache = ingest(params, cache, tokens_of(22, 140), 0, 24)
    _, cache = ingest(params, cache, tokens_of(23, 9), 2, 24)
    beside, _ = decode(params, cache, more, 1, others=(0, 2))
    for a, b in zip(alone, beside):
        assert (_bits(a) == _bits(b)).all()
    assert rel_l2(alone[-1], reference_logits(
        params, np.concatenate([mine, more]))[-1]) < TOL


# ------------------------------------------------------ (d) shares add up

def _named(layer, mamba):
    names = {"ln_attn": "attn_norm", "ln_mlp": "mlp_norm",
             **({"wo": "out_proj"} if mamba else {})}
    return {names.get(name, name): leaf for name, leaf in layer.items()}


@pytest.mark.parametrize("stack", ["layers", llama.SSM])
def test_the_ranks_shares_add_up_to_the_uncut_layer(params, stack):
    """Two ranks hold four of the router's eight experts each (experts
    0-3, 4-7): the routed parts of their shares, with the shared SwiGLU
    and the mix — which every rank computes alike — counted once, are
    the uncut reference's layer.  (At the published cut: two ranks of
    36, experts 0-35 and 36-71.)"""
    dims = {k: v for k, v in dims_of(CFG).items()
            if k not in ("embedding_multiplier", "logits_scaling")}
    mamba, scale = stack == llama.SSM, CFG.residual_multiplier
    layer = {name: leaf[0] for name, leaf in params[stack].items()}
    keys = jax.random.split(jax.random.PRNGKey(30), 3)
    full = {name: jnp.concatenate([
        layer[name], 6.0 * 0.02 * jax.random.normal(
            key, layer[name].shape)]) for name, key in zip(
                ("w_gate", "w_up", "w_down"), keys)}          # experts 4-7
    x = jax.random.normal(jax.random.PRNGKey(31), (40, CFG.dim))
    # the mix once: what every rank's feed-forward reads
    h = ref.rms_norm(x, layer["ln_attn"], CFG.norm_eps)
    named = _named(layer, mamba)
    y = x + scale * (ref.mamba_mix(named, h, CFG.norm_eps) if mamba
                     else ref.softmax_mix(named, h, CFG.n_heads,
                                          CFG.n_kv_heads,
                                          CFG.attention_multiplier))
    h = ref.rms_norm(y, layer["ln_mlp"], CFG.norm_eps)
    total, held_rows = 0.0, 0
    for rank in range(2):
        cfg = dataclasses.replace(CFG, first_expert=4 * rank)
        held = {name: leaf[4 * rank:4 * rank + 4]
                for name, leaf in full.items()}
        out, load = llama._routed_mlp({**layer, **held}, h, cfg)
        total, held_rows = total + out, held_rows + int(jnp.sum(load))
    assert held_rows == 40 * 3                               # none lost
    shared = ref.swiglu(h, layer["shared_gate"], layer["shared_up"],
                        layer["shared_down"])
    want = ref.block(_named({**layer, **full}, mamba), x, jnp.arange(40),
                     **{**dims, "first_expert": 0})
    np.testing.assert_allclose(y + scale * (total + shared), want,
                               rtol=2e-4, atol=2e-5)


# --------------------------------------------------------- (e) the engine

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
    prompts = [tokens_of(40, 57).tolist(), [5, 9, 17],
               tokens_of(41, 23).tolist()]
    eng = _engine(params)
    together = eng.generate(prompts, SamplingParams(max_tokens=12))
    for prompt, out in zip(prompts, together):
        alone = _engine(params).generate([prompt],
                                         SamplingParams(max_tokens=12))
        assert alone[0].token_ids == out.token_ids
    # greedy tokens are the reference's arg-max, chunks and steps through
    tokens = np.asarray(prompts[0] + together[0].token_ids)
    want = np.asarray(reference_logits(params, tokens))[56:-1].argmax(-1)
    assert want.tolist() == together[0].token_ids
    # nine state-space layers a period at the published cut; six of
    # eight here, counted as the linear kind's are
    stats = eng.stats
    assert stats["recurrent_resets"] == 3
    assert stats["recurrent_chunk_rows"] == 6 * 16 * stats["chunks"]
    assert stats["recurrent_chunk_tokens"] == 6 * (57 + 3 + 23)
    assert stats["recurrent_slot_rows"] == 6 * 3 * stats["decode_steps"]
    assert 0 < stats["recurrent_decode_rows"] <= stats["recurrent_slot_rows"]


def test_the_probe_of_three_geometries_holds_the_program_to_each():
    """``chipbench/replica_median_triple.py`` (the configuration's
    ``serve.replica``) through the configuration FILE, its factory and
    ``reference_layers``: the traffic file's probe of whole chunks, in
    the same slot after it one whose prompt ends a few tokens behind a
    chunk boundary, and a third of a few tokens; all three medians in
    ``rel_l2``.  A state that is not handed from chunk to chunk is caught
    by the second, a softmax scale of head_dim^-1/2 by the third."""
    from chipbench.replica_median_triple import MedianTripleProbeLLMServer

    with open(PUBLISHED) as f:
        spec = json.load(f)
    assert spec["serve"]["replica"] == (
        "chipbench.replica_median_triple:MedianTripleProbeLLMServer")
    spec.update(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        vocab_size=256, intermediate_size=32, shared_intermediate_size=64,
        num_local_experts=4, num_experts_per_tok=3,
        max_position_embeddings=512, mamba_n_heads=4, mamba_d_head=32,
        mamba_d_state=128, attention_multiplier=1 / 16,
        deployment={**spec["deployment"], "router_width": 8,
                    "experts_held": [0, 3]})
    spec["serve"]["probe_short_last_chunk"] = {"tokens_behind_boundary": 5}
    spec["serve"]["probe_short_prompt"] = {"tokens": 9}
    server = MedianTripleProbeLLMServer(spec, slots=2, max_seq=256, seed=3,
                                        prefill_chunk_tokens=32)
    try:
        eng = server.engine
        assert eng.config.kinds == ("ssm",) * 5 + ("full",) + ("ssm",) * 4
        # at 64 wide a normed row times in_proj is an eighth of what it
        # is at 4,096, and q . k a 64th: scaled up, what the layers
        # write and read their state along and the softmax layer's
        # scores have the published size, and each its share
        ssm, softmax = eng.params[llama.SSM], eng.params["layers"]
        eng.params = {**eng.params, llama.SSM: {
            **ssm, "in_proj": ssm["in_proj"] * 8}, "layers": {
            **softmax, "wq": softmax["wq"] * 8, "wk": softmax["wk"] * 8,
            "wv": softmax["wv"] * 8}}
        out = server.probe_logits(7, 128, 4)
        assert out["prompt_tokens"] == [128, 96 + 5, 9]
        assert out["positions"] == 15 and len(out["rel_l2"]) == 3
        assert out["rel_l2"] == [statistics.median(by) for by in
                                 out["rel_l2_by_position"]]
        # bfloat16 weights against the float32 reference, on the CPU
        assert max(out["rel_l2"]) < 0.1
        plain = eng._prefill_chunk_jit

        def not_handed_over(params, cache, buf, slot, start, n):
            if start:
                cache = {**cache, **{
                    name: cache[name].at[:, slot].set(0)
                    for name in llama.state_slabs(eng.config)}}
            return plain(params, cache, buf, slot, start, n)

        eng._prefill_chunk_jit = not_handed_over
        lost = server.probe_logits(7, 128, 4)["rel_l2"]
        assert min(lost[:2]) > 3 * max(out["rel_l2"]) and max(lost) > 0.05
        assert lost[2] == out["rel_l2"][2]          # one chunk: no hand-over
        eng._prefill_chunk_jit = plain
        # the reference given head_dim^-1/2 (at these lengths every
        # probe is a short one: the chip's second reads it least)
        server._spec = {**spec, "attention_multiplier": 16 ** -0.5}
        wrong = server.probe_logits(7, 128, 4)["rel_l2"]
        assert wrong[2] > 5 * out["rel_l2"][2]
    finally:
        server.shutdown()


def test_what_a_recurrent_state_is_refused_by_name(params):
    eng = _engine(params)
    with pytest.raises(ValueError, match="sessions are not kept over a "
                                         "recurrent state.*state-space"):
        eng.add_request([1, 2, 3], session_id="turns")
    with pytest.raises(ValueError, match="a recurrent state .*state-space.* "
                                         "is not sharded"):
        _engine(params, tensor_parallel_size=2)
    with pytest.raises(ValueError, match="no ssm layers"):
        llama.loss_fn_pp(params, {"tokens": jnp.zeros((2, 9), jnp.int32)},
                         CFG, mesh=type("M", (), {"shape": {"pp": 2}})())
    with pytest.raises(ValueError, match="ssm layers state their heads"):
        dataclasses.replace(llama.CONFIGS["tiny"],
                            layer_kinds=("full", "ssm"))
    with pytest.raises(ValueError, match="ssm_groups 1"):
        dataclasses.replace(CFG, ssm_groups=2)
    with pytest.raises(ValueError, match="in ONE model are not computed"):
        dataclasses.replace(CFG, layer_kinds=("ssm", "linear", "full", "ssm"))


def test_the_state_moves_with_the_slabs(params):
    """``_extract`` / ``_install`` (what ``kv_offload.py`` stores) move
    a slot's ``s`` and ``conv`` by the slabs' own rule, under the same
    names as the linear kind's: the row decodes in another slot of
    another cache what it decodes where it lay."""
    tokens = tokens_of(43, 52)
    eng = _engine(params)
    _, cache = ingest(params, eng.cache, tokens[:51], 0, 16)
    moved = eng._install_jit(
        llama.init_kv_cache(CFG, 3, MAX_SEQ, 16),
        eng._extract_jit(cache, 0), jnp.int32(51), 2)
    last = jnp.full((3,), int(tokens[51]), jnp.int32)
    here, _ = decode_step(params, last, cache,
                          jnp.asarray([True, False, False]))
    there, _ = decode_step(params, last, moved,
                           jnp.asarray([False, False, True]))
    assert (_bits(here[0]) == _bits(there[2])).all()
    assert rel_l2(here[0], reference_logits(params, tokens)[-1]) < TOL


# ------------------------------------------- the factory, and the counts

@pytest.mark.parametrize("key,value,message", [
    ("mamba_n_groups", 8, "mamba_n_groups other than 1"),
    ("mamba_proj_bias", True, "a projection bias"),
    ("attention_bias", True, "a projection bias"),
    ("mamba_conv_bias", False, "a convolution without bias"),
    ("position_embedding_type", "rope", "rotary softmax layers"),
    ("tie_word_embeddings", False, "an untied head"),
    ("mamba_expand", 3, "mamba_expand \\* hidden_size other than"),
    ("shared_intermediate_size", 1000, "not a whole number of experts"),
    ("num_local_experts", 35, "experts_held does not name"),
])
def test_the_factory_refuses_what_it_does_not_map(key, value, message):
    with open(PUBLISHED) as f:
        spec = json.load(f)
    with pytest.raises(ValueError, match=message):
        granite_hybrid.build({**spec, key: value})


def test_num_params_and_cache_at_the_published_cut():
    """4,757,211,776 parameters held (one period of ten layers with 36
    of 72 experts, half the vocabulary, tied), by ``jax.eval_shape`` of
    the initialiser — nothing is allocated — and the cell's cache, 48 x
    6,144: ONE layer's slabs, 1.125 GiB, beside 9 x 48 states of 4 MiB,
    1.69 GiB, float32."""
    spec = json.load(open(PUBLISHED))
    cfg = granite_hybrid.build(spec)
    assert cfg.kinds == ("ssm",) * 5 + ("full",) + ("ssm",) * 4
    assert cfg.layer_counts() == (0, 1) and cfg.n_recurrent == 9
    assert cfg.recurrent == "ssm" and cfg.n_linear == 0
    assert not cfg.full_rope and cfg.tie_embeddings
    assert cfg.attn_scale == 1 / 128 and cfg.head_dim == 128
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling) == (12.0, 0.22, 16.0)
    assert [cfg.place(j) for j in (0, 4, 5, 6, 9)] == [
        ("ssm_layers", 9, 0), ("ssm_layers", 9, 4), ("layers", 1, 0),
        ("ssm_layers", 9, 5), ("ssm_layers", 9, 8)]
    shapes = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    held = sum(leaf.size for leaf in jax.tree.leaves(shapes))
    assert held == cfg.num_params() == 4_757_211_776
    mamba, softmax = shapes[llama.SSM], shapes["layers"]
    assert mamba["in_proj"].shape == (9, 4096, 8192 + 8448 + 128)
    assert mamba["conv_w"].shape == (9, 4, 8448)
    assert mamba["conv_b"].shape == (9, 8448)
    assert mamba["wo"].shape == (9, 8192, 4096)
    assert mamba["w_gate"].shape == (9, 36, 4096, 768)
    assert mamba["shared_gate"].shape == (9, 4096, 1536)
    assert softmax["wk"].shape == (1, 4096, 1024)
    assert softmax["router"].shape == (1, 4096, 72)
    assert "lm_head" not in shapes
    ffn = 36 * 9_437_184 + 18_874_368 + 294_912 + 2 * 4096
    assert sum(leaf.size for leaf in jax.tree.leaves(mamba)) == 9 * (
        102_286_976 + ffn)
    assert sum(leaf.size for leaf in jax.tree.leaves(softmax)) == (
        41_943_040 + ffn)
    assert shapes["embed"].size == 205_520_896
    cache = jax.eval_shape(lambda: llama.init_kv_cache(
        cfg, 48, 6144, spec["serve"]["kwargs"]["prefill_chunk_tokens"]))
    assert {n: (cache[n].shape, cache[n].dtype.name) for n in (
        *llama.kv_slabs(cfg), *llama.state_slabs(cfg))} == {
        "k": ((1, 48, 6144, 8, 128), "bfloat16"),
        "v": ((1, 48, 6144, 8, 128), "bfloat16"),
        "s": ((9, 48, 128, 64, 128), "float32"),
        "conv": ((9, 48, 3, 8448), "bfloat16")}
    a_state = 128 * 64 * 128 * 4
    assert a_state == 4 * 2 ** 20                    # 4 MiB a slot-layer
    assert 2 * 48 * 6144 * 8 * 128 * 2 == 1.125 * 2 ** 30
    assert round(9 * 48 * a_state / 2 ** 30, 2) == 1.69
    # the floors of a model_config cut
    assert spec["num_hidden_layers"] == 10 and spec["num_local_experts"] >= 8
    assert spec["vocab_size"] * 2 == 100_352
    assert set(spec["reduced"]) == {"num_hidden_layers", "num_local_experts",
                                    "vocab_size"}
    # what the cut leaves as published
    assert len(spec["layer_types"]) == 40
    assert spec["layer_types"][:10] == ["mamba"] * 5 + ["attention"] + [
        "mamba"] * 4


def test_the_linear_kinds_cache_is_what_it_was():
    """``solar2-tiny`` builds the cache it built before this kind came:
    the same names, shapes and dtypes (its programs' outputs are pinned
    in ``tests/test_solar_open2.py``)."""
    solar = llama.CONFIGS["solar2-tiny"]
    assert solar.recurrent == "linear" and solar.n_recurrent == 6
    cache = jax.eval_shape(lambda: llama.init_kv_cache(solar, 3, 64, 16))
    assert {n: (cache[n].shape, cache[n].dtype.name) for n in cache} == {
        "k": ((2, 3, 64, 2, 16), "float32"),
        "v": ((2, 3, 64, 2, 16), "float32"),
        "s": ((6, 3, 4, 16, 16), "float32"),
        "conv": ((6, 3, 3, 192), "float32"),
        "length": ((3,), "int32"),
        "routing": ((len(llama.ROUTING_COUNTERS),), "uint32")}
    assert llama.CONFIGS["tiny"].recurrent == ""
    assert llama.state_slabs(llama.CONFIGS["cmdaplus-tiny"]) == {}
