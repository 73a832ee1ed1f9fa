"""Solar Open 2's block (three gated delta-rule linear layers to one
gated softmax layer without positional embedding: a recurrent state a
slot BESIDE the slabs, carried across chunks and decode steps; a
sigmoid router over more experts than are held, one shared expert, the
head not tied) on the program's normal paths, against the plain
reference ``chipbench/reference/solar_open2_decoder.py`` on seeded
random weights at a tiny shape, on the CPU in float32.

The measure is the benchmark's own: the relative L2 distance of the
logits, per position.  Both sides compute in float32, so the distance
is rounding and the order of summation — the program runs the
recurrence in blocks of 64 tokens and solves a block's triangular
system by matrix products, the reference goes token by token: 5e-6 at
worst here.  ``TOL`` = 5e-5 is four orders under what it must catch:
the state not handed from chunk to chunk (0.8 on the chip), the decay
left off (1.2), a padded token that writes.

Chunks are 48 tokens (not a multiple of the 64-token block) and 64; the
step programs' attention block is cut to 16 positions.
"""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ant_ray_tpu.llm import LLMEngine, SamplingParams
from ant_ray_tpu.models import llama
from ant_ray_tpu.ops import delta_rule
from benchmarks import delta_rule_chunk
from chipbench.models import solar_open2
from chipbench.reference import solar_open2_decoder as ref

CFG = llama.CONFIGS["solar2-tiny"]                   # two periods
ONE = dataclasses.replace(CFG, n_layers=4)           # one period
GQA_LAYERS = [0, 4]
TOL = 5e-5
SLOTS, MAX_SEQ = 3, 256
PUBLISHED = os.path.join(os.path.dirname(__file__), "..", "chipbench",
                         "configs", "solar-open2.json")


@pytest.fixture(autouse=True)
def short_blocks(monkeypatch):
    monkeypatch.setattr(llama, "ATTEND_BLOCK", 16)


def dims_of(cfg):
    return dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps,
                experts_per_token=cfg.experts_per_token,
                first_expert=cfg.first_expert)


def seeded_params(cfg=CFG, seed=0):
    """Seeded weights, less bland than the initialiser's: matrices
    large enough that the router decides, attention attends and the
    write strengths spread over (0, 2); norm weights that are not all
    ones.  The decay's leaves stay as drawn."""
    p = llama.init_params(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 128))
    drawn = ("a_log", "dt_bias")

    def livelier(stack):
        return {name: leaf if name in drawn else leaf * (
            jax.random.uniform(next(keys), leaf.shape, minval=0.5,
                               maxval=1.5)
            if name.startswith("ln_") or name == "o_norm" else 6.0)
            for name, leaf in stack.items()}

    return {**p, "norm_f": p["norm_f"] * 0.7,
            "layers": livelier(p["layers"]),
            llama.LINEAR: livelier(p[llama.LINEAR])}


@pytest.fixture(scope="module")
def params():
    return seeded_params()


_BLOCK = jax.jit(ref.block, static_argnames=(       # as the harness does
    "n_heads", "n_kv_heads", "rope_theta", "norm_eps"))


def reference_logits(params, tokens, cfg=CFG):
    embed, layer, n, norm_f, head = solar_open2.reference_layers(
        params, GQA_LAYERS)
    return ref.forward(embed, (layer, n), norm_f, head, jnp.asarray(tokens),
                       block_fn=_BLOCK, **dims_of(cfg))


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
    tokens = tokens_of(1, 150)              # two blocks of 64 and a tail
    got = forward(params, jnp.asarray(tokens)[None], cfg=cfg)[0]
    assert rel_l2(got, reference_logits(params, tokens, cfg)).max() < TOL


@pytest.mark.parametrize("chunk,prompt", [
    (64, 128), (48, 130), (64, 70), (16, 150)],
    ids=["chunks-divide-the-prompt", "a-padded-last-chunk-of-34",
         "a-padded-last-chunk-of-6", "chunks-shorter-than-a-block"])
def test_chunks_and_decode_through_the_cache_are_forward(params, chunk,
                                                         prompt):
    """Prefill in chunks — the state handed from chunk to chunk, a last
    chunk padded behind its real tokens — then decode steps, equal
    ``forward`` (and the reference) at every position read."""
    tokens = tokens_of(2, prompt + 9)
    got, at = through_the_cache(params, tokens, prompt, chunk)
    want = reference_logits(params, tokens)
    assert rel_l2(got, want[np.asarray(at)]).max() < TOL
    whole = forward(params, jnp.asarray(tokens)[None])[0]
    assert rel_l2(got, whole[np.asarray(at)]).max() < TOL


def test_a_state_that_is_not_handed_over_is_caught(params):
    """What ``TOL`` must catch: the slot's state emptied between two
    chunks, and a decay left off."""
    tokens = tokens_of(3, 96)
    want = reference_logits(params, tokens)[-1]
    cache = llama.init_kv_cache(CFG, SLOTS, MAX_SEQ, 48)
    _, cache = ingest(params, cache, tokens[:48], 1, 48)
    dropped = {**cache, "s": cache["s"].at[:, 1].set(0.0)}
    (kept,), _ = ingest(params, cache, tokens[48:], 1, 48, start=48)
    (lost,), _ = ingest(params, dropped, tokens[48:], 1, 48, start=48)
    assert rel_l2(kept, want) < TOL < 1e-2 < rel_l2(lost, want)
    undamped = {**params, llama.LINEAR: {
        **params[llama.LINEAR],
        "a_log": jnp.full_like(params[llama.LINEAR]["a_log"], -jnp.inf)}}
    off = forward(undamped, jnp.asarray(tokens)[None])[0, -1]
    assert rel_l2(off, want) > 1e-2


# ------------------------------------- (b) the chunk form, the step, the conv

def _delta_inputs(seed, tokens, heads=3, d_k=16, d_v=16, fastest=3.0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k = (jax.random.normal(key, (tokens, heads, d_k)) for key in keys[:2])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d_k ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], (tokens, heads, d_v))
    g = -jnp.exp(jax.random.uniform(keys[3], (tokens, heads, d_k),
                                    minval=np.log(1e-3),
                                    maxval=np.log(fastest)))
    g = g.at[:, :, 0].set(-fastest)          # one channel always fastest
    beta = 2 * jax.nn.sigmoid(jax.random.normal(keys[4], (tokens, heads)))
    return q, k, v, g, beta, jax.random.normal(keys[5], (heads, d_k, d_v))


@pytest.mark.parametrize("tokens", [64, 192, 150],
                         ids=["one-block", "three-blocks", "a-padded-block"])
def test_blocks_of_64_are_the_recurrence_token_by_token(tokens):
    """Decays as fast as exp(-3) a token: the cumulative log-decay of a
    block reaches -190, so ``exp(-G)`` alone is far past float32's
    3.4e38 — the chunk form takes differences only and stays finite and
    right."""
    q, k, v, g, beta, s0 = _delta_inputs(4, tokens)
    deepest = float(jnp.min(jnp.cumsum(g[:64], axis=0)))
    assert deepest < -100 and np.exp(-deepest) > np.finfo(np.float32).max
    want_o, want_s = delta_rule.delta_rule_scan(q, k, v, g, beta, s0)
    got_o, got_s = jax.jit(delta_rule.chunk_delta_rule)(q, k, v, g, beta, s0)
    assert np.isfinite(np.asarray(got_o)).all()
    np.testing.assert_allclose(got_o, want_o, rtol=1e-4, atol=2e-6)
    np.testing.assert_allclose(got_s, want_s, rtol=1e-4, atol=2e-6)


def _fast_between(g, first, last, fastest=3.0):
    """Channel 0 forgets at the fastest rate, exp(-3) a token, in tokens
    [first, last) and next to nothing elsewhere."""
    g = g.at[:, :, 0].set(-1e-3)
    return g.at[first:last, :, 0].set(-fastest)


@pytest.mark.parametrize("tokens, shape, fast, block", [
    (128, dict(heads=2, d_k=128, d_v=128), None, delta_rule.BLOCK),
    (64, {}, (18, 31), delta_rule.BLOCK),
    (64, {}, (26, 39), delta_rule.BLOCK),
    (17, {}, None, delta_rule.BLOCK),
    (40, {}, None, delta_rule.SUB),
], ids=["the-published-head", "fast-inside-a-sub-block",
        "fast-across-a-sub-blocks-first-token", "seventeen-tokens",
        "blocks-of-16-are-pairwise-whole"])
def test_sub_blocks_of_16_are_the_recurrence_token_by_token(tokens, shape,
                                                            fast, block):
    """The pair sums between sub-blocks are matrix products against the
    later sub-block's first token (``delta_rule.SUB``): the published
    128 x 128 head; a channel that forgets fastest only INSIDE sub-block
    1 (tokens 16-31: the pairwise part alone sees it fall) and only
    ACROSS sub-block 2's first token, 32 (both factors fall); a block
    whose real tokens end one token into the second sub-block; a
    caller's block of one sub-block, pairwise whole.  (A factor that
    underflows: the three-blocks case above, whose channel 0 falls by
    exp(-48) a sub-block, so from a token of sub-block 0 to sub-block
    3's first token by exp(-144), 0 in float32.)"""
    q, k, v, g, beta, s0 = _delta_inputs(9, tokens, **shape)
    if fast:
        g = _fast_between(g, *fast)
    want_o, want_s = delta_rule.delta_rule_scan(q, k, v, g, beta, s0)
    got_o, got_s = jax.jit(functools.partial(
        delta_rule.chunk_delta_rule, block=block))(q, k, v, g, beta, s0)
    assert np.isfinite(np.asarray(got_o)).all()
    np.testing.assert_allclose(got_o, want_o, rtol=1e-4, atol=2e-6)
    np.testing.assert_allclose(got_s, want_s, rtol=1e-4, atol=2e-6)


def test_a_blocks_exponentials_are_a_quarter_of_the_pairwise_forms():
    """What holds the quarter: in the jaxpr of one block of one
    published head no ``exp`` reads BLOCK x BLOCK x d_k elements, and
    the pair sums' exponentials — every ``exp`` but ``into`` and
    ``out_of``, BLOCK x d_k each, which scale the products with the
    state — are under 0.3 of that (the four diagonal sub-blocks a
    quarter, the factors to and from a sub-block's first token the
    rest)."""
    block, d_k = delta_rule.BLOCK, 128
    sizes = delta_rule_chunk.exp_operands(
        delta_rule.chunk_delta_rule, *_delta_inputs(1, block, heads=1,
                                                    d_k=d_k, d_v=d_k))
    assert max(sizes) == block * delta_rule.SUB * d_k < block * block * d_k
    assert sum(sizes) - 2 * block * d_k <= 0.3 * block * block * d_k


def test_keys_that_lie_close_together_do_not_lose_the_inverse():
    """64 keys of a block in 8 dimensions are far from independent: the
    triangular system's series over all 64 rows at once loses four
    digits there; merged from blocks of 16 it does not."""
    q, k, v, g, beta, s0 = _delta_inputs(5, 64, d_k=8, fastest=0.05)
    want_o, _ = delta_rule.delta_rule_scan(q, k, v, g, beta, s0)
    got_o, _ = delta_rule.chunk_delta_rule(q, k, v, g, beta, s0)
    np.testing.assert_allclose(got_o, want_o, rtol=2e-4, atol=2e-5)


def test_a_step_is_the_recurrence_and_an_idle_row_keeps_its_state():
    q, k, v, g, beta, s0 = _delta_inputs(6, 5)
    want_o, _ = delta_rule.delta_rule_scan(q, k, v, g, beta, s0)
    s = jnp.stack([s0, s0])
    for t in range(5):
        two = [jnp.stack([x[t], x[t]]) for x in (q, k, v, g, beta)]
        o, s = delta_rule.delta_rule_step(*two, s, jnp.array([True, False]))
        np.testing.assert_allclose(o[0], want_o[t], rtol=1e-5, atol=1e-6)
    assert (_bits(s[1]) == _bits(s0)).all()


def test_padding_and_the_convolutions_tail():
    """A padded token (beta = 0, g = 0) changes neither the outputs
    before it nor the state; the convolution over a sequence cut in two
    is the convolution over the whole, its tail the last real inputs."""
    q, k, v, g, beta, s0 = _delta_inputs(7, 40)
    o, s = delta_rule.chunk_delta_rule(q, k, v, g, beta, s0)
    pad = [jnp.concatenate([x, 5.0 + jnp.zeros_like(x[:24])])
           for x in (q, k, v)]
    o2, s2 = delta_rule.chunk_delta_rule(
        *pad, jnp.pad(g, ((0, 24), (0, 0), (0, 0))),
        jnp.pad(beta, ((0, 24), (0, 0))), s0)
    np.testing.assert_array_equal(np.asarray(o2[:40]), np.asarray(o))
    np.testing.assert_array_equal(np.asarray(s2), np.asarray(s))
    u = jax.random.normal(jax.random.PRNGKey(8), (30, 12))
    w = jax.random.normal(jax.random.PRNGKey(9), (4, 12))
    whole, _ = delta_rule.causal_conv(u, jnp.zeros((3, 12)), w)
    first, ext = delta_rule.causal_conv(u[:17], jnp.zeros((3, 12)), w)
    second, _ = delta_rule.causal_conv(u[17:], ext[-3:], w)
    np.testing.assert_allclose(jnp.concatenate([first, second]), whole,
                               rtol=1e-6, atol=1e-6)
    step, tail = delta_rule.causal_conv_step(u[17][None], ext[-3:][None], w)
    np.testing.assert_allclose(step[0], whole[17], rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(tail[0]),
                                  np.asarray(u[15:18]))


# ------------------------------------------- (c) slots, neighbours, idle rows

def test_a_slot_used_again_gives_what_a_fresh_cache_gives(params):
    """A chunk whose ``start`` is 0 begins from an EMPTY state whatever
    the slot's last occupant left — selected in the program, no reset
    call: bit-equal logits to the same prompt in a fresh cache."""
    first, second = tokens_of(10, 100), tokens_of(11, 70)
    cache = llama.init_kv_cache(CFG, SLOTS, MAX_SEQ, 48)
    _, cache = ingest(params, cache, first, 1, 48)
    _, cache = decode(params, cache, tokens_of(12, 5), 1)
    assert float(jnp.abs(cache["s"][:, 1]).max()) > 0
    again, cache = ingest(params, cache, second, 1, 48)
    fresh, clean = ingest(
        params, llama.init_kv_cache(CFG, SLOTS, MAX_SEQ, 48), second, 1, 48)
    for a, b in zip(again, fresh):
        assert (_bits(a) == _bits(b)).all()
    for name in llama.state_slabs(CFG):
        assert (_bits(cache[name][:, 1]) == _bits(clean[name][:, 1])).all()


def test_a_row_between_two_of_its_chunks_is_not_advanced(params):
    """While a prompt's chunks wait, its neighbours decode: the row is
    not ``active``, so its state and tails stay bit for bit and its
    prompt ends on the logits it gives alone."""
    prompt = tokens_of(13, 120)
    alone, _ = ingest(params, llama.init_kv_cache(CFG, SLOTS, MAX_SEQ, 48),
                      prompt, 1, 48)
    cache = llama.init_kv_cache(CFG, SLOTS, MAX_SEQ, 48)
    _, cache = ingest(params, cache, tokens_of(14, 30), 0, 48)
    _, cache = ingest(params, cache, tokens_of(15, 90), 2, 48)
    among = []
    for at in range(0, 120, 48):
        out, cache = ingest(params, cache, prompt[at:at + 48], 1, 48,
                            start=at)
        among += out
        held = {n: np.asarray(cache[n][:, 1]) for n in llama.state_slabs(CFG)}
        _, cache = decode(params, cache, tokens_of(16 + at, 3), 0,
                          others=(2,))
        for name, before in held.items():
            assert (_bits(cache[name][:, 1]) == _bits(before)).all()
    for a, b in zip(among, alone):
        assert (_bits(a) == _bits(b)).all()


def test_a_row_decodes_the_same_beside_longer_and_shorter_rows(params):
    """The probes' rule: a row's logits do not depend, to the bit, on
    the rows that decode beside it."""
    mine, more = tokens_of(20, 60), tokens_of(21, 6)
    cache = llama.init_kv_cache(CFG, SLOTS, MAX_SEQ, 48)
    _, cache = ingest(params, cache, mine, 1, 48)
    alone, _ = decode(params, cache, more, 1)
    _, cache = ingest(params, cache, tokens_of(22, 140), 0, 48)
    _, cache = ingest(params, cache, tokens_of(23, 9), 2, 48)
    beside, _ = decode(params, cache, more, 1, others=(0, 2))
    for a, b in zip(alone, beside):
        assert (_bits(a) == _bits(b)).all()
    assert rel_l2(alone[-1], reference_logits(
        params, np.concatenate([mine, more]))[-1]) < TOL


# ------------------------------------------------------ (d) shares add up

def test_the_ranks_shares_add_up_to_the_uncut_layer(params):
    """Two ranks hold eight of the router's sixteen experts each: the
    routed parts of their shares, with the shared expert and the mix —
    which every rank computes alike — counted once, are the uncut
    reference's layer.  (At the published cut: eight ranks of 40.)"""
    dims = dims_of(CFG)
    for stack, mixed in (("layers", ref.softmax_mix),
                         (llama.LINEAR, ref.linear_mix)):
        layer = {name: leaf[0] for name, leaf in params[stack].items()}
        keys = jax.random.split(jax.random.PRNGKey(30), 3)
        full = {name: jnp.concatenate([
            layer[name], 6.0 * 0.02 * jax.random.normal(
                key, layer[name].shape)]) for name, key in zip(
                    ("w_gate", "w_up", "w_down"), keys)}      # experts 8-15
        x = jax.random.normal(jax.random.PRNGKey(31), (40, CFG.dim))
        # the mix once: what every rank's feed-forward reads
        h = ref.rms_norm(x, layer["ln_attn"], CFG.norm_eps)
        y = x + (mixed(_named(layer), h, CFG.norm_eps)
                 if stack == llama.LINEAR else mixed(
                     _named(layer), h, jnp.arange(40), CFG.n_heads,
                     CFG.n_kv_heads))
        h = ref.rms_norm(y, layer["ln_mlp"], CFG.norm_eps)
        total, held_rows = 0.0, 0
        for rank in range(2):
            cfg = dataclasses.replace(CFG, first_expert=8 * rank)
            held = {name: leaf[8 * rank:8 * rank + 8]
                    for name, leaf in full.items()}
            out, load = llama._routed_mlp({**layer, **held}, h, cfg)
            total, held_rows = total + out, held_rows + int(jnp.sum(load))
        assert held_rows == 40 * 2                           # none lost
        shared = ref.swiglu(h, layer["shared_gate"], layer["shared_up"],
                            layer["shared_down"])
        uncut = _named({**layer, **full})
        want = ref.block(uncut, x, jnp.arange(40), **{**dims,
                                                      "first_expert": 0})
        np.testing.assert_allclose(y + total + shared, want,
                                   rtol=2e-4, atol=2e-5)


def _named(layer):
    names = {"ln_attn": "attn_norm", "ln_mlp": "mlp_norm"}
    return {names.get(name, name): leaf for name, leaf in layer.items()}


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


def test_the_probe_of_two_geometries_holds_the_program_to_both():
    """``chipbench/replica_median_pair.py`` (the configuration's
    ``serve.replica``): the traffic file's probe of whole chunks and,
    in the same slot after it, one whose prompt ends a few tokens
    behind a chunk boundary; both medians in ``rel_l2``.  A state that
    is not handed from chunk to chunk is caught by the second."""
    import statistics

    from chipbench.replica_median_pair import MedianPairProbeLLMServer

    with open(PUBLISHED) as f:
        spec = json.load(f)
    assert spec["serve"]["replica"] == (
        "chipbench.replica_median_pair:MedianPairProbeLLMServer")
    spec.update(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, vocab_size=256, moe_intermediate_size=32,
        n_routed_experts=8, num_experts_per_tok=2,
        max_position_embeddings=512,
        linear_attn_config={**spec["linear_attn_config"], "num_heads": 4,
                            "head_dim": 16},
        deployment={**spec["deployment"], "router_width": 16,
                    "experts_held": [0, 7]})
    spec["serve"]["probe_short_last_chunk"] = {"tokens_behind_boundary": 5}
    server = MedianPairProbeLLMServer(spec, slots=2, max_seq=256, seed=3,
                                      prefill_chunk_tokens=32)
    try:
        out = server.probe_logits(7, 128, 4)
        assert out["prompt_tokens"] == [128, 96 + 5]
        assert out["positions"] == 10 and len(out["rel_l2"]) == 2
        assert out["rel_l2"] == [statistics.median(by) for by in
                                 out["rel_l2_by_position"]]
        # bfloat16 weights against the float32 reference, on the CPU
        assert max(out["rel_l2"]) < 0.1
        eng = server.engine
        plain = eng._prefill_chunk_jit

        def not_handed_over(params, cache, buf, slot, start, n):
            if start:
                cache = {**cache, **{
                    name: cache[name].at[:, slot].set(0)
                    for name in llama.state_slabs(eng.config)}}
            return plain(params, cache, buf, slot, start, n)

        eng._prefill_chunk_jit = not_handed_over
        lost = server.probe_logits(7, 128, 4)["rel_l2"]
        assert lost[1] > 3 * max(out["rel_l2"]) and lost[1] > 0.2
    finally:
        server.shutdown()


def test_a_stop_token_read_a_step_late_leaves_nothing_behind(params):
    """The step dispatched one ahead advances the state of a row whose
    stop token is read a step late.  Nothing reads that state again:
    the slot's next prompt begins from an empty one — its tokens are
    those of a fresh engine."""
    probe = tokens_of(42, 40).tolist()
    fresh = _engine(params, slots=1).generate(
        [probe], SamplingParams(max_tokens=8))[0].token_ids
    first = _engine(params, slots=1).generate(
        [[5, 9, 17]], SamplingParams(max_tokens=6))[0].token_ids
    # the first token that was not seen before ends the answer
    k = next(i for i in range(1, 6) if first[i] not in first[:i])
    eng = _engine(params, slots=1)
    stopped = eng.generate([[5, 9, 17]], SamplingParams(
        max_tokens=6, stop_token_ids=(first[k],)))[0]
    assert stopped.token_ids == first[:k] and stopped.finish_reason == "stop"
    # the step dispatched ahead wrote the row BEHIND the stop token's
    assert int(eng.cache["length"][0]) == 3 + k + 1
    assert float(jnp.abs(eng.cache["s"][:, 0]).max()) > 0
    again = eng.generate([probe], SamplingParams(max_tokens=8))[0].token_ids
    assert again == fresh


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


def test_the_state_moves_with_the_slabs(params):
    """``_extract`` / ``_install`` (what ``kv_offload.py`` stores) move
    a slot's ``s`` and ``conv`` by the slabs' own rule: the row decodes
    in another slot of another cache what it decodes where it lay."""
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


# ------------------------------------------- the counts, without allocating

def test_num_params_and_cache_at_the_published_cut():
    """3,308,352,064 parameters held (one period of four layers with 40
    of 320 experts, 1/8 of the vocabulary), by ``jax.eval_shape`` of the
    initialiser — nothing is allocated — and the cell's cache, 24 x
    32,768: ONE layer's slabs, 3 GiB, beside 0.30 GiB of state."""
    spec = json.load(open(PUBLISHED))
    cfg = solar_open2.build(spec)
    assert cfg.kinds == ("full", "linear", "linear", "linear")
    assert cfg.period == (False,) * 4 and cfg.layer_counts() == (0, 1)
    assert cfg.n_linear == 3 and not cfg.full_rope and cfg.attn_gate
    assert [cfg.place(j) for j in range(4)] == [
        ("layers", 1, 0), ("linear_layers", 3, 0), ("linear_layers", 3, 1),
        ("linear_layers", 3, 2)]
    shapes = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    held = sum(leaf.size for leaf in jax.tree.leaves(shapes))
    assert held == cfg.num_params() == 3_308_352_064
    linear, softmax = shapes[llama.LINEAR], shapes["layers"]
    assert linear["wq"].shape == (3, 4096, 8192)
    assert linear["conv_w"].shape == (3, 4, 24576)
    assert linear["w_fa"].shape == (3, 4096, 128)
    assert linear["w_gate"].shape == (3, 40, 4096, 1280)
    assert softmax["w_attn_gate"].shape == (1, 4096, 8192)
    assert softmax["wk"].shape == (1, 4096, 1024)
    assert softmax["router"].shape == (1, 4096, 320)
    ffn = 40 * 15_728_640 + 15_728_640 + 1_310_720 + 2 * 4096
    assert sum(leaf.size for leaf in jax.tree.leaves(linear)) == 3 * (
        137_732_288 + ffn)
    assert sum(leaf.size for leaf in jax.tree.leaves(softmax)) == (
        109_051_904 + ffn)
    assert shapes["embed"].size == shapes["lm_head"].size == 100_663_296
    cache = jax.eval_shape(lambda: llama.init_kv_cache(
        cfg, 24, 32768, spec["serve"]["kwargs"]["prefill_chunk_tokens"]))
    assert {n: (cache[n].shape, cache[n].dtype.name) for n in (
        *llama.kv_slabs(cfg), *llama.state_slabs(cfg))} == {
        "k": ((1, 24, 32768, 8, 128), "bfloat16"),
        "v": ((1, 24, 32768, 8, 128), "bfloat16"),
        "s": ((3, 24, 64, 128, 128), "float32"),
        "conv": ((3, 24, 3, 24576), "bfloat16")}
    slabs = 2 * 24 * 32768 * 8 * 128 * 2
    state = 3 * 24 * (64 * 128 * 128 * 4 + 3 * 24576 * 2)
    assert slabs == 3 * 2 ** 30 and round(state / 2 ** 30, 2) == 0.29
    # the floors of a model_config cut
    assert spec["num_hidden_layers"] == 4 and spec["n_routed_experts"] >= 8
    assert spec["vocab_size"] * 8 == 196_608
    assert set(spec["reduced"]) == {"num_hidden_layers", "n_routed_experts",
                                    "vocab_size"}


def test_a_window_pattern_is_layer_kinds_in_booleans():
    """``window_pattern`` is taken at construction and kept as the ONE
    field, ``layer_kinds``: the same config either way."""
    cmd = llama.CONFIGS["cmdaplus-tiny"]
    assert cmd.layer_kinds == ("window", "window", "window", "full")
    assert cmd == dataclasses.replace(cmd, n_layers=8)
    assert dataclasses.replace(
        llama.CONFIGS["tiny"], window=4,
        layer_kinds=("window", "full")) == dataclasses.replace(
        llama.CONFIGS["tiny"], window=4, window_pattern=(True, False))
    with pytest.raises(ValueError, match="give one of them"):
        dataclasses.replace(cmd, window_pattern=(True, False))
    with pytest.raises(ValueError, match="unknown layer kinds"):
        dataclasses.replace(llama.CONFIGS["tiny"], layer_kinds=("hyena",))
    with pytest.raises(ValueError, match="linear layers state"):
        dataclasses.replace(llama.CONFIGS["tiny"],
                            layer_kinds=("full", "linear"))
