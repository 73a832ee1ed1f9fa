"""The Solar Open 2 reference against a third evaluation of its
equations, written as loops in numpy float64 — one token, one head, one
seen position, one chosen expert at a time, the state a matrix updated
by the published recurrence (decay, erase along the key, write) — at a
toy size; that a linear layer's output at a position depends on every
earlier token and on none later; that a softmax layer knows no
position; that it imports nothing of the program; ``opsbytes_recurrent``
against the arithmetic of the issue that added the configuration; and
the four readers on a hand-made ``obs``."""

import ast
import json
import math
import os

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import opsbytes_recurrent
from chipbench.layer_metrics import (
    recurrent_chunk_fill_pct,
    recurrent_chunk_roofline_pct,
    recurrent_decode_roofline_pct,
    recurrent_state_live_pct,
)
from chipbench.reference import solar_open2_decoder as ref

HERE = os.path.dirname(os.path.abspath(__file__))
D, H, KVH, HD, F, E, HELD = 24, 4, 2, 6, 10, 8, 4
LH, DK, RANK, TAPS = 3, 5, 4, 4
DIMS = dict(n_heads=H, n_kv_heads=KVH, rope_theta=100.0, norm_eps=1e-5,
            experts_per_token=2, first_expert=4)


def spec():
    with open(os.path.join(HERE, "..", "configs", "solar-open2.json")) as f:
        return json.load(f)


def test_it_imports_nothing_of_the_program():
    with open(ref.__file__) as f:
        tree = ast.parse(f.read())
    names = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names += [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            names.append(node.module or "")
    assert names and all(
        n.split(".")[0] in ("__future__", "jax")
        or n.startswith("chipbench.reference.") for n in names), names


@pytest.fixture(scope="module")
def layers():
    rng = np.random.default_rng(0)

    def w(*shape, scale=0.3):
        return jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)

    ffn = {"mlp_norm": 1 + w(D), "router": w(D, E, scale=1.0),
           "w_gate": w(HELD, D, F), "w_up": w(HELD, D, F),
           "w_down": w(HELD, F, D), "shared_gate": w(D, F),
           "shared_up": w(D, F), "shared_down": w(F, D)}
    softmax = {"attn_norm": 1 + w(D), "wq": w(D, H * HD),
               "wk": w(D, KVH * HD), "wv": w(D, KVH * HD),
               "w_attn_gate": w(D, H * HD), "wo": w(H * HD, D), **ffn}
    linear = {"attn_norm": 1 + w(D), "wq": w(D, LH * DK),
              "wk": w(D, LH * DK), "wv": w(D, LH * DK),
              "conv_w": w(TAPS, 3 * LH * DK, scale=0.5),
              "w_fa": w(D, RANK), "w_fb": w(RANK, LH * DK),
              # rates 1 to 6 a head, steps around 0.05 to 1: some
              # channels forget within a few tokens, some hardly
              "a_log": jnp.log(jnp.asarray([1.0, 3.0, 6.0], jnp.float32)),
              "dt_bias": w(LH * DK, scale=1.5) - 1.0,
              "w_beta": w(D, LH, scale=1.0), "w_ga": w(D, RANK),
              "w_gb": w(RANK, LH * DK), "o_norm": 1 + w(DK),
              "wo": w(LH * DK, D), **ffn}
    return [softmax, linear]


def loops(layer, x):
    """One layer, by the docstring's equations, scalar loops."""
    lw = {n: np.asarray(v, np.float64) for n, v in layer.items()}
    seq, eps = len(x), DIMS["norm_eps"]

    def norm(v, weight):
        return v / math.sqrt(np.mean(v * v) + eps) * weight

    def silu(a):
        return a / (1 + np.exp(-a))

    def sigmoid(a):
        return 1 / (1 + np.exp(-a))

    def expert(h, gate, up, down):
        return (silu(h @ gate) * (h @ up)) @ down

    hs = [norm(x[t], lw["attn_norm"]) for t in range(seq)]
    mixed = np.zeros_like(x)
    if "a_log" in lw:
        pre = [np.concatenate([hs[t] @ lw[n] for n in ("wq", "wk", "wv")])
               for t in range(seq)]
        state = np.zeros((LH, DK, DK))
        for t in range(seq):
            conv = np.zeros(3 * LH * DK)
            for j in range(TAPS):
                if t - (TAPS - 1) + j >= 0:
                    conv += lw["conv_w"][j] * pre[t - (TAPS - 1) + j]
            q, k, v = silu(conv).reshape(3, LH, DK)
            step = np.log1p(np.exp(
                (hs[t] @ lw["w_fa"]) @ lw["w_fb"] + lw["dt_bias"]))
            alpha = np.exp(-np.exp(lw["a_log"])[:, None]
                           * step.reshape(LH, DK))
            beta = 2 * sigmoid(hs[t] @ lw["w_beta"])
            gate = sigmoid((hs[t] @ lw["w_ga"]) @ lw["w_gb"]).reshape(LH, DK)
            heads = []
            for i in range(LH):
                qi = q[i] / math.sqrt(q[i] @ q[i] + 1e-6) / math.sqrt(DK)
                ki = k[i] / math.sqrt(k[i] @ k[i] + 1e-6)
                s = alpha[i][:, None] * state[i]             # Diag(a) S
                s = (np.eye(DK) - beta[i] * np.outer(ki, ki)) @ s
                state[i] = s + beta[i] * np.outer(ki, v[i])
                heads.append(norm(state[i].T @ qi, lw["o_norm"]) * gate[i])
            mixed[t] = np.concatenate(heads) @ lw["wo"]
    else:
        ks = [(hs[s] @ lw["wk"]).reshape(KVH, HD) for s in range(seq)]
        vs = [(hs[s] @ lw["wv"]).reshape(KVH, HD) for s in range(seq)]
        for t in range(seq):
            q = (hs[t] @ lw["wq"]).reshape(H, HD)
            heads = []
            for i in range(H):
                kv = i // (H // KVH)
                scores = np.array([q[i] @ ks[s][kv] / math.sqrt(HD)
                                   for s in range(t + 1)])
                p = np.exp(scores - scores.max())
                p /= p.sum()
                heads.append(sum(p[s] * vs[s][kv] for s in range(t + 1)))
            mixed[t] = (np.concatenate(heads)
                        * sigmoid(hs[t] @ lw["w_attn_gate"])) @ lw["wo"]
    out = np.zeros_like(x)
    for t in range(seq):
        y = x[t] + mixed[t]
        h = norm(y, lw["mlp_norm"])
        # the router over all E experts; of the top 2, those held (4-7)
        score = sigmoid(h @ lw["router"])
        best = np.argsort(-score)[:2]
        routed = np.zeros(D)
        for e in best:
            if DIMS["first_expert"] <= e < DIMS["first_expert"] + HELD:
                j = e - DIMS["first_expert"]
                routed += score[e] / (score[best].sum() + 1e-20) * expert(
                    h, lw["w_gate"][j], lw["w_up"][j], lw["w_down"][j])
        out[t] = y + routed + expert(h, lw["shared_gate"], lw["shared_up"],
                                     lw["shared_down"])
    return out


@pytest.mark.parametrize("kind", [0, 1], ids=["softmax", "linear"])
def test_block_equals_the_equations_token_by_token(layers, kind):
    seq = 23
    x = np.random.default_rng(1).normal(size=(seq, D))
    got = ref.block(layers[kind], jnp.asarray(x, jnp.float32),
                    jnp.arange(seq), **DIMS)
    np.testing.assert_allclose(got, loops(layers[kind], x),
                               rtol=2e-4, atol=2e-5)


def test_a_linear_layer_is_causal_and_remembers(layers):
    """Position t's output moves with any earlier token — through the
    state, far beyond the convolution's four taps — and with no later
    one."""
    seq, t = 20, 12
    x = np.random.default_rng(2).normal(size=(seq, D)).astype(np.float32)

    def out_at_t(touched):
        moved = x.copy()
        moved[touched] += np.linspace(-1.0, 1.0, D)
        got = ref.block(layers[1], jnp.asarray(moved), jnp.arange(seq),
                        **DIMS)
        return np.asarray(got[t])

    base = out_at_t([])
    assert np.abs(out_at_t([t - 9]) - base).max() > 1e-4
    assert np.abs(out_at_t([t + 1]) - base).max() == 0.0


def test_no_layer_knows_a_position(layers):
    seq = 9
    x = jnp.asarray(np.random.default_rng(3).normal(size=(seq, D)),
                    jnp.float32)
    for layer in layers:
        here = ref.block(layer, x, jnp.arange(seq), **DIMS)
        stretched = ref.block(layer, x, jnp.arange(seq) * 3 + 7, **DIMS)
        np.testing.assert_array_equal(np.asarray(stretched),
                                      np.asarray(here))


def test_forward_takes_each_layer_by_its_kind(layers):
    rng = np.random.default_rng(5)
    embed = jnp.asarray(rng.normal(size=(50, D)), jnp.float32)
    head = jnp.asarray(rng.normal(size=(D, 50)) * 0.3, jnp.float32)
    tokens = jnp.asarray(rng.integers(0, 50, 11))
    order = [layers[0], layers[1], layers[1]]
    got = ref.forward(embed, (order.__getitem__, 3), jnp.ones(D), head,
                      tokens, **DIMS)
    x = np.asarray(embed, np.float64)[np.asarray(tokens)]
    for layer in order:
        x = loops(layer, x)
    want = x / np.sqrt(np.mean(x * x, -1, keepdims=True) + 1e-5) \
        @ np.asarray(head, np.float64)
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-4)


# ------------------------------------------------ operations and bytes

def test_counts_are_the_issues_arithmetic():
    c = opsbytes_recurrent.counts(spec())
    assert c["linear_matmul"] == 4 * 33_554_432 + 2 * (
        4096 * 128 + 128 * 8192) + 4096 * 64
    assert c["softmax_matmul"] == 3 * 33_554_432 + 2 * 4_194_304
    assert c["expert"] == 15_728_640 == c["shared"]
    assert c["router"] == 1_310_720
    assert c["total"] == 3_308_352_064         # 6.16 GiB of bfloat16
    assert opsbytes_recurrent.layer_kinds(spec()) == (3, 1)
    assert opsbytes_recurrent.state_values(spec()) == 64 * 128 * 128
    assert opsbytes_recurrent.conv_tail_values(spec()) == 3 * 24_576


def test_a_decode_step_counts_the_rows_decoded_and_what_is_read():
    s = spec()
    a_row = 3 * 2 * (4 * 64 * 128 * 128 + 2 * 3 * 24_576)    # r + w
    assert opsbytes_recurrent.state_bytes(s, 1) == a_row
    assert a_row == 3 * 2 * 4_341_760                 # 4.14 MiB each way
    position = 2 * 8 * 128 * 2          # ONE softmax layer: 4,096 B
    assert opsbytes_recurrent.cache_bytes(s, [999, 19_999]) == (
        1000 + 20_000) * position
    every = opsbytes_recurrent.decode_step(s, [19_999, 999], 1.0, 1.0)
    none = opsbytes_recurrent.decode_step(s, [19_999, 999], 0.0, 1.0)
    assert every["expert_bytes"] == 2 * 4 * 40 * 15_728_640
    assert every["bytes"] - none["bytes"] == every["expert_bytes"]
    # held weights outside the experts once, the head once, of the
    # embedding two rows; the state of the two rows decoded
    assert none["bytes"] == 2 * (
        3_308_352_064 - 4 * 40 * 15_728_640 - 100_663_296) + 2 * a_row + (
        1000 + 20_000) * position + 2 * 4096 * 2
    # twenty-four slots' states are not what two rows need
    assert every["state_bytes"] == 2 * a_row
    # bound by bytes, not by operations, at any batch the cell reaches
    full = opsbytes_recurrent.decode_step(s, [12_000] * 24, 0.5, 1.0)
    assert full["bytes"] / 819e9 > full["flops"] / 197e12


def _obs(programs=None, **engine):
    before = dict.fromkeys(engine, 0)
    return {"traced": {"engine": engine, "engine_before": before,
                       "wall": 1020.0, "host_window_s": 4.0,
                       "chunk_width": 512},
            "window_wall": 1000.0, "config": spec(),
            "peaks": {"hbm_bytes_per_s": 819e9,
                      "bf16_flops_per_s": 197e12},
            "client": {"requests": [(8000, [10.0 + 0.05 * i
                                            for i in range(400)])] * 20},
            "trace": programs and {"devices": [{"programs": programs}]}}


def test_state_live_pct_on_a_hand_made_window():
    obs = _obs(recurrent_decode_rows=3 * 20 * 100,
               recurrent_slot_rows=3 * 24 * 100)
    assert recurrent_state_live_pct.read(obs) == pytest.approx(100 * 20 / 24)
    assert recurrent_state_live_pct.read(_obs(recurrent_slot_rows=0,
                                              recurrent_decode_rows=0)) is None
    # the parent's program has no such counter; nothing traced
    assert recurrent_state_live_pct.read(_obs(decode_steps=5)) is None
    assert recurrent_state_live_pct.read({"traced": None}) is None
    assert recurrent_state_live_pct.read({}) is None


def test_recurrent_decode_roofline_is_least_time_over_step_time():
    counters = dict(moe_decode_experts_hit=64, moe_decode_expert_slots=160,
                    moe_decode_assignments=20, moe_decode_rows_routed=160,
                    recurrent_decode_rows=60)
    programs = {"jit__decode": {"count": 100, "total_s": 1.2},
                "jit__sample_batch": {"count": 100, "total_s": 0.05}}
    obs = _obs(programs, **counters)
    got = recurrent_decode_roofline_pct.read(obs)
    # twenty rows at contexts of about 8,200: what must be moved at
    # 819 GB/s over the 12.5 ms a step took
    need = opsbytes_recurrent.decode_step(spec(), [8200] * 20, 0.4, 1.0)
    assert got == pytest.approx(
        100 * need["bytes"] / 819e9 / 12.5e-3, rel=0.02)
    assert 0 < got < 100
    # no device trace -> no step time -> nothing; a program without the
    # recurrent counters (the parent) reports nothing either
    assert recurrent_decode_roofline_pct.read(_obs(None, **counters)) is None
    del counters["recurrent_decode_rows"]
    assert recurrent_decode_roofline_pct.read(
        _obs(programs, **counters)) is None
    assert recurrent_decode_roofline_pct.read({"traced": None}) is None
    assert recurrent_decode_roofline_pct.read({}) is None


def test_a_chunk_counts_one_slots_state_and_the_weights_once():
    s = spec()
    a_row = 3 * 2 * 4_341_760
    position = 2 * 8 * 128 * 2
    whole = opsbytes_recurrent.prefill_chunk(s, 4096, 512, 1.0, 1.0)
    # every weight held once but the embedding (its rows only), one
    # slot's state each way, 4,608 positions read and 512 written
    assert whole["bytes"] == 2 * (3_308_352_064 - 100_663_296) + a_row + (
        4096 + 2 * 512) * position + 2 * 4096 * 512
    assert whole["state_bytes"] == a_row
    fewer = opsbytes_recurrent.prefill_chunk(s, 4096, 512, 0.5, 1.0)
    assert whole["bytes"] - fewer["bytes"] == 4 * 20 * 15_728_640 * 2
    # a token: the weights' products, three with the state in each
    # linear layer, its pairs in the softmax layer; the head once
    one = opsbytes_recurrent.prefill_chunk(s, 0, 1, 1.0, 1.0)
    c = opsbytes_recurrent.counts(s)
    assert one["flops"] == 2.0 * (
        3 * c["linear_matmul"] + c["softmax_matmul"]
        + 4 * (c["shared"] + c["router"] + c["expert"])
        + c["head"]) + 3 * 3 * 2 * 64 * 128 * 128 + 2 * 2 * 64 * 128
    # bound by the weights' bytes up to the chunk the cell uses
    assert whole["bytes"] / 819e9 > whole["flops"] / 197e12


def test_chunk_fill_pct_on_a_hand_made_window():
    obs = _obs(recurrent_chunk_tokens=3 * 9_000,
               recurrent_chunk_rows=3 * 20 * 512)
    assert recurrent_chunk_fill_pct.read(obs) == pytest.approx(
        100 * 9_000 / 10_240)
    # no chunk in the window; the parent's program; nothing traced
    assert recurrent_chunk_fill_pct.read(_obs(
        recurrent_chunk_tokens=0, recurrent_chunk_rows=0)) is None
    assert recurrent_chunk_fill_pct.read(_obs(chunks=5)) is None
    assert recurrent_chunk_fill_pct.read({"traced": None}) is None
    assert recurrent_chunk_fill_pct.read({}) is None


def test_recurrent_chunk_roofline_is_least_time_over_chunk_time():
    counters = dict(
        recurrent_chunk_tokens=3 * 9_000, recurrent_chunk_rows=3 * 20 * 512,
        moe_experts_hit=4 * 40 * 20 + 64, moe_decode_experts_hit=64,
        moe_expert_slots=4 * 40 * 20 + 160, moe_decode_expert_slots=160,
        moe_assignments=10_240 + 20, moe_decode_assignments=20,
        moe_rows_routed=81_920 + 160, moe_decode_rows_routed=160)
    programs = {"jit__prefill_chunk": {"count": 20, "total_s": 0.46},
                "jit__decode": {"count": 100, "total_s": 1.2}}
    got = recurrent_chunk_roofline_pct.read(_obs(programs, **counters))
    # prompts of 8,000 tokens in chunks of 512: the mean chunk starts
    # at 3,840 and holds 450 real tokens; every held expert hit
    need = opsbytes_recurrent.prefill_chunk(spec(), 3840, 450, 1.0, 1.0)
    assert got == pytest.approx(100 * need["bytes"] / 819e9 / 23e-3)
    assert 30 < got < 40
    # no device trace -> no chunk time; no chunk in the window; a
    # program without the recurrent counters (the parent)
    assert recurrent_chunk_roofline_pct.read(_obs(None, **counters)) is None
    assert recurrent_chunk_roofline_pct.read(_obs(programs, **{
        **counters, "recurrent_chunk_rows": 0})) is None
    del counters["recurrent_chunk_tokens"]
    assert recurrent_chunk_roofline_pct.read(
        _obs(programs, **counters)) is None
    assert recurrent_chunk_roofline_pct.read({"traced": None}) is None
    assert recurrent_chunk_roofline_pct.read({}) is None
