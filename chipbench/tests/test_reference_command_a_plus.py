"""The Command A+ reference against a third evaluation of its equations,
written as loops in numpy float64 — one token, one head, one seen
position, one chosen expert at a time — at a toy size (window 5): the
window's edge (``t - s`` = window - 1 seen, window not), full layers
that rotate nothing, the shared experts averaged, the share; that it
imports nothing of the program; ``opsbytes_mixed`` against the
arithmetic of the issue that added the configuration; and the two
readers on a hand-made ``obs``."""

import ast
import json
import math
import os

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import opsbytes_mixed
from chipbench.layer_metrics import mixed_decode_roofline_pct, window_walk_pct
from chipbench.reference import command_a_plus_decoder as ref

HERE = os.path.dirname(os.path.abspath(__file__))
D, H, KVH, HD, F, E, HELD, SHARED, WINDOW = 24, 4, 2, 6, 10, 8, 4, 4, 5
DIMS = dict(n_heads=H, n_kv_heads=KVH, rope_theta=100.0, norm_eps=1e-5,
            window=WINDOW, experts_per_token=2, n_shared_experts=SHARED,
            first_expert=4)


def spec():
    with open(os.path.join(HERE, "..", "configs",
                           "command-a-plus.json")) as f:
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

    def layer(windowed):
        return {"attn_norm": 1 + w(D), "wq": w(D, H * HD),
                "wk": w(D, KVH * HD), "wv": w(D, KVH * HD),
                "wo": w(H * HD, D), "windowed": jnp.float32(windowed),
                "router": w(D, E, scale=1.0), "w_gate": w(HELD, D, F),
                "w_up": w(HELD, D, F), "w_down": w(HELD, F, D),
                "shared_gate": w(D, SHARED * F),
                "shared_up": w(D, SHARED * F),
                "shared_down": w(SHARED * F, D)}

    return [layer(True), layer(False)]


def loops(layer, x, positions):
    """One layer, by the docstring's equations, scalar loops."""
    lw = {n: np.asarray(v, np.float64) for n, v in layer.items()}
    seq, windowed = len(x), bool(lw["windowed"] > 0)

    def norm(v, weight):
        v = v - np.mean(v)
        return v / math.sqrt(np.mean(v * v) + DIMS["norm_eps"]) * weight

    def silu(a):
        return a / (1 + np.exp(-a))

    def rotate(v, t):
        if not windowed:
            return v                         # a full layer rotates nothing
        out = v.copy()
        for j in range(HD // 2):
            angle = t * 100.0 ** (-2 * j / HD)
            c, s = math.cos(angle), math.sin(angle)
            out[2 * j] = v[2 * j] * c - v[2 * j + 1] * s
            out[2 * j + 1] = v[2 * j + 1] * c + v[2 * j] * s
        return out

    def expert(h, gate, up, down):
        return (silu(h @ gate) * (h @ up)) @ down

    out = np.zeros_like(x)
    hs = [norm(x[t], lw["attn_norm"]) for t in range(seq)]
    ks = [(hs[s] @ lw["wk"]).reshape(KVH, HD) for s in range(seq)]
    vs = [(hs[s] @ lw["wv"]).reshape(KVH, HD) for s in range(seq)]
    for t in range(seq):
        q = (hs[t] @ lw["wq"]).reshape(H, HD)
        heads = []
        for i in range(H):
            kv = i // (H // KVH)
            seen = [s for s in range(seq)
                    if 0 <= positions[t] - positions[s]
                    and (not windowed
                         or positions[t] - positions[s] < WINDOW)]
            scores = np.array([
                rotate(q[i], positions[t]) @ rotate(ks[s][kv], positions[s])
                / math.sqrt(HD) for s in seen])
            p = np.exp(scores - scores.max())
            p /= p.sum()
            heads.append(sum(p[n] * vs[s][kv] for n, s in enumerate(seen)))
        attn = np.concatenate(heads) @ lw["wo"]
        # the router over all E experts; of the top 2, those held (4-7)
        score = 1 / (1 + np.exp(-(hs[t] @ lw["router"])))
        best = np.argsort(-score)[:2]
        routed = np.zeros(D)
        for e in best:
            if DIMS["first_expert"] <= e < DIMS["first_expert"] + HELD:
                j = e - DIMS["first_expert"]
                routed += score[e] / (score[best].sum() + 1e-20) * expert(
                    hs[t], lw["w_gate"][j], lw["w_up"][j], lw["w_down"][j])
        shared = sum(expert(hs[t],
                            lw["shared_gate"][:, j * F:(j + 1) * F],
                            lw["shared_up"][:, j * F:(j + 1) * F],
                            lw["shared_down"][j * F:(j + 1) * F])
                     for j in range(SHARED)) / SHARED
        out[t] = x[t] + attn + routed + shared
    return out


@pytest.mark.parametrize("blocks", [4, 64], ids=["query-blocks-of-4",
                                                 "one-query-block"])
def test_block_equals_the_equations_token_by_token(layers, blocks,
                                                   monkeypatch):
    monkeypatch.setattr(ref, "QUERY_BLOCK", blocks)
    seq = 13                                   # > 2 windows of 5
    x = np.random.default_rng(1).normal(size=(seq, D))
    positions = np.arange(seq)
    for layer in layers:
        got = ref.block(layer, jnp.asarray(x, jnp.float32),
                        jnp.asarray(positions), **DIMS)
        np.testing.assert_allclose(got, loops(layer, x, positions),
                                   rtol=2e-4, atol=2e-5)


def test_the_windows_edge(layers):
    """Query t sees key s iff 0 <= t - s < window: moving the value at
    distance window - 1 changes a window layer's output at t, moving the
    one at distance window does not; a full layer sees both."""
    seq, t = 12, 11
    x = np.random.default_rng(2).normal(size=(seq, D)).astype(np.float32)

    def out_at_t(layer, touched):
        moved = x.copy()          # not a constant: LayerNorm takes it off
        moved[touched] += np.linspace(-1.0, 1.0, D)
        got = ref.block(layer, jnp.asarray(moved), jnp.arange(seq), **DIMS)
        return np.asarray(got[t])

    for layer, sees_beyond in zip(layers, (False, True)):
        base = out_at_t(layer, [])
        inside = np.abs(out_at_t(layer, [t - (WINDOW - 1)]) - base).max()
        beyond = np.abs(out_at_t(layer, [t - WINDOW]) - base).max()
        assert inside > 1e-4
        assert (beyond > 1e-4) == sees_beyond
        if not sees_beyond:
            assert beyond == 0.0


def test_a_full_layer_knows_no_position(layers):
    """No positional embedding at all: a full layer's output does not
    change when every position is shifted; a window layer's does not
    either (rotary scores depend on differences) but it does change
    when the positions are stretched."""
    seq = 9
    x = jnp.asarray(np.random.default_rng(3).normal(size=(seq, D)),
                    jnp.float32)
    for layer, windowed in zip(layers, (True, False)):
        wide = {**DIMS, "window": 10 ** 6}
        here = ref.block(layer, x, jnp.arange(seq), **wide)
        shifted = ref.block(layer, x, jnp.arange(seq) + 7, **wide)
        stretched = ref.block(layer, x, jnp.arange(seq) * 3, **wide)
        np.testing.assert_allclose(shifted, here, rtol=1e-4, atol=1e-5)
        assert (np.abs(np.asarray(stretched - here)).max() > 1e-3) \
            == windowed


def test_the_shares_routed_parts_add_up(layers):
    """Experts 0-3 and 4-7 held by two ranks: their routed parts are the
    routed part with all eight held; the gates are normalised over the
    top 2 whoever holds them."""
    layer = layers[0]
    rng = np.random.default_rng(4)
    other = {n: jnp.asarray(rng.normal(size=layer[n].shape) * 0.3,
                            jnp.float32)
             for n in ("w_gate", "w_up", "w_down")}
    h = jnp.asarray(rng.normal(size=(11, D)), jnp.float32)
    gates = ref.gate_map(h, layer["router"], 2)
    np.testing.assert_allclose(gates.sum(-1), 1.0, rtol=1e-6)
    assert ((np.asarray(gates) > 0).sum(-1) == 2).all()
    low = ref.held_experts({**layer, **other}, h, gates, 0)
    high = ref.held_experts(layer, h, gates, 4)
    whole = ref.held_experts(
        {n: jnp.concatenate([other[n], layer[n]]) for n in other},
        h, gates, 0)
    np.testing.assert_allclose(low + high, whole, rtol=1e-5, atol=1e-6)


# ------------------------------------------------ operations and bytes

def test_counts_are_the_issues_arithmetic():
    c = opsbytes_mixed.counts(spec())
    assert c["attention_matmul"] == 2 * 67_108_864 + 2 * 4_194_304
    assert c["expert"] == 50_331_648 and c["shared"] == 201_326_592
    assert c["router"] == 524_288
    assert c["layer"] == 1_149_767_680
    assert c["total"] == 4_733_292_544
    assert opsbytes_mixed.layer_kinds(spec()) == (3, 1)


def test_a_decode_step_counts_what_is_read_not_what_is_reserved():
    s = spec()
    position = 2 * 8 * 128 * 2                       # 4,096 B a layer
    # a context inside the window reads the same on all four layers
    assert opsbytes_mixed.cache_bytes(s, [999]) == 4 * 1000 * position
    # beyond it the window layers stop at 4,096, the full layer goes on
    assert opsbytes_mixed.cache_bytes(s, [19_999]) == (
        20_000 + 3 * 4096) * position
    every = opsbytes_mixed.decode_step(s, [19_999, 999], 1.0, 1.0)
    none = opsbytes_mixed.decode_step(s, [19_999, 999], 0.0, 1.0)
    assert every["bytes"] - none["bytes"] == 2 * 4 * 16 * 50_331_648
    assert every["expert_bytes"] == 2 * 4 * 16 * 50_331_648
    # the tied embedding is read once, as the head
    assert none["bytes"] == 2 * (4_733_292_544 - 4 * 16 * 50_331_648) + (
        (20_000 + 3 * 4096) + 4 * 1000) * position + 2 * 4096 * 2
    # what a ring reserves (4,608 rows) and a slab (32,768) is not read
    assert every["cache_bytes"] < 16 * (32_768 + 3 * 4608) * position


def _obs(**engine):
    before = dict.fromkeys(engine, 0)
    return {"traced": {"engine": engine, "engine_before": before,
                       "wall": 1020.0, "host_window_s": 4.0},
            "window_wall": 1000.0, "config": spec(),
            "peaks": {"hbm_bytes_per_s": 819e9,
                      "bf16_flops_per_s": 197e12},
            "client": {"requests": [(8000, [10.0 + 0.05 * i
                                            for i in range(400)])]},
            "trace": {"programs": {}}}


def test_window_walk_pct_on_a_hand_made_window():
    # 3 window layers walked 4,608, the full layer 16,384: 28 %
    obs = _obs(window_span_positions=3 * 4608 * 10,
               full_span_positions=16384 * 10)
    assert window_walk_pct.read(obs) == pytest.approx(100 * 4608 / 16384)
    # nothing past the window: the rings are walked as far as the slab
    obs = _obs(window_span_positions=3 * 1024, full_span_positions=1024)
    assert window_walk_pct.read(obs) == pytest.approx(100.0)
    assert window_walk_pct.read(_obs(full_span_positions=5)) is None
    assert window_walk_pct.read({"traced": None}) is None


def test_mixed_decode_roofline_reads_nothing_without_its_counters():
    obs = _obs(moe_decode_experts_hit=40, moe_decode_expert_slots=64,
               moe_decode_assignments=10, moe_decode_rows_routed=80)
    # no device trace -> no step time -> nothing; and a program without
    # the window counters (the parent) reports nothing either
    assert mixed_decode_roofline_pct.read(obs) is None
    assert mixed_decode_roofline_pct.read({"traced": None}) is None
