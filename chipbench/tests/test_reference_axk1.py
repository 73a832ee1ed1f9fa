"""The A.X-K1 reference against a third evaluation of its equations,
written as loops in numpy float64 — one token, one head, one cached
position, one chosen expert at a time — at a tiny size; that it imports
nothing of the program; ``opsbytes_latent`` against the table of the
issue that added the configuration; and the two readers on a hand-made
``obs``."""

import ast
import json
import math
import os

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import opsbytes_latent
from chipbench.layer_metrics import (latent_decode_roofline_pct,
                                     moe_local_share_pct)
from chipbench.reference import axk1_decoder as ref

HERE = os.path.dirname(os.path.abspath(__file__))
D, H, NOPE, ROPE, V, RQ, RKV, F, E, HELD, FS = 32, 4, 8, 4, 6, 12, 10, 16, \
    8, 4, 16
YARN = dict(yarn_factor=4.0, yarn_original=32.0, yarn_beta_fast=32.0,
            yarn_beta_slow=1.0, yarn_mscale=1.0, yarn_mscale_all_dim=1.0)
DIMS = dict(n_heads=H, n_kv_heads=H, rope_theta=100.0, norm_eps=1e-6,
            experts_per_token=2, routed_scaling_factor=2.5, first_expert=4,
            **YARN)


def spec():
    with open(os.path.join(HERE, "..", "configs", "ax-k1.json")) as f:
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
        n.split(".")[0] in ("__future__", "functools", "jax")
        or n == "chipbench.reference" or n.startswith("chipbench.reference.")
        for n in names), names


@pytest.fixture(scope="module")
def layers():
    rng = np.random.default_rng(0)

    def w(*shape, scale=0.3):
        return jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)

    attn = lambda: {                                        # noqa: E731
        "attn_norm": 1 + w(D), "w_qa": w(D, RQ), "q_a_norm": 1 + w(RQ),
        "w_qb": w(RQ, H * (NOPE + ROPE)), "w_kva": w(D, RKV + ROPE),
        "kv_a_norm": 1 + w(RKV), "w_kvb": w(RKV, H * (NOPE + V)),
        "wo": w(H * V, D), "mlp_norm": 1 + w(D)}
    dense = {**attn(), "w_gate": w(D, F), "w_up": w(D, F), "w_down": w(F, D)}
    routed = {**attn(), "router": w(D, E, scale=1.0),
              "w_gate": w(HELD, D, F), "w_up": w(HELD, D, F),
              "w_down": w(HELD, F, D), "shared_gate": w(D, FS),
              "shared_up": w(D, FS), "shared_down": w(FS, D)}
    return [dense, routed]


def loops(layer, x, positions):
    """One layer, by the docstring's equations, scalar loops."""
    lw = {n: np.asarray(v, np.float64) for n, v in layer.items()}
    seq = len(x)

    def norm(v, weight):
        return v / math.sqrt(np.mean(v * v) + DIMS["norm_eps"]) * weight

    def silu(a):
        return a / (1 + np.exp(-a))

    # YaRN: ROPE 4 -> 2 pairs; theta 100, factor 4 over 32
    inv = []
    for j in range(ROPE // 2):
        f = 100.0 ** (-2 * j / ROPE)
        low = max(math.floor(ROPE * math.log(32 / (32 * 2 * math.pi))
                             / (2 * math.log(100.0))), 0)
        high = min(math.ceil(ROPE * math.log(32 / (1 * 2 * math.pi))
                             / (2 * math.log(100.0))), ROPE - 1)
        ramp = min(max((j - low) / max(high - low, 1e-3), 0.0), 1.0)
        inv.append(f / 4.0 * ramp + f * (1 - ramp))
    m = 0.1 * 1.0 * math.log(4.0) + 1.0

    def rotate(v, t):
        out = v.copy()
        for j in range(ROPE // 2):
            c, s = math.cos(t * inv[j]), math.sin(t * inv[j])
            out[2 * j] = v[2 * j] * c - v[2 * j + 1] * s
            out[2 * j + 1] = v[2 * j + 1] * c + v[2 * j] * s
        return out

    out = np.zeros_like(x)
    hs = [norm(x[t], lw["attn_norm"]) for t in range(seq)]
    c_kv = [norm((hs[s] @ lw["w_kva"])[:RKV], lw["kv_a_norm"])
            for s in range(seq)]
    k_rope = [rotate((hs[s] @ lw["w_kva"])[RKV:], positions[s])
              for s in range(seq)]
    for t in range(seq):
        q = (norm(hs[t] @ lw["w_qa"], lw["q_a_norm"]) @ lw["w_qb"]).reshape(
            H, NOPE + ROPE)
        heads = []
        for i in range(H):
            q_rope = rotate(q[i, NOPE:], positions[t])
            scores, values = [], []
            for s in range(t + 1):
                kv = (c_kv[s] @ lw["w_kvb"]).reshape(H, NOPE + V)[i]
                scores.append((q[i, :NOPE] @ kv[:NOPE] + q_rope @ k_rope[s])
                              * (NOPE + ROPE) ** -0.5 * m * m)
                values.append(kv[NOPE:])
            p = np.exp(np.asarray(scores) - max(scores))
            heads.append((p / p.sum()) @ np.asarray(values))
        x1 = x[t] + np.concatenate(heads) @ lw["wo"]
        h = norm(x1, lw["mlp_norm"])
        if "router" not in lw:
            y = (silu(h @ lw["w_gate"]) * (h @ lw["w_up"])) @ lw["w_down"]
        else:
            s = 1 / (1 + np.exp(-(h @ lw["router"])))
            chosen = np.argsort(-s)[:2]
            y = (silu(h @ lw["shared_gate"]) * (h @ lw["shared_up"])) \
                @ lw["shared_down"]
            for e in chosen:
                if 4 <= e < 4 + HELD:                      # held here
                    g = s[e] / (s[chosen].sum() + 1e-20) * 2.5
                    a = h @ lw["w_gate"][e - 4]
                    y = y + g * ((silu(a) * (h @ lw["w_up"][e - 4]))
                                 @ lw["w_down"][e - 4])
        out[t] = x1 + y
    return out


def test_block_equals_the_equations_written_as_loops(layers):
    x = np.random.default_rng(1).normal(size=(7, D))
    positions = np.arange(3, 10)                 # not from zero: RoPE shows
    for layer in layers:
        got = ref.block(layer, jnp.asarray(x, jnp.float32),
                        jnp.asarray(positions), **DIMS)
        np.testing.assert_allclose(got, loops(layer, x, positions),
                                   rtol=3e-5, atol=3e-6)
        x = np.asarray(got, np.float64)


def test_some_assignments_fall_on_absent_experts_and_add_nothing(layers):
    """The gate map is over all 8 experts; only columns 4-7 are used."""
    h = jnp.asarray(np.random.default_rng(2).normal(size=(40, D)),
                    jnp.float32)
    gates = np.asarray(ref.gate_map(h, layers[1]["router"], 2, 2.5))
    assert ((gates > 0).sum(-1) == 2).all()
    np.testing.assert_allclose(gates.sum(-1), 2.5, rtol=1e-6)
    assert (gates[:, :4] > 0).any() and (gates[:, 4:] > 0).any()
    mine = ref.held_experts(layers[1], h, jnp.asarray(gates), 4)
    only = gates.copy()
    only[:, :4] = 0.0
    np.testing.assert_allclose(mine, ref.held_experts(
        layers[1], h, jnp.asarray(only), 4), rtol=1e-6)


def test_dims_of_reads_the_published_numbers_and_the_share():
    dims = ref.dims_of(spec())
    assert dims["n_heads"] == 64 and dims["experts_per_token"] == 8
    assert dims["routed_scaling_factor"] == 2.5 and dims["first_expert"] == 0
    assert (dims["yarn_factor"], dims["yarn_original"]) == (32.0, 4096.0)


# ------------------------------------------------------- opsbytes_latent

def test_parameters_and_cache_bytes_are_the_issues_table():
    c = opsbytes_latent.counts(spec())
    assert c["attention"] == 101_138_432
    assert c["expert"] == c["shared"] == 44_040_192
    assert c["router"] == 1_376_256
    assert c["held"] * c["expert"] == 528_482_304
    assert c["moe_layer"] == 675_037_184
    assert c["dense_layer"] == 497_500_160
    assert c["embed"] + c["head"] == 293_601_280
    assert c["total"] == 4_841_331_712
    assert opsbytes_latent.cache_bytes_per_position(spec()) == 8_064
    assert 48 * 4096 * 8_064 / 1e9 == pytest.approx(1.585, abs=1e-3)


def test_a_decode_step_reads_held_weights_once_and_the_experts_hit():
    s = spec()
    c = opsbytes_latent.counts(s)
    none = opsbytes_latent.decode_step(s, [0], 0.0, 0.0)
    every = opsbytes_latent.decode_step(s, [0], 1.0, 0.0)
    assert every["bytes"] - none["bytes"] == 2 * 6 * 12 * c["expert"]
    assert every["bytes"] == 2 * (c["total"] - c["embed"]) + 8064 + 2 * 7168
    # the issue's arithmetic: 87 % of the held experts hit, 48 contexts
    full = opsbytes_latent.decode_step(s, [4095] * 48, 0.87, 0.5)
    weights = full["bytes"] - 48 * 4096 * 8064 - 48 * 2 * 7168
    assert weights / 1e9 == pytest.approx(8.6, abs=0.1)
    assert full["attention_flops"] / 1e9 == pytest.approx(190, abs=2)
    assert full["attention_flops"] == 2 * 64 * (576 + 512) * 48 * 4096 * 7
    # half a held expert a token, the shared one, the router, the head
    per_token = (7 * c["attention_matmul"] + c["dense_mlp"] + 6 * (
        c["shared"] + c["router"] + 0.5 * c["expert"]) + 7168 * 20480)
    assert full["flops"] == pytest.approx(
        2 * 48 * per_token + full["attention_flops"])
    assert full["bytes"] / 819e9 > full["flops"] / 197e12     # bytes bind


# --------------------------------------------------------------- readers

def obs(**more):
    before = {"moe_assignments": 1000, "moe_rows_routed": 16000,
              "moe_experts_hit": 500, "moe_expert_slots": 720}
    after = {"moe_assignments": 1000 + 2400, "moe_rows_routed": 16000 + 38400,
             "moe_experts_hit": 500 + 6480, "moe_expert_slots": 720 + 7200}
    # of these the decode steps' (half the executions; the chunks hit
    # more of the held experts and found more of their rows local)
    decode = {"moe_decode_assignments": 1152, "moe_decode_rows_routed": 18432,
              "moe_decode_experts_hit": 2700, "moe_decode_expert_slots": 3600}
    before.update(dict.fromkeys(decode, 7))
    after.update({name: 7 + n for name, n in decode.items()})
    return {"traced": {"engine": after, "engine_before": before,
                       "wall": 112.0, "host_window_s": 4.0},
            "window_wall": 100.0, "config": spec(), **more}


def test_local_share_is_assignments_held_over_pairs_routed():
    assert moe_local_share_pct.read(obs()) == pytest.approx(6.25)
    # the parent has no such counter: nothing, and no error
    parent = obs()
    for part in ("engine", "engine_before"):
        for name in [n for n in parent["traced"][part] if "_routed" in n]:
            del parent["traced"][part][name]
    assert moe_local_share_pct.read(parent) is None
    assert latent_decode_roofline_pct.read(parent) is None
    assert moe_local_share_pct.read({}) is None


def test_roofline_share_is_the_bound_over_the_step_time():
    s = spec()
    # 48 requests, each decoding through the traced window at context
    # 1,000 (prompt 1,000 and a token every second before the window)
    requests = [[1000, [-5.0, 20.0]] for _ in range(48)]
    trace = {"devices": [{"programs": {
        "jit__decode": {"count": 100, "total_s": 2.5},
        "jit__sample_batch": {"count": 100, "total_s": 0.3}}}]}
    got = latent_decode_roofline_pct.read(obs(
        client={"requests": requests}, trace=trace,
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}))
    need = opsbytes_latent.decode_step(s, [1001] * 48, 0.75, 0.5)
    assert need["bytes"] / 819e9 > need["flops"] / 197e12
    assert got == pytest.approx(100 * need["bytes"] / 819e9 / 0.028, rel=1e-6)
    assert 30 < got < 50
