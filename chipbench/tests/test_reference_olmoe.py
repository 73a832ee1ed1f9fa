"""The OLMoE reference against a second, independent few-line
formulation of what is its own (the router with its un-renormalised
gates, the RMSNorm over the whole q and k projections), that it imports
nothing of the program, and the two routing metrics on a hand-made
``obs``."""

import ast
import importlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import olmoe_decoder as ref

HERE = os.path.dirname(os.path.abspath(__file__))


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
    assert not any("ant_ray_tpu" in n for n in names)


@pytest.fixture(scope="module")
def layer():
    rng = np.random.default_rng(0)
    d, h, kvh, hd, f, e = 32, 4, 2, 8, 16, 8

    def w(*shape, scale=0.3):
        return jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)

    return {"attn_norm": 1 + w(d), "wq": w(d, h * hd), "wk": w(d, kvh * hd),
            "wv": w(d, kvh * hd), "q_norm": 1 + w(h * hd),
            "k_norm": 1 + w(kvh * hd), "wo": w(h * hd, d),
            "mlp_norm": 1 + w(d), "router": w(d, e, scale=1.0),
            "w_gate": w(e, d, f), "w_up": w(e, d, f), "w_down": w(e, f, d)}


@pytest.mark.parametrize("k,norm", [(2, False), (3, False), (2, True)])
def test_gates_and_experts_equal_a_loop_over_each_tokens_experts(layer, k,
                                                                 norm):
    """numpy, float64, one token and one chosen expert at a time."""
    h = np.random.default_rng(1).normal(size=(11, 32))
    got = ref.experts(layer, jnp.asarray(h, jnp.float32), ref.gate_map(
        jnp.asarray(h, jnp.float32), layer["router"], k, norm))
    lw = {n: np.asarray(v, np.float64) for n, v in layer.items()}
    want = np.zeros_like(h)
    for t, x in enumerate(h):
        z = x @ lw["router"]
        p = np.exp(z - z.max()) / np.exp(z - z.max()).sum()
        chosen = np.argsort(-p)[:k]
        total = p[chosen].sum() if norm else 1.0
        for e in chosen:
            a = x @ lw["w_gate"][e]
            want[t] += p[e] / total * (
                (a / (1 + np.exp(-a)) * (x @ lw["w_up"][e]))
                @ lw["w_down"][e])
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-6)


def test_gate_map_keeps_k_probabilities_as_they_are(layer):
    h = jnp.asarray(np.random.default_rng(2).normal(size=(9, 32)),
                    jnp.float32)
    gates = np.asarray(ref.gate_map(h, layer["router"], 3, False))
    probs = np.asarray(jax.nn.softmax(h @ layer["router"], axis=-1))
    assert ((gates > 0).sum(-1) == 3).all()
    np.testing.assert_array_equal(gates[gates > 0], probs[gates > 0])
    assert (gates.sum(-1) < 1).all()             # not renormalised
    normed = np.asarray(ref.gate_map(h, layer["router"], 3, True))
    np.testing.assert_allclose(normed.sum(-1), 1.0, rtol=1e-6)


def test_qk_norm_is_over_the_whole_projection_before_rotary(layer):
    """``block`` against attention put together by hand from q and k
    normalised over all heads at once — and NOT equal to the same with
    a per-head norm."""
    x = jnp.asarray(np.random.default_rng(3).normal(size=(6, 32)),
                    jnp.float32)
    dims = dict(n_heads=4, n_kv_heads=2, rope_theta=10000.0, norm_eps=1e-5)
    pos = jnp.arange(6)

    def by_hand(per_head):
        h = ref.rms_norm(x, layer["attn_norm"], 1e-5)

        def norm(y, weight, heads):
            if per_head:
                y3 = y.reshape(6, heads, 8)
                y3 = y3 / jnp.sqrt(jnp.mean(y3 ** 2, -1, keepdims=True)
                                   + 1e-5)
                return y3.reshape(6, -1) * weight
            return y / jnp.sqrt(jnp.mean(y ** 2, -1, keepdims=True)
                                + 1e-5) * weight

        q = norm(h @ layer["wq"], layer["q_norm"], 4).reshape(6, 4, 8)
        k = norm(h @ layer["wk"], layer["k_norm"], 2).reshape(6, 2, 8)
        v = (h @ layer["wv"]).reshape(6, 2, 8)
        a = ref.attention(ref.rotary(q, pos, 10000.0),
                          ref.rotary(k, pos, 10000.0), v).reshape(6, 32)
        y = x + a @ layer["wo"]
        hm = ref.rms_norm(y, layer["mlp_norm"], 1e-5)
        return y + ref.experts(layer, hm, ref.gate_map(
            hm, layer["router"], 2, False))

    got = ref.block(layer, x, pos, **dims, experts_per_token=2,
                    norm_topk_prob=False)
    np.testing.assert_allclose(got, by_hand(False), rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(got - by_hand(True)).max()) > 1e-2


def test_block_takes_k_and_the_norm_flag_as_traced_values(layer):
    """The harness jits ``block`` with the dense reference's four static
    names only; the other two arrive traced."""
    x = jnp.asarray(np.random.default_rng(4).normal(size=(5, 32)),
                    jnp.float32)
    dims = dict(n_heads=4, n_kv_heads=2, rope_theta=10000.0, norm_eps=1e-5)
    jitted = jax.jit(ref.block, static_argnames=tuple(dims))
    for k, norm in ((2, False), (3, True)):
        np.testing.assert_allclose(
            jitted(layer, x, jnp.arange(5), **dims, experts_per_token=k,
                   norm_topk_prob=norm),
            ref.block(layer, x, jnp.arange(5), **dims, experts_per_token=k,
                      norm_topk_prob=norm), rtol=1e-5, atol=1e-6)


# ------------------------------------------------ the routing metrics

def read(name, obs):
    return importlib.import_module(
        "chipbench.layer_metrics." + name).read(obs)


def test_routing_metrics_from_the_engine_counters():
    before = {"moe_assignments": 1000, "moe_experts_hit": 400,
              "moe_expert_slots": 512, "moe_load_max": 50}
    # 10 executions of 8 layers of 64 experts, 128 rows each
    after = {"moe_assignments": 1000 + 10 * 8 * 128,
             "moe_experts_hit": 400 + 4438,
             "moe_expert_slots": 512 + 10 * 8 * 64,
             "moe_load_max": 50 + 10 * 8 * 6}
    obs = {"traced": {"engine": after, "engine_before": before},
           "config": {"num_experts": 64}}
    assert read("moe_experts_hit_pct", obs) == pytest.approx(
        100 * 4438 / 5120)
    assert read("moe_load_max_over_mean", obs) == pytest.approx(3.0)


@pytest.mark.parametrize("name", ["moe_experts_hit_pct",
                                  "moe_load_max_over_mean"])
def test_routing_metrics_are_none_without_the_counters(name):
    dense = {"steps": 5, "d2h_syncs": 5}
    for obs in ({}, {"traced": None},
                {"traced": {"engine": dense, "engine_before": dense},
                 "config": {"num_experts": 64}},
                {"traced": {"engine": {"moe_assignments": 0,
                                       "moe_experts_hit": 0,
                                       "moe_expert_slots": 0,
                                       "moe_load_max": 0},
                            "engine_before": {"moe_assignments": 0,
                                              "moe_experts_hit": 0,
                                              "moe_expert_slots": 0,
                                              "moe_load_max": 0}},
                 "config": {"num_experts": 64}}):
        assert read(name, obs) is None
