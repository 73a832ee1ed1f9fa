"""The Xing4.0 reference against a third evaluation of what is its own —
the residual streams' maps, the Sinkhorn passes, the read and the mix,
the bias-corrected gate map — written as loops in numpy float64, one
token, one stream, one entry at a time, at a tiny size (its latent
attention and experts are ``axk1_decoder``'s, held to loops of their own
in ``test_reference_axk1.py``); that it imports nothing of the program;
and that its ``forward`` opens and joins the streams as its docstring
says."""

import ast
import json
import math
import os

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import axk1_decoder as axk1
from chipbench.reference import xing4_decoder as ref

HERE = os.path.dirname(os.path.abspath(__file__))
N, D, H, NOPE, ROPE, V, RQ, RKV, F, E, FS = 4, 16, 2, 8, 4, 6, 12, 10, 12, \
    6, 12
WIDTH = 2 * N + N * N
PASSES = 20
DIMS = dict(n_heads=H, n_kv_heads=H, rope_theta=100.0, norm_eps=1e-6,
            yarn_factor=4.0, yarn_original=32.0, yarn_beta_fast=32.0,
            yarn_beta_slow=1.0, yarn_mscale=1.0, yarn_mscale_all_dim=1.0,
            experts_per_token=2, routed_scaling_factor=2.0, hc_eps=1e-6,
            clamp_min=-1.5, clamp_max=1.5)


def spec():
    with open(os.path.join(HERE, "..", "configs",
                           "xing4.0-29b-a4b.json")) as f:
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
        or n == "chipbench.reference" or n.startswith("chipbench.reference.")
        for n in names), names


@pytest.fixture(scope="module")
def layers():
    rng = np.random.default_rng(0)

    def w(*shape, scale=0.3):
        return jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)

    def maps():
        return {f"hc_{sub}_{name}": leaf for sub in ("attn", "mlp")
                for name, leaf in (("phi", w(N * D, WIDTH, scale=0.2)),
                                   ("b", w(WIDTH, scale=1.0)),
                                   ("alpha", 1 + w(3)))}

    attn = lambda: {                                        # noqa: E731
        "attn_norm": 1 + w(D), "w_qa": w(D, RQ), "q_a_norm": 1 + w(RQ),
        "w_qb": w(RQ, H * (NOPE + ROPE)), "w_kva": w(D, RKV + ROPE),
        "kv_a_norm": 1 + w(RKV), "w_kvb": w(RKV, H * (NOPE + V)),
        "wo": w(H * V, D), "mlp_norm": 1 + w(D), **maps()}
    dense = {**attn(), "w_gate": w(D, F), "w_up": w(D, F), "w_down": w(F, D)}
    routed = {**attn(), "router": w(D, E, scale=1.0),
              "router_bias": w(E, scale=0.3),
              "w_gate": w(E, D, F), "w_up": w(E, D, F), "w_down": w(E, F, D),
              "shared_gate": w(D, FS), "shared_up": w(D, FS),
              "shared_down": w(FS, D)}
    return [dense, routed]


def maps_by_loops(x, phi, b, alpha, passes=PASSES):
    """One token's three maps, entry by entry."""
    flat = [x[i][c] for i in range(N) for c in range(D)]
    rms = math.sqrt(sum(v * v for v in flat) / len(flat) + DIMS["norm_eps"])
    pqr = [sum(flat[k] / rms * phi[k][j] for k in range(N * D))
           for j in range(WIDTH)]
    sig = lambda a: 1 / (1 + math.exp(-a))                  # noqa: E731
    h_pre = [sig(alpha[0] * pqr[i] + b[i]) for i in range(N)]
    h_post = [2 * sig(alpha[1] * pqr[N + i] + b[N + i]) for i in range(N)]
    m = [[math.exp(min(max(alpha[2] * pqr[2 * N + i * N + j]
                           + b[2 * N + i * N + j], DIMS["clamp_min"]),
                       DIMS["clamp_max"])) for j in range(N)]
         for i in range(N)]
    for _ in range(passes):
        cols = [sum(m[i][j] for i in range(N)) + DIMS["hc_eps"]
                for j in range(N)]
        m = [[m[i][j] / cols[j] for j in range(N)] for i in range(N)]
        rows = [sum(m[i]) + DIMS["hc_eps"] for i in range(N)]
        m = [[m[i][j] / rows[i] for j in range(N)] for i in range(N)]
    return h_pre, h_post, m


def test_the_maps_equal_loops_and_the_clamp_bites(layers):
    rng = np.random.default_rng(1)
    x = rng.normal(size=(5, N, D)) * 2.0
    layer = {k: np.asarray(v, np.float64) for k, v in layers[0].items()}
    got = ref.hc_maps(jnp.asarray(x, jnp.float32), layers[0]["hc_attn_phi"],
                      layers[0]["hc_attn_b"], layers[0]["hc_attn_alpha"],
                      PASSES, DIMS["clamp_min"], DIMS["clamp_max"],
                      DIMS["hc_eps"], DIMS["norm_eps"])
    clamped = 0
    for t in range(5):
        want = maps_by_loops(x[t], layer["hc_attn_phi"], layer["hc_attn_b"],
                             layer["hc_attn_alpha"])
        for ours, theirs in zip(got, want):
            np.testing.assert_allclose(ours[t], theirs, rtol=2e-5, atol=2e-6)
        raw = maps_by_loops(x[t], layer["hc_attn_phi"], layer["hc_attn_b"],
                            layer["hc_attn_alpha"], passes=0)[2]
        clamped += sum(v in (math.exp(-1.5), math.exp(1.5))
                       for row in raw for v in row)
    assert clamped > 0                       # the clamp is on the path
    h_res = np.asarray(got[2])
    np.testing.assert_allclose(h_res.sum(-1), 1.0, atol=1e-5)
    np.testing.assert_allclose(h_res.sum(-2), 1.0, atol=1e-3)
    assert (np.asarray(got[0]) > 0).all() and (np.asarray(got[0]) < 1).all()
    assert (np.asarray(got[1]) > 0).all() and (np.asarray(got[1]) < 2).all()


def test_a_pass_is_columns_then_rows():
    m = jnp.asarray([[1.0, 3.0], [2.0, 2.0]])
    once = np.asarray(ref.sinkhorn(m, 1, 0.0))
    # columns first: [[1/3, 3/5], [2/3, 2/5]], then every row over its sum
    want = np.asarray([[1 / 3, 3 / 5], [2 / 3, 2 / 5]])
    want = want / want.sum(-1, keepdims=True)
    np.testing.assert_allclose(once, want, rtol=1e-6)
    assert not np.allclose(once.sum(0), 1.0)         # rows were last
    np.testing.assert_allclose(
        np.asarray(ref.sinkhorn(m, 40, 0.0)).sum(0), 1.0, atol=1e-6)
    # hc_eps is in every sum divided by
    np.testing.assert_allclose(
        ref.sinkhorn(jnp.asarray([[1.0]]), 1, 1.0), [[1 / 3]], rtol=1e-6)


def test_the_bias_picks_and_the_scores_weigh(layers):
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.normal(size=(9, D)), jnp.float32)
    router, bias = layers[1]["router"], layers[1]["router_bias"]
    gates = np.asarray(ref.gate_map(h, router, bias, 2, 2.0))
    scores = 1 / (1 + np.exp(-np.asarray(h, np.float64)
                             @ np.asarray(router, np.float64)))
    moved = 0
    for t in range(9):
        by = scores[t] + np.asarray(bias, np.float64)
        picked = sorted(range(E), key=lambda e: -by[e])[:2]
        moved += set(picked) != set(np.argsort(-scores[t])[:2])
        total = sum(scores[t][e] for e in picked)
        for e in range(E):
            want = scores[t][e] / total * 2.0 if e in picked else 0.0
            assert gates[t][e] == pytest.approx(want, rel=1e-5, abs=1e-7)
    assert moved > 0
    # without a bias it is A.X-K1's gate map
    np.testing.assert_allclose(
        ref.gate_map(h, router, bias * 0, 2, 2.0),
        axk1.gate_map(h, router, 2, 2.0), rtol=1e-6)


def test_a_block_reads_mixes_and_writes_as_written(layers):
    """Layer by layer: ``block`` against the maps by loops around the
    sub-layers themselves (``axk1_decoder``'s attention and experts on
    the mix the sub-layer reads)."""
    rng = np.random.default_rng(3)
    seq = 6
    x = jnp.asarray(rng.normal(size=(seq, N, D)), jnp.float32)
    positions = jnp.arange(seq)
    passes = jnp.zeros((PASSES,))
    for layer in layers:
        got = np.asarray(ref.block({**layer, "sinkhorn_passes": passes}, x,
                                   positions, **DIMS))
        state = np.asarray(x, np.float64)
        # the sub-layers alone, from A.X-K1's block on ONE stream with
        # the other sub-layer switched off (its output projection zero)
        for sub, off in (("attn", ("w_down", "shared_down")),
                         ("mlp", ("wo",))):
            lw = {k: np.asarray(v, np.float64) for k, v in layer.items()}
            maps = [maps_by_loops(state[t], lw[f"hc_{sub}_phi"],
                                  lw[f"hc_{sub}_b"], lw[f"hc_{sub}_alpha"])
                    for t in range(seq)]
            h = np.stack([sum(maps[t][0][i] * state[t][i] for i in range(N))
                          for t in range(seq)])
            alone = {**layer, **{k: layer[k] * 0 for k in off if k in layer}}
            plain = {k: v for k, v in alone.items()
                     if not k.startswith("hc_") and k != "router_bias"}
            if "router" in layer and sub == "mlp":
                # A.X-K1's block has no bias: fold the pick into a gate
                # map of this file's and use the experts' sum directly
                hn = ref.rms_norm(jnp.asarray(h, jnp.float32),
                                  layer["mlp_norm"], DIMS["norm_eps"])
                y = axk1.held_experts(layer, hn, ref.gate_map(
                    hn, layer["router"], layer["router_bias"], 2, 2.0), 0) \
                    + ref.swiglu(hn, layer["shared_gate"],
                                 layer["shared_up"], layer["shared_down"])
            else:
                y = axk1.block(plain, jnp.asarray(h, jnp.float32), positions,
                               **{k: v for k, v in DIMS.items() if k not in (
                                   "hc_eps", "clamp_min", "clamp_max")},
                               first_expert=0) - jnp.asarray(h, jnp.float32)
            y = np.asarray(y, np.float64)
            state = np.stack([[sum(maps[t][2][i][j] * state[t][j]
                                   for j in range(N)) + maps[t][1][i] * y[t]
                               for i in range(N)] for t in range(seq)])
        np.testing.assert_allclose(got, state, rtol=2e-4, atol=2e-5)


def test_forward_opens_equal_streams_and_joins_them_by_their_sum(layers):
    rng = np.random.default_rng(4)
    embed = jnp.asarray(rng.normal(size=(11, D)), jnp.float32)
    head = jnp.asarray(rng.normal(size=(D, 11)), jnp.float32)
    norm_f = jnp.ones((D,))
    tokens = jnp.asarray([3, 1, 4, 1, 5])
    seen = []

    def spy(layer, x, positions, **dims):
        seen.append((np.asarray(x), layer["sinkhorn_passes"].shape, dims))
        return x * 1.5

    logits = ref.forward(embed, [{}, {}], norm_f, head, tokens, block_fn=spy,
                         hc_mult=N, hc_sinkhorn_iters=7, norm_eps=1e-6)
    first = seen[0][0]
    assert first.shape == (5, N, D)
    for i in range(N):
        np.testing.assert_array_equal(first[:, i], np.asarray(embed)[
            np.asarray(tokens)])
    assert seen[0][1] == (7,) and "hc_mult" not in seen[0][2]
    joined = N * 1.5 * 1.5 * np.asarray(embed)[np.asarray(tokens)]
    want = ref.logits_of(norm_f, head, jnp.asarray(joined), 1e-6)
    np.testing.assert_allclose(logits, want, rtol=1e-5)


def test_dims_of_reads_the_published_keys():
    dims = ref.dims_of(spec())
    assert dims["hc_mult"] == 4 and dims["hc_sinkhorn_iters"] == 20
    assert dims["hc_eps"] == 1e-6
    assert (dims["clamp_min"], dims["clamp_max"]) == (-30.0, 30.0)
    assert dims["experts_per_token"] == 4
    assert dims["routed_scaling_factor"] == 2.0
    assert dims["yarn_factor"] == 64.0 and "first_expert" not in dims
