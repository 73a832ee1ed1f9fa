"""The LFM2 reference against a second evaluation of its equations,
written as loops in numpy float64 — one token, one channel's taps, one
head, one seen position, one chosen expert at a time — at a toy size:
one conv layer with a dense feed-forward, one conv layer with a routed
one, one softmax layer; that a conv layer's output at a position
depends on the two tokens before it and on none later or earlier; that
it imports nothing of the program."""

import ast

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import lfm2_decoder as ref

D, H, KVH, F, FD, E, K, TAPS = 24, 4, 2, 10, 14, 8, 3, 3
DIMS = dict(n_heads=H, n_kv_heads=KVH, rope_theta=100.0, norm_eps=1e-5,
            experts_per_token=K, routed_scaling_factor=1.0)


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

    routed = {"mlp_norm": 1 + w(D), "router": w(D, E, scale=1.0),
              "router_bias": w(E, scale=0.3),
              "w_gate": w(E, D, F), "w_up": w(E, D, F), "w_down": w(E, F, D)}
    dense = {"mlp_norm": 1 + w(D), "w_gate": w(D, FD), "w_up": w(D, FD),
             "w_down": w(FD, D)}
    conv = {"attn_norm": 1 + w(D), "in_proj": w(D, 3 * D),
            "conv_w": w(TAPS, D, scale=0.5), "out_proj": w(D, D)}
    hd = D // H
    softmax = {"attn_norm": 1 + w(D), "wq": w(D, H * hd),
               "wk": w(D, KVH * hd), "wv": w(D, KVH * hd),
               "wo": w(H * hd, D), "q_norm": 1 + w(hd), "k_norm": 1 + w(hd)}
    return {**conv, **dense}, {**conv, **routed}, {**softmax, **routed}


def _norm(x, w, eps=1e-5):
    return x / np.sqrt(np.mean(x * x) + eps) * w


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _conv_mix(layer, hs):
    """hs (seq, D) normed inputs -> (seq, D), one token after the other:
    the gated inputs of the two tokens before it are looked up, zeros
    before the sequence."""
    w_in, taps, w_out = (np.asarray(layer[n], np.float64)
                         for n in ("in_proj", "conv_w", "out_proj"))
    gated, out = [], []
    for t, h in enumerate(hs):
        bcu = h @ w_in
        b, c, u = bcu[:D], bcu[D:2 * D], bcu[2 * D:]
        gated.append(b * u)
        z = np.zeros(D)
        for ch in range(D):
            for j in range(TAPS):
                at = t - (TAPS - 1) + j
                if at >= 0:
                    z[ch] += taps[j, ch] * gated[at][ch]
        out.append((c * z) @ w_out)
    return np.stack(out)


def _rope(x, t, theta=100.0):
    half = x.shape[-1] // 2
    out = x.copy()
    for i in range(half):
        angle = t * theta ** (-2.0 * i / x.shape[-1])
        a, b = x[i], x[half + i]
        out[i] = a * np.cos(angle) - b * np.sin(angle)
        out[half + i] = b * np.cos(angle) + a * np.sin(angle)
    return out


def _softmax_mix(layer, hs):
    wq, wk, wv, wo, qn, kn = (np.asarray(layer[n], np.float64) for n in (
        "wq", "wk", "wv", "wo", "q_norm", "k_norm"))
    hd, group = D // H, H // KVH
    q = [[_rope(_norm((h @ wq)[i * hd:(i + 1) * hd], qn), t)
          for i in range(H)] for t, h in enumerate(hs)]
    k = [[_rope(_norm((h @ wk)[i * hd:(i + 1) * hd], kn), t)
          for i in range(KVH)] for t, h in enumerate(hs)]
    v = [[(h @ wv)[i * hd:(i + 1) * hd] for i in range(KVH)] for h in hs]
    out = []
    for t in range(len(hs)):
        heads = []
        for i in range(H):
            scores = np.array([q[t][i] @ k[s][i // group] / np.sqrt(hd)
                               for s in range(t + 1)])
            p = np.exp(scores - scores.max())
            p /= p.sum()
            heads.append(sum(p[s] * v[s][i // group] for s in range(t + 1)))
        out.append(np.concatenate(heads) @ wo)
    return np.stack(out)


def _ffn(layer, h):
    g = {n: np.asarray(layer[n], np.float64)
         for n in ("w_gate", "w_up", "w_down")}
    if "router" not in layer:
        return (_silu(h @ g["w_gate"]) * (h @ g["w_up"])) @ g["w_down"]
    scores = 1.0 / (1.0 + np.exp(-(h @ np.asarray(layer["router"],
                                                  np.float64))))
    picked = np.argsort(-(scores + np.asarray(layer["router_bias"],
                                              np.float64)))[:K]
    total = sum(scores[e] for e in picked) + 1e-6
    return sum(scores[e] / total * (
        (_silu(h @ g["w_gate"][e]) * (h @ g["w_up"][e])) @ g["w_down"][e])
        for e in picked)


def _block(layer, x):
    x = np.asarray(x, np.float64)
    hs = np.stack([_norm(row, np.asarray(layer["attn_norm"], np.float64))
                   for row in x])
    x = x + (_conv_mix(layer, hs) if "conv_w" in layer
             else _softmax_mix(layer, hs))
    return np.stack([row + _ffn(layer, _norm(row, np.asarray(
        layer["mlp_norm"], np.float64))) for row in x])


@pytest.mark.parametrize("which", [0, 1, 2],
                         ids=["conv-dense", "conv-routed", "softmax-routed"])
def test_a_layer_is_its_equations_in_loops(layers, which):
    x = np.random.default_rng(1).normal(size=(11, D))
    got = ref.block(layers[which], jnp.asarray(x, jnp.float32),
                    jnp.arange(11), **DIMS)
    np.testing.assert_allclose(got, _block(layers[which], x), rtol=2e-4,
                               atol=2e-5)


def test_the_bias_picks_and_does_not_weigh(layers):
    """A bias that lifts the weakest expert of a token among the picked
    changes WHO is picked; the gates stay the picked experts' own
    scores over their sum."""
    h = jnp.asarray(np.random.default_rng(2).normal(size=(1, D)),
                    jnp.float32)
    router = layers[1]["router"]
    plain = np.asarray(ref.gate_map(h, router, jnp.zeros(E), K, 1.0))[0]
    weakest = int(np.argmin(np.where(plain > 0, np.inf, 0) + np.asarray(
        jnp.asarray(h @ router)[0])))
    lifted = np.asarray(ref.gate_map(
        h, router, jnp.zeros(E).at[weakest].set(10.0), K, 1.0))[0]
    assert plain[weakest] == 0 and lifted[weakest] > 0
    assert (lifted > 0).sum() == (plain > 0).sum() == K
    scores = 1 / (1 + np.exp(-np.asarray(h @ router)[0]))
    kept = lifted > 0
    np.testing.assert_allclose(lifted[kept], scores[kept] / (
        scores[kept].sum() + 1e-6), rtol=1e-5)


def test_a_conv_layers_output_sees_two_tokens_back_and_no_further(layers):
    hs = np.random.default_rng(3).normal(size=(9, D))
    base = np.asarray(ref.conv_mix(layers[0], jnp.asarray(hs, jnp.float32)))
    moved = hs.copy()
    moved[4] += 1.0
    other = np.asarray(ref.conv_mix(layers[0],
                                    jnp.asarray(moved, jnp.float32)))
    changed = np.abs(other - base).max(axis=-1) > 1e-6
    assert changed.tolist() == [False] * 4 + [True] * 3 + [False] * 2
