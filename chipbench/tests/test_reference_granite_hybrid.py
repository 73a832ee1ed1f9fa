"""The Granite 4.0-H reference against a third evaluation of its
equations, written as loops in numpy float64 — one token, one head, one
seen position, one chosen expert at a time, the state a matrix updated
by the published recurrence (decay, write, read) — at a toy size; that
a state-space layer's output at a position depends on every earlier
token and on none later; that it imports nothing of the program."""

import ast

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import granite_hybrid_decoder as ref

D, H, KVH, F, E, HELD = 24, 4, 2, 10, 8, 4
SH, P, N, TAPS = 3, 5, 6, 4
DIMS = dict(n_heads=H, n_kv_heads=KVH, rope_theta=100.0, norm_eps=1e-5,
            experts_per_token=3, first_expert=4, residual_multiplier=0.22,
            attention_multiplier=1 / 8)


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

    inner, channels = SH * P, SH * P + 2 * N
    ffn = {"mlp_norm": 1 + w(D), "router": w(D, E, scale=1.0),
           "w_gate": w(HELD, D, F), "w_up": w(HELD, D, F),
           "w_down": w(HELD, F, D), "shared_gate": w(D, 2 * F),
           "shared_up": w(D, 2 * F), "shared_down": w(2 * F, D)}
    mamba = {"attn_norm": 1 + w(D), "in_proj": w(D, inner + channels + SH),
             "conv_w": w(TAPS, channels, scale=0.5), "conv_b": w(channels),
             "dt_bias": w(SH), "a_log": w(SH), "d_skip": 1 + w(SH),
             "ssm_norm": 1 + w(inner), "out_proj": w(inner, D), **ffn}
    hd = D // H
    softmax = {"attn_norm": 1 + w(D), "wq": w(D, H * hd),
               "wk": w(D, KVH * hd), "wv": w(D, KVH * hd),
               "wo": w(H * hd, D), **ffn}
    return mamba, softmax


def _norm(x, w):
    return x / np.sqrt(np.mean(x * x) + 1e-5) * w


def _silu(x):
    return x / (1 + np.exp(-x))


def _swiglu(h, gate, up, down):
    return (_silu(h @ gate) * (h @ up)) @ down


def _ffn(layer, h):
    logits = h @ layer["router"]
    picked = np.argsort(-logits)[:DIMS["experts_per_token"]]
    gates = np.exp(logits[picked] - logits[picked].max())
    gates /= gates.sum()
    out = _swiglu(h, layer["shared_gate"], layer["shared_up"],
                  layer["shared_down"])
    for e, g in zip(picked, gates):
        at = e - DIMS["first_expert"]
        if 0 <= at < HELD:                   # an absent expert: no one's
            out = out + g * _swiglu(h, layer["w_gate"][at],
                                    layer["w_up"][at], layer["w_down"][at])
    return out


def loops(layer, x):
    """One layer, one token at a time, float64."""
    layer = {k: np.asarray(v, np.float64) for k, v in layer.items()}
    seq, r = x.shape[0], DIMS["residual_multiplier"]
    hs = np.stack([_norm(row, layer["attn_norm"]) for row in x])
    mix = np.zeros_like(x)
    if "a_log" in layer:
        inner, channels = SH * P, SH * P + 2 * N
        zxd = hs @ layer["in_proj"]
        state = np.zeros((SH, P, N))
        for t in range(seq):
            u = layer["conv_b"].copy()
            for j in range(TAPS):
                if t - (TAPS - 1) + j >= 0:
                    u += layer["conv_w"][j] * zxd[
                        t - (TAPS - 1) + j, inner:inner + channels]
            u = _silu(u)
            b, c = u[inner:inner + N], u[inner + N:]
            y = np.zeros(inner)
            for h in range(SH):
                xh = u[h * P:(h + 1) * P]
                dt = np.log1p(np.exp(zxd[t, inner + channels + h]
                                     + layer["dt_bias"][h]))
                state[h] = np.exp(-dt * np.exp(layer["a_log"][h])) \
                    * state[h] + dt * np.outer(xh, b)
                y[h * P:(h + 1) * P] = state[h] @ c + layer["d_skip"][h] * xh
            mix[t] = _norm(y * _silu(zxd[t, :inner]), layer["ssm_norm"]) \
                @ layer["out_proj"]
    else:
        hd = D // H
        q, k, v = (hs @ layer[n] for n in ("wq", "wk", "wv"))
        for t in range(seq):
            out = np.zeros(H * hd)
            for h in range(H):
                g = h // (H // KVH)
                scores = np.array([
                    q[t, h * hd:(h + 1) * hd] @ k[s, g * hd:(g + 1) * hd]
                    for s in range(t + 1)]) * DIMS["attention_multiplier"]
                p = np.exp(scores - scores.max())
                p /= p.sum()
                out[h * hd:(h + 1) * hd] = sum(
                    p[s] * v[s, g * hd:(g + 1) * hd] for s in range(t + 1))
            mix[t] = out @ layer["wo"]
    x = x + r * mix
    return x + r * np.stack([
        _ffn(layer, _norm(row, layer["mlp_norm"])) for row in x])


@pytest.mark.parametrize("kind", [0, 1], ids=["mamba", "attention"])
def test_a_block_is_the_loops(layers, kind):
    x = np.random.default_rng(3).normal(size=(13, D))
    got = ref.block(layers[kind], jnp.asarray(x, jnp.float32),
                    jnp.arange(13), **DIMS)
    np.testing.assert_allclose(got, loops(layers[kind], x), rtol=2e-4,
                               atol=2e-5)


def test_a_state_space_layer_reads_the_past_and_not_the_future(layers):
    x = np.random.default_rng(4).normal(size=(12, D)).astype(np.float32)
    base = np.asarray(ref.block(layers[0], jnp.asarray(x), None, **DIMS))
    later = x.copy()
    later[7] += 1.0
    moved = np.asarray(ref.block(layers[0], jnp.asarray(later), None,
                                 **DIMS))
    np.testing.assert_array_equal(moved[:7], base[:7])
    # through the state, not the four taps alone: the last token too
    assert np.abs(moved[11] - base[11]).max() > 1e-4


def test_forward_takes_each_layer_by_its_kind_and_the_multipliers(layers):
    rng = np.random.default_rng(5)
    embed = jnp.asarray(rng.normal(size=(50, D)) * 0.1, jnp.float32)
    tokens = jnp.asarray(rng.integers(0, 50, 11))
    order = [layers[0], layers[1], layers[0]]
    got = ref.forward(embed, (order.__getitem__, 3), jnp.ones(D), embed.T,
                      tokens, embedding_multiplier=12.0, logits_scaling=16.0,
                      **DIMS)
    x = 12.0 * np.asarray(embed, np.float64)[np.asarray(tokens)]
    for layer in order:
        x = loops(layer, x)
    want = x / np.sqrt(np.mean(x * x, -1, keepdims=True) + 1e-5) \
        @ np.asarray(embed, np.float64).T / 16.0
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-4)
