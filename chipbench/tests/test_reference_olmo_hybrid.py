"""The Olmo Hybrid reference against a third evaluation of its
equations, written as loops in numpy float64 — one token, one head, one
seen position at a time, the state a 3 x 5 matrix updated by the
published recurrence (decay, erase along the key, write) — at a toy
size; that a linear layer's output at a position depends on every
earlier token and on none later; that the norms sit on the sub-layers'
OUTPUTS; that it imports nothing of the program."""

import ast

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import olmo_hybrid_decoder as ref

D, H, F = 24, 4, 10
LH, DK, DV, TAPS = 3, 3, 5, 4
EPS = 1e-6
DIMS = dict(n_heads=H, n_kv_heads=H, rope_theta=0.0, norm_eps=EPS)


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

    ffn = {"attn_norm": 1 + w(D), "mlp_norm": 1 + w(D), "w_gate": w(D, F),
           "w_up": w(D, F), "w_down": w(F, D)}
    linear = {"wq": w(D, LH * DK), "wk": w(D, LH * DK), "wv": w(D, LH * DV),
              "conv_w": w(TAPS, LH * (2 * DK + DV), scale=0.5),
              "w_a": w(D, LH), "a_log": w(LH), "dt_bias": w(LH),
              "w_beta": w(D, LH, scale=1.0), "w_g": w(D, LH * DV),
              "o_norm": 1 + w(DV), "wo": w(LH * DV, D), **ffn}
    hd = D // H
    softmax = {"wq": w(D, H * hd), "wk": w(D, H * hd), "wv": w(D, H * hd),
               "q_norm": 1 + w(H * hd), "k_norm": 1 + w(H * hd),
               "wo": w(H * hd, D), **ffn}
    return linear, softmax


def _norm(x, w):
    return x / np.sqrt(np.mean(x * x) + EPS) * w


def _silu(x):
    return x / (1 + np.exp(-x))


def _softplus(x):
    return np.log1p(np.exp(x))


def loops(layer, x, write_scale=2.0):
    """One block, position by position, in float64."""
    layer = {k: np.asarray(v, np.float64) for k, v in layer.items()}
    x = np.asarray(x, np.float64)
    seq = x.shape[0]
    mix = np.zeros((seq, D))
    if "a_log" in layer:
        u = np.concatenate([x @ layer[n] for n in ("wq", "wk", "wv")], -1)
        conv = np.zeros_like(u)
        for t in range(seq):
            for j in range(TAPS):
                if t - (TAPS - 1) + j >= 0:
                    conv[t] += layer["conv_w"][j] * u[t - (TAPS - 1) + j]
        conv = _silu(conv)
        states = [np.zeros((DK, DV)) for _ in range(LH)]
        for t in range(seq):
            gate = _silu(x[t] @ layer["w_g"])
            out = np.zeros(LH * DV)
            for h in range(LH):
                q = conv[t, h * DK:(h + 1) * DK]
                k = conv[t, LH * DK + h * DK:LH * DK + (h + 1) * DK]
                v = conv[t, 2 * LH * DK + h * DV:2 * LH * DK + (h + 1) * DV]
                q = q / np.sqrt(q @ q + 1e-6) * DK ** -0.5
                k = k / np.sqrt(k @ k + 1e-6)
                g = -np.exp(layer["a_log"][h]) * _softplus(
                    x[t] @ layer["w_a"][:, h] + layer["dt_bias"][h])
                beta = write_scale / (1 + np.exp(-(x[t] @ layer["w_beta"][:, h])))
                s = np.exp(g) * states[h]                       # decay
                s = s - beta * np.outer(k, k @ s)               # erase
                s = s + beta * np.outer(k, v)                   # write
                states[h] = s
                out[h * DV:(h + 1) * DV] = _norm(q @ s, layer["o_norm"])
            mix[t] = (out * gate) @ layer["wo"]
    else:
        hd = D // H
        q = np.stack([_norm(row, layer["q_norm"]) for row in x @ layer["wq"]])
        k = np.stack([_norm(row, layer["k_norm"]) for row in x @ layer["wk"]])
        v = x @ layer["wv"]
        for t in range(seq):
            out = np.zeros(H * hd)
            for h in range(H):
                cols = slice(h * hd, (h + 1) * hd)
                scores = np.array([q[t, cols] @ k[s, cols] / np.sqrt(hd)
                                   for s in range(t + 1)])
                p = np.exp(scores - scores.max())
                p = p / p.sum()
                out[cols] = sum(p[s] * v[s, cols] for s in range(t + 1))
            mix[t] = out @ layer["wo"]
    x = x + np.stack([_norm(row, layer["attn_norm"]) for row in mix])
    ffn = (_silu(x @ layer["w_gate"]) * (x @ layer["w_up"])) @ layer["w_down"]
    return x + np.stack([_norm(row, layer["mlp_norm"]) for row in ffn])


@pytest.mark.parametrize("kind", [0, 1], ids=["linear", "full"])
def test_a_block_is_the_loops(layers, kind):
    x = np.random.default_rng(kind + 1).normal(size=(11, D))
    got = ref.block(layers[kind], jnp.asarray(x, jnp.float32),
                    jnp.arange(11), **DIMS)
    want = loops(layers[kind], x)
    assert np.abs(np.asarray(got) - want).max() < 2e-4 * np.abs(want).max()


def test_the_write_strength_follows_its_key(layers):
    x = np.random.default_rng(5).normal(size=(9, D))
    got = ref.block(layers[0], jnp.asarray(x, jnp.float32), jnp.arange(9),
                    **DIMS, write_scale=1.0)
    want = loops(layers[0], x, write_scale=1.0)
    assert np.abs(np.asarray(got) - want).max() < 2e-4 * np.abs(want).max()
    assert np.abs(want - loops(layers[0], x)).max() > 1e-2


def test_a_linear_layer_reads_the_past_and_not_the_future(layers):
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(12, D)), jnp.float32)
    base = ref.block(layers[0], x, jnp.arange(12), **DIMS)
    moved = ref.block(layers[0], x.at[4].add(1.0), jnp.arange(12), **DIMS)
    changed = np.abs(np.asarray(moved - base)).max(-1)
    assert (changed[:4] == 0).all() and (changed[4:] > 1e-6).all()


def test_forward_takes_each_layer_by_its_kind_and_norms_the_outputs(layers):
    rng = np.random.default_rng(4)
    embed = jnp.asarray(rng.normal(size=(50, D)), jnp.float32)
    head = jnp.asarray(rng.normal(size=(D, 50)) * 0.3, jnp.float32)
    norm_f = jnp.ones((D,), jnp.float32)
    tokens = jnp.asarray(rng.integers(0, 50, 9))
    order = [layers[0], layers[0], layers[0], layers[1]]
    got = ref.forward(embed, order, norm_f, head, tokens, **DIMS)
    x = np.asarray(embed, np.float64)[np.asarray(tokens)]
    for layer in order:
        x = loops(layer, x)
    want = np.stack([_norm(row, 1.0) for row in x]) @ np.asarray(
        head, np.float64)
    assert got.shape == (9, 50)
    assert np.abs(np.asarray(got) - want).max() < 5e-4 * np.abs(want).max()
    # the head in blocks of columns is the head
    whole = ref.rms_norm(jnp.asarray(x, jnp.float32), norm_f, EPS) @ head
    assert np.abs(np.asarray(got - whole)).max() < 1e-4
    # a sub-layer whose output norm's weight is zero adds nothing
    silent = {**layers[1], "attn_norm": jnp.zeros(D), "mlp_norm": jnp.zeros(D)}
    x0 = jnp.asarray(rng.normal(size=(5, D)), jnp.float32)
    assert (ref.block(silent, x0, jnp.arange(5), **DIMS) == x0).all()


def test_dims_of_reads_the_published_keys():
    import json
    import os

    with open(os.path.join(os.path.dirname(__file__), "..", "configs",
                           "olmo-hybrid-7b.json")) as f:
        spec = json.load(f)
    assert ref.dims_of(spec) == dict(
        n_heads=30, n_kv_heads=30, rope_theta=0.0, norm_eps=1e-6,
        write_scale=2.0)
    assert ref.dims_of({**spec, "linear_allow_neg_eigval": False})[
        "write_scale"] == 1.0
    assert ref.HEAD_BLOCK * 8 == spec["vocab_size"]
