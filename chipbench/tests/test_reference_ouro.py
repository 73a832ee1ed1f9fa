"""``reference/ouro_decoder.py`` against a hand-written two-pass,
one-layer case in numpy (float64), and its exit rule."""

import numpy as np
import pytest

import jax.numpy as jnp

from chipbench.reference import ouro_decoder as ref

D, HEADS, HD, F, VOCAB, SEQ = 8, 2, 4, 12, 16, 5
THETA, EPS = 1000000.0, 1e-6


def weights(seed=0):
    rng = np.random.default_rng(seed)

    def m(*shape):
        return rng.normal(0, 0.5, shape)

    layer = {"attn_norm": 1 + m(D) * 0.2, "attn_out_norm": 1 + m(D) * 0.2,
             "mlp_norm": 1 + m(D) * 0.2, "mlp_out_norm": 1 + m(D) * 0.2,
             "wq": m(D, HEADS * HD), "wk": m(D, HEADS * HD),
             "wv": m(D, HEADS * HD), "wo": m(HEADS * HD, D),
             "w_gate": m(D, F), "w_up": m(D, F), "w_down": m(F, D)}
    closing = {"norm_f": 1 + m(D) * 0.2, "gate_w": m(D, 1),
               "gate_b": m(1)}
    return m(VOCAB, D), layer, closing, m(D, VOCAB)


def by_hand(embed, layer, closing, head, tokens, passes=2):
    """The equations of the file's docstring, a position at a time."""
    def norm(x, w):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + EPS) * w

    def rot(x, pos):                       # (heads, hd), half-split pairs
        inv = 1.0 / THETA ** (np.arange(0, HD, 2) / HD)
        cos, sin = np.cos(pos * inv), np.sin(pos * inv)
        a, b = x[:, :HD // 2], x[:, HD // 2:]
        return np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    def block(x):
        h = norm(x, layer["attn_norm"])
        q = [rot((h[t] @ layer["wq"]).reshape(HEADS, HD), t)
             for t in range(SEQ)]
        k = [rot((h[t] @ layer["wk"]).reshape(HEADS, HD), t)
             for t in range(SEQ)]
        v = [(h[t] @ layer["wv"]).reshape(HEADS, HD) for t in range(SEQ)]
        out = np.zeros((SEQ, HEADS, HD))
        for t in range(SEQ):
            for a in range(HEADS):
                s = np.array([q[t][a] @ k[j][a] for j in range(t + 1)])
                p = np.exp(s / np.sqrt(HD) - (s / np.sqrt(HD)).max())
                p /= p.sum()
                out[t, a] = sum(p[j] * v[j][a] for j in range(t + 1))
        x = x + norm(out.reshape(SEQ, -1) @ layer["wo"],
                     layer["attn_out_norm"])
        h = norm(x, layer["mlp_norm"])
        g = h @ layer["w_gate"]
        return x + norm((g / (1 + np.exp(-g)) * (h @ layer["w_up"]))
                        @ layer["w_down"], layer["mlp_out_norm"])

    x, states, gates = embed[tokens], [], []
    for _ in range(passes):
        x = norm(block(x), closing["norm_f"])
        states.append(x)
        gates.append(1 / (1 + np.exp(-(x @ closing["gate_w"][:, 0]
                                       + closing["gate_b"][0]))))
    return states, gates, states[-1] @ head


def test_two_passes_of_one_layer_are_the_equations_by_hand():
    embed, layer, closing, head = weights()
    tokens = np.array([3, 1, 4, 1, 5])
    states, gates, logits = by_hand(embed, layer, closing, head, tokens)
    dims = dict(n_heads=HEADS, n_kv_heads=HEADS, rope_theta=THETA,
                norm_eps=EPS)
    got_states, got_gates = ref.passes(
        embed, [layer], closing, jnp.asarray(tokens), total_ut_steps=2,
        **dims)
    for got, want in zip(got_states + got_gates, states + gates):
        assert np.abs(np.asarray(got) - want).max() < 2e-5
    got = ref.forward(embed, [layer], closing, head, jnp.asarray(tokens),
                      total_ut_steps=2, early_exit_threshold=1.0, **dims)
    assert np.abs(np.asarray(got) - logits).max() < 1e-4
    # the norm between the passes is part of the model: the second pass
    # starts from the first's NORMED state
    other, _ = ref.passes(embed, [layer], closing, jnp.asarray(tokens),
                          total_ut_steps=2, norm_between=False, **dims)
    assert np.abs(np.asarray(other[-1]) - states[-1]).max() > 1e-2
    # one pass is the first pass
    one, _ = ref.passes(embed, [layer], closing, jnp.asarray(tokens),
                        total_ut_steps=1, **dims)
    assert np.abs(np.asarray(one[0]) - states[0]).max() < 2e-5


def test_the_exit_rule_picks_the_first_pass_whose_sum_reaches_the_threshold():
    gates = [jnp.asarray([0.5, 0.1, 0.9, 0.0]),
             jnp.asarray([0.5, 0.2, 0.5, 0.0]),
             jnp.asarray([0.9, 0.3, 0.1, 0.0])]
    p = np.asarray(ref.exit_distribution(gates))
    assert p.shape == (3, 4) and np.allclose(p.sum(0), 1.0)
    assert np.allclose(p[:, 0], [0.5, 0.25, 0.25])
    assert np.allclose(p[:, 1], [0.1, 0.18, 0.72])
    assert np.allclose(p[:, 3], [0.0, 0.0, 1.0])
    # the published threshold: only the last running sum reaches 1
    assert ref.exit_pass(gates, 1).tolist() == [2, 2, 2, 2]
    assert ref.exit_pass(gates, 0.7).tolist() == [1, 2, 0, 2]
    assert ref.exit_pass(gates, 0.05).tolist() == [0, 0, 0, 2]


def test_forward_gives_each_token_the_logits_of_the_pass_it_leaves_behind():
    embed, layer, closing, head = weights(1)
    tokens = jnp.asarray([2, 7, 7, 9, 0])
    dims = dict(n_heads=HEADS, n_kv_heads=HEADS, rope_theta=THETA,
                norm_eps=EPS, total_ut_steps=3)
    states, gates = ref.passes(embed, [layer], closing, tokens, **dims)
    low = ref.forward(embed, [layer], closing, head, tokens,
                      early_exit_threshold=0.5, **dims)
    chosen = np.asarray(ref.exit_pass(gates, 0.5))
    assert len(set(chosen.tolist())) > 1       # the case decides something
    for t, u in enumerate(chosen):
        want = np.asarray(ref.head_of(head, states[u]))[t]
        assert np.abs(np.asarray(low)[t] - want).max() < 1e-5
    last = ref.forward(embed, [layer], closing, head, tokens,
                       early_exit_threshold=1.0, **dims)
    assert np.abs(np.asarray(last) - np.asarray(
        ref.head_of(head, states[-1]))).max() < 1e-6


def test_dims_of_reads_the_published_keys():
    assert ref.dims_of({"num_attention_heads": 16, "num_key_value_heads": 16,
                        "rope_theta": 1000000, "rms_norm_eps": 1e-06,
                        "total_ut_steps": 4, "early_exit_threshold": 1}) == {
        "n_heads": 16, "n_kv_heads": 16, "rope_theta": 1e6,
        "norm_eps": 1e-6, "total_ut_steps": 4, "early_exit_threshold": 1.0}
    with pytest.raises(KeyError):
        ref.dims_of({"num_attention_heads": 16})
