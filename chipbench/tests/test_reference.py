"""The plain reference against the program's model at a tiny size on the
CPU: forward logits, loss and gradients; and prefill through the
engine's cache + decode against the reference's full forward."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ant_ray_tpu.models import llama
from chipbench.models import dense_llama
from chipbench.reference import dense_decoder as ref

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def tiny():
    with open(os.path.join(HERE, "..", "rehearsal", "configs",
                           "tiny-dense.json")) as f:
        spec = json.load(f)
    config = dense_llama.build(spec, dtype="float32")
    params = llama.init_params(config, jax.random.PRNGKey(0))
    # norms that are not all ones, so that a swapped norm would show
    params["layers"]["ln_attn"] = params["layers"]["ln_attn"] * 1.3
    params["norm_f"] = params["norm_f"] * 0.7
    return spec, config, params


def as_reference(params):
    embed, layer, n, norm_f, head = dense_llama.reference_layers(params)
    return embed, [layer(i) for i in range(n)], norm_f, head


def test_forward_logits_equal(tiny):
    spec, config, params = tiny
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 256, 48))
    want = ref.forward(*as_reference(params), tokens, **ref.dims_of(spec))
    got = llama.forward(params, tokens[None], config,
                        attn_impl="reference")[0]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_loss_and_gradients_equal(tiny):
    spec, config, params = tiny
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 256, (2, 33)))

    def ref_loss(p):
        return ref.loss(*as_reference(p), tokens, **ref.dims_of(spec))

    def own_loss(p):
        return llama.loss_fn(p, {"tokens": tokens}, config,
                             attn_impl="reference", remat="none")

    want, want_g = jax.value_and_grad(ref_loss)(params)
    got, got_g = jax.value_and_grad(own_loss)(params)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-6)


def test_prefill_in_chunks_then_decode_equals_the_full_forward(tiny):
    spec, config, params = tiny
    tokens = np.random.default_rng(2).integers(0, 256, 100).astype(np.int32)
    prompt, chunk, slot = 90, 64, 2
    cache = llama.init_kv_cache(config, 4, 128)
    for start in range(0, prompt, chunk):
        part = tokens[start:min(start + chunk, prompt)]
        buf = np.zeros((chunk,), np.int32)
        buf[:len(part)] = part
        logits, cache = llama.prefill_chunk_into_cache(
            params, jnp.asarray(buf), cache, slot, start, len(part), config)
    got = [logits]
    active = np.zeros((4,), bool)
    active[slot] = True
    for j in range(prompt, 100):
        last = np.zeros((4,), np.int32)
        last[slot] = tokens[j]
        logits, cache = llama.decode_step(params, jnp.asarray(last), cache,
                                          config, active=jnp.asarray(active))
        got.append(logits[slot])
    want = ref.forward(*as_reference(params), jnp.asarray(tokens),
                       **ref.dims_of(spec))[prompt - 1:]
    np.testing.assert_allclose(jnp.stack(got), want, rtol=2e-4, atol=2e-5)


def test_the_reference_imports_nothing_of_the_model_under_test():
    with open(ref.__file__) as f:
        text = f.read()
    assert "import ant_ray_tpu" not in text
    assert "from ant_ray_tpu" not in text
