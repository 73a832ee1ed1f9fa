"""Llama model tests: shapes, loss/grad sanity, sharded == unsharded, and
a short training run that actually learns."""

import numpy as np
import pytest

from ant_ray_tpu._private.jax_utils import import_jax
from ant_ray_tpu.models import llama
from ant_ray_tpu.parallel import MeshConfig, build_mesh

jax = import_jax()
import jax.numpy as jnp  # noqa: E402

CFG = llama.CONFIGS["tiny"]


@pytest.fixture(scope="module")
def tiny_params():
    return llama.init_params(CFG, jax.random.PRNGKey(0))


def _tokens(batch=2, seq=64, seed=0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randint(0, CFG.vocab_size, (batch, seq)),
                       jnp.int32)


def test_forward_shapes(tiny_params):
    logits = llama.forward(tiny_params, _tokens(), CFG)
    assert logits.shape == (2, 64, CFG.vocab_size)
    assert logits.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_param_count_consistency(tiny_params):
    actual = sum(x.size for x in jax.tree.leaves(tiny_params))
    assert actual == CFG.num_params()


def test_causality(tiny_params):
    """Changing a future token must not affect earlier logits."""
    t1 = _tokens(batch=1)
    t2 = t1.at[0, -1].set((t1[0, -1] + 1) % CFG.vocab_size)
    l1 = llama.forward(tiny_params, t1, CFG)
    l2 = llama.forward(tiny_params, t2, CFG)
    np.testing.assert_allclose(np.asarray(l1[0, :-1]),
                               np.asarray(l2[0, :-1]), atol=1e-5)
    assert not np.allclose(np.asarray(l1[0, -1]), np.asarray(l2[0, -1]))


@pytest.mark.slow
def test_loss_and_grad_finite(tiny_params):
    batch = {"tokens": _tokens(seq=65)}
    loss, grads = jax.value_and_grad(llama.loss_fn)(tiny_params, batch, CFG)
    assert np.isfinite(float(loss))
    assert float(loss) > 0
    for g in jax.tree.leaves(grads):
        assert bool(jnp.all(jnp.isfinite(g)))


def test_sharded_matches_unsharded(tiny_params):
    """FSDP+TP sharded forward must equal the single-device forward."""
    mesh = build_mesh(fsdp=2, tp=4)
    sharded_params = jax.device_put(
        tiny_params, llama.param_shardings(CFG, mesh))
    tokens = _tokens()
    base = llama.forward(tiny_params, tokens, CFG)
    sharded = jax.jit(
        lambda p, t: llama.forward(p, t, CFG, mesh=mesh))(
            sharded_params, tokens)
    np.testing.assert_allclose(np.asarray(base), np.asarray(sharded),
                               atol=2e-4, rtol=2e-4)


def test_ring_sharded_matches_unsharded(tiny_params):
    """Sequence-parallel (ring attention) forward equals the base."""
    mesh = build_mesh(MeshConfig(sp=4, dp=-1))
    sharded_params = jax.device_put(
        tiny_params, llama.param_shardings(CFG, mesh))
    tokens = _tokens()
    base = llama.forward(tiny_params, tokens, CFG)
    sharded = jax.jit(
        lambda p, t: llama.forward(p, t, CFG, mesh=mesh))(
            sharded_params, tokens)
    np.testing.assert_allclose(np.asarray(base), np.asarray(sharded),
                               atol=2e-4, rtol=2e-4)


def test_training_learns(tiny_params):
    """A few steps on a repetitive sequence should cut the loss."""
    import optax

    pattern = jnp.asarray(
        np.tile(np.arange(8), 9)[None, :65].repeat(2, 0), jnp.int32)
    batch = {"tokens": pattern}
    opt = optax.adam(3e-3)
    params = tiny_params
    state = opt.init(params)

    @jax.jit
    def step(params, state):
        loss, grads = jax.value_and_grad(llama.loss_fn)(params, batch, CFG)
        updates, state = opt.update(grads, state)
        return optax.apply_updates(params, updates), state, loss

    first = None
    for i in range(30):
        params, state, loss = step(params, state)
        if first is None:
            first = float(loss)
    assert float(loss) < first * 0.5, (first, float(loss))


def test_greedy_generate(tiny_params):
    out = llama.greedy_generate(tiny_params, CFG, jnp.arange(8),
                                max_new_tokens=4)
    assert out.shape == (1, 12)


# The routed models: Mixtral-style (gates renormalised) and OLMoE-style
# (gates as the softmax gave them, QK-norm).  One routed MLP serves both.
ROUTED = ["moe-tiny", "olmoe-tiny"]


@pytest.fixture(scope="module", params=ROUTED)
def routed(request):
    cfg = llama.CONFIGS[request.param]
    return cfg, llama.init_params(cfg, jax.random.PRNGKey(1))


def test_moe_forward_and_grad(routed):
    cfg, moe_params = routed
    tokens = _tokens()
    logits = llama.forward(moe_params, tokens, cfg)
    assert logits.shape == (2, 64, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()
    batch = {"tokens": _tokens(2, 65)}
    loss, grads = jax.value_and_grad(llama.loss_fn)(
        moe_params, batch, cfg)
    assert np.isfinite(float(loss))
    flat = jax.tree.leaves(grads)
    assert all(np.isfinite(np.asarray(g)).all() for g in flat)
    # The router actually routes: gradients reach the router weights.
    assert float(jnp.abs(grads["layers"]["router"]).sum()) > 0


def test_moe_expert_sharded_matches_unsharded(routed):
    """Expert-parallel (ep) sharded forward equals the base — the ep
    axis is real, not decorative."""
    cfg, moe_params = routed
    mesh = build_mesh(MeshConfig(ep=2, tp=2, dp=-1))
    sharded_params = jax.device_put(
        moe_params, llama.param_shardings(cfg, mesh))
    tokens = _tokens()
    base = llama.forward(moe_params, tokens, cfg)
    sharded = jax.jit(
        lambda p, t: llama.forward(p, t, cfg, mesh=mesh))(
            sharded_params, tokens)
    np.testing.assert_allclose(np.asarray(base), np.asarray(sharded),
                               atol=2e-4, rtol=2e-4)


def _per_token_loop(layer, h, cfg):
    """The routed MLP written the slow way: for each token, its k most
    probable experts one by one (numpy, float64)."""
    h = np.asarray(h, np.float64)
    w = {name: np.asarray(layer[name], np.float64)
         for name in ("router", "w_gate", "w_up", "w_down")}
    out = np.zeros_like(h)
    load = np.zeros((cfg.num_experts,), np.int64)
    for t, x in enumerate(h):
        logits = x @ w["router"]
        probs = np.exp(logits - logits.max())
        probs /= probs.sum()
        chosen = np.argsort(-probs, kind="stable")[:cfg.experts_per_token]
        gates = probs[chosen]
        if cfg.norm_topk_prob:
            gates = gates / gates.sum()
        for e, g in zip(chosen, gates):
            a = x @ w["w_gate"][e]
            out[t] += g * ((a / (1 + np.exp(-a)) * (x @ w["w_up"][e]))
                           @ w["w_down"][e])
            load[e] += 1
    return out, load


def _routed_case(name):
    """(config, one layer's weights, activations) of a routing case."""
    import dataclasses

    base, steer = {
        "mixtral-style": ("moe-tiny", None),
        "olmoe-style": ("olmoe-tiny", None),
        "one-expert-takes-all": ("olmoe-tiny", +1),
        "one-expert-gets-none": ("olmoe-tiny", -1),
        "three-of-eight": ("olmoe-tiny", None),
    }[name]
    cfg = llama.CONFIGS[base]
    if name == "one-expert-takes-all":
        cfg = dataclasses.replace(cfg, experts_per_token=1)
    if name == "three-of-eight":
        cfg = dataclasses.replace(cfg, experts_per_token=3)
    params = llama.init_params(cfg, jax.random.PRNGKey(3))
    layer = {k: v[0] * 8 for k, v in params["layers"].items()}
    h = jax.random.normal(jax.random.PRNGKey(4), (3, 7, cfg.dim))
    if steer:
        # feature 0 is +4 on every token and the router reads it into
        # expert 5 alone: every token's first choice, or nobody's.
        h = h.at[..., 0].set(4.0)
        layer["router"] = layer["router"].at[0].set(0.0).at[0, 5].set(
            steer * 8.0)
    return cfg, layer, h


@pytest.mark.parametrize("name", [
    "mixtral-style", "olmoe-style", "one-expert-takes-all",
    "one-expert-gets-none", "three-of-eight"])
def test_routed_mlp_equals_the_per_token_loop(name):
    """Sort, grouped product, unsort and combine against the loop over
    each token's experts, values and gradients' reach; float32 against
    float64, so 1e-5 relative is rounding and any routing or gate slip
    is orders above it."""
    cfg, layer, h = _routed_case(name)
    got, load = jax.jit(lambda l, x: llama._routed_mlp(l, x, cfg))(layer, h)
    want, want_load = _per_token_loop(layer, h.reshape(-1, cfg.dim), cfg)
    np.testing.assert_array_equal(np.asarray(load), want_load)
    assert int(load.sum()) == 21 * cfg.experts_per_token
    if name == "one-expert-takes-all":
        assert int(load[5]) == 21
    if name == "one-expert-gets-none":
        assert int(load[5]) == 0
    np.testing.assert_allclose(np.asarray(got).reshape(-1, cfg.dim), want,
                               rtol=1e-5, atol=1e-6)
    grads = jax.grad(lambda l: jnp.sum(
        llama._routed_mlp(l, h, cfg)[0] ** 2))(layer)
    hit = np.asarray(jnp.abs(grads["w_down"]).sum(axis=(1, 2)) > 0)
    np.testing.assert_array_equal(hit, want_load > 0)


def test_pp_loss_matches_dense_loss(tiny_params):
    """The GPipe pipeline loss (pp axis) equals the plain scan loss —
    microbatching and stage hops change nothing numerically."""
    mesh = build_mesh(MeshConfig(pp=2, tp=2, dp=-1))
    sharded_params = jax.device_put(
        tiny_params, llama.param_shardings(CFG, mesh))
    batch = {"tokens": _tokens(4, 65)}
    base = float(llama.loss_fn(tiny_params, batch, CFG))
    pp = float(jax.jit(
        lambda p, b: llama.loss_fn_pp(p, b, CFG, mesh=mesh,
                                      num_microbatches=2))(
            sharded_params, batch))
    assert abs(base - pp) < 2e-4, (base, pp)


def test_pp_grads_flow(tiny_params):
    """Backward through the pipeline reaches every stage's params."""
    mesh = build_mesh(MeshConfig(pp=2, tp=2, dp=-1))
    sharded_params = jax.device_put(
        tiny_params, llama.param_shardings(CFG, mesh))
    batch = {"tokens": _tokens(4, 65)}
    grads = jax.jit(jax.grad(
        lambda p: llama.loss_fn_pp(p, batch, CFG, mesh=mesh,
                                   num_microbatches=2)))(sharded_params)
    for name in ("wq", "w_gate", "w_down"):
        g = np.asarray(grads["layers"][name])
        # Both layers (= both pipeline stages) receive gradient signal.
        assert np.abs(g[0]).sum() > 0 and np.abs(g[1]).sum() > 0, name


def _trace_forward(params):
    return llama.forward(params, _tokens(), CFG)


def _trace_loss_fn_pp(params):
    mesh = build_mesh(MeshConfig(pp=2, tp=2, dp=-1))
    return llama.loss_fn_pp(params, {"tokens": _tokens(4, 65)}, CFG,
                            mesh=mesh, num_microbatches=2)


def _trace_decode_step(params):
    cache = llama.init_kv_cache(CFG, 4, 64)
    return llama.decode_step(params, jnp.zeros((4,), jnp.int32), cache, CFG,
                             active=jnp.ones((4,), bool))


def _trace_prefill_chunk_into_cache(params):
    cache = llama.init_kv_cache(CFG, 4, 64)
    return llama.prefill_chunk_into_cache(
        params, jnp.zeros((16,), jnp.int32), cache, 1, 0, 5, CFG)


@pytest.mark.parametrize("program", [
    _trace_forward, _trace_loss_fn_pp, _trace_decode_step,
    _trace_prefill_chunk_into_cache], ids=lambda f: f.__name__[7:])
def test_every_program_runs_the_one_block(tiny_params, monkeypatch, program):
    """Training, the pipeline stages, a prefill chunk and a decode step
    are traced through ``llama.apply_block``: the block's equations are
    written there and nowhere else."""
    calls = []
    apply_block = llama.apply_block

    def counted(*args, **kwargs):
        calls.append(None)
        return apply_block(*args, **kwargs)

    monkeypatch.setattr(llama, "apply_block", counted)
    jax.eval_shape(program, tiny_params)
    assert calls
