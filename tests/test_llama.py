"""Llama model tests: shapes, loss/grad sanity, sharded == unsharded, and
a short training run that actually learns."""

import dataclasses
import functools

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
        for e, g in zip(chosen - cfg.first_expert, gates):
            if not 0 <= e < cfg.num_experts:
                continue        # a share: held elsewhere, counts nothing
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


def _wide_case(k, share):
    """(config, one layer's weights, 40 tokens) of a router as wide as
    the serving cells': ``k`` of 16 experts a token, all held — or,
    ``share``, experts 4-9 of them."""
    import dataclasses

    cfg = dataclasses.replace(
        llama.CONFIGS["olmoe-tiny"], experts_per_token=k,
        **(dict(num_experts=6, router_width=16, first_expert=4) if share
           else dict(num_experts=16)))
    params = llama.init_params(cfg, jax.random.PRNGKey(5))
    layer = {name: leaf[0] * 8 for name, leaf in params["layers"].items()}
    return cfg, layer, jax.random.normal(jax.random.PRNGKey(6),
                                         (40, cfg.dim))


@pytest.mark.parametrize("share", [False, True], ids=["all-held", "a-share"])
@pytest.mark.parametrize("k", [8, 10])
def test_way_back_sums_a_tokens_own_rows(k, share):
    """Eight and ten picks a token (the serving cells'), every expert
    held and a share whose other picks sort behind the last group:
    ``_routed_mlp`` against the loop over each token's held experts."""
    cfg, layer, h = _wide_case(k, share)
    got, load = jax.jit(lambda l, x: llama._routed_mlp(l, x, cfg))(layer, h)
    want, want_load = _per_token_loop(layer, h, cfg)
    np.testing.assert_array_equal(np.asarray(load), want_load)
    assert (int(load.sum()) < 40 * k) == share
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)


def _way_back_case(k, tokens, dim=128):
    """(rows sorted by "expert", back, gates, held) of ``tokens`` tokens
    of which the FIRST is the same in every case — its k rows, its
    gates, which of its picks are held — wherever the sort put them;
    the rows from ``held`` on are NaN."""
    rng = np.random.default_rng(53)
    mine, gate = rng.standard_normal((k, dim)), rng.random(k)
    keep = np.arange(k) % 3 != 1                # the first token's held picks
    rng = np.random.default_rng(tokens)
    held = int(keep.sum()) + (tokens - 1) * k // 2
    first = np.empty(k, np.int64)
    first[keep] = rng.permutation(held)[:keep.sum()]
    first[~keep] = held + rng.permutation(tokens * k - held)[:k - keep.sum()]
    others = rng.permutation(np.setdiff1d(np.arange(tokens * k), first))
    back = np.concatenate([first, others]).reshape(tokens, k)
    down = rng.standard_normal((tokens * k, dim))
    down[first] = mine
    down[held:] = np.nan
    gates = rng.random((tokens, k))
    gates[0] = gate
    return (jnp.asarray(down, jnp.float32), jnp.asarray(back, jnp.int32),
            jnp.asarray(gates, jnp.float32), jnp.int32(held), keep)


def _way_back_kernel(down, back, gates, held, dtype, columns=None):
    from ant_ray_tpu.ops.pallas import gather_sum

    return gather_sum.gather_sum(down, back, gates, held, dtype=dtype,
                                 columns=columns, interpret=True)


# the way back's two forms: the plain one (training, any backend, a
# mesh) and the step programs' kernel on one TPU device, interpreted
# here — whole and in panels of 128 columns
WAYS_BACK = {
    "plain": jax.jit(llama._back_to_tokens, static_argnums=4),
    "kernel": _way_back_kernel,
    "kernel-in-panels": functools.partial(_way_back_kernel, columns=128),
}


@pytest.mark.parametrize("form", WAYS_BACK)
@pytest.mark.parametrize("k", [8, 10])
def test_way_back_drops_a_row_of_no_group_by_a_select(k, form):
    """The rows behind the last group are whatever the grouped product
    left there — NaN here.  They count zero by a SELECT: the sum is
    that of the held rows alone, and neither a NaN nor its gradient
    (the gate's is the row itself) gets through."""
    down, back, gates, held, _ = _way_back_case(k, 48, dim=256)
    clean = jnp.where(jnp.isnan(down), 0.0, down)

    def total(down, gates):
        return jnp.sum(llama._back_to_tokens(down, back, gates, held,
                                             jnp.float32) ** 2)

    got = WAYS_BACK[form](down, back, gates, held, jnp.float32)
    want = sum(np.where((np.asarray(back)[:, j] < int(held))[:, None],
                        np.asarray(clean, np.float64)[np.asarray(back)[:, j]],
                        0.0) * np.asarray(gates, np.float64)[:, j, None]
               for j in range(k))
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)
    if form != "plain":
        return                  # the step programs' kernel: no gradient
    poisoned = jax.grad(total, argnums=(0, 1))(down, gates)
    sound = jax.grad(total, argnums=(0, 1))(clean, gates)
    for a, b in zip(poisoned, sound):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("form", WAYS_BACK)
@pytest.mark.parametrize("k", [8, 10])
def test_way_back_gives_a_token_the_same_bits_in_any_company(k, form):
    """One token's row alone, among 47 and among 511 others (a decode
    step's rows, a chunk's): the same k rows under the same gates,
    wherever the sort put them, are the same bits — the k terms are
    added in the picks' order, not in one the compiler takes from the
    operand's shape."""
    rows = []
    for tokens in (1, 48, 512):
        down, back, gates, held, keep = _way_back_case(k, tokens, dim=256)
        np.testing.assert_array_equal(np.asarray(back[0]) < int(held), keep)
        rows.append(np.asarray(WAYS_BACK[form](
            down, back, gates, held, jnp.float32))[0])
    assert np.abs(rows[0]).sum() > 0
    np.testing.assert_array_equal(rows[1], rows[2])
    if form == "plain":
        np.testing.assert_array_equal(rows[0], rows[1])
    else:
        # interpreted on the CPU a lone tile is no loop, and XLA's CPU
        # compiler contracts its multiply-adds as it likes: the chip's
        # proof is benchmarks/mixed_step_bits and routed_way_back
        np.testing.assert_allclose(rows[0], rows[1], rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("name", ["moe-tiny", "olmoe-tiny", "axk1-tiny",
                                  "granite-h-tiny"])
def test_loss_gradients_through_the_way_back(name, monkeypatch):
    """``loss_fn`` differentiates through the gathers and selects as
    written: every gradient finite and that of the form it replaced."""
    cfg = llama.CONFIGS[name]
    params = llama.init_params(cfg, jax.random.PRNGKey(1))
    batch = {"tokens": _tokens(1, 33)}

    def grads():
        return jax.value_and_grad(llama.loss_fn)(params, batch, cfg)

    from benchmarks import routed_way_back

    loss, got = grads()
    monkeypatch.setattr(llama, "_back_to_tokens",
                        routed_way_back.four_passes)
    was_loss, was = grads()
    np.testing.assert_allclose(float(loss), float(was_loss), rtol=1e-5)
    flat, was_flat = jax.tree.leaves(got), jax.tree.leaves(was)
    assert all(np.isfinite(np.asarray(g)).all() for g in flat)
    routers = [stack["router"] for stack in jax.tree.leaves(
        got, is_leaf=lambda x: isinstance(x, dict) and "router" in x)
        if isinstance(stack, dict)]
    assert routers and all(float(jnp.abs(r).sum()) > 0 for r in routers)
    for a, b in zip(flat, was_flat):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4 * float(
                                       jnp.abs(b).max()))


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


def test_only_the_step_programs_spell_their_roundings_out(tiny_params):
    """``_proj``'s named rounding is the step programs' (they pass
    ``index`` to the block): training's ``forward`` and ``loss_fn``
    trace to the bare products, as before it existed."""
    bf16 = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), tiny_params)

    def loss(p):
        return llama.loss_fn(p, {"tokens": _tokens(seq=17)}, bf16)

    def decode(p):
        return llama.decode_step(p, jnp.zeros((4,), jnp.int32),
                                 llama.init_kv_cache(bf16, 4, 64), bf16,
                                 active=jnp.ones((4,), bool))

    def chunk(p):
        return llama.prefill_chunk_into_cache(
            p, jnp.zeros((16,), jnp.int32), llama.init_kv_cache(bf16, 4, 64),
            1, 0, 5, bf16)

    spelt = {name: str(jax.make_jaxpr(f)(params)).count("reduce_precision")
             for name, f in (("loss", loss), ("grad", jax.grad(loss)),
                             ("decode", decode), ("chunk", chunk))}
    # a layer's scan body is traced once: q, k, v, the gate and the up
    assert spelt == {"loss": 0, "grad": 0, "decode": 5, "chunk": 5}


@pytest.mark.parametrize("rows", [12, 64, 512])
@pytest.mark.parametrize("name", ["tiny", "olmoe-tiny", "axk1-tiny"])
def test_the_step_form_projection_is_the_bare_product(name, rows):
    """The products a step program splits into heads, spelt out
    (``_proj``: float32 sums, one rounding by name), for a decode
    step's rows and a 64- and a 512-row chunk's: bit for bit the bare
    ``h @ w`` in bfloat16, each compiled alone — a spelling, not another
    precision.  At 512 rows the CPU's bare product takes another
    kernel, whose float32 sums run in another order: an element or two
    in 32,768 then land on the other side of a rounding, one ulp away,
    and as often it is the bare one that misses the exact product's
    rounding.  (Compiled TOGETHER with what follows it the bare form is
    the one that moves: the CPU's compiler too keeps its sums unrounded
    into the QK-norm it fuses them with.)"""
    c = dataclasses.replace(llama.CONFIGS[name], dtype=jnp.bfloat16)
    layer = jax.tree.map(lambda x: x[0], llama.init_params(
        c, jax.random.PRNGKey(0))["layers"])
    proj = jax.jit(llama._proj, static_argnums=2)
    for leaf in ("w_qb",) if c.kv_lora_rank else ("wq", "wk", "wv"):
        w = layer[leaf]
        h = jax.random.normal(jax.random.PRNGKey(rows), (rows, w.shape[0]),
                              c.dtype)
        bare, spelt = proj(h, w, False), proj(h, w, True)
        assert spelt.dtype == bare.dtype == jnp.bfloat16
        apart = _bits(spelt) != _bits(bare)
        assert apart.sum() <= (bare.size // 8192 if rows > 64 else 0), leaf
        ulp = np.abs(np.asarray(bare, np.float32)) * 2.0 ** -7
        assert (np.abs(np.asarray(spelt, np.float32) - np.asarray(
            bare, np.float32)) <= ulp).all(), leaf


# ------------------------------------------ the block walk over the cache
# ``_attend_slab`` walks a slot's slab in blocks of ``ATTEND_BLOCK``
# positions and stops behind the longest live one.  The tests' slabs are
# shorter than the serving block, so the walk is cut to 16 positions a
# block here: 40 positions are two blocks and a tail of 8, 48 three
# whole ones, 12 are less than one.

BLOCK = 16
LAYOUTS = {
    "grouped-query": CFG,                                       # 4 / 2
    "16-of-16": dataclasses.replace(CFG, n_kv_heads=4),         # 4 / 4
    "latent": llama.CONFIGS["axk1-tiny"],
}


@pytest.fixture
def short_blocks(monkeypatch):
    monkeypatch.setattr(llama, "ATTEND_BLOCK", BLOCK)


def _one_shot(xq, ck, cv, pos, c, w_kvb=None):
    """What ``_attend_slab`` was before the walk, kept as the reference:
    ONE slab (max_seq, ...) the rows share, or a slab a row, scored over
    every reserved position at once and normalised before the cast."""
    f32 = {"preferred_element_type": jnp.float32}
    valid = jnp.arange(ck.shape[-2 if w_kvb is not None else -3]) \
        <= pos[:, None]
    if w_kvb is not None:
        nope = c.qk_nope_head_dim
        wk, wv = llama._kvb_by_head(w_kvb, c)
        t = "rtc" if ck.ndim == 3 else "tc"
        q_lat = jnp.einsum("rhd,chd->rhc", xq[..., :nope], wk,
                           **f32).astype(xq.dtype)
        scores = (jnp.einsum(f"rhc,{t}->rht", q_lat, ck, **f32)
                  + jnp.einsum(f"rhc,{t}->rht", xq[..., nope:], cv, **f32))
        scores = jnp.where(valid[:, None, :], scores * c.attn_scale,
                           -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum(f"rht,{t}->rhc", probs.astype(ck.dtype), ck,
                         **f32).astype(xq.dtype)
        return jnp.einsum("rhc,chd->rhd", ctx, wv, **f32).astype(xq.dtype)
    t = "rtkd" if ck.ndim == 4 else "tkd"
    q = xq.reshape(xq.shape[0], c.n_kv_heads, c.n_heads // c.n_kv_heads,
                   c.head_dim)
    scores = jnp.einsum(f"rkgd,{t}->rkgt", q, ck, **f32)
    scores = scores / jnp.sqrt(jnp.float32(c.head_dim))
    scores = jnp.where(valid[:, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(f"rkgt,{t}->rkgd", probs.astype(ck.dtype), cv, **f32)
    return out.reshape(xq.shape).astype(xq.dtype)


def _slabs(layout, dtype, slots, max_seq, rows=None, seed=0):
    """Random queries for ``rows`` rows (a row a slot unless given) and
    a random two-layer cache as the step programs carry it."""
    c = dataclasses.replace(LAYOUTS[layout], dtype=dtype)
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    xq = jax.random.normal(keys[0], (rows or slots, c.n_heads, c.head_dim),
                           jnp.float32).astype(dtype)
    ks, vs = (jax.random.normal(key, (2, slots, max_seq, *position),
                                jnp.float32).astype(dtype)
              for key, position in zip(keys[1:], llama.kv_slabs(c).values()))
    w_kvb = None
    if c.kv_lora_rank:
        w_kvb = (jax.random.normal(keys[3], (c.kv_lora_rank, c.n_heads * (
            c.qk_nope_head_dim + c.v_head_dim))) * 0.3).astype(dtype)
    return c, xq, ks, vs, w_kvb


def _bits(x):
    return np.asarray(x, np.float32).view(np.uint32)


# The one-shot form rounds the probabilities to the cache's dtype after
# the division, the walk before it: in bf16 that is 2**-9 relative a
# probability, and both outputs are then rounded to bf16 themselves (one
# unit in the last place of a value of 1-2 is 2**-7); in float32 only
# the order of the sums differs.
WALK_ATOL = {jnp.float32: 5e-6, jnp.bfloat16: 2.0 ** -6}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("max_seq", [40, 48, 12],
                         ids=["tail-of-8", "whole-blocks", "under-a-block"])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_walk_equals_the_one_shot_attention_a_slab_a_row(
        short_blocks, layout, max_seq, dtype):
    """A decode step's attention, rows at position 0, a block's edge and
    either side of it, the slab's last position and a FULL slot
    (``pos == max_seq``: its write was dropped, it sees every row)."""
    pos = [0, BLOCK - 2, BLOCK - 1, BLOCK, max_seq - 1, max_seq, 5]
    pos = jnp.asarray([min(p, max_seq) for p in pos], jnp.int32)
    c, xq, ks, vs, w_kvb = _slabs(layout, dtype, len(pos), max_seq)
    blocks = llama._span_blocks(jnp.max(pos) + 1, max_seq)
    assert int(blocks) == -(-max_seq // BLOCK)
    got = llama._attend_slab(xq, ks, vs, 1, None, pos, blocks, c, w_kvb)
    want = _one_shot(xq, ks[1], vs[1], pos, c, w_kvb)
    assert got.dtype == want.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=WALK_ATOL[dtype], rtol=0)


@pytest.mark.parametrize("start,chunk_len", [(10, 9), (0, 16), (30, 10),
                                             (16, 1)],
                         ids=["crosses-an-edge", "ends-on-an-edge",
                              "ends-at-the-slab", "starts-a-block"])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_walk_equals_the_one_shot_attention_of_a_padded_chunk(
        short_blocks, layout, start, chunk_len):
    """A chunk's attention over its ONE slot: 16 rows of which
    ``chunk_len`` are real, walked as far as ``start + chunk_len``
    rounded up to a block; the real rows see what the one-shot form
    sees.  (A padded row's position may lie behind the walk: nobody
    reads it.)"""
    max_seq, slot = 40, 2
    c, xq, ks, vs, w_kvb = _slabs(layout, jnp.float32, 3, max_seq, rows=16)
    pos = start + jnp.arange(16, dtype=jnp.int32)
    blocks = llama._span_blocks(start + chunk_len, max_seq)
    assert int(blocks) == min(-(-(start + chunk_len) // BLOCK), 3)
    got = llama._attend_slab(xq, ks, vs, 1, slot, pos, blocks, c, w_kvb)
    want = _one_shot(xq, ks[1, slot], vs[1, slot], pos, c, w_kvb)
    np.testing.assert_allclose(got[:chunk_len], want[:chunk_len], atol=5e-6,
                               rtol=0)
    assert np.isfinite(np.asarray(got)).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_a_rows_attention_is_bit_equal_whatever_bound_the_others_set(
        short_blocks, layout, dtype):
    """Row 0 (position 9: one block) under a walk of one block, of two
    and of the whole slab — as the OTHER rows' lengths would set it —
    comes out bit for bit the same: the blocks behind its position add
    exact zeros under a rescale of exactly 1."""
    max_seq = 40
    c, xq, ks, vs, w_kvb = _slabs(layout, dtype, 4, max_seq)
    pos = jnp.asarray([9, 3, 12, 7], jnp.int32)
    outs = [llama._attend_slab(xq, ks, vs, 0, None, pos, jnp.int32(blocks),
                               c, w_kvb)
            for blocks in (1, 2, 3)]
    for out in outs[1:]:
        assert (_bits(out) == _bits(outs[0])).all()
    # and a walk that stops short of a row's position changes THAT row
    far = pos.at[1].set(max_seq - 1)
    short, whole = (llama._attend_slab(xq, ks, vs, 0, None, far,
                                       jnp.int32(blocks), c, w_kvb)
                    for blocks in (1, 3))
    assert (_bits(short[0]) == _bits(whole[0])).all()
    assert (_bits(short[1]) != _bits(whole[1])).any()


@pytest.mark.parametrize("longest,max_seq,blocks", [
    (0, 40, 1), (1, 40, 1), (16, 40, 1), (17, 40, 2), (32, 40, 2),
    (33, 40, 3), (41, 40, 3), (49, 48, 3), (5, 12, 1), (13, 12, 1)])
def test_span_on_the_host_is_the_span_on_the_device(
        short_blocks, longest, max_seq, blocks):
    """``span_positions`` (the engine's counter) and ``_span_blocks``
    (the step programs' bound) are one rule: whole blocks that hold
    ``longest`` positions, at least one, at most the slab."""
    assert int(llama._span_blocks(jnp.int32(longest), max_seq)) == blocks
    assert llama.span_positions(longest, max_seq) == min(
        blocks * min(BLOCK, max_seq), max_seq)


def _step_logits(params, c, lengths, active, max_seq=40, seed=1):
    """One ``decode_step`` over a random cache whose slots hold
    ``lengths`` positions."""
    cache = llama.init_kv_cache(c, len(lengths), max_seq)
    for name, key in zip(llama.kv_slabs(c),
                         jax.random.split(jax.random.PRNGKey(seed), 2)):
        cache[name] = jax.random.normal(key, cache[name].shape, c.dtype)
    cache["length"] = jnp.asarray(lengths, jnp.int32)
    last = jnp.arange(len(lengths), dtype=jnp.int32) + 3
    return llama.decode_step(params, last, cache, c,
                             active=jnp.asarray(active))


@pytest.mark.parametrize("config", [
    *LAYOUTS.values(), llama.CONFIGS["olmoe-tiny"]],
    ids=[*LAYOUTS, "routed"])
def test_decode_step_row_is_bit_equal_whatever_the_other_rows_hold(
        short_blocks, config):
    """A whole decode step's logits for row 0: the other rows short (the
    walk is one block), one other row near its slab's end (every
    block), and that long row INACTIVE (one block again: a resident
    session's slab does not lengthen the walk) — bit for bit the same,
    through routed experts too, where the other rows' tokens change
    which rows each expert is given; and an inactive row's slab and
    length stay as they were."""
    params = llama.init_params(config, jax.random.PRNGKey(0))
    short, long_ = [9, 3, 12, 7], [9, 3, 38, 7]
    on, off = [True] * 4, [True, True, False, True]
    a, _ = _step_logits(params, config, short, on)
    b, _ = _step_logits(params, config, long_, on)
    d, after = _step_logits(params, config, long_, off)
    assert (_bits(a[0]) == _bits(b[0])).all()
    assert (_bits(a[0]) == _bits(d[0])).all()
    assert (_bits(a[2]) != _bits(b[2])).any()
    assert after["length"].tolist() == [10, 4, 38, 8]
    _, before = _step_logits(params, config, long_, [False] * 4)
    for name in llama.kv_slabs(config):
        assert (_bits(after[name][:, 2]) == _bits(before[name][:, 2])).all()


def test_decode_step_with_no_active_row_and_with_a_full_slot(
        short_blocks, tiny_params, monkeypatch):
    """No active row at all: the walk is its one least block, every
    logit finite, nothing written.  A full slot (length == max_seq)
    among the active rows: every block, its write dropped, its logits
    the one-shot form's — the step equals the same step with the walk
    switched off (one block as long as the slab)."""
    logits, cache = _step_logits(tiny_params, CFG, [9, 40, 12, 7],
                                 [False] * 4)
    assert np.isfinite(np.asarray(logits)).all()
    assert cache["length"].tolist() == [9, 40, 12, 7]
    walked, cache = _step_logits(tiny_params, CFG, [9, 40, 12, 7],
                                 [True] * 4)
    assert cache["length"].tolist() == [10, 40, 13, 8]
    monkeypatch.setattr(llama, "ATTEND_BLOCK", 40)
    whole, _ = _step_logits(tiny_params, CFG, [9, 40, 12, 7], [True] * 4)
    np.testing.assert_allclose(walked, whole, atol=2e-5, rtol=0)
