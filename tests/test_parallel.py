"""Parallel layer tests: mesh building, sharding rules, ring attention and
Ulysses vs the exact-attention oracle — all on the virtual 8-device mesh."""

import numpy as np
import pytest

from ant_ray_tpu._private.jax_utils import import_jax
from ant_ray_tpu.parallel import (
    AxisNames,
    MeshConfig,
    build_mesh,
    logical_to_spec,
    ring_attention,
    shard_pytree,
    ulysses_attention,
)
from ant_ray_tpu.parallel.ring import reference_attention

jax = import_jax()
import jax.numpy as jnp  # noqa: E402


def test_build_mesh_explicit():
    mesh = build_mesh(dp=2, tp=4)
    assert mesh.shape["dp"] == 2
    assert mesh.shape["tp"] == 4
    assert mesh.shape["pp"] == 1
    assert mesh.axis_names == AxisNames.ORDER


def test_build_mesh_wildcard():
    mesh = build_mesh(MeshConfig(tp=2, fsdp=-1))
    assert mesh.shape["fsdp"] == 4
    assert mesh.shape["tp"] == 2


def test_build_mesh_errors():
    with pytest.raises(ValueError, match="needs"):
        build_mesh(dp=3)
    with pytest.raises(ValueError, match="at most one"):
        build_mesh(MeshConfig(dp=-1, tp=-1))


def test_logical_to_spec():
    spec = logical_to_spec(("batch", "seq", "embed"))
    assert spec == jax.sharding.PartitionSpec(("dp", "fsdp"), "sp", None)
    with pytest.raises(KeyError):
        logical_to_spec(("unknown_dim",))


def test_shard_pytree():
    mesh = build_mesh(fsdp=2, tp=4)
    params = {"w": np.zeros((8, 16), np.float32),
              "b": np.zeros((16,), np.float32)}
    logical = {"w": ("embed_param", "mlp"), "b": ("mlp",)}
    sharded = shard_pytree(params, logical, mesh)
    w_shard = sharded["w"].addressable_shards[0].data
    assert w_shard.shape == (4, 4)  # 8/fsdp=2 × 16/tp=4
    assert sharded["b"].addressable_shards[0].data.shape == (4,)


def _qkv(batch=2, seq=64, heads=4, kv_heads=None, dim=16, seed=0):
    rng = np.random.RandomState(seed)
    kv_heads = kv_heads or heads
    q = rng.randn(batch, seq, heads, dim).astype(np.float32)
    k = rng.randn(batch, seq, kv_heads, dim).astype(np.float32)
    v = rng.randn(batch, seq, kv_heads, dim).astype(np.float32)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_reference(causal):
    mesh = build_mesh(sp=8)
    q, k, v = _qkv()
    expected = reference_attention(q, k, v, causal=causal)
    out = ring_attention(q, k, v, mesh=mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)


def test_ring_attention_gqa():
    mesh = build_mesh(sp=4, tp=2)
    q, k, v = _qkv(heads=8, kv_heads=2)
    expected = reference_attention(q, k, v, causal=True)
    out = ring_attention(q, k, v, mesh=mesh, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)


def test_ring_attention_with_dp_and_tp():
    mesh = build_mesh(dp=2, sp=2, tp=2)
    q, k, v = _qkv(batch=4, seq=32, heads=4)
    expected = reference_attention(q, k, v, causal=True)
    out = ring_attention(q, k, v, mesh=mesh, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_matches_reference(causal):
    mesh = build_mesh(MeshConfig(sp=4, dp=-1))
    q, k, v = _qkv(heads=8, seq=32)
    expected = reference_attention(q, k, v, causal=causal)
    out = ulysses_attention(q, k, v, mesh=mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)


def test_ulysses_rejects_indivisible_heads():
    mesh = build_mesh(sp=8)
    q, k, v = _qkv(heads=4, seq=32)  # 4 heads < 8-way sp
    with pytest.raises(Exception, match="divisible"):
        ulysses_attention(q, k, v, mesh=mesh)


def test_gpipe_matches_sequential():
    from ant_ray_tpu.parallel.pipeline import gpipe

    n_stages, num_micro, batch, dim = 4, 6, 4, 8
    mesh = build_mesh(pp=n_stages, dp=2)
    rng = np.random.RandomState(0)
    weights = jnp.asarray(rng.randn(n_stages, dim, dim).astype(np.float32)
                          * 0.3)
    xs = jnp.asarray(rng.randn(num_micro, batch, dim).astype(np.float32))

    def stage_fn(params, x):
        return jnp.tanh(x @ params["w"])

    out = gpipe(stage_fn, {"w": weights}, xs, mesh=mesh)

    expected = xs
    for s in range(n_stages):
        expected = jnp.tanh(expected @ weights[s])
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=1e-5, rtol=1e-5)


def test_gpipe_single_stage_degenerate():
    from ant_ray_tpu.parallel.pipeline import gpipe

    mesh = build_mesh(pp=1, dp=8)
    w = jnp.ones((1, 4, 4), jnp.float32)
    xs = jnp.ones((3, 8, 4), jnp.float32)
    out = gpipe(lambda p, x: x @ p["w"], {"w": w}, xs, mesh=mesh)
    np.testing.assert_allclose(np.asarray(out), 4.0)


# ------------------------------------------------- flash kernel backward


@pytest.mark.slow
@pytest.mark.parametrize("nkv", [8, 4])
def test_flash_backward_matches_reference(nkv):
    """dq/dk/dv from the pallas backward kernels (interpret mode on CPU)
    against jax.grad through the exact-attention oracle, incl. GQA."""
    from ant_ray_tpu.ops.attention import attention

    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(keys[0], (2, 256, 8, 128), jnp.float32)
    k = jax.random.normal(keys[1], (2, 256, nkv, 128), jnp.float32)
    v = jax.random.normal(keys[2], (2, 256, nkv, 128), jnp.float32)
    w = jnp.linspace(0.5, 2.0, 128)

    def loss(impl):
        return lambda q, k, v: (attention(q, k, v, impl=impl) * w).sum()

    got = jax.grad(loss("pallas"), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss("reference"), argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=2e-3, atol=2e-3)


def test_flash_forward_lse_matches_logsumexp():
    from ant_ray_tpu.ops.pallas.flash_attention import flash_attention_fwd_lse

    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(keys[0], (1, 256, 4, 128), jnp.float32)
    k = jax.random.normal(keys[1], (1, 256, 4, 128), jnp.float32)
    v = jax.random.normal(keys[2], (1, 256, 4, 128), jnp.float32)
    out, lse = flash_attention_fwd_lse(q, k, v, causal=True,
                                       interpret=True)
    scale = 128 ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    mask = jnp.tril(jnp.ones((256, 256), bool))
    s = jnp.where(mask[None, None], s, -jnp.inf)
    want = jax.scipy.special.logsumexp(s, axis=-1)       # (B, H, S)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("axes", [{"fsdp": 4}, {"fsdp": 2, "tp": 2},
                                  {"dp": 2, "tp": 2}], ids=str)
def test_flash_kernel_runs_per_shard_under_a_mesh(axes):
    """Under a mesh the pallas kernel runs inside a shard_map over batch
    and heads (a Mosaic kernel is not partitioned automatically — the
    TPU compiler refuses the bare call, tests/test_tpu_compile.py):
    loss and grads equal the unsharded exact-attention oracle."""
    import dataclasses

    from jax.sharding import NamedSharding

    from ant_ray_tpu.models import llama
    from ant_ray_tpu.parallel.sharding import logical_to_spec

    cfg = dataclasses.replace(llama.CONFIGS["tiny"], dim=256, max_seq=256)
    assert cfg.head_dim == 64                      # a kernel tile
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (4, 129)), jnp.int32)

    def loss(params, tokens, mesh, impl):
        return llama.loss_fn(params, {"tokens": tokens}, cfg, mesh=mesh,
                             attn_impl=impl)

    want_loss, want_grads = jax.value_and_grad(loss)(
        params, tokens, None, "reference")
    mesh = build_mesh(devices=jax.devices()[:4], **axes)
    got_loss, got_grads = jax.jit(
        jax.value_and_grad(loss), static_argnums=(2, 3))(
        jax.device_put(params, llama.param_shardings(cfg, mesh)),
        jax.device_put(tokens, NamedSharding(
            mesh, logical_to_spec(("batch", None)))),
        mesh, "pallas")
    np.testing.assert_allclose(float(got_loss), float(want_loss),
                               rtol=1e-5)
    for got, want in zip(jax.tree.leaves(got_grads),
                         jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)
