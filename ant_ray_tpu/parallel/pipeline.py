"""Pipeline parallelism: SPMD GPipe schedule over a ``pp`` mesh axis.

The reference provides PP only as vLLM config passthrough plus compiled-DAG
actor microbatching (SURVEY §2.3); here it is a single compiled XLA
program: every stage runs the same shard_map kernel, activations hop one
station per tick via ``ppermute``, bubbles are masked.  This composes with
the other axes (dp/fsdp/tp/sp) because it is just another mesh dimension.

Restriction (GPipe-standard): every stage preserves the activation
shape/dtype — true for transformer blocks.
"""

from __future__ import annotations

import functools

from ant_ray_tpu._private.jax_utils import import_jax


def gpipe_kernel(stage_fn, stage_params, microbatches, *, axis_name: str,
                 axis_size: int):
    """Per-device GPipe (call inside shard_map).

    stage_params: this stage's params with leading stage dim of 1
                  (tree_map-squeezed before use).
    microbatches: (num_micro, ...) — identical on every stage (replicated).
    Returns (num_micro, ...) final-stage outputs, replicated to all stages.
    """
    jax = import_jax()
    import jax.numpy as jnp  # noqa: PLC0415
    from jax import lax  # noqa: PLC0415

    params = jax.tree.map(lambda p: p[0], stage_params)
    idx = lax.axis_index(axis_name)
    num_micro = microbatches.shape[0]
    ticks = num_micro + axis_size - 1

    # Forward-shift permutation: stage i → i+1 (last stage's send drops
    # into stage 0, which ignores it).
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def tick(carry, t):
        pending = carry                       # activation from prev stage
        x_first = jnp.take(microbatches, jnp.clip(t, 0, num_micro - 1),
                           axis=0)
        x_in = jnp.where(idx == 0, x_first, pending)
        active = (t - idx >= 0) & (t - idx < num_micro)
        y = stage_fn(params, x_in)
        y = jnp.where(active, y, jnp.zeros_like(y))
        return lax.ppermute(y, axis_name, perm), y

    # The carry becomes pp-varying after the first ppermute; mark the
    # initial value accordingly (microbatches are replicated over pp).
    zeros0 = jnp.zeros_like(microbatches[0])
    if hasattr(lax, "pcast"):          # jax >= the pvary deprecation
        pending0 = lax.pcast(zeros0, axis_name, to="varying")
    elif hasattr(lax, "pvary"):        # the pvary window
        pending0 = lax.pvary(zeros0, axis_name)
    else:
        # jax predating varying-axes typing: there is no replicated vs.
        # varying distinction to annotate — the carry is just a value.
        pending0 = zeros0
    _, stage_outs = lax.scan(tick, pending0, jnp.arange(ticks))

    # Microbatch j leaves the last stage at tick j + axis_size - 1;
    # broadcast the last stage's tick outputs to everyone and slice.
    all_outs = lax.all_gather(stage_outs, axis_name)      # (pp, T, ...)
    last = jnp.take(all_outs, axis_size - 1, axis=0)      # (T, ...)
    return lax.dynamic_slice_in_dim(last, axis_size - 1, num_micro, axis=0)


def gpipe(stage_fn, stacked_params, microbatches, *, mesh,
          axis_name: str = "pp", batch_axes=("dp", "fsdp"),
          extra_activation_specs=None):
    """Run a GPipe pipeline over global arrays.

    Args:
      stage_fn: (params, x) -> y with y.shape == x.shape.
      stacked_params: pytree whose leaves have leading dim == pp degree
        (stage i's params at index i); sharded over the pp axis.
      microbatches: (num_micro, batch, ...) inputs; batch sharded over
        ``batch_axes``, replicated over pp.
    """
    jax = import_jax()
    from jax import shard_map  # noqa: PLC0415
    from jax.sharding import PartitionSpec as P  # noqa: PLC0415

    axis_size = mesh.shape[axis_name]
    param_spec = jax.tree.map(lambda _: P(axis_name), stacked_params)
    x_spec = P(None, batch_axes)
    kernel = functools.partial(gpipe_kernel, stage_fn,
                               axis_name=axis_name, axis_size=axis_size)
    # The final all_gather+take replicates the output over pp, but the
    # varying-axes checker can't infer that statically — disable it.
    fn = shard_map(kernel, mesh=mesh, in_specs=(param_spec, x_spec),
                   out_specs=x_spec, check_vma=False)
    return jax.jit(fn)(stacked_params, microbatches)
