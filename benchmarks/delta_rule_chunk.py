"""On the chip: the channel-gated delta rule's block form alone,
``ops/delta_rule.py`` ``chunk_delta_rule``, at the shape ONE linear
layer of ``solar-open2.digest`` runs it at in a chunk: 512 tokens, 64
heads of 128 x 128, float32, a state handed in.  Jitted, device ms a
call over ``RUNS`` calls (``XLA Modules`` events of a profiler trace,
median and least), and what the jaxpr says of the exponentials: the
elements of every ``exp``'s operand in one block of one head, summed,
and the largest single one.  Without a TPU nothing is timed and the
shape is cut.  One JSON line; through the chip tool, from the root:

    python -m benchmarks.delta_rule_chunk
"""

import json
import statistics
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from ant_ray_tpu.ops import delta_rule
from benchmarks.sampler_paths import device_ms

TOKENS, HEADS, D_K, D_V = 512, 64, 128, 128
RUNS = 50


def inputs(seed, tokens, heads, d_k, d_v):
    """Unit keys, queries of length d_k ** -0.5, log-decays between
    -1e-3 and -0.5 a token, write strengths in (0, 2), a random state."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k = (jax.random.normal(key, (tokens, heads, d_k)) for key in keys[:2])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d_k ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], (tokens, heads, d_v))
    g = -jnp.exp(jax.random.uniform(keys[3], (tokens, heads, d_k),
                                    minval=np.log(1e-3), maxval=np.log(0.5)))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(keys[4], (tokens, heads)))
    return q, k, v, g, beta, jax.random.normal(keys[5], (heads, d_k, d_v))


def exp_operands(fn, *args):
    """The element counts of every ``exp``'s operand in ``fn``'s jaxpr,
    the bodies of its scans and calls included (each body once)."""
    sizes = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "exp":
                sizes.append(int(np.prod(eqn.invars[0].aval.shape)))
            for value in eqn.params.values():
                for inner in value if isinstance(value, (tuple, list)) \
                        else (value,):
                    inner = getattr(inner, "jaxpr", inner)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return sizes


def measure():
    on_chip = jax.default_backend() == "tpu"
    tokens, heads = (TOKENS, HEADS) if on_chip else (128, 2)
    sizes = exp_operands(delta_rule.chunk_delta_rule,
                         *inputs(0, delta_rule.BLOCK, 1, D_K, D_V))
    line = {"tokens": tokens, "heads": heads, "state": [D_K, D_V],
            "block": delta_rule.BLOCK,
            "exp_elements_a_head_block": sum(sizes),
            "largest_exp_operand": max(sizes),
            "exp_share_of_block_block_d_k": sum(sizes) / (
                delta_rule.BLOCK ** 2 * D_K)}
    args = inputs(51, tokens, heads, D_K, D_V)
    run = jax.jit(delta_rule.chunk_delta_rule)
    o, s = run(*args)
    line["finite"] = bool(jnp.isfinite(o).all() & jnp.isfinite(s).all())
    if on_chip:
        with tempfile.TemporaryDirectory() as directory:
            jax.profiler.start_trace(directory)
            for _ in range(RUNS):
                run(*args)[0].block_until_ready()
            jax.profiler.stop_trace()
            ms = [t for _, t in device_ms(directory)]
        line.update(calls=len(ms), ms_a_call=statistics.median(ms),
                    ms_least=min(ms), ms_most=max(ms))
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    print(jax.devices())
    measure()
