"""On the chip: the routed experts' way BACK to token order alone — the
grouped down product's float32 rows, sorted by expert, to one row a
token under its k gates — at the shapes the benchmark's routed cells run
(five configurations x decode step / widest chunk or mixed step), three
forms side by side: ``four_passes`` (what the step programs did until
PR 53: a select over the rows behind the last group, a gather to token
order, a reshape to (tokens, k, width), a sum over k — each a pass over
the whole (tokens x k, width) array), ``plain``
(``llama._back_to_tokens``: k gathers of a token's row, selected, gated
and added in the picks' order) and ``kernel``
(``ops/pallas/gather_sum.py``, the step programs' form on one TPU
device: the same equation in one call).  Device ms a call (``XLA
Modules`` events of a profiler trace, median of the executions; without
a TPU nothing is timed and the kernel is interpreted), whether the
kernel's bits are the plain form's, and how far ``four_passes`` (whose
sum order is the compiler's) lies from them.  Alone, XLA fuses the
plain form's k gathers into a few operations; inside a step program it
leaves 2 k a layer (PERF.md section 6, PR 53), so a form's cost in a
program is read from a cell's trace, not here.  The rows behind the
last group are NaN here: no form may let one through.  One JSON line a
shape; through the chip tool, from the root:

    python -m benchmarks.routed_way_back [shape ...]
"""

import functools
import json
import statistics
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ant_ray_tpu.models import llama
from ant_ray_tpu.ops.pallas import gather_sum
from benchmarks.sampler_paths import device_ms

# shape: (tokens of the step, k a token, experts held, router width, dim)
SHAPES = {
    "olmoe-1b-7b.decode": (16, 8, 64, 64, 2048),
    "olmoe-1b-7b.mixed": (80, 8, 64, 64, 2048),
    "ax-k1.decode": (48, 8, 12, 192, 7168),
    "ax-k1.mixed": (112, 8, 12, 192, 7168),
    "command-a-plus.decode": (16, 8, 16, 128, 4096),
    "command-a-plus.chunk": (512, 8, 16, 128, 4096),
    "solar-open2.decode": (24, 8, 40, 320, 4096),
    "solar-open2.chunk": (512, 8, 40, 320, 4096),
    "granite-4.0-h-small.decode": (48, 10, 36, 72, 4096),
    "granite-4.0-h-small.chunk": (512, 10, 36, 72, 4096),
}
RUNS = 12


def four_passes(down, back, gates, held, dtype=jnp.bfloat16):
    """The way back as it stood until PR 53: a select over the rows, a
    gather to token order, a sum over the picks' axis."""
    down = jnp.where((jnp.arange(down.shape[0]) < held)[:, None], down, 0.0)
    out = down[back.reshape(-1)].reshape(*back.shape, -1)
    return jnp.sum(out * gates[..., None], axis=1).astype(dtype)


FORMS = {
    "four_passes": four_passes,
    "plain": functools.partial(llama._back_to_tokens, dtype=jnp.bfloat16),
    "kernel": functools.partial(gather_sum.gather_sum, dtype=jnp.bfloat16,
                                interpret=jax.default_backend() != "tpu"),
}


def measure(shape):
    tokens, k, n_held, width, dim = SHAPES[shape]
    key = jax.random.PRNGKey(53)
    gates, experts = lax.top_k(jax.nn.softmax(
        jax.random.normal(key, (tokens, width))), k)
    experts = jnp.where(experts.reshape(-1) < n_held, experts.reshape(-1),
                        n_held)
    back = jnp.argsort(jnp.argsort(experts)).reshape(-1, k)
    held = jnp.sum(experts < n_held)
    down = jax.random.normal(key, (tokens * k, dim), jnp.float32)
    down = jnp.where((jnp.arange(tokens * k) < held)[:, None], down, jnp.nan)
    programs = {name: jax.jit(form) for name, form in FORMS.items()}
    got = {name: np.asarray(program(down, back, gates, held), np.float32)
           for name, program in programs.items()}
    line = {"shape": shape, "tokens": tokens, "k": k, "dim": dim,
            "rows_held_pct": round(100 * int(held) / (tokens * k), 1),
            "device": jax.devices()[0].device_kind,
            "finite": bool(all(np.isfinite(g).all() for g in got.values())),
            "four_passes_worst_diff": float(np.abs(
                got["four_passes"] - got["plain"]).max()),
            "kernel_equals_plain": bool(np.array_equal(
                got["kernel"], got["plain"])),
            "device_ms": {}}
    with tempfile.TemporaryDirectory() as directory:
        jax.profiler.start_trace(directory)
        for program in programs.values():
            for _ in range(RUNS):
                out = program(down, back, gates, held)
            jax.block_until_ready(out)
        jax.profiler.stop_trace()
        ran = [ms for _, ms in device_ms(directory)]
    if ran:
        assert len(ran) == RUNS * len(programs), len(ran)
        for i, name in enumerate(programs):
            line["device_ms"][name] = round(
                statistics.median(ran[i * RUNS:][:RUNS]), 4)
    print(json.dumps(line), flush=True)
    return line["finite"]


def main(shapes):
    print(jax.devices(), flush=True)
    return 0 if all([measure(shape) for shape in shapes or SHAPES]) else 1


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
