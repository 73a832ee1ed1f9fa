"""On the chip: the routed experts' grouped product alone, XLA's
``ragged-dot`` kernel beside ``ops/pallas/grouped_matmul.py`` by tile, at
the fourteen shapes the benchmark's routed cells run (seven
configurations x decode step / prefill chunk): device ms a call, the
GB/s at which the experts HIT are read, the rule's visits and crossings
(visits beyond a group's first) a call, whether the two agree to bf16
rounding on the rows that belong to a group, and a digest of each
tiling's output bits (``bits``: equal between two trees where the
kernel's sums are the same — run this file against another tree with
``cd <its copy> && PYTHONPATH=. python <this file>``).  Both read the whole stack of ``LAYERS``
layers' experts with a traced layer index, as the step programs do; the
routing is a top-k of random scores over the router's width, of which
the first ``held`` experts are here (the benchmark's weights are random
too).  Times are the device's (``XLA Modules`` events of a profiler
trace, median of the executions); without a TPU there is no device time
and nothing is timed.  One JSON line a product; through the chip tool,
from the root:

    python -m benchmarks.grouped_product [shape ...]
"""

import functools
import hashlib
import json
import statistics
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ant_ray_tpu.ops.pallas import grouped_matmul as gm
from benchmarks.sampler_paths import device_ms

# shape: (tokens of the step, k a token, experts held, router width,
#         an expert's (dim, width))
SHAPES = {
    "olmoe-1b-7b.decode": (16, 8, 64, 64, (2048, 1024)),
    "olmoe-1b-7b.chunk": (64, 8, 64, 64, (2048, 1024)),
    "ax-k1.decode": (48, 8, 12, 192, (7168, 2048)),
    "ax-k1.chunk": (64, 8, 12, 192, (7168, 2048)),
    "command-a-plus.decode": (16, 8, 16, 128, (4096, 4096)),
    "command-a-plus.chunk": (512, 8, 16, 128, (4096, 4096)),
    "granite-4.0-h-small.decode": (48, 10, 36, 72, (4096, 768)),
    "granite-4.0-h-small.chunk": (512, 10, 36, 72, (4096, 768)),
    "xing4.0-29b-a4b.decode": (16, 4, 64, 64, (3584, 1024)),
    "xing4.0-29b-a4b.chunk": (512, 4, 64, 64, (3584, 1024)),
    "solar-open2.decode": (24, 8, 40, 320, (4096, 1280)),
    "solar-open2.chunk": (512, 8, 40, 320, (4096, 1280)),
    "lfm2-8b-a1b.decode": (96, 4, 32, 32, (2048, 1792)),
    "lfm2-8b-a1b.chunk": (512, 4, 32, 32, (2048, 1792)),
}
LAYERS, LAYER, RUNS = 2, 1, 12
ROW_TILES = (16, 32, 64, 128, 256)
PANELS_MB = (1, 2, 4, 8, 12, 16)


def routing(key, tokens, k, held, width):
    """(rows sorted by expert's order, sizes (held,)) of a top-k over
    random scores: assignments to experts not held sort behind."""
    _, experts = lax.top_k(jax.random.normal(key, (tokens, width)), k)
    experts = jnp.where(experts.reshape(-1) < held, experts.reshape(-1), held)
    return (jnp.argsort(experts) // k,
            jnp.zeros((held,), jnp.int32).at[experts].add(1))


def xla_product(rows, stack, sizes, layer):
    """The step programs' form before the kernel: the stack as layers x
    experts groups, all empty but ``layer``'s."""
    groups = lax.dynamic_update_slice(
        jnp.zeros((stack.shape[0] * stack.shape[1],), jnp.int32), sizes,
        (layer * stack.shape[1],))
    return lax.ragged_dot(rows, stack.reshape(-1, *stack.shape[-2:]), groups,
                          preferred_element_type=jnp.float32)


def candidates(m, rows_an_expert, k, n):
    """{label: (tm, tk, tn)}: the rule's tiling, every row tile beside
    its panel, every panel beside its row tile, whole columns, and the
    whole expert as one block."""
    rule = gm.tiling(rows_an_expert, k, n)
    found = {"rule": rule}
    for tm in ROW_TILES:
        if tm <= max(m, gm.MIN_ROWS):
            found[f"tm{tm}"] = (tm,) + rule[1:]
    for mb in PANELS_MB:
        found[f"panel{mb}MB"] = (rule[0],) + gm.cut(k, n, 2, mb << 20)
    # whole columns instead of whole rows: tk = k, tn cut
    tn = n
    while k * tn * 2 > gm.PANEL_BYTES and tn % 256 == 0:
        tn //= 2
    found["columns"] = (rule[0], k, tn)
    for tm in (32, 64, 128):    # the whole expert one block
        found[f"whole{tm}"] = (tm, k, n)
    seen, unique = set(), {}
    for label, tiling in found.items():
        if tiling not in seen:
            unique[label] = tiling
            seen.add(tiling)
    return unique


def measure(shape, product, k, n, x, sizes, key):
    """One product of ``shape`` — ``x`` (m, k) against experts of (k,
    n) — timed under every candidate tiling and XLA's kernel."""
    tokens, per_token, held, width, _ = SHAPES[shape]
    m = x.shape[0]
    stack = jax.random.normal(key, (LAYERS, held, k, n), jnp.bfloat16) * (
        k ** -0.5)
    tilings = candidates(m, tokens * per_token / width, k, n)
    programs = {"xla": jax.jit(xla_product)}
    for label, tiling in tilings.items():
        programs[label] = jax.jit(functools.partial(
            gm.grouped_matmul, tiling=tiling,
            interpret=jax.default_backend() != "tpu"))
    real = int(sizes.sum())
    want = np.asarray(programs["xla"](x, stack, sizes, LAYER))[:real]
    hit, rule_visits = int((sizes > 0).sum()), int(gm.visits(
        sizes, tilings["rule"][0]))
    line = {"shape": shape, "product": product, "rows": m, "k": k, "n": n,
            "experts_hit": hit, "experts_held": held,
            "rows_held": real, "visits": rule_visits,
            "crossings": rule_visits - hit,
            "device": jax.devices()[0].device_kind,
            "tilings": {}, "worst_rel_diff": {}, "bits": {}}
    if hasattr(gm, "fetches_ahead"):
        line["fetches_ahead"] = int(gm.fetches_ahead(
            sizes, tilings["rule"][0], k // tilings["rule"][1]))
    ok = True
    for label, program in programs.items():
        got = np.asarray(program(x, stack, sizes, LAYER))[:real]
        diff = float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-9))
        line["worst_rel_diff"][label] = diff
        line["bits"][label] = hashlib.sha256(got.tobytes()).hexdigest()[:12]
        ok = ok and diff < 2.0 ** -8
    with tempfile.TemporaryDirectory() as directory:
        jax.profiler.start_trace(directory)
        for program in programs.values():
            for _ in range(RUNS):
                out = program(x, stack, sizes, LAYER)
            jax.block_until_ready(out)
        jax.profiler.stop_trace()
        ran = [ms for _, ms in device_ms(directory)]
    if ran:
        assert len(ran) == RUNS * len(programs), len(ran)
        hit_bytes = hit * k * n * 2
        for i, label in enumerate(programs):
            ms = statistics.median(ran[i * RUNS:][:RUNS])
            line["tilings"][label] = {
                "tiling": tilings.get(label),
                "device_ms": round(ms, 4),
                "gb_s": round(hit_bytes / ms / 1e6, 1)}
    print(json.dumps(line), flush=True)
    return ok


def main(shapes):
    print(jax.devices(), flush=True)
    ok = True
    for shape in shapes or SHAPES:
        tokens, per_token, held, width, (dim, wide) = SHAPES[shape]
        key = jax.random.PRNGKey(37)
        order, sizes = routing(key, tokens, per_token, held, width)
        hidden = jax.random.normal(key, (tokens, dim), jnp.bfloat16)
        gated = jax.random.normal(key, (tokens * per_token, wide),
                                  jnp.bfloat16)
        ok &= measure(shape, "gate_or_up", dim, wide, hidden[order], sizes,
                      jax.random.PRNGKey(1))
        ok &= measure(shape, "down", wide, dim, gated, sizes,
                      jax.random.PRNGKey(2))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
