"""On the chip: the residual streams' maps and mixes of ONE sub-layer
alone (``models/llama.py``: ``hc_maps``, ``_hc_read``, ``_residual``), at
the published widths of ``chipbench/configs/xing4.0-29b-a4b.json`` (4
streams of 3,584, bfloat16) and at the two shapes the cell
``xing4.0-29b-a4b.docqa`` runs them at: a prompt chunk's 512 rows and a
decode step's 8.  Each part jitted on its own (the mix from maps made
before) and all of them as one program around a sub-layer that adds
nothing of its own (``y = h``),
device ms a call over ``RUNS`` calls (``XLA Modules`` events of a
profiler trace, median and least), beside the bytes the part must move
(the streams once a pass, ``phi`` once) and what those take at the
chip's HBM bandwidth.  In a step program the parts are fused with their
neighbours (the sub-layer's norm, its last product): a part's cost
there is the cell's trace's; this is the first reading of what the
residual path costs by itself.  Without a TPU nothing is timed and the
width is cut.  One JSON line; through the chip tool, from the root:

    python -m benchmarks.hc_mix
"""

import json
import os
import statistics
import tempfile

import jax
import jax.numpy as jnp

from ant_ray_tpu.models import llama
from benchmarks.sampler_paths import device_ms

HERE = os.path.dirname(os.path.abspath(__file__))
RUNS = 50
ROWS = (512, 8)


def parts(c):
    """name -> (function of (layer, x, the maps made before), passes
    over the streams it needs)."""
    def maps(layer, x, _given):
        return llama.hc_maps(layer["hc_attn_phi"], layer["hc_attn_b"],
                             layer["hc_attn_alpha"], x, c)

    def read(layer, x, _given):
        return llama._hc_read(layer, x, c, "hc_attn")[0]

    def mix(_layer, x, given):
        _, h_post, h_res = given
        return llama._residual(None, x[..., 0, :], c, (x, h_post, h_res))

    def whole(layer, x, _given):
        h, streams = llama._hc_read(layer, x, c, "hc_attn")
        return llama._residual(h, h, c, streams)

    return {"maps": (maps, 1), "maps_and_read": (read, 2),
            "mix_and_write": (mix, 2), "whole": (whole, 3)}


def measure():
    from chipbench.models import xing4

    with open(os.path.join(HERE, os.pardir, "chipbench", "configs",
                           "xing4.0-29b-a4b.json")) as f:
        spec = json.load(f)
    with open(os.path.join(HERE, os.pardir, "chipbench",
                           "peaks.json")) as f:
        peaks = json.load(f)["devices"]["TPU v5 lite"]
    on_chip = jax.default_backend() == "tpu"
    c = xing4.build(spec if on_chip else {**spec, "hidden_size": 256})
    keys = jax.random.split(jax.random.PRNGKey(56), 4)
    n, width = c.hc_mult, 2 * c.hc_mult + c.hc_mult ** 2
    layer = {
        "hc_attn_phi": (jax.random.normal(keys[0], (n * c.dim, width))
                        * (n * c.dim) ** -0.5).astype(c.dtype),
        "hc_attn_b": jax.random.normal(keys[1], (width,)).astype(c.dtype),
        "hc_attn_alpha": jnp.ones((3,), c.dtype)}
    line = {"streams": n, "dim": c.dim, "dtype": str(jnp.dtype(c.dtype)),
            "sinkhorn_passes": c.hc_sinkhorn_iters, "shapes": {}}
    for rows in ROWS:
        x = jax.random.normal(keys[2], (rows, n, c.dim)).astype(c.dtype)
        a_pass = rows * n * c.dim * jnp.dtype(c.dtype).itemsize
        shape = line["shapes"][str(rows)] = {"bytes_a_pass": a_pass}
        given = None
        for name, (fn, passes) in parts(c).items():
            run = jax.jit(fn)
            out = run(layer, x, given)
            given = given or out
            entry = shape[name] = {
                "finite": bool(all(jnp.isfinite(leaf).all()
                                   for leaf in jax.tree.leaves(out))),
                "passes": passes,
                "least_ms_by_bytes": 1e3 * (
                    passes * a_pass + layer["hc_attn_phi"].nbytes)
                / peaks["hbm_bytes_per_s"]}
            if not on_chip:
                continue
            with tempfile.TemporaryDirectory() as directory:
                jax.profiler.start_trace(directory)
                for _ in range(RUNS):
                    jax.block_until_ready(run(layer, x, given))
                jax.profiler.stop_trace()
                ms = [t for _, t in device_ms(directory)]
            entry.update(calls=len(ms), ms_a_call=statistics.median(ms),
                         ms_least=min(ms), ms_most=max(ms))
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    print(jax.devices())
    measure()
