"""On the chip: a decode step's attention over the slabs alone, the XLA
walk of ``llama._attend_slab`` beside ``ops/pallas/decode_attention.py``,
at the shapes and contexts the benchmark's serving cells decode at: one
program a path that attends in ONE layer of the cell's slabs (a traced
layer index, as the step programs have), run on each layer in turn,
device ms a layer,
the GB/s over the LIVE rows' own whole blocks of keys and values — of
latent slabs the latents and rotary keys, the layer then with
``w_kvb``'s two by-head products around either path; of a window
layer's RINGS (PR 59: a shape with a window, its ``max_seq`` the ring's
rows) the ring blocks a row has written, all of them once it has wrapped
— (what
the kernel reads; the walk reads every slot as far as the longest), and
the largest difference between the two on an active row.  Times are the
device's (``XLA Modules`` events of a profiler trace, median of the
executions); without a TPU the kernel runs in the interpreter at a cut
size and nothing is timed.  One JSON line a shape; through the chip
tool, from the root:

    python -m benchmarks.decode_walk [shape ...]
"""

import json
import statistics
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from ant_ray_tpu.models import llama
from ant_ray_tpu.ops.pallas import decode_attention
from benchmarks.sampler_paths import device_ms

# shape: (softmax layers with full slabs, slots, max_seq, heads, KV
#         heads — 0: latent slabs, ranks 1536 / 512, 128 + 64 / 128 a
#         head, both by-head products of the absorbed form beside either
#         path —, the active rows' contexts; the other slots idle[, the
#         window: the slabs are then that many window layers' RINGS of
#         ``max_seq`` rows — the window and a chunk of 512])
SHAPES = {
    "olmo-hybrid-7b.longdoc": (4, 8, 12288, 30, 30,
                               (11000, 9500, 7700, 6000, 4100, 1500)),
    "command-a-plus.docqa": (1, 16, 32768, 128, 8, (26000, 9000, 2500)),
    "solar-open2.digest": (1, 24, 32768, 64, 8,
                           tuple(range(8000, 31000, 1000)) + (25000,)),
    "granite-4.0-h-small.sessions": (1, 48, 6144, 32, 8,
                                     tuple(range(700, 5400, 100))),
    "mistral-7b.decode": (16, 16, 3072, 32, 8, tuple(range(300, 940, 40))),
    "olmoe-1b-7b.rollout": (8, 16, 3072, 16, 16, tuple(range(300, 940, 40))),
    "internlm2-1.8b.chat": (24, 12, 2048, 16, 8, tuple(range(150, 450, 25))),
    "ax-k1.reason": (7, 48, 4096, 64, 0, tuple(
        512 + 2048 * (7 * n % 48) // 47 for n in range(48))),
    "xing4.0-29b-a4b.docqa": (7, 16, 16384, 32, 0,
                              (1500, 4000, 6000, 11000)),
    "command-a-plus.docqa.rings": (3, 16, 4608, 128, 8,
                                   (26000, 9000, 6000), 4096),
}
RUNS = 8


def measure(name):
    layers, slots, max_seq, heads, kv_heads, contexts, *window = SHAPES[name]
    window = window[0] if window else 0
    on_chip = jax.default_backend() == "tpu"
    if not on_chip:                              # a rehearsal's size
        layers, max_seq = min(layers, 2), 1024
        contexts = tuple((n if window else min(n, max_seq - 1)) // 4 + 1
                         for n in contexts)      # a ring's have wrapped
        window = min(window, max_seq - 256)
    latent = dict(q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128) if not kv_heads else {}
    # (of a model with a window: ``layers`` window layers to a full one)
    rings = dict(window=window,
                 window_pattern=(True,) * layers + (False,)) if window else {}
    c = llama.LlamaConfig(vocab_size=256, dim=heads * 128,
                          n_layers=layers + bool(window), n_heads=heads,
                          n_kv_heads=kv_heads or heads, head_width=128,
                          mlp_dim=256, max_seq=max_seq, **latent, **rings)
    keys = jax.random.split(jax.random.PRNGKey(47), 4)
    ks, vs = (jax.jit(lambda k, position=position: jax.random.normal(
        k, (layers, slots, max_seq) + position, jnp.float32).astype(
            c.dtype))(k)
        for k, position in zip(keys[:2], llama.kv_slabs(c).values()))
    xq = jax.random.normal(keys[2], (slots, heads, c.head_dim),
                           jnp.float32).astype(c.dtype)
    w_kvb = (jax.random.normal(keys[3], (512, heads * 256), jnp.float32)
             / 16).astype(c.dtype) if latent else None
    pos = jnp.asarray(contexts + (77,) * (slots - len(contexts)), jnp.int32)
    active = jnp.arange(slots) < len(contexts)
    blocks = llama._span_blocks(max(contexts) + 1, max_seq)

    def walk(xq, ks, vs, w_kvb, i, pos, active):
        return llama._attend_slab(xq, ks, vs, i, None, pos, blocks, c, w_kvb,
                                  window, pos)

    def kernel(xq, ks, vs, w_kvb, i, pos, active):
        # the step's work list is built once a step: not a layer's cost,
        # but small beside one
        return llama._attend_slab(
            xq, ks, vs, i, None, pos, blocks, c, w_kvb, window, pos,
            visits=decode_attention.work_list(
                pos, active, llama.ATTEND_BLOCK, max_seq))

    paths = {"walk": jax.jit(walk), "kernel": jax.jit(kernel)}
    layer = [jnp.int32(i) for i in range(layers)]
    out = {path: np.asarray(run(xq, ks, vs, w_kvb, layer[-1], pos,
                                active).astype(jnp.float32))
           for path, run in paths.items()}
    live = np.asarray(active)
    line = {"shape": name, "layers": layers, "slots": slots,
            "max_seq": max_seq, "window": window,
            "heads": [heads, kv_heads],
            "flat_kv_heads": c.flat_kv_heads, "active": len(contexts),
            "max_abs_diff_active_rows": float(np.abs(
                out["walk"][live] - out["kernel"][live]).max()),
            "idle_rows_zero": bool((out["kernel"][~live] == 0).all())}
    read = llama.read_positions([n + 1 for n in contexts], max_seq)
    walked = slots * llama.span_positions(max(contexts) + 1, max_seq)
    line["read_pct_of_walk"] = 100.0 * read / walked
    if on_chip:
        for path, run in paths.items():
            with tempfile.TemporaryDirectory() as directory:
                jax.profiler.start_trace(directory)
                for run_no in range(RUNS):
                    run(xq, ks, vs, w_kvb, layer[run_no % layers], pos,
                        active).block_until_ready()
                jax.profiler.stop_trace()
                # the path's own program: no other is in the trace
                ms = statistics.median(t for program, t in device_ms(
                    directory) if path in program)
            line[f"{path}_ms_a_layer"] = ms
            line[f"{path}_live_gb_s"] = read * sum(
                x.shape[-1] * x.shape[-2] if x.ndim == 5 else x.shape[-1]
                for x in (ks, vs)) * 2 / ms / 1e6
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    print(jax.devices())
    for shape in sys.argv[1:] or SHAPES:
        measure(shape)
