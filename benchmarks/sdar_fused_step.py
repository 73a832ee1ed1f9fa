"""What `sdar-30b-a3b`'s ONE step program costs ALONE, by how many
slots have a block closing.  On the chip, one process, the engine as the
cell's replica builds it (published widths, 48 slots x 4,096, weights
from ``--seed``), no request path:

    python -m benchmarks.sdar_fused_step [--seed 1] [--steps 40] \\
        [--closing 0,12,48] [--out chiprun_out/fused_step.json]

Since PR 55 a block's store pass rides the next block's first step: the
program carries ``2 x block_length`` rows a slot — the blocks in flight
and, behind them, the blocks closing, live where a slot has one — and a
chunk's 64: 448 rows where it had 256.  The schedule pays while a step
stays under 5 / 4 of the old one (21.8 ms against 17.4).  Per case
(``--closing``: slots with a block closing, of 48 active at contexts
drawn like the cell's): ``--steps`` steps of ``jit__block_decode`` and
``jit__sample_block`` under the profiler, the device's mean ms of each,
and the operations that took the most (``chipbench.trace_reduce``).
The slabs hold zeros (a time does not hang on their values), the tokens
are random, every expert is hit.  On the CPU it runs ``sdar-tiny``
(``--tiny``) and times nothing.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--steps", type=int, default=40)
    parser.add_argument("--closing", default="0,12,48")
    parser.add_argument("--out", default=None)
    parser.add_argument("--tiny", action="store_true",
                        help="the preset sdar-tiny at a small size: a dry "
                        "run of this script on the CPU, no reading")
    args = parser.parse_args(argv)

    import jax
    import numpy as np

    from ant_ray_tpu.llm.engine import LLMEngine
    from chipbench import trace_reduce
    from chipbench.spec import Cell, resolve

    cell = Cell("sdar-30b-a3b.reason")
    spec, traffic = cell.config, cell.traffic
    if args.tiny:
        eng = LLMEngine("sdar-tiny", slots=6, max_seq=256, seed=args.seed,
                        prefill_chunk_tokens=16)
    else:
        eng = LLMEngine(resolve(spec["model"]["factory"])(spec),
                        slots=traffic["slots"], max_seq=traffic["max_seq"],
                        seed=args.seed, **spec["serve"]["kwargs"])
    jnp, slots, size = eng._jnp, eng.slots, eng._block
    rng = np.random.default_rng([args.seed, 5])
    vocab = eng.config.vocab_size
    # a caller's context half-way through its answer, as the cell draws
    # its prompts and answers
    p, o = traffic["prompt_tokens"], traffic["output_tokens"]
    contexts = np.clip(
        rng.lognormal(np.log(p["median"]), p["sigma"], slots), p["min"],
        p["max"]) + 0.5 * np.clip(
        rng.lognormal(np.log(o["median"]), o["sigma"], slots), o["min"],
        o["max"])
    contexts = np.minimum(contexts.astype(np.int32) // size * size,
                          eng.max_seq - 3 * size)
    active = jnp.ones((slots,), bool)
    temps = jnp.ones((slots,), jnp.float32)
    none, all_ = jnp.zeros((slots,), jnp.int32), jnp.ones((slots,),
                                                          jnp.float32)
    rows, directory = [], os.path.join("chiprun_out", "_fused_step_trace")

    def step(closing):
        """One block step and its sampler; the lengths go back where
        they were, so that every step of a case does the same work."""
        blocks = jnp.asarray(rng.integers(0, vocab, (slots, size)), jnp.int32)
        closed = jnp.asarray(rng.integers(0, vocab, (slots, size)), jnp.int32)
        masked = jnp.asarray(rng.random((slots, size)) < 0.5) \
            | (jnp.arange(size) == 0)          # a mask left: no store alone
        logits, _, cache = eng._mixed_step_jit(
            eng.params, eng.cache, blocks, masked, closed, closing, active,
            *eng._no_chunk)
        eng.cache = {**cache, "length": jnp.asarray(contexts)}
        out = eng._sample_jit(
            logits, eng._keys, active, temps, none, all_,
            eng.cache.get("routing"), blocks, masked, closed, closing,
            eng._block_counts, cache["length"])
        eng._keys = out[1]
        return out[0]

    for n in [int(x) for x in args.closing.split(",")]:
        closing = np.zeros((slots,), bool)
        closing[rng.permutation(slots)[:min(n, slots)]] = True
        closing = jnp.asarray(closing)
        eng.cache = {**eng.cache, "length": jnp.asarray(contexts)}
        for _ in range(3):
            jax.block_until_ready(step(closing))            # compiled, warm
        shutil.rmtree(directory, ignore_errors=True)
        trace_reduce.start_trace(jax, directory)
        for _ in range(args.steps):
            read = step(closing)
        jax.block_until_ready(read)
        jax.profiler.stop_trace()
        trace = trace_reduce.reduce_dir(directory)
        row = {"closing": int(closing.sum()), "steps": args.steps,
               "contexts_mean": float(contexts.mean()),
               "device": jax.devices()[0].device_kind}
        for name, pattern in (("block_decode_ms", r"_block_decode$"),
                              ("sample_block_ms", r"_sample_block$")):
            found = trace_reduce.program_time(trace, pattern)
            row[name] = 1000.0 * found[1] / found[0] if found else None
        if trace.get("devices"):
            row["ops_ms_a_step"] = [
                [name, round(1000.0 * t / args.steps, 4), calls]
                for name, t, calls in trace["devices"][0]["ops"][:14]]
        rows.append(row)
        print("[fused_step] " + json.dumps(row), flush=True)
    shutil.rmtree(directory, ignore_errors=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"seed": args.seed, "rows": rows}, f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
