"""How `command-a-plus`'s parity tolerance was set and what it refuses.

    python -m benchmarks.command_a_plus_parity --seeds 1,2,3 \\
        [--fp8 | --window-off] [--out chiprun_out/parity.json]

On the chip, one process, at the published widths of
``chipbench/configs/command-a-plus.json`` and the probe size of
``chipbench/traffic/docqa-mixed.json`` (5,120 tokens through the
engine's own 512-token chunks, then 8 decode steps): per seed, weights
drawn from the seed and the benchmark's own probe
(``chipbench.replica.ProbeLLMServer.probe_logits``) against the plain
float32 reference.  ``--fp8``: instead, no engine at all — the
reference with its weights rounded to ``float8_e4m3fn`` against itself
in float32 on the probe's tokens (must read above the tolerance).
``--window-off``: instead, the PROGRAM
with the window mask left off — the same weights under a config whose
window no context reaches, 2 slots — against the true reference (must
read far above the tolerance: the mistake the long probe exists for).
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import time


def _rel_l2(got, want, jnp):
    return [float(e) for e in jnp.sqrt(jnp.sum((got - want) ** 2, -1))
            / jnp.sqrt(jnp.sum(want ** 2, -1))]


def _reference(spec, params, tokens, first, jax, jnp, cast=None, **other):
    """The plain reference's logits from position ``first`` on; ``cast``
    rounds every matrix it reads first; ``other``: static keywords of
    the reference's ``block`` that a control sets (another model on the
    same weights)."""
    import importlib

    from chipbench.spec import resolve

    ref = importlib.import_module(spec["reference"]["module"])
    embed, layer, n, norm_f, head = resolve(spec["reference"]["params"])(
        params)
    if cast is not None:
        plain = layer

        def layer(i):
            return {name: leaf if leaf.ndim < 2 else cast(leaf)
                    for name, leaf in plain(i).items()}

        embed, head = cast(embed), cast(head)
    block = jax.jit(ref.block, static_argnames=(
        "n_heads", "n_kv_heads", "rope_theta", "norm_eps", *other))
    return ref.forward(embed, (layer, n), norm_f, head, jnp.asarray(tokens),
                       block_fn=block, **ref.dims_of(spec), **other)[first:]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seeds", default="1")
    parser.add_argument("--fp8", action="store_true")
    parser.add_argument("--window-off", action="store_true")
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ant_ray_tpu.models import llama
    from chipbench.replica import ProbeLLMServer
    from chipbench.spec import Cell

    cell = Cell("command-a-plus.docqa")
    spec, traffic = cell.config, cell.traffic
    prompt, steps = (traffic["parity"]["prompt_tokens"],
                     traffic["parity"]["decode_steps"])
    seeds = [int(s) for s in args.seeds.split(",")]
    draw = jax.jit(llama.init_params, static_argnums=0)
    rows = []

    def keep(row):
        rows.append(row)
        print("[parity] " + json.dumps(row), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "w") as f:
                json.dump({"window_off": args.window_off, "fp8": args.fp8,
                           "rows": rows}, f)

    if args.fp8:
        from chipbench.spec import resolve

        config = resolve(spec["model"]["factory"])(spec)
        for seed in seeds:
            params = draw(config, jax.random.PRNGKey(seed))
            tokens = np.random.default_rng([seed, 11]).integers(
                0, config.vocab_size, prompt + steps, dtype=np.int32)
            want = _reference(spec, params, tokens, prompt - 1, jax, jnp)
            low = _reference(
                spec, params, tokens, prompt - 1, jax, jnp,
                cast=lambda w: w.astype(jnp.float8_e4m3fn).astype(
                    jnp.bfloat16))
            err = _rel_l2(low, want, jnp)
            keep({"seed": seed, "fp8_rel_l2": err, "fp8_worst": max(err)})
            del params, want, low
        return 0

    run = copy.deepcopy(spec)
    slots = traffic["slots"]
    if args.window_off:
        run["sliding_window"], slots = 10 ** 6, 2
    t0 = time.perf_counter()
    server = ProbeLLMServer(run, slots=slots, max_seq=traffic["max_seq"],
                            seed=seeds[0], **spec["serve"]["kwargs"])
    server._spec = spec                 # the reference keeps the window
    eng = server.engine
    print(f"[parity] {jax.devices()[0].device_kind}: replica ready in "
          f"{time.perf_counter() - t0:.1f} s, cache "
          f"{ {k: v.shape for k, v in eng.cache.items()} }", flush=True)
    try:
        for i, seed in enumerate(seeds):
            if i:
                eng.params = None
                eng.params = draw(eng.config, jax.random.PRNGKey(seed))
            out = server.probe_logits(seed, prompt, steps)
            keep({"seed": seed, "rel_l2": out["rel_l2"],
                  "worst": max(out["rel_l2"]),
                  "argmax_equal": out["argmax_equal"],
                  "system_s": out["system_s"], "seconds": out["seconds"]})
    finally:
        server.shutdown()
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[parity] peak bytes in use "
          f"{stats.get('peak_bytes_in_use', 0) / 2 ** 30:.2f} GiB")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
