"""How `ouro-2.6b`'s parity tolerance was set and what it refuses.

    python -m benchmarks.ouro_parity --seeds 1,2,3 [--controls 3] \\
        [--ladder 3] [--slots 2] [--out chiprun_out/ouro_parity.json]

On the chip, one process, at the published widths of
``chipbench/configs/ouro-2.6b.json`` and at the cell's probe geometry
(the traffic file's ``parity``: 256 prompt tokens through the engine's
own 64-token chunks, then 8 decode steps, as
``chipbench.replica.ProbeLLMServer`` does it): per seed, weights drawn
from the seed, and readings of the logits' relative L2 against the
plain float32 reference at the probe's 9 positions, each as the
positions' worst (what the replica compares) and median:

* ``program`` — the engine's programs as they are: must read inside
  the tolerance;
* ``fp8`` — no engine: the reference with its matrices rounded to
  ``float8_e4m3fn``, the nearest precision below the stated one,
  against itself in float32;
* for the first ``--controls`` seeds: ``pass0_slabs`` — the step
  programs with the (pass, layer) index dropped, every pass writing and
  reading pass 0's slab layers (``llama._pass_first`` patched) — and the
  programs as they are against a reference that computes ANOTHER model
  on the same weights: ``no_norm_between`` (the final norm behind the
  last pass only), ``pre_norm`` (no norm on a sub-layer's output),
  ``three_passes`` (``total_ut_steps`` 3);
* for the first ``--ladder`` seeds, WHERE the error arises:
  ``passes_<u>``, the step programs of the same weights with ``loops``
  ``u`` (1, 2, 3) against the reference's logits behind pass ``u`` —
  the state 48, 96 and 144 blocks deep — and ``f32_exact``, the
  step programs traced under
  ``jax.default_matmul_precision("highest")`` (the decode rows through
  the XLA walk there).

Every control must read OUTSIDE the tolerance.  ``--slots`` makes the
engine smaller than the cell's (the programs' mathematics does not
depend on the slot count; the side programs' caches and the float8
control's copies of the embedding and the head need the room).
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import importlib
import json
import os
import statistics
import time

from benchmarks.solar_open2_parity import _through_engine

CELL = "ouro-2.6b.rollout"


def _side_programs(llama, jax, config):
    """The chunk and the decode program of ``config`` jitted as the
    engine jits them, apart from any engine."""
    def chunk(params, cache, tokens, slot, start, length):
        return llama.prefill_chunk_into_cache(
            params, tokens, cache, slot, start, length, config)

    def decode(params, cache, last, active):
        return llama.decode_step(params, last, cache, config, active=active)

    return (jax.jit(chunk, donate_argnums=(1,)),
            jax.jit(decode, donate_argnums=(1,)))


def _through(programs, params, cache, tokens, prompt, steps, width, jnp):
    """``_through_engine``'s path through ``programs`` and a one-slot
    ``cache`` of their own."""
    import numpy as np

    chunk, decode = programs
    for start in range(0, prompt, width):
        part = tokens[start:min(start + width, prompt)]
        buf = np.zeros((width,), np.int32)
        buf[:len(part)] = part
        logits, cache = chunk(params, cache, jnp.asarray(buf), 0, start,
                              len(part))
    got = [logits]
    for j in range(steps):
        logits, cache = decode(params, cache, jnp.asarray(
            tokens[prompt + j:prompt + j + 1]), jnp.ones((1,), bool))
        got.append(logits[0])
    return jnp.stack(got)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seeds", default="1")
    parser.add_argument("--controls", type=int, default=0,
                        help="seeds that also get the controls")
    parser.add_argument("--ladder", type=int, default=0,
                        help="seeds that also get the readings by pass")
    parser.add_argument("--slots", type=int, default=0,
                        help="slots of the engine (0: the cell's)")
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ant_ray_tpu.llm import LLMEngine
    from ant_ray_tpu.models import llama
    from chipbench.spec import Cell, resolve

    cell = Cell(CELL)
    spec, traffic = cell.config, cell.traffic
    steps = traffic["parity"]["decode_steps"]
    prompt = traffic["parity"]["prompt_tokens"]
    first = prompt - 1
    seeds = [int(s) for s in args.seeds.split(",")]
    config = resolve(spec["model"]["factory"])(spec)
    ref = importlib.import_module(spec["reference"]["module"])
    to_reference = resolve(spec["reference"]["params"])
    dims = ref.dims_of(spec)
    threshold = dims.pop("early_exit_threshold")
    block = jax.jit(ref.block, static_argnames=(
        "n_heads", "n_kv_heads", "rope_theta", "norm_eps", "sandwich"))
    draw = jax.jit(llama.init_params, static_argnums=0)
    t0 = time.perf_counter()
    eng = LLMEngine(config, slots=args.slots or traffic["slots"],
                    max_seq=traffic["max_seq"], seed=seeds[0])
    jax.block_until_ready(eng.params)
    width = eng._chunk_tokens
    print(f"[parity] {jax.devices()[0].device_kind}: engine ready in "
          f"{time.perf_counter() - t0:.1f} s, prompt {prompt} + {steps} in "
          f"chunks of {width}, cache "
          f"{ {k: v.shape for k, v in eng.cache.items()} }", flush=True)
    rows = []

    def rel_l2(got, want):
        err = [float(e) for e in jnp.sqrt(jnp.sum((got - want) ** 2, -1))
               / jnp.sqrt(jnp.sum(want ** 2, -1))]
        return {"median": statistics.median(err), "worst": max(err),
                "by_position": err}

    def keep(row):
        rows.append(row)
        print("[parity] " + json.dumps(
            {k: ({"median": round(v["median"], 4),
                  "worst": round(v["worst"], 4)}
                 if isinstance(v, dict) and "worst" in v else v)
             for k, v in row.items()}), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "w") as f:
                json.dump({"rows": rows}, f)

    def reference(params, tokens, cast=None, block_fn=block, **changed):
        """-> (each pass's logits from position ``first`` on, its gates
        there)."""
        embed, layer, n, closing, head = to_reference(params)
        if cast is not None:
            plain = layer

            def layer(i):
                return {name: leaf if leaf.ndim < 2 else cast(leaf)
                        for name, leaf in plain(i).items()}

            embed, head = cast(embed), cast(head)
            closing = {**closing, "gate_w": cast(closing["gate_w"])}
        states, gates = ref.passes(
            embed, (layer, n), closing, jnp.asarray(tokens),
            block_fn=block_fn, **{**dims, **changed})
        return ([ref.head_of(head, x[first:]) for x in states],
                [g[first:] for g in gates])

    def side(cfg, patched=None):
        """The chunk and the decode program of ``cfg`` for a one-slot
        cache, compiled HERE: under ``patched`` in ``_pass_first``'s
        place where given."""
        held = llama._pass_first
        if patched is not None:
            llama._pass_first = patched
        try:
            chunk, decode = _side_programs(llama, jax, cfg)
            cache = jax.eval_shape(lambda: llama.init_kv_cache(
                cfg, 1, traffic["max_seq"], width))
            params = jax.eval_shape(lambda: eng.params)
            if not cfg.exit_gate:
                params.pop("exit_gate")
            return (chunk.lower(params, cache,
                                jnp.zeros((width,), jnp.int32), 0, 0,
                                width).compile(),
                    decode.lower(params, cache, jnp.zeros((1,), jnp.int32),
                                 jnp.ones((1,), bool)).compile())
        finally:
            llama._pass_first = held

    sides = {}
    for i, seed in enumerate(seeds):
        if i:
            eng.params = None
            eng.params = draw(config, jax.random.PRNGKey(seed))
        params = eng.params
        t1 = time.perf_counter()
        tokens = np.random.default_rng([seed, 11]).integers(
            0, config.vocab_size, prompt + steps, dtype=np.int32)
        by_pass, gates = reference(params, tokens)
        want = by_pass[-1]
        chosen = ref.exit_pass(gates, threshold)
        got = _through_engine(eng, tokens, prompt, steps)
        expected = jnp.sum(jnp.arange(1, len(gates) + 1)[:, None]
                           * ref.exit_distribution(gates), axis=0)
        row = {"seed": seed, "program": rel_l2(got, want),
               "argmax_equal": int(jnp.sum(
                   jnp.argmax(got, -1) == jnp.argmax(want, -1))),
               "exit_pass_last": bool(jnp.all(chosen == len(gates) - 1)),
               "exit_pass_mean": float(jnp.mean(expected)),
               "logit_rms": float(jnp.sqrt(jnp.mean(want ** 2)))}
        low, _ = reference(params, tokens, cast=lambda w: w.astype(
            jnp.float8_e4m3fn).astype(jnp.bfloat16))
        row["fp8"] = rel_l2(low[-1], want)
        del low
        if i < args.controls:
            if "pass0" not in sides:
                sides["pass0"] = side(config, lambda c, u: 0 * u)
            row["pass0_slabs"] = rel_l2(_through(
                sides["pass0"], params, llama.init_kv_cache(
                    config, 1, traffic["max_seq"], width),
                tokens, prompt, steps, width, jnp), want)
            row["no_norm_between"] = rel_l2(got, reference(
                params, tokens, norm_between=False)[0][-1])
            row["pre_norm"] = rel_l2(got, reference(
                params, tokens, block_fn=functools.partial(
                    block, sandwich=False))[0][-1])
            row["three_passes"] = rel_l2(got, by_pass[-2])
        if i < args.ladder:
            # (the decode rows through the XLA walk: the kernel's
            # bfloat16 products do not compile at that precision)
            kernel, llama._decode_kernel = llama._decode_kernel, \
                lambda *_: False
            try:
                with jax.default_matmul_precision("highest"):
                    exact = _side_programs(llama, jax, config)
                    row["f32_exact"] = rel_l2(_through(
                        exact, params, llama.init_kv_cache(
                            config, 1, traffic["max_seq"], width),
                        tokens, prompt, steps, width, jnp), want)
            finally:
                llama._decode_kernel = kernel
            del exact
            for u in range(1, config.loops):
                cfg = dataclasses.replace(config, loops=u, exit_gate=u > 1)
                if u not in sides:
                    sides[u] = side(cfg)
                mine = params if u > 1 else {
                    k: v for k, v in params.items() if k != "exit_gate"}
                row[f"passes_{u}"] = rel_l2(_through(
                    sides[u], mine, llama.init_kv_cache(
                        cfg, 1, traffic["max_seq"], width),
                    tokens, prompt, steps, width, jnp), by_pass[u - 1])
        row["seconds"] = time.perf_counter() - t1
        keep(row)
        del want, got, by_pass, params
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[parity] peak bytes in use "
          f"{stats.get('peak_bytes_in_use', 0) / 2 ** 30:.2f} GiB")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
