"""How `granite-4.0-h-small`'s parity tolerance was set and what it
refuses.

    python -m benchmarks.granite_hybrid_parity --seeds 1,2,3 \\
        [--controls 8] [--depth 2] [--skip fp8,...] \\
        [--out chiprun_out/granite_parity.json]

On the chip, one process, at the published widths of
``chipbench/configs/granite-4.0-h-small.json`` and at BOTH probe
geometries of the cell (``chipbench/replica_median_pair.py``: the
traffic file's whole chunks, and a prompt that ends a few tokens behind
a chunk boundary; each through the engine's own 512-token chunks, then
8 decode steps, as ``chipbench.replica.ProbeLLMServer`` does it): per
seed, weights drawn from the seed, and readings of the logits' relative
L2 against the plain float32 reference at the probe's positions, each
as the positions' median (what the replica compares) and worst:

* ``program`` — the engine's programs as they are: must read inside
  the tolerance;
* ``no_carry`` — the same programs with the slot's recurrent state and
  convolution tails emptied before every chunk but the first: the state
  not handed from chunk to chunk;
* ``no_decay`` — the same programs on weights whose ``a_log`` is -inf,
  so that every rate is 0 and every decay exp(0) = 1, against the TRUE
  reference;
* ``fp8`` — no engine: the reference with its matrices rounded to
  ``float8_e4m3fn``, the nearest precision below the stated one,
  against itself in float32;
* for the first ``--controls`` seeds, ``softmax_scale`` and
  ``no_residual_multiplier`` — the programs as they are against a
  reference that multiplies the softmax layer's scores by head_dim^-1/2
  in place of ``attention_multiplier``, and one without the
  ``residual_multiplier``: a multiplier that one side does not read;
  with ``--short N`` the same two and ``program`` at a prompt of N
  tokens besides (one padded chunk: behind a 2k-token context the
  softmax layer's output is an average over thousands of values and a
  wrong scale moves the logits by a few hundredths; behind a few dozen
  it is most of that layer).

For the first ``--depth`` seeds, at the first geometry, WHERE the
program's error arises: ``after_<n>``, program against reference on
weights whose layers from the n-th on add nothing (their norms' weights
zero: the block is the identity), and, in a last pass, ``no_routed``:
all layers with the routed experts adding nothing (no expert pick can
flip).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import time

from benchmarks.command_a_plus_parity import _reference
from benchmarks.solar_open2_parity import _through_engine

DEPTHS = (1, 3, 5, 6, 8)


def _adding_nothing(params, llama, kinds, depth):
    """``params`` with the layers from place ``depth`` of the period on
    made the identity: their two norms' weights zero, so that a block
    reads zeros and adds zeros."""
    out = dict(params)
    for name in ("layers", llama.SSM):
        mine = [llama.RECURRENT.get(kind, "layers") == name
                for kind in kinds]
        first = sum(mine[:depth])
        out[name] = {**params[name], **{
            norm: params[name][norm].at[first:].set(0)
            for norm in ("ln_attn", "ln_mlp")}}
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seeds", default="1")
    parser.add_argument("--controls", type=int, default=0,
                        help="seeds that also get the multipliers' controls")
    parser.add_argument("--depth", type=int, default=0,
                        help="seeds that also get the readings by depth")
    parser.add_argument("--short", type=int, default=0,
                        help="a third prompt length for those controls")
    parser.add_argument("--skip", default="",
                        help="readings to leave out, comma-separated")
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ant_ray_tpu.llm import LLMEngine
    from ant_ray_tpu.models import llama
    from chipbench.spec import Cell, resolve

    cell = Cell("granite-4.0-h-small.sessions")
    spec, traffic = cell.config, cell.traffic
    steps = traffic["parity"]["decode_steps"]
    chunk = spec["serve"]["kwargs"]["prefill_chunk_tokens"]
    whole = traffic["parity"]["prompt_tokens"]
    prompts = (whole, (whole - 1) // chunk * chunk + spec["serve"][
        "probe_short_last_chunk"]["tokens_behind_boundary"])
    seeds = [int(s) for s in args.seeds.split(",")]
    skip = set(filter(None, args.skip.split(",")))
    config = resolve(spec["model"]["factory"])(spec)
    other = {
        "softmax_scale": {**spec, "attention_multiplier":
                          (spec["hidden_size"]
                           // spec["num_attention_heads"]) ** -0.5},
        "no_residual_multiplier": {**spec, "residual_multiplier": 1.0}}
    draw = jax.jit(llama.init_params, static_argnums=0)
    t0 = time.perf_counter()
    eng = LLMEngine(config, slots=traffic["slots"],
                    max_seq=traffic["max_seq"], seed=seeds[0],
                    **spec["serve"]["kwargs"])
    jax.block_until_ready(eng.params)
    print(f"[parity] {jax.devices()[0].device_kind}: engine ready in "
          f"{time.perf_counter() - t0:.1f} s, prompts {prompts} + {steps}, "
          f"cache { {k: v.shape for k, v in eng.cache.items()} }",
          flush=True)
    rows = []

    def rel_l2(got, want):
        err = [float(e) for e in jnp.sqrt(jnp.sum((got - want) ** 2, -1))
               / jnp.sqrt(jnp.sum(want ** 2, -1))]
        return {"median": statistics.median(err), "worst": max(err),
                "by_position": err}

    def keep(row):
        rows.append(row)
        print("[parity] " + json.dumps(
            {k: ({"median": v["median"], "worst": v["worst"]}
                 if isinstance(v, dict) and "worst" in v else v)
             for k, v in row.items()}),
            flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "w") as f:
                json.dump({"rows": rows}, f)

    def weights_of(i, seed):
        if i or eng.params is None:
            eng.params = None
            eng.params = draw(config, jax.random.PRNGKey(seed))
        return eng.params

    def tokens_of(seed, prompt):
        return np.random.default_rng([seed, 11]).integers(
            0, config.vocab_size, prompt + steps, dtype=np.int32)

    def through(tokens, prompt, params=None, **how):
        mine = eng.params
        if params is not None:
            eng.params = params
        try:
            return _through_engine(eng, tokens, prompt, steps, **how)
        finally:
            eng.params = mine

    def reference(tokens, prompt, params, spec=spec, **how):
        return _reference(spec, params, tokens, prompt - 1, jax, jnp, **how)

    for i, seed in enumerate(seeds):
        params = weights_of(i, seed)
        for prompt in prompts:
            t1 = time.perf_counter()
            tokens = tokens_of(seed, prompt)
            want = reference(tokens, prompt, params)
            got = through(tokens, prompt)
            row = {"seed": seed, "prompt": prompt,
                   "program": rel_l2(got, want),
                   "argmax_equal": int(jnp.sum(
                       jnp.argmax(got, -1) == jnp.argmax(want, -1)))}
            if "no_carry" not in skip:
                row["no_carry"] = rel_l2(
                    through(tokens, prompt, carry=False), want)
            if "no_decay" not in skip:
                ssm = params[llama.SSM]
                row["no_decay"] = rel_l2(through(tokens, prompt, {
                    **params, llama.SSM: {**ssm, "a_log": jnp.full_like(
                        ssm["a_log"], -jnp.inf)}}), want)
                del ssm
            if "fp8" not in skip:
                row["fp8"] = rel_l2(reference(
                    tokens, prompt, params,
                    cast=lambda w: w.astype(jnp.float8_e4m3fn).astype(
                        jnp.bfloat16)), want)
            if i < args.controls:
                for name, changed in other.items():
                    row[name] = rel_l2(got, reference(
                        tokens, prompt, params, spec=changed))
            if i < args.depth and prompt == prompts[0]:
                for depth in DEPTHS:
                    less = _adding_nothing(params, llama, config.kinds,
                                           depth)
                    row[f"after_{depth}"] = rel_l2(
                        through(tokens, prompt, less),
                        reference(tokens, prompt, less))
                    del less
            row["seconds"] = time.perf_counter() - t1
            keep(row)
            del want, got
        if i < args.controls and args.short:
            tokens = tokens_of(seed, args.short)
            got = through(tokens, args.short)
            keep({"seed": seed, "prompt": args.short,
                  "program": rel_l2(got, reference(
                      tokens, args.short, params)),
                  **{name: rel_l2(got, reference(
                      tokens, args.short, params, spec=changed))
                     for name, changed in other.items()}})
            del got
        del params

    # last: the expert matrices are swapped in place, nothing is kept
    for seed in seeds[:args.depth]:
        t1 = time.perf_counter()
        less = dict(weights_of(1, seed))
        eng.params = None
        for name in ("layers", llama.SSM):
            stack = dict(less[name])
            w_down = stack.pop("w_down")
            shape, dtype = w_down.shape, w_down.dtype
            less[name] = stack
            del w_down              # 2 GB freed before as many zeros
            stack["w_down"] = jnp.zeros(shape, dtype)
        tokens = tokens_of(seed, prompts[0])
        keep({"seed": seed, "prompt": prompts[0], "no_routed": rel_l2(
            through(tokens, prompts[0], less),
            reference(tokens, prompts[0], less)),
            "seconds": time.perf_counter() - t1})
        del less, stack
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[parity] peak bytes in use "
          f"{stats.get('peak_bytes_in_use', 0) / 2 ** 30:.2f} GiB")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
