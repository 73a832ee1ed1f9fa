"""How `olmo-hybrid-7b`'s parity tolerance was set and what it refuses.

    python -m benchmarks.olmo_hybrid_parity --seeds 1,2,3 \\
        [--controls 3] [--skip fp8,...] \\
        [--out chiprun_out/olmo_parity.json]

On the chip, one process, at the published widths of
``chipbench/configs/olmo-hybrid-7b.json`` and at BOTH probe geometries
of the cell (``chipbench/replica_median_pair.py``: the traffic file's
whole chunks, and a prompt that ends a few tokens behind a chunk
boundary; each through the engine's own 512-token chunks, then 8
decode steps, as ``chipbench.replica.ProbeLLMServer`` does it): per
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
* for the first ``--controls`` seeds, the programs as they are against
  a reference that computes ANOTHER model on the same weights:
  ``write_scale_1`` (beta without its factor 2:
  ``linear_allow_neg_eigval`` false), ``rope`` (the full layers
  rotated at theta 500,000 in place of not at all), ``pre_norm`` (the
  norms on each sub-layer's input, not its output), ``no_qk_norm`` (q
  and k as projected).

Every control must read OUTSIDE the tolerance at the geometry that
decides it.

For the first ``--depth`` seeds, at the first geometry, WHERE the
program's error arises: ``after_<n>``, program against reference on
weights whose layers from the n-th on add nothing (their norms' weights
zero: with the norms on the outputs a block then adds zeros), and
``f32_exact``, the programs traced under
``jax.default_matmul_precision("highest")``: every product of float32
operands — the block form's with the state, the step's — exact, the
bfloat16 ones as they are.

``--slots`` makes the engine smaller than the cell's (the programs'
mathematics does not depend on the slot count; the float8 control and
the depth readings need the room a copy of the embedding and the head
takes).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import time

from benchmarks.command_a_plus_parity import _reference
from benchmarks.solar_open2_parity import _through_engine

CELL = "olmo-hybrid-7b.longdoc"
ROPE_THETA = 500000.0
DEPTHS = (1, 2, 3, 4, 8, 12)


def _adding_nothing(params, llama, kinds, depth):
    """``params`` with the layers from the ``depth``-th on made the
    identity: their two norms' weights zero (the norms sit on the
    sub-layers' outputs)."""
    out = dict(params)
    periods, place = divmod(depth, len(kinds))
    for name in ("layers", llama.LINEAR):
        mine = [llama.RECURRENT.get(kind, "layers") == name
                for kind in kinds]
        first = periods * sum(mine) + sum(mine[:place])
        out[name] = {**params[name], **{
            norm: params[name][norm].at[first:].set(0)
            for norm in ("ln_attn", "ln_mlp")}}
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seeds", default="1")
    parser.add_argument("--controls", type=int, default=0,
                        help="seeds that also get the other-model controls")
    parser.add_argument("--depth", type=int, default=0,
                        help="seeds that also get the readings by depth")
    parser.add_argument("--skip", default="",
                        help="readings to leave out, comma-separated")
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
    chunk = spec["serve"]["kwargs"]["prefill_chunk_tokens"]
    whole = traffic["parity"]["prompt_tokens"]
    prompts = (whole, (whole - 1) // chunk * chunk + spec["serve"][
        "probe_short_last_chunk"]["tokens_behind_boundary"])
    seeds = [int(s) for s in args.seeds.split(",")]
    skip = set(filter(None, args.skip.split(",")))
    config = resolve(spec["model"]["factory"])(spec)
    other = {
        "write_scale_1": ({**spec, "linear_allow_neg_eigval": False}, {}),
        "rope": ({**spec, "rope_parameters": {"rope_theta": ROPE_THETA}},
                 {}),
        "pre_norm": (spec, {"reordered_norm": False}),
        "no_qk_norm": (spec, {"qk_norm": False}),
    }
    draw = jax.jit(llama.init_params, static_argnums=0)
    t0 = time.perf_counter()
    eng = LLMEngine(config, slots=args.slots or traffic["slots"],
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

    def through(tokens, prompt, params=None, **how):
        mine = eng.params
        if params is not None:
            eng.params = params
        try:
            return _through_engine(eng, tokens, prompt, steps, **how)
        finally:
            eng.params = mine

    for i, seed in enumerate(seeds):
        if i:
            eng.params = None
            eng.params = draw(config, jax.random.PRNGKey(seed))
        params = eng.params
        for prompt in prompts:
            t1 = time.perf_counter()
            tokens = np.random.default_rng([seed, 11]).integers(
                0, config.vocab_size, prompt + steps, dtype=np.int32)
            want = _reference(spec, params, tokens, prompt - 1, jax, jnp)
            got = through(tokens, prompt)
            row = {"seed": seed, "prompt": prompt,
                   "program": rel_l2(got, want),
                   "argmax_equal": int(jnp.sum(
                       jnp.argmax(got, -1) == jnp.argmax(want, -1)))}
            if "no_carry" not in skip:
                row["no_carry"] = rel_l2(
                    through(tokens, prompt, carry=False), want)
            if "no_decay" not in skip:
                lin = params[llama.LINEAR]
                row["no_decay"] = rel_l2(through(tokens, prompt, {
                    **params, llama.LINEAR: {**lin, "a_log": jnp.full_like(
                        lin["a_log"], -jnp.inf)}}), want)
                del lin
            if "fp8" not in skip:
                row["fp8"] = rel_l2(_reference(
                    spec, params, tokens, prompt - 1, jax, jnp,
                    cast=lambda w: w.astype(jnp.float8_e4m3fn).astype(
                        jnp.bfloat16)), want)
            if i < args.controls:
                for name, (changed, family) in other.items():
                    if name not in skip:
                        row[name] = rel_l2(got, _reference(
                            changed, params, tokens, prompt - 1, jax, jnp,
                            **family))
            if i < args.depth and prompt == prompts[0]:
                with jax.default_matmul_precision("highest"):
                    row["f32_exact"] = rel_l2(through(tokens, prompt), want)
                for depth in DEPTHS:
                    less = _adding_nothing(params, llama, config.kinds,
                                           depth)
                    row[f"after_{depth}"] = rel_l2(
                        through(tokens, prompt, less),
                        _reference(spec, less, tokens, prompt - 1, jax,
                                   jnp))
                    del less
            row["seconds"] = time.perf_counter() - t1
            keep(row)
            del want, got
        del params
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[parity] peak bytes in use "
          f"{stats.get('peak_bytes_in_use', 0) / 2 ** 30:.2f} GiB")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
