"""How `solar-open2`'s parity tolerance was set and what it refuses.

    python -m benchmarks.solar_open2_parity --seeds 1,2,3 \\
        [--depth 4] [--skip fp8,...] [--out chiprun_out/solar_parity.json]

On the chip, one process, at the published widths of
``chipbench/configs/solar-open2.json`` and at BOTH probe geometries of
the cell (``chipbench/replica_median_pair.py``: the traffic file's
whole chunks, and a prompt that ends a few tokens behind a chunk
boundary; each through the engine's own 512-token chunks, then 8
decode steps, as ``chipbench.replica.ProbeLLMServer`` does it): per
seed, weights drawn from the seed, and readings of the logits'
relative L2 against the plain float32 reference at the probe's
positions, each as the positions' median (what the replica compares)
and worst:

* ``program`` — the engine's programs as they are: must read inside
  the tolerance;
* ``no_carry`` — the same programs with the slot's recurrent state and
  convolution tails emptied before every chunk but the first: the state
  not handed from chunk to chunk;
* ``no_decay`` — the same programs on weights whose ``a_log`` is -inf,
  so that every decay is exp(0) = 1, against the TRUE reference;
* ``fp8`` — no engine: the reference with its matrices rounded to
  ``float8_e4m3fn``, the nearest precision below the stated one,
  against itself in float32;
* ``bf16_state`` — the engine's programs with what the configuration
  states as float32 kept in bfloat16 instead: the recurrent state
  wherever it is handed on (block to block, chunk to chunk, step to
  step), the log-decays and the write strengths
  (``_state_in_bfloat16``; a second pass, the step programs compiled
  anew).

For the first ``--depth`` seeds, at the first geometry, WHERE the
program's error arises: ``after_1`` .. ``after_3``, program against
reference on weights whose later layers add nothing (their norms'
weights zero: the block is the identity), and, in a last pass,
``no_routed``: all layers with the routed experts adding nothing (no
expert pick can flip).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import time

from benchmarks.command_a_plus_parity import _reference


def _through_engine(eng, tokens, prompt: int, steps: int, carry: bool = True):
    """``ProbeLLMServer._probe_on_loop``'s path: the prompt in the
    engine's chunks into its last free slot, ``steps`` teacher-forced
    decode steps; logits from the last prompt token on.  ``carry``
    False empties the slot's state between chunks."""
    import numpy as np

    jnp = eng._jnp
    slot, chunk = eng._free_slots[-1], eng._chunk_tokens
    got = []
    for start in range(0, prompt, chunk):
        part = tokens[start:min(start + chunk, prompt)]
        buf = np.zeros((chunk,), np.int32)
        buf[:len(part)] = part
        if start and not carry:
            eng.cache = {**eng.cache, **{
                name: eng.cache[name].at[:, slot].set(0)
                for name in eng._llama.state_slabs(eng.config)}}
        logits, eng.cache = eng._prefill_chunk_jit(
            eng.params, eng.cache, jnp.asarray(buf), slot, start, len(part))
    got.append(logits)
    mask = np.zeros((eng.slots,), bool)
    mask[slot] = True
    for j in range(steps):
        last = np.zeros((eng.slots,), np.int32)
        last[slot] = tokens[prompt + j]
        logits, eng.cache = eng._decode_jit(
            eng.params, eng.cache, jnp.asarray(last), jnp.asarray(mask))
        got.append(logits[slot])
    return jnp.stack(got)


def _adding_nothing(params, llama, depth=None):
    """``params`` with the layers from ``depth`` on made the identity —
    their two norms' weights zero: a block then reads zeros and adds
    zeros (``depth`` counts the softmax layer first, then the linear
    ones: one period) — or, without ``depth``, every layer's routed
    experts adding nothing (``w_down`` zero: a copy of 1.7 GB)."""
    def cut(stack, first, names):
        return {**stack, **{name: stack[name].at[first:].set(0)
                            for name in names}}

    if depth is None:
        return {**params, "layers": cut(params["layers"], 0, ["w_down"]),
                llama.LINEAR: cut(params[llama.LINEAR], 0, ["w_down"])}
    return {**params, llama.LINEAR: cut(
        params[llama.LINEAR], depth - 1, ["ln_attn", "ln_mlp"])}


def _state_in_bfloat16(delta_rule, jnp):
    """Patches ``ops/delta_rule.py`` for the ``bf16_state`` control and
    returns what undoes it."""
    chunk, step = delta_rule.chunk_delta_rule, delta_rule.delta_rule_step

    def low(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    def chunk_low(q, k, v, g, beta, s0, block=delta_rule.BLOCK):
        g, beta, s, outs = low(g), low(beta), low(s0), []
        for a in range(0, q.shape[0], block):
            o, s = chunk(*(x[a:a + block] for x in (q, k, v, g, beta)), s,
                         block)
            s = low(s)
            outs.append(o)
        return jnp.concatenate(outs), s

    def step_low(q, k, v, g, beta, s, active):
        o, new = step(q, k, v, low(g), low(beta), low(s), active)
        return o, low(new)

    delta_rule.chunk_delta_rule = chunk_low
    delta_rule.delta_rule_step = step_low

    def undo():
        delta_rule.chunk_delta_rule = chunk
        delta_rule.delta_rule_step = step

    return undo


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seeds", default="1")
    parser.add_argument("--depth", type=int, default=0,
                        help="seeds that also get the readings by depth")
    parser.add_argument("--skip", default="",
                        help="readings to leave out, comma-separated")
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ant_ray_tpu.llm import LLMEngine
    from ant_ray_tpu.models import llama
    from ant_ray_tpu.ops import delta_rule
    from chipbench.spec import Cell, resolve

    cell = Cell("solar-open2.digest")
    spec, traffic = cell.config, cell.traffic
    steps = traffic["parity"]["decode_steps"]
    chunk = spec["serve"]["kwargs"]["prefill_chunk_tokens"]
    whole = traffic["parity"]["prompt_tokens"]
    prompts = (whole, (whole - 1) // chunk * chunk + spec["serve"][
        "probe_short_last_chunk"]["tokens_behind_boundary"])
    seeds = [int(s) for s in args.seeds.split(",")]
    skip = set(filter(None, args.skip.split(",")))
    config = resolve(spec["model"]["factory"])(spec)
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
    rows, wanted = [], {}

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

    for i, seed in enumerate(seeds):
        params = weights_of(i, seed)
        for prompt in prompts:
            t1 = time.perf_counter()
            tokens = tokens_of(seed, prompt)
            want = _reference(spec, params, tokens, prompt - 1, jax, jnp)
            wanted[seed, prompt] = np.asarray(want)
            row = {"seed": seed, "prompt": prompt}
            if "program" not in skip:
                row["program"] = rel_l2(through(tokens, prompt), want)
            if "no_carry" not in skip:
                row["no_carry"] = rel_l2(
                    through(tokens, prompt, carry=False), want)
            if "no_decay" not in skip:
                linear = params[llama.LINEAR]
                row["no_decay"] = rel_l2(through(tokens, prompt, {
                    **params, llama.LINEAR: {**linear, "a_log": jnp.full_like(
                        linear["a_log"], -jnp.inf)}}), want)
                del linear
            if "fp8" not in skip:
                row["fp8"] = rel_l2(_reference(
                    spec, params, tokens, prompt - 1, jax, jnp,
                    cast=lambda w: w.astype(jnp.float8_e4m3fn).astype(
                        jnp.bfloat16)), want)
            if i < args.depth and prompt == prompts[0]:
                for depth in (1, 2, 3):
                    less = _adding_nothing(params, llama, depth)
                    row[f"after_{depth}"] = rel_l2(
                        through(tokens, prompt, less), _reference(
                            spec, less, tokens, prompt - 1, jax, jnp))
                    del less
            row["seconds"] = time.perf_counter() - t1
            keep(row)
            del want
        del params

    if "bf16_state" not in skip:
        undo = _state_in_bfloat16(delta_rule, jnp)
        jax.clear_caches()             # the step programs compile anew
        try:
            for i, seed in enumerate(seeds):
                weights_of(1, seed)
                for prompt in prompts:
                    t1 = time.perf_counter()
                    keep({"seed": seed, "prompt": prompt,
                          "bf16_state": rel_l2(
                              through(tokens_of(seed, prompt), prompt),
                              jnp.asarray(wanted[seed, prompt])),
                          "seconds": time.perf_counter() - t1})
        finally:
            undo()
            jax.clear_caches()
    # last: it needs the most memory, and what is above is written
    for i, seed in enumerate(seeds[:args.depth]):
        t1 = time.perf_counter()
        less = _adding_nothing(weights_of(1, seed), llama)
        eng.params = None
        tokens = tokens_of(seed, prompts[0])
        keep({"seed": seed, "prompt": prompts[0], "no_routed": rel_l2(
            through(tokens, prompts[0], less),
            _reference(spec, less, tokens, prompts[0] - 1, jax, jnp)),
            "seconds": time.perf_counter() - t1})
        del less
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[parity] peak bytes in use "
          f"{stats.get('peak_bytes_in_use', 0) / 2 ** 30:.2f} GiB")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
