"""The mixed step against each configuration's plain reference, on the
chip — what the benchmark's own logit probe cannot show, because it
calls the chunk program and the decode program by name and never the
third one (``llama.mixed_step``, PR 39: a prompt's chunk riding the
decode step it shares).

    python -m benchmarks.mixed_step_parity [--cells a,b,...] \\
        [--seeds 1,2] [--out chiprun_out/mixed_parity.json]

One process, one cell after another, each at its published widths, its
deployment's slots, cache and chunk width, weights drawn from the seed.
Two seeded sequences: the cell's own probe (``traffic.parity``:
``prompt_tokens`` then ``decode_steps`` teacher-forced tokens) and a
companion.  The companion's first two chunks are ingested alone; from
then on EVERY program is the mixed one:

* the probe's prompt rides in, a chunk a step, while the companion
  decodes beside it (teacher-forced) — the probe's first logits are the
  last mixed step's chunk logits;
* then both decode, ``decode_steps`` steps, while the companion's
  tokens ride in again as a third prompt's chunks.

Readings, relative L2 against the reference's float32 forward:

* ``prompt`` — the probe's positions, the ones the cell's own probe
  compares (the last prompt token and the decode steps behind it): rows
  written by the mixed step's chunk part, read by its decode part;
* ``beside`` — the companion's positions at every mixed step: decode
  rows that shared their step with a chunk;

each as the cell compares it (the worst position, or the median where
the configuration's replica reports the median), against the cell's own
tolerance.  Solar Open 2 runs its second geometry too (the prompt ends
24 tokens behind a chunk boundary: a padded last chunk through the
mixed step).

The control, which must fail: the same with the chunk's rows attending
over the WRONG slot — the companion's — in every layer that has slabs
(``wrong_slot``).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import time

from benchmarks.command_a_plus_parity import _reference

CELLS = ("internlm2-1.8b.chat", "mistral-7b.decode", "olmoe-1b-7b.rollout",
         "ax-k1.reason", "command-a-plus.docqa", "solar-open2.digest")


def _drive(eng, mixed, probe, prompt: int, steps: int, beside):
    """``probe`` (``prompt`` + ``steps`` tokens) through ``mixed`` — the
    engine's mixed program, or the control's — beside ``beside`` (the
    companion: two chunks alone, then a decode step at every mixed
    step).  Returns (the probe's logits from its last prompt token on,
    the companion's at every mixed step)."""
    import numpy as np

    jnp = eng._jnp
    chunk = eng._chunk_tokens
    a, p, q = eng._free_slots[-3:]        # companion, probe, third prompt

    def padded(part):
        buf = np.zeros((chunk,), np.int32)
        buf[:len(part)] = part
        return jnp.asarray(buf)

    for start in (0, chunk):
        _, eng.cache = eng._prefill_chunk_jit(
            eng.params, eng.cache, padded(beside[start:start + chunk]), a,
            start, chunk)
    ahead = 2 * chunk                     # the companion's next position
    got_probe, got_beside = [], []

    def step(active_slots, fed, tokens, slot, start):
        nonlocal ahead
        mask = np.zeros((eng.slots,), bool)
        mask[list(active_slots)] = True
        last = np.zeros((eng.slots,), np.int32)
        for s, token in fed.items():
            last[s] = token
        decode, at_end, eng.cache = mixed(
            eng.params, eng.cache, jnp.asarray(last), jnp.asarray(mask),
            padded(tokens), slot, start, len(tokens))
        got_beside.append(decode[a])
        ahead += 1
        return decode, at_end

    for start in range(0, prompt, chunk):
        _, at_end = step((a,), {a: beside[ahead]},
                         probe[start:min(start + chunk, prompt)], p, start)
    got_probe.append(at_end)
    for j in range(steps):
        decode, _ = step((a, p), {a: beside[ahead], p: probe[prompt + j]},
                         beside[j * chunk:(j + 1) * chunk], q, j * chunk)
        got_probe.append(decode[p])
    return jnp.stack(got_probe), jnp.stack(got_beside)


def _wrong_slot(llama, wrong: int):
    """``llama._chunk_rows`` whose rows attend over slot ``wrong``."""
    real = llama._chunk_rows

    def chunk_rows(cache, c, chunk, slot, start, chunk_len):
        rows, pos, write, _, state = real(cache, c, chunk, slot, start,
                                          chunk_len)
        attend = real(cache, c, chunk, wrong, start, chunk_len)[3]
        return rows, pos, write, attend, state

    return chunk_rows


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--cells", default=",".join(CELLS))
    parser.add_argument("--seeds", default="1")
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ant_ray_tpu.llm import LLMEngine
    from ant_ray_tpu.models import llama
    from chipbench.spec import Cell, resolve

    seeds = [int(s) for s in args.seeds.split(",")]
    rows = []

    def keep(row):
        rows.append(row)
        print("[mixed] " + json.dumps(row), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "w") as f:
                json.dump({"rows": rows}, f)

    print(f"[mixed] {jax.devices()[0].device_kind}", flush=True)
    for name in args.cells.split(","):
        cell = Cell(name)
        spec, traffic = cell.config, cell.traffic
        steps = traffic["parity"]["decode_steps"]
        limit = spec["tolerance"]["serve_logit_rel_l2"]
        median = "median" in spec["serve"]["replica"].lower()
        config = resolve(spec["model"]["factory"])(spec)
        t0 = time.perf_counter()
        eng = LLMEngine(config, slots=traffic["slots"],
                        max_seq=traffic["max_seq"], seed=seeds[0],
                        **{"prefill_chunk_tokens": 64,
                           **spec["serve"]["kwargs"]})
        chunk = eng._chunk_tokens
        prompts = [traffic["parity"]["prompt_tokens"]]
        short = spec["serve"].get("probe_short_last_chunk")
        if short:
            prompts.append((prompts[0] - 1) // chunk * chunk
                           + short["tokens_behind_boundary"])
        jax.block_until_ready(eng.params)
        print(f"[mixed] {name}: engine ready in "
              f"{time.perf_counter() - t0:.1f} s, chunk {chunk}, prompts "
              f"{prompts} + {steps}, tolerance {limit} over the "
              f"{'median' if median else 'worst'} position", flush=True)

        def reading(got, want):
            err = [float(e) for e in jnp.sqrt(jnp.sum((got - want) ** 2, -1))
                   / jnp.sqrt(jnp.sum(want ** 2, -1))]
            finite = all(map(math.isfinite, err))
            return {"compared": (statistics.median(err) if median
                                 else max(err)) if finite else math.inf,
                    "median": statistics.median(err), "worst": max(err),
                    "positions": len(err)}

        def mixed_of(module):
            return jax.jit(
                lambda params, cache, last, active, tokens, slot, start, n:
                module.mixed_step(params, last, tokens, cache, config,
                                  active, slot, start, n),
                donate_argnums=(1,))

        draw = jax.jit(llama.init_params, static_argnums=0)
        for i, seed in enumerate(seeds):
            if i:
                eng.params = None
                eng.params = draw(config, jax.random.PRNGKey(seed))
            for prompt in prompts:
                t1 = time.perf_counter()
                rng = np.random.default_rng([seed, 11])
                probe = rng.integers(0, config.vocab_size, prompt + steps,
                                     dtype=np.int32)
                mixed_steps = -(-prompt // chunk) + steps
                beside = np.random.default_rng([seed, 12]).integers(
                    0, config.vocab_size,
                    max(2 * chunk + mixed_steps, steps * chunk),
                    dtype=np.int32)
                want_probe = _reference(spec, eng.params, probe, prompt - 1,
                                        jax, jnp)
                want_beside = _reference(
                    spec, eng.params, beside[:2 * chunk + mixed_steps],
                    2 * chunk, jax, jnp)
                row = {"cell": name, "seed": seed, "prompt": prompt,
                       "mixed_steps": mixed_steps, "tolerance": limit}
                for control in (False, True):
                    if control:
                        real = llama._chunk_rows
                        llama._chunk_rows = _wrong_slot(
                            llama, eng._free_slots[-3])
                    try:
                        got_probe, got_beside = _drive(
                            eng, mixed_of(llama) if control
                            else eng._mixed_step_jit, probe, prompt, steps,
                            beside)
                        got_probe.block_until_ready()
                    finally:
                        if control:
                            llama._chunk_rows = real
                    prefix = "wrong_slot_" if control else ""
                    row[prefix + "prompt"] = reading(got_probe, want_probe)
                    if not control:
                        row["beside"] = reading(got_beside, want_beside)
                row["inside"] = (row["prompt"]["compared"] <= limit
                                 and row["beside"]["compared"] <= limit)
                row["control_fails"] = \
                    row["wrong_slot_prompt"]["compared"] > limit
                row["seconds"] = time.perf_counter() - t1
                keep(row)
                del want_probe, want_beside
        stats = jax.devices()[0].memory_stats() or {}
        print(f"[mixed] {name}: peak bytes in use "
              f"{stats.get('peak_bytes_in_use', 0) / 2 ** 30:.2f} GiB",
              flush=True)
        eng.params = eng.cache = None
        del eng
        jax.clear_caches()
    ok = all(row["inside"] and row["control_fails"] for row in rows)
    print(f"[mixed] {'ALL INSIDE, EVERY CONTROL FAILS' if ok else 'FAULT'}",
          flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
