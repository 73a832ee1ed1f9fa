"""How `sdar-30b-a3b`'s parity tolerance was set and what it refuses.

    python -m benchmarks.sdar_block_parity --seeds 1,2,3 \\
        [--controls] [--out chiprun_out/parity.json]

On the chip, one process, at the published widths of
``chipbench/configs/sdar-30b-a3b.json`` and the deployment of
``chipbench/traffic/reason-batch.json`` (48 slots x 4,096): per seed,
weights drawn from the seed and the benchmark's own probe
(``chipbench.replica_block``: 64 prompt blocks through the engine's
chunks, then teacher-forced block states through its block-step
program) against the plain float32 reference.  Beside the probe, per
prompt, the FUSED states (PR 55: a block's store pass rides its
successor's first step, ``fused_states``): "block b closing + block
b+1's first step" through the engine's one step program with the
closing rows live, the rows of the block in flight against the same
reference — ``fused`` on a seed's line, the median row a prompt as the
probe reads it.

``--controls``: for each seed and each of the probe's two prompts also
every row of the readings that must lie OUTSIDE the tolerance:

* ``fp8``: the reference with its matrices rounded to ``float8_e4m3fn``
  against itself in float32 (no engine: the nearest precision below the
  stated one);
* ``causal_inside``: the system against a reference whose block in
  flight is masked causally;
* ``prompt_causal``: against a reference whose PROMPT rows are masked
  causally (what the chunk program computed before this model);
* ``whole_qk_norm``: against a reference that norms q and k over the
  whole width (the program's other form);
* ``store_dropped``: against a reference whose stored block is the one
  its LAST DENOISE step was fed, one place still the mask token — what a
  cache holds if the store pass is saved (states behind a store only);
* of the fused states, ``closing_sees_new``: against a reference whose
  closing block also sees its successor's places, and
  ``new_blind_to_closing``: against one whose block in flight does not
  see the closing block.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import time


def fused_states(eng, seed: int, prompt_tokens: int, probe: dict):
    """``replica_block.block_states``' sequence (the same seeded
    tokens), the store passes RIDING: the prompt through the engine's
    chunk program, then one fused step a block b < ``blocks`` - 1 —
    block b's final tokens closing at ``stored``, block b + 1 in flight
    behind them with its first seeded place known (block 1: none) ->
    ``(tokens, states)``, a state ``(stored + block_length, fed,
    logits)`` as the probe's: the positions before the block in flight
    (the closing block among them), the block as fed, its rows'
    logits."""
    import numpy as np

    jnp, cfg = eng._jnp, eng.config
    size, slots = cfg.block_length, eng.slots
    tokens = np.random.default_rng([seed, 11]).integers(
        0, cfg.vocab_size, prompt_tokens + probe["blocks"] * size,
        dtype=np.int32)
    order = np.random.default_rng([seed, 13]).permutation(size)
    slot, chunk = eng._free_slots[-1], eng._chunk_tokens
    for start in range(0, prompt_tokens, chunk):
        part = tokens[start:min(start + chunk, prompt_tokens)]
        buf = np.zeros((chunk,), np.int32)
        buf[:len(part)] = part
        _, eng.cache = eng._prefill_chunk_jit(
            eng.params, eng.cache, jnp.asarray(buf), slot, start, len(part))
    live = np.zeros((slots,), bool)
    live[slot] = True
    live = jnp.asarray(live)
    states, stored = [], prompt_tokens
    for b in range(probe["blocks"] - 1):
        known = np.arange(size) == (order[0] if b else -1)
        final = tokens[stored + size:stored + 2 * size]
        fed = np.where(known, final, cfg.mask_token).astype(np.int32)
        blocks = np.full((slots, size), cfg.mask_token, np.int32)
        masked = np.zeros((slots, size), bool)
        closed = np.zeros((slots, size), np.int32)
        blocks[slot], masked[slot] = fed, ~known
        closed[slot] = tokens[stored:stored + size]
        logits, _, eng.cache = eng._mixed_step_jit(
            eng.params, eng.cache, jnp.asarray(blocks), jnp.asarray(masked),
            jnp.asarray(closed), live, live, *eng._no_chunk)
        stored += size
        if int(eng.cache["length"][slot]) != stored:
            raise RuntimeError("the fused step did not move the slot's "
                               f"length over its closing block to {stored}")
        states.append((stored, fed,
                       logits.reshape(slots, size, -1)[slot]))
    return tokens, states


def fused_reading(spec, eng, seed, prompt, jnp, np, with_controls):
    """One prompt's fused states against the reference: the median
    row, every row, and (``with_controls``) the two masks that must
    read outside the tolerance, a list of rows a state."""
    from chipbench.reference import sdar_moe_decoder as ref
    from chipbench.replica_block import reference_rows, rel_l2

    size = spec["generation"]["block_length"]
    tokens, states = fused_states(eng, seed, prompt, spec["serve"]["probe"])
    block_causal = ref.block_mask(len(tokens), size)
    rows, wrong = [], {"closing_sees_new": [], "new_blind_to_closing": []}
    for stored, fed, logits in states:
        rows += rel_l2(logits, reference_rows(
            spec, eng.params, tokens, stored, fed), jnp)
        if not with_controls:
            continue
        new, closing = slice(stored, stored + size), slice(stored - size,
                                                           stored)
        for name, mask in (
                ("closing_sees_new", block_causal.at[closing, new].set(True)),
                ("new_blind_to_closing",
                 block_causal.at[new, closing].set(False))):
            wrong[name].append(rel_l2(logits, reference_rows(
                spec, eng.params, tokens, stored, fed, mask=mask), jnp))
    return {"rel_l2": statistics.median(rows), "rows": rows,
            **({"controls": wrong} if with_controls else {})}


def controls(spec, params, tokens, states, jnp, np) -> dict:
    """Every control's rows for one prompt's states: name -> a list a
    state of the rows' relative L2 (``store_dropped``: the states behind
    a store pass alone)."""
    from chipbench.reference import sdar_moe_decoder as ref
    from chipbench.replica_block import reference_rows, rel_l2

    size = spec["generation"]["block_length"]
    mask_token = spec["generation"]["mask_token_id"]
    total = len(tokens)
    prompt = states[0][0]
    causal = jnp.tril(jnp.ones((total, total), bool))
    block_causal = ref.block_mask(total, size)
    readings = {name: [] for name in (
        "fp8", "causal_inside", "prompt_causal", "whole_qk_norm",
        "store_dropped")}
    for stored, fed, logits in states:
        def against(**how):
            return rel_l2(logits, reference_rows(
                spec, params, how.pop("tokens", tokens), stored, fed, **how),
                jnp)

        want = reference_rows(spec, params, tokens, stored, fed)
        readings["fp8"].append(rel_l2(reference_rows(
            spec, params, tokens, stored, fed, weights="float8_e4m3fn"),
            want, jnp))
        readings["causal_inside"].append(against(
            mask=block_causal.at[stored:stored + size].set(
                causal[stored:stored + size])))
        readings["prompt_causal"].append(against(
            mask=block_causal.at[:prompt].set(causal[:prompt])))
        readings["whole_qk_norm"].append(against(qk_norm="whole"))
        if stored > prompt:
            # the block before this one, with the place its last
            # denoise step still fed as the mask
            kept = np.array(tokens)
            kept[last_filled(states, stored - size, mask_token)] = mask_token
            readings["store_dropped"].append(against(tokens=kept))
    return readings


def last_filled(states, at: int, mask_token: int) -> int:
    """The position that the block stored at ``at`` was still fed as the
    mask in its last denoise step."""
    fed = [f for stored, f, _ in states if stored == at][-1]
    return at + [j for j, tok in enumerate(fed) if tok == mask_token][-1]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seeds", default="1")
    parser.add_argument("--controls", action="store_true")
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ant_ray_tpu.models import llama
    from chipbench import replica_block
    from chipbench.spec import Cell

    cell = Cell("sdar-30b-a3b.reason")
    spec, traffic = cell.config, cell.traffic
    prompt = traffic["parity"]["prompt_tokens"]
    seeds = [int(s) for s in args.seeds.split(",")]
    draw = jax.jit(llama.init_params, static_argnums=0)
    rows = []

    def keep(row):
        rows.append(row)
        print("[parity] " + json.dumps(
            {k: v for k, v in row.items()
             if k not in ("rows", "controls", "fused_rows")}),
            flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "w") as f:
                json.dump({"rows": rows}, f)

    t0 = time.perf_counter()
    server = replica_block.BlockProbeLLMServer(
        spec, slots=traffic["slots"], max_seq=traffic["max_seq"],
        seed=seeds[0], **spec["serve"]["kwargs"])
    eng = server.engine
    print(f"[parity] {jax.devices()[0].device_kind}: replica ready in "
          f"{time.perf_counter() - t0:.1f} s, cache "
          f"{ {k: v.shape for k, v in eng.cache.items()} }", flush=True)
    try:
        for i, seed in enumerate(seeds):
            if i:
                eng.params = None
                eng.params = draw(eng.config, jax.random.PRNGKey(seed))
            out = server.probe_logits(seed, prompt, 0)
            row = {"seed": seed, "rel_l2": out["rel_l2"],
                   "rows": out["rel_l2_by_position"],
                   "argmax_equal": out["argmax_equal"],
                   "positions": out["positions"],
                   "system_s": out["system_s"], "seconds": out["seconds"]}
            fused = [server._loop._call_on_loop(
                lambda e, seed=seed, n=n: fused_reading(
                    spec, e, seed, n, jnp, np, args.controls),
                timeout=1800.0)
                for n in replica_block.sequences(spec, prompt)]
            row["fused"] = [f["rel_l2"] for f in fused]
            row["fused_rows"] = [f["rows"] for f in fused]
            if args.controls:
                # the probe's reading of each: the median row, a prompt
                row["fused_controls"] = [
                    {name: statistics.median(sum(rows, []))
                     for name, rows in f["controls"].items()} for f in fused]
            if args.controls:
                row["controls"] = [server._loop._call_on_loop(
                    lambda e, seed=seed, n=n: controls(
                        spec, e.params, *replica_block.block_states(
                            e, seed, n, spec["serve"]["probe"]), jnp, np),
                    timeout=1800.0)
                    for n in replica_block.sequences(spec, prompt)]
            keep(row)
    finally:
        server.shutdown()
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[parity] peak bytes in use "
          f"{stats.get('peak_bytes_in_use', 0) / 2 ** 30:.2f} GiB")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
