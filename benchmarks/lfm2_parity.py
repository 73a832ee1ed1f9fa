"""How `lfm2-8b-a1b`'s parity tolerance was set and what it refuses.

    python -m benchmarks.lfm2_parity --seeds 1,2,3 [--controls 2] \\
        [--skip fp8,...] [--out chiprun_out/lfm2_parity.json]

On the chip, one process, at the published widths of
``chipbench/configs/lfm2-8b-a1b.json`` and at all THREE probe
geometries of the cell (``chipbench/replica_median_triple.py``: the
traffic file's whole chunks; a prompt that ends a few tokens behind a
chunk boundary; a prompt of a few dozen tokens, one padded chunk; each
through the engine's own 512-token chunks, then 8 decode steps, as
``chipbench.replica.ProbeLLMServer`` does it): per seed, weights drawn
from the seed, and readings of the logits' relative L2 against the
plain float32 reference at the probe's positions, each as the
positions' median (what the replica compares) and worst:

* ``program`` — the engine's programs as they are: must read inside
  the tolerance;
* ``no_carry`` — the same programs with the slot's convolution tails
  emptied before every chunk but the first: the tail not handed from
  chunk to chunk (where a prompt is one chunk there is no hand-over);
* ``no_routed`` (``--no-routed N``: the first N seeds, in a last
  pass) — programs and reference on weights whose routed experts add
  nothing (``w_down`` zero), so that no expert pick can flip: what
  bfloat16 alone does through the mixers and the dense layers;
* ``fp8`` — no engine: the reference with its matrices rounded to
  ``float8_e4m3fn``, the nearest precision below the stated one,
  against itself in float32;
* for the first ``--controls`` seeds, the programs as they are against
  a reference that leaves ONE part of the mathematics off
  (``left_off``): ``no_gate_b`` (the convolution runs over ``u``, not
  ``B * u``), ``no_gate_c`` (its output is not gated by ``C``),
  ``no_expert_bias`` (the experts are picked by their scores alone) and
  ``no_qk_norm`` (q and k go to the rotary embedding as projected).

``left_off`` is what ``tests/test_lfm2.py`` runs at a tiny size too.

``--picks N`` (the first N seeds) shows what the program's distance IS
made of.  The step programs are compiled a second time with every
routed layer handing its rows' expert picks to the host
(``watched_programs``: the same functions, ``llama._routed_mlp``
wrapped while they are traced; ``patched_max_abs`` says how far their
logits lie from the timed programs' — 0 where the picks are the timed
programs' own), the reference hands out its own (``reference``), and
per compared position:

* ``flips`` — in how many of the twelve routed layers the program's
  four experts are not the float32 reference's four (``flips_behind``:
  summed over the 8 positions before it too, whose tails and keys the
  position reads), beside the position's relative L2;
* ``forced`` — the relative L2 against the reference GIVEN THE
  PROGRAM'S PICKS (its gates are its own float32 scores of those
  experts): what is left of the distance once no pick can differ —
  bfloat16 through fourteen blocks and nothing else.  ``picks_equal_pct``
  is the share of (position, layer) places at which the forced
  reference's own four — computed on the program's path in float32 —
  are the program's: it falls where the program picks by another rule;
* every ``left_off`` control and the dropped tail once more against
  the forced reference (``<control>_forced``): a reading no flipped
  pick can hide or feign.  ``no_expert_bias`` has no forced reading —
  forcing the picks is forcing what it leaves off — and is read by
  ``picks_equal_pct`` of a reference that picks without the bias.

The exit code holds these readings to the configuration's
``tolerance.forced_picks`` (``held_to_the_forced_limits``); the cell's
own ``correct`` compares the probes' medians with
``tolerance.serve_logit_rel_l2`` and cannot see the program's picks.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import statistics
import time

CELL = "lfm2-8b-a1b.chat-rate"
LEFT_OFF = ("no_gate_b", "no_gate_c", "no_expert_bias", "no_qk_norm")


@contextlib.contextmanager
def left_off(what: str):
    """``chipbench.reference.lfm2_decoder`` with ONE part of its
    mathematics left off while the block is traced: another model on
    the same weights, which the tolerance has to tell from this one."""
    import jax.numpy as jnp

    from chipbench.reference import lfm2_decoder as ref

    f32 = ref._f32

    def conv_mix(layer, h):
        b, c, u = jnp.split(h @ f32(layer["in_proj"]), 3, axis=-1)
        z = ref.short_conv(u if what == "no_gate_b" else b * u,
                           layer["conv_w"])
        return (z if what == "no_gate_c" else c * z) @ f32(
            layer["out_proj"])

    def gate_map(h, router, expert_bias, *rest):
        return plain["gate_map"](h, router, jnp.zeros_like(expert_bias),
                                 *rest)

    def softmax_mix(layer, h, positions, n_heads, n_kv_heads, rope_theta,
                    norm_eps):
        seq = h.shape[0]
        q = (h @ f32(layer["wq"])).reshape(seq, n_heads, -1)
        k = (h @ f32(layer["wk"])).reshape(seq, n_kv_heads, -1)
        v = (h @ f32(layer["wv"])).reshape(seq, n_kv_heads, -1)
        q, k = (ref.rotary(x, positions, rope_theta) for x in (q, k))
        return ref.attention(q, k, v, q.shape[-1] ** -0.5).reshape(
            seq, -1) @ f32(layer["wo"])

    def block(*args, **kwargs):
        # a function of its own: jit keys its traces by the function,
        # and ``ref.block``'s were made with every part in place
        return plain["block"](*args, **kwargs)

    patched = {"block": block, **{
        "no_gate_b": {"conv_mix": conv_mix},
        "no_gate_c": {"conv_mix": conv_mix},
        "no_expert_bias": {"gate_map": gate_map},
        "no_qk_norm": {"softmax_mix": softmax_mix}}[what]}
    plain = {name: getattr(ref, name) for name in patched}
    for name, fn in patched.items():
        setattr(ref, name, fn)
    try:
        yield
    finally:
        for name, fn in plain.items():
            setattr(ref, name, fn)


def watched_programs(config, slots: int, max_seq: int, chunk: int, sink):
    """The engine's chunk and decode programs compiled once more with
    every routed layer's picks sent to ``sink(softmax_layer, index,
    picks)`` as the layer runs (``index``: the layer's place in the
    stack of its kind; ``picks`` (rows, k)).  The picks are computed
    beside the program's own from the same operands by the same
    operations; nothing else of the program differs."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax import lax

    from ant_ray_tpu.llm import programs
    from ant_ray_tpu.models import llama

    plain = llama._routed_mlp

    def routed(layer, h, c, index=None, tile=0, live=None):
        scores = jax.nn.sigmoid(jnp.dot(
            h.reshape(-1, h.shape[-1]), layer["router"],
            preferred_element_type=jnp.float32))
        _, picks = lax.top_k(
            scores + layer["router_bias"].astype(jnp.float32),
            c.experts_per_token)
        jax.debug.callback(functools.partial(sink, "wq" in layer),
                           index, picks)
        return plain(layer, h, c, index, tile, live)

    run = programs.step_programs(config, slots=slots, max_seq=max_seq,
                                 chunk=chunk)

    def traced_with_the_watch(program):
        def call(*args):
            llama._routed_mlp = routed
            try:
                out = program(*args)
            finally:
                llama._routed_mlp = plain
            jax.effects_barrier()
            return out
        return call

    return (traced_with_the_watch(run.prefill_chunk),
            traced_with_the_watch(run.decode))


def program_picks(eng, watched, seen: list, config, tokens, prompt: int,
                  steps: int, carry: bool = True):
    """The probe through ``watched`` (``watched_programs``' pair, whose
    sink appends to ``seen``): its logits and, for every routed layer in
    the model's order, which experts each of the ``prompt + steps``
    positions was given — (routed layers, positions, experts) bool."""
    import numpy as np

    from benchmarks.solar_open2_parity import _through_engine

    kinds = config.pattern[config.n_dense_layers:]
    place = {}                  # (softmax?, index in its stack) -> layer
    for i, kind in enumerate(kinds):
        softmax = kind == "full"
        place[softmax, sum((k == "full") == softmax
                           for k in kinds[:i])] = i
    chunk, slot = eng._chunk_tokens, eng._free_slots[-1]
    timed = eng._prefill_chunk_jit, eng._decode_jit
    del seen[:]
    eng._prefill_chunk_jit, eng._decode_jit = watched
    try:
        got = _through_engine(eng, tokens, prompt, steps, carry=carry)
    finally:
        eng._prefill_chunk_jit, eng._decode_jit = timed
    picked = np.zeros((len(kinds), prompt + steps, config.num_experts),
                      bool)
    calls = [seen[i:i + len(kinds)]
             for i in range(0, len(seen), len(kinds))]
    assert len(calls) == -(-prompt // chunk) + steps, len(seen)
    for n, call in enumerate(calls):
        assert len({(s, int(i)) for s, i, _ in call}) == len(kinds)
        for softmax, index, picks in call:
            layer = place[softmax, int(index)]
            if n * chunk < prompt:          # a chunk: its real tokens
                first = n * chunk
                rows = picks[:min(chunk, prompt - first)]
            else:                           # a decode step: the slot's row
                first = prompt + n - -(-prompt // chunk)
                rows = picks[slot:slot + 1]
            at = first + np.arange(len(rows))
            picked[layer, at[:, None], rows] = True
    return got, picked


@contextlib.contextmanager
def picks_in_hand():
    """``chipbench.reference.lfm2_decoder`` whose routed layers say
    which experts they picked and take them from the caller where the
    layer's dict holds ``picks`` ((seq, experts) bool): yields
    ``reference(spec, params, tokens, first, picks=None)`` -> ``(logits
    from position first on, (routed layers, seq, experts) bool)``.
    Inside ``left_off`` it wraps what that left."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.reference import lfm2_decoder as ref
    from chipbench.spec import resolve

    plain = {"gate_map": ref.gate_map, "block": ref.block}
    hand = {}

    def gate_map(h, router, expert_bias, experts_per_token, scaling):
        if hand["forced"] is None:
            gates = plain["gate_map"](h, router, expert_bias,
                                      experts_per_token, scaling)
        else:
            kept = jnp.where(hand["forced"],
                             jax.nn.sigmoid(h @ ref._f32(router)), 0.0)
            gates = kept / (jnp.sum(kept, axis=-1, keepdims=True)
                            + ref.GATE_EPS) * scaling
            own = plain["gate_map"](h, router, expert_bias,
                                    experts_per_token, scaling)
            hand["own"] = own > 0
        hand["picked"] = gates > 0
        return gates

    def block(layer, x, positions, **dims):
        layer = dict(layer)
        hand.update(forced=layer.pop("picks", None), picked=None, own=None)
        x = plain["block"](layer, x, positions, **dims)
        return x, hand["picked"], hand["own"]

    ref.gate_map = gate_map
    jitted = jax.jit(block, static_argnames=(
        "n_heads", "n_kv_heads", "rope_theta", "norm_eps"))

    def reference(spec, params, tokens, first, picks=None):
        embed, layer, n, norm_f, head = resolve(
            spec["reference"]["params"])(params)
        dims = ref.dims_of(spec)
        tokens = jnp.asarray(tokens)
        positions = jnp.arange(tokens.shape[0])
        x, picked, own = ref.embed_tokens(embed, tokens), [], []
        for i in range(n):
            leaves = layer(i)
            if picks is not None and "router" in leaves:
                leaves = {**leaves, "picks": jnp.asarray(
                    picks[len(picked)])}
            x, p, o = jitted(leaves, x, positions, **dims)
            if p is not None:
                picked.append(np.asarray(p))
                own.append(np.asarray(p if o is None else o))
        logits = ref.logits_of(norm_f, head, x, dims["norm_eps"])[first:]
        return logits, np.stack(picked), np.stack(own)

    try:
        yield reference
    finally:
        ref.gate_map = plain["gate_map"]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seeds", default="1")
    parser.add_argument("--controls", type=int, default=0,
                        help="seeds that also get the left_off controls")
    parser.add_argument("--no-routed", type=int, default=0,
                        help="seeds read again with the routed experts "
                             "adding nothing (w_down zero): no pick can flip")
    parser.add_argument("--picks", type=int, default=0,
                        help="seeds whose expert picks are compared "
                             "between program and reference, and read "
                             "again with the program's picks forced")
    parser.add_argument("--skip", default="",
                        help="readings to leave out, comma-separated")
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ant_ray_tpu.llm import LLMEngine
    from ant_ray_tpu.models import llama
    from benchmarks.command_a_plus_parity import _reference
    from benchmarks.solar_open2_parity import _through_engine
    from chipbench.spec import Cell, resolve

    cell = Cell(CELL)
    spec, traffic = cell.config, cell.traffic
    steps = traffic["parity"]["decode_steps"]
    chunk = spec["serve"]["kwargs"]["prefill_chunk_tokens"]
    whole = traffic["parity"]["prompt_tokens"]
    prompts = (whole, (whole - 1) // chunk * chunk + spec["serve"][
        "probe_short_last_chunk"]["tokens_behind_boundary"],
        spec["serve"]["probe_short_prompt"]["tokens"])
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
    rows, seen = [], []
    watched = watched_programs(
        config, traffic["slots"], traffic["max_seq"], chunk,
        lambda softmax, index, picks: seen.append(
            (softmax, index, np.asarray(picks)))) if args.picks else None

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
             for k, v in row.items()}),
            flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "w") as f:
                json.dump({"rows": rows}, f)

    def reference(tokens, prompt, params, **how):
        return _reference(spec, params, tokens, prompt - 1, jax, jnp, **how)

    def by_picks(tokens, prompt, params, got, controls):
        """The ``--picks`` readings of one probe (module docstring)."""
        def equal_pct(own, picked):
            return 100.0 * float(np.mean((own == picked).all(-1)))

        with picks_in_hand() as pick_reference:
            _, theirs, _ = pick_reference(spec, params, tokens, prompt - 1)
            again, mine = program_picks(eng, watched, seen, config, tokens,
                                        prompt, steps)
            forced, _, own = pick_reference(spec, params, tokens,
                                            prompt - 1, picks=mine)
            out = {"patched_max_abs": float(jnp.max(jnp.abs(again - got))),
                   "forced": rel_l2(got, forced),
                   "picks_equal_pct": equal_pct(own, mine)}
            if controls and prompt > chunk:
                less, theirs_less = program_picks(
                    eng, watched, seen, config, tokens, prompt, steps,
                    carry=False)
                out["no_carry_forced"] = rel_l2(less, pick_reference(
                    spec, params, tokens, prompt - 1, picks=theirs_less)[0])
        differs = (mine != theirs).any(-1)      # (routed layers, positions)
        compared = range(prompt - 1, prompt + steps)
        out["flips"] = [int(differs[:, p].sum()) for p in compared]
        out["flips_behind"] = [int(differs[:, max(0, p - 8):p + 1].sum())
                               for p in compared]
        for what in LEFT_OFF if controls else ():
            with left_off(what), picks_in_hand() as pick_reference:
                logits, _, own = pick_reference(spec, params, tokens,
                                                prompt - 1, picks=mine)
            if what == "no_expert_bias":
                out[what + "_picks_equal_pct"] = equal_pct(own, mine)
            else:
                out[what + "_forced"] = rel_l2(got, logits)
        return out

    for i, seed in enumerate(seeds):
        if i:
            eng.params = None
            eng.params = draw(config, jax.random.PRNGKey(seed))
        params = eng.params
        for prompt in prompts:
            t1 = time.perf_counter()
            tokens = np.random.default_rng([seed, 11]).integers(
                0, config.vocab_size, prompt + steps, dtype=np.int32)
            want = reference(tokens, prompt, params)
            got = _through_engine(eng, tokens, prompt, steps)
            row = {"seed": seed, "prompt": prompt,
                   "program": rel_l2(got, want),
                   "argmax_equal": int(jnp.sum(
                       jnp.argmax(got, -1) == jnp.argmax(want, -1)))}
            if "no_carry" not in skip and prompt > chunk:
                row["no_carry"] = rel_l2(_through_engine(
                    eng, tokens, prompt, steps, carry=False), want)
            if "fp8" not in skip:
                row["fp8"] = rel_l2(reference(
                    tokens, prompt, params,
                    cast=lambda w: w.astype(jnp.float8_e4m3fn).astype(
                        jnp.bfloat16)), want)
            if i < args.controls:
                for what in LEFT_OFF:
                    with left_off(what):
                        row[what] = rel_l2(got, reference(
                            tokens, prompt, params))
            if i < args.picks:
                row.update(by_picks(tokens, prompt, params, got,
                                    i < args.controls))
            row["seconds"] = time.perf_counter() - t1
            keep(row)
            del want, got
        del params
    # last: the expert matrices are swapped in place, nothing is kept
    for seed in seeds[:args.no_routed]:
        eng.params = None
        less = dict(draw(config, jax.random.PRNGKey(seed)))
        for name in ("layers", llama.CONV):
            stack = dict(less[name])
            w_down = stack.pop("w_down")
            shape, dtype = w_down.shape, w_down.dtype
            less[name] = stack
            del w_down              # 1 GB freed before as many zeros
            stack["w_down"] = jnp.zeros(shape, dtype)
        eng.params = less
        for prompt in prompts:
            tokens = np.random.default_rng([seed, 11]).integers(
                0, config.vocab_size, prompt + steps, dtype=np.int32)
            keep({"seed": seed, "prompt": prompt, "no_routed": rel_l2(
                _through_engine(eng, tokens, prompt, steps),
                reference(tokens, prompt, less))})
        del less, stack
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[parity] peak bytes in use "
          f"{stats.get('peak_bytes_in_use', 0) / 2 ** 30:.2f} GiB")
    return held_to_the_forced_limits(rows, spec["tolerance"])


def held_to_the_forced_limits(rows: list, tolerance: dict) -> int:
    """The ``--picks`` readings against the configuration's
    ``tolerance.forced_picks``: 0 if every probe of the sound program
    reads inside both limits at its WORST position and every control
    reads outside at one probe at least (the harness compares the
    largest of a run's readings: one probe that catches a fault is
    enough), else 1.  Rows without the readings decide nothing."""
    limits = tolerance["forced_picks"]
    worst, least = limits["rel_l2_worst_position"], limits[
        "picks_equal_pct_min"]
    by_seed, verdict = {}, 0
    for row in rows:
        if "forced" in row:
            by_seed.setdefault(row["seed"], []).append(row)
    for seed, probes in by_seed.items():
        sound = all(p["forced"]["worst"] <= worst
                    and p["picks_equal_pct"] >= least for p in probes)
        caught = {
            name[:-len("_forced")]: any(
                p[name]["worst"] > worst for p in probes if name in p)
            for name in sorted({k for p in probes for k in p
                                if k.endswith("_forced")})}
        if any("no_expert_bias_picks_equal_pct" in p for p in probes):
            caught["no_expert_bias"] = any(
                p["no_expert_bias_picks_equal_pct"] < least for p in probes
                if "no_expert_bias_picks_equal_pct" in p)
        print(f"[parity] seed {seed} against the forced limits ({worst} at "
              f"the worst position, picks equal >= {least} %): sound "
              f"{'inside' if sound else 'OUTSIDE'}; controls outside: "
              f"{caught}", flush=True)
        verdict |= not sound or not all(caught.values())
    return int(verdict)


if __name__ == "__main__":
    raise SystemExit(main())
