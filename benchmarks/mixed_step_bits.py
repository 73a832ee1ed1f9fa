"""Does a row come out of the mixed step with the BITS its own program
gives it?  On the chip — the CPU's answer (``tests/test_mixed_step.py``:
yes, everywhere) says nothing about the TPU compiler's fusions and
tilings, which follow the rows a product has.

    python -m benchmarks.mixed_step_bits [--cells a,b,...] [--compile default,no_excess]

Why it matters: the benchmark answers its greedy probes alone in set-up
(``_prefill_chunk``, ``_decode``) and again among the traffic (mostly
``_mixed_step``, PR 39) and holds the two replies equal
(``probes_equal_among_traffic``); a reply must not depend on the
company its prompt had.  A relative 1.6e-3 in four first tokens failed
one run in five (PERF.md section 6, PR 39).

A cell at a time, at its published widths, its deployment's slots,
cache and chunk width, random weights: eight rows hold some 300 tokens
(two chunks where a chunk is 512), a ninth slot two chunks of a prompt.
Then from the SAME cache (lengths put back, a linear layer's states and
conv tails restored) the prompt's third chunk runs alone, in a mixed
step beside nobody, in a mixed step beside the eight rows, and padded
to 20 real tokens alone and beside them; the eight rows decode alone
and in that mixed step.  Compared, as counts of float32 / bfloat16
patterns that differ: the chunk's logits, the rows it wrote a layer
(slabs, rings) and the states it left; the decode rows' logits, written
rows and states.  All zeros is the proof; the first layer that differs
says where to look (layer 0's keys are norm, product and rotation; a
count of a few in half a million is a float32 sum in another order, a
count of thousands a rounding one program skips).

``--compile no_excess`` compiles the three programs with
``xla_allow_excess_precision=false`` beside the default: what differs
with the default and not without it is a rounding the compiler left out
in one program and not in the other (``llama._swiglu``).

As measured (PR 39, PERF.md section 6): every row bit-equal in the five
cells whose chunk is 64 tokens (76–112 rows a mixed step); NOT in the
two whose chunk is 512 — Command A+'s chunk rows differ with either
setting (8 of 524,288 keys in the first layer, two fifths of them three
layers on), Solar Open 2's states with the default and its decode rows
with either — which is why the engine lets a chunk ride only up to
``engine.RIDE_ROWS`` rows a step.  The exit code is 1 where a row
differs, so those two cells fail here by design until that is cured.
"""

from __future__ import annotations

import argparse
import json

CELLS = ("internlm2-1.8b.chat", "mistral-7b.decode", "olmoe-1b-7b.rollout",
         "ax-k1.reason", "command-a-plus.docqa", "solar-open2.digest")
OPTIONS = {"default": None,
           "no_excess": {"xla_allow_excess_precision": False}}
ROWS = 8                         # rows that decode


def _bits(x):
    import jax.numpy as jnp
    import numpy as np

    return np.asarray(jnp.asarray(x).astype(jnp.float32)).view(np.uint32)


def _differ(a, b) -> int:
    return int((_bits(a) != _bits(b)).sum())


def run_cell(name: str, label: str) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ant_ray_tpu.models import llama
    from chipbench.spec import Cell, resolve

    cell = Cell(name)
    spec, traffic = cell.config, cell.traffic
    c = resolve(spec["model"]["factory"])(spec)
    slots, max_seq = traffic["slots"], traffic["max_seq"]
    chunk = spec["serve"]["kwargs"].get("prefill_chunk_tokens", 64)
    rows = min(ROWS, slots - 1)
    params = jax.jit(llama.init_params, static_argnums=0)(
        c, jax.random.PRNGKey(5))
    rng = np.random.default_rng(7)
    held_tokens = 320 if chunk == 64 else 2 * chunk
    kw = {"compiler_options": OPTIONS[label]} if OPTIONS[label] else {}
    chunk_p = jax.jit(
        lambda p, cache, t, slot, start, n: llama.prefill_chunk_into_cache(
            p, t, cache, slot, start, n, c), donate_argnums=(1,), **kw)
    decode_p = jax.jit(
        lambda p, cache, last, act: llama.decode_step(p, last, cache, c, act),
        donate_argnums=(1,), **kw)
    mixed_p = jax.jit(
        lambda p, cache, last, act, t, slot, start, n: llama.mixed_step(
            p, last, t, cache, c, act, slot, start, n),
        donate_argnums=(1,), **kw)

    slabs, states = tuple(llama.kv_slabs(c)), tuple(llama.state_slabs(c))
    cache = llama.init_kv_cache(c, slots, max_seq, chunk)
    for slot in range(rows):
        toks = rng.integers(0, c.vocab_size, held_tokens).astype(np.int32)
        for at in range(0, held_tokens, chunk):
            _, cache = chunk_p(params, cache, jnp.asarray(toks[at:at + chunk]),
                               slot, at, chunk)
    prompt = rng.integers(0, c.vocab_size, 3 * chunk).astype(np.int32)
    for at in (0, chunk):
        _, cache = chunk_p(params, cache, jnp.asarray(prompt[at:at + chunk]),
                           rows, at, chunk)
    length = np.asarray(cache["length"])
    kept = {n: np.asarray(cache[n]) for n in states}   # small; slabs stay
    third = jnp.asarray(prompt[2 * chunk:])
    last = jnp.asarray(rng.integers(0, c.vocab_size, slots), jnp.int32)
    on = jnp.asarray([True] * rows + [False] * (slots - rows))
    off = jnp.zeros((slots,), bool)

    def back(cache):
        """The cache as it was before the third chunk: a slab's rows
        behind a length are never read, states and tails are."""
        return {**cache, "length": jnp.asarray(length),
                **{n: jnp.asarray(v) for n, v in kept.items()}}

    def chunk_rows(cache, n=chunk):
        at = 2 * chunk
        return ([np.asarray(cache[s][:, rows, at:at + n].astype(jnp.float32))
                 for s in slabs if cache[s].shape[2] == max_seq]
                + [np.asarray(cache[s][:, rows].astype(jnp.float32))
                   for s in states])

    def decode_rows(cache):
        return ([np.stack([np.asarray(cache[s][:, r, held_tokens].astype(
            jnp.float32)) for r in range(rows)], 1)
                 for s in slabs if cache[s].shape[2] == max_seq]
                + [np.asarray(cache[s][:, :rows].astype(jnp.float32))
                   for s in states])

    def by_layer(one, two):
        return [[int((a[i] != b[i]).sum()) for i in range(a.shape[0])]
                for a, b in zip(one, two)]

    lg_alone, cache = chunk_p(params, back(cache), third, rows, 2 * chunk,
                              chunk)
    lg_alone, r_alone = np.asarray(lg_alone), chunk_rows(cache)
    _, lg_empty, cache = mixed_p(params, back(cache), last, off, third, rows,
                                 2 * chunk, chunk)
    lg_empty, r_empty = np.asarray(lg_empty), chunk_rows(cache)
    dl_mixed, lg_beside, cache = mixed_p(params, back(cache), last, on, third,
                                         rows, 2 * chunk, chunk)
    lg_beside, r_beside = np.asarray(lg_beside), chunk_rows(cache)
    dl_mixed, d_mixed = np.asarray(dl_mixed[:rows]), decode_rows(cache)
    dl_alone, cache = decode_p(params, back(cache), last, on)
    dl_alone, d_alone = np.asarray(dl_alone[:rows]), decode_rows(cache)
    lg_pad, cache = chunk_p(params, back(cache), third, rows, 2 * chunk, 20)
    lg_pad, r_pad = np.asarray(lg_pad), chunk_rows(cache, 20)
    _, lg_pad_mixed, cache = mixed_p(params, back(cache), last, on, third,
                                     rows, 2 * chunk, 20)
    r_pad_mixed = chunk_rows(cache, 20)
    del cache
    out = {
        "cell": name, "compile": label, "slots": slots, "chunk": chunk,
        "chunk_logits": {
            "alone_vs_beside_nobody": _differ(lg_alone, lg_empty),
            "alone_vs_beside_rows": _differ(lg_alone, lg_beside),
            "padded_alone_vs_beside_rows": _differ(lg_pad, lg_pad_mixed),
            "rel": float(np.linalg.norm(lg_alone - lg_beside)
                         / np.linalg.norm(lg_alone)),
            "of": int(lg_alone.size)},
        "chunk_rows_by_layer": {
            "alone_vs_beside_rows": by_layer(r_alone, r_beside),
            "beside_nobody_vs_beside_rows": by_layer(r_empty, r_beside),
            "padded_alone_vs_beside_rows": by_layer(r_pad, r_pad_mixed),
            "of_a_layer": [int(r[0].size) for r in r_alone]},
        "decode_logits": {
            "alone_vs_mixed": _differ(dl_alone, dl_mixed),
            "rel": float(np.linalg.norm(dl_alone - dl_mixed)
                         / np.linalg.norm(dl_alone)),
            "of": int(dl_alone.size)},
        "decode_rows_by_layer": {
            "alone_vs_mixed": by_layer(d_alone, d_mixed),
            "of_a_layer": [int(r[0].size) for r in d_alone]},
    }
    counts = [out["chunk_logits"][k] for k in (
        "alone_vs_beside_nobody", "alone_vs_beside_rows",
        "padded_alone_vs_beside_rows")] + [
        out["decode_logits"]["alone_vs_mixed"]] + [
        n for key in ("alone_vs_beside_rows", "padded_alone_vs_beside_rows")
        for layers in out["chunk_rows_by_layer"][key] for n in layers] + [
        n for layers in out["decode_rows_by_layer"]["alone_vs_mixed"]
        for n in layers]
    out["bit_equal"] = not any(counts)
    return out


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--cells", default=",".join(CELLS))
    parser.add_argument("--compile", default="default")
    args = parser.parse_args()
    equal = True
    for name in args.cells.split(","):
        for label in args.compile.split(","):
            out = run_cell(name, label)
            equal &= out["bit_equal"]
            print("[bits] " + json.dumps(out), flush=True)
    print("[bits] " + ("EVERY ROW HAS ITS OWN PROGRAM'S BITS" if equal
                       else "SOME ROWS DIFFER"), flush=True)
    return 0 if equal else 1


if __name__ == "__main__":
    raise SystemExit(main())
