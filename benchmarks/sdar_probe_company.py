"""Does a greedy reply of `sdar-30b-a3b` depend on the company its
prompt had?  On the chip, one process, the engine as the cell's replica
builds it (published widths, 48 slots x 4,096, weights from ``--seed``).

    python -m benchmarks.sdar_probe_company --seed 1551952836 \
        [--probes 16] [--out chiprun_out/company.json]

The benchmark answers its four greedy probes alone in set-up and again
among the traffic and holds the replies equal
(``probes_equal_among_traffic``).  This script does the same through
``LLMEngine`` itself — each probe (``--probes``: the benchmark's four,
then more of their kind) alone on the idle engine, then among 47 sampled
requests that come and go (so chunks ride the block steps), then alone
again in the slot it had among them — and, where a pair differs, says
WHERE: the prompt's stored keys by layer and, of the first layer that
differs, by position; per block step of the probe's row whether a chunk
rode it (``decode`` / ``mixed``: one program either way) and how many
bits of its four rows' logits differ.  Exit code 1 where a reply among
the company is not the reply alone.

Since PR 55 a block's store pass rides its successor's first step: what
a step was FED is the block in flight, its masks, and the block closing
where the slot has one — all three are recorded and compared, so that a
closing block's tokens or its timing differing between alone and in
company would show as ``fed_equal`` false at that step.

As measured (PR 54, PERF.md section 6): with a chunk program, a block
program and a mixed step of their own (64, 192 and 256 rows) one
row-layer in about a thousand came out of the mixed step with another
bfloat16 in ONE element of the residual behind the attention, 7 of 16
probes differed somewhere and the driver's seed 1551952836 gave probe 2
another reply; through ONE program (``LLMEngine._block_programs``) 16 of
16 are bit-equal at every step, layer and position.  PR 55's 448-row
program (the closing blocks' rows behind the blocks in flight): PERF.md
section 6, PR 55.
"""

from __future__ import annotations

import argparse
import json
import os


def _bits(x):
    import numpy as np

    return np.asarray(x, np.float32).view(np.uint32)


class Watch:
    """Records, for ONE sequence, what every block step fed its slot and
    the logits the step gave its rows (kept on the device until read)."""

    def __init__(self, eng):
        self.eng, self.seq, self.steps, self.prompt_kv = eng, None, [], None
        self.chunks = []
        self._mixed = eng._mixed_step_jit
        eng._mixed_step_jit = self._wrap(self._mixed)
        alone = eng._prefill_chunk_jit

        def chunk_alone(params, cache, tokens, slot, start, n):
            self._chunk("alone", slot, start, n)
            return alone(params, cache, tokens, slot, start, n)

        eng._prefill_chunk_jit = chunk_alone

    def follow(self, seq):
        self.seq, self.steps, self.prompt_kv = seq, [], None
        self.chunks = []

    def _chunk(self, kind, slot, start, n):
        """The program a chunk of the followed prompt ran in, and its
        company: the rows that decoded beside it, the longest of them."""
        seq, eng = self.seq, self.eng
        if seq is not None and seq.slot == slot and seq in eng._prefilling:
            held = [s.kv_len for s in eng._active.values()]
            self.chunks.append({
                "program": kind, "slot": int(slot), "start": int(start),
                "tokens": int(n), "rows_beside": len(held),
                "longest_beside": max(held, default=0)})

    def _wrap(self, program):
        def run(params, cache, blocks, masked, closed, closing, active,
                *chunk):
            seq, eng = self.seq, self.eng
            # every step is the one program; no chunk is a chunk of no
            # token in the slot behind the last
            kind = "mixed" if int(chunk[1]) < eng.slots else "decode"
            if kind == "mixed":
                self._chunk(kind, *chunk[1:])
            mine = (seq is not None and seq.slot is not None
                    and eng._active.get(seq.slot) is seq)
            if mine:
                slot, size = seq.slot, eng._block
                if self.prompt_kv is None:
                    n = len(seq.prompt) - len(seq.prompt) % size
                    self.prompt_kv = (cache["k"][:, slot, :n],
                                      cache["v"][:, slot, :n])
                fed = (blocks[slot], masked[slot], cache["length"][slot],
                       closing[slot], closed[slot])
            out = program(params, cache, blocks, masked, closed, closing,
                          active, *chunk)
            if mine:
                self.steps.append((kind, fed,
                                   out[0][slot * size:(slot + 1) * size]))
            return out
        return run


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seed", type=int, default=1551952836)
    parser.add_argument("--company", type=int, default=47)
    parser.add_argument("--probes", type=int, default=None,
                        help="as many probes (the benchmark's four first, "
                        "then more of their kind)")
    parser.add_argument("--out", default=None)
    parser.add_argument("--tiny", action="store_true",
                        help="the preset sdar-tiny at a small size: a dry "
                        "run of this script on the CPU, no reading")
    args = parser.parse_args(argv)

    import numpy as np

    from ant_ray_tpu.llm.engine import LLMEngine
    from ant_ray_tpu.llm.sampling import SamplingParams
    from chipbench import loadgen
    from chipbench.spec import Cell, resolve

    cell = Cell("sdar-30b-a3b.reason")
    spec, traffic = cell.config, cell.traffic
    if args.tiny:
        traffic = {**traffic, "slots": 6, "max_seq": 256,
                   "prompt_tokens": {"median": 40, "sigma": 0.5, "min": 8,
                                     "max": 100}}
        eng = LLMEngine("sdar-tiny", slots=6, max_seq=256, seed=args.seed,
                        prefill_chunk_tokens=16)
    else:
        eng = LLMEngine(resolve(spec["model"]["factory"])(spec),
                        slots=traffic["slots"], max_seq=traffic["max_seq"],
                        seed=args.seed, **spec["serve"]["kwargs"])
    vocab = eng.config.vocab_size
    if args.probes:
        traffic = {**traffic, "probe_prompts": {
            **traffic["probe_prompts"], "count": args.probes}}
    probes = loadgen.probe_requests(traffic, vocab)
    greedy = SamplingParams(max_tokens=probes[0].max_tokens, temperature=0.0)
    watch = Watch(eng)

    def run_probe(i, more=lambda: None, slot=None):
        """One probe to its end -> what was seen of it; ``slot``: the
        free slot it is to take (the engine hands out the last)."""
        if slot is not None:
            eng._free_slots.remove(slot)
            eng._free_slots.append(slot)
        rid = eng.add_request(probes[i].prompt, greedy, f"probe-{i}",
                              admit=False)
        watch.follow(eng._waiting[-1])
        while True:
            more()
            for out in eng.step():
                live.discard(out.request_id)
                if out.request_id == rid:
                    seen = {
                        "tokens": list(out.token_ids),
                        "steps": [(kind, [np.asarray(f) for f in fed],
                                   np.asarray(lg))
                                  for kind, fed, lg in watch.steps],
                        "kv": [np.asarray(x.astype("float32"))
                               for x in watch.prompt_kv],
                        "chunks": watch.chunks}
                    watch.follow(None)
                    return seen

    # company: sampled requests of the mix's prompt lengths that come
    # and go, so that chunks ride the block steps the probes are in
    rng = np.random.default_rng([args.seed, 3])
    live, made = set(), [0]
    p = traffic["prompt_tokens"]

    def more():
        while len(live) < min(args.company, eng.slots - 1):
            n = int(np.clip(rng.lognormal(np.log(p["median"]), p["sigma"]),
                            p["min"], p["max"]))
            made[0] += 1
            live.add(eng.add_request(
                rng.integers(0, vocab, n).tolist(),
                SamplingParams(max_tokens=int(rng.integers(48, 400)),
                               temperature=1.0, seed=made[0]),
                f"bg-{made[0]}", admit=False))

    def settle(steps):
        for _ in range(steps):
            more()
            for out in eng.step():
                live.discard(out.request_id)

    def drain():
        while eng.has_unfinished():
            for out in eng.step():
                live.discard(out.request_id)

    def compare(one, two) -> dict:
        """``one`` against ``two`` (the reference of the pair)."""
        differ = [_bits(x) != _bits(y)
                  for x, y in zip(one["kv"], two["kv"])]
        by_layer = [int(x.sum()) for x in differ[0]]
        first = next((n for n, x in enumerate(by_layer) if x), None)
        steps = []
        for j, ((kind, fed, lg), (_, a_fed, a_lg)) in enumerate(
                zip(one["steps"], two["steps"])):
            # the block in flight, its masks, whether a block is
            # closing and, where one is, its tokens
            same_fed = all(np.array_equal(x, y) for x, y in zip(
                fed[:2] + fed[3:4 + bool(fed[3])],
                a_fed[:2] + a_fed[3:4 + bool(a_fed[3])]))
            steps.append({
                "step": j, "program": kind, "fed_equal": bool(same_fed),
                "length": int(fed[2]), "masks": int(fed[1].sum()),
                "closing": bool(fed[3]),
                "logit_bits": int((_bits(lg) != _bits(a_lg)).sum()),
                "rel": float(np.linalg.norm(lg - a_lg)
                             / np.linalg.norm(a_lg))})
            if not same_fed:
                break                     # from here on another reply
        return {"tokens_equal": one["tokens"] == two["tokens"],
                # keys (layers, positions, heads, head_dim), then values
                "prompt_kv_bits": [int(d.sum()) for d in differ],
                "prompt_k_bits_by_layer": by_layer,
                "first_layer_positions": None if first is None else {
                    int(at): int(n) for at, n in enumerate(
                        differ[0][first].sum(axis=(1, 2))) if n},
                "programs": "".join(s["program"][0] for s in steps),
                "first_differing_step": next(
                    (s for s in steps if s["logit_bits"]), None)}

    # A: each probe alone, on the idle engine
    alone = [run_probe(i) for i in range(len(probes))]
    drain()
    # B: among the company
    among = []
    for i in range(len(probes)):
        settle(20 if args.tiny else 150)
        among.append(run_probe(i, more))
    # C: alone again, in the slot it had among the company (with what
    # that slot's occupants left behind the prompt)
    args.company = 0
    drain()
    again = [run_probe(i, slot=among[i]["chunks"][0]["slot"])
             for i in range(len(probes))]
    drain()
    rows, equal = [], True
    for i in range(len(probes)):
        row = {"probe": i, "alone": alone[i]["tokens"],
               "among": among[i]["tokens"],
               "alone_again": again[i]["tokens"],
               "chunks": {"alone": alone[i]["chunks"],
                          "among": among[i]["chunks"],
                          "alone_again": again[i]["chunks"]},
               "among_vs_alone": compare(among[i], alone[i]),
               "alone_again_vs_alone": compare(again[i], alone[i]),
               "alone_again_vs_among": compare(again[i], among[i])}
        print("[company] " + json.dumps(row), flush=True)
        rows.append(row)
        equal &= row["among_vs_alone"]["tokens_equal"]
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"seed": args.seed, "rows": rows}, f)
    return 0 if equal else 1


if __name__ == "__main__":
    raise SystemExit(main())
