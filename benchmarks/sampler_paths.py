"""On the chip: what each of the sampler's three amounts of work costs
(``LLMEngine._sample_batch``: arg-max, plain draw, one sort) at the
serving cells' sampler shapes, beside the three sorts it replaced, and
whether a seeded batch's tokens are the ones the three sorts drew (the
share must be 100) — what ``tests/test_llm.py`` holds on the CPU at
2,048 columns, here at the cells' vocabularies.  Times are the device's
(``XLA Modules`` events of a profiler trace, median of the executions);
without a TPU there is no device time and the host's clock is printed
under its own name.  One JSON line a shape; through the chip tool, from
the root:

    python -m benchmarks.sampler_paths
"""

import json
import statistics
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from ant_ray_tpu.llm import LLMEngine
from ant_ray_tpu.models import llama
from chipbench import trace_reduce

# cell: (slots, vocabulary, what its traffic asks of an active row)
GREEDY, PLAIN, NUCLEUS = (0.0, 0, 1.0), (1.0, 0, 1.0), (0.7, 0, 0.9)
SHAPES = {
    "mistral-7b.decode": (16, 32768, [GREEDY]),
    "olmoe-1b-7b.rollout": (16, 50304, [PLAIN]),
    "ax-k1.reason": (48, 20480, [PLAIN]),
    "command-a-plus.docqa": (16, 32768, [PLAIN]),
    "internlm2-1.8b.chat": (12, 92544, [GREEDY, NUCLEUS]),
}
# every shape runs these; the cell's own mix is run as "cell"
PATHS = {
    "argmax": [GREEDY],
    "plain": [PLAIN, GREEDY],
    "sort_top_p": [NUCLEUS, GREEDY],
    "sort_top_k_top_p": [(0.8, 40, 0.95), PLAIN],
}
RUNS, BATCHES = 20, 8


def three_sorts(logits, keys, active, temps, top_ks, top_ps):
    """The sampler before PR 35 (the formula ``tests/test_llm.py``
    keeps as its reference)."""
    vocab = logits.shape[-1]
    split = jax.vmap(jax.random.split)(keys)
    next_keys = jnp.where(active[:, None], split[:, 0], keys)
    greedy = jnp.argmax(logits, axis=-1)
    scaled = logits / jnp.maximum(temps[:, None], 1e-6)
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    k_idx = jnp.clip(top_ks - 1, 0, vocab - 1)
    kth = jnp.take_along_axis(sorted_desc, k_idx[:, None], axis=-1)
    keep_k = (top_ks[:, None] <= 0) | (scaled >= kth)
    probs_sorted = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs_sorted, axis=-1)
    cutoff_rank = jnp.sum(cum < top_ps[:, None], axis=-1)
    ranks = jnp.argsort(jnp.argsort(-scaled, axis=-1), axis=-1)
    keep_p = ranks <= cutoff_rank[:, None]
    masked = jnp.where(keep_k & keep_p, scaled, -jnp.inf)
    sampled = jax.vmap(jax.random.categorical)(split[:, 1], masked)
    tokens = jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)
    return tokens, next_keys


def asked(slots, mix):
    """The sampling rows of ``slots`` active rows that ask ``mix`` in
    turn, as the engine hands them to the sampler."""
    temps, top_ks, top_ps = zip(*(mix[i % len(mix)] for i in range(slots)))
    return (jnp.ones((slots,), bool), jnp.asarray(temps, jnp.float32),
            jnp.asarray(top_ks, jnp.int32), jnp.asarray(top_ps, jnp.float32))


def device_ms(directory):
    """Device milliseconds of every program execution in the trace, in
    the order they ran: [(program, ms)]; empty without a TPU plane."""
    path = trace_reduce.newest_xplane(directory)
    if path is None:
        return []
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                events += [(e.start_ns, trace_reduce.program_name(e.name),
                            e.duration_ns / 1e6) for e in line.events]
    return [(name, ms) for _, name, ms in sorted(events)]


def measure(cell, sampler, reference):
    slots, vocab, mix = SHAPES[cell]
    paths = {**PATHS, "cell": mix}
    key = jax.random.PRNGKey(35)
    # the head's logits: bf16 products cast to float32, so ties abound
    logits = (3.0 * jax.random.normal(key, (BATCHES, slots, vocab))).astype(
        jnp.bfloat16).astype(jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(7), BATCHES * slots).reshape(
        BATCHES, slots, 2)
    equal = {}
    for path, rows in paths.items():
        args = asked(slots, rows)
        same = total = 0
        for batch in range(BATCHES):
            got, got_keys, _ = sampler(logits[batch], keys[batch], *args)
            want, want_keys = reference(logits[batch], keys[batch], *args)
            same += int((np.asarray(got) == np.asarray(want)).sum())
            same += int((np.asarray(got_keys) == np.asarray(want_keys)).all(
                axis=-1).sum())
            total += 2 * slots
        equal[path] = 100.0 * same / total

    # every program is compiled and every operand made by now: the
    # trace holds the two programs' executions only
    first, host = (logits[0], keys[0]), {}
    timed = [(path, reference if path == "three_sorts" else sampler,
              asked(slots, rows))
             for path, rows in [*paths.items(), ("three_sorts", mix)]]
    jax.block_until_ready((first, timed))
    with tempfile.TemporaryDirectory() as directory:
        jax.profiler.start_trace(directory)
        for path, fn, args in timed:
            start = time.perf_counter()
            for _ in range(RUNS):
                out = fn(*first, *args)
            jax.block_until_ready(out)
            host[path] = 1000.0 * (time.perf_counter() - start) / RUNS
        jax.profiler.stop_trace()
        ran = [(name, ms) for name, ms in device_ms(directory)
               if name.endswith(("_sample_batch", "three_sorts"))]
    line = {"cell": cell, "slots": slots, "vocab": vocab,
            "device": jax.devices()[0].device_kind,
            "tokens_and_keys_equal_pct": equal}
    if ran:
        assert len(ran) == RUNS * len(host), (len(ran), sorted(set(
            name for name, _ in ran)))
        line["device_ms"] = {
            path: statistics.median(ms for _, ms in ran[i * RUNS:][:RUNS])
            for i, path in enumerate(host)}
    else:
        line["host_clock_ms_no_device_time"] = host
    print(json.dumps(line), flush=True)
    return all(share == 100.0 for share in equal.values())


if __name__ == "__main__":
    print(jax.devices())
    tiny = llama.CONFIGS["tiny"]
    engine = LLMEngine(tiny, llama.init_params(tiny, jax.random.PRNGKey(0)),
                       slots=2, max_seq=32)
    reference = jax.jit(three_sorts)
    ok = [measure(cell, engine._sample_jit, reference) for cell in SHAPES]
    raise SystemExit(0 if all(ok) else 1)
