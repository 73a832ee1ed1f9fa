"""A decode step and a 64-token chunk of ``llama3-1b`` (8 KV heads of 64
lanes: what ``chip_smoke.py`` serves) at 8 slots x 2048, every slot
1,024 tokens deep, through the engine's own programs.

    python -m benchmarks.llama3_1b_step <label>      # on the chip, ~1 min

One JSON line: the slabs' shape, ms a decode step over 200 steps, the
median ms of a chunk, the device's peak GiB.  To read another tree,
run this file there with ``PYTHONPATH=.``.  PR 65 (my chip runs): with
a heads axis (16, 8, 2048, 8, 64) a step took 47.9 ms and a chunk 49.3 —
the compiler re-laid both slabs on the way in and out of every program
— with the heads side by side (``LlamaConfig.flat_kv_heads``: (16, 8,
2048, 512)) 4.72 and 5.72, the same logits."""
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from ant_ray_tpu.llm import LLMEngine
from ant_ray_tpu.models import llama

SLOTS, MAX_SEQ, DEPTH, STEPS = 8, 2048, 1024, 200


def main(label: str) -> int:
    cfg = llama.CONFIGS["llama3-1b"]
    eng = LLMEngine(cfg, slots=SLOTS, max_seq=MAX_SEQ, seed=5)
    rng = np.random.default_rng(5)
    chunk, chunks = eng._chunk_tokens, []

    def ids(n):
        return jnp.asarray(rng.integers(0, cfg.vocab_size, n, dtype=np.int32))

    for slot in range(SLOTS):
        for start in range(0, DEPTH, chunk):
            tokens = ids(chunk)
            t0 = time.perf_counter()
            logits, eng.cache = eng._prefill_chunk_jit(
                eng.params, eng.cache, tokens, slot, start, chunk)
            jax.block_until_ready(logits)
            chunks.append(time.perf_counter() - t0)
    active, last = jnp.ones((SLOTS,), bool), ids(SLOTS)
    for n in (5, STEPS):            # warm, then timed
        t0 = time.perf_counter()
        for _ in range(n):
            logits, eng.cache = eng._decode_jit(eng.params, eng.cache,
                                                last, active)
        jax.block_until_ready(logits)
        step = (time.perf_counter() - t0) / n
    stats = jax.devices()[0].memory_stats() or {}
    print(json.dumps({
        "tree": label, "k": list(eng.cache["k"].shape),
        "decode_step_ms": 1000 * step,
        "chunk_ms_median": 1000 * float(np.median(chunks[SLOTS:])),
        "peak_GiB": stats.get("peak_bytes_in_use", 0) / 2 ** 30,
        "logits_sum": float(jnp.sum(logits[:, :8].astype(jnp.float32)))}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main((sys.argv[1:] or ["here"])[0]))
