"""Kernels: required operations of a prefill chunk over the chip's bf16
peak, over ``prefill_chunk_ms``.  The chunk is the mean one of the
traced window: its real tokens from the engine's counters
(``chunk_tokens`` / ``chunks``), its start from the prompts of the
client's log and the chunk width the engine reports.  At 64 tokens a
chunk the lower bound is the weights' bandwidth, not the MXU: PERF.md
gives both."""

from chipbench import opsbytes
from chipbench.layer_metrics import prefill_chunk_ms



def mean_start(prompt_lengths, width: int) -> float:
    starts = [s for n in prompt_lengths for s in range(0, n, width)]
    return sum(starts) / len(starts) if starts else 0.0


def read(obs):
    chunk_ms = prefill_chunk_ms.read(obs)
    traced, client, peaks = obs.get("traced"), obs.get("client"), \
        obs.get("peaks")
    if not chunk_ms or not traced or not client or not peaks:
        return None
    chunks = traced["engine"]["chunks"] - traced["engine_before"]["chunks"]
    tokens = traced["engine"]["chunk_tokens"] \
        - traced["engine_before"]["chunk_tokens"]
    if not chunks:
        return None
    need = opsbytes.prefill_chunk(
        obs["config"], mean_start((n for n, _ in client["requests"]),
                                  traced["chunk_width"]),
        tokens / chunks)
    return 100.0 * need["flops"] / peaks["bf16_flops_per_s"] \
        / (chunk_ms / 1000.0)
