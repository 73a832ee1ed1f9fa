"""Engine loop: of the prefill chunks dispatched over the traced
window, the share that rode a decode step — one program for the chunk's
rows and the decoding rows, the weights read once for both:
``LLMEngine.stats["chunks_fused"]`` over ``chunks``; deltas between the
owner's readings at trace start and stop.  100 means every chunk found
rows decoding (a closed loop at its batch); 0 that every chunk ran
alone (an idle engine's prompt).  A program that runs a chunk and a
decode step as two programs has no such counter, and the metric is
left out; so it is where the window held no chunk."""

from chipbench.layer_metrics.loop_host_ms_per_step import deltas


def read(obs):
    found = deltas(obs, "chunks_fused", "chunks")
    if not found or found[1] <= 0:
        return None
    return 100.0 * found[0] / found[1]
