"""Model step: the share of a slot's reserved positions that a decode
step's attention walks, over the traced window:
``LLMEngine.stats["decode_span_positions"]`` (per step, whole blocks up
to the longest active row) over ``decode_slab_positions`` (per step,
the slab's ``max_seq``); deltas between the owner's readings at trace
start and stop.  100 means every step read every reserved position, as
a program without the walk does: it has no such counters, and the
metric is left out."""

from chipbench.layer_metrics.loop_host_ms_per_step import deltas


def read(obs):
    found = deltas(obs, "decode_span_positions", "decode_slab_positions")
    if not found or found[1] <= 0:
        return None
    return 100.0 * found[0] / found[1]
