"""Model step: of the positions a decode step's attention would read
with every slot walked as far as the longest active row, the share the
dispatched program reads, over the traced window:
``LLMEngine.stats["decode_read_positions"]`` (per step: each ACTIVE
row's own whole blocks where the rows attend through
``ops/pallas/decode_attention.py``, the walk's where they walk in XLA)
over ``decode_walk_positions`` (per step: slots x the longest active
row's whole blocks); deltas between the owner's readings at trace start
and stop.  100 means the step read what the bound-by-the-longest walk
reads; a program without the counters has no reading, and the metric
is left out."""

from chipbench.layer_metrics.loop_host_ms_per_step import deltas


def read(obs):
    found = deltas(obs, "decode_read_positions", "decode_walk_positions")
    if not found or found[1] <= 0:
        return None
    return 100.0 * found[0] / found[1]
