"""Kernels (a window layer's rings): of the ring positions a decode
step's attention would read with every slot's ring walked as far as
the longest active row, the share the dispatched program reads, over
the traced window: ``LLMEngine.stats["window_read_positions"]`` (per
step: window layers x each ACTIVE row's own whole ring blocks where the
rows attend through ``ops/pallas/decode_attention.py``, the walk's
where they walk in XLA) over ``window_walk_positions`` (per step:
window layers x slots x the longest active row's whole ring blocks);
deltas between the owner's readings at trace start and stop.  100 means
the step read what the walk reads; about 100 x live rows / slots once
every context has passed the ring.  A program without the counters, or
a model without window layers (they stay at zero), has no reading, and
the metric is left out."""

from chipbench.layer_metrics.loop_host_ms_per_step import deltas


def read(obs):
    found = deltas(obs, "window_read_positions", "window_walk_positions")
    if not found or found[1] <= 0:
        return None
    return 100.0 * found[0] / found[1]
