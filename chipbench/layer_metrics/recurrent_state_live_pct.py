"""Model step (a recurrent state a slot beside the slabs): of the
states a decode step's program read and wrote over the traced window —
every slot's, in every linear layer,
``LLMEngine.stats["recurrent_slot_rows"]`` — the share that belonged to
rows the step DECODED, ``recurrent_decode_rows``; deltas between the
owner's readings at trace start and stop.  The rest is traffic for
rows that sat the step out (free slots, rows between two chunks of
their own prompt), which a step that took the active rows alone would
not pay: 4 MiB a slot-layer each way.  100 with every slot decoding.
A program without the counters (before PR 38), or a model without
linear layers (they stay at zero), reports nothing."""

from chipbench.layer_metrics.loop_host_ms_per_step import deltas


def read(obs):
    found = deltas(obs, "recurrent_decode_rows", "recurrent_slot_rows")
    if not found or found[1] <= 0:
        return None
    return 100.0 * found[0] / found[1]
