"""Model step (a recurrent state a slot beside the slabs): of the rows
the prefill-chunk program ran through its linear layers' block form
over the traced window — the chunk's whole width, in every linear
layer, ``LLMEngine.stats["recurrent_chunk_rows"]`` — the share that
were a prompt's real tokens, ``recurrent_chunk_tokens``; deltas between
the owner's readings at trace start and stop.  The rest is padding
behind a prompt's end (it neither decays nor writes, and is computed
all the same): a prompt of n tokens in chunks of 512 pays for
ceil(n / 512) * 512.  A program without the counters (before PR 38), a
model without linear layers or a window without a chunk reports
nothing."""

from chipbench.layer_metrics.loop_host_ms_per_step import deltas


def read(obs):
    found = deltas(obs, "recurrent_chunk_tokens", "recurrent_chunk_rows")
    if not found or found[1] <= 0:
        return None
    return 100.0 * found[0] / found[1]
