"""Model step (routed experts): of the rows the grouped product
multiplied over the traced window — the row tiles its work list
visited, ``LLMEngine.stats["moe_tile_rows"]``: tile x (row tile, expert)
visits, summed over layers and executions — the share that were
(row, expert) pairs it was asked for, ``moe_assignments``; deltas
between the owner's readings at trace start and stop.  100 would be a
product that multiplies no row but its own; XLA's ``ragged-dot`` kernel,
whose row tile is the operand's row count, filled 0.7 % of a 64-token
chunk's tiles on a share of 12 of 192 experts.  A program without the
counter (before PR 37), or whose grouped product is XLA's (the counter
stands still), reports nothing."""

from chipbench.layer_metrics.loop_host_ms_per_step import deltas


def read(obs):
    found = deltas(obs, "moe_assignments", "moe_tile_rows")
    if not found or found[1] <= 0:
        return None
    return 100.0 * found[0] / found[1]
