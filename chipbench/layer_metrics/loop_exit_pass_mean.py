"""Model step: the pass a decoded row is EXPECTED to leave a looped
model's stack behind, under the exit distribution its gates give
(``p_u = lambda_u * prod_{j<u} (1 - lambda_j)``, the last pass's the
remainder): ``LLMEngine.stats["exit_pass_sum"]`` over ``exit_rows``,
deltas between the owner's readings at trace start and stop — the
active rows of the window's decode steps.  Between 1 and
``total_ut_steps``.  The program runs every row through every pass
whatever it reads (``early_exit_threshold`` 1): this is the size of the
lever an early exit would have, not a share of work saved; with seeded
random weights it says only that the gate ran on each pass's normed
state.  A program without the counters, or a window in which no row
decoded, reports nothing."""

from chipbench.layer_metrics.loop_host_ms_per_step import deltas


def read(obs):
    found = deltas(obs, "exit_pass_sum", "exit_rows")
    if not found or found[1] <= 0:
        return None
    return found[0] / found[1]
