"""Engine loop: the share of decode steps dispatched while the step
before them was still unread, over the traced window:
``LLMEngine.stats["decode_ahead_steps"]`` over ``decode_steps``; deltas
between the owner's readings at trace start and stop.  100 means the
host's turn-around between two steps always ran under a device step;
a program that reads every step before it dispatches the next has no
such counter, and the metric is left out."""

from chipbench.layer_metrics.loop_host_ms_per_step import deltas


def read(obs):
    found = deltas(obs, "decode_ahead_steps", "decode_steps")
    if not found or found[1] <= 0:
        return None
    return 100.0 * found[0] / found[1]
