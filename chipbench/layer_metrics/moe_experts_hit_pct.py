"""Model step (routed experts): of the experts there were in the step
programs' executions over the traced window (``num_experts`` a layer, a
decode step or a prefill chunk), the share that was given at least one
row, so whose weights the grouped product read:
``LLMEngine.stats["moe_experts_hit"]`` over ``moe_expert_slots``; deltas
between the owner's readings at trace start and stop.  The program
counts on the device, in the cache, and a decode step's one read brings
the counters with its tokens.  16 slots x 8 picks over 64 experts,
independent and uniform, hit 1 - (63/64)^128 = 86.7 %; a routing that
has frozen onto few experts reads far less."""

from chipbench.layer_metrics.loop_host_ms_per_step import deltas


def read(obs):
    found = deltas(obs, "moe_experts_hit", "moe_expert_slots")
    if not found or found[1] <= 0:
        return None
    return 100.0 * found[0] / found[1]
