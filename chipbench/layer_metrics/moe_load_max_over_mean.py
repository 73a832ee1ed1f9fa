"""Model step (routed experts): how uneven the routing was over the
traced window — the rows of a layer's busiest expert over the rows of
its average expert, averaged over layers and step-program executions:
``LLMEngine.stats["moe_load_max"]`` (the busiest expert's rows, summed
over layers and executions) times ``num_experts`` over
``moe_assignments``; deltas between the owner's readings at trace start
and stop.  1.0 is a perfectly even routing; the grouped product's time
follows its busiest experts' tiles, not the mean."""

from chipbench.layer_metrics.loop_host_ms_per_step import deltas


def read(obs):
    found = deltas(obs, "moe_load_max", "moe_assignments")
    experts = (obs.get("config") or {}).get("num_experts")
    if not found or not experts or found[1] <= 0:
        return None
    return found[0] * experts / found[1]
