"""Model step (routed experts held as a share): of the (row, expert)
pairs the routers made over the traced window — rows x
``num_experts_per_tok``, over ALL the experts the router scores — the
share that fell on experts this chip holds, so that it multiplied for
them: ``LLMEngine.stats["moe_assignments"]`` over ``moe_rows_routed``;
deltas between the owner's readings at trace start and stop.  12 held of
192 read 6.25 % under an even router; 100 % where every expert is held.
It guards that the routing is over the router's whole width and the
products over the experts held: a router narrowed to the held experts
would read 100.  A program without the counter (before PR 32) reports
nothing."""

from chipbench.layer_metrics.loop_host_ms_per_step import deltas


def read(obs):
    found = deltas(obs, "moe_assignments", "moe_rows_routed")
    if not found or found[1] <= 0:
        return None
    return 100.0 * found[0] / found[1]
