"""Engine loop: blocking device->host reads per decode step over the
traced window: ``LLMEngine.stats["d2h_syncs"]`` (every such read of the
engine goes through one helper that counts it) over ``decode_steps``;
deltas between the owner's readings at trace start and stop."""

from chipbench.layer_metrics.loop_host_ms_per_step import deltas


def read(obs):
    found = deltas(obs, "d2h_syncs", "decode_steps")
    if not found or found[1] <= 0:
        return None
    return found[0] / found[1]
