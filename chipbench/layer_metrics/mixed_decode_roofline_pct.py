"""Kernels (program level: the walk over slabs and rings is XLA's
products, no kernel of its own): the least time a decode step of a
model with window and full layers, routed and held as a share, could
take over the time it took.  Required bytes (``opsbytes_mixed``: every
weight held once, the tied embedding once — of the routed experts those
HIT; of the cache the full layers' live positions and the window
layers' newest ``sliding_window`` at most: what is read, not what is
reserved) over the chip's HBM bandwidth, against required operations
over its bf16 peak; the larger is the bound; over ``decode_step_ms``.
The hit share and the local share are those of the window's DECODE
STEPS, which the program counts apart (``moe_decode_*``).  Contexts are
those of the client's log over the traced window.  A program without
the counters reports nothing."""

from chipbench import opsbytes_mixed
from chipbench.layer_metrics import decode_step_ms
from chipbench.layer_metrics.decode_hbm_roofline_pct import contexts_at
from chipbench.layer_metrics.loop_host_ms_per_step import deltas


def read(obs):
    step_ms = decode_step_ms.read(obs)
    traced, client, peaks = obs.get("traced"), obs.get("client"), \
        obs.get("peaks")
    found = deltas(obs, "moe_decode_experts_hit", "moe_decode_expert_slots",
                   "moe_decode_assignments", "moe_decode_rows_routed")
    if not step_ms or not traced or not client or not peaks or not found \
            or found[1] <= 0 or found[3] <= 0 \
            or deltas(obs, "window_span_positions") is None:
        return None
    hit = found[0] / found[1]
    local = obs["config"]["num_experts_per_tok"] * found[2] / found[3]
    t1 = traced["wall"] - obs["window_wall"]
    t0 = t1 - traced["host_window_s"]
    samples = [opsbytes_mixed.decode_step(
        obs["config"],
        contexts_at(client["requests"], t0 + (t1 - t0) * i / 8), hit, local)
        for i in range(1, 8)]
    samples = [s for s in samples if s["attention_flops"] > 0]
    if not samples:
        return None
    least = sum(max(s["bytes"] / peaks["hbm_bytes_per_s"],
                    s["flops"] / peaks["bf16_flops_per_s"])
                for s in samples) / len(samples)
    return 100.0 * least / (step_ms / 1000.0)
