"""Kernels (program level: the gated short convolution is element-wise
work XLA fuses beside the two projections, and at a head of 64 lanes
the softmax layers' slabs are walked by XLA, no kernel of their own):
the least time a decode step of a model with gated short-convolution
and softmax layers, leading dense layers and routed experts all held
could take over the time it took.  Required bytes (``opsbytes_conv``:
every weight outside the routed experts once, the tied embedding once
as the head, of the routed experts those HIT; for each row DECODED its
convolution tail read and written once in every conv layer; of the
slabs the softmax layers' live positions: what is read, not what is
reserved or touched) over the chip's HBM bandwidth, against required
operations over its bf16 peak; the larger is the bound; over
``decode_step_ms``.  The hit share is that of the window's DECODE
STEPS, which the program counts apart (``moe_decode_*``).  Contexts are
those of the client's log over the traced window.  A program without
the counters, a window without a decode step, or a configuration
without ``conv_L_cache`` (a program before PR 65 cannot run the model
either) reports nothing."""

from chipbench import opsbytes_conv
from chipbench.layer_metrics import decode_step_ms
from chipbench.layer_metrics.decode_hbm_roofline_pct import contexts_at
from chipbench.layer_metrics.loop_host_ms_per_step import deltas


def read(obs):
    step_ms = decode_step_ms.read(obs)
    traced, client, peaks = obs.get("traced"), obs.get("client"), \
        obs.get("peaks")
    found = deltas(obs, "moe_decode_experts_hit", "moe_decode_expert_slots")
    if not step_ms or not traced or not client or not peaks or not found \
            or found[1] <= 0 \
            or deltas(obs, "recurrent_decode_rows") is None \
            or "conv_L_cache" not in (obs.get("config") or {}):
        return None
    t1 = traced["wall"] - obs["window_wall"]
    t0 = t1 - traced["host_window_s"]
    samples = [opsbytes_conv.decode_step(
        obs["config"],
        contexts_at(client["requests"], t0 + (t1 - t0) * i / 8),
        found[0] / found[1]) for i in range(1, 8)]
    samples = [s for s in samples if s["tail_bytes"] > 0]
    if not samples:
        return None
    least = sum(max(s["bytes"] / peaks["hbm_bytes_per_s"],
                    s["flops"] / peaks["bf16_flops_per_s"])
                for s in samples) / len(samples)
    return 100.0 * least / (step_ms / 1000.0)
