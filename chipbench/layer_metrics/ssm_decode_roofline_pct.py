"""Kernels (program level: the state-space recurrence's step and the
walk over the softmax layer's slabs are XLA's, no kernel of their own
yet): the least time a decode step of a model with state-space and
softmax layers, routed and held as a share, could take over the time it
took.  Required bytes (``opsbytes_ssm``: every weight held outside the
routed experts once, the tied embedding's slice once as the head, of
the routed experts those HIT; for each row DECODED its state and
convolution tail read and written once in every state-space layer; of
the slabs the softmax layers' live positions: what is read, not what is
reserved or touched) over the chip's HBM bandwidth, against required
operations over its bf16 peak; the larger is the bound; over
``decode_step_ms``.  The hit share and the local share are those of the
window's DECODE STEPS, which the program counts apart
(``moe_decode_*``).  Contexts are those of the client's log over the
traced window.  A program without the recurrent counters (before PR 38;
one before PR 42 cannot run the model either) reports nothing."""

from chipbench import opsbytes_ssm
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
            or deltas(obs, "recurrent_decode_rows") is None \
            or "mamba_n_heads" not in obs["config"]:
        return None
    hit = found[0] / found[1]
    local = obs["config"]["num_experts_per_tok"] * found[2] / found[3]
    t1 = traced["wall"] - obs["window_wall"]
    t0 = t1 - traced["host_window_s"]
    samples = [opsbytes_ssm.decode_step(
        obs["config"],
        contexts_at(client["requests"], t0 + (t1 - t0) * i / 8), hit, local)
        for i in range(1, 8)]
    samples = [s for s in samples if s["state_bytes"] > 0]
    if not samples:
        return None
    least = sum(max(s["bytes"] / peaks["hbm_bytes_per_s"],
                    s["flops"] / peaks["bf16_flops_per_s"])
                for s in samples) / len(samples)
    return 100.0 * least / (step_ms / 1000.0)
