"""Kernels (program level: the state-space recurrence's block form is
XLA's, no kernel of its own yet): the least time the mean prefill chunk
of the traced window could take on a model with state-space and softmax
layers, routed and held as a share, over the time it took
(``prefill_chunk_ms``).  Required bytes (``opsbytes_ssm``: every weight
held outside the routed experts once, the tied embedding's slice once
as the head, of the routed experts those the chunks HIT, the slot's
state and tail read and written once in every state-space layer, the
softmax layers' live positions) over the chip's HBM bandwidth, against
required operations (the weights' products a real token, the
recurrence's two products with the state, the softmax layers' pairs)
over its bf16 peak; the larger is the bound.  The mean chunk: its real
tokens from ``recurrent_chunk_tokens`` over ``recurrent_chunk_rows``
times the width the engine reports, its start from the prompts of the
client's log; the experts hit and the local share are the chunks' own,
the step programs' counters less the decode steps' (they count the rows
the program computed, padding included: what it read).  A program
without the recurrent counters (before PR 38) reports nothing."""

from chipbench import opsbytes_ssm
from chipbench.layer_metrics import prefill_chunk_ms
from chipbench.layer_metrics.loop_host_ms_per_step import deltas
from chipbench.layer_metrics.prefill_mxu_roofline_pct import mean_start


def read(obs):
    chunk_ms = prefill_chunk_ms.read(obs)
    traced, client, peaks = obs.get("traced"), obs.get("client"), \
        obs.get("peaks")
    fill = deltas(obs, "recurrent_chunk_tokens", "recurrent_chunk_rows")
    moe = deltas(obs, "moe_experts_hit", "moe_decode_experts_hit",
                 "moe_expert_slots", "moe_decode_expert_slots",
                 "moe_assignments", "moe_decode_assignments",
                 "moe_rows_routed", "moe_decode_rows_routed")
    if not chunk_ms or not client or not peaks or not fill or not moe \
            or fill[1] <= 0 or "mamba_n_heads" not in obs["config"]:
        return None
    hit, slots, local, routed = (a - b for a, b in zip(moe[::2], moe[1::2]))
    if slots <= 0 or routed <= 0:
        return None
    width = traced["chunk_width"]
    need = opsbytes_ssm.prefill_chunk(
        obs["config"], mean_start((n for n, _ in client["requests"]), width),
        width * fill[0] / fill[1], hit / slots,
        obs["config"]["num_experts_per_tok"] * local / routed)
    least = max(need["bytes"] / peaks["hbm_bytes_per_s"],
                need["flops"] / peaks["bf16_flops_per_s"])
    return 100.0 * least / (chunk_ms / 1000.0)
