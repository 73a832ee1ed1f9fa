"""Kernels (program level: the gated short convolution over a chunk is
element-wise work XLA fuses beside the two projections, no kernel of
its own): the least time the mean prefill chunk of the traced window
could take on a model with gated short-convolution and softmax layers,
leading dense layers and routed experts all held, over the time it
took (``prefill_chunk_ms``).  Required bytes (``opsbytes_conv``: every
weight outside the routed experts once, the tied embedding once as the
head, of the routed experts those the chunks HIT, the slot's tail read
and written once in every conv layer, the softmax layers' live
positions) over the chip's HBM bandwidth, against required operations
(the weights' products a REAL token, a tap a multiply-add a channel,
the softmax layers' pairs) over its bf16 peak; the larger is the bound.
The mean chunk: its real tokens from ``recurrent_chunk_tokens`` over
``recurrent_chunk_rows`` times the width the engine reports — most of
this cell's chunks are mostly padding, which the algorithm does not
need —, its start from the prompts of the client's log; the experts hit
are the chunks' own, the step programs' counters less the decode
steps'.  A program without the counters, a window without a chunk, or
a configuration without ``conv_L_cache`` reports nothing."""

from chipbench import opsbytes_conv
from chipbench.layer_metrics import prefill_chunk_ms
from chipbench.layer_metrics.loop_host_ms_per_step import deltas
from chipbench.layer_metrics.prefill_mxu_roofline_pct import mean_start


def read(obs):
    chunk_ms = prefill_chunk_ms.read(obs)
    traced, client, peaks = obs.get("traced"), obs.get("client"), \
        obs.get("peaks")
    fill = deltas(obs, "recurrent_chunk_tokens", "recurrent_chunk_rows")
    moe = deltas(obs, "moe_experts_hit", "moe_decode_experts_hit",
                 "moe_expert_slots", "moe_decode_expert_slots")
    if not chunk_ms or not traced or not client or not peaks or not fill \
            or not moe or fill[1] <= 0 \
            or "conv_L_cache" not in (obs.get("config") or {}):
        return None
    hit, slots = moe[0] - moe[1], moe[2] - moe[3]
    if slots <= 0:
        return None
    width = traced["chunk_width"]
    need = opsbytes_conv.prefill_chunk(
        obs["config"], mean_start((n for n, _ in client["requests"]), width),
        width * fill[0] / fill[1], hit / slots)
    least = max(need["bytes"] / peaks["hbm_bytes_per_s"],
                need["flops"] / peaks["bf16_flops_per_s"])
    return 100.0 * least / (chunk_ms / 1000.0)
