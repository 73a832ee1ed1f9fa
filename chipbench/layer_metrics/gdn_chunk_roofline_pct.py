"""Kernels (program level: the scalar-gated delta rule's block form is
XLA's, no kernel of its own yet): the least time the mean prefill chunk
of the traced window could take on a dense model with gated delta-rule
layers of one decay a head and multi-head softmax layers, over the time
it took (``prefill_chunk_ms``).  Required operations
(``opsbytes_gdn``: the weights' products a real token, the recurrence's
three products with the 96 x 192 state, the softmax layers' pairs, the
head once) over the chip's bf16 peak, against required bytes (every
weight once, the whole untied head once, the slot's state and tails
read and written once in every linear layer, the softmax layers' live
positions of 30 KV heads) over its HBM bandwidth; the larger is the
bound — at this model's widths a 512-token chunk is compute-bound.  The
mean chunk: its real tokens from ``recurrent_chunk_tokens`` over
``recurrent_chunk_rows`` times the width the engine reports, its start
from the prompts of the client's log.  A program without the recurrent
counters, or a configuration without this family's keys, reports
nothing."""

from chipbench import opsbytes_gdn
from chipbench.layer_metrics import prefill_chunk_ms
from chipbench.layer_metrics.loop_host_ms_per_step import deltas
from chipbench.layer_metrics.prefill_mxu_roofline_pct import mean_start


def read(obs):
    chunk_ms = prefill_chunk_ms.read(obs)
    traced, client, peaks = obs.get("traced"), obs.get("client"), \
        obs.get("peaks")
    fill = deltas(obs, "recurrent_chunk_tokens", "recurrent_chunk_rows")
    if not chunk_ms or not client or not peaks or not fill \
            or fill[1] <= 0 or "linear_key_head_dim" not in obs["config"]:
        return None
    width = traced["chunk_width"]
    need = opsbytes_gdn.prefill_chunk(
        obs["config"], mean_start((n for n, _ in client["requests"]), width),
        width * fill[0] / fill[1])
    least = max(need["bytes"] / peaks["hbm_bytes_per_s"],
                need["flops"] / peaks["bf16_flops_per_s"])
    return 100.0 * least / (chunk_ms / 1000.0)
