"""Kernels (program level: the residual streams' maps and mixes are
XLA's, no kernel of their own): the least time the mean lone prefill
chunk of the traced window could take on a latent, routed model with
several residual streams a token, over the time it took
(``prefill_chunk_ms``) — the share of the WHOLE chunk program, whatever
implements it.  Required work from ``opsbytes_hc``: every weight held
once, of the routed experts those the chunks HIT (the window's routing
counters less the decode steps' own, ``moe_decode_*``), the slot's valid
latent positions, the chunk's REAL rows' streams three passes a
sub-layer, attention in the cheaper of its two forms; the larger of
bytes over the chip's HBM bandwidth and operations over its bf16 peak.
The mean chunk: its real rows from ``hc_chunk_rows`` over the chunks
counted, its start from the prompts of the client's log and the width
the engine reports.  A program without ``hc_chunk_rows``, or a
configuration without ``hc_mult``, reports nothing."""

from chipbench import opsbytes_hc
from chipbench.layer_metrics import prefill_chunk_ms
from chipbench.layer_metrics.loop_host_ms_per_step import deltas
from chipbench.layer_metrics.prefill_mxu_roofline_pct import mean_start


def mean_chunk(obs):
    """``(chunk_ms, what opsbytes_hc says the window's mean chunk
    needs, the chip's peaks)``, or None where any of it is missing."""
    chunk_ms = prefill_chunk_ms.read(obs)
    traced, client, peaks = obs.get("traced"), obs.get("client"), \
        obs.get("peaks")
    rows = deltas(obs, "hc_chunk_rows", "chunks")
    hit = deltas(obs, "moe_experts_hit", "moe_decode_experts_hit",
                 "moe_expert_slots", "moe_decode_expert_slots")
    if not chunk_ms or not client or not peaks or not rows or not hit \
            or rows[1] <= 0 or hit[2] - hit[3] <= 0 \
            or "hc_mult" not in obs["config"]:
        return None
    width = traced["chunk_width"]
    need = opsbytes_hc.prefill_chunk(
        obs["config"], mean_start((n for n, _ in client["requests"]), width),
        rows[0] / rows[1], (hit[0] - hit[1]) / (hit[2] - hit[3]))
    return chunk_ms, need, peaks


def read(obs):
    found = mean_chunk(obs)
    if not found:
        return None
    chunk_ms, need, peaks = found
    return 100.0 * opsbytes_hc.least_seconds(need, peaks)[0] \
        / (chunk_ms / 1000.0)
