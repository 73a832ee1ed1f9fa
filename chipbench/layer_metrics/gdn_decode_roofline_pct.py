"""Kernels (program level: the scalar-gated delta rule's step and the
walk over the softmax layers' slabs are XLA's, no kernel of their own
yet): the least time a decode step of a dense model with gated
delta-rule layers of one decay a head and multi-head softmax layers
could take over the time it took.  Required bytes (``opsbytes_gdn``:
every weight once, the whole untied head once; for each row DECODED its
96 x 192 float32 state and convolution tails read and written once in
every linear layer; of the slabs the softmax layers' live positions of
30 KV heads: what is read, not what is reserved or touched) over the
chip's HBM bandwidth, against required operations over its bf16 peak;
the larger is the bound; over ``decode_step_ms``.  Contexts are those
of the client's log over the traced window.  A program without the
recurrent counters, or a configuration without this family's keys,
reports nothing."""

from chipbench import opsbytes_gdn
from chipbench.layer_metrics import decode_step_ms
from chipbench.layer_metrics.decode_hbm_roofline_pct import contexts_at
from chipbench.layer_metrics.loop_host_ms_per_step import deltas


def read(obs):
    step_ms = decode_step_ms.read(obs)
    traced, client, peaks = obs.get("traced"), obs.get("client"), \
        obs.get("peaks")
    if not step_ms or not traced or not client or not peaks \
            or deltas(obs, "recurrent_decode_rows") is None \
            or "linear_key_head_dim" not in obs["config"]:
        return None
    t1 = traced["wall"] - obs["window_wall"]
    t0 = t1 - traced["host_window_s"]
    samples = [opsbytes_gdn.decode_step(
        obs["config"],
        contexts_at(client["requests"], t0 + (t1 - t0) * i / 8))
        for i in range(1, 8)]
    samples = [s for s in samples if s["state_bytes"] > 0]
    if not samples:
        return None
    least = sum(max(s["bytes"] / peaks["hbm_bytes_per_s"],
                    s["flops"] / peaks["bf16_flops_per_s"])
                for s in samples) / len(samples)
    return 100.0 * least / (step_ms / 1000.0)
