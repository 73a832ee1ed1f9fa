"""Kernels (program level: the decode rows' attention is the kernel
``ops/pallas/decode_attention.py``, the products are XLA's): the least
time a decode step of a LOOPED dense model could take over the time it
took.  Required bytes (``opsbytes_loop``: every layer's weights once a
PASS — the re-read is required, see there — the head once, and of the
slabs the live positions of ``total_ut_steps * num_hidden_layers`` slab
layers: what is read, not what is reserved) over the chip's HBM
bandwidth, against required operations (products and score pairs times
the passes) over its bf16 peak; the larger is the bound; over
``decode_step_ms``.  Contexts are those of the client's log over the
traced window.  A configuration without ``total_ut_steps``, or a
program without the ``loop_passes`` counter, reports nothing."""

from chipbench import opsbytes_loop
from chipbench.layer_metrics import decode_step_ms
from chipbench.layer_metrics.decode_hbm_roofline_pct import contexts_at
from chipbench.layer_metrics.loop_host_ms_per_step import deltas


def read(obs):
    if "total_ut_steps" not in (obs.get("config") or {}) \
            or deltas(obs, "loop_passes") is None:
        return None
    step_ms = decode_step_ms.read(obs)
    traced, client, peaks = obs.get("traced"), obs.get("client"), \
        obs.get("peaks")
    if not step_ms or not client or not peaks:
        return None
    t1 = traced["wall"] - obs["window_wall"]
    t0 = t1 - traced["host_window_s"]
    samples = [opsbytes_loop.decode_step(
        obs["config"], contexts_at(client["requests"], t0 + (t1 - t0) * i / 8))
        for i in range(1, 8)]
    samples = [s for s in samples if s["flops"] > 0]
    if not samples:
        return None
    least = sum(max(s["bytes"] / peaks["hbm_bytes_per_s"],
                    s["flops"] / peaks["bf16_flops_per_s"])
                for s in samples) / len(samples)
    return 100.0 * least / (step_ms / 1000.0)
