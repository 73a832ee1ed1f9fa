"""Kernels (program level until kernels are named): the least time a
decode step could take over the time it took.  Required bytes (every
weight once + the VALID cache of the active contexts, ``opsbytes``) over
the chip's HBM bandwidth, against required operations over its bf16
peak; the larger is the bound (bandwidth, at these batches); over
``decode_step_ms``.  Contexts are those of the client's log over the
traced window."""

from chipbench import opsbytes
from chipbench.layer_metrics import decode_step_ms


def contexts_at(requests, t: float) -> list:
    """Context lengths of the requests decoding at client time ``t``:
    prompt + tokens received so far."""
    out = []
    for n_prompt, token_t in requests:
        if token_t and token_t[0] <= t <= token_t[-1]:
            out.append(n_prompt + sum(1 for x in token_t if x <= t))
    return out


def read(obs):
    step_ms = decode_step_ms.read(obs)
    traced, client, peaks = obs.get("traced"), obs.get("client"), \
        obs.get("peaks")
    if not step_ms or not traced or not client or not peaks:
        return None
    t1 = traced["wall"] - obs["window_wall"]
    t0 = t1 - traced["host_window_s"]
    samples = [opsbytes.decode_step(
        obs["config"], contexts_at(client["requests"], t0 + (t1 - t0) * i / 8))
        for i in range(1, 8)]
    samples = [s for s in samples if s["flops"] > 0]
    if not samples:
        return None
    least = sum(max(s["bytes"] / peaks["hbm_bytes_per_s"],
                    s["flops"] / peaks["bf16_flops_per_s"])
                for s in samples) / len(samples)
    return 100.0 * least / (step_ms / 1000.0)
