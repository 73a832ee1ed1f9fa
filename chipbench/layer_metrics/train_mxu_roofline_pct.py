"""Kernels: required operations of a train step (``opsbytes``: no
recomputation, causal attention once, embedding not a matmul) over the
bf16 peak of all the cell's chips, over ``train_step_ms``: the
required-operations MFU on DEVICE time.  The same on wall time is
printed beside ``train_tok_s``."""

from chipbench import opsbytes
from chipbench.layer_metrics import train_step_ms


def read(obs):
    step_ms, m, peaks = train_step_ms.read(obs), obs.get("train"), \
        obs.get("peaks")
    if not step_ms or not m or not peaks:
        return None
    flops = opsbytes.train_flops_per_token(
        obs["config"], obs["traffic"]["sequence_tokens"]) \
        * m["tokens_per_step"]
    return 100.0 * flops / (obs["chips"] * peaks["bf16_flops_per_s"]) \
        / (step_ms / 1000.0)
