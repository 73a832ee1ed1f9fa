"""Device: opening the chip — the ``device_open`` stage of the
replica's ``llm:init`` span (``require_tpu``: the jax import where it
is the process's first, the backend's start, the first
``jax.devices()``)."""

from chipbench.layer_metrics.setup_serve_run_s import llm_init


def read(obs):
    span = llm_init(obs)
    stages = (span or {}).get("stages") or {}
    if "device_open" not in stages:
        return None
    return float(stages["device_open"])
