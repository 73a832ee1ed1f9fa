"""Engine loop: parameters and cache onto the device — the ``weights``
and ``cache`` stages of the replica's ``llm:init`` span, each closed
behind a ``block_until_ready``: the device's seconds, not the
dispatch's (the weights' program compiles inside ``weights``)."""

from chipbench.layer_metrics.setup_serve_run_s import llm_init


def read(obs):
    span = llm_init(obs)
    stages = (span or {}).get("stages") or {}
    if "weights" not in stages or "cache" not in stages:
        return None
    return float(stages["weights"] + stages["cache"])
