"""Engine loop: ``EngineLoop.submit`` -> the request's first prefill
program dispatched (the inbox, the wait for a slot and for its chunk
turn): the ``queue`` stage of the ``llm:engine`` span, mean over the
requests the proxy received inside the window.  A mean because
``ttft_mean_ms`` is one: ingress + queue + prefill + egress add up to
it (less the client's own send and receive)."""

from chipbench.layer_metrics.serve_ingress_p50_ms import requests

STAGE = "queue"


def read(obs, stage=STAGE):
    found = requests(obs)
    if not found:
        return None
    return 1000.0 * sum(r["llm:engine"]["stages"][stage]
                        for r in found) / len(found)
