"""Serve runtime, the way back, for the first token: from the end of
``llm:engine``'s ``prefill`` stage (the engine loop hands the token to
the request's stream) to the proxy's first SSE frame written (``ts +
first_chunk_s`` of the ``http:`` span) — what the replica's generator,
the object-plane stream and the proxy add; median over the requests
the proxy received inside the window.  Request spans at sample rate 1;
the two ends are wall-clock times of two processes of one host."""

from chipbench.layer_metrics.serve_ingress_p50_ms import (
    first_token_wall,
    requests,
)
from chipbench.loadgen import percentile


def read(obs):
    found = requests(obs)
    if not found:
        return None
    return 1000.0 * percentile(
        [r["http"]["ts"] + r["http"]["attrs"]["first_chunk_s"]
         - first_token_wall(r) for r in found], 50)
