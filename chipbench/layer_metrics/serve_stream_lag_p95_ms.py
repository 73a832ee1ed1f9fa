"""Serve runtime, the way back, for every token: from the engine's
hand-over of token ``k`` (end of ``llm:engine``'s ``prefill`` stage +
``emit_ms[k]``) to the proxy's frame ``k`` written (``ts +
frame_ms[k]`` of the ``http:`` span) — what the replica's generator,
the object-plane stream, the wait for a pool thread and the write add
to a token; p95 over the token frames written in the window.  The two
ends are wall-clock times of two processes of one host, as
``serve_egress_p50_ms``'s are; a stream's last frame, the finish chunk,
has no hand-over and no lag."""

from chipbench.layer_metrics.engine_itl_p95_ms import in_window, streams
from chipbench.layer_metrics.serve_ingress_p50_ms import first_token_wall
from chipbench.loadgen import percentile


def frames(obs) -> list:
    """``(k, lag seconds or None for a finish chunk, its pull's wait for
    a pool thread in seconds)`` of every ``data:`` frame written in the
    window, ``k`` its place in its stream."""
    found = []
    for stream in streams(obs):
        http, first = stream["http"], first_token_wall(stream)
        emit_ms = stream["llm:engine"]["attrs"]["emit_ms"]
        for k, (frame, wait) in enumerate(zip(
                http["attrs"]["frame_ms"], http["attrs"]["pull_wait_ms"])):
            wrote = http["ts"] + 0.001 * frame
            if in_window(obs, wrote):
                found.append((k, wrote - first - 0.001 * emit_ms[k]
                              if k < len(emit_ms) else None, 0.001 * wait))
    return found


def read(obs):
    lags = [lag for _, lag, _ in frames(obs) if lag is not None]
    if not lags:
        return None
    return 1000.0 * percentile(lags, 95)
