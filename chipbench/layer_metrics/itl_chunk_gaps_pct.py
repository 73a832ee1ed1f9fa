"""Engine loop: of the gaps between consecutive hand-overs that end in
the window (``engine_itl_p95_ms``'s population), the share across which
the engine dispatched a prefill program of ANY request (``chunk_gaps``
of the ``llm:engine`` span).  Under 5, the window's p95 gap is deaf to
the chunk that shares a step; far over 5, it is that step."""

from chipbench.layer_metrics.engine_itl_p95_ms import gaps


def read(obs):
    found = gaps(obs)
    if not found:
        return None
    return 100.0 * sum(chunked for _, chunked in found) / len(found)
