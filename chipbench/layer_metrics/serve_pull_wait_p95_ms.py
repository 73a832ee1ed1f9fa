"""Serve runtime: how long a stream's pull waits for a thread of the
pool it is run on — ``pull_wait_ms`` of the ``http:`` span, the time
between ``run_in_executor(None, next_chunk, gen)`` being submitted and
``next_chunk`` starting on a thread (asyncio's default pool: 17 threads
on a 13-core host); p95 over the ``data:`` frames written in the
window.  Near zero while the pool has a thread a stream; with more
streams than threads it is the part of ``serve_stream_lag_p95_ms`` that
the pool owes."""

from chipbench.layer_metrics.serve_stream_lag_p95_ms import frames
from chipbench.loadgen import percentile


def read(obs):
    waits = [wait for _, _, wait in frames(obs)]
    if not waits:
        return None
    return 1000.0 * percentile(waits, 95)
