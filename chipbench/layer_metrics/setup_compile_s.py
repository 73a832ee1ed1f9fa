"""Model step: what set-up paid for compilation — the summed ``dur_s``
of the ``jit:compile`` spans of the replica's process that began before
the window (tracing, lowering, and the backend compile or the cache's
retrieval of every program: the engine's construction, the warm-up's
step programs, the parity probe's reference).  ``fun_name`` on each
span splits it by program."""

from chipbench.layer_metrics.setup_serve_run_s import compiles


def read(obs):
    found = compiles(obs)
    if not found:
        return None
    return float(sum(span["dur_s"] for span in found))
