"""Control plane: from ``serve.run``'s call to the first line of the
replica's constructor — ``llm:init``'s ``ts`` minus ``serve:run``'s:
the controller, the replica's placement, the daemon's ``Popen``, the
worker's imports and registration, the replica class unpickled.  The
spans between the two (``actor:create``, ``worker:spawn``,
``worker:boot``, ``actor:init``) split it."""

from chipbench.layer_metrics.setup_serve_run_s import llm_init, serve_run


def read(obs):
    run, init = serve_run(obs), llm_init(obs)
    if run is None or init is None:
        return None
    return float(init["ts"] - run["ts"])
