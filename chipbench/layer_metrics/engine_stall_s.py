"""Engine loop: seconds of the window in which an engine iteration
stood still — the summed ``dur_s`` of the ``llm:stall`` spans (forced:
an iteration that kept decode rows waiting over ``engine.STALL_S``)
whose ``ts`` lies in the window; 0.0 in a steady run.  Each span says
in which phase, and whether blocked on the device or on the host.  A
program whose request spans carry no hand-over times (the parent of the
PR that added both) records no stall either: no metric, not 0."""

from chipbench.layer_metrics.engine_itl_p95_ms import in_window, streams


def read(obs):
    if not streams(obs):
        return None
    return float(sum(span["dur_s"] for span in obs["spans"]
                     if span.get("name") == "llm:stall"
                     and in_window(obs, span["ts"])))
