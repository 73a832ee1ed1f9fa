"""Control plane: ``serve.run`` from inside — the ``dur_s`` of the
``serve:run`` span, the root of the deployment's start-up trace: the
call to the handle returned (controller, deploy, every replica's
readiness gate, the proxy).  The outside reading beside it is the
benchmark's ``replica_ready`` lap, which also holds one ``device_info``
call.

Source: ``tracing_plane`` start-up spans, FORCED (recorded whatever the
sample rate; the traced run fetches them).  Every reader of the six
``setup_*`` metrics takes spans whose ``ts`` lies BEFORE the window's
opening, tells the replica's process by ``obs["device"]["pid"]``, and
returns None — never raises — where its span is absent: the parent of
the PR that added them records none.  ``serve_run``, ``llm_init`` and
``compiles`` are shared by the five other metrics."""


def before_window(obs) -> list:
    spans, opening = obs.get("spans"), obs.get("window_wall")
    if not spans or opening is None:
        return []
    return [s for s in spans
            if isinstance(s.get("ts"), (int, float)) and s["ts"] < opening]


def _owner_pid(obs):
    return (obs.get("device") or {}).get("pid")


def serve_run(obs):
    """The newest ``serve:run`` span before the window, or None."""
    found = [s for s in before_window(obs) if s.get("name") == "serve:run"]
    return max(found, key=lambda s: s["ts"]) if found else None


def llm_init(obs):
    """The ``llm:init`` span of the process that owns the chip, or
    None."""
    pid = _owner_pid(obs)
    found = [s for s in before_window(obs)
             if s.get("name") == "llm:init" and s.get("pid") == pid]
    return max(found, key=lambda s: s["ts"]) if found else None


def compiles(obs) -> list:
    """The ``jit:compile`` spans of the process that owns the chip that
    began before the window: the engine's construction, the warm-up
    requests' step programs and the parity probe's reference, which
    compiles in that process too — what set-up paid, whoever asked."""
    pid = _owner_pid(obs)
    return [s for s in before_window(obs)
            if s.get("name") == "jit:compile" and s.get("pid") == pid]


def read(obs):
    span = serve_run(obs)
    return None if span is None else float(span["dur_s"])
