"""Device: of the idle seconds of device 0 that the reduction labelled
(``idle_by_host``: each gap by the shortest host event covering half of
it), the share that no phase of the program owns — labelled
``unattributed``, or only by the engine's whole-step event ``engine``.
Device trace; reads a program without annotations too (there the share
is what XLA's own host events leave)."""

BLIND = ("unattributed", "engine")


def read(obs):
    trace = obs.get("trace")
    if not trace or not trace.get("devices"):
        return None
    device = min(trace["devices"], key=lambda d: d["index"])
    labelled = device.get("idle_by_host") or []
    total = sum(seconds for _, seconds, _ in labelled)
    if total <= 0:
        return None
    blind = sum(seconds for label, seconds, _ in labelled
                if label.split(": ", 1)[-1] in BLIND)
    return 100.0 * blind / total
