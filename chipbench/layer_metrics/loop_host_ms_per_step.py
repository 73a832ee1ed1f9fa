"""Engine loop: host time proper per engine step over the traced
window: the loop's phase seconds (``LLMEngine.stats["phase_*_s"]``,
which tile every iteration) less the time blocked on device->host
reads (``block_s``) and less the wait for work (``phase_idle_wait_s``),
over the iterations that dispatched a program (``steps``); deltas
between the owner's readings at trace start and stop.  A reading taken
in mid-phase lacks the open phase's seconds: at most one phase (tens of
milliseconds) over a window of seconds."""


def deltas(obs, *keys):
    """after - before of the engine counters ``keys`` over the traced
    window; None where the program has no such counter."""
    traced = obs.get("traced") or {}
    after, before = traced.get("engine"), traced.get("engine_before")
    if not after or not before or any(
            k not in after or k not in before for k in keys):
        return None
    return [after[k] - before[k] for k in keys]


def read(obs):
    found = deltas(obs, "steps", "block_s", "phase_idle_wait_s")
    if not found or found[0] <= 0:
        return None
    steps, blocked, waited = found
    after = obs["traced"]["engine"]
    phases = sum(deltas(obs, *[k for k in after if k.startswith("phase_")]))
    return 1000.0 * (phases - blocked - waited) / steps
