"""Device: 1 - union of busy intervals / traced window; the least busy
device where there are several.  In an open-loop cell part of it is
the wait for arrivals: PERF.md says how to read it per cell."""


def read(obs):
    trace = obs.get("trace")
    if not trace or not trace.get("devices") or not trace["window_s"]:
        return None
    busy = min(d["busy_s"] for d in trace["devices"])
    return 100.0 * (1.0 - busy / trace["window_s"])
