"""Parallel: time in collective operations during which no other
operation ran on that device, over the traced window; worst device.
Device trace."""

from chipbench.trace_reduce import worst_device


def read(obs):
    device = worst_device(obs.get("trace"), "collective_exposed_s")
    if device is None:
        return None
    return 100.0 * device["collective_exposed_s"] / obs["trace"]["window_s"]
