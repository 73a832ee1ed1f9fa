"""Device: ``memory_stats()["peak_bytes_in_use"]`` read by the owner
after the window, fullest chip."""


def read(obs):
    peak = obs.get("memory_peak_bytes")
    return peak / 2.0 ** 30 if peak else None
