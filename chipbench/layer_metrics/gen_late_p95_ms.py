"""Load generator (the benchmark's own): send time - due time, 95th
percentile.  A starved generator is not a fast server."""

from chipbench.loadgen import percentile


def read(obs):
    client = obs.get("client")
    if not client or not client["late_s"]:
        return None
    return 1000.0 * percentile(client["late_s"], 95)
