"""Engine loop (its queue: the wait for a slot and for one's chunks):
due time -> first streamed token at the client, 90th percentile over
the requests DUE inside the window; a failed, refused or tokenless
request counts as +inf.  Recorded, not judged: a window holds a few
tens of requests, and two or three order statistics carry no bound
(``ttft_mean_ms`` is what is judged).  Host clock."""

from chipbench.loadgen import percentile


def read(obs):
    client = obs.get("client")
    if not client or not client["ttft_s"]:
        return None
    return 1000.0 * percentile(client["ttft_s"], 90)
