"""Due time -> first streamed token at the client, mean over the
requests DUE inside the window; a failed, refused or tokenless request
counts as +inf (it misses).  The mean, because every request of the few
tens a window holds then carries the same weight: a percentile of so
few is two or three of them."""


def read(obs):
    if not obs["client"] or not obs["client"]["ttft_s"]:
        return None
    ttft = obs["client"]["ttft_s"]
    return 1000.0 * sum(ttft) / len(ttft)
