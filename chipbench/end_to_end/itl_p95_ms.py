"""Gap between consecutive streamed tokens at the client, all gaps that
end inside the window pooled, 95th percentile."""

from chipbench.loadgen import percentile


def read(obs):
    if not obs["client"] or not obs["client"]["gaps_s"]:
        return None
    return 1000.0 * percentile(obs["client"]["gaps_s"], 95)
