"""Model step: of the least time the traced window's mean lone prefill
chunk could take (``hc_chunk_roofline_pct``'s numerator), the part that
is the residual streams' own — their three passes a sub-layer and the
maps' products, by the resource that bounds the whole chunk
(``opsbytes_hc.least_seconds``).  How much of a PERFECT chunk the
several streams are: the size of the lever before anyone pulls it.  From
the program's counters (``hc_chunk_rows``) and the configuration alone;
it does not move with the program's speed.  A program without the
counter, or a configuration without ``hc_mult``, reports nothing."""

from chipbench import opsbytes_hc
from chipbench.layer_metrics.hc_chunk_roofline_pct import mean_chunk


def read(obs):
    found = mean_chunk(obs)
    if not found:
        return None
    least, streams = opsbytes_hc.least_seconds(found[1], found[2])
    return 100.0 * streams / least
