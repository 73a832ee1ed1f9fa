"""Engine loop: how long a decoding request waits for its next token
across a prompt's end — the median of the gaps between consecutive
hand-overs, ending in the window, across which a prompt of ANY request
ended (``itl_prompt_end_gaps_pct``'s gaps, ``prompt_end_gaps`` of the
``llm:engine`` span).  The program that ends a prompt is a chunk or a
mixed step; what the gap holds beyond it and one decode step is the
order of the host's reads: a first token read BEFORE the step in
flight is landed holds that step's tokens back a second chunk.

A program without the attribute, or a window across which no prompt
ended beside a decoding request, has no reading."""

from chipbench.layer_metrics.itl_prompt_end_gaps_pct import prompt_end_gaps
from chipbench.loadgen import percentile


def read(obs):
    found = [gap for gap, ended in prompt_end_gaps(obs) if ended]
    if not found:
        return None
    return 1000.0 * percentile(found, 50)
