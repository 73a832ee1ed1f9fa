"""Engine loop: of the gaps between consecutive hand-overs that end in
the window (``engine_itl_p95_ms``'s population), the share across which
a prompt of ANY request ended — the engine dispatched a prompt's LAST
prefill program, alone or riding (``prompt_end_gaps`` of the
``llm:engine`` span: ``LLMEngine.stats["prompt_ends"]`` rose between
the two hand-overs).  Over 5, the window's p95 gap IS the gap that
holds a prompt's end; under 5, it is deaf to how that end is read.

A program whose spans carry no ``prompt_end_gaps`` (the parent of the
PR that added it) has no reading, and the metric is left out.
``prompt_end_gaps`` is shared with ``prompt_end_gap_p50_ms``."""

from chipbench.layer_metrics.engine_itl_p95_ms import in_window, streams
from chipbench.layer_metrics.serve_ingress_p50_ms import first_token_wall


def prompt_end_gaps(obs) -> list:
    """``(seconds, a prompt ended across it)`` of every gap between
    consecutive hand-overs that ends in the window, the population of
    ``engine_itl_p95_ms.gaps``; none where a span does not say."""
    found = []
    for stream in streams(obs):
        attrs = stream["llm:engine"]["attrs"]
        if "prompt_end_gaps" not in attrs:
            return []
        first, emit_ms = first_token_wall(stream), attrs["emit_ms"]
        ended = set(attrs["prompt_end_gaps"])
        found += [(0.001 * (emit_ms[i] - emit_ms[i - 1]), i in ended)
                  for i in range(1, len(emit_ms))
                  if in_window(obs, first + 0.001 * emit_ms[i])]
    return found


def read(obs):
    found = prompt_end_gaps(obs)
    if not found:
        return None
    return 100.0 * sum(ended for _, ended in found) / len(found)
