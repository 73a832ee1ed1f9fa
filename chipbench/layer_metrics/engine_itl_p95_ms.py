"""Engine loop: the gap between two tokens of a request, read where the
engine hands them to the request's stream — the client's ``itl_p95_ms``
from inside, over the WHOLE window: the differences of consecutive
``emit_ms`` of the ``llm:engine`` span (one entry a token handed over,
one clock read a landed step), pooled over the traffic's requests, a gap
counted if it ENDS in the window, whenever its request began; p95.

Source: ``tracing_plane`` request spans at sample rate 1 (traced run).
A program whose spans carry no ``emit_ms`` / ``frame_ms`` (the parent of
the PR that added them) gives no stream and no metric.  ``streams`` and
``gaps`` are shared by the four other metrics read from them."""

from chipbench.layer_metrics.serve_ingress_p50_ms import (
    MIN_REQUESTS,
    first_token_wall,
)
from chipbench.loadgen import percentile


def streams(obs) -> list:
    """One ``{"http", "llm:engine"}`` dict of spans per streamed request
    of the traffic that ended well and left both spans, the engine's
    with every hand-over (``emit_ms``), the proxy's with every frame
    (``frame_ms``, ``pull_wait_ms``); fewer than ``MIN_REQUESTS`` count
    as none.  The correctness probes are told from the traffic by prompt
    length, as ``serve_ingress_p50_ms.requests`` tells them."""
    spans = obs.get("spans")
    if not spans or obs.get("window_wall") is None or not obs.get("seconds"):
        return []
    lengths = {n for n, _ in (obs.get("client") or {}).get("requests", ())}
    by_id: dict = {}
    for span in spans:
        name = span.get("name", "")
        key = "http" if name.startswith("http:") else name
        if key in ("http", "llm:engine"):
            by_id.setdefault(span.get("trace_id"), {})[key] = span
    found = [
        t for t in by_id.values()
        if len(t) == 2
        and {"frame_ms", "pull_wait_ms"} <= set(t["http"].get("attrs", {}))
        and "emit_ms" in t["llm:engine"].get("attrs", {})
        and "stages" in t["llm:engine"]
        and (not lengths
             or t["llm:engine"]["attrs"]["prompt_tokens"] in lengths)
        and not any(s.get("error") for s in t.values())]
    return found if len(found) >= MIN_REQUESTS else []


def in_window(obs, wall: float) -> bool:
    return 0 <= wall - obs["window_wall"] < obs["seconds"]


def gaps(obs) -> list:
    """``(seconds, saw a prefill program dispatched)`` of every gap
    between consecutive hand-overs that ends in the window."""
    found = []
    for stream in streams(obs):
        attrs = stream["llm:engine"]["attrs"]
        first, emit_ms = first_token_wall(stream), attrs["emit_ms"]
        chunked = set(attrs.get("chunk_gaps", ()))
        found += [(0.001 * (emit_ms[i] - emit_ms[i - 1]), i in chunked)
                  for i in range(1, len(emit_ms))
                  if in_window(obs, first + 0.001 * emit_ms[i])]
    return found


def read(obs):
    found = gaps(obs)
    if not found:
        return None
    return 1000.0 * percentile([gap for gap, _ in found], 95)
