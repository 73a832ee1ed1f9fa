"""Serve runtime, the way in: from the proxy's receipt of a streamed
request (``ts`` of its ``http:`` span) to the end of the replica's
``llm:admission`` span, i.e. until the engine loop's inbox has it;
median over the traffic's requests that the proxy received inside the
window — the population of ``ttft_mean_ms``, to a request or two at the
window's edges.

Source: ``tracing_plane`` request spans at sample rate 1 (traced run),
joined by ``trace_id``: the proxy records an ``http:`` span for a
stream that succeeds, with ``first_chunk_s``, and the engine one
``llm:engine`` stage span per request.  A program that records neither
(the parent of the PR that added them) gives no request and no metric.
``requests`` is shared by the three other metrics read from these
spans."""

from chipbench.loadgen import percentile

MIN_REQUESTS = 10
_WANTED = ("http", "llm:admission", "llm:engine")


def requests(obs) -> list:
    """One ``{"http", "llm:admission", "llm:engine"}`` dict of spans per
    streamed request of the traffic that the proxy received inside the
    window and that ended well; fewer than ``MIN_REQUESTS`` count as
    none.  Spans carry no id the client knows, so the correctness probes
    sent among the traffic (short, greedy, not in ``ttft_mean_ms``) are
    told from it by prompt length: a request counts if the client's log
    of the traffic has a prompt of its ``prompt_tokens``."""
    spans, lo = obs.get("spans"), obs.get("window_wall")
    if not spans or lo is None or not obs.get("seconds"):
        return []
    lengths = {n for n, _ in (obs.get("client") or {}).get("requests", ())}
    by_id: dict = {}
    for span in spans:
        name = span.get("name", "")
        key = "http" if name.startswith("http:") else name
        if key in _WANTED:
            by_id.setdefault(span.get("trace_id"), {})[key] = span
    found = [
        t for t in by_id.values()
        if len(t) == len(_WANTED)
        and lo <= t["http"]["ts"] < lo + obs["seconds"]
        and "first_chunk_s" in t["http"].get("attrs", {})
        and "stages" in t["llm:engine"]
        and (not lengths
             or t["llm:engine"]["attrs"]["prompt_tokens"] in lengths)
        and not any(s.get("error") for s in t.values())]
    return found if len(found) >= MIN_REQUESTS else []


def first_token_wall(request: dict) -> float:
    """Wall time at which the engine handed the request's first token
    to its stream: the end of ``llm:engine``'s ``prefill`` stage."""
    engine = request["llm:engine"]
    return engine["ts"] + engine["stages"]["queue"] \
        + engine["stages"]["prefill"]


def read(obs):
    found = requests(obs)
    if not found:
        return None
    return 1000.0 * percentile(
        [r["llm:admission"]["ts"] + r["llm:admission"]["dur_s"]
         - r["http"]["ts"] for r in found], 50)
