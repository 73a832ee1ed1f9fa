"""Serve runtime: from the client's send to the end of the
``llm:admission`` span, i.e. until the engine has the request; median.

Source: ``tracing_plane`` request spans at sample rate 1 (traced run)
and the client log, both on the host's wall clock.  A streamed request
leaves no proxy span on success (``serve/api.py`` records ``http:``
spans for streams only when dispatch fails), so the request's start is
taken at the client; spans carry no id the client knows, so a span is
joined to its request by ``max_tokens`` (in the ``llm:stream`` span's
attributes) and order in time within equal ``max_tokens``; groups whose
counts differ are left out."""

from chipbench.loadgen import percentile


def read(obs):
    spans, client = obs.get("spans"), obs.get("client")
    if not spans or not client:
        return None
    by_id = {}
    for s in spans:
        by_id.setdefault(s["trace_id"], {})[s["name"]] = s
    admitted = {}                       # max_tokens -> [admission end]
    for trace in by_id.values():
        stream, adm = trace.get("llm:stream"), trace.get("llm:admission")
        if stream and adm and "attrs" in stream:
            admitted.setdefault(stream["attrs"]["max_tokens"], []).append(
                adm["ts"] + adm["dur_s"])
    sent = {}
    for wall, max_tokens in client["sends"]:
        sent.setdefault(max_tokens, []).append(wall)
    waits = []
    for max_tokens, ends in admitted.items():
        starts = sent.get(max_tokens, [])
        if len(starts) == len(ends):
            waits.extend(e - s for s, e in zip(sorted(starts), sorted(ends)))
    if len(waits) < 10:
        return None
    return 1000.0 * percentile(waits, 50)
