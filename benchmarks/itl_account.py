"""The gap between tokens, accounted for from inside, of one traced
benchmark run: reads what ``python3 -m chipbench.run --workload <cell>
--trace 1 --dump <file>`` kept and prints one JSON line —

* the client's ``itl_p95_ms`` of THAT run beside the engine's
  (``engine_itl_p95_ms``), the stream's lag and the pool's wait, the
  share of gaps that saw a prefill dispatched (the five readers of
  ``chipbench/layer_metrics``, as the benchmark applies them);
* how long a gap is where ``chunk_gaps`` names it and where not, and
  where ANOTHER request's first token was handed over inside it — a
  prompt's end as any program's spans show it, the parent's of the PR
  that added ``prompt_end_gaps`` too (that attribute names the gap in
  which the prompt's last chunk was DISPATCHED: the same gap where the
  first token is read before the step in flight is landed, the one
  before where it is read behind it);
* the stream's lag of a request's FIRST token beside that of its later
  ones (p50, p95, p99), and the pool's wait likewise;
* the requests joined by ``trace_id`` beside the client's count of
  requests ended in the window, the spans the run left by name, a
  request's spans and the bytes of its ``llm:engine`` and ``http:``
  span as JSON (the dump keeps the first 2,000 spans of the ring);
* every ``llm:stall`` span, in or out of the window;
* the engine loop's own cost over the traced seconds:
  ``loop_host_ms_per_step`` and the ``emit`` phase a step.

No chip and no jax needed: it reads the file.

    python -m benchmarks.itl_account chiprun_out/decode.json [...]
"""

import bisect
import collections
import importlib
import json
import sys

from chipbench.layer_metrics import engine_itl_p95_ms as itl
from chipbench.layer_metrics.loop_host_ms_per_step import deltas
from chipbench.layer_metrics.serve_ingress_p50_ms import first_token_wall
from chipbench.layer_metrics.serve_stream_lag_p95_ms import frames
from chipbench.loadgen import percentile

METRICS = ("engine_itl_p95_ms", "itl_chunk_gaps_pct",
           "itl_prompt_end_gaps_pct", "prompt_end_gap_p50_ms",
           "serve_stream_lag_p95_ms", "serve_pull_wait_p95_ms",
           "engine_stall_s", "loop_host_ms_per_step")
DUMP_KEEPS = 2000


def _ms(values, q):
    return round(1000.0 * percentile(values, q), 3) if values else None


def first_token_gaps(obs) -> list:
    """Seconds of the window's gaps inside which ANOTHER request (a
    probe too) was handed its first token."""
    firsts = sorted(
        first_token_wall({"llm:engine": span})
        for span in obs.get("spans") or ()
        if span.get("name") == "llm:engine" and "stages" in span)
    found = []
    for stream in itl.streams(obs):
        first = first_token_wall(stream)
        walls = [first + 0.001 * ms
                 for ms in stream["llm:engine"]["attrs"]["emit_ms"]]
        found += [b - a for a, b in zip(walls, walls[1:])
                  if itl.in_window(obs, b)
                  and bisect.bisect_right(firsts, b)
                  > bisect.bisect_right(firsts, a)]
    return found


def gap_populations(obs) -> dict:
    """Milliseconds (count, p50, p95) of the window's gaps, those
    ``chunk_gaps`` names apart from the others, and those that hold
    another request's first token."""
    found = itl.gaps(obs)
    return {key: [len(v), _ms(v, 50), _ms(v, 95)] for key, v in
            (("in_chunk_gaps", [g for g, named in found if named]),
             ("others", [g for g, named in found if not named]),
             ("across_a_first_token", first_token_gaps(obs)))}


def lag_populations(obs) -> dict:
    """Milliseconds (count, p50, p95, p99) of the lag of the window's
    token frames, a request's first apart from its later ones."""
    lags = [(k, lag) for k, lag, _ in frames(obs) if lag is not None]
    return {key: [len(v), _ms(v, 50), _ms(v, 95), _ms(v, 99)]
            for key, v in
            (("first_token", [lag for k, lag in lags if not k]),
             ("later", [lag for k, lag in lags if k]))}


def account(path: str) -> dict:
    with open(path) as f:
        obs = json.load(f)
    spans, client = obs.get("spans") or [], obs["client"]
    out = {"dump": path, "cell": obs["cell"]["name"],
           "client_itl_p95_ms": _ms(client["gaps_s"], 95),
           "client_gaps": len(client["gaps_s"])}
    for name in METRICS:
        out[name] = importlib.import_module(
            "chipbench.layer_metrics." + name).read(obs)
    found = deltas(obs, "steps", "phase_emit_s")
    if found and found[0] > 0:
        out["emit_ms_per_step"] = 1000.0 * found[1] / found[0]
        out["traced_steps"] = found[0]
    out["gaps_ms_count_p50_p95"] = gap_populations(obs)
    out["lag_ms_count_p50_p95_p99"] = lag_populations(obs)
    waits = [wait for _, _, wait in frames(obs)]
    out["pull_wait_ms_p50_p95_p99_max"] = [
        _ms(waits, 50), _ms(waits, 95), _ms(waits, 99),
        _ms(waits, 100)]
    streams = itl.streams(obs)
    out["joined_requests"] = len(streams)
    out["client_ended_in_window"] = client["ended_in_window"]
    out["joined_ended_in_window"] = sum(
        itl.in_window(obs, s["http"]["ts"] + s["http"]["dur_s"])
        for s in streams)
    out["spans"] = len(spans)
    out["dump_is_cut"] = len(spans) >= DUMP_KEEPS
    out["spans_by_name"] = dict(collections.Counter(
        s["name"].split(":/")[0] for s in spans).most_common(12))
    if streams:
        ids = {s["http"]["trace_id"] for s in streams}
        out["spans_a_request"] = sum(
            s.get("trace_id") in ids for s in spans) / len(ids)
        for key in ("llm:engine", "http"):
            sizes = [len(json.dumps(s[key])) for s in streams]
            out[f"bytes_{key}"] = [round(sum(sizes) / len(sizes)),
                                   max(sizes)]
    out["bytes_all_spans"] = len(json.dumps(spans))
    out["stalls"] = [
        {"at_s": round(s["ts"] - obs["window_wall"], 2),
         "dur_s": round(s["dur_s"], 3), **s["attrs"]}
        for s in spans if s.get("name") == "llm:stall"]
    return out


if __name__ == "__main__":
    for dump in sys.argv[1:]:
        print(json.dumps(account(dump)), flush=True)
