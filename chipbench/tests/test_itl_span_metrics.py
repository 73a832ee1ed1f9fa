"""The five per-layer metrics read from every token's hand-over
(``emit_ms`` / ``chunk_gaps`` of ``llm:engine``), every frame's write
(``frame_ms`` / ``pull_wait_ms`` of ``http:``) and the ``llm:stall``
spans, each on a hand-made ``obs`` — the window's edges, the probe
filter, too few requests, and None, never an exception, where the
program records none of them (the parent of the PR that added them)."""

import importlib

import pytest

WINDOW, SECONDS = 1000.0, 50
NAMES = ("engine_itl_p95_ms", "itl_chunk_gaps_pct",
         "serve_stream_lag_p95_ms", "serve_pull_wait_p95_ms",
         "engine_stall_s")


def read(name, obs):
    return importlib.import_module(
        "chipbench.layer_metrics." + name).read(obs)


def stream(i, first_token, tokens=11, *, step_ms=10.0, chunk_gaps=(),
           long_ms=30.0, lag_ms=2.0, wait_ms=0.1, prompt_tokens=128,
           new_attrs=True):
    """The two spans of one streamed request whose first token the
    engine hands over at wall time ``first_token``: a gap is
    ``step_ms``, or ``long_ms`` where ``chunk_gaps`` names it; frame k
    is written ``lag_ms`` after token k, the finish chunk after the
    last."""
    trace, queue, prefill = f"{i:032x}", 0.010, 0.200
    submit = first_token - queue - prefill
    received = submit - 0.004
    emit_ms = [0.0]
    for k in range(1, tokens):
        emit_ms.append(emit_ms[-1] + (long_ms if k in chunk_gaps
                                      else step_ms))
    frame_ms = [1000 * (first_token - received) + e + lag_ms
                for e in emit_ms]
    frame_ms.append(frame_ms[-1] + 0.5)
    http = {"trace_id": trace, "name": "http:/v1/completions",
            "ts": received, "dur_s": 1.0,
            "attrs": {"path": "/v1/completions", "stream": True,
                      "status": 200, "chunks": tokens + 1,
                      "first_chunk_s": frame_ms[0] / 1000}}
    engine = {"trace_id": trace, "name": "llm:engine", "ts": submit,
              "dur_s": queue + prefill + emit_ms[-1] / 1000,
              "stages": {"queue": queue, "prefill": prefill,
                         "decode": emit_ms[-1] / 1000},
              "attrs": {"chunks": 2, "prompt_tokens": prompt_tokens,
                        "output_tokens": tokens}}
    if new_attrs:
        http["attrs"].update(frame_ms=frame_ms,
                             pull_wait_ms=[wait_ms] * (tokens + 1))
        engine["attrs"].update(emit_ms=emit_ms,
                               chunk_gaps=sorted(chunk_gaps))
    return [http,
            {"trace_id": trace, "name": "llm:admission",
             "ts": received + 0.003, "dur_s": 0.001},
            engine]


def spans_obs(spans, **more):
    return {"spans": spans, "window_wall": WINDOW, "seconds": SECONDS,
            **more}


def twelve(**kw):
    """Twelve streams of 10 gaps each, first tokens a second apart."""
    return [s for i in range(12)
            for s in stream(i, WINDOW + 1 + i, **kw)]


def test_gaps_and_frames_over_the_whole_window():
    # one gap in ten saw a chunk and is 30 ms: the p95 hears it
    obs = spans_obs(twelve(chunk_gaps={4}))
    assert read("itl_chunk_gaps_pct", obs) == pytest.approx(10.0)
    assert read("engine_itl_p95_ms", obs) == pytest.approx(30.0)
    assert read("serve_stream_lag_p95_ms", obs) == pytest.approx(
        2.0, abs=1e-3)
    assert read("serve_pull_wait_p95_ms", obs) == pytest.approx(0.1)
    assert read("engine_stall_s", obs) == 0.0
    # one in twenty-five: deaf to it
    few = spans_obs([s for i in range(12) for s in stream(
        i, WINDOW + 1 + i, tokens=26, chunk_gaps={7})])
    assert read("itl_chunk_gaps_pct", few) == pytest.approx(4.0)
    assert read("engine_itl_p95_ms", few) == pytest.approx(10.0)


def test_the_windows_edges_cut_gaps_and_frames_not_requests():
    spans = twelve()
    # began in the ramp: its first token 35 ms before the window, so
    # gaps 4 to 10 (the long one among them) end inside, 1 to 3 do not
    spans += stream(100, WINDOW - 0.035, chunk_gaps={6}, long_ms=500.0,
                    wait_ms=30.0)
    # ends after the window: only its first three gaps end inside
    spans += stream(101, WINDOW + SECONDS - 0.035, chunk_gaps={9},
                    long_ms=900.0, wait_ms=60.0)
    obs = spans_obs(spans)
    gaps = importlib.import_module(
        "chipbench.layer_metrics.engine_itl_p95_ms").gaps(obs)
    assert len(gaps) == 120 + 7 + 3
    assert sum(chunked for _, chunked in gaps) == 1
    assert max(gap for gap, _ in gaps) == pytest.approx(0.5)
    frames = importlib.import_module(
        "chipbench.layer_metrics.serve_stream_lag_p95_ms").frames(obs)
    assert len(frames) == 12 * 12 + 8 + 4
    # a frame is written 2 ms after its token: of the early stream the
    # frames of tokens 4 to 10 and the finish chunk lie inside
    early = [lag for _, lag, wait in frames
             if wait == pytest.approx(0.030)]
    assert len(early) == 8 and early.count(None) == 1
    # of the late one tokens 0 to 3, and no finish chunk
    late = [lag for _, lag, wait in frames
            if wait == pytest.approx(0.060)]
    assert len(late) == 4 and None not in late
    assert all(lag == pytest.approx(0.002, abs=1e-6)
               for lag in early + late if lag is not None)
    assert read("engine_stall_s", obs) == 0.0


def test_probes_are_told_from_the_traffic_by_prompt_length():
    spans = twelve()
    spans += stream(103, WINDOW + 30, step_ms=700.0, prompt_tokens=96)
    obs = spans_obs(spans)
    assert read("engine_itl_p95_ms", obs) > 600
    obs["client"] = {"requests": [(128, [0.1, 0.2])]}
    assert read("engine_itl_p95_ms", obs) == pytest.approx(10.0)


def test_too_few_streams_or_a_failed_one_give_nothing():
    nine = spans_obs([s for i in range(9)
                      for s in stream(i, WINDOW + 1 + i)])
    ten = twelve()[:10 * 3]
    for name in NAMES:
        assert read(name, nine) is None, name
        assert read(name, spans_obs(ten)) is not None, name
    ten[4 * 3 + 2]["error"] = True            # an llm:engine span
    for name in NAMES:
        assert read(name, spans_obs(ten)) is None, name


def stall(ts, dur_s, phase="fetch"):
    return {"trace_id": f"{int(ts * 1000):032x}", "name": "llm:stall",
            "ts": ts, "dur_s": dur_s, "error": True, "forced": True,
            "attrs": {"step": 7, "phase": phase, "phase_s": dur_s,
                      "blocked_s": dur_s, "rows": 16}}


def test_stalls_are_summed_where_they_start_in_the_window():
    spans = twelve() + [stall(WINDOW - 3.0, 2.9),      # set-up's
                        stall(WINDOW + 10.0, 1.25),
                        stall(WINDOW + 49.9, 3.0, "emit"),
                        stall(WINDOW + 50.0, 0.4)]     # the drain's
    assert read("engine_stall_s", spans_obs(spans)) == pytest.approx(4.25)
    # a stall span alone does not say the program keeps hand-over times
    assert read("engine_stall_s", spans_obs(spans[-4:])) is None


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("obs", [
    {},
    {"spans": None, "traced": None, "trace": None},
    # the parent: http: and llm:engine spans without the new attributes
    spans_obs(twelve(new_attrs=False),
              client={"requests": [(128, [0.1, 0.2])]}),
    # no request's gap or frame lies in the window
    spans_obs([s for i in range(12)
               for s in stream(i, WINDOW - 30 + i)]),
], ids=["empty", "untraced", "parent", "all-before-the-window"])
def test_absent_source_reads_as_none(name, obs):
    if name == "engine_stall_s" and obs.get("spans") \
            and "emit_ms" in obs["spans"][2]["attrs"]:
        assert read(name, obs) == 0.0     # the program can tell: none
    else:
        assert read(name, obs) is None
