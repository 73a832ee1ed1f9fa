"""The six per-layer metrics read from the start-up spans (``serve:run``,
``llm:init``, ``jit:compile``), each on a hand-made span list: the right
number, spans at or after the window's opening and other processes'
ignored, and None — never an exception — on a span list without its
span (the parent's program, which the driver runs under these files)."""

import importlib

import pytest

WINDOW, OWNER, OTHER = 1000.0, 4242, 777
NAMES = ("setup_serve_run_s", "setup_spawn_s", "setup_device_open_s",
         "setup_weights_s", "setup_compile_s", "setup_cache_hit_pct")


def read(name, obs):
    return importlib.import_module(
        "chipbench.layer_metrics." + name).read(obs)


def span(name, ts, dur_s, *, pid=OWNER, stages=None, **attrs):
    out = {"trace_id": "t" * 32, "span_id": f"{abs(hash((name, ts))):016x}",
           "name": name, "ts": ts, "dur_s": dur_s, "pid": pid,
           "forced": True}
    if stages:
        out["stages"] = stages
    if attrs:
        out["attrs"] = attrs
    return out


def compile_(ts, dur_s, cache, *, pid=OWNER, name="step"):
    return span("jit:compile", ts, dur_s, pid=pid, fun_name=name,
                cache=cache, trace_s=0.1, lower_s=0.1,
                backend_s=0.0 if cache == "hit" else dur_s - 0.2,
                retrieval_s=0.0)


def startup():
    """A warm set-up: ``serve.run`` at 950 for 21 s, the constructor's
    first line 9.5 s in, four programs of the owner before the window."""
    return [
        span("serve:run", 950.0, 21.0, pid=1,
             stages={"controller": 0.5, "deploy": 0.3,
                     "replicas_ready": 19.7, "proxy": 0.5}),
        span("actor:create", 950.8, 19.7, pid=2,
             stages={"schedule": 0.1, "start": 19.6}),
        span("llm:init", 959.5, 11.0,
             stages={"device_open": 6.0, "tokenizer": 0.1, "weights": 3.5,
                     "cache": 1.3, "loop": 0.1}),
        compile_(960.0, 1.0, "hit", name="init_params"),
        compile_(975.0, 2.0, "hit", name="_decode"),
        compile_(980.0, 4.0, "miss", name="_mixed_step"),
        compile_(990.0, 1.0, "hit", name="block"),
        # not the owner's, and not before the window
        compile_(961.0, 50.0, "miss", pid=OTHER),
        compile_(WINDOW, 30.0, "miss"),
        compile_(WINDOW + 5.0, 30.0, "miss"),
        span("llm:init", 959.0, 99.0, pid=OTHER,
             stages={"device_open": 90.0, "weights": 5.0, "cache": 4.0}),
        span("serve:run", WINDOW + 1.0, 77.0, pid=1),
        {"trace_id": "r" * 32, "name": "http:/v1/completions",
         "ts": WINDOW + 2.0, "dur_s": 1.0, "pid": 3},
    ]


def obs_of(spans):
    return {"spans": spans, "window_wall": WINDOW, "seconds": 50,
            "device": {"platform": "tpu", "kind": "TPU v5 lite",
                       "count": 1, "pid": OWNER}}


@pytest.mark.parametrize("name, want", [
    ("setup_serve_run_s", 21.0),
    ("setup_spawn_s", 9.5),
    ("setup_device_open_s", 6.0),
    ("setup_weights_s", 4.8),
    ("setup_compile_s", 8.0),
    ("setup_cache_hit_pct", 75.0),
])
def test_reads_its_span(name, want):
    assert read(name, obs_of(startup())) == pytest.approx(want)


@pytest.mark.parametrize("name", NAMES)
def test_none_without_its_span(name):
    """The parent's program: request spans only, or none at all."""
    requests = [s for s in startup()
                if s["name"].startswith("http:")]
    for spans in (requests, [], None):
        assert read(name, obs_of(spans)) is None
    assert read(name, {"spans": startup(), "window_wall": None,
                       "device": {"pid": OWNER}}) is None
    # the train driver's observation: no spans, no window of this kind
    assert read(name, {"spans": None, "device": {"pid": OWNER}}) is None


@pytest.mark.parametrize("name", NAMES)
def test_ignores_the_window_and_other_processes(name):
    """Only spans at or after the opening, or of another pid: nothing."""
    late = [dict(s, ts=s["ts"] + 100.0) for s in startup()
            if s["pid"] == OWNER or s["name"] == "serve:run"]
    assert read(name, obs_of(late)) is None
    if name not in ("setup_serve_run_s",):
        others = [dict(s, pid=OTHER) if s["name"] != "serve:run" else s
                  for s in startup() if s["ts"] < WINDOW]
        assert read(name, obs_of(others)) is None


def test_spawn_needs_both_ends():
    spans = [s for s in startup() if s["name"] != "serve:run"]
    assert read("setup_spawn_s", obs_of(spans)) is None
    assert read("setup_device_open_s", obs_of(spans)) == pytest.approx(6.0)


def test_cache_off_is_no_share():
    spans = [compile_(960.0, 1.0, "off"), compile_(970.0, 2.0, "off")]
    assert read("setup_cache_hit_pct", obs_of(spans)) is None
    assert read("setup_compile_s", obs_of(spans)) == pytest.approx(3.0)
    cold = [compile_(960.0, 9.0, "miss"), compile_(970.0, 2.0, "miss")]
    assert read("setup_cache_hit_pct", obs_of(cold)) == 0.0


def test_a_span_without_stages_is_none_not_an_error():
    bare = [span("llm:init", 959.5, 11.0),
            span("serve:run", 950.0, 21.0, pid=1)]
    assert read("setup_device_open_s", obs_of(bare)) is None
    assert read("setup_weights_s", obs_of(bare)) is None
    assert read("setup_spawn_s", obs_of(bare)) == pytest.approx(9.5)
