"""Set-up from inside: ``serve.run`` / ``JaxTrainer.fit`` to a ready
replica / a reporting gang as ONE trace of forced start-up spans, every
compilation a ``jit:compile`` span, and the ``forced`` keyword that
keeps them whatever the sampling coin says.  The whole-tree CPU pin
simulates ``TPU`` resources."""

import logging
import time

import pytest

import jax
import jax.numpy as jnp

import ant_ray_tpu as art
from ant_ray_tpu.observability import compile_watch, tracing_plane

CHAIN = ("actor:create", "worker:spawn", "worker:boot", "actor:init")


def _trace(root_name, needed, timeout=30.0):
    """The spans of the newest ``root_name`` trace, once it holds every
    span name in ``needed`` (other processes publish a second late)."""
    from ant_ray_tpu.util.timeline import fetch_span_events

    deadline = time.monotonic() + timeout
    while True:
        spans = fetch_span_events()
        roots = [s for s in spans if s["name"] == root_name]
        if roots:
            root = max(roots, key=lambda s: s["ts"])
            mine = [s for s in spans if s["trace_id"] == root["trace_id"]]
            if needed <= {s["name"] for s in mine}:
                return root, mine
        assert time.monotonic() < deadline, (
            root_name, sorted({s["name"] for s in spans}))
        time.sleep(0.5)


def _by_id(spans):
    return {s["span_id"]: s for s in spans}


def _lineage(span, by_id):
    """The names from ``span`` up to its root."""
    names = []
    while span is not None:
        names.append(span["name"])
        span = by_id.get(span["parent_id"])
    return names


def _covered(root, spans):
    """The share of ``root``'s interval that the other spans cover."""
    lo, hi = root["ts"], root["ts"] + root["dur_s"]
    cuts = sorted((max(lo, s["ts"]), min(hi, s["ts"] + s["dur_s"]))
                  for s in spans if s is not root)
    covered, at = 0.0, lo
    for begin, end in cuts:
        if end > max(at, begin):
            covered += end - max(at, begin)
            at = end
    return covered / root["dur_s"]


def _assert_stages_add_up(span):
    assert sum(span["stages"].values()) == pytest.approx(
        span["dur_s"], rel=0.01, abs=2e-3), span


def test_serve_run_leaves_one_startup_trace(shutdown_only, caplog):
    from ant_ray_tpu import serve
    from ant_ray_tpu.llm.serve_llm import build_llm_deployment

    art.init(num_cpus=2, num_tpus=1)         # the replica leases a chip
    try:
        with caplog.at_level(logging.INFO, logger="ant_ray_tpu.serve.api"):
            handle = serve.run(
                build_llm_deployment("tiny", slots=2, max_seq=64), port=0)
        out = art.get(handle.remote({"prompt": "hi", "max_tokens": 3}),
                      timeout=180)
        assert out["choices"]
        root, spans = _trace("serve:run", {*CHAIN, "llm:init",
                                           "jit:compile"})
    finally:
        serve.shutdown()
    by_id = _by_id(spans)
    assert root["parent_id"] == "" and root["forced"] is True
    assert "error" not in root
    assert list(root["stages"]) == ["controller", "deploy",
                                    "replicas_ready", "proxy"]
    assert root["attrs"]["app"] == "llm" and root["attrs"]["replicas"] == 1
    # ONE info line, from the root's own stages, with the trace id
    (line,) = [r.getMessage() for r in caplog.records
               if "serve.run ready in" in r.getMessage()]
    assert root["trace_id"] in line and "replicas_ready" in line

    # the replica's chain, each span the child of the one that caused it
    (init,) = [s for s in spans if s["name"] == "llm:init"]
    assert _lineage(init, by_id) == ["llm:init", *reversed(CHAIN),
                                     "serve:deploy", "serve:run"]
    create = by_id[by_id[by_id[by_id[init["parent_id"]]["parent_id"]]
                         ["parent_id"]]["parent_id"]]
    assert create["attrs"]["class"] == "Replica"
    assert create["attrs"]["resources"]["TPU"] == 1
    assert list(create["stages"]) == ["schedule", "start"]
    spawn = next(s for s in spans if s["name"] == "worker:spawn"
                 and s["parent_id"] == create["span_id"])
    boot = next(s for s in spans if s["parent_id"] == spawn["span_id"])
    # under the whole-tree pin the chip's worker is spawned onto the cpu
    assert spawn["attrs"]["JAX_PLATFORMS"] == "cpu"
    assert spawn["attrs"]["tpu_chips"] == [0]
    assert spawn["attrs"]["pid"] == boot["pid"] == init["pid"]
    assert boot["attrs"]["pid"] == boot["pid"]
    assert list(boot["stages"]) == ["imports", "connect", "register"]
    # the worker's boot starts at the daemon's Popen, not at a guess
    assert boot["ts"] == spawn["ts"]
    # the controller and the proxy are actors of the same trace, the
    # proxy's under the controller's `serve:proxy`
    creates = {s["attrs"]["class"]: s for s in spans
               if s["name"] == "actor:create"}
    assert set(creates) == {"ServeController", "Replica", "HttpProxy"}
    assert by_id[creates["ServeController"]["parent_id"]] is root
    assert _lineage(creates["HttpProxy"], by_id) == [
        "actor:create", "serve:proxy", "serve:run"]
    # the driver's own share of `deploy`: the call's arguments pickled
    (submit,) = [s for s in spans if s["name"] == "serve:submit"]
    assert by_id[submit["parent_id"]] is root
    assert submit["pid"] == root["pid"]
    assert list(submit["stages"]) == ["serialize"]
    deploy = by_id[create["parent_id"]]
    assert deploy["ts"] >= submit["ts"] + submit["dur_s"] - 1e-3
    assert list(deploy["stages"]) == ["create", "replicas_ready", "publish"]
    # the driver's stage is the controller's, handed back in the reply
    assert root["stages"]["replicas_ready"] == pytest.approx(
        deploy["stages"]["replicas_ready"], abs=1e-3)

    assert list(init["stages"]) == ["device_open", "tokenizer", "weights",
                                    "cache", "loop"]
    attrs = init["attrs"]
    assert attrs["platform"] == "cpu" and attrs["slots"] == 2
    assert attrs["max_seq"] == 64
    assert attrs["param_bytes"] > 0 and attrs["cache_bytes"] > 0
    for span in spans:
        assert span["forced"] is True and "error" not in span
        if span.get("stages"):
            _assert_stages_add_up(span)
    # what the constructor compiled hangs under it, named
    under = [s for s in spans if s["name"] == "jit:compile"
             and s["parent_id"] == init["span_id"]]
    assert "init_params" in {s["attrs"]["fun_name"] for s in under}
    assert all(s["pid"] == init["pid"] and "after_ready" not in s["attrs"]
               for s in under)
    # self time: the other spans leave under a tenth of it uncovered
    assert _covered(root, spans) >= 0.9


def test_fit_leaves_one_startup_trace(shutdown_only, tmp_path, caplog):
    from ant_ray_tpu import train
    from ant_ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    def loop(config):
        for i in range(2):
            train.report({"i": i})

    art.init(num_cpus=4, num_tpus=2)
    trainer = JaxTrainer(
        loop, train_loop_config={},
        scaling_config=ScalingConfig(num_workers=2, use_tpu=True,
                                     chips_per_worker=1),
        run_config=RunConfig(storage_path=str(tmp_path)))
    with caplog.at_level(logging.INFO, logger="ant_ray_tpu.train.trainer"):
        result = trainer.fit()
    assert result.metrics == {"i": 1}
    root, spans = _trace("train:fit", {*CHAIN, "train:worker_init"})
    by_id = _by_id(spans)
    assert list(root["stages"]) == ["controller", "placement_group",
                                    "workers", "backend", "first_report"]
    assert root["stages"] == result.startup
    assert root["attrs"]["workers"] == 2 and root["forced"] is True
    _assert_stages_add_up(root)
    (line,) = [r.getMessage() for r in caplog.records
               if "first report" in r.getMessage()]
    assert root["trace_id"] in line and "placement_group" in line
    inits = sorted((s for s in spans if s["name"] == "train:worker_init"),
                   key=lambda s: s["attrs"]["rank"])
    assert [s["attrs"]["rank"] for s in inits] == [0, 1]
    for init in inits:
        assert _lineage(init, by_id) == ["train:worker_init",
                                         *reversed(CHAIN), "train:fit"]
        assert list(init["stages"]) == ["distributed_init", "run_dispatch",
                                        "device_open"]
        assert init["attrs"]["platform"] == "cpu"
        _assert_stages_add_up(init)
    classes = sorted(s["attrs"]["class"] for s in spans
                     if s["name"] == "actor:create")
    assert classes == ["TrainController", "TrainWorker", "TrainWorker"]
    assert _covered(root, spans) >= 0.9


def _compiles(name):
    return [s for s in tracing_plane.recorder().snapshot()
            if s["name"] == "jit:compile"
            and s["attrs"]["fun_name"] == name]


def test_first_call_of_a_jitted_function_is_one_compile_span():
    def fresh_startup_span_fn(x):
        return jnp.tanh(x) @ x.T

    fn = jax.jit(fresh_startup_span_fn)
    ctx = tracing_plane.mint(sampled=False)
    began = time.time()
    with tracing_plane.use(ctx):
        first = fn(jnp.ones((8, 8))).block_until_ready()
    (span,) = _compiles("fresh_startup_span_fn")
    assert span["forced"] is True and "error" not in span
    # under the context current on the compiling thread
    assert span["trace_id"] == ctx.trace_id
    assert span["parent_id"] == ctx.span_id
    attrs = span["attrs"]
    assert attrs["cache"] in ("hit", "miss", "off")
    assert (attrs["backend_s"] == 0.0) == (attrs["cache"] == "hit")
    assert attrs["trace_s"] > 0 and attrs["lower_s"] > 0
    assert began - 1 <= span["ts"] <= time.time()
    assert span["dur_s"] >= attrs["trace_s"] + attrs["lower_s"]
    # a second call compiles nothing: no span
    second = fn(jnp.ones((8, 8))).block_until_ready()
    assert (first == second).all()
    assert len(_compiles("fresh_startup_span_fn")) == 1
    # with no context on the thread: the process's own trace id
    other = jax.jit(lambda x: fresh_startup_span_fn(x) + 1)
    other(jnp.ones((8, 8))).block_until_ready()
    (lone,) = _compiles("<lambda>")[-1:]
    assert lone["trace_id"] != ctx.trace_id
    assert lone["trace_id"] == compile_watch._process_ctx.trace_id


def test_a_listener_that_raises_does_not_break_the_compile(monkeypatch):
    def boom(*args, **kwargs):
        raise RuntimeError("the listener's own fault")

    monkeypatch.setattr(compile_watch, "_record", boom)

    def guarded_startup_span_fn(x):
        return jnp.cos(x).sum()

    out = jax.jit(guarded_startup_span_fn)(jnp.zeros((4,)))
    assert float(out) == 4.0
    assert _compiles("guarded_startup_span_fn") == []


def test_a_compile_after_the_first_step_says_so(monkeypatch, caplog):
    """The engine's first completed step marks the process ready; the
    first eviction then compiles ``_extract`` behind it."""
    from ant_ray_tpu.llm import LLMEngine, SamplingParams
    from ant_ray_tpu.llm.kv_offload import LocalKvStore

    monkeypatch.setattr(compile_watch, "_ready", False)
    eng = LLMEngine("tiny", slots=2, max_seq=64,
                    kv_offload_store=LocalKvStore())
    assert eng.init_s["weights"] > 0 and eng.init_s["cache"] > 0
    eng.add_request([5, 9, 17], SamplingParams(max_tokens=3), admit=False,
                    session_id="s")
    while eng.has_unfinished():
        eng.step()
    assert compile_watch._ready
    with caplog.at_level(logging.WARNING,
                         logger="ant_ray_tpu.observability.compile_watch"):
        assert eng.evict_session("s")
    (span,) = _compiles("_extract")[-1:]
    assert span["attrs"]["after_ready"] is True and span["forced"] is True
    (line,) = [r.getMessage() for r in caplog.records
               if "_extract" in r.getMessage()]
    assert "jit:compile after the engine's first step" in line
    # the programs of the first step itself were compiled before it ended
    first = [s for s in tracing_plane.recorder().snapshot()
             if s["name"] == "jit:compile"
             and s["attrs"]["fun_name"] == "_decode"][-1:]
    assert first and "after_ready" not in first[0]["attrs"]


@pytest.mark.parametrize("sampled", [False, True])
def test_forced_keeps_a_span_at_sample_rate_0_without_error(sampled):
    ctx = tracing_plane.mint(sampled=sampled)
    rec = tracing_plane.recorder()
    assert tracing_plane.record_span(ctx, "plain", ts=1.0,
                                     dur_s=0.1) is not None or not sampled
    sid = tracing_plane.record_span(ctx, "kept", ts=2.0, dur_s=0.1,
                                    forced=True)
    mine = [s for s in rec.snapshot() if s["trace_id"] == ctx.trace_id]
    assert [s["name"] for s in mine] == (["plain", "kept"] if sampled
                                         else ["kept"])
    kept = mine[-1]
    assert kept["span_id"] == sid and kept["forced"] is True
    assert "error" not in kept
    # into the protected ring, which healthy traffic never wraps
    assert kept in rec._forced and kept not in rec._ring


def test_staged_span_laps_add_up_and_scope_the_context():
    outer = tracing_plane.mint(sampled=False)
    with tracing_plane.use(outer):
        with tracing_plane.staged_span("unit:staged", attrs={"a": 1}) as sp:
            assert tracing_plane.current() is sp.ctx
            time.sleep(0.01)
            sp.lap("one")
            time.sleep(0.01)
            sp.lap("two")
        assert tracing_plane.current() is outer
    (span,) = [s for s in tracing_plane.recorder().snapshot()
               if s["trace_id"] == outer.trace_id]
    assert span["parent_id"] == outer.span_id
    assert span["span_id"] == sp.ctx.span_id
    assert list(span["stages"]) == ["one", "two"]
    _assert_stages_add_up(span)
    assert span["trace_id"] in sp.summary() and "one 0.0" in sp.summary()
    # alone it is the root of a trace of its own; a failure marks it
    with pytest.raises(ValueError):
        with tracing_plane.staged_span("unit:alone") as lone:
            raise ValueError("x")
    (span,) = [s for s in tracing_plane.recorder().snapshot()
               if s["trace_id"] == lone.ctx.trace_id]
    assert span["parent_id"] == "" and span["error"] is True


def test_a_stall_is_forced_and_not_an_error(caplog):
    from ant_ray_tpu.llm.engine import STALL_S, _PhaseRecorder

    rec = _PhaseRecorder(jax, {})
    rec.landed = 3
    rec._began = (time.perf_counter() - STALL_S - 0.05, 0.0)
    rec._longest = ("fetch", STALL_S)
    before = [s for s in tracing_plane.recorder().snapshot()
              if s["name"] == "llm:stall"]
    with caplog.at_level(logging.WARNING, logger="ant_ray_tpu.llm.engine"):
        rec._record_stall(time.perf_counter())
    (stall,) = [s for s in tracing_plane.recorder().snapshot()
                if s["name"] == "llm:stall" and s not in before]
    assert stall["forced"] is True and "error" not in stall
    assert stall["attrs"]["rows"] == 3
    assert stall["attrs"]["phase"] == "fetch"
    assert any("stood still" in r.getMessage() for r in caplog.records)

