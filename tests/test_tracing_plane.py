"""Request-level distributed tracing plane
(observability/tracing_plane.py): context minting/propagation, the
flight recorder's force-sampled ring, serve end-to-end trace stitching
across processes, shed/deadline force-sampling, and the dashboard
``/api/trace`` + Perfetto + /metrics-exemplar surfaces."""

from __future__ import annotations

import json
import os
import pickle
import time
import urllib.request

import pytest

import ant_ray_tpu as art
from ant_ray_tpu.observability import tracing_plane as tp

JAX = pytest.importorskip("jax")  # noqa: F841 — cluster boots need jax


# ---------------------------------------------------------------------------
# unit: contexts, spans, rings
# ---------------------------------------------------------------------------


def test_context_mint_child_and_pickle():
    ctx = tp.mint(sampled=True)
    assert len(ctx.trace_id) == 32 and len(ctx.span_id) == 16
    child = ctx.child()
    assert child.trace_id == ctx.trace_id
    assert child.span_id != ctx.span_id
    assert child.sampled
    # The sampled flag must survive pickling (contexts ride handles and
    # specs across processes).
    for flag in (True, False):
        c = tp.mint(sampled=flag)
        c2 = pickle.loads(pickle.dumps(c))
        assert (c2.trace_id, c2.span_id, c2.sampled) == \
            (c.trace_id, c.span_id, c.sampled)
    # Wire round trip.
    assert tp.TraceContext.from_wire(ctx.to_wire()).to_wire() == \
        ctx.to_wire()
    assert tp.TraceContext.from_wire(None) is None


def test_mint_respects_sample_rate():
    from ant_ray_tpu._private.config import global_config

    cfg = global_config()
    old = cfg.trace_sample_rate
    try:
        cfg.trace_sample_rate = 0.0
        assert not any(tp.mint().sampled for _ in range(50))
        cfg.trace_sample_rate = 1.0
        assert all(tp.mint().sampled for _ in range(50))
    finally:
        cfg.trace_sample_rate = old


@pytest.fixture
def fresh_recorder(monkeypatch):
    rec = tp.FlightRecorder(size=64)
    monkeypatch.setattr(tp, "_recorder", rec)
    return rec


def test_unsampled_span_records_nothing(fresh_recorder):
    with tp.use(tp.mint(sampled=False)):
        with tp.span("quiet"):
            pass
    assert fresh_recorder.snapshot() == []


def test_no_context_span_is_noop(fresh_recorder):
    assert tp.current() is None
    with tp.span("nothing"):
        pass
    assert fresh_recorder.snapshot() == []


def test_error_span_force_sampled_even_unsampled(fresh_recorder):
    ctx = tp.mint(sampled=False)
    with pytest.raises(ValueError):
        with tp.use(ctx):
            with tp.span("boom", {"k": "v"}):
                raise ValueError("x")
    spans = fresh_recorder.snapshot()
    assert len(spans) == 1
    s = spans[0]
    assert s["error"] and s["forced"]
    assert s["trace_id"] == ctx.trace_id
    assert s["name"] == "boom" and s["attrs"] == {"k": "v"}


def test_ring_wrap_preserves_force_sampled(fresh_recorder):
    """A flood of healthy sampled spans wrapping the main ring must not
    evict the force-sampled error span — it lives in its own ring."""
    err_ctx = tp.mint(sampled=False)
    tp.record_span(err_ctx, "the-failure", ts=time.time(), dur_s=0.01,
                   error=True)
    ok_ctx = tp.mint(sampled=True)
    for i in range(fresh_recorder.size * 3):      # wrap the main ring 3x
        tp.record_span(ok_ctx, f"ok-{i}", ts=time.time(), dur_s=0.0)
    names = {s["name"] for s in fresh_recorder.snapshot()}
    assert "the-failure" in names
    # ...and the main ring really did wrap (early spans evicted).
    assert "ok-0" not in names


def test_span_tree_folding():
    spans = [
        {"trace_id": "t", "span_id": "a", "parent_id": "", "ts": 1.0,
         "name": "root"},
        {"trace_id": "t", "span_id": "b", "parent_id": "a", "ts": 2.0,
         "name": "child"},
        {"trace_id": "t", "span_id": "c", "parent_id": "b", "ts": 3.0,
         "name": "grandchild"},
        {"trace_id": "t", "span_id": "d", "parent_id": "missing",
         "ts": 4.0, "name": "orphan"},
    ]
    roots = tp.span_tree(spans)
    assert [r["name"] for r in roots] == ["root", "orphan"]
    assert roots[0]["children"][0]["name"] == "child"
    assert roots[0]["children"][0]["children"][0]["name"] == "grandchild"


def test_handle_pickle_keeps_sampling_flag():
    """Serve composition: a handle bound to a trace context and pickled
    into a downstream deployment must keep the context — including the
    sampled flag — so its dispatches join the originating trace."""
    from ant_ray_tpu.serve.api import DeploymentHandle

    ctx = tp.mint(sampled=True)
    handle = DeploymentHandle("dep", [], controller=None,
                              trace_ctx=ctx)
    h2 = pickle.loads(pickle.dumps(handle))
    assert h2._trace_ctx is not None
    assert h2._trace_ctx.sampled is True
    assert h2._trace_ctx.trace_id == ctx.trace_id
    # ...and the trace root resolution prefers it when nothing is
    # ambient.
    assert h2._trace_root().trace_id == ctx.trace_id
    # An unsampled binding stays unsampled (no re-flip downstream).
    h3 = pickle.loads(pickle.dumps(
        DeploymentHandle("dep", [], controller=None,
                         trace_ctx=tp.mint(sampled=False))))
    assert h3._trace_ctx.sampled is False


def test_attempt_salted_span_ids():
    from ant_ray_tpu.util.tracing import _span_id, task_spans

    assert _span_id("task1", 0) == _span_id("task1")
    assert _span_id("task1", 1) != _span_id("task1", 0)
    # Retried execution: same task id, two attempts → two spans with
    # distinct span ids under one trace.
    base = {"task_id": "t1", "name": "f", "node_id": "n", "pid": 1}
    events = [
        dict(base, event="submitted", ts=1.0, attempt=0),
        dict(base, event="started", ts=1.1, attempt=0),
        dict(base, event="failed", ts=1.2, attempt=0),
        dict(base, event="started", ts=1.4, attempt=1),
        dict(base, event="finished", ts=1.5, attempt=1),
    ]
    spans = task_spans(events, span_events=[])
    assert len(spans) == 2
    assert len({s.span_id for s in spans}) == 2
    assert len({s.trace_id for s in spans}) == 1
    failed = next(s for s in spans if not s.ok)
    ok = next(s for s in spans if s.ok)
    assert failed.attributes.get("art.attempt", 0) == 0
    assert ok.attributes["art.attempt"] == 1


def test_task_spans_folds_live_spans_single_code_path():
    """Propagated spans take precedence: a task covered by a live
    execution span is NOT re-derived from events."""
    from ant_ray_tpu.util.tracing import task_spans

    live = [{"trace_id": "a" * 32, "span_id": "b" * 16,
             "parent_id": "", "name": "run:f", "ts": 1.0, "dur_s": 0.5,
             "stages": {"queue": 0.1, "execute": 0.4},
             "attrs": {"task_id": "t1"}, "node_id": "n", "pid": 2}]
    events = [
        {"task_id": "t1", "name": "f", "event": "started", "ts": 1.0,
         "node_id": "n", "pid": 2},
        {"task_id": "t1", "name": "f", "event": "finished", "ts": 1.5,
         "node_id": "n", "pid": 2},
        {"task_id": "t2", "name": "g", "event": "started", "ts": 2.0,
         "node_id": "n", "pid": 3},
        {"task_id": "t2", "name": "g", "event": "finished", "ts": 2.1,
         "node_id": "n", "pid": 3},
    ]
    spans = task_spans(events, span_events=live)
    names = [s.name for s in spans]
    assert names.count("run:f") == 1          # live span, not re-derived
    assert "f" not in names                   # derived duplicate absent
    assert "g" in names                       # uncovered task derived
    live_span = next(s for s in spans if s.name == "run:f")
    assert live_span.trace_id == "a" * 32
    assert live_span.attributes["art.stage.execute_s"] == 0.4


# ---------------------------------------------------------------------------
# cluster end-to-end
# ---------------------------------------------------------------------------


def test_two_node_cross_node_trace():
    """Satellite propagation edge: a traced task pinned to a second
    node pulls a head-owned plasma object — the execution span and the
    pull span land on node 2 under the driver's single trace id.
    (Runs FIRST among the cluster tests: it boots its own 2-node
    cluster, which must not coexist with the module fixture's.)"""
    import numpy as np

    from ant_ray_tpu._private import config as config_mod
    from ant_ray_tpu.cluster_utils import Cluster
    from ant_ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy,
    )

    os.environ["ART_TRACE_SAMPLE_RATE"] = "1.0"
    config_mod._global_config = None
    cluster = Cluster(head_node_args={"num_cpus": 1})
    second = cluster.add_node(num_cpus=1)
    try:
        cluster.connect()
        target = next(
            n["NodeID"] for n in art.nodes()
            if n["Address"] == second)

        blob_ref = art.put(np.ones(400_000, dtype=np.uint8))

        @art.remote
        def consume(arr):
            return int(arr.sum())           # arg auto-fetch = the pull

        strategy = NodeAffinitySchedulingStrategy(node_id=target)
        value = art.get(consume.options(
            scheduling_strategy=strategy).remote(blob_ref))
        assert value == 400_000

        def _runs(spans):
            return [s for s in spans if s["name"].startswith("run:")
                    and "consume" in s["name"]]

        def _landed(spans):
            # both: the worker's span and the daemon's arrive on
            # flushes of their own
            return _runs(spans) and any(
                s["name"] == "daemon:object_pull" for s in spans)

        spans = _gcs_spans(_landed)
        runs = _runs(spans)
        assert runs, [s["name"] for s in spans]
        trace_id = runs[-1]["trace_id"]
        ours = [s for s in spans if s["trace_id"] == trace_id]
        names = {s["name"] for s in ours}
        assert "daemon:object_pull" in names, names
        pull = next(s for s in ours
                    if s["name"] == "daemon:object_pull")
        # The pull executed on the SECOND node, stitched into the
        # driver-minted trace.
        assert pull["node_id"] == target[:12]
        assert runs[-1]["node_id"] == target[:12]
    finally:
        art.shutdown()
        cluster.shutdown()
        os.environ.pop("ART_TRACE_SAMPLE_RATE", None)
        config_mod._global_config = None


@pytest.fixture(scope="module")
def traced_cluster():
    os.environ["ART_TRACE_SAMPLE_RATE"] = "1.0"
    from ant_ray_tpu._private import config as config_mod

    config_mod._global_config = None
    ctx = art.init(num_cpus=4,
                   _system_config={"include_dashboard": True})
    assert ctx.dashboard_url, "dashboard did not start"
    yield ctx.dashboard_url
    from ant_ray_tpu import serve

    serve.shutdown()
    art.shutdown()
    os.environ.pop("ART_TRACE_SAMPLE_RATE", None)
    config_mod._global_config = None


def _gcs_spans(predicate=None, timeout=20.0, **payload):
    """Poll the GCS span ring until ``predicate(spans)`` holds (span
    publication is batched per process on a ~1s age flush)."""
    from ant_ray_tpu.api import global_worker

    deadline = time.monotonic() + timeout
    while True:
        tp.flush()
        spans = global_worker.runtime._gcs.call(
            "SpanEventsGet", dict({"limit": 50000}, **payload),
            retries=3)
        if predicate is None or predicate(spans) \
                or time.monotonic() > deadline:
            return spans
        time.sleep(0.3)


def test_serve_request_one_trace_across_processes(traced_cluster):
    """The acceptance shape: one serve request — HTTP ingress → router
    → replica → nested actor task → plasma object pull — is ONE
    trace_id across >= 3 processes and renders as a single tree via
    GET /api/trace/{id}."""
    import numpy as np

    from ant_ray_tpu import serve

    blob_ref = art.put(np.zeros(300_000, dtype=np.uint8))  # plasma-sized

    @art.remote
    def nested(n):
        return int(n) * 2

    @serve.deployment(name="traced_dep", route_prefix="/traced_dep")
    class Traced:
        def __init__(self, cfg):
            self._ref = cfg["ref"]     # kept as a ref (nested in dict)

        def __call__(self, request):
            data = art.get(self._ref)             # plasma pull
            return art.get(nested.remote(len(data)))  # nested task

    handle = serve.run(Traced.bind({"ref": blob_ref}), port=0)
    port = serve.api.run.last_http_port
    with urllib.request.urlopen(
            urllib.request.Request(
                f"http://127.0.0.1:{port}/traced_dep",
                data=json.dumps({}).encode(),
                headers={"Content-Type": "application/json"}),
            timeout=30) as resp:
        assert json.loads(resp.read())["result"] == 600_000
    del handle

    def _complete(spans):
        names = {s["name"] for s in spans}
        return (any(n.startswith("http:") for n in names)
                and "daemon:object_pull" in names
                and "replica:traced_dep" in names
                and any(n.startswith("run:") and "nested" in n
                        for n in names))

    spans = _gcs_spans(_complete)
    http_spans = [s for s in spans if s["name"].startswith("http:")]
    assert http_spans, [s["name"] for s in spans]
    trace_id = http_spans[-1]["trace_id"]
    ours = [s for s in spans if s["trace_id"] == trace_id]
    names = {s["name"] for s in ours}
    assert "route:traced_dep" in names, names
    assert "replica:traced_dep" in names, names
    assert any(n.startswith("run:") and "nested" in n
               for n in names), names
    assert "daemon:object_pull" in names, names
    # >= 3 distinct processes stitched by the single trace id.
    assert len({(s.get("node_id"), s["pid"]) for s in ours}) >= 3, ours

    # One tree via the dashboard.
    with urllib.request.urlopen(
            traced_cluster + f"/api/trace/{trace_id}",
            timeout=15) as resp:
        body = json.loads(resp.read())
    assert body["trace_id"] == trace_id
    assert body["span_count"] == len(ours)
    assert len(body["tree"]) == 1, [r["name"] for r in body["tree"]]
    root = body["tree"][0]
    assert root["name"].startswith("http:")

    def walk(node):
        yield node["name"]
        for c in node["children"]:
            yield from walk(c)

    flat = list(walk(root))
    assert "replica:traced_dep" in flat
    assert "daemon:object_pull" in flat


def test_timeline_and_otlp_carry_request_spans(traced_cluster):
    """Perfetto rows per request + OTLP export through the existing
    exporters read the same span ring."""
    trace = art.timeline()
    request_rows = [t for t in trace if t.get("cat") == "request_span"]
    assert request_rows
    assert any(t["name"].startswith("replica:") for t in request_rows)
    json.dumps(trace)                              # Perfetto-loadable

    from ant_ray_tpu.util.tracing import export_otlp_json, task_spans

    spans = task_spans()
    live = [s for s in spans if s.name.startswith("replica:")]
    assert live, [s.name for s in spans][:20]
    payload = export_otlp_json(spans=spans)
    otlp = payload["resourceSpans"][0]["scopeSpans"][0]["spans"]
    assert any(s["name"].startswith("replica:") for s in otlp)


def test_flightrecorder_endpoint(traced_cluster):
    with urllib.request.urlopen(traced_cluster + "/api/flightrecorder",
                                timeout=15) as resp:
        nodes = json.loads(resp.read())
    assert nodes and all("spans" in n and "node_id" in n for n in nodes)
    # The daemon's own ring holds its lease/pull spans.
    names = {s["name"] for n in nodes for s in n["spans"]}
    assert names & {"daemon:lease", "daemon:object_pull"}, names


def test_rpc_latency_histogram_with_exemplar(traced_cluster):
    # OpenMetrics negotiation: exemplars + EOF marker.
    req = urllib.request.Request(
        traced_cluster + "/metrics",
        headers={"Accept": "application/openmetrics-text"})
    with urllib.request.urlopen(req, timeout=15) as resp:
        assert "openmetrics" in resp.headers.get("Content-Type", "")
        text = resp.read().decode()
    assert text.endswith("# EOF\n")
    lines = [l for l in text.splitlines()
             if l.startswith("art_rpc_latency_s_bucket")]
    assert lines, text[:2000]
    assert any('stage="execute"' in l or 'stage="wire"' in l
               for l in lines)
    # At least one bucket line carries an OpenMetrics exemplar linking
    # to a concrete trace id.
    assert any("# {" in l and "trace_id=" in l for l in lines), \
        lines[:10]
    # Classic text-format scrape: same series, NO exemplar suffixes (a
    # 0.0.4 parser would fail the whole scrape on the '#').
    with urllib.request.urlopen(traced_cluster + "/metrics",
                                timeout=15) as resp:
        plain = resp.read().decode()
    assert "art_rpc_latency_s_bucket" in plain
    assert not any("# {" in l for l in plain.splitlines())


def test_shed_and_deadline_spans_force_sampled(traced_cluster):
    """429 (backpressure) and 504 (deadline) outcomes must surface as
    error spans even when the request was NOT head-sampled."""
    import threading

    from ant_ray_tpu import serve
    from ant_ray_tpu._private.config import global_config
    from ant_ray_tpu.exceptions import (
        BackPressureError,
        DeadlineExceededError,
    )

    @serve.deployment(name="bounded_traced", max_ongoing_requests=1,
                      max_queued_requests=1)
    class Bounded:
        def __call__(self, request=None):
            time.sleep(0.5)
            return "ok"

    handle = serve.run(Bounded.bind())
    handle.call()                                  # warm
    cfg = global_config()
    old = cfg.trace_sample_rate
    cfg.trace_sample_rate = 0.0                    # NOTHING head-sampled
    try:
        def hold():
            try:
                handle.call()
            except Exception:  # noqa: BLE001
                pass

        # 1 running + 1 queued → the third call sheds (429-shaped).
        holders = [threading.Thread(target=hold) for _ in range(2)]
        for t in holders:
            t.start()
            time.sleep(0.1)
        with pytest.raises(BackPressureError):
            handle.call()
        for t in holders:
            t.join()
        # Deadline expiring while queued → 504-shaped shed.
        t = threading.Thread(target=hold)
        t.start()
        time.sleep(0.1)
        with pytest.raises(DeadlineExceededError):
            handle.call(timeout_s=0.15)
        t.join()
    finally:
        cfg.trace_sample_rate = old
    def _has_sheds(spans):
        kinds = {(s.get("attrs") or {}).get("shed") for s in spans}
        return {"BackPressureError", "DeadlineExceededError"} <= kinds

    spans = _gcs_spans(_has_sheds, errors_only=True)
    shed = [s for s in spans
            if (s.get("attrs") or {}).get("shed") == "BackPressureError"]
    deadline = [s for s in spans
                if (s.get("attrs") or {}).get("shed")
                == "DeadlineExceededError"]
    assert shed and deadline, [
        (s["name"], s.get("attrs")) for s in spans][-20:]
    # Force-sampled: the sheds above ran with sample rate 0.
    assert any(s.get("forced") for s in shed + deadline)


def test_serve_metric_series_expire_on_teardown(traced_cluster):
    """Satellite: stale-series expiry.  MetricsExpire drops matching
    series; serve teardown uses it for deployment/replica gauges."""
    from ant_ray_tpu.api import global_worker

    gcs = global_worker.runtime._gcs
    gcs.call("MetricRecord", {
        "name": "art_serve_queue_depth", "type": "gauge", "value": 3.0,
        "tags": {"deployment": "expire_me"}, "description": "t"})
    gcs.call("MetricRecord", {
        "name": "art_serve_breaker_state", "type": "gauge", "value": 0.0,
        "tags": {"deployment": "expire_me", "replica": "abc123"},
        "description": "t"})
    gcs.call("MetricRecord", {
        "name": "art_device_hbm_bytes_in_use", "type": "gauge",
        "value": 1.0, "tags": {"node_id": "deadbeef0000",
                               "device": "d0"}, "description": "t"})
    names = {(m["name"], tuple(sorted(m["tags"].items())))
             for m in gcs.call("MetricsGet")}
    assert any(n == "art_serve_queue_depth" for n, _t in names)
    dropped = gcs.call("MetricsExpire", {
        "match_tags": {"deployment": "expire_me"},
        "name_prefix": "art_serve_"})
    assert dropped == 2
    remaining = [m for m in gcs.call("MetricsGet")
                 if m["tags"].get("deployment") == "expire_me"]
    assert remaining == []
    # Node-tagged series expire by node id match too.
    dropped = gcs.call("MetricsExpire", {
        "match_tags": {"node_id": "deadbeef0000"}})
    assert dropped == 1


