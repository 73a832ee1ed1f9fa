"""Serve layer tests (ref test model: serve/tests)."""

import pytest

import ant_ray_tpu as art
from ant_ray_tpu import serve


@pytest.fixture(scope="module")
def cluster():
    art.init(num_cpus=4, num_tpus=0)
    yield None
    serve.shutdown()
    art.shutdown()


def test_function_deployment(cluster):
    @serve.deployment
    def double(x):
        return x * 2

    handle = serve.run(double.bind())
    assert art.get(handle.remote(21)) == 42


def test_class_deployment_with_state(cluster):
    @serve.deployment(name="counter")
    class Counter:
        def __init__(self, start):
            self.n = start

        def __call__(self, k):
            self.n += k
            return self.n

        def peek(self):
            return self.n

    handle = serve.run(Counter.bind(100))
    assert art.get(handle.remote(5)) == 105
    assert art.get(handle.options(method_name="peek").remote()) == 105


def test_multi_replica_distribution(cluster):
    @serve.deployment(name="who", num_replicas=2)
    class Who:
        def __call__(self):
            import os

            return os.getpid()

    handle = serve.run(Who.bind())
    pids = set(art.get([handle.remote() for _ in range(8)]))
    assert len(pids) == 2


def test_redeploy_replaces_replicas(cluster):
    @serve.deployment(name="ver")
    class V1:
        def __call__(self):
            return "v1"

    @serve.deployment(name="ver")
    class V2:
        def __call__(self):
            return "v2"

    h1 = serve.run(V1.bind())
    assert art.get(h1.remote()) == "v1"
    h2 = serve.run(V2.bind())
    assert art.get(h2.remote()) == "v2"


def test_http_ingress(cluster):
    @serve.deployment(name="api", route_prefix="/api")
    class Api:
        def __call__(self, body):
            return {"echo": body.get("msg", ""), "n": body.get("n", 0) + 1}

    serve.run(Api.bind(), port=0)
    port = serve.api.run.last_http_port
    assert port

    import json
    import urllib.request

    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/api",
        data=json.dumps({"msg": "hi", "n": 41}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as resp:
        out = json.loads(resp.read())
    assert out["result"] == {"echo": "hi", "n": 42}

    # 404 for unknown route
    try:
        urllib.request.urlopen(
            f"http://127.0.0.1:{port}/nope", timeout=30)
        raised = False
    except urllib.error.HTTPError as e:
        raised = e.code == 404
    assert raised


def test_streaming_handle(cluster):
    """handle.options(stream=True) returns an ObjectRefGenerator fed by
    the replica's generator method (ref: serve streaming handles)."""
    from ant_ray_tpu import serve

    @serve.deployment(name="streamer")
    class Streamer:
        def stream(self, request):
            for i in range(int(request["n"])):
                yield {"i": i}

    handle = serve.run(Streamer.bind())
    gen = handle.options(method_name="stream", stream=True).remote(
        {"n": 4})
    items = [art.get(ref, timeout=60) for ref in gen]
    assert items == [{"i": 0}, {"i": 1}, {"i": 2}, {"i": 3}]
    serve.shutdown()


def test_batching_coalesces_requests(cluster):
    """@serve.batch turns N concurrent single calls into few list calls
    (ref: serve/batching.py)."""
    from ant_ray_tpu import serve

    @serve.deployment(name="batched",
                      ray_actor_options={"max_concurrency": 16})
    class Batched:
        def __init__(self):
            self.batch_sizes = []

        @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.2)
        def __call__(self, items):
            self.batch_sizes.append(len(items))
            return [x * 2 for x in items]

        def sizes(self):
            return self.batch_sizes

    handle = serve.run(Batched.bind())
    refs = [handle.remote(i) for i in range(8)]
    assert sorted(art.get(refs, timeout=60)) == [i * 2 for i in range(8)]
    sizes = art.get(handle.options(method_name="sizes").remote(),
                    timeout=60)
    # 8 concurrent requests must NOT take 8 model invocations.
    assert sum(sizes) == 8
    assert max(sizes) >= 2, sizes
    serve.shutdown()


@pytest.mark.slow
def test_autoscaling_follows_load(cluster):
    """Replica count rises under queued load and returns to min when
    idle (ref: serve/_private/autoscaling_state.py)."""
    import threading as _threading
    import time as _time

    from ant_ray_tpu import serve

    @serve.deployment(name="scaly",
                      autoscaling_config={"min_replicas": 1,
                                          "max_replicas": 3,
                                          "target_ongoing_requests": 1.0,
                                          "downscale_patience": 2})
    class Scaly:
        def __call__(self, x):
            _time.sleep(1.0)
            return x

    handle = serve.run(Scaly.bind())
    assert serve_replica_count("scaly") == 1

    # Offer sustained concurrent load for a few seconds.
    stop = _time.monotonic() + 6
    def pump():
        while _time.monotonic() < stop:
            try:
                art.get(handle.remote(1), timeout=30)
            except Exception:
                return
    threads = [_threading.Thread(target=pump) for _ in range(6)]
    for t in threads:
        t.start()
    grown = 0
    while _time.monotonic() < stop:
        grown = max(grown, serve_replica_count("scaly"))
        if grown >= 2:
            break
        _time.sleep(0.25)
    for t in threads:
        t.join()
    assert grown >= 2, f"never scaled up (peak {grown})"

    # Idle: back down to min.
    deadline = _time.monotonic() + 20
    while _time.monotonic() < deadline:
        if serve_replica_count("scaly") == 1:
            break
        _time.sleep(0.5)
    assert serve_replica_count("scaly") == 1
    serve.shutdown()


def serve_replica_count(name):
    from ant_ray_tpu import serve as _serve

    controller = art.get_actor(_serve.CONTROLLER_NAME, namespace="_serve")
    info = art.get(controller.list_deployments.remote())
    return info[name]["num_replicas"]


def test_autoscaling_scales_on_target_signal(cluster):
    """`AutoscalingConfig(target_signal=...)` sizes the deployment from
    the replicas' load_signals() gauges (the LLM engine loop publishes
    art_llm_* this way) — here the signal demands 3 replicas while
    ongoing-request load is zero."""
    import time as _time

    from ant_ray_tpu import serve

    @serve.deployment(name="siggy",
                      autoscaling_config={
                          "min_replicas": 1, "max_replicas": 3,
                          "target_ongoing_requests": 100.0,
                          "interval_s": 0.3,
                          "target_signal": "art_llm_queue_depth",
                          "target_value": 2.0})
    class Siggy:
        def __call__(self, x):
            return x

        def load_signals(self):
            return {"art_llm_queue_depth": 5.0}

    serve.run(Siggy.bind())
    # One replica reports 5.0 → ceil(5/2) = 3 > ongoing-based 0.
    deadline = _time.monotonic() + 30
    while _time.monotonic() < deadline and \
            serve_replica_count("siggy") < 3:
        _time.sleep(0.25)
    assert serve_replica_count("siggy") == 3
    serve.shutdown()


def test_model_multiplexing(cluster):
    """Multiplexed models: per-replica LRU loading + model->replica
    affinity routing (ref: serve/_private/multiplex.py,
    @serve.multiplexed, handle.options(multiplexed_model_id=...))."""
    from ant_ray_tpu import serve

    @serve.deployment(num_replicas=2)
    class MuxModel:
        def __init__(self):
            self.loads = []

        @serve.multiplexed(max_num_models_per_replica=1)
        def get_model(self, model_id):
            self.loads.append(model_id)
            return f"model-{model_id}"

        def __call__(self, x):
            import os
            model_id = serve.get_multiplexed_model_id()
            model = self.get_model()
            return {"model": model, "pid": os.getpid(),
                    "loads": len(self.loads), "x": x}

    handle = serve.run(MuxModel.bind())

    # Same model id -> same replica every time (affinity).
    a_pids = {art.get(handle.options(multiplexed_model_id="a")
                      .remote(i))["pid"] for i in range(4)}
    assert len(a_pids) == 1

    out_b = art.get(handle.options(multiplexed_model_id="b").remote(0))
    assert out_b["model"] == "model-b"

    # LRU width 1: re-requesting "a" after "b" on the SAME replica
    # would reload; with affinity, "a" stays on its own replica and its
    # second batch of calls does not grow the load count.
    out_a = art.get(handle.options(multiplexed_model_id="a").remote(9))
    assert out_a["model"] == "model-a"
    assert out_a["pid"] in a_pids
    assert out_a["loads"] == 1  # loaded once, cached since
    serve.shutdown()


@pytest.mark.slow
def test_scale_up_pushed_to_handle_without_ttl(cluster):
    """Long-poll push (ref: serve/_private/long_poll.py): a scale-up
    must reach the HANDLE's routing state well inside the fallback TTL
    — the controller pushes the new replica set, the handle never
    polls for it."""
    import threading as _threading
    import time as _time

    from ant_ray_tpu import serve
    from ant_ray_tpu.serve.api import DeploymentHandle

    assert DeploymentHandle._REFRESH_TTL_S >= 10, \
        "fallback TTL must be long, or this test proves nothing"

    @serve.deployment(name="pushy",
                      autoscaling_config={"min_replicas": 1,
                                          "max_replicas": 3,
                                          "target_ongoing_requests": 1.0,
                                          "downscale_patience": 2})
    class Pushy:
        def __call__(self, x):
            _time.sleep(0.8)
            return x

    handle = serve.run(Pushy.bind())
    assert len(handle._routing.replicas) == 1
    handle.remote(0)                      # arm the listener
    start = _time.monotonic()
    stop = start + 8
    def pump():
        while _time.monotonic() < stop:
            try:
                art.get(handle.remote(1), timeout=30)
            except Exception:
                return
    threads = [_threading.Thread(target=pump) for _ in range(5)]
    for t in threads:
        t.start()
    observed_at = None
    while _time.monotonic() < stop:
        if len(handle._routing.replicas) >= 2:
            observed_at = _time.monotonic() - start
            break
        _time.sleep(0.1)
    for t in threads:
        t.join()
    assert observed_at is not None, \
        "handle never observed the scale-up"
    # Well inside the 30s fallback TTL -> it was pushed, not polled.
    assert observed_at < DeploymentHandle._REFRESH_TTL_S / 2, \
        f"scale-up took {observed_at:.1f}s to reach the handle"


def test_a_handles_listener_ends_with_its_runtime(cluster, monkeypatch):
    """The long-poll thread of a handle whose deployment was never shut
    down stops once the runtime it was started under is gone: were it
    to ask the controller again, that call would auto-init a cluster
    of its own in this process, inside whatever it runs next."""
    import threading

    from ant_ray_tpu._private.worker import global_worker
    from ant_ray_tpu.serve.api import _RoutingState

    asked = threading.Event()

    class GoneController:
        class listen_for_change:
            @staticmethod
            def remote(_versions):
                asked.set()
                raise ConnectionError("the controller went with its cluster")

    state = _RoutingState("orphan", [], GoneController())
    state.ensure_listener()
    assert asked.wait(10)
    # another runtime than the listener's own, as after shutdown + init
    # (not None: nothing may auto-init under the module's cluster)
    monkeypatch.setattr(global_worker, "runtime", object())
    state._listener.join(10)
    assert not state._listener.is_alive()
