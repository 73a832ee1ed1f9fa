"""The HTTP proxy's stream path (ISSUE 43): the owner of a streaming
call PUSHES every item to the request's handler — no pool thread pulls
the stream and no item passes through the object store.  Fake
deployments, no engine: what a reply guarantees (every frame, once, in
order; typed statuses before the SSE headers; a failure after the items
before it; ``[DONE]`` last) and what the path must not hold (a pool
thread while a first item is awaited, owner state after a client went
away)."""

import http.client
import json
import os
import threading
import time
import urllib.error
import urllib.request

import pytest

import ant_ray_tpu as art
from ant_ray_tpu import serve

STREAMS, ITEMS = 48, 200
BIG = 200 * 1024            # past max_inline_object_size (100 KiB)


@serve.deployment(name="items", route_prefix="/items",
                  max_ongoing_requests=STREAMS)
class Items:
    """``n`` items ``{"id", "i"}``; ``fail_at``: raise in place of item
    ``fail_at``; ``big``: those items carry ``BIG`` bytes; ``first_s``
    / ``step_s``: seconds before the first item / before every item."""

    def __call__(self, body=None):
        return "unary"

    def stream(self, body):
        time.sleep(body.get("first_s", 0))
        for i in range(body["n"]):
            time.sleep(body.get("step_s", 0))
            if i == body.get("fail_at"):
                raise RuntimeError(f"item {i} went wrong")
            item = {"id": body.get("id"), "i": i}
            if i in body.get("big", ()):
                item["pad"] = "x" * BIG
            yield item


@serve.deployment(name="gate", route_prefix="/gate",
                  max_ongoing_requests=1, max_queued_requests=0)
class Gate:
    def __call__(self, body=None):
        time.sleep(body["sleep_s"])
        return "done"

    def stream(self, body=None):
        yield {"i": 0}


class ProbedProxy(serve.api.HttpProxy):
    """The proxy itself, hosted by this test so that its process can be
    asked what its runtime still holds."""

    def owner_state(self):
        from ant_ray_tpu._private.worker import global_worker

        runtime = global_worker.runtime
        with runtime.memory._lock:
            stored = sum(1 for kind, _ in runtime.memory._entries.values()
                         if kind != "pending")
        return {"streams": len(runtime._streams), "stored": stored}

    def failures(self, name):
        """Failed outcomes in the breakers of ``name``'s replicas."""
        handle = self._handles.get(name)
        if handle is None:
            return 0
        with handle._routing.lock:
            return sum(not ok
                       for br in handle._routing.breakers.values()
                       for ok in br.outcomes)


@pytest.fixture(scope="module")
def ports():
    """(port of the controller's proxy, the probed proxy and its port)"""
    art.init(num_cpus=4, num_tpus=0)
    try:
        serve.run(Items.bind(), port=0)
        serve.run(Gate.bind(), port=0)
        controller = art.get_actor(serve.api.CONTROLLER_NAME,
                                   namespace="_serve")
        probed = art.remote(ProbedProxy).options(
            max_concurrency=32, num_cpus=0).remote(controller)
        yield (serve.run.last_http_port, probed,
               art.get(probed.start.remote(0)))
    finally:
        serve.shutdown()
        art.shutdown()


def sse(port, path, body, timeout=60):
    """(status, headers, body) of one POST; of an event stream the body
    is the list of its ``data:`` payloads."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            status, headers, raw = resp.status, dict(resp.headers), \
                resp.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()
    except http.client.IncompleteRead as e:     # ended without its end
        raw = e.partial
    if "text/event-stream" not in headers["Content-Type"]:
        return status, headers, raw
    return status, headers, [line[len("data: "):]
                             for line in raw.decode().splitlines()
                             if line.startswith("data: ")]


def eventually(read, want, seconds=30):
    deadline = time.monotonic() + seconds
    while (got := read()) != want:
        assert time.monotonic() < deadline, (got, want)
        time.sleep(0.1)


def test_concurrent_streams_arrive_complete_and_in_order(ports):
    port, results = ports[0], {}

    def client(k):
        results[k] = sse(port, "/items",
                         {"stream": True, "n": ITEMS, "id": k}, 120)

    threads = [threading.Thread(target=client, args=(k,))
               for k in range(STREAMS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(150)
        assert not t.is_alive()
    assert sorted(results) == list(range(STREAMS))
    for k, (status, headers, frames) in results.items():
        assert status == 200
        assert "text/event-stream" in headers["Content-Type"]
        assert frames[-1] == "[DONE]"
        assert [json.loads(f) for f in frames[:-1]] == [
            {"id": k, "i": i} for i in range(ITEMS)]


def test_mid_stream_raise_surfaces_after_the_items_before_it(ports):
    _, probed, port = ports
    before = art.get(probed.owner_state.remote())
    failures = art.get(probed.failures.remote("items"))
    status, headers, frames = sse(
        port, "/items", {"stream": True, "n": 9, "fail_at": 5, "id": "f"})
    # headers were out already: 200, every item before the failure,
    # then the stream just ends — no [DONE]
    assert status == 200 and "text/event-stream" in headers["Content-Type"]
    assert [json.loads(f) for f in frames] == [
        {"id": "f", "i": i} for i in range(5)]
    # the failure fed the replica's breaker, and nothing is left behind
    assert art.get(probed.failures.remote("items")) == failures + 1
    eventually(lambda: art.get(probed.owner_state.remote()), before)
    # a failure BEFORE any item is a plain 500, not an event stream
    status, headers, raw = sse(
        port, "/items", {"stream": True, "n": 3, "fail_at": 0})
    assert status == 500 and b"went wrong" in raw
    assert "text/event-stream" not in headers["Content-Type"]


def test_shed_before_the_first_item_is_a_typed_status(ports):
    port = ports[0]
    status, _, frames = sse(port, "/gate", {"stream": True})
    assert status == 200 and frames == ['{"i": 0}', "[DONE]"]
    blocker = threading.Thread(
        target=lambda: sse(port, "/gate", {"sleep_s": 1.5}))
    blocker.start()
    try:
        time.sleep(0.4)             # the unary call now holds the slot
        status, headers, raw = sse(port, "/gate", {"stream": True})
        assert status == 429, (status, raw)
        assert int(headers["Retry-After"]) >= 1
        assert "text/event-stream" not in headers["Content-Type"]
        assert json.loads(raw)["retry_after_s"] > 0
        # and a deadline that the queue + first item cannot meet: 504
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/items",
            data=json.dumps({"stream": True, "n": 1,
                             "first_s": 0}).encode(),
            headers={"Content-Type": "application/json",
                     "X-Request-Timeout-S": "0.000001"})
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req, timeout=30)
        assert e.value.code == 504
    finally:
        blocker.join()


def test_client_that_goes_away_leaves_nothing_behind(ports):
    _, probed, port = ports
    before = art.get(probed.owner_state.remote())
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("POST", "/items", json.dumps(
        {"stream": True, "n": 600, "step_s": 0.002, "id": "gone",
         "big": list(range(3, 600, 50))}),
        {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    read = 0
    while read < 5:                     # past the first BIG item
        read += resp.readline().startswith(b"data: ")
    resp.close()
    conn.close()
    # the producer runs on for a second: what it still sends is dropped
    # on arrival, the owner's stream state and every stored item go
    eventually(lambda: art.get(probed.owner_state.remote()), before)
    time.sleep(1.5)
    assert art.get(probed.owner_state.remote()) == before
    # the proxy serves on
    status, _, frames = sse(port, "/items", {"stream": True, "n": 2})
    assert status == 200 and len(frames) == 3


def test_item_too_large_to_be_inline_arrives_between_small_ones(ports):
    port = ports[0]
    status, _, frames = sse(
        port, "/items", {"stream": True, "n": 7, "big": [0, 3, 6],
                         "id": "b"})
    assert status == 200 and frames[-1] == "[DONE]"
    items = [json.loads(f) for f in frames[:-1]]
    assert [(it["id"], it["i"]) for it in items] == [
        ("b", i) for i in range(7)]
    assert [len(it.get("pad", "")) for it in items] == [
        BIG, 0, 0, BIG, 0, 0, BIG]


def test_awaited_first_item_holds_no_pool_thread(ports):
    """As many streams whose first item takes 2 s as asyncio's default
    pool has threads: a unary call beside them answers at once (pulled,
    each first item held a pool thread for its whole wait)."""
    port = ports[0]
    pool = min(32, (os.cpu_count() or 1) + 4)
    results = []
    threads = [threading.Thread(target=lambda: results.append(sse(
        port, "/items", {"stream": True, "n": 1, "first_s": 2.0})))
        for _ in range(pool)]
    for t in threads:
        t.start()
    time.sleep(0.7)                 # every stream started, none has an item
    began = time.monotonic()
    status, _, raw = sse(port, "/items", {})
    took = time.monotonic() - began
    for t in threads:
        t.join(60)
        assert not t.is_alive()
    assert status == 200 and json.loads(raw) == {"result": "unary"}
    assert took < 1.0, took
    assert [(s, f) for s, _, f in results] == [
        (200, ['{"id": null, "i": 0}', "[DONE]"])] * pool
