"""Overload-resilience serving plane tests (ISSUE 7): replica-side
admission control with load shedding, end-to-end request deadlines
(expired work is shed, never executed), router circuit breakers with
half-open probation, token-bucket retry budgets, the suspect plane fed
by ongoing-poll strikes, deadline-aware @serve.batch flushing, LLM
engine admission, chaos latency injection, and the overload soak."""

import json
import os
import signal
import threading
import time
import urllib.error
import urllib.request

import pytest

import ant_ray_tpu as art
from ant_ray_tpu import serve
from ant_ray_tpu.exceptions import (
    BackPressureError,
    DeadlineExceededError,
    TaskCancelledError,
)
from ant_ray_tpu.util.chaos import ChaosSchedule


@pytest.fixture(scope="module")
def cluster():
    # The WHOLE module runs under injected slow-network chaos: every
    # actor call (PushTask) rides a 5 ms congested link, built from the
    # same seeded ChaosSchedule the resilience suite uses — breaker and
    # soak behavior is exercised under latency, not on a pristine rig.
    chaos = ChaosSchedule(seed=7).rpc_latency("PushTask", 0.005)
    art.init(num_cpus=4, num_tpus=0,
             _system_config=chaos.system_config())
    yield None
    serve.shutdown()
    art.shutdown()


def _concurrent(fn, n):
    """Run fn(i) on n threads behind a start barrier; returns the
    (tag, value) records the calls appended."""
    out = []
    barrier = threading.Barrier(n)

    def run(i):
        barrier.wait()
        out.append(fn(i))

    threads = [threading.Thread(target=run, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return out


@pytest.fixture(scope="module")
def cap(cluster):
    """ONE bounded deployment + both ingresses, shared by the
    admission / latency / HTTP / gRPC contract tests (replica spawns
    and proxy boots are the expensive part of every serve test)."""

    @serve.deployment(name="cap", route_prefix="/cap",
                      max_ongoing_requests=1, max_queued_requests=1)
    class Cap:
        def __call__(self, body=None):
            sleep_s = 0.3
            if isinstance(body, dict):
                sleep_s = float(body.get("sleep_s", 0.3))
            time.sleep(sleep_s)
            return "done"

    return serve.run(Cap.bind(), port=0, grpc_port=0)


# --------------------------------------------------------- admission


def test_admission_sheds_at_capacity(cap):
    """max_ongoing + max_queued bound the replica; excess fast-fails
    with a typed BackPressureError carrying a Retry-After hint."""

    def call(i):
        try:
            return ("ok", cap.call({"sleep_s": 0.3}))
        except BackPressureError as e:
            return ("shed", e.retry_after_s)

    results = _concurrent(call, 6)
    ok = [r for r in results if r[0] == "ok"]
    shed = [r for r in results if r[0] == "shed"]
    # 1 running + 1 queued admitted; the other 4 shed (thread-start
    # skew can let a queued one finish first, freeing a slot — so >= 3).
    assert len(ok) >= 2, results
    assert len(shed) >= 3, results
    assert all(r[1] > 0 for r in shed), results


def test_rpc_latency_injection_is_live(cap):
    """The module cluster's ChaosSchedule really injects: no actor call
    round-trips faster than the configured PushTask latency."""
    cap.call({"sleep_s": 0})                    # warm the route
    for _ in range(5):
        t0 = time.perf_counter()
        cap.call({"sleep_s": 0})
        assert time.perf_counter() - t0 >= 0.005


def test_chaos_rpc_latency_spec_parses(chaos_schedule):
    """testing_rpc_latency_s rides the same _system_config channel as
    the failure knob and parses per-method in the injector."""
    from ant_ray_tpu._private.config import Config
    from ant_ray_tpu._private.protocol import _ChaosInjector

    chaos_schedule.rpc_latency("PushTask", 0.05)
    chaos_schedule.rpc_latency("Ping", 0.01)
    cfg = chaos_schedule.system_config()
    assert cfg["testing_rpc_latency_s"] == "Ping:0.01,PushTask:0.05"
    assert hasattr(Config(), "testing_rpc_latency_s")

    inj = _ChaosInjector("", latency_spec=cfg["testing_rpc_latency_s"])
    assert inj.delay_for("PushTask") == 0.05
    assert inj.delay_for("Ping") == 0.01
    assert inj.delay_for("ReadChunk") == 0.0


def test_serve_metrics_instruments():
    from ant_ray_tpu.serve import api as serve_api

    m = serve_api._metrics()
    assert {n._name for n in m.values()} == {
        "art_serve_shed_requests_total", "art_serve_queue_depth",
        "art_serve_breaker_state", "art_serve_suspect_replicas",
        "art_serve_retries_total",
        "art_serve_retry_budget_exhausted_total"}


# --------------------------------------------------------- deadlines


def test_deadline_sheds_queued_work_never_executed(cluster):
    """A request whose deadline expires while queued for a replica slot
    is PROVABLY not executed (the handler never sees it), and the
    deployment's request_timeout_s default stamps calls that set no
    explicit timeout."""

    @serve.deployment(name="dlshed", max_ongoing_requests=1,
                      max_queued_requests=8, request_timeout_s=0.25)
    class DlShed:
        def __init__(self):
            self.executed = []

        def __call__(self, i, sleep_s=0.0):
            self.executed.append(i)
            time.sleep(sleep_s)
            return i

        def executed_ids(self):
            return list(self.executed)

    h = serve.run(DlShed.bind())

    # The occupier sets NO explicit timeout: the deployment default
    # (0.25 s) applies, so its 0.6 s execution exceeds the deadline
    # client-side — but admitted work is never interrupted, so it
    # keeps the slot the whole 0.6 s.
    occupier_result = []

    def occupy():
        try:
            occupier_result.append(("ok", h.call(0, sleep_s=0.6)))
        except DeadlineExceededError:
            occupier_result.append(("deadline", 0))

    occupier = threading.Thread(target=occupy)
    occupier.start()
    time.sleep(0.2)                      # let it take the only slot

    def call(i):
        try:
            return ("ok", h.call(i + 1, timeout_s=0.25))
        except DeadlineExceededError:
            return ("deadline", i + 1)

    results = _concurrent(call, 3)
    occupier.join()
    assert occupier_result == [("deadline", 0)], occupier_result
    assert all(r[0] == "deadline" for r in results), results

    # Shed means shed: even after the slot frees, the expired requests
    # never run.
    time.sleep(0.3)
    executed = h.options(method_name="executed_ids").call()
    assert executed == [0], executed


def test_cancel_reaps_queued_actor_task(cluster):
    """art.cancel on a not-yet-executing actor task: the call fails
    with TaskCancelledError and the method body never runs."""

    @art.remote
    class Slow:
        def __init__(self):
            self.ran = []

        def work(self, i, sleep_s=0.0):
            self.ran.append(i)
            time.sleep(sleep_s)
            return i

        def ran_ids(self):
            return list(self.ran)

    actor = Slow.remote()
    first = actor.work.remote(0, sleep_s=0.6)    # occupies the executor
    time.sleep(0.1)
    queued = actor.work.remote(1)
    art.cancel(queued)
    with pytest.raises(Exception) as err:
        art.get(queued, timeout=10)
    exc = err.value
    assert isinstance(exc, TaskCancelledError) or isinstance(
        getattr(exc, "cause", None), TaskCancelledError), exc
    assert art.get(first, timeout=10) == 0
    assert art.get(actor.ran_ids.remote(), timeout=10) == [0]


# --------------------------------------------------------- @serve.batch


def test_batch_deadline_pulls_flush_forward(cluster):
    """A tight end-to-end deadline flushes the batch EARLY (with margin
    to execute), instead of parking the item for the full batch window."""

    @serve.deployment(name="batchpull",
                      ray_actor_options={"max_concurrency": 16})
    class Batchy:
        @serve.batch(max_batch_size=8, batch_wait_timeout_s=5.0)
        def __call__(self, items):
            return [x * 2 for x in items]

    h = serve.run(Batchy.bind())
    t0 = time.perf_counter()
    assert h.call(21, timeout_s=0.8) == 42
    elapsed = time.perf_counter() - t0
    # Served before its 0.8 s deadline, nowhere near the 5 s window.
    assert 0.3 < elapsed < 2.0, elapsed


def test_batch_expired_items_shed_not_executed():
    """An item whose deadline has already expired by flush time is shed
    with the typed error and NEVER reaches the model function; live
    batch-mates still execute.  (In-process: the deadline context is
    set directly, so expiry-at-flush is deterministic — in the served
    path this arises when items queue behind a busy flusher.)"""
    from ant_ray_tpu.serve import api as serve_api

    class Model:
        def __init__(self):
            self.seen = []

        @serve_api.batch(max_batch_size=4, batch_wait_timeout_s=0.2)
        def fn(self, items):
            self.seen.extend(items)
            return [x * 2 for x in items]

    m = Model()
    results = {}

    def call(i, deadline_offset):
        token = serve_api._request_deadline.set(
            None if deadline_offset is None
            else time.time() + deadline_offset)
        try:
            results[i] = ("ok", m.fn(i))
        except DeadlineExceededError:
            results[i] = ("shed", i)
        finally:
            serve_api._request_deadline.reset(token)

    live = threading.Thread(target=call, args=(0, None))
    expired = threading.Thread(target=call, args=(1, -0.05))
    live.start()
    expired.start()
    live.join()
    expired.join()
    assert results[0] == ("ok", 0), results
    assert results[1] == ("shed", 1), results
    # Provably not executed: the model never saw the expired item.
    assert m.seen == [0], m.seen


def test_batch_flush_is_event_driven(cluster):
    """A full batch flushes the moment its last item lands — not after
    the old polling flusher's batch_wait/10 nap."""

    @serve.deployment(name="batchcv",
                      ray_actor_options={"max_concurrency": 16})
    class Batchy:
        @serve.batch(max_batch_size=4, batch_wait_timeout_s=10.0)
        def __call__(self, items):
            return [x + 1 for x in items]

    h = serve.run(Batchy.bind())
    t0 = time.perf_counter()
    results = _concurrent(lambda i: h.call(i, timeout_s=5.0), 4)
    elapsed = time.perf_counter() - t0
    assert sorted(results) == [1, 2, 3, 4]
    # Old flusher slept batch_wait/10 = 1.0 s before first checking.
    assert elapsed < 0.9, elapsed


# --------------------------------------------------------- ingress contracts


def test_http_contract_429_retry_after_and_504(cap):
    """The documented client-visible contract: sheds surface as HTTP
    429 + Retry-After (integral, >= 1), deadline misses as 504, and a
    malformed timeout header as 400."""
    port = serve.api.run.last_http_port
    assert port

    def post(payload, headers=None):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/cap",
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json",
                     **(headers or {})})
        try:
            with urllib.request.urlopen(req, timeout=30) as resp:
                return resp.status, dict(resp.headers), \
                    json.loads(resp.read())
        except urllib.error.HTTPError as e:
            return e.code, dict(e.headers), json.loads(e.read())

    statuses = _concurrent(lambda i: post({"sleep_s": 0.35}), 5)
    by_code = {}
    for code, headers, body in statuses:
        by_code.setdefault(code, []).append((headers, body))
    assert 200 in by_code, statuses
    assert 429 in by_code, statuses
    for headers, body in by_code[429]:
        assert int(headers["Retry-After"]) >= 1
        assert body["retry_after_s"] > 0

    # Client-stamped deadline (X-Request-Timeout-S) -> 504.
    code, _, body = post({"sleep_s": 0.45},
                         headers={"X-Request-Timeout-S": "0.2"})
    assert code == 504, (code, body)

    # Malformed header -> 400, not a 500 from float().
    code, _, _ = post({"sleep_s": 0},
                      headers={"X-Request-Timeout-S": "soon"})
    assert code == 400


def test_grpc_contract_resource_exhausted_and_deadline(cap):
    """gRPC ingress: sheds map to RESOURCE_EXHAUSTED with a
    retry-after-s trailer; deadline misses to DEADLINE_EXCEEDED."""
    grpc = pytest.importorskip("grpc")
    port = serve.run.last_grpc_port
    assert port

    channel = grpc.insecure_channel(f"127.0.0.1:{port}")
    call = channel.unary_unary("/antray.serve.Ingress/Call")

    def rpc(payload):
        try:
            reply = call(json.dumps({"route": "/cap",
                                     "request": payload}).encode(),
                         timeout=30)
            return ("ok", json.loads(reply))
        except grpc.RpcError as e:
            return ("err", e)

    results = _concurrent(lambda i: rpc({"sleep_s": 0.35}), 5)
    oks = [r for r in results if r[0] == "ok"]
    errs = [r[1] for r in results if r[0] == "err"]
    assert oks and errs, results
    exhausted = [e for e in errs
                 if e.code() == grpc.StatusCode.RESOURCE_EXHAUSTED]
    assert exhausted, [e.code() for e in errs]
    trailers = dict(exhausted[0].trailing_metadata() or ())
    assert float(trailers["retry-after-s"]) > 0

    tag, e = rpc({"sleep_s": 0.45, "timeout_s": 0.2})
    assert tag == "err" and \
        e.code() == grpc.StatusCode.DEADLINE_EXCEEDED, (tag, e)
    channel.close()


def test_http_stream_shed_surfaces_429(cluster):
    """Streaming requests honor the same shed contract as unary ones:
    the first chunk is pulled BEFORE the SSE headers go out, so an
    admission shed surfaces as 429 + Retry-After — never a 200 stream
    that dies mid-flight."""

    @serve.deployment(name="sse", route_prefix="/sse",
                      max_ongoing_requests=1, max_queued_requests=0)
    class Sse:
        def __call__(self, body=None):
            time.sleep(float(body.get("sleep_s", 0.2))
                       if isinstance(body, dict) else 0.2)
            return "done"

        def stream(self, body=None):
            for i in range(3):
                yield {"i": i}

    h = serve.run(Sse.bind(), port=0)
    port = serve.api.run.last_http_port
    assert port

    def post(payload):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/sse",
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=30) as resp:
                return resp.status, dict(resp.headers), resp.read()
        except urllib.error.HTTPError as e:
            return e.code, dict(e.headers), e.read()

    # Happy path: a real SSE stream, 3 frames + [DONE].
    code, headers, raw = post({"stream": True})
    assert code == 200 and "text/event-stream" in headers["Content-Type"]
    frames = [ln for ln in raw.decode().splitlines()
              if ln.startswith("data: ")]
    assert len(frames) == 4 and frames[-1] == "data: [DONE]", frames

    # Occupy the lone slot (queue seats: 0), then open a stream: the
    # shed must arrive as a typed 429, before any SSE bytes.
    blocker = threading.Thread(target=lambda: h.call({"sleep_s": 1.2}))
    blocker.start()
    try:
        time.sleep(0.3)             # the unary call now holds the slot
        code, headers, raw = post({"stream": True})
        assert code == 429, (code, raw)
        assert int(headers["Retry-After"]) >= 1
        assert json.loads(raw)["retry_after_s"] > 0
    finally:
        blocker.join()


# --------------------------------------------------------- router resilience


def test_breaker_ejects_probes_and_reenters(cluster, tmp_path):
    """Failure rate opens a replica's breaker (traffic routes around
    it); after cooldown exactly one probation probe goes through —
    failure re-opens, success re-enters the replica."""
    poison_file = tmp_path / "poison_pid"

    @serve.deployment(name="flaky", num_replicas=2,
                      breaker_config={"window": 8, "min_outcomes": 3,
                                      "failure_rate": 0.5,
                                      "cooldown_s": 0.6})
    class Flaky:
        def __init__(self, poison_file):
            self.poison_file = poison_file
            self.pid = os.getpid()

        def __call__(self, x=None):
            try:
                poisoned = int(open(self.poison_file).read())
            except (OSError, ValueError):
                poisoned = -1
            if poisoned == self.pid:
                raise RuntimeError("poisoned replica")
            return self.pid

    h = serve.run(Flaky.bind(str(poison_file)))

    pids = set()
    deadline = time.monotonic() + 20
    while len(pids) < 2 and time.monotonic() < deadline:
        pids.add(h.call())
    assert len(pids) == 2, pids
    victim = sorted(pids)[0]
    survivor = (pids - {victim}).pop()

    poison_file.write_text(str(victim))
    opened = False
    for _ in range(60):
        try:
            h.call()
        except Exception:  # noqa: BLE001 — poisoned replica errors
            pass
        if any(br.state == "open"
               for br in h._routing.breakers.values()):
            opened = True
            break
    assert opened, "breaker never opened on a failing replica"

    # While open (inside cooldown): all traffic lands on the survivor.
    for _ in range(8):
        assert h.call() == survivor

    # Probation probe with the poison still on: the probe is routed to
    # the ejected replica, fails, and the breaker re-opens.
    time.sleep(0.7)
    with pytest.raises(Exception):  # noqa: B017 — replica error
        h.call()
    assert any(br.state == "open"
               for br in h._routing.breakers.values())

    # Heal it: the next probe succeeds, the breaker closes, and the
    # replica rejoins the candidate set.
    poison_file.unlink()
    time.sleep(0.7)
    seen = set()
    deadline = time.monotonic() + 15
    while seen != pids and time.monotonic() < deadline:
        seen.add(h.call())
    assert seen == pids, (seen, pids)
    assert all(br.state == "closed"
               for br in h._routing.breakers.values())


@pytest.mark.slow
def test_ongoing_poll_strikes_eject_wedged_replica(cluster):
    """Satellite 1 + acceptance: a WEDGED replica (SIGSTOP — answers
    nothing, closes nothing) used to freeze the autoscaler's queue
    snapshot via the swallowed poll loop while po2 kept routing to it.
    Now repeated per-replica poll timeouts count strikes, the
    controller marks it suspect, every handle's breaker force-opens
    (zero traffic to the wedge), and a successful poll after recovery
    drops it to half-open for probation re-entry."""

    @serve.deployment(name="wedge", num_replicas=2,
                      max_ongoing_requests=4,
                      breaker_config={"cooldown_s": 0.5})
    class Wedge:
        def __call__(self, x=None):
            return os.getpid()

    h = serve.run(Wedge.bind())
    pids = set()
    deadline = time.monotonic() + 20
    while len(pids) < 2 and time.monotonic() < deadline:
        pids.add(h.call(timeout_s=5))
    assert len(pids) == 2, pids
    victim = sorted(pids)[0]
    survivor = (pids - {victim}).pop()

    os.kill(victim, signal.SIGSTOP)
    try:
        deadline = time.monotonic() + 25
        while not h._routing.suspect and time.monotonic() < deadline:
            time.sleep(0.2)
        assert h._routing.suspect, \
            "poll strikes never marked the wedged replica suspect"

        # Ejected: the wedge receives no traffic, and no request
        # blocks on it (the old behavior: ~half of these would hang
        # into their deadline).
        for _ in range(8):
            assert h.call(timeout_s=2.0) == survivor
        assert any(br.state == "open"
                   for br in h._routing.breakers.values())
    finally:
        os.kill(victim, signal.SIGCONT)

    # Recovery: a successful poll clears the suspect mark, probation
    # re-admits the replica, and po2 uses both again.
    deadline = time.monotonic() + 20
    while h._routing.suspect and time.monotonic() < deadline:
        time.sleep(0.2)
    assert not h._routing.suspect, "suspect mark never cleared"
    seen = set()
    deadline = time.monotonic() + 15
    while seen != pids and time.monotonic() < deadline:
        seen.add(h.call(timeout_s=5))
    assert seen == pids, (seen, pids)


def test_retry_budget_token_bucket_exhaustion(cluster, tmp_path):
    """Opt-in retries re-pick a different replica, but the token bucket
    bounds amplification: with the budget spent, failures surface
    immediately instead of doubling offered load."""
    log = tmp_path / "invocations"

    @serve.deployment(name="budget", num_replicas=2,
                      retry_config={"max_attempts": 3,
                                    "budget_fraction": 0.0,
                                    "budget_burst": 1.0},
                      breaker_config={"window": 100,
                                      "min_outcomes": 100})
    class AlwaysFails:
        def __init__(self, log):
            self.log = log

        def __call__(self, x=None):
            with open(self.log, "a") as f:
                f.write(f"{os.getpid()}\n")
            raise RuntimeError("handler failure")

    h = serve.run(AlwaysFails.bind(str(log)))

    # Call 1: attempt + one budgeted retry on the OTHER replica = 2
    # invocations; the original error (not BackPressure) surfaces.
    with pytest.raises(Exception, match="handler failure"):
        h.call()
    invocations = log.read_text().splitlines()
    assert len(invocations) == 2, invocations
    assert len(set(invocations)) == 2, \
        "retry must re-pick a different replica"

    # Call 2: bucket empty (fraction=0 earns nothing back) — exactly
    # one invocation, no retry amplification.
    with pytest.raises(Exception, match="handler failure"):
        h.call()
    assert len(log.read_text().splitlines()) == 3
    assert h._routing.retry_tokens == 0.0


# --------------------------------------------------------- engine admission


def test_llm_engine_admission_sheds_when_kv_full():
    """The engine rejects at admission once every KV slot is busy and
    the waiting line is full — overload sheds typed instead of growing
    an unbounded prompt queue; offline generate() still queues."""
    import jax

    from ant_ray_tpu.llm import LLMEngine
    from ant_ray_tpu.models import llama

    cfg = llama.CONFIGS["tiny"]
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    eng = LLMEngine(cfg, params, slots=1, max_seq=64, max_waiting=1)

    eng.add_request([1, 2, 3])
    eng.step()                      # the lone KV slot is now busy
    eng.add_request([4, 5])         # waiting line: 1/1
    with pytest.raises(BackPressureError) as err:
        eng.add_request([6, 7])
    assert err.value.retry_after_s > 0
    # Offline batch path opts out of the gate.
    eng.add_request([8, 9], admit=False)
    while eng.has_unfinished():
        eng.step()


def test_error_serialization_stays_jax_free():
    """Shed replies must return in MILLISECONDS: serializing an
    exception in a jax-free worker (every serve replica) must not pull
    the ~1s jax import onto the reply path.  The serializer's jax-array
    probe may only consult an ALREADY-imported jax."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from ant_ray_tpu._private import serialization\n"
        "from ant_ray_tpu.exceptions import BackPressureError\n"
        "p = serialization.serialize_error(BackPressureError('full'))\n"
        "assert 'jax' not in sys.modules, 'error pickling imported jax'\n"
        "err = serialization.deserialize(\n"
        "    serialization.SerializedObject.from_payload(p.to_payload()))\n"
        "assert err.retry_after_s == 1.0\n"
        "print('OK')\n")
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0 and "OK" in proc.stdout, proc.stderr


def test_llm_server_max_waiting_bounds_loop_queue():
    """The serving path realizes `max_waiting` at the engine-loop
    submit boundary: with the lone KV slot busy and the line full, a
    request sheds typed BackPressureError (retry hint from the
    measured chunk-drain rate) instead of parking a replica thread
    without bound."""
    from ant_ray_tpu.llm import SamplingParams
    from ant_ray_tpu.llm.serve_llm import LLMServer

    srv = LLMServer(slots=1, max_seq=64, max_waiting=0,
                    kv_offload="local")
    # Pin the slot: a long generation submitted straight to the loop.
    pin = srv._loop.submit([1, 2, 3], SamplingParams(temperature=0.0,
                                                     max_tokens=40))
    assert pin.events.get(timeout=60)["type"] == "token", \
        "pin request never started"
    with pytest.raises(BackPressureError) as err:
        srv({"prompt": "hi", "max_tokens": 1})
    assert err.value.retry_after_s > 0
    pin.wait(timeout=120)
    out = srv({"prompt": "hi", "max_tokens": 1})  # slot free again
    assert out["choices"]
    srv.shutdown()


# --------------------------------------------------------- overload soak


@pytest.mark.slow
def test_overload_soak_bounded_p99_and_zero_crashes(cluster):
    """Acceptance: offered load >= 4x capacity with chaos latency on.
    Admitted requests keep a p99 bounded by the deadline, the excess is
    shed with the typed contract (never an unbounded queue), and no
    replica crashes."""

    @serve.deployment(name="soak", num_replicas=2,
                      max_ongoing_requests=1, max_queued_requests=1,
                      request_timeout_s=1.0)
    class Soak:
        def __call__(self, x=None):
            time.sleep(0.1)
            return os.getpid()

    h = serve.run(Soak.bind())
    pids_before = set()
    deadline = time.monotonic() + 20
    while len(pids_before) < 2 and time.monotonic() < deadline:
        pids_before.add(h.call())
    assert len(pids_before) == 2

    # Capacity ~= 2 slots / 0.1 s = 20 rps (+ 2 queue seats).  16
    # closed-loop clients whose sheds return in milliseconds offer
    # several hundred rps — far past 4x capacity.
    stop_at = time.monotonic() + 6.0
    records = []
    rec_lock = threading.Lock()

    def client():
        while time.monotonic() < stop_at:
            t0 = time.perf_counter()
            try:
                h.call()
                tag = "ok"
            except BackPressureError:
                tag = "shed"
            except DeadlineExceededError:
                tag = "deadline"
            # Anything else (replica crash, connection loss) propagates
            # and fails the test via the thread's saved exception.
            with rec_lock:
                records.append((tag, time.perf_counter() - t0))

    errors = []

    def run_client():
        try:
            client()
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [threading.Thread(target=run_client) for _ in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    assert not errors, f"non-contract failures under overload: {errors!r}"
    oks = sorted(lat for tag, lat in records if tag == "ok")
    sheds = [1 for tag, _ in records if tag != "ok"]
    assert len(oks) >= 50, f"too few admitted: {len(oks)}"
    assert sheds, "offered >> capacity yet nothing was shed"
    # Offered load really exceeded capacity by a wide margin.
    assert len(records) >= 4 * len(oks) or len(sheds) >= len(oks), \
        (len(records), len(oks))
    p99 = oks[int(0.99 * (len(oks) - 1))]
    assert p99 <= 1.0 + 0.3, f"admitted p99 {p99:.3f}s exceeds deadline"

    # Zero replica crashes: the same two processes still serve.
    time.sleep(0.3)
    pids_after = {h.call() for _ in range(12)}
    assert pids_after == pids_before, (pids_before, pids_after)
