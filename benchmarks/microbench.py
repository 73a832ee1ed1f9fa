"""Control/object-plane microbenchmarks
(ref: python/ray/_private/ray_perf.py:122-317 + release/microbenchmark/
run_microbenchmark.py — the reference's per-release throughput suite:
tasks/s, actor calls/s, put/get, wait over many refs).

Run:  python benchmarks/microbench.py [--quick]
Prints one JSON line per workload:
    {"metric": ..., "value": N, "unit": ...}

These are CONTROL-PLANE numbers (scheduler, RPC, object store) — the
accelerator-plane number (train-step MFU) lives in bench.py.
"""

from __future__ import annotations

import argparse
import json
import os
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402


def timeit(fn, n: int, warmup: int = 1) -> float:
    """Ops/s of fn(batch_size=n) after warmup."""
    for _ in range(warmup):
        fn(max(1, n // 10))
    t0 = time.perf_counter()
    fn(n)
    return n / (time.perf_counter() - t0)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--quick", action="store_true",
                        help="10x smaller workloads")
    parser.add_argument("--json-out", default="",
                        help="also write results to this JSON file "
                             "(committed as BENCH_control.json)")
    parser.add_argument("--note", default="",
                        help="free-form provenance note recorded in "
                             "--json-out")
    args = parser.parse_args()
    scale = 0.1 if args.quick else 1.0

    import ant_ray_tpu as art

    # Autodetected sizing, like the reference's ray_perf (ray.init()
    # detects cores; provisioning more workers than cores only adds
    # scheduler pressure on small rigs).
    art.init()
    results = []

    def emit(metric: str, value: float, unit: str):
        line = {"metric": metric, "value": round(value, 4), "unit": unit}
        results.append(line)
        print(json.dumps(line), flush=True)

    # ---- single small task round trips (ray_perf: "tasks sync")
    @art.remote
    def nop():
        return None

    def sync_tasks(n):
        for _ in range(n):
            art.get(nop.remote())

    emit("task_sync_roundtrips_per_s", timeit(sync_tasks, int(200 * scale)),
         "tasks/s")

    # ---- batched task submission (ray_perf: "tasks async")
    def async_tasks(n):
        art.get([nop.remote() for _ in range(n)])

    emit("task_async_throughput_per_s",
         timeit(async_tasks, int(2000 * scale)), "tasks/s")

    # ---- 1:1 actor call round trips (ray_perf: "1:1 actor calls sync")
    @art.remote
    class Echo:
        def ping(self, x=None):
            return x

    actor = Echo.remote()
    art.get(actor.ping.remote())

    def actor_sync(n):
        for _ in range(n):
            art.get(actor.ping.remote())

    emit("actor_call_sync_per_s", timeit(actor_sync, int(200 * scale)),
         "calls/s")

    # ---- pipelined actor calls (ray_perf: "1:1 actor calls async")
    def actor_async(n):
        art.get([actor.ping.remote() for _ in range(n)])

    emit("actor_call_async_per_s", timeit(actor_async, int(2000 * scale)),
         "calls/s")

    # ---- small put/get (ray_perf: "single client put/get")
    def put_get(n):
        for _ in range(n):
            art.get(art.put(b"x" * 100))

    emit("small_put_get_per_s", timeit(put_get, int(500 * scale)), "ops/s")

    # ---- large object bandwidth (ray_perf: "put gigabytes" — numpy
    # payloads, matching python/ray/_private/ray_perf.py's array puts;
    # get() of the array is a zero-copy view into the node arena)
    blob = np.random.default_rng(0).integers(
        0, 127, size=64 << 20, dtype=np.int8)  # 64 MiB

    def put_gb(n):
        for _ in range(n):
            got = art.get(art.put(blob))
            assert got.nbytes == blob.nbytes

    n_big = max(2, int(8 * scale))
    # Steady-state warmup: the first rounds pay one-off tmpfs page
    # faults while the arena ping-pongs onto fresh pages; a bandwidth
    # metric should report the plane's sustained rate, not first-touch
    # page zeroing (3 rounds observed sufficient to stabilize).
    put_gb(3)
    t0 = time.perf_counter()
    put_gb(n_big)
    gbps = (len(blob) * n_big / (1 << 30)) / (time.perf_counter() - t0)
    emit("put_get_bandwidth_gb_s", gbps, "GiB/s")

    # ---- wait over many refs (ray_perf: "wait 1k refs").  The refs
    # are all ready, so one wait is far below clock resolution —
    # measure many rounds and report µs/round (a visible unit: the
    # old single-round seconds reading rounded to a degenerate 0.0).
    refs = [nop.remote() for _ in range(int(1000 * scale))]
    art.get(refs)
    rounds = 20
    t0 = time.perf_counter()
    for _ in range(rounds):
        ready, _ = art.wait(refs, num_returns=len(refs), timeout=60)
    emit("wait_1k_ready_refs_us", 1e6 *
         (time.perf_counter() - t0) / rounds, "us")
    assert len(ready) == len(refs)

    # ---- hot-frame codec (hotframe.py): per-call encode/decode cost
    # of the zero-pickle PushTask wire format, measured on the exact
    # actor-call shape the cluster benches above push.  Guarded "lower"
    # so framing overhead can never silently regress — this is the
    # per-call floor under every number in this file.
    from ant_ray_tpu._private import hotframe  # noqa: PLC0415
    from ant_ray_tpu._private.ids import ActorID, JobID, TaskID  # noqa: PLC0415
    from ant_ray_tpu._private.specs import TaskSpec  # noqa: PLC0415

    aid = ActorID.of(JobID.from_random())
    frame_spec = TaskSpec(
        task_id=TaskID.for_actor_task(aid), function_id="",
        function_name="Echo.ping", args_payload=b"x" * 100,
        num_returns=1, owner_address="127.0.0.1:12345", resources={},
        actor_id=aid, method_name="ping", sequence_no=1)
    cache = hotframe.TemplateCache()
    tid_, _new = cache.intern(hotframe.template_key(frame_spec))
    table = dict((hotframe.decode_template(
        hotframe.encode_template(tid_, frame_spec)),))
    n_frames = max(5000, int(50000 * scale))

    def frame_encode_ns() -> float:
        t0 = time.perf_counter()
        for i in range(n_frames):
            hotframe.encode_call(tid_, frame_spec, i)
        return (time.perf_counter() - t0) / n_frames * 1e9

    body = hotframe.encode_call(tid_, frame_spec, 7)

    def frame_decode_ns() -> float:
        t0 = time.perf_counter()
        for _ in range(n_frames):
            hotframe.decode_call(body, table)
        return (time.perf_counter() - t0) / n_frames * 1e9

    frame_encode_ns(), frame_decode_ns()              # warmup
    emit("rpc_frame_encode_ns",
         sorted(frame_encode_ns() for _ in range(3))[1], "ns")
    emit("rpc_frame_decode_ns",
         sorted(frame_decode_ns() for _ in range(3))[1], "ns")

    # ---- device-feed ingest (data/device_feed.py): consumer starve-
    # fraction with prefetch on vs. off, plus end-to-end batches/s.
    # The consumer's "step" is a sleep: like a TPU step (which runs on
    # the device) it releases the GIL, so the producer's block-pull +
    # collate + transfer-issue overlap it — real jit compute on this
    # 1-cpu rig would instead contend for the producer's core and hide
    # the effect being measured.
    from ant_ray_tpu import data as art_data  # noqa: PLC0415

    feed_rows = max(2560, int(12800 * scale))
    step_s = 0.004                     # simulated train_step compute

    def feed_run(prefetch: int):
        it = art_data.range(feed_rows, parallelism=4).iterator()
        n = 0
        t0 = time.perf_counter()
        for _ in it.iter_device_batches(batch_size=256,
                                        prefetch_batches=prefetch):
            time.sleep(step_s)
            n += 1
        wall = time.perf_counter() - t0
        return it.stats()["device_feed"], n, wall

    feed_run(2)                        # warmup: plan + device init
    starve0, _, _ = feed_run(0)
    starve2, n2, wall2 = feed_run(2)
    emit("data_device_feed_starve_frac_prefetch0",
         starve0["consumer_starve_fraction"], "fraction")
    emit("data_device_feed_starve_frac_prefetch2",
         starve2["consumer_starve_fraction"], "fraction")
    emit("data_device_feed_batches_per_s", n2 / wall2, "batches/s")

    # ---- fused bucketed allreduce vs the per-tensor loop
    # (util/collective/fusion.py): 256 x 16 KiB float32 tensors — the
    # sub-MiB gradient-pytree regime where per-call launch overhead
    # dominates.  gloo/CPU world_size=1 so the workload runs in the
    # tier-1 environment; the collective round trip per CALL is what
    # differs between the two paths.
    from ant_ray_tpu._private.protocol import find_free_port  # noqa: PLC0415
    from ant_ray_tpu.util import collective as col  # noqa: PLC0415

    col.init_collective_group(
        1, 0, backend="gloo", group_name="bench_fusion",
        init_method=f"tcp://127.0.0.1:{find_free_port()}")
    grads = [np.ones((4096,), np.float32) for _ in range(256)]

    def naive_rounds(r):
        for _ in range(r):
            for t in grads:
                col.allreduce(t, group_name="bench_fusion")

    def fused_rounds(r):
        for _ in range(r):
            col.allreduce_coalesced(grads, group_name="bench_fusion")

    naive_rounds(1)                    # warmup (gloo lazy init)
    fused_rounds(1)                    # warmup (plan + compile caches)
    r_naive = max(1, int(3 * scale))
    t0 = time.perf_counter()
    naive_rounds(r_naive)
    naive_per_s = len(grads) * r_naive / (time.perf_counter() - t0)
    r_fused = max(2, int(10 * scale))
    t0 = time.perf_counter()
    fused_rounds(r_fused)
    fused_per_s = len(grads) * r_fused / (time.perf_counter() - t0)
    # ---- int8 wire quantization (EQuARX-style blockwise int8 codes +
    # f32 scale sidecar): bytes that actually crossed the wire vs the
    # logical f32 payload.  Guarded "lower" — the acceptance bar is
    # <= 0.35x; drifting up means the quantized path stopped engaging.
    col.allreduce_coalesced(grads, group_name="bench_fusion",
                            transport_dtype="int8")
    q8 = col.fusion_stats("bench_fusion")["last"]
    emit("collective_int8_wire_bytes_ratio",
         q8["wire_bytes"] / q8["bytes"] if q8["bytes"] else 1.0,
         "fraction")

    # ---- gradient-ready overlap (fusion.GradientSyncer): leaves are
    # marked ready one at a time with real compute between them (the
    # backward-pass shape), so bucket k's collective runs while leaves
    # of bucket k+1 are still being "produced".  The metric is the
    # share of collective wall time hidden under that compute window —
    # the DDP overlap number ROADMAP item 3 targets (>= 0.5).
    syncer = col.gradient_syncer(group_name="bench_fusion",
                                 bucket_bytes=64 << 10)
    leaf_compute_s = 0.002
    for _ in range(2):                 # round 0 warms plan/lazy init
        syncer.begin(grads)
        for i in reversed(range(len(grads))):
            time.sleep(leaf_compute_s)
            syncer.ready(i)
        syncer.wait()
    ov = col.fusion_stats("bench_fusion")["last"]
    emit("collective_overlap_fraction",
         min(1.0, ov["overlap_s"] / ov["collective_s"])
         if ov["collective_s"] else 0.0, "fraction")
    col.destroy_collective_group("bench_fusion")
    emit("collective_allreduce_naive_per_s", naive_per_s, "tensors/s")
    emit("collective_allreduce_fused_per_s", fused_per_s, "tensors/s")
    emit("collective_allreduce_fused_naive_ratio",
         fused_per_s / naive_per_s if naive_per_s else 0.0, "x")

    # ---- step-profiler overhead (observability/step_profiler.py):
    # instrumented vs. bare loop.  The headline metric is the step-path
    # instrumentation cost (publishing disabled) — budgeted at < 2 µs
    # per step (bench.py fails its summary record past that).  The
    # _publish variant includes the batched GCS publication a connected
    # training loop pays (amortized flush every publish_batch steps —
    # off the 2 µs budget because it is not on the step's timed path
    # in any real loop, where a step is ≥ milliseconds).
    from ant_ray_tpu.observability import StepProfiler  # noqa: PLC0415

    n_steps = max(2000, int(20000 * scale))

    def profiler_overhead_ns(prof):
        t0 = time.perf_counter()
        for _ in range(n_steps):
            pass
        bare = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(n_steps):
            with prof.step():
                pass
        return (time.perf_counter() - t0 - bare) / n_steps * 1e9

    profiler_overhead_ns(StepProfiler(publish=False))     # warmup
    emit("step_profiler_overhead_ns",
         profiler_overhead_ns(StepProfiler(publish=False)), "ns")
    emit("step_profiler_overhead_publish_ns",
         profiler_overhead_ns(StepProfiler()), "ns")

    # ---- tracing plane (observability/tracing_plane.py): the headline
    # metric is the UNSAMPLED per-call cost — an ingress mint (coin
    # flip + ids) plus an entered-but-unrecorded span block — budgeted
    # at < 2 µs so always-on tracing is free for untraced traffic.
    from ant_ray_tpu._private.config import global_config  # noqa: PLC0415
    from ant_ray_tpu.observability import tracing_plane  # noqa: PLC0415

    n_spans = max(2000, int(20000 * scale))

    def trace_overhead_ns() -> float:
        """Per-call cost of the unsampled TASK-SUBMIT path — exactly
        what core._trace_attach adds to a driver .remote() with tracing
        always-on: one contextvar read plus the ingress coin
        (maybe_mint miss generates no ids, allocates nothing).  The
        per-REQUEST serve-hop shapes (entered span blocks, full mints)
        are request-scale costs exercised by rpc_p99_actor_call_us."""
        current, maybe_mint = (tracing_plane.current,
                               tracing_plane.maybe_mint)
        t0 = time.perf_counter()
        for _ in range(n_spans):
            pass
        bare = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(n_spans):
            if current() is None:
                maybe_mint()
        return (time.perf_counter() - t0 - bare) / n_spans * 1e9

    trace_overhead_ns()                                   # warmup
    trace_ns = sorted(trace_overhead_ns() for _ in range(3))[1]
    emit("trace_overhead_unsampled_ns", trace_ns, "ns")
    if trace_ns > 2000.0:
        # Observability must stay free: the unsampled path taxing calls
        # past the budget is a regression, not a tuning matter.
        print(json.dumps({"metric": "bench_error",
                          "bench_error":
                          f"trace_overhead_unsampled_ns={trace_ns:.0f} "
                          "exceeds 2000ns budget"}))

    # ---- traced actor-call p99 with the per-stage decomposition the
    # control-plane fast-path work (ROADMAP item 2) attributes against:
    # sample rate forced to 1.0 so EVERY call records client/worker
    # spans — this is the fully-instrumented number, deliberately.
    cfg = global_config()
    old_rate = cfg.trace_sample_rate
    cfg.trace_sample_rate = 1.0
    try:
        n_rpc = max(200, int(1000 * scale))
        art.get(actor.ping.remote())                      # warm trace path
        lat = []
        for _ in range(n_rpc):
            t0 = time.perf_counter()
            art.get(actor.ping.remote())
            lat.append(time.perf_counter() - t0)
        lat.sort()
        emit("rpc_p99_actor_call_us",
             lat[int(0.99 * (len(lat) - 1))] * 1e6, "us")
        stages: dict = {}
        for s in tracing_plane.recorder().snapshot():
            if s.get("name") == "call:Echo.ping":
                for stage, sec in (s.get("stages") or {}).items():
                    stages.setdefault(stage, []).append(sec)
        for stage, vals in sorted(stages.items()):
            emit(f"rpc_actor_call_{stage}_us_mean",
                 sum(vals) / len(vals) * 1e6, "us")
    finally:
        cfg.trace_sample_rate = old_rate

    # ---- continuous-profiler overhead (observability/cpu_profiler.py):
    # the pipelined actor-call workload with the driver's sampler
    # stopped vs. running.  Arms run in ABBA order — every bench arm
    # leaves the cluster a little slower (the GCS task table grows with
    # each burst), so a fixed off-then-on order reads that monotone
    # drift as profiler overhead; ABBA gives both arms the same mean
    # position and cancels it.  The fraction compares MEDIANS of the
    # per-arm rates (a median-of-ratios amplifies single-round noise on
    # 1-cpu rigs).  Budgeted at <= 2% — the always-on contract the
    # profiler ships under (bench_error past it, like the other
    # observability budgets).  Runs AFTER the traced sections: its
    # extra pipelined calls must not pollute the span recorder the
    # wire-stage means read.
    from ant_ray_tpu.observability import cpu_profiler  # noqa: PLC0415

    n_prof = max(400, int(2000 * scale))

    def rate(n) -> float:
        t0 = time.perf_counter()
        actor_async(n)
        return n / (time.perf_counter() - t0)

    def arm(sampler_on: bool) -> float:
        if sampler_on:
            cpu_profiler.start("driver")
        else:
            cpu_profiler.stop()
        rate(n_prof // 4)                              # settle each arm
        return rate(n_prof)

    offs, ons = [], []
    for sampler_on in (False, True, True, False, False, True, True,
                       False):
        (ons if sampler_on else offs).append(arm(sampler_on))
    prof_frac = max(0.0, 1.0 - sorted(ons)[2] / sorted(offs)[2])
    emit("cpu_profiler_overhead_fraction", prof_frac, "fraction")
    if prof_frac > 0.02:
        print(json.dumps({"metric": "bench_error",
                          "bench_error":
                          f"cpu_profiler_overhead_fraction={prof_frac:.4f}"
                          " exceeds 0.02 budget"}))

    # ---- wire cost accounting smoke (protocol.wire_counters): the
    # per-method byte counters behind art_rpc_bytes_total, read around
    # a known burst of pushes.  Guarded "lower": bytes-per-call creeping
    # up is frame bloat on the hottest method of the wire.
    from ant_ray_tpu._private import protocol  # noqa: PLC0415

    def push_send_bytes() -> int:
        entry = protocol.wire_counters.get(("PushTask", "send"))
        return entry[1] if entry else 0

    before_bytes = push_send_bytes()
    n_push = max(200, int(1000 * scale))
    actor_async(n_push)
    delta_bytes = push_send_bytes() - before_bytes
    assert delta_bytes > 0, "PushTask wire accounting recorded nothing"
    emit("rpc_pushtask_send_bytes_per_call", delta_bytes / n_push,
         "bytes/call")

    # ---- cluster state observatory (_private/task_state.py): (a) the
    # per-event fold cost on the TaskEventsAdd ingest path — the gcs.py
    # export-gate comment pins why per-event work there must stay ~free
    # (it taxes EVERY task the cluster runs); (b) the server-side
    # ListTasks round trip over the populated table (the thousands of
    # task/actor-call records the workloads above produced), replacing
    # the old pull-50k-raw-events-and-fold-client-side state query.
    from ant_ray_tpu._private import task_events  # noqa: PLC0415
    from ant_ray_tpu._private.task_state import ingest_overhead_ns  # noqa: PLC0415
    from ant_ray_tpu._private.worker import global_worker  # noqa: PLC0415

    ingest_ns = sorted(
        ingest_overhead_ns(max(3000, int(20000 * scale)))
        for _ in range(3))[1]
    emit("task_state_ingest_overhead_ns", ingest_ns, "ns")
    if ingest_ns > 4000.0:
        # The fold rides the hottest GCS write path: past this budget
        # it is a throughput regression, not a tuning matter.
        print(json.dumps({"metric": "bench_error",
                          "bench_error":
                          f"task_state_ingest_overhead_ns={ingest_ns:.0f}"
                          " exceeds 4000ns budget"}))

    task_events.flush()               # this driver's tail of records
    gcs = global_worker.runtime._gcs
    gcs.call("ListTasks", {"limit": 1000})          # warm the route
    rounds = 20
    t0 = time.perf_counter()
    for _ in range(rounds):
        reply = gcs.call("ListTasks", {"limit": 1000})
    emit("state_list_tasks_us",
         1e6 * (time.perf_counter() - t0) / rounds, "us")
    assert reply["tasks"], "state table unexpectedly empty"

    art.shutdown()

    # ---- striped broadcast pull (node_daemon._pull_chunks): a third
    # node pulls a 2-holder object over the bulk transfer channel with
    # multi-holder striping.  Driven by direct EnsureLocal RPCs (no
    # worker leases — this measures the object plane, not scheduling).
    try:
        from ant_ray_tpu._private.protocol import ClientPool  # noqa: PLC0415
        from ant_ray_tpu.cluster_utils import Cluster  # noqa: PLC0415

        cluster = Cluster(head_node_args={"num_cpus": 1})
        n1 = cluster.add_node(num_cpus=1)
        n2 = cluster.add_node(num_cpus=1)
        cluster.connect()
        try:
            stripe_mb = max(32, int(256 * scale))    # >= stripe_min
            stripe_blob = np.random.default_rng(1).integers(
                0, 127, size=stripe_mb << 20, dtype=np.int8)
            ref = art.put(stripe_blob)
            pool = ClientPool()

            def ensure(addr):
                reply = pool.get(addr).call(
                    "EnsureLocal",
                    {"object_id": ref.id, "timeout": 120,
                     "prefetch": True}, timeout=180)
                assert reply.get("ok"), reply

            ensure(n1)                       # second holder (warm-up pull)
            t0 = time.perf_counter()
            ensure(n2)                       # striped: head + n1 serve
            striped_gbps = (stripe_blob.nbytes / (1 << 30)) / \
                (time.perf_counter() - t0)
            emit("object_broadcast_striped_gb_s", striped_gbps, "GiB/s")
        finally:
            art.shutdown()
            cluster.shutdown()
    except Exception as e:  # noqa: BLE001 — bench must not die here
        print(json.dumps({"metric": "bench_error",
                          "bench_error":
                          f"striped bench failed: {e!r}"[:300]}))

    # ---- hierarchical allreduce DCN economics: 4 gloo ranks simulate
    # 2 slices x 2 hosts; the two-level verb reduces intra-slice
    # first and exchanges once per SLICE, so its cross-slice (DCN)
    # participant count per bucket is num_slices while the flat verb's
    # is world_size.  The ratio (0.5 here) is the wire-message scaling
    # the 100k-GPU topology split buys; guarded "lower" — drifting to
    # 1.0 means the hierarchy stopped engaging.
    try:
        from ant_ray_tpu.util import collective as col  # noqa: PLC0415

        art.init(num_cpus=4, ignore_reinit_error=True)
        topo = col.SliceTopology.regular(4, 2)

        @art.remote
        class _HierRanker(col.CollectiveActorMixin):
            def sync(self, rank, hierarchy):
                tensors = [np.full((4096,), float(rank + 1),
                                   np.float32)]
                col.allreduce_coalesced(tensors, group_name="bench_hier",
                                        hierarchy=hierarchy)
                dcn_hier = col.fusion_stats(
                    "bench_hier")["dcn_participants"]
                col.allreduce_coalesced(tensors, group_name="bench_hier")
                dcn_total = col.fusion_stats(
                    "bench_hier")["dcn_participants"]
                return dcn_hier, dcn_total - dcn_hier

        actors = [_HierRanker.remote() for _ in range(4)]
        col.create_collective_group(actors, world_size=4,
                                    ranks=[0, 1, 2, 3], backend="gloo",
                                    group_name="bench_hier")
        replies = art.get([a.sync.remote(rank, topo)
                           for rank, a in enumerate(actors)])
        dcn_hier, dcn_flat = replies[0]
        emit("allreduce_hierarchical_vs_flat_rpc_ratio",
             dcn_hier / dcn_flat if dcn_flat else 1.0, "fraction")
        art.shutdown()
    except Exception as e:  # noqa: BLE001 — bench must not die here
        print(json.dumps({"metric": "bench_error",
                          "bench_error":
                          f"hierarchical bench failed: {e!r}"[:300]}))

    # ---- resilience plane: recovery time + goodput under chaos.
    # A 1-worker fit crashes deterministically mid-run (attempt 0,
    # checkpointing every step); the restart resumes from the latest
    # checkpoint.  `train_recovery_time_s` is the gap between the last
    # pre-crash step and the first post-restart step (failure
    # detection + gang relaunch + restore — the "recovery time as a
    # throughput term" the 100k-GPU collectives paper budgets for);
    # `goodput_under_chaos` is unique productive steps over total step
    # executions (re-executed steps are waste — 1.0 means the failure
    # cost zero recomputation).
    try:
        import tempfile  # noqa: PLC0415

        from ant_ray_tpu.train import (  # noqa: PLC0415
            FailureConfig,
            JaxTrainer,
            RunConfig,
            ScalingConfig,
        )

        art.init(num_cpus=2)
        steps_total = max(8, int(20 * scale))
        crash_at = steps_total // 2
        log_path = tempfile.mktemp(prefix="art_bench_resilience_")

        def resilience_loop(config):
            import time as _t  # noqa: PLC0415

            from ant_ray_tpu import train as _train  # noqa: PLC0415

            ctx = _train.get_context()
            start = 0
            if ctx.latest_checkpoint is not None:
                start = int(ctx.latest_checkpoint.to_pytree()["step"]) + 1
            for step in range(start, config["steps"]):
                # CLOCK_MONOTONIC is system-wide on Linux, so stamps
                # from the pre- and post-restart worker processes are
                # directly comparable.
                with open(config["log"], "a") as f:
                    f.write(f"{ctx.attempt} {step} {_t.monotonic()}\n")
                if step == config["crash_at"] and ctx.attempt == 0:
                    raise RuntimeError("chaos: induced worker failure")
                _train.report({"step": step}, checkpoint={"step": step})

        result = JaxTrainer(
            resilience_loop,
            train_loop_config={"steps": steps_total, "crash_at": crash_at,
                               "log": log_path},
            scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(
                name="bench-resilience", storage_path=tempfile.mkdtemp(),
                failure_config=FailureConfig(max_failures=1))).fit()
        assert result.error is None, result.error
        rows = [(int(a), int(s), float(ts))
                for a, s, ts in (line.split()
                                 for line in open(log_path))]
        crash_ts = max(ts for a, _s, ts in rows if a == 0)
        resume_ts = min(ts for a, _s, ts in rows if a > 0)
        emit("train_recovery_time_s", resume_ts - crash_ts, "s")
        emit("goodput_under_chaos",
             len({s for _a, s, _ts in rows}) / len(rows), "fraction")
        art.shutdown()
    except Exception as e:  # noqa: BLE001 — bench must not die here
        print(json.dumps({"metric": "bench_error",
                          "bench_error":
                          f"resilience bench failed: {e!r}"[:300]}))

    # ---- control-plane HA: failover MTTR + goodput under a leader
    # kill.  One replicated head (leader + 2 warm standbys over the
    # shared store) takes two SIGKILLs of whoever currently leads:
    # (a) at rest — `gcs_failover_time_s` is the gap from the kill to
    # the first acknowledged mutation on the promoted standby (lease
    # expiry + promotion + client re-resolve: the control plane's
    # MTTR, the number the lease-TTL knob trades against); and
    # (b) mid-fit — `goodput_under_leader_kill` is unique productive
    # steps over total step executions while the leader dies under an
    # active training run (1.0 = the control-plane loss unwound
    # nothing and recomputed nothing; acceptance bar 0.90).
    try:
        import tempfile  # noqa: PLC0415
        import threading  # noqa: PLC0415

        from ant_ray_tpu.cluster_utils import Cluster  # noqa: PLC0415
        from ant_ray_tpu.train import (  # noqa: PLC0415
            FailureConfig,
            JaxTrainer,
            RunConfig,
            ScalingConfig,
        )
        from ant_ray_tpu.util.chaos import ChaosSchedule  # noqa: PLC0415

        cluster = Cluster(head_node_args={"num_cpus": 2,
                                          "gcs_standbys": 2})
        cluster.add_node(num_cpus=2)
        cluster.connect()
        try:
            from ant_ray_tpu.api import global_worker  # noqa: PLC0415

            rt = global_worker.runtime
            rt._gcs.call("KVPut", {"key": "warm", "value": b"1"},
                         retries=3)
            cluster.kill_gcs_leader()
            t0 = time.perf_counter()
            deadline = time.monotonic() + 60
            while True:
                try:
                    rt._gcs.call("KVPut", {"key": "probe",
                                           "value": b"1"}, timeout=2)
                    break
                except Exception:  # noqa: BLE001 — failover in progress
                    if time.monotonic() > deadline:
                        raise
                    time.sleep(0.02)
            emit("gcs_failover_time_s", time.perf_counter() - t0, "s")

            steplog = tempfile.mktemp(prefix="art_bench_ha_")
            chaos = ChaosSchedule(seed=7)
            chaos.kill_leader(3, cluster)

            def ha_loop(config):
                import time as _t  # noqa: PLC0415

                from ant_ray_tpu import train as _train  # noqa: PLC0415

                ctx = _train.get_context()
                for step in range(config["steps"]):
                    with open(config["log"], "a") as f:
                        f.write(f"{ctx.attempt} {step}\n")
                    _t.sleep(0.25)
                    _train.report({"step": step},
                                  checkpoint={"step": step})

            steps_total = max(8, int(10 * scale))
            trainer = JaxTrainer(
                ha_loop,
                train_loop_config={"steps": steps_total,
                                   "log": steplog},
                scaling_config=ScalingConfig(num_workers=1),
                run_config=RunConfig(
                    name="bench-ha", storage_path=tempfile.mkdtemp(),
                    failure_config=FailureConfig(max_failures=0)))
            box = {}
            fit_thread = threading.Thread(
                target=lambda: box.update(result=trainer.fit()),
                daemon=True)
            fit_thread.start()
            fit_deadline = time.monotonic() + 240
            while time.monotonic() < fit_deadline and \
                    fit_thread.is_alive():
                if os.path.exists(steplog):
                    lines = open(steplog).read().splitlines()
                    if lines:
                        chaos.fire(int(lines[-1].split()[1]))
                time.sleep(0.1)
            fit_thread.join(timeout=30)
            assert not fit_thread.is_alive(), "fit wedged"
            assert box["result"].error is None, box["result"].error
            assert chaos.killed_leaders, "leader kill never fired"
            rows = open(steplog).read().splitlines()
            unique = {int(line.split()[1]) for line in rows}
            assert len(unique) == steps_total, (len(unique), steps_total)
            emit("goodput_under_leader_kill",
                 len(unique) / len(rows), "fraction")
        finally:
            art.shutdown()
            cluster.shutdown()
    except Exception as e:  # noqa: BLE001 — bench must not die here
        print(json.dumps({"metric": "bench_error",
                          "bench_error":
                          f"gcs ha bench failed: {e!r}"[:300]}))

    # ---- serve overload plane: goodput + shed fraction at >= 4x
    # offered load.  A bounded deployment (2 replicas x (1 running +
    # 1 queued), 100 ms service, 1 s deadline) takes closed-loop
    # traffic from 8 clients whose sheds return in milliseconds, so
    # offered load far exceeds the ~20 req/s capacity.  The 100 ms
    # service time is deliberate: capacity is service-dominated (not
    # RPC-RTT-dominated), so both numbers are stable on a loaded rig.
    # `serve_goodput_under_overload` is completed-in-deadline requests
    # per second (healthy admission control keeps it near replica
    # capacity no matter the offered load); `serve_shed_fraction` is
    # the typed-reject share of offered requests — at 4x+ overload
    # MOST requests must shed, so a drop toward zero means the
    # admission bound stopped holding (work queueing unboundedly
    # instead of fast-failing).
    try:
        import threading  # noqa: PLC0415

        from ant_ray_tpu import serve  # noqa: PLC0415
        from ant_ray_tpu.exceptions import (  # noqa: PLC0415
            BackPressureError,
            DeadlineExceededError,
        )

        art.init(num_cpus=2, ignore_reinit_error=True)

        @serve.deployment(name="bench_overload", num_replicas=2,
                          max_ongoing_requests=1, max_queued_requests=1,
                          request_timeout_s=1.0)
        class _Bounded:
            def __call__(self, x=None):
                time.sleep(0.1)
                return x

        handle = serve.run(_Bounded.bind())
        handle.call()                               # warm the route
        duration = max(3.0, 8 * scale)
        stop_at = time.monotonic() + duration
        counts = {"ok": 0, "shed": 0, "deadline": 0}
        count_lock = threading.Lock()

        def overload_client():
            while time.monotonic() < stop_at:
                try:
                    handle.call()
                    tag = "ok"
                except BackPressureError:
                    tag = "shed"
                except DeadlineExceededError:
                    tag = "deadline"
                with count_lock:
                    counts[tag] += 1

        clients = [threading.Thread(target=overload_client)
                   for _ in range(8)]
        for t in clients:
            t.start()
        for t in clients:
            t.join()
        offered = sum(counts.values())
        assert offered and counts["ok"], counts
        emit("serve_goodput_under_overload", counts["ok"] / duration,
             "req/s")
        emit("serve_shed_fraction",
             (offered - counts["ok"]) / offered, "fraction")
        serve.shutdown()
        art.shutdown()
    except Exception as e:  # noqa: BLE001 — bench must not die here
        print(json.dumps({"metric": "bench_error",
                          "bench_error":
                          f"serve overload bench failed: {e!r}"[:300]}))

    # ---- LLM serving plane (PR 18): chunked-prefill TTFT isolation +
    # session-offload capacity, via the committed multi-client load
    # generator (benchmarks/llm_loadgen.py).  The TTFT leg offers 3
    # closed-loop long-prompt ingesters (896 tokens) interfering with 1
    # short-prompt client (8 tokens); `llm_ttft_short_p50/p99_us` guard
    # the short client's wait absolutely.  The session leg runs 6 pausing
    # sessions against 2 KV slots with an idle sweep:
    # `llm_resident_sessions` > slots means offload is doing its job
    # (every session completes, none shed).
    try:
        import sys as _sys  # noqa: PLC0415

        _sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        import jax as _jax  # noqa: PLC0415
        import jax.numpy as _jnp  # noqa: PLC0415
        from llm_loadgen import ClientSpec, LoadGen  # noqa: PLC0415

        from ant_ray_tpu.llm import (  # noqa: PLC0415
            LLMEngine,
            SamplingParams,
        )
        from ant_ray_tpu.llm.engine import EngineLoop  # noqa: PLC0415
        from ant_ray_tpu.models import llama  # noqa: PLC0415

        # Big enough that a chunk costs more than its dispatch (the
        # tiny cfg's would not).
        llm_cfg = llama.LlamaConfig(
            vocab_size=256, dim=128, n_layers=2, n_heads=4,
            n_kv_heads=2, mlp_dim=256, max_seq=1024,
            dtype=_jnp.float32)
        llm_params = llama.init_params(llm_cfg, _jax.random.PRNGKey(7))
        duration = max(4.0, 10 * scale)

        eng = LLMEngine(llm_cfg, llm_params, slots=4, max_seq=1024,
                        prefill_chunk_tokens=16)
        loop = EngineLoop(eng, metrics_interval_s=3600.0)
        # Compile outside the measured window (chunk, decode, both).
        for p in ([3] * 896, [4] * 8):
            loop.submit(list(p), SamplingParams(
                temperature=0.0, max_tokens=2)).wait(timeout=600)
        # 3 long ingesters + 1 short interactive client fills the 4 KV
        # slots exactly (no slot-wait noise); the short's TTFT then
        # measures pure prefill interference.
        ttft = LoadGen(loop, seed=18).run(
            [ClientSpec("long", 896, 2, count=3),
             ClientSpec("short", 8, 8, count=1,
                        think_time_s=0.01)], duration)
        loop.shutdown()
        assert ttft.failed == 0, ttft.errors[:3]
        assert ttft.ttft_us.get("short"), "no short TTFT samples"
        emit("llm_tokens_per_s", ttft.tokens_per_s(), "tokens/s")
        emit("llm_ttft_short_p50_us", ttft.percentile("short", 50), "us")
        emit("llm_ttft_short_p99_us", ttft.percentile("short", 99), "us")

        sess_eng = LLMEngine("tiny", slots=2, max_seq=128,
                             prefill_chunk_tokens=16,
                             kv_idle_evict_s=0.05)
        sess_loop = EngineLoop(sess_eng, metrics_interval_s=3600.0)
        sess_rep = LoadGen(sess_loop, seed=18).run(
            [ClientSpec("session", 12, 4, count=6, session=True,
                        pause_s=0.15, turns=3)],
            max(6.0, 12 * scale))
        sess_loop.shutdown()
        assert sess_rep.failed == 0, sess_rep.errors[:3]
        assert sess_rep.finished == 18, sess_rep
        emit("llm_resident_sessions",
             float(sess_eng.resident_sessions()), "sessions")
        emit("llm_session_restores",
             float(sess_eng.stats["restores"]), "restores")
    except Exception as e:  # noqa: BLE001 — bench must not die here
        print(json.dumps({"metric": "bench_error",
                          "bench_error":
                          f"llm serving bench failed: {e!r}"[:300]}))

    # ---- scale observatory (benchmarks/scale_harness.py): control-
    # plane cost at N=100 stub nodes over the real wire protocol —
    # lease throughput (SelectNode → LeaseWorker → ReturnWorker, with
    # the sticky pack-pick cache on), GCS CPU per second per 100
    # heartbeating nodes, and the head's io-loop busy fraction under
    # combined heartbeat + lease + task-event load.  The full
    # BENCH_scale.json sweep runs these at many N; this is the guarded
    # N=100 point.
    try:
        import sys as _sys  # noqa: PLC0415

        _sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from scale_harness import measure_point  # noqa: PLC0415

        row = measure_point(100, window_s=3.0, ha_standbys=0,
                            measure_failover=False)
        emit("sched_leases_per_s_100n", row["leases_per_s"],
             "leases/s")
        emit("heartbeat_cpu_ms_per_100n",
             row["heartbeat_cpu_ms_per_s_per_100n"], "ms/s")
        duty = row.get("gcs_io_loop_duty_loaded")
        if duty is not None:
            emit("gcs_loop_duty_at_100n", duty, "fraction")
    except Exception as e:  # noqa: BLE001 — bench must not die here
        print(json.dumps({"metric": "bench_error",
                          "bench_error":
                          f"scale bench failed: {e!r}"[:300]}))

    # ---- regression guard vs the committed control file
    import sys  # noqa: PLC0415

    sys.path.insert(0, os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    import bench as bench_mod  # noqa: PLC0415

    regressions = bench_mod.check_regression(
        {r["metric"]: r["value"] for r in results})
    if regressions:
        print(json.dumps({"metric": "bench_regression",
                          "regressions": regressions}))

    print(json.dumps({"metric": "microbench_summary",
                      "workloads": len(results),
                      # Sync task/actor roundtrips are bounded by the
                      # host's core count (driver + daemon + worker
                      # share one CPU on the bench rig); the async
                      # figures are the engine numbers.
                      "note": "sync paths rig-limited on 1-cpu hosts"}))
    if args.json_out:
        import platform

        with open(args.json_out, "w") as f:
            json.dump({"results": results,
                       "cpu_count": os.cpu_count(),
                       "platform": platform.platform(),
                       "note": args.note}, f, indent=1)


if __name__ == "__main__":
    main()
