"""The 1000-node scale observatory: stub-node harness + cost curves.

``ScaleCluster`` boots a REAL GCS (plain, or replicated with warm
standbys over the shared store) and N in-process
:class:`~ant_ray_tpu._private.sim_node.StubNode` clients — each one a
real wire-protocol participant (register, versioned heartbeats, lease
grants over its own RPC server, task-event flushes, parked SubPoll
long-polls) with no worker processes behind it, so one driver on a
1-core rig presents a 500-node cluster's control-plane load to the
head.  The driver then applies OPEN-LOOP load (SelectNode →
LeaseWorker → ReturnWorker churn, per-stub task-event streams) and
reads the GCS's own attribution back out over ``GetScaleStats``:
per-method server handle time, scheduler scan width, heartbeat ingest
counters, table/ring occupancy, io-loop duty.

Run the sweep (writes the committed cost curves):

    python benchmarks/scale_harness.py \
        --nodes 10,50,100,250,500 --json-out BENCH_scale.json

Each sweep point runs two lease-churn arms — ART_SCHED_PICK_CACHE=1
(default) and =0 — which is the before/after curve for the measured
O(nodes) scheduler-scan-per-lease cliff that the sticky pack-pick
cache in ``gcs._pick_node`` flattens.

Read the result via ``python -m ant_ray_tpu scale-report`` or
``GET /api/scale`` on the dashboard.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# The observatory measures the control plane, not the data plane: no
# dashboard, no node agents even if a config on this host enables them.
os.environ.setdefault("ART_INCLUDE_DASHBOARD", "0")
os.environ.setdefault("ART_ENABLE_NODE_AGENT", "0")

# Runnable as a plain script: python benchmarks/scale_harness.py puts
# benchmarks/ (not the repo root) on sys.path.
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

from ant_ray_tpu._private import services  # noqa: E402
from ant_ray_tpu._private.protocol import ClientPool, IoThread  # noqa: E402
from ant_ray_tpu._private.sim_node import StubNode  # noqa: E402

_CLK_TCK = os.sysconf("SC_CLK_TCK") if hasattr(os, "sysconf") else 100


def _proc_cpu_s(pid: int) -> float:
    """utime+stime of one process from /proc (Linux rigs only)."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        return (int(fields[11]) + int(fields[12])) / _CLK_TCK
    except (OSError, IndexError, ValueError):
        return 0.0


def _raise_nofile(need: int) -> None:
    """N stubs hold ~3 fds each (listen socket, GCS conn, driver conn);
    the default 1024 soft limit dies around N=300."""
    try:
        import resource  # noqa: PLC0415

        soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
        if soft < need:
            resource.setrlimit(resource.RLIMIT_NOFILE,
                               (min(need, hard), hard))
    except (ImportError, ValueError, OSError):
        pass


class ScaleCluster:
    """A real GCS + N stub nodes + driver-side load appliers."""

    def __init__(self, num_stubs: int, *, ha_standbys: int = 0,
                 stub_cpus: float = 8.0, subscribe: bool = True,
                 env: dict | None = None):
        self.num_stubs = num_stubs
        self._ha_standbys = ha_standbys
        self._stub_cpus = stub_cpus
        self._subscribe = subscribe
        self._env = dict(env or {})
        self._saved_env: list[tuple[str, str | None]] = []
        self._gcs_procs: list = []          # (proc, address)
        self.stubs: list[StubNode] = []
        self._pool = ClientPool()
        self._session_dir = ""
        self.gcs_address = ""

    # ------------------------------------------------------- lifecycle

    def start(self) -> str:
        _raise_nofile(self.num_stubs * 4 + 256)
        for key, value in self._env.items():
            self._saved_env.append((key, os.environ.get(key)))
            os.environ[key] = str(value)
        self._session_dir = services.new_session_dir()
        replicas = 1 + self._ha_standbys
        for i in range(replicas):
            proc, address = services.start_gcs(
                self._session_dir,
                ha_replica_id=f"r{i}" if replicas > 1 else None)
            self._gcs_procs.append((proc, address))
        self.gcs_address = ",".join(a for _p, a in self._gcs_procs)
        for _ in range(self.num_stubs):
            stub = StubNode(self.gcs_address, num_cpus=self._stub_cpus)
            stub.start()
            if self._subscribe:
                stub.subscribe(("node",))
            self.stubs.append(stub)
        return self.gcs_address

    def stop(self) -> None:
        for stub in self.stubs:
            stub.stop()
        self.stubs.clear()
        self._pool.close_all()
        services.stop_processes([p for p, _a in self._gcs_procs])
        self._gcs_procs.clear()
        for key, old in reversed(self._saved_env):
            if old is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = old
        self._saved_env.clear()

    def __enter__(self) -> "ScaleCluster":
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.stop()

    # ------------------------------------------------------ GCS access

    def client(self):
        """Leader-aware client for the replica spec (plain client when
        not replicated)."""
        return self._pool.get(self.gcs_address)

    def scale_stats(self, replica: str | None = None) -> dict:
        """One replica's local cost counters.  GetScaleStats is a
        follower-servable introspection read, so under HA the router
        would round-robin it onto a standby whose scheduler/heartbeat
        counters are idle — query the leader (or the given replica)
        directly instead."""
        if replica is None:
            replica = (self.leader_address()
                       if self._ha_standbys else self.gcs_address)
        return self._pool.get(replica).call("GetScaleStats", {},
                                            timeout=30)

    def gcs_cpu_s(self) -> float:
        """CPU seconds burned by all live GCS replicas so far."""
        return sum(_proc_cpu_s(p.pid) for p, _a in self._gcs_procs
                   if p.poll() is None)

    def leader_address(self, timeout: float = 15.0) -> str:
        deadline = time.monotonic() + timeout
        last_err: Exception | None = None
        while time.monotonic() < deadline:
            for _proc, addr in self._gcs_procs:
                try:
                    view = self._pool.get(addr).call("GetHaView", {},
                                                     timeout=2)
                except Exception as e:  # noqa: BLE001 — replica down
                    last_err = e
                    continue
                if view.get("role") == "leader":
                    return view["address"]
            time.sleep(0.05)
        raise RuntimeError(f"no GCS leader elected: {last_err}")

    def kill_leader(self) -> str:
        leader = self.leader_address()
        for index, (proc, addr) in enumerate(self._gcs_procs):
            if addr == leader:
                proc.kill()
                proc.wait(timeout=5)
                del self._gcs_procs[index]
                return addr
        raise RuntimeError(f"leader {leader} not in replica set")

    # ------------------------------------------------------- load legs

    def start_task_events(self, total_rate_hz: float) -> None:
        """Spread an aggregate task-event rate across all stubs."""
        per_stub = total_rate_hz / max(1, len(self.stubs))
        for stub in self.stubs:
            stub.start_task_event_loop(per_stub)

    def lease_churn(self, duration_s: float, concurrency: int = 8,
                    resources: dict | None = None) -> dict:
        """Open-loop lease pressure from the driver: ``concurrency``
        async clients each running SelectNode → LeaseWorker (at the
        picked stub, over the wire) → ReturnWorker until the window
        closes.  Exactly the control-plane path a `.remote()` pays,
        minus worker execution."""
        demand = dict(resources or {"CPU": 1.0})
        counts = {"leases": 0, "infeasible": 0, "errors": 0}
        gcs = self.client()
        pool = self._pool

        async def churn_client() -> None:
            deadline = time.monotonic() + duration_s
            while time.monotonic() < deadline:
                try:
                    node = await gcs.call_async(
                        "SelectNode", {"resources": demand}, timeout=10)
                    if node is None:
                        counts["infeasible"] += 1
                        await asyncio.sleep(0.01)
                        continue
                    reply = await pool.get(node.address).call_async(
                        "LeaseWorker", {"resources": demand}, timeout=10)
                    if "granted" not in reply:
                        counts["infeasible"] += 1
                        continue
                    await pool.get(node.address).call_async(
                        "ReturnWorker",
                        {"worker_id": reply["worker_id"]}, timeout=10)
                    counts["leases"] += 1
                except Exception:  # noqa: BLE001 — failover window
                    counts["errors"] += 1
                    await asyncio.sleep(0.05)

        async def run() -> None:
            await asyncio.gather(*(churn_client()
                                   for _ in range(concurrency)))

        t0 = time.perf_counter()
        IoThread.get().run_coro(run(), timeout=duration_s + 60)
        wall = time.perf_counter() - t0
        counts["wall_s"] = wall
        counts["leases_per_s"] = counts["leases"] / wall if wall else 0.0
        return counts

    def measure_failover(self, timeout: float = 60.0) -> float:
        """Kill the leader; seconds until the promoted standby
        acknowledges a mutation through the leader-aware router (lease
        expiry + promotion + client re-resolve)."""
        assert self._ha_standbys > 0, "failover needs standbys"
        gcs = self.client()
        gcs.call("KVPut", {"key": "scale_warm", "value": b"1"},
                 timeout=10)
        self.kill_leader()
        t0 = time.perf_counter()
        deadline = time.monotonic() + timeout
        while True:
            try:
                gcs.call("KVPut", {"key": "scale_probe", "value": b"1"},
                         timeout=2)
                return time.perf_counter() - t0
            except Exception:  # noqa: BLE001 — failover in progress
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.02)


# ------------------------------------------------------------ measurement


def _stats_window(cluster: ScaleCluster, window_s: float) -> dict:
    """Sample GetScaleStats + GCS CPU around a settle window and return
    the deltas that turn into per-second costs."""
    before = cluster.scale_stats()
    cpu0 = cluster.gcs_cpu_s()
    t0 = time.perf_counter()
    time.sleep(window_s)
    after = cluster.scale_stats()
    cpu1 = cluster.gcs_cpu_s()
    wall = time.perf_counter() - t0
    beats = after["heartbeat"]["beats"] - before["heartbeat"]["beats"]
    return {
        "wall_s": wall,
        "gcs_cpu_s": cpu1 - cpu0,
        "beats": beats,
        "beats_per_s": beats / wall,
        "before": before,
        "after": after,
    }


def _handle_attribution(stats: dict) -> dict:
    """method -> {calls, ms, us_per_call} from cumulative handle
    counters, sorted by total time (the per-method cost ranking)."""
    out = {}
    for method, (calls, ns) in sorted(
            stats.get("handle", {}).items(),
            key=lambda kv: -kv[1][1]):
        if calls:
            out[method] = {"calls": calls,
                           "ms": round(ns / 1e6, 3),
                           "us_per_call": round(ns / calls / 1e3, 2)}
    return out


def measure_point(num_stubs: int, *, window_s: float = 5.0,
                  lease_concurrency: int = 8,
                  task_event_rate_hz: float = 500.0,
                  ha_standbys: int = 1,
                  measure_failover: bool = True,
                  pick_cache: bool = True,
                  stub_cpus: float = 8.0) -> dict:
    """One sweep point: boot N stubs against a real (replicated) GCS,
    measure heartbeat-only cost, then combined lease + task-event load,
    then (optionally) leader-kill failover.  Returns one BENCH_scale
    sweep row."""
    env = {"ART_SCHED_PICK_CACHE": "1" if pick_cache else "0"}
    with ScaleCluster(num_stubs, ha_standbys=ha_standbys,
                      stub_cpus=stub_cpus, env=env) as cluster:
        # Let registrations drain and heartbeats reach steady state
        # (jitter spreads phases across one period).
        time.sleep(2.0)

        idle = _stats_window(cluster, window_s)
        hb_cpu_ms_per_s = idle["gcs_cpu_s"] * 1e3 / idle["wall_s"]

        cluster.start_task_events(task_event_rate_hz)
        cpu0 = cluster.gcs_cpu_s()
        stats0 = cluster.scale_stats()
        churn = cluster.lease_churn(window_s,
                                    concurrency=lease_concurrency)
        stats1 = cluster.scale_stats()
        cpu1 = cluster.gcs_cpu_s()

        sched0, sched1 = stats0["sched"], stats1["sched"]
        scans = sched1["scans"] - sched0["scans"]
        scanned = sched1["scanned_nodes"] - sched0["scanned_nodes"]
        picks = sched1["picks"] - sched0["picks"]
        hits = sched1["pick_cache_hits"] - sched0["pick_cache_hits"]
        folded = (stats1["table_rows"]["tasks"]
                  - stats0["table_rows"]["tasks"])

        row = {
            "nodes": num_stubs,
            "pick_cache": pick_cache,
            "window_s": round(window_s, 2),
            # heartbeat-only leg
            "heartbeat_cpu_ms_per_s": round(hb_cpu_ms_per_s, 2),
            "heartbeat_cpu_ms_per_s_per_100n": round(
                hb_cpu_ms_per_s / (num_stubs / 100.0), 2),
            "beats_per_s": round(idle["beats_per_s"], 1),
            "gcs_io_loop_duty_idle":
                idle["after"].get("io_loop_duty"),
            # loaded leg
            "leases_per_s": round(churn["leases_per_s"], 1),
            "lease_errors": churn["errors"],
            "lease_infeasible": churn["infeasible"],
            "sched_scans": scans,
            "sched_scanned_nodes_per_pick": round(
                scanned / picks, 2) if picks else None,
            "pick_cache_hit_rate": round(hits / picks, 3)
                if picks else None,
            "task_rows_folded": folded,
            "gcs_cpu_s_loaded": round(cpu1 - cpu0, 3),
            "gcs_io_loop_duty_loaded": stats1.get("io_loop_duty"),
            "subscribers": stats1.get("subscribers"),
            "table_rows": stats1.get("table_rows"),
            "handle_by_method": _handle_attribution(stats1),
        }
        if measure_failover and ha_standbys > 0:
            row["failover_s"] = round(cluster.measure_failover(), 3)
            # Post-failover sanity: stubs re-resolve and keep beating.
            time.sleep(2.0)
            post = cluster.scale_stats()
            row["beats_after_failover"] = (
                post["heartbeat"]["beats"])
        return row


def run_sweep(nodes: list[int], *, window_s: float = 5.0,
              lease_concurrency: int = 8,
              task_event_rate_hz: float = 500.0,
              compare_pick_cache: bool = True) -> dict:
    """The committed BENCH_scale.json payload: one row per N (pick
    cache ON, with failover), plus a nocache arm per N for the
    before/after cliff curve."""
    import platform  # noqa: PLC0415

    sweep, nocache = [], []
    for n in nodes:
        print(f"== N={n} (pick cache on) ==", flush=True)
        row = measure_point(
            n, window_s=window_s, lease_concurrency=lease_concurrency,
            task_event_rate_hz=task_event_rate_hz)
        print(json.dumps({k: row[k] for k in
                          ("nodes", "leases_per_s",
                           "heartbeat_cpu_ms_per_s_per_100n",
                           "gcs_io_loop_duty_loaded", "failover_s")
                          if k in row}), flush=True)
        sweep.append(row)
        if compare_pick_cache:
            print(f"== N={n} (pick cache off) ==", flush=True)
            arm = measure_point(
                n, window_s=window_s,
                lease_concurrency=lease_concurrency,
                task_event_rate_hz=task_event_rate_hz,
                measure_failover=False, pick_cache=False)
            print(json.dumps({"nodes": n,
                              "leases_per_s": arm["leases_per_s"],
                              "sched_scanned_nodes_per_pick":
                              arm["sched_scanned_nodes_per_pick"]}),
                  flush=True)
            nocache.append(arm)
    return {
        "schema": "art-scale-sweep-v1",
        "generated_by": "benchmarks/scale_harness.py",
        "config": {
            "window_s": window_s,
            "lease_concurrency": lease_concurrency,
            "task_event_rate_hz": task_event_rate_hz,
            "ha_standbys": 1,
            "cpu_count": os.cpu_count(),
            "platform": platform.platform(),
        },
        "sweep": sweep,
        "cliff_fix": {
            "name": "sched_pick_cache",
            "flag": "ART_SCHED_PICK_CACHE",
            "description":
                "O(nodes) feasibility scan per SelectNode was the "
                "worst measured cliff: scanned-nodes-per-pick grows "
                "linearly with N while the availability view only "
                "moves on heartbeats.  The sticky pack-pick cache "
                "revalidates the previous winner (O(1)) and falls "
                "back to the full scan on miss; the nocache arm below "
                "is the same sweep with the cache disabled.",
            "nocache_sweep": nocache,
        },
    }


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--nodes", default="10,50,100,250,500",
                        help="comma-separated sweep sizes")
    parser.add_argument("--window", type=float, default=5.0,
                        help="seconds per measurement window")
    parser.add_argument("--concurrency", type=int, default=8,
                        help="open-loop lease clients")
    parser.add_argument("--event-rate", type=float, default=500.0,
                        help="aggregate task-events/s across stubs")
    parser.add_argument("--no-cache-arm", action="store_true",
                        help="skip the ART_SCHED_PICK_CACHE=0 arm")
    parser.add_argument("--json-out", default="",
                        help="write the sweep (BENCH_scale.json) here")
    args = parser.parse_args()
    nodes = [int(n) for n in args.nodes.split(",") if n]
    report = run_sweep(nodes, window_s=args.window,
                       lease_concurrency=args.concurrency,
                       task_event_rate_hz=args.event_rate,
                       compare_pick_cache=not args.no_cache_arm)
    report["generated_at"] = time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                           time.gmtime())
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(report, f, indent=1)
        print(f"wrote {args.json_out}", flush=True)
    else:
        json.dump(report, sys.stdout, indent=1)
        print(flush=True)


if __name__ == "__main__":
    main()
