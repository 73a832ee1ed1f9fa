"""Cluster process bootstrap (ref: python/ray/_private/services.py +
node.py — start/stop of gcs_server, raylet, workers)."""

from __future__ import annotations

import json
import logging
import os
import subprocess
import sys
import time
import uuid

from ant_ray_tpu._private import jax_utils
from ant_ray_tpu._private.protocol import ClientPool, find_free_port

logger = logging.getLogger(__name__)

_READY_TIMEOUT_S = 30.0


def _wait_ready(proc: subprocess.Popen, marker: str) -> str:
    """Read the child's stdout until `<marker> <address>` appears."""
    deadline = time.monotonic() + _READY_TIMEOUT_S
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            raise RuntimeError(
                f"process exited (code={proc.poll()}) before ready")
        text = line.decode(errors="replace").strip()
        if text.startswith(marker):
            return text.split(" ", 1)[1]
    raise RuntimeError(f"timed out waiting for {marker}")


def start_gcs(session_dir: str,
              port: int | None = None,
              ha_replica_id: str | None = None
              ) -> tuple[subprocess.Popen, str]:
    """Start (or restart — same port + store file) the GCS head.

    Tables persist to ``<session_dir>/gcs_store.db`` so a restarted head
    resumes the cluster (ref: Redis-backed GCS fault tolerance,
    src/ray/gcs/store_client/redis_store_client.h).  With
    ``ha_replica_id`` the process joins the replicated control plane
    over that same store: the lease elects a leader, the rest run as
    warm standbys (follower reads + NotLeader redirects)."""
    port = port or find_free_port()
    store = os.path.join(session_dir, "gcs_store.db")
    cmd = [sys.executable, "-m", "ant_ray_tpu._private.gcs",
           "--port", str(port), "--store", store,
           "--export-dir", os.path.join(session_dir, "export_events"),
           "--monitor-pid", str(os.getpid())]
    if ha_replica_id:
        cmd += ["--ha-replica-id", ha_replica_id]
    proc = subprocess.Popen(
        cmd,
        stdout=subprocess.PIPE, stderr=_log_file(session_dir, "gcs.err"),
        env=jax_utils.cpu_pinned_env(), start_new_session=True)
    address = _wait_ready(proc, "GCS_READY")
    return proc, address


def start_node(gcs_address: str, resources: dict, session_dir: str,
               labels: dict | None = None) -> tuple[subprocess.Popen, str]:
    proc = subprocess.Popen(
        [sys.executable, "-m", "ant_ray_tpu._private.node_daemon",
         "--gcs-address", gcs_address,
         "--resources", json.dumps(resources),
         "--session-dir", session_dir,
         "--labels", json.dumps(labels or {}),
         "--chip-platform", jax_utils.chip_platform(),
         "--monitor-pid", str(os.getpid())],
        stdout=subprocess.PIPE, stderr=_log_file(session_dir, "noded.err"),
        env=jax_utils.cpu_pinned_env(), start_new_session=True)
    address = _wait_ready(proc, "NODED_READY")
    return proc, address


def _log_file(session_dir: str, name: str):
    log_dir = os.path.join(session_dir, "logs")
    os.makedirs(log_dir, exist_ok=True)
    return open(os.path.join(log_dir, name), "ab")


def default_resources(num_cpus: int | None, num_tpus: int | None,
                      resources: dict | None) -> dict:
    """A node's resources.  The chip count comes from ``num_tpus=``, the
    visible-chips variable or the driver's /dev nodes — never from jax:
    this runs in the launcher, which must not open the chip."""
    out = dict(resources or {})
    out["CPU"] = float(num_cpus if num_cpus is not None
                       else (os.cpu_count() or 1))
    if num_tpus is not None:
        out["TPU"] = float(num_tpus)
    else:
        from ant_ray_tpu._private.accelerators import tpu  # noqa: PLC0415

        detected = tpu.num_tpu_chips()
        if detected:
            out["TPU"] = float(detected)
    return out


def new_session_dir() -> str:
    session_dir = os.path.join(
        "/tmp", f"art_session_{uuid.uuid4().hex[:10]}")
    os.makedirs(os.path.join(session_dir, "logs"), exist_ok=True)
    return session_dir


def start_dashboard(gcs_address: str, session_dir: str
                    ) -> tuple[subprocess.Popen, str]:
    proc = subprocess.Popen(
        [sys.executable, "-m", "ant_ray_tpu._private.dashboard",
         "--gcs-address", gcs_address,
         "--session-dir", session_dir,
         "--monitor-pid", str(os.getpid())],
        stdout=subprocess.PIPE, stderr=_log_file(session_dir, "dash.err"),
        env=jax_utils.cpu_pinned_env(), start_new_session=True)
    url = _wait_ready(proc, "DASH_READY")
    return proc, url


def start_cluster(num_cpus: int | None = None, num_tpus: int | None = None,
                  resources: dict | None = None,
                  include_dashboard: bool | None = None) -> dict:
    """Start head (GCS) + one node daemon (+ dashboard); returns
    addresses + procs."""
    from ant_ray_tpu._private.config import global_config  # noqa: PLC0415

    node_resources = default_resources(num_cpus, num_tpus, resources)
    if node_resources.get("TPU") and \
            "tpu" in jax_utils.opened_platforms():
        raise RuntimeError(
            "this process has already opened the TPU backend and holds "
            "the chip, so no worker that leases TPU could open it: a "
            "chip belongs to one process.  Call init() before touching "
            "jax, pin this driver with JAX_PLATFORMS=cpu, or start the "
            "cluster with num_tpus=0 to keep the chip in the driver.")
    session_dir = new_session_dir()
    gcs_proc, gcs_address = start_gcs(session_dir)
    procs = [gcs_proc]
    try:
        node_proc, node_address = start_node(
            gcs_address, node_resources, session_dir)
        procs.insert(0, node_proc)
        dashboard_url = ""
        want_dashboard = (include_dashboard if include_dashboard is not None
                          else global_config().include_dashboard)
        if want_dashboard:
            try:
                import aiohttp  # noqa: F401, PLC0415
            except ImportError:
                logger.warning("aiohttp not installed; dashboard (state "
                               "API, /metrics, job server) disabled")
                want_dashboard = False
        if want_dashboard:
            try:
                dash_proc, dashboard_url = start_dashboard(
                    gcs_address, session_dir)
            except Exception as e:  # noqa: BLE001 — dashboard is optional
                logger.warning("dashboard failed to start: %s", e)
            else:
                procs.insert(0, dash_proc)
                # Publish for late-joining drivers / the jobs SDK.
                pool = ClientPool()
                try:
                    pool.get(gcs_address).call("KVPut", {
                        "key": "dashboard_url",
                        "value": dashboard_url.encode()}, retries=3)
                finally:
                    pool.close_all()
    except Exception:
        stop_processes(procs)
        raise
    store_dir = _store_dir_of(node_address)
    return {
        "gcs_address": gcs_address,
        "node_address": node_address,
        "store_dir": store_dir,
        "session_dir": session_dir,
        "dashboard_url": dashboard_url,
        "processes": procs,
    }


def _store_dir_of(node_address: str) -> str:
    pool = ClientPool()
    try:
        info = pool.get(node_address).call("GetNodeInfo", retries=3)
        return info.object_store_dir
    finally:
        pool.close_all()


def find_local_node(gcs_address: str) -> tuple[str, str]:
    """Pick a node for a connecting driver (first alive node)."""
    pool = ClientPool()
    try:
        nodes = pool.get(gcs_address).call("GetAllNodes", retries=5)
        for info in nodes.values():
            if info.alive:
                return info.address, info.object_store_dir
        raise RuntimeError("no alive nodes in cluster")
    finally:
        pool.close_all()


def stop_processes(procs: list) -> None:
    for proc in procs:
        if proc.poll() is None:
            proc.terminate()
    deadline = time.monotonic() + 15
    for proc in procs:
        remaining = max(0.1, deadline - time.monotonic())
        try:
            proc.wait(timeout=remaining)
        except subprocess.TimeoutExpired:
            proc.kill()
