"""TPU accelerator detection and topology metadata.

Parity target: the reference's TPUAcceleratorManager
(ref: python/ray/_private/accelerators/tpu.py:267 — GKE/GCE metadata
detection :105, TPU_VISIBLE_CHIPS :36, valid types v2–v6e :65, topology
tables :88, pod-type inference :151, chips-per-host rule :184).
Redesigned: detection prefers cheap environment/sysfs signals over
importing jax (daemon processes must stay light); the GCE metadata server
is consulted behind a short timeout when env vars are absent (plain GCE
TPU-VMs set no TPU_* env vars — only GKE does).  Host-level detection
never touches jax: the launcher and the daemons hold no chip, and a
process that opens the TPU backend takes it from the worker that leased
it.  Only the OWNING process asks the device what it is
(:func:`device_generation`).
"""

from __future__ import annotations

import glob
import logging
import os
import threading
import time
import urllib.error
import urllib.request

from ant_ray_tpu._private.config import global_config
from ant_ray_tpu._private.jax_utils import PLATFORM_ENV
from ant_ray_tpu.exceptions import TpuLeaseError

logger = logging.getLogger(__name__)

# Accelerator-type names (resource label values), v2 → v6e.
VALID_TPU_TYPES = (
    "TPU-V2", "TPU-V3", "TPU-V4", "TPU-V5E", "TPU-V5P", "TPU-V6E",
)

# generation → (max chips on a single-host node, peak bf16 TFLOP/s per
# chip, HBM GiB per chip).  v5e/v6e are the 8-chip single-host
# generations (ref: SINGLE_HOST_8_CHIPS_TPU_TYPES, tpu.py:59); all
# others host 4 chips.
TPU_HARDWARE_TABLE: dict[str, tuple[int, float, float]] = {
    "v2": (4, 45.0, 8),
    "v3": (4, 123.0, 16),
    "v4": (4, 275.0, 32),
    "v5e": (8, 197.0, 16),
    "v5p": (4, 459.0, 95),
    "v6e": (8, 918.0, 32),
}

_EIGHT_CHIP_GENERATIONS = ("v5e", "v6e")

# ``jax.Device.device_kind`` → generation, for the process that owns the
# chip (the v5e reports "TPU v5 lite").
_DEVICE_KIND_GENERATION = {
    "TPU v2": "v2", "TPU v3": "v3", "TPU v4": "v4",
    "TPU v5 lite": "v5e", "TPU v5": "v5p", "TPU v6 lite": "v6e",
}

# GCE instance-metadata server (ref: GCE_TPU_ACCELERATOR_ENDPOINT,
# tpu.py:27-34).  The host is overridable so tests can stand up a local
# mock; real TPU-VMs resolve metadata.google.internal instantly and
# everything else fails DNS fast.
_METADATA_ATTRIBUTES_URL = (
    "http://metadata.google.internal/computeMetadata/v1/instance/attributes/"
)
_METADATA_KEY_ACCELERATOR_TYPE = "accelerator-type"
_METADATA_KEY_INSTANCE_ID = "instance-id"
_METADATA_KEY_WORKER_ID = "agent-worker-number"
_METADATA_KEY_TPU_ENV = "tpu-env"


def _metadata_base_url() -> str:
    return os.environ.get("ART_GCE_METADATA_URL", _METADATA_ATTRIBUTES_URL)


def _sysfs_chip_count() -> int:
    """TPU devices visible in /dev — the cheap "am I a TPU-VM" signal
    that gates metadata-server lookups (CPU hosts must never pay a DNS
    stall in daemon startup)."""
    vfio = glob.glob("/dev/vfio/*")
    accel = glob.glob("/dev/accel*")
    return (len([p for p in vfio if os.path.basename(p) != "vfio"])
            or len(accel))


def _may_query_metadata() -> bool:
    if os.environ.get("ART_GCE_METADATA_URL"):
        return True  # test mock is wired up
    return _sysfs_chip_count() > 0


# Successful lookups (incl. genuine 404 "attribute absent") are cached;
# transient failures are NOT — a metadata server that is briefly slow at
# boot must not pin None for the process lifetime.  After a failure the
# server is considered unreachable for a grace window so the remaining
# keys don't each pay the stall.
_metadata_cache: dict[str, str | None] = {}
_metadata_backoff_until = 0.0
_METADATA_BACKOFF_S = 30.0
_METADATA_DEADLINE_S = 1.0


def _fetch_metadata_once(url: str) -> tuple[bool, str | None]:
    """(ok, value) — run in a worker thread; ok=False means transient."""
    req = urllib.request.Request(url, headers={"Metadata-Flavor": "Google"})
    try:
        with urllib.request.urlopen(
                req, timeout=_METADATA_DEADLINE_S) as resp:
            return True, (resp.read().decode() or None)
    except urllib.error.HTTPError as e:
        if e.code == 404:
            return True, None  # attribute genuinely absent — cacheable
        return False, None
    except (urllib.error.URLError, OSError, ValueError) as e:
        logger.debug("GCE metadata unavailable: %s", e)
        return False, None


def get_tpu_metadata(key: str) -> str | None:
    """One instance-metadata attribute, or None.  The whole lookup —
    including DNS resolution, which urlopen's timeout does not bound —
    runs in a daemon thread joined with a hard deadline, so non-GCE
    hosts (even VFIO-bearing ones with a dead resolver) can't stall
    daemon startup."""
    global _metadata_backoff_until
    if os.environ.get("ART_DISABLE_GCE_METADATA") or \
            not _may_query_metadata():
        return None
    if key in _metadata_cache:
        return _metadata_cache[key]
    if time.monotonic() < _metadata_backoff_until:
        return None
    ok, value = _fetch_metadata_deadline(_metadata_base_url() + key)
    if not ok:
        _metadata_backoff_until = time.monotonic() + _METADATA_BACKOFF_S
        return None
    _metadata_cache[key] = value
    return value


def _fetch_metadata_deadline(url: str) -> tuple[bool, str | None]:
    """_fetch_metadata_once in a daemon thread joined with a hard
    deadline: DNS resolution is NOT bounded by urlopen's timeout, so a
    dead resolver would otherwise hang the caller for minutes — fatal
    for the preemption watcher, whose whole job is reacting within an
    announced grace window."""
    result: list[tuple[bool, str | None]] = []
    t = threading.Thread(
        target=lambda: result.append(_fetch_metadata_once(url)),
        daemon=True)
    t.start()
    t.join(_METADATA_DEADLINE_S + 0.3)
    if not result or not result[0][0]:
        return False, None
    return result[0]


def _metadata_cache_clear() -> None:
    global _metadata_backoff_until
    _metadata_cache.clear()
    _metadata_backoff_until = 0.0


get_tpu_metadata.cache_clear = _metadata_cache_clear  # test hook


def normalize_generation(name: str) -> str:
    """"v5litepod-16" / "TPU-V5E" / "v5e" → "v5e"."""
    name = name.lower().replace("tpu-", "")
    prefix = name.split("-")[0]
    return {"v5litepod": "v5e"}.get(prefix, prefix)


def topology_chip_count(topology: str) -> int:
    """"AxB" / "AxBxC" slice topology → total chips."""
    dims = [int(d) for d in topology.lower().split("x")]
    count = 1
    for d in dims:
        count *= d
    return count


def chips_per_host(topology: str, generation: str) -> int:
    """Chips per VM in a slice (ref rule: get_chips_per_host, tpu.py:184):
    multi-host slices pack 4 chips per VM on every generation; v5e/v6e
    slices of ≤8 chips fit on one VM holding all of them."""
    total = topology_chip_count(topology)
    if total <= 8 and normalize_generation(generation) in \
            _EIGHT_CHIP_GENERATIONS:
        return total
    return 4


def hosts_in_slice(topology: str, generation: str) -> int:
    total = topology_chip_count(topology)
    per_host = chips_per_host(topology, generation)
    return max(1, (total + per_host - 1) // per_host)


def infer_pod_type(topology: str, generation: str) -> str:
    """("4x4", "v5e") → "v5e-16" (ref: infer_tpu_pod_type_from_topology)."""
    return (f"{normalize_generation(generation)}-"
            f"{topology_chip_count(topology)}")


_generation_memo: list = []  # [gen] once positively detected


def detect_generation() -> str | None:
    """TPU generation of this host ("v5e", ...), or None.  Order: explicit
    override → GKE env var → GCE metadata server.  Only POSITIVE results
    memoize — a transiently-unreachable metadata server must not pin
    None for the process lifetime (the metadata layer has its own
    short backoff)."""
    if _generation_memo:
        return _generation_memo[0]
    env = os.environ.get("ART_TPU_GENERATION")
    accel_type = env or os.environ.get("TPU_ACCELERATOR_TYPE")  # GKE
    if not accel_type:
        accel_type = get_tpu_metadata(_METADATA_KEY_ACCELERATOR_TYPE)
    if accel_type:  # e.g. "v5litepod-16"
        gen = normalize_generation(accel_type)
        _generation_memo.append(gen)
        return gen
    return None


def _detect_generation_cache_clear() -> None:
    _generation_memo.clear()


detect_generation.cache_clear = _detect_generation_cache_clear  # test hook


def num_tpu_chips() -> int:
    """Chips attached to this host, from the visible-chips variable or
    the driver's /dev nodes.  Never asks jax: this runs in the launcher
    and the daemons, which must not open the chip."""
    override = global_config().tpu_chips_override
    if override >= 0:
        return override
    visible = os.environ.get("TPU_VISIBLE_CHIPS")
    if visible:
        return len([c for c in visible.split(",") if c.strip()])
    return _sysfs_chip_count()  # vfio/accel devices from the TPU driver


def current_pod_name() -> str | None:
    """Name of the TPU slice this host belongs to: GKE TPU_NAME env, else
    the GCE instance id (ref: get_current_node_tpu_name, tpu.py:453)."""
    name = os.environ.get("TPU_NAME")
    if name:
        return name
    return get_tpu_metadata(_METADATA_KEY_INSTANCE_ID)


def current_worker_id() -> int:
    """This host's index within its slice: GKE TPU_WORKER_ID env, else the
    GCE agent-worker-number (ref: get_current_node_tpu_worker_id)."""
    wid = os.environ.get("TPU_WORKER_ID")
    if not wid:
        wid = get_tpu_metadata(_METADATA_KEY_WORKER_ID)
    try:
        return int(wid) if wid else 0
    except ValueError:
        return 0


def current_topology() -> str | None:
    topology = os.environ.get("TPU_TOPOLOGY")
    if topology:
        return topology
    # Plain GCE VMs carry the slice env in the `tpu-env` metadata blob
    # (lines of KEY: 'value' pairs, ref: GCE_TPU_ENV_KEY usage).
    blob = get_tpu_metadata(_METADATA_KEY_TPU_ENV)
    if blob:
        for line in blob.splitlines():
            key, _, value = line.partition(":")
            if key.strip() == "TOPOLOGY":
                return value.strip().strip("'\"") or None
    return None


def device_generation(device) -> str:
    """Generation of a ``jax.Device`` the calling process owns, keyed by
    its ``device_kind``.  A device that is not in the table is an error,
    not a v5e."""
    gen = _DEVICE_KIND_GENERATION.get(device.device_kind)
    if gen is None:
        raise ValueError(
            f"device kind {device.device_kind!r} (platform "
            f"{device.platform!r}) is not in TPU_HARDWARE_TABLE; known "
            f"kinds: {sorted(_DEVICE_KIND_GENERATION)}")
    return gen


def _hardware(generation: str) -> tuple[int, float, float]:
    gen = normalize_generation(generation)
    if gen not in TPU_HARDWARE_TABLE:
        raise ValueError(
            f"TPU generation {generation!r} is not in TPU_HARDWARE_TABLE "
            f"({sorted(TPU_HARDWARE_TABLE)})")
    return TPU_HARDWARE_TABLE[gen]


def peak_bf16_tflops(generation: str) -> float:
    return _hardware(generation)[1]


def hbm_gib_per_chip(generation: str) -> float:
    return _hardware(generation)[2]


# Chips a worker was granted → the bounds of the sub-host mesh libtpu
# builds over them (a v5e/v6e host is 2x2 or 2x4 chips).
_PROCESS_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1"}


class ChipLeases:
    """Which worker owns which chip of this host — the node daemon's
    ledger of chip indices, kept the way it keeps free resources.

    :meth:`grant` returns the platform environment a worker is spawned
    with, from the ``TPU`` it leased: none → pinned to the CPU backend;
    some → the chip platform *required* (``tpu``: a failed init raises
    instead of falling to the CPU), and, where it was granted fewer
    chips than the host has, the chip indices assigned to it with the
    matching per-process bounds.  ``platform="cpu"`` is the whole-tree
    pin from outside: indices are still assigned, but no process opens
    a TPU."""

    def __init__(self, host_chips: int, platform: str):
        self.platform = platform
        self._host_chips = host_chips
        self._free = list(range(host_chips))
        self._held: dict = {}

    def grant(self, holder, tpu: float) -> dict:
        if not tpu:
            return {PLATFORM_ENV: "cpu"}
        count = int(tpu)
        if count != tpu:
            raise TpuLeaseError(
                f"TPU={tpu}: a chip belongs to one process, so a lease "
                "is a whole number of chips")
        if count > len(self._free):
            raise TpuLeaseError(
                f"TPU={count} requested but only chips {self._free} of "
                f"{self._host_chips} are free on this host")
        sub_host = count < self._host_chips and self.platform == "tpu"
        if sub_host and count not in _PROCESS_BOUNDS:
            raise TpuLeaseError(
                f"TPU={count} of {self._host_chips}: a sub-host lease is "
                f"{sorted(_PROCESS_BOUNDS)} chips or the whole host")
        chips = tuple(self._free[:count])
        del self._free[:count]
        self._held[holder] = chips
        env = {PLATFORM_ENV: self.platform}
        if sub_host:
            env["TPU_VISIBLE_CHIPS"] = ",".join(map(str, chips))
            env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = _PROCESS_BOUNDS[count]
            env["TPU_PROCESS_BOUNDS"] = "1,1,1"
        return env

    def release(self, holder) -> None:
        """Return a dead worker's chips (no-op for one that held none)."""
        self._free = sorted([*self._free, *self._held.pop(holder, ())])

    def held_by(self, holder) -> tuple:
        return self._held.get(holder, ())


# GCE/TPU maintenance-event surface (ref: the instance metadata
# `maintenance-event` attribute — TPU VMs see "TERMINATE_ON_HOST_
# MAINTENANCE" minutes before an announced preemption; the reference
# consumes the equivalent via the TPU maintenance-event API).
_METADATA_KEY_MAINTENANCE = "maintenance-event"
_MAINTENANCE_NONE = "NONE"


def maintenance_watch_possible() -> bool:
    """Whether ANY notice source could ever fire on this host — the
    daemon's watcher exits immediately when none can (CPU test rigs
    must not pay a poll thread per node forever)."""
    if global_config().testing_preemption_notice:
        return True
    return not os.environ.get("ART_DISABLE_GCE_METADATA") and \
        _may_query_metadata()


def maintenance_notice() -> "tuple[str, float] | None":
    """A pending preemption/maintenance notice for THIS host, or None.

    Returns ``(reason, deadline_s)`` — ``deadline_s`` is the announced
    grace (seconds from now; 0.0 = none announced).  Sources, in order:

    * ``testing_preemption_notice`` (chaos harness): a file path whose
      existence IS the notice; its first line may carry
      ``"<deadline_s> <reason...>"``.
    * The GCE ``maintenance-event`` metadata attribute (un-memoized —
      unlike the identity attributes, this one CHANGES over the
      instance lifetime, so the positive-result cache must not pin it).
    """
    notice_path = global_config().testing_preemption_notice
    if notice_path:
        try:
            with open(notice_path) as f:
                first = f.readline().split(None, 1)
            deadline = float(first[0]) if first else 0.0
            reason = (first[1].strip() if len(first) > 1
                      else "testing preemption notice")
            return reason, deadline
        except FileNotFoundError:
            return None
        except (OSError, ValueError):
            return "testing preemption notice", 0.0
    global _metadata_backoff_until
    if os.environ.get("ART_DISABLE_GCE_METADATA") or \
            not _may_query_metadata():
        return None
    if time.monotonic() < _metadata_backoff_until:
        return None
    ok, value = _fetch_metadata_deadline(
        _metadata_base_url() + _METADATA_KEY_MAINTENANCE)
    if not ok:
        # Same backoff as get_tpu_metadata: an unreachable metadata
        # server must not cost the 1 Hz preemption watcher a blocking
        # probe (and a stuck thread) per poll forever.
        _metadata_backoff_until = time.monotonic() + _METADATA_BACKOFF_S
        return None
    if value is None or value.strip() in ("", _MAINTENANCE_NONE):
        return None
    return value.strip(), 0.0


def node_labels() -> dict[str, str]:
    """Labels a node daemon advertises for topology-aware placement
    (ref: TPU-<pod>-head resource + slice labels, util/tpu.py:52)."""
    labels: dict[str, str] = {}
    gen = detect_generation()
    if gen:
        labels["tpu-generation"] = gen
    pod = current_pod_name()
    if pod:
        labels["tpu-pod-name"] = pod
        labels["tpu-worker-id"] = str(current_worker_id())
    topology = current_topology()
    if topology:
        labels["tpu-topology"] = topology
        if gen:
            labels["tpu-pod-type"] = infer_pod_type(topology, gen)
    return labels


def slice_groups(pod_names) -> list:
    """Group ranks by the TPU slice they sit on: ranks whose nodes
    advertise the same ``tpu-pod-name`` label share ICI; distinct pod
    names only reach each other over DCN.  Input is one pod name per
    rank (``None``/"" ranks are treated as a standalone slice each —
    a CPU stand-in host is its own 'slice').  Returns rank tuples,
    ordered by each slice's lowest rank, for
    ``SliceTopology.from_labels``."""
    by_pod: dict = {}
    for rank, pod in enumerate(pod_names):
        key = pod if pod else f"_solo_{rank}"
        by_pod.setdefault(key, []).append(rank)
    return [tuple(ranks)
            for ranks in sorted(by_pod.values(), key=lambda r: r[0])]
