"""One owner per chip (CPU only): a process may open the TPU backend
only if the scheduler granted it ``TPU > 0``; every other process of the
tree is pinned to the CPU backend.  Under this suite's whole-tree CPU
pin ``TPU`` is simulated — the daemon still assigns chip indices, and
every process reports ``cpu`` — so the environment an owner gets on a
real host is checked on the ledger that builds it."""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

import ant_ray_tpu as art
from ant_ray_tpu._private import jax_utils, serialization, services
from ant_ray_tpu._private.accelerators import tpu
from ant_ray_tpu.exceptions import TpuLeaseError

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SUB_HOST = {"TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
             "TPU_PROCESS_BOUNDS": "1,1,1"}


# ------------------------------------------- the environment of a worker

@pytest.mark.parametrize("host_chips,lease,expected", [
    (1, 1, {"JAX_PLATFORMS": "tpu"}),                  # the whole host:
    (4, 4, {"JAX_PLATFORMS": "tpu"}),                  # nothing to narrow
    (4, 1, {"JAX_PLATFORMS": "tpu", "TPU_VISIBLE_CHIPS": "0", **_SUB_HOST}),
    (4, 2, {"JAX_PLATFORMS": "tpu", "TPU_VISIBLE_CHIPS": "0,1",
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,2,1",
            "TPU_PROCESS_BOUNDS": "1,1,1"}),
    (4, 0, {"JAX_PLATFORMS": "cpu"}),                  # no lease: pinned
], ids=["1of1", "4of4", "1of4", "2of4", "none"])
def test_environment_of_a_worker_by_its_lease(host_chips, lease, expected):
    assert tpu.ChipLeases(host_chips, "tpu").grant("w", lease) == expected


def test_two_sub_host_owners_get_different_chips():
    leases = tpu.ChipLeases(4, "tpu")
    first, second = leases.grant("a", 1), leases.grant("b", 1)
    assert first["TPU_VISIBLE_CHIPS"] == "0"
    assert second["TPU_VISIBLE_CHIPS"] == "1"
    assert leases.held_by("a") == (0,) and leases.held_by("b") == (1,)


def test_whole_tree_pin_simulates_chips():
    """``platform="cpu"`` is the pin from outside: indices are still
    assigned, but the owner's environment opens no TPU."""
    leases = tpu.ChipLeases(4, "cpu")
    assert leases.grant("a", 1) == {"JAX_PLATFORMS": "cpu"}
    assert leases.grant("b", 1) == {"JAX_PLATFORMS": "cpu"}
    assert leases.held_by("a") != leases.held_by("b")


def test_chips_return_when_their_owner_is_released():
    leases = tpu.ChipLeases(2, "tpu")
    leases.grant("a", 1)
    leases.grant("b", 1)
    with pytest.raises(TpuLeaseError, match="free"):
        leases.grant("c", 1)
    leases.release("a")
    assert leases.grant("c", 1)["TPU_VISIBLE_CHIPS"] == "0"
    leases.release("never-held")                       # a no-op


@pytest.mark.parametrize("host_chips,lease", [(4, 0.5), (4, 3), (8, 5)])
def test_odd_chip_counts_are_refused(host_chips, lease):
    with pytest.raises(TpuLeaseError):
        tpu.ChipLeases(host_chips, "tpu").grant("w", lease)


@pytest.mark.parametrize("outside,expected", [
    ("cpu", "cpu"), ("tpu", "tpu"), ("tpu,cpu", "tpu"), (None, "tpu")])
def test_chip_platform_follows_the_pin_from_outside(monkeypatch, outside,
                                                    expected):
    if outside is None:
        monkeypatch.delenv("JAX_PLATFORMS")
    else:
        monkeypatch.setenv("JAX_PLATFORMS", outside)
    assert jax_utils.chip_platform() == expected
    assert jax_utils.cpu_pinned_env()["JAX_PLATFORMS"] == "cpu"


# ------------------------------------------------ what the device is

class _Device:
    platform = "tpu"

    def __init__(self, kind):
        self.device_kind = kind


def test_device_kind_keys_the_hardware_table():
    gen = tpu.device_generation(_Device("TPU v5 lite"))
    assert gen == "v5e"
    assert tpu.peak_bf16_tflops(gen) == 197.0
    assert tpu.hbm_gib_per_chip(gen) == 16
    with pytest.raises(ValueError, match="TPU v9"):
        tpu.device_generation(_Device("TPU v9"))
    with pytest.raises(ValueError, match="v9"):
        tpu.peak_bf16_tflops("v9")


# ---------------------------------------------------- in a live cluster

@pytest.fixture(scope="module")
def four_chip_node():
    art.init(num_cpus=4, num_tpus=4)
    yield None
    art.shutdown()


def _ledger() -> dict:
    from ant_ray_tpu.util import state

    node = next(n for n in state.list_nodes() if n.alive)
    return state._client_pool().get(node.address).call("DebugResources")


def _chips_by_pid() -> dict:
    return {w["pid"]: tuple(w["tpu_chips"])
            for w in _ledger()["workers"] if w["tpu_chips"]}


def _wait_all_chips_free() -> None:
    """Kills of an earlier test land asynchronously."""
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if art.available_resources().get("TPU") == 4.0 \
                and not _chips_by_pid():
            return
        time.sleep(0.1)
    raise AssertionError(f"chips still held: {_chips_by_pid()}")


@art.remote
class _Probe:
    def where(self):
        return os.getpid(), os.environ["JAX_PLATFORMS"]

    def block_on(self, seconds):
        @art.remote
        def nap(s):
            time.sleep(s)
            return s

        return art.get(nap.remote(seconds))


def test_daemon_assigns_chips_and_takes_them_back(four_chip_node):
    _wait_all_chips_free()
    owners = [_Probe.options(num_tpus=1).remote() for _ in range(2)]
    plain = _Probe.remote()
    pids = [art.get(a.where.remote(), timeout=60)[0] for a in owners]
    # Whole-tree pin: owners are started on the simulated platform too.
    assert _ledger()["chip_platform"] == "cpu"
    assert art.get(plain.where.remote(), timeout=60)[1] == "cpu"
    chips = _chips_by_pid()
    assert set(chips) == set(pids)                 # the plain actor: none
    assert chips[pids[0]] != chips[pids[1]]
    assert art.available_resources().get("TPU") == 2.0

    art.kill(owners[0])
    deadline = time.monotonic() + 30
    while pids[0] in _chips_by_pid() and time.monotonic() < deadline:
        time.sleep(0.1)
    assert set(_chips_by_pid()) == {pids[1]}
    again = _Probe.options(num_tpus=1).remote()
    pid = art.get(again.where.remote(), timeout=60)[0]
    assert _chips_by_pid()[pid] == chips[pids[0]]  # the index came back
    for a in (owners[1], plain, again):
        art.kill(a)


def test_owner_parked_in_get_keeps_its_chips(four_chip_node):
    """A worker blocked in get() gives its CPU back — not its chips: the
    process still has the device open."""
    _wait_all_chips_free()
    owner = _Probe.options(num_tpus=1, num_cpus=1).remote()
    art.get(owner.where.remote(), timeout=60)
    ref = owner.block_on.remote(2.0)
    seen = set()
    while not art.wait([ref], timeout=0.1)[0]:
        seen.add(art.available_resources().get("TPU", 0.0))
    assert art.get(ref) == 2.0
    assert seen == {3.0}
    art.kill(owner)


def test_plain_task_asking_for_tpu_is_refused(four_chip_node):
    @art.remote(num_tpus=1)
    def on_chip():
        return 1

    with pytest.raises(TpuLeaseError, match="actor"):
        on_chip.remote()


def test_use_tpu_workers_always_lease_chips(four_chip_node):
    from ant_ray_tpu.train import ScalingConfig

    # all of the host's chips, from the cluster's resource view …
    assert ScalingConfig(use_tpu=True).worker_resources()["TPU"] == 4.0
    # … or from the slice topology where one is named
    assert ScalingConfig(use_tpu=True, topology="2x2x2",
                         accelerator_type="TPU-V4"
                         ).worker_resources()["TPU"] == 4.0
    assert ScalingConfig(use_tpu=True, chips_per_worker=2
                         ).worker_resources()["TPU"] == 2.0
    assert "TPU" not in ScalingConfig().worker_resources()


def test_serve_replica_leases_what_its_options_ask(four_chip_node):
    from ant_ray_tpu import serve
    from ant_ray_tpu.llm.serve_llm import build_llm_deployment

    llm = build_llm_deployment("tiny", tensor_parallel_size=2).deployment
    assert llm.ray_actor_options["num_tpus"] == 2

    @serve.deployment(ray_actor_options={"num_tpus": 2})
    class Echo:
        def __call__(self, request):
            return os.getpid()

    _wait_all_chips_free()
    handle = serve.run(Echo.bind())
    try:
        pid = art.get(handle.remote({}), timeout=60)
        assert len(_chips_by_pid()[pid]) == 2
    finally:
        serve.shutdown()


# ------------------------------------------------- outside the cluster

def test_init_refuses_a_driver_that_holds_the_chip(monkeypatch):
    monkeypatch.setattr(jax_utils, "opened_platforms", lambda: ("tpu",))
    with pytest.raises(RuntimeError, match="holds the chip"):
        services.start_cluster(num_cpus=1, num_tpus=1)


def test_reading_a_jax_array_opens_no_backend(tmp_path):
    """A process that has not touched jax gets the host numpy array
    back — even with the TPU platform required and no chip, where
    opening a backend would raise."""
    import jax.numpy as jnp

    value = {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3)}
    blob = tmp_path / "value.bin"
    blob.write_bytes(serialization.serialize(value).to_payload())
    # … while a process whose backend is up gets a jax.Array on it.
    here = serialization.deserialize(serialization.serialize(value))["w"]
    assert type(here).__module__.startswith("jax")

    script = (
        "import sys, numpy as np\n"
        "from ant_ray_tpu._private import serialization as s\n"
        "from ant_ray_tpu._private.jax_utils import opened_platforms\n"
        f"data = open({str(blob)!r}, 'rb').read()\n"
        "w = s.deserialize(s.SerializedObject.from_payload(data))['w']\n"
        "assert type(w) is np.ndarray, type(w)\n"
        "assert w.tolist() == [[0, 1, 2], [3, 4, 5]]\n"
        "assert opened_platforms() == ()\n"
        "print('READ', 'jax' in sys.modules)\n")
    env = dict(os.environ, JAX_PLATFORMS="tpu", PYTHONPATH=_REPO)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == ["READ", "False"]


@pytest.mark.parametrize("from_outside", [False, True],
                         ids=["checkout", "variable"])
def test_compile_cache_has_one_place(tmp_path, from_outside):
    """JAX_COMPILATION_CACHE_DIR where set (nothing is set in code),
    else <checkout>/.jax_cache."""
    env = dict(os.environ, PYTHONPATH=_REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    expected = os.path.join(_REPO, ".jax_cache")
    if from_outside:
        expected = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    script = ("from ant_ray_tpu._private.jax_utils import import_jax\n"
              "print(import_jax().config.jax_compilation_cache_dir)\n")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == expected


def test_chip_smoke_fails_where_its_worker_reports_cpu():
    """Under the whole-tree CPU pin ``TPU`` is simulated, the Train
    worker reports ``cpu``, and chip_smoke.py fails at once with no
    result — while the suites that simulate TPU gangs still pass."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", ART_TPU_CHIPS_OVERRIDE="1")
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")], env=env,
        cwd=_REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert time.monotonic() - t0 < 120
    assert "runs on 'cpu'" in proc.stdout
    assert '"ok"' not in proc.stdout


def test_head_frozen_past_the_death_timeout_kills_no_node(four_chip_node):
    """Opening a TPU freezes EVERY process of a sandboxed VM for ~6 s
    (measured on the v5e machine), longer than the heartbeat death
    timeout.  A head that could not run must not judge nodes by the
    beats it could not read: frozen alone here (SIGSTOP), it wakes to
    find its node alive."""
    import signal

    from ant_ray_tpu._private.config import global_config
    from ant_ray_tpu._private.worker import global_worker

    cfg = global_config()
    death_timeout = cfg.heartbeat_period_s * cfg.num_heartbeats_timeout
    head = next(p for p in global_worker.runtime._owned_processes
                if "ant_ray_tpu._private.gcs" in p.args)
    os.kill(head.pid, signal.SIGSTOP)
    try:
        time.sleep(death_timeout + 1.5)
    finally:
        os.kill(head.pid, signal.SIGCONT)
    time.sleep(4 * cfg.heartbeat_period_s)         # a few health ticks
    assert [n["Alive"] for n in art.nodes()] == [True]

    @art.remote
    def still_here():
        return "yes"

    assert art.get(still_here.remote(), timeout=60) == "yes"
