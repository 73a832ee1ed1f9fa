"""Test bootstrap: pin the WHOLE process tree to the CPU backend, on a
virtual 8-device mesh, before any jax import — so sharding/collective
tests run without TPU hardware.

``JAX_PLATFORMS=cpu`` set here, from outside the launcher, is the
whole-tree pin of ``_private/jax_utils.py``: every process the tests
start inherits it, ``TPU`` resources are simulated (no process opens a
TPU, owners included), and whatever reports a device reports ``cpu``.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# Spawned daemons/workers must never consult the GCE metadata server
# (tests mock it explicitly where needed via ART_GCE_METADATA_URL).
os.environ.setdefault("ART_DISABLE_GCE_METADATA", "1")
# Persistent XLA compile cache, shared by every process of every run:
# worker subprocesses re-jit the same tiny programs constantly, and
# those compiles dominate suite time.  Its place is import_jax()'s rule:
# JAX_COMPILATION_CACHE_DIR where set, else <checkout>/.jax_cache.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.3")
# Dashboard boot costs ~0.7s per cluster; most tests never touch it.
# Suites that DO exercise it (test_ops) re-enable it via
# art.init(_system_config={"include_dashboard": True}) or
# ART_INCLUDE_DASHBOARD=1.
os.environ.setdefault("ART_INCLUDE_DASHBOARD", "0")
# Same for the per-node agent process (runtime-env builds fall back
# in-process); test_node_agent re-enables it explicitly.
os.environ.setdefault("ART_ENABLE_NODE_AGENT", "0")

from ant_ray_tpu._private.jax_utils import import_jax  # noqa: E402

import_jax()

import pytest  # noqa: E402

import ant_ray_tpu as art  # noqa: E402

# Chaos-harness fixture (util/chaos.py): importing it into conftest
# registers `chaos_schedule` for the whole suite.
from ant_ray_tpu.util.chaos import chaos_schedule  # noqa: E402, F401


@pytest.fixture
def shutdown_only():
    """Ensure the cluster from the test is torn down (ref: conftest.py:513)."""
    yield None
    art.shutdown()


@pytest.fixture
def local_mode():
    art.init(local_mode=True)
    yield None
    art.shutdown()


@pytest.fixture
def start_cluster():
    art.init(num_cpus=4)
    yield None
    art.shutdown()
