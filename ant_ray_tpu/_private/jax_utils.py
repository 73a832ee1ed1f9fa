"""Central jax import point, and the rule for who may open the chip.

A TPU chip belongs to one process at a time, and this system is a tree
of processes (driver, GCS, node daemon, dashboard, agent, pooled and
actor workers).  One rule decides the owner: **a process may open the
TPU backend only if the scheduler granted it ``TPU > 0``.**  The node
daemon starts that worker with :data:`PLATFORM_ENV` set to ``tpu`` —
*required*, so a failed init raises instead of computing on the CPU —
and the launcher starts every other process of the tree with it set to
``cpu`` before the process can import jax (:func:`cpu_pinned_env`).

The one way onto the CPU for an owner is the whole-tree pin set from
OUTSIDE (``JAX_PLATFORMS=cpu`` in the launcher's own environment, as
the tests' conftest does).  ``TPU`` resources are then simulated: no
process opens a TPU, owners included, and whatever reports a device
reports ``cpu`` (:func:`chip_platform`).

Every module of the package imports jax through :func:`import_jax`,
the one place that applies the launcher's platform, places the
persistent compile cache and registers the process's one compilation
listener (``observability/compile_watch.py``).
"""

from __future__ import annotations

import contextlib
import os
import sys

PLATFORM_ENV = "JAX_PLATFORMS"
_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

_configured = False


def chip_platform() -> str:
    """The platform a process that leased ``TPU`` is started with, as
    the launcher of a node decides it from its own environment: ``tpu``
    — or ``cpu`` under the whole-tree pin from outside."""
    pinned = os.environ.get(PLATFORM_ENV, "").strip().lower() == "cpu"
    return "cpu" if pinned else "tpu"


def cpu_pinned_env() -> dict:
    """Environment for a process of the tree that holds no chip."""
    env = os.environ.copy()
    env[PLATFORM_ENV] = "cpu"
    return env


def opened_platforms() -> tuple:
    """Platforms whose backend THIS process has already opened — empty
    when jax is not imported or no backend was touched.  Never opens
    one."""
    if "jax" not in sys.modules:
        return ()
    from jax._src import xla_bridge  # noqa: PLC0415

    return tuple(xla_bridge._backends)


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache`` — a fixed path, because the path is
    part of the cache key: a directory that moves never hits."""
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(checkout, ".jax_cache")


def import_jax():
    global _configured
    import jax  # noqa: PLC0415

    if not _configured:
        platform = os.environ.get(PLATFORM_ENV)
        if platform and jax.config.jax_platforms != platform:
            # The variable was set after jax was imported (jax reads it
            # at import); too late once a backend is open.
            opened = opened_platforms()
            if opened:
                raise RuntimeError(
                    f"{PLATFORM_ENV}={platform} cannot be applied: this "
                    f"process already opened the {list(opened)} backend")
            jax.config.update("jax_platforms", platform)
        if not os.environ.get(_CACHE_ENV):
            # Where the variable is set jax reads it itself and nothing
            # is set in code; children inherit the variable.
            jax.config.update("jax_compilation_cache_dir",
                              default_cache_dir())
        # Every compilation of this process a `jit:compile` span.
        from ant_ray_tpu.observability import compile_watch  # noqa: PLC0415

        compile_watch.install(jax)
        _configured = True
    return jax


def trace_annotation(name: str):
    """A host event ``name`` in the jax profiler's trace, on the device
    events' clock, for code that does not otherwise need jax.  Only a
    process that imported jax can be taking such a trace, so elsewhere
    this is a null context; with no trace running it costs a flag
    test."""
    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(name)


def require_tpu(who: str):
    """The chip path has no fallback: ``who`` leased a chip, so outside
    the whole-tree CPU pin its default device must be a TPU.  Returns
    ``jax.devices()[0]``."""
    jax = import_jax()
    device = jax.devices()[0]
    if device.platform != chip_platform():
        raise RuntimeError(
            f"{who} leased a TPU chip but jax runs on "
            f"{device.platform!r} ({device.device_kind}); refusing to "
            f"run on another device ({PLATFORM_ENV}="
            f"{os.environ.get(PLATFORM_ENV)!r})")
    return device
