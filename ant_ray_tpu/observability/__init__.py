"""Step-level TPU telemetry plane.

What task-level observability (timeline / tracing / insight) cannot
see is the structure *inside* a training step — the split that actually
determines TPU throughput: how long each step waited on data, on
host→HBM transfer, on compute, on collectives, and how much HBM it
held while doing so.  T3 (arXiv:2401.16677) motivates exactly this
fine-grained compute/collective attribution; the 100k+-GPU collective
paper (arXiv:2510.20171) shows cross-rank skew telemetry is what makes
pod-scale debugging tractable.  This package is that measurement
substrate:

* :class:`StepProfiler` (``step_profiler.py``) — per-step phase
  timings (data_wait / h2d / compute / collective), optional MFU
  against the detected TPU peak, absorbing the device-feed and
  collective-fusion stats streams as phases instead of parallel
  idioms.  Near-zero overhead (< 2 µs/step, benchmarked) and a cheap
  no-op outside a cluster — safe to leave in production train loops.
  (The LLM engine loop accounts for its own time instead: phase seconds
  and step / sync counters in ``LLMEngine.stats``, ``engine:<phase>``
  events in the jax profiler's trace, one ``llm:engine`` stage span per
  request — see ``llm/engine.py``.)
* ``device_stats.py`` — per-device HBM occupancy from
  ``jax.Device.memory_stats()`` (graceful ``None`` on CPU), published
  through the node agent and the GCS metrics table.
* on-demand XLA trace capture — ``POST /api/profile`` on the dashboard
  → node-agent RPC → ``jax.profiler.trace`` into the session dir,
  archive served by the existing log routes.
* Train integration — ``session.report()`` auto-attaches the latest
  step record; the controller aggregates across ranks into Prometheus
  gauges (step-time mean/p50/max, phase fractions, straggler ratio)
  and ``util/timeline.py`` merges step-phase slices as per-rank device
  rows into the chrome trace.
* :mod:`tracing_plane` (``tracing_plane.py``) — the request-level
  plane: W3C-traceparent-shaped contexts minted at every ingress and
  propagated through request metadata, per-process flight recorders
  (force-sampled error rings), the GCS span ring behind
  ``GET /api/trace/{id}``, and ``art_rpc_latency_s`` histograms with
  trace-id exemplars.  ``serve.run`` and ``JaxTrainer.fit`` leave their
  own START-UP on it as one trace of forced spans
  (``tracing_plane.staged_span``; `serve:run` / `train:fit` →
  `actor:create` → `worker:spawn` → `worker:boot` → `actor:init` →
  `llm:init` / `train:worker_init`).
* ``compile_watch.py`` — the process's one ``jax.monitoring`` listener
  (installed by ``jax_utils.import_jax()``): every compilation a forced
  `jit:compile` span with its program's name and the persistent cache's
  verdict, marked ``after_ready`` once the engine has completed a step.
"""

from ant_ray_tpu.observability import tracing_plane
from ant_ray_tpu.observability.device_stats import (
    device_memory_stats,
    device_stats_gauges,
)
from ant_ray_tpu.observability.step_profiler import StepProfiler, StepRecord
from ant_ray_tpu.observability.tracing_plane import (
    FlightRecorder,
    TraceContext,
)

__all__ = [
    "FlightRecorder",
    "StepProfiler",
    "StepRecord",
    "TraceContext",
    "device_memory_stats",
    "device_stats_gauges",
    "tracing_plane",
]
