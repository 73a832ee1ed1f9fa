"""Benchmark: Llama training-step MFU on the local accelerator.

Measures a full jitted train step (loss + grad + adam) on a Llama-family
config that fits the chip, and reports MFU against the north-star
baseline (BASELINE.md: Llama-3-8B ≥ 40% MFU on v5e — here normalized
per-chip: achieved_flops / peak_bf16_flops, vs_baseline = mfu / 0.40).

This process owns the chip: it is one process, it requires a TPU, and
there is no fallback — no chip means one error line on stderr and a
non-zero exit, at once, with no metric printed.  HBM OOM falls back
through remat policies and then smaller batch; a number that is printed
always names the device it was measured on.

Prints exactly one JSON line:
    {"metric": ..., "value": ..., "unit": ..., "vs_baseline": ...}

The CPU-side guards (collective fusion ratio, step-profiler and lint
budgets, the BENCH_control.json regression check) run from
benchmarks/microbench.py on the CPU rig, not here.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "OOM")


def _is_oom(error: Exception) -> bool:
    return any(marker in repr(error) for marker in _OOM_MARKERS)


def measure(remat: str, batch_scale: float, *, config_key: str | None =
            None, seq_override: int | None = None, base_batch: int = 8,
            n_steps: int = 10):
    from ant_ray_tpu._private.accelerators import tpu as tpu_accel
    from ant_ray_tpu._private.jax_utils import import_jax
    from ant_ray_tpu.models import llama

    jax = import_jax()
    import jax.numpy as jnp
    import optax

    device = jax.devices()[0]
    gen = tpu_accel.device_generation(device)   # raises off the table
    config = llama.CONFIGS[config_key or "llama-400m"]
    batch = max(1, int(base_batch * batch_scale))
    seq = seq_override or 2048
    peak_flops = tpu_accel.peak_bf16_tflops(gen) * 1e12
    metric = (f"llama_{config_key}_train_mfu_1chip" if config_key
              else f"llama400m_train_mfu_{gen}_1chip")

    params = llama.init_params(config, jax.random.PRNGKey(0))
    opt = optax.adamw(3e-4, weight_decay=0.01)
    opt_state = opt.init(params)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(
            0, config.vocab_size, (batch, seq + 1)), jnp.int32)

    def train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(llama.loss_fn)(
            params, {"tokens": tokens}, config, remat=remat)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    step = jax.jit(train_step, donate_argnums=(0, 1))

    # Warmup (compile) + timed steps, each ending in a value fetch.
    for _ in range(3):
        params, opt_state, loss = step(params, opt_state, tokens)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(n_steps):
        params, opt_state, loss = step(params, opt_state, tokens)
    float(loss)
    elapsed = time.perf_counter() - t0

    tokens_per_step = batch * seq
    steps_per_s = n_steps / elapsed
    tokens_per_s = tokens_per_step * steps_per_s
    achieved = tokens_per_s * llama.flops_per_token(config, seq)
    mfu = achieved / peak_flops

    return {
        "metric": metric,
        "value": round(mfu, 4),
        "unit": "MFU",
        "vs_baseline": round(mfu / 0.40, 4),
        "tokens_per_s": round(tokens_per_s, 1),
        "step_time_ms": round(1000 * elapsed / n_steps, 2),
        "loss": round(float(loss), 4),
        "device": {"platform": device.platform,
                   "kind": device.device_kind,
                   "count": len(jax.devices())},
        "remat": remat,
        "batch_scale": batch_scale,
    }


# ---------------------------------------------------------------------------
# Regression guard: compare a run's metrics against the committed control
# (BENCH_control.json) instead of silently drifting.  "higher" metrics fail
# below control/tolerance; "lower" metrics fail above control*tolerance
# (default 2x — i.e. a 2x slowdown / 0.5x throughput drop trips it; tune
# with ART_BENCH_REGRESSION_TOLERANCE).
# ---------------------------------------------------------------------------

_GUARDED_METRICS = {
    "put_get_bandwidth_gb_s": "higher",
    "object_broadcast_striped_gb_s": "higher",
    "wait_1k_ready_refs_us": "lower",
    "collective_allreduce_fused_naive_ratio": "higher",
    # Multi-slice collectives (PR 14): share of collective wall time
    # hidden under backward compute by the gradient-ready syncer
    # (acceptance >= 0.5), wire bytes crossing per logical f32 byte
    # under int8 blockwise transport (acceptance <= 0.35), and the
    # cross-slice participant ratio of the hierarchical vs flat verb
    # (num_slices/world — 0.5 on the 2x2 sim; 1.0 means the two-level
    # path stopped engaging).
    "collective_overlap_fraction": "higher",
    "collective_int8_wire_bytes_ratio": "lower",
    "allreduce_hierarchical_vs_flat_rpc_ratio": "lower",
    "step_profiler_overhead_ns": "lower",
    # Resilience plane (PR 6): failure-detection + gang-relaunch +
    # restore latency, and productive-step fraction under an induced
    # mid-run crash.  Recovery time IS a throughput term at scale
    # (arxiv 2510.20171) — regressions here are regressions in goodput.
    "train_recovery_time_s": "lower",
    "goodput_under_chaos": "higher",
    # Serve overload plane (PR 7): admitted-request throughput under
    # >= 4x offered load, and the typed-shed share of offered requests.
    # BOTH guard "higher": goodput dropping means the request plane
    # lost capacity; shed fraction dropping toward zero at fixed 4x+
    # overload means the admission bound stopped holding (requests
    # queueing unboundedly instead of fast-failing with 429).
    "serve_goodput_under_overload": "higher",
    "serve_shed_fraction": "higher",
    # Tracing plane (PR 8): the unsampled per-call cost of always-on
    # request tracing (mint + entered-but-unrecorded span; < 2 µs
    # budget hard-failed in microbench) and the fully-instrumented
    # (sample rate 1.0) sync actor-call p99 with per-stage spans — the
    # number ROADMAP item 2's fast-path work decomposes against.
    "trace_overhead_unsampled_ns": "lower",
    "rpc_p99_actor_call_us": "lower",
    # Control-plane fast path (PR 15): the hot-frame codec's per-call
    # encode/decode cost (the floor under every PushTask), and the
    # tracing-attributed wire-stage mean itself — the end-to-end
    # throughput guards alone would let framing overhead hide inside
    # rig variance; the attributed wire cost is fenced directly.
    "rpc_frame_encode_ns": "lower",
    "rpc_frame_decode_ns": "lower",
    "rpc_actor_call_wire_us_mean": "lower",
    # No-SPOF control plane (PR 13): the replicated head's MTTR (kill
    # → first acknowledged mutation on the promoted standby; "lower")
    # and the productive-step fraction of a fit run across a leader
    # kill ("higher", acceptance bar 0.90) — the two numbers that say a
    # control-plane loss is survived, not merely restarted around.
    "gcs_failover_time_s": "lower",
    "goodput_under_leader_kill": "higher",
    # State observatory (PR 11): the per-event fold cost on the GCS
    # TaskEventsAdd ingest path (hard 4 µs budget in microbench — the
    # fold taxes EVERY task the cluster runs) and the server-side
    # ListTasks round trip that replaced the pull-the-raw-ring state
    # query.  Both "lower".
    "task_state_ingest_overhead_ns": "lower",
    "state_list_tasks_us": "lower",
    # Continuous profiling plane (PR 16): the always-on sampler's
    # measured throughput tax on the pipelined actor-call workload
    # (hard 0.02 budget in microbench), and the wire-accounting view of
    # PushTask frame size — bytes-per-call creeping up is frame bloat
    # on the hottest method of the wire.
    "cpu_profiler_overhead_fraction": "lower",
    "rpc_pushtask_send_bytes_per_call": "lower",
    # LLM serving plane (PR 18): short-prompt TTFT under long-prompt
    # interference (absolute guard), decode throughput under that same
    # mixed load, and the number of live sessions a 2-slot engine held
    # via KV offload (> slots, or eviction/restore stopped expanding
    # capacity).
    "llm_tokens_per_s": "higher",
    "llm_ttft_short_p50_us": "lower",
    "llm_ttft_short_p99_us": "lower",
    "llm_resident_sessions": "higher",
    # Scale observatory (PR 19): control-plane cost at 100 stub nodes
    # (benchmarks/scale_harness.py — real wire protocol, no workers).
    # Lease throughput through SelectNode → LeaseWorker → ReturnWorker
    # ("higher" — the sticky pack-pick cache's before/after headline),
    # GCS CPU per second per 100 heartbeating nodes ("lower" — the
    # steady-state tax every idle node levies on the head), and the
    # head io-loop busy fraction under combined lease + task-event +
    # heartbeat load ("lower" — duty creeping toward 1.0 is the
    # saturation cliff the sweep exists to see coming).
    "sched_leases_per_s_100n": "higher",
    "heartbeat_cpu_ms_per_100n": "lower",
    "gcs_loop_duty_at_100n": "lower",
}


def _control_values(control_path: str | None) -> dict:
    control_path = control_path or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_control.json")
    try:
        with open(control_path) as f:
            control = json.load(f)
    except (OSError, ValueError):
        return {}
    return {r["metric"]: r["value"]
            for r in control.get("results", [])
            if isinstance(r.get("value"), (int, float))}


def check_regression(results: dict, control_path: str | None = None,
                     tolerance: float | None = None) -> list:
    """Compare ``{metric: value}`` against the control file; returns a
    list of regression records (empty = within tolerance).  Only
    metrics in _GUARDED_METRICS with a control entry are judged."""
    if tolerance is None:
        tolerance = float(os.environ.get(
            "ART_BENCH_REGRESSION_TOLERANCE", "2.0"))
    control = _control_values(control_path)
    regressions = []
    for metric, value in results.items():
        direction = _GUARDED_METRICS.get(metric)
        if direction is None or not isinstance(value, (int, float)):
            continue
        ref = control.get(metric)
        if not ref:
            continue
        ratio = value / ref
        bad = (ratio < 1.0 / tolerance if direction == "higher"
               else ratio > tolerance)
        if bad:
            regressions.append({
                "metric": metric, "value": round(value, 4),
                "control": ref, "ratio": round(ratio, 3),
                "direction": direction, "tolerance": tolerance})
    return regressions


def _rig_context() -> dict:
    """The rig facts that decide whether two bench records are even
    comparable: core count, the 1-minute load average (stamped before
    AND after the run — a spike between them taints the numbers), and
    whether the runtime lockcheck was on (it taxes every lock acquire).
    Summary records carry these so BENCH_*.json archaeology can reject
    apples-to-oranges comparisons instead of explaining them."""
    ctx: dict = {"cpu_count": os.cpu_count(),
                 "lockcheck": os.environ.get("ART_LOCKCHECK", "")}
    try:
        ctx["loadavg_1m"] = round(os.getloadavg()[0], 2)
    except OSError:  # platform without getloadavg
        ctx["loadavg_1m"] = None
    return ctx


def main() -> int:
    """Run the measurement in THIS process (one process, one chip); OOM
    falls back through remat policies / batch.  Any other failure — no
    chip first of all — is one error line and a non-zero exit."""
    from ant_ray_tpu._private.jax_utils import import_jax

    try:
        device = import_jax().devices()[0]
        if device.platform != "tpu":
            raise RuntimeError(f"jax runs on {device.platform!r} "
                               f"({device.device_kind})")
    except Exception as e:  # noqa: BLE001 — whatever stopped the chip
        print(f"bench.py: no TPU to measure on: {e}"
              .replace("\n", " ")[:600], file=sys.stderr)
        return 1
    rig = _rig_context()
    # "matmuls" (dots_saveable + saved flash residuals) measured best on
    # v5e: no backward recompute, fits HBM at batch 8.  "none" is
    # deliberately absent — it OOMs at 400m/batch-8.
    plans = [("matmuls", 1.0), ("full", 1.0), ("full", 0.5),
             ("matmuls", 0.25)]
    result = None
    for remat, scale in plans:
        try:
            result = measure(remat, scale)
            break
        except Exception as e:  # noqa: BLE001 — only OOM moves on
            if not _is_oom(e):
                raise
    if result is None:
        print("bench.py: every plan ran out of device memory",
              file=sys.stderr)
        return 1
    # Secondary metric: the north-star model SHAPE on one chip — a
    # llama-1B step (full remat; bf16 adam states), so the 8B-class
    # memory regime is measured at all.
    for batch in (4, 2, 1):
        try:
            r1b = measure("full", 1.0, config_key="llama3-1b",
                          base_batch=batch, n_steps=4)
        except Exception as e:  # noqa: BLE001 — OOM → smaller batch
            if not _is_oom(e):
                raise
            continue
        result["llama1b_mfu"] = r1b["value"]
        result["llama1b_step_time_ms"] = r1b["step_time_ms"]
        result["llama1b_batch"] = batch
        break
    result["rig"] = {**rig,
                     "loadavg_1m_after": _rig_context()["loadavg_1m"]}
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
