"""``kind: train`` — a training job through ``JaxTrainer(...).fit()``:
one worker that leases the cell's chips runs the configuration's loop;
the window is kept inside the loop, which alone sees every step end."""

from __future__ import annotations

import math
import os
import shutil
import time

import numpy as np

from chipbench.session import Session, log
from chipbench.spec import CHECKOUT, resolve


def run(cell, args) -> dict:
    job, spec = cell.traffic, cell.config
    trace_dir = os.path.join(CHECKOUT, ".chipbench_trace", cell.name)
    shutil.rmtree(trace_dir, ignore_errors=True)
    with Session(cell, bool(args.trace)) as session:
        from ant_ray_tpu import data, train

        t_session = time.time()
        rows = np.random.default_rng([args.seed, 3]).integers(
            0, spec["vocab_size"],
            (job["dataset_rows"], job["sequence_tokens"] + 1),
            dtype=np.int32)
        result = train.JaxTrainer(
            resolve(spec["train"]["loop"]),
            train_loop_config={
                "spec": spec, "job": job, "seed": args.seed,
                "seconds": args.seconds, "trace": bool(args.trace),
                "platform": session.platform, "chips": cell.chips,
                "trace_dir": trace_dir},
            scaling_config=train.ScalingConfig(num_workers=1, use_tpu=True),
            run_config=train.RunConfig(
                name=f"chipbench-{os.getpid()}",
                storage_path=os.path.join(CHECKOUT, ".chipbench_runs")),
            datasets={"train": data.from_numpy(rows, parallelism=8)},
        ).fit()
        m = result.metrics
        session.check_device(m["device"])
        one_owner = session.watch.verdict(m["device"]["pid"], cell.rehearsal)
    shutil.rmtree(trace_dir, ignore_errors=True)
    shutil.rmtree(os.path.join(CHECKOUT, ".chipbench_runs"),
                  ignore_errors=True)

    losses, parity, tol = m["losses"], m["parity"], spec["tolerance"]
    finite = all(math.isfinite(x) for x in losses)
    checks = {
        "loss_finite": finite and len(losses) >= 10,
        "loss_falls": finite and len(losses) >= 10 and
        sum(losses[-5:]) < sum(losses[:5]),
        "loss_parity": parity["loss_rel_err"] <= tol["train_loss_rel"],
        "grad_parity": max(parity["grad_rel_l2"].values())
        <= tol["train_grad_rel_l2"],
        "no_compile_in_window": m["compiles_in_window"] == 0,
        "steps_done": m["steps_done_in_window"] > 0,
        "one_owner_per_chip": one_owner,
    }
    setup_s = m["window_wall"] - args.t0
    step = sorted(m["step_s"])[len(m["step_s"]) // 2] if m["step_s"] else 0
    log(f"[setup] {setup_s:.1f} s = process start to session "
        f"{t_session - args.t0:.1f} | session to worker's loop "
        f"{m['worker_entered_wall'] - t_session:.1f} | parameters "
        f"{m['init_s']:.1f} | parity probe {parity['seconds']:.1f} | "
        f"optimizer state and step compile {m['compile_s']:.1f} | the rest "
        f"(TPU open, imports, {m['warmup_steps']} warm-up steps) "
        f"{m['window_wall'] - m['worker_entered_wall'] - m['init_s'] - parity['seconds'] - m['compile_s']:.1f}")
    log(f"[parity] loss {parity['loss_system']:.5f} vs reference "
        f"{parity['loss_reference']:.5f} (relative {parity['loss_rel_err']:.2e}"
        f", tolerance {tol['train_loss_rel']:.1e}); gradient leaves, relative "
        f"L2: worst {max(parity['grad_rel_l2'].values()):.4f} of {len(parity['grad_rel_l2'])} leaves "
        f"(tolerance {tol['train_grad_rel_l2']:.1e})")
    log(f"[window] {args.seconds} s: steps begun {m['steps_begun_in_window']}"
        f", done {m['steps_done_in_window']} in {m['window_used_s']:.3f} s, "
        f"median step {1000 * step:.1f} ms, tokens/step "
        f"{m['tokens_per_step']}, compilations in window "
        f"{m['compiles_in_window']}, loss {losses[0]:.4f} -> {losses[-1]:.4f}"
        f", loss at step 8: {losses[8] if len(losses) > 8 else None}, "
        f"collectives in the program {m['collectives_in_program']}, kernel "
        f"{m['has_kernel']}, program bytes {m['program_bytes']}")
    log(f"[checks] {checks}")
    return {
        "device": m["device"], "checks": checks, "setup_s": setup_s,
        "client": None, "train": m, "trace": m["trace"], "spans": None,
        "attempted": m["steps_begun_in_window"],
        "failed": sum(1 for x in losses[m["warmup_steps"]:]
                      if not math.isfinite(x)),
        "memory_peak_bytes": m["memory_peak_bytes"],
    }
